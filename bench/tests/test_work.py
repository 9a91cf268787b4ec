"""Counts of required work, against hand-computed numbers."""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import peaks, work  # noqa: E402


def test_gibbs_flops_per_token_by_hand():
    # K=4, 3 sweeps, 1 burn-in: per sweep 1+8+3+5+1 = 18; per kept 3+4+4 = 11
    assert work.gibbs_flops_per_token(4, 3, 1) == 3 * 18 + 2 * 11


def test_round_bytes_by_hand():
    # n=2, K=3, V=5: one statistic 2*3*5*4 = 120 bytes; mix+blend 240;
    # history and consensus every 4 rounds: 240/4 = 60
    assert work.round_bytes(2, 3, 5, 4) == 240 + 60
    assert work.mix_bytes(2, 3, 5) == 240


def test_round_flops_by_hand():
    # 10 tokens at K=4, 3 sweeps, 1 burn-in: 10*(76+4); statistic 6*2*4*5
    assert work.round_flops(10, 2, 4, 5, 3, 1) == 10 * 80 + 240


def test_least_time_picks_the_larger_bound():
    pk = peaks.PEAKS["TPU v5 lite"]
    t, bound = work.least_time(197e12, 1.0, 1, pk)
    assert bound == "flops" and abs(t - 1.0) < 1e-12
    t, bound = work.least_time(1.0, 819e9 * 4, 4, pk)
    assert bound == "bytes" and abs(t - 1.0) < 1e-12


def test_pubmed_round_is_bytes_bound():
    pk = peaks.PEAKS["TPU v5 lite"]
    n, k, v = 32, 100, 141043
    flops = work.round_flops(32 * 20 * 89, n, k, v, 4, 2)
    t, bound = work.least_time(flops, work.round_bytes(n, k, v, 4), 1, pk)
    assert bound == "bytes"
    assert abs(t - 2.5 * n * k * v * 4 / 819e9) < 1e-12


def test_unknown_device_kind_is_an_error():
    try:
        peaks.peaks_for("TPU v9 imaginary")
    except KeyError:
        return
    raise AssertionError("unknown device kind accepted")


def test_round_mfu_reads_the_traced_window():
    """train_round_mfu divides by the trace's window, not a host clock."""
    from bench import common, trace
    from bench import run as run_mod
    reader = run_mod.load_module(ROOT / "bench" / "metrics" /
                                 "train_round_mfu.py", "mfu_under_test")
    cfg = {"n_nodes": 2, "n_topics": 3, "vocab_size": 5, "n_gibbs": 3,
           "n_gibbs_burnin": 1}
    d = trace.Device("/device:TPU:0", ops=[trace.Event("f", 0, 4e9)],
                     modules=[])
    window = trace.Summary([d], [trace.Event("segment dispatch", 0, 1)])
    pk = {"flops_per_s": 1e3, "hbm_bytes_per_s": 1e12}
    layer = common.Layer(window=window, probes=None, config=cfg, peaks=pk,
                         chips=1, counters={"rounds": 8, "tokens": 80,
                                            "record_every": 4})
    # 10 tokens a round, flops-bound at these peaks; 4 s over 8 rounds
    flops = work.round_flops(10, 2, 3, 5, 3, 1)
    assert reader.read(layer) == (100.0 * (flops / 1e3) / 0.5)
    layer.window = None
    assert reader.read(layer) is None
