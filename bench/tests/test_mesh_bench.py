"""The node-mesh cell's reference and readers, on the CPU.

The blocked reference follows ``bench/reference.py`` round for round; its
bfloat16 form fails the comparison that decides ``correct``; and the
readers of the mesh round's layers read a synthetic trace and HLO text
as intended, the mix's roofline at 100% when its time equals its bound.
"""

import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from bench import common, control, gen, mesh_scopes, reference  # noqa: E402
from bench import reference_blocked as rb  # noqa: E402
from bench import run as run_mod  # noqa: E402
from bench import scopes  # noqa: E402
from bench.drivers import mesh_rounds, train_rounds  # noqa: E402
from bench.trace import Device, Event, Summary  # noqa: E402

HP = dict(batch=3, tau=0.01, alpha=0.5, n_sweeps=4, burnin=2, rho_t0=10.0,
          rho_kappa=0.6)


def test_blocked_reference_equals_unblocked():
    n, k, v, d, l = 8, 5, 1003, 6, 16
    rng = np.random.default_rng(0)
    words = jnp.asarray(rng.integers(0, v, (n, d, l)), jnp.int32)
    mask = jnp.asarray(rng.random((n, d, l)) < 0.7)
    partners = gen.matchings(gen.watts_strogatz_edges(n, 4, 0.3, rng), n, 3,
                             rng)
    devices = jax.devices()[:1] * 4               # four blocks of two nodes
    stats, r_key = reference.init_stats(jax.random.key(3), n, k, v)
    blocks, b_key = rb.init_blocks(jax.random.key(3), n, k, v, devices)
    np.testing.assert_array_equal(np.concatenate(blocks), stats)
    steps = jnp.zeros((n,), jnp.int32)
    b_steps = rb.split_rows(steps, devices)
    b_words, b_mask = rb.split_rows(words, devices), rb.split_rows(mask,
                                                                  devices)
    for t in range(3):
        stats, steps = reference.round_(stats, steps, r_key, jnp.int32(t),
                                        jnp.asarray(partners[t]), words,
                                        mask, **HP)
        blocks, b_steps = rb.round_(blocks, b_steps, b_key, t, partners[t],
                                    b_words, b_mask, **HP)
        np.testing.assert_allclose(np.concatenate(blocks), stats, rtol=1e-6,
                                   atol=1e-9)
        np.testing.assert_array_equal(np.concatenate(b_steps), steps)


def test_bfloat16_reference_fails_the_blocked_comparison():
    config, traffic = control.setup("pubmed-k100-x4.train", tiny=True)
    config["n_nodes"] = 8
    run = common.Run(workload="pubmed-k100-x4.train", seed=3_000_000_017,
                     seconds=0.0, trace=False, config=config,
                     traffic=traffic, devices=jax.devices()[:1] * 4,
                     peaks={}, t_process=0.0)
    words, mask, partners, run_key = train_rounds.make_inputs(run)
    seg, n_check = traffic["segment_rounds"], traffic["check_steps"]
    ref = rb.reference_steps(run, words, mask, partners, run_key,
                             n_check * seg, seg, jnp.float32)
    low = rb.reference_steps(run, words, mask, partners, run_key,
                             n_check * seg, seg, jnp.bfloat16)
    assert all(c.ok for c in train_rounds.compare(ref, ref))
    checks = train_rounds.compare(low, ref)
    assert not all(c.ok for c in checks), [(c.name, c.value) for c in checks]


def test_cross_pairs_counts_pairs_between_chips():
    # 8 nodes on 2 chips: (0,1) within chip 0, (2,5) and (3,4) across
    p = np.array([[1, 0, 5, 4, 3, 2, 6, 7]])
    live = np.ones_like(p, bool)
    assert mesh_rounds.cross_pairs(p, live, 2) == 2
    live[0, 5] = False                      # the guard drops (2, 5)
    assert mesh_rounds.cross_pairs(p, live, 2) == 1


def test_the_cell_is_in_the_manifest():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, config, e2e, layer = run_mod.cell_entries(manifest,
                                                    "pubmed-k100-x4.train")
    assert cell["chips"] == 4 and cell["traffic"] == "mesh_rounds"
    assert {m["name"] for m in layer} == {
        "mesh_round_mfu", "mesh_round_estep_ms", "mesh_round_mix_ms",
        "ici_permute_ms", "mesh_mix_roofline"}
    assert {m["name"] for m in e2e} == {"train_tokens_per_s", "setup_s"}


# A node-sharded round cut to what the readers read: the E-step, the mix
# within a block, one ppermute pass (an async pair; the done carries no
# metadata) with its average, and the record's all-reduce.
HLO = """\
HloModule jit_train_steps, is_scheduled=true

ENTRY %main.1 (a.1: f32[4]) -> f32[4] {
  %a.1 = f32[4]{0} parameter(0)
  %fusion.2 = f32[4]{0} fusion(%a.1), kind=kLoop, calls=%f, metadata={op_name="jit(train_steps)/shard_map/deleda.estep/mul"}
  %fusion.3 = f32[4]{0} fusion(%fusion.2), kind=kLoop, calls=%f, metadata={op_name="jit(train_steps)/shard_map/deleda.mix/select_n"}
  %collective-permute-start.4 = (f32[4]{0}, f32[4]{0}, u32[], u32[]) collective-permute-start(%fusion.3), channel_id=1, source_target_pairs={{0,1},{1,0}}, metadata={op_name="jit(train_steps)/shard_map/deleda.mix/mix.permute/ppermute"}
  %collective-permute-done.5 = f32[4]{0} collective-permute-done(%collective-permute-start.4)
  %fusion.6 = f32[4]{0} fusion(%collective-permute-done.5, %fusion.3), kind=kLoop, calls=%f, metadata={op_name="jit(train_steps)/shard_map/deleda.mix/mix.permute/select_n"}
  ROOT %all-reduce.7 = f32[4]{0} all-reduce(%fusion.6), replica_groups={{0,1}}, to_apply=%add, metadata={op_name="jit(train_steps)/shard_map/deleda.record/psum_invariant"}
}
"""

MS = 1_000_000  # ns


def _device(i):
    """Two rounds: per round E-step 3 ms, mix 0.2 + 0.1 + 1.1 + 0.2 ms."""
    ops = []
    for r in range(2):
        t = r * 10 * MS
        for name, start, dur in [("fusion.2", 0, 3), ("fusion.3", 3, 0.2),
                                 ("collective-permute-start.4", 3.2, 0.1),
                                 ("collective-permute-done.5", 3.3, 1.1),
                                 ("fusion.6", 4.4, 0.2),
                                 ("all-reduce.7", 4.6, 0.05)]:
            ops.append(Event(f"%{name} = f32[4]{{0}} op(...)",
                             t + start * MS, dur * MS))
    return Device(f"/device:TPU:{i}", ops,
                  [Event("jit_train_steps(1)", 0, 20 * MS)])


def _layer(cross_pairs=4):
    summary = Summary([_device(0), _device(1)], [])
    cfg = {"n_nodes": 8, "n_topics": 10, "vocab_size": 1000, "n_gibbs": 4,
           "n_gibbs_burnin": 2}
    layer = common.Layer(
        window=summary, probes=None, config=cfg, chips=2,
        peaks={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9,
               "ici_bytes_per_s": 1e8},
        counters={"rounds": 2, "tokens": 100, "record_every": 2,
                  "cross_pairs": cross_pairs})
    layer.mesh_split = mesh_scopes.MeshSplit(
        scopes.scope_seconds(summary, HLO),
        mesh_scopes.permute_seconds(summary, HLO))
    return layer


def _read(metric, layer):
    return run_mod.load_module(ROOT / "bench" / "metrics" / f"{metric}.py",
                               f"bench_metric_{metric}").read(layer)


def test_mesh_readers_read_the_scopes_and_the_permutes():
    layer = _layer()
    assert _read("mesh_round_estep_ms", layer) == pytest.approx(3.0)
    assert _read("mesh_round_mix_ms", layer) == pytest.approx(1.6)
    # the async pair, start and done, and nothing else of the mix
    assert _read("ici_permute_ms", layer) == pytest.approx(1.2)


def test_mesh_mix_roofline_reads_100_at_its_ici_bound():
    # a round's 2 cross-chip pairs x 2 rows x 10 x 1,000 x 4 B = 160 kB
    # over 2 chips' 1e8 B/s take 0.8 ms, above the HBM bound (640 kB over
    # 2e9 B/s: 0.32 ms); the mix takes 1.6 ms
    assert _read("mesh_mix_roofline", _layer(4)) == pytest.approx(50.0)
    # twice the pairs: the ICI bound is the mix's whole time
    layer = _layer(8)
    share = _read("mesh_mix_roofline", layer)
    assert share == pytest.approx(100.0) and share <= 100.0 + 1e-9
    # the round's least time is that bound too (its HBM bytes take
    # 0.48 ms), over 20 ms of window in 2 rounds
    assert _read("mesh_round_mfu", layer) == pytest.approx(16.0)
