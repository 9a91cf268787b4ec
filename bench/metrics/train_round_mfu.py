"""train_round_mfu: a training round's share of the chip's peak, in %.

The least time of the work one round requires (``bench.work``: the larger
of its operations over peak FLOP/s and its bytes over peak HBM bandwidth,
across the cell's chips) over the round time read from the trace: the
traced window's length (first dispatch to the last device operation) over
the rounds it holds.
"""

from bench import work


def read(layer):
    c, cfg, w = layer.counters, layer.config, layer.window
    if not c.get("rounds") or w is None or w.window_s <= 0:
        return None
    n, k, v = cfg["n_nodes"], cfg["n_topics"], cfg["vocab_size"]
    flops = work.round_flops(c["tokens"] / c["rounds"], n, k, v,
                             cfg["n_gibbs"], cfg["n_gibbs_burnin"])
    bytes_ = work.round_bytes(n, k, v, c["record_every"])
    least, _bound = work.least_time(flops, bytes_, layer.chips, layer.peaks)
    return 100.0 * least / (w.window_s / c["rounds"])
