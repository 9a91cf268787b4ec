"""mesh_round_mfu: a node-sharded round's share of its chips' peak, in %.

The least time of the work one round requires over the round time read
from the trace: the traced window's length (first dispatch to the last
device operation) over the rounds it holds. The work is
``bench.work``'s operations and HBM bytes at the cell's n over the
cell's chips' FLOP and HBM peaks, or the interconnect bound of its
cross-chip matched pairs (``bench.mesh_work``) where that is larger.
"""

from bench import mesh_work


def read(layer):
    c, cfg, w = layer.counters, layer.config, layer.window
    if not c.get("rounds") or "cross_pairs" not in c or w is None \
            or w.window_s <= 0:
        return None
    least, _bound = mesh_work.round_least_time(
        c["tokens"] / c["rounds"], cfg["n_nodes"], cfg["n_topics"],
        cfg["vocab_size"], cfg["n_gibbs"], cfg["n_gibbs_burnin"],
        c["record_every"], c["cross_pairs"] / c["rounds"], layer.chips,
        layer.peaks)
    return 100.0 * least / (w.window_s / c["rounds"])
