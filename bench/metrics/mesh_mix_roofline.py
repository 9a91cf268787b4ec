"""mesh_mix_roofline: the node-sharded mix's share of its roofline, in %.

The mix's least time over its device time a round (``mesh_round_mix_ms``).
The least time is the larger of two bounds (``bench.mesh_work``): HBM,
``work.mix_bytes`` at the cell's n over the chips' HBM bandwidth; ICI,
two [K, V] float32 rows for each cross-chip matched pair of a round (the
counter ``cross_pairs`` over the window's rounds) over the chips'
interconnect bandwidth. Both come from the algorithm, not from what the
program ships, so shipping whole blocks reads low and cannot read over
100%.
"""

from bench import mesh_scopes, mesh_work


def read(layer):
    mix_ms = mesh_scopes.round_ms(layer, "deleda.mix")
    c, cfg = layer.counters, layer.config
    if mix_ms is None or "cross_pairs" not in c:
        return None
    least, _bound = mesh_work.mix_least_time(
        cfg["n_nodes"], cfg["n_topics"], cfg["vocab_size"],
        c["cross_pairs"] / c["rounds"], layer.chips, layer.peaks)
    return 100.0 * least / (mix_ms * 1e-3)
