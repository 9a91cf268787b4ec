"""mix_matching_roofline: the gossip mix's share of its HBM roofline, in %.

``comm.get_communicator(<default>).mix_matching`` called alone on the
carried statistic and one matching: the bytes it must move
(``bench.work.mix_bytes``, read and write every statistic once) over peak
HBM bandwidth, against the device time of its program
(``bench_probe_mix``) in the probe trace, per call.
"""

from bench import work


def read(layer):
    if layer.probes is None:
        return None
    calls, seconds = layer.probes.module("bench_probe_mix")
    if not calls or seconds <= 0:
        return None
    cfg = layer.config
    bytes_ = work.mix_bytes(cfg["n_nodes"], cfg["n_topics"],
                            cfg["vocab_size"])
    least, _bound = work.least_time(0.0, bytes_, 1, layer.peaks)
    return 100.0 * least / (seconds / calls)
