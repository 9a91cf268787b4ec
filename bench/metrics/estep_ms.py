"""estep_ms: device time of the E-step entry called alone, in ms.

``estep.estep_batch_from_stats`` with the backend the program's defaults
select, on one round's inputs (every node's minibatch and the carried
statistic), outside the window; the device time of its program
(``bench_probe_estep``) in the probe trace, per call.
"""


def read(layer):
    if layer.probes is None:
        return None
    calls, seconds = layer.probes.module("bench_probe_estep")
    if not calls:
        return None
    return 1e3 * seconds / calls
