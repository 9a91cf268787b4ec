"""scoped_pct: share of the window's train_steps device time under a scope, %.

Device time of the ops under any of the round's top-level scopes
(``deleda.mix``, ``deleda.estep``, ``deleda.blend``, ``deleda.record``)
over the device time of every op in the traced window's ``train_steps``
executions (``bench.scopes``). The rest is what XLA adds that neither a
scoped loop runs nor a scoped op feeds (relayout copies of the carried
statistic, zero-filled buffers, the segment's output copies), and ops
the program's text does not name.
"""

from bench import scopes


def read(layer):
    times = scopes.round_split(layer)
    if times is None or times.total_s <= 0:
        return None
    return 100.0 * times.scoped_s / times.total_s
