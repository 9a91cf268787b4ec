"""round_record_ms: the segment record's device ms a round, in the window.

Inclusive device time of the ops under the ``deleda.record`` scope in the
traced window's ``train_steps`` executions (``bench.scopes``), over the
rounds in the window. The scope covers the consensus distance at the end
of each record block; the history snapshot is written by the scan itself,
outside the scope.
"""

from bench import scopes


def read(layer):
    return scopes.round_ms(layer, "deleda.record")
