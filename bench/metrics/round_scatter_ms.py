"""round_scatter_ms: the statistic scatter's device ms a round, in the window.

Inclusive device time of the ops under the ``estep.scatter`` scope in the
traced window's ``train_steps`` executions (``bench.scopes``), over the
rounds in the window. The scope is ``estep.stats_from_per_pos``: the
scatter-add of the per-position statistics into each node's [K, V] and
the per-document mean. Most of its time is in what XLA expands the
node-batched scatter into (an index sort, the scatter kernel, two
relayout loops), which carries no metadata and is charged to the scope
whose result it reads.
"""

from bench import scopes


def read(layer):
    return scopes.round_ms(layer, "estep.scatter")
