"""round_gather_ms: the beta-column gather's device ms a round, in the window.

Inclusive device time of the ops under the ``estep.gather`` scope in the
traced window's ``train_steps`` executions (``bench.scopes``), over the
rounds in the window. The scope is ``estep.beta_w_from_stats``: the
normaliser's pass over each node's [K, V] statistic and the gather of the
minibatch's columns.
"""

from bench import scopes


def read(layer):
    return scopes.round_ms(layer, "estep.gather")
