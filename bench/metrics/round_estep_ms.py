"""round_estep_ms: the E-step's device ms a round, in the traced window.

Inclusive device time of the ops under the ``deleda.estep`` scope in the
window's ``train_steps`` executions (``bench.scopes``), over the rounds in
the window. The scope covers the per-node key derivation, the minibatch
draw and the fused E-step (gather, sweeps, scatter): the in-window
counterpart of ``estep_ms``, which times the E-step entry called alone.
"""

from bench import scopes


def read(layer):
    return scopes.round_ms(layer, "deleda.estep")
