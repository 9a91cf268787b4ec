"""round_sweeps_ms: the Gibbs sweeps' device ms a round, in the traced window.

Inclusive device time of the ops under the ``estep.sweeps`` scope in the
window's ``train_steps`` executions (``bench.scopes``), over the rounds in
the window. The scope is ``estep.fused_sweeps``: the Gibbs randoms and the
backend's sweeps over every node's minibatch as one [n*B, L] batch.
"""

from bench import scopes


def read(layer):
    return scopes.round_ms(layer, "estep.sweeps")
