"""round_blend_ms: the G-OEM blend's device ms a round, in the traced window.

Inclusive device time of the ops under the ``deleda.blend`` scope in the
window's ``train_steps`` executions (``bench.scopes``), over the rounds in
the window. The scope covers rho and the decay, ``(1 - rho) s + rho s_hat``
and the selects of the updated nodes' statistics and step counters.
"""

from bench import scopes


def read(layer):
    return scopes.round_ms(layer, "deleda.blend")
