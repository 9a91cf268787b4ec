"""round_mix_ms: the gossip mix's device ms a round, in the traced window.

Inclusive device time of the ops under the ``deleda.mix`` scope in the
window's ``train_steps`` executions (``bench.scopes``), over the rounds in
the window. The scope covers the liveness guard on the matching and
``comm.mix_matching`` (the partner gather and the average): the in-window
counterpart of the mix that ``mix_matching_roofline`` times alone.
"""

from bench import scopes


def read(layer):
    return scopes.round_ms(layer, "deleda.mix")
