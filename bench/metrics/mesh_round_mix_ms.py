"""mesh_round_mix_ms: the gossip mix's device ms a round on the node mesh.

Inclusive device time of the ops under ``deleda.mix`` in the traced
window's node-sharded ``train_steps`` executions, averaged over the
chips, over the rounds in the window (``bench.mesh_scopes``): the
liveness guard, the mix of the pairs within a chip's block, and every
ppermute pass with its row gather and average.
"""

from bench import mesh_scopes


def read(layer):
    return mesh_scopes.round_ms(layer, "deleda.mix")
