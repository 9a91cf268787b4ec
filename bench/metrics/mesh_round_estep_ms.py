"""mesh_round_estep_ms: the E-step's device ms a round on the node mesh.

Inclusive device time of the ops under ``deleda.estep`` in the traced
window's node-sharded ``train_steps`` executions, averaged over the
chips, over the rounds in the window (``bench.mesh_scopes``). Each chip
runs its block's E-step, so this is one chip's E-step a round, the
counterpart of a one-chip cell's ``round_estep_ms`` at the same block.
"""

from bench import mesh_scopes


def read(layer):
    return mesh_scopes.round_ms(layer, "deleda.estep")
