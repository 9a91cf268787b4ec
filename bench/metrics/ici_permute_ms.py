"""ici_permute_ms: device ms a round of the mix's collective-permutes.

Device time of the collective-permute instructions (start and done, or
whole) under the ``mix.permute`` scope in the traced window's
node-sharded ``train_steps`` executions, averaged over the chips, over
the rounds in the window (``bench.mesh_scopes.permute_seconds``): the
part of ``mesh_round_mix_ms`` in which blocks cross chips over ICI.
"""

from bench import mesh_scopes


def read(layer):
    return mesh_scopes.permute_ms(layer)
