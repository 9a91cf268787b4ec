"""Public jit'd wrapper for the gossip_mix Pallas kernel."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.common import resolve_interpret
from repro.kernels.gossip_mix.gossip_mix import mix_matching_pallas

__all__ = ["mix_matching", "resolve_interpret"]


def _v_block(v: int, requested: int) -> int:
    """Largest multiple of 128 lanes that divides v and does not exceed
    `requested`, else the full v: a TPU block's last dim must be a
    128-multiple or the whole array dim."""
    for cand in range(min(requested, v) // 128 * 128, 0, -128):
        if v % cand == 0:
            return cand
    return v


@partial(jax.jit, static_argnames=("block_v", "interpret"))
def mix_matching(stats: jax.Array, partners: jax.Array,
                 block_v: int = 512,
                 interpret: bool | None = None) -> jax.Array:
    """Kernel-backed matching mix; accepts any V (auto block size).

    Drop-in for `repro.core.gossip.mix_matching`.
    """
    n, k, v = stats.shape
    bv = _v_block(v, block_v)
    return mix_matching_pallas(stats, partners.astype(jnp.int32),
                               block_v=bv,
                               interpret=resolve_interpret(interpret))
