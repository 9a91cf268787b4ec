"""Pallas TPU kernel: apply a whole gossip matching to stacked statistics.

Computes S_out[i] = (S[i] + S[p[i]]) / 2 for a partner vector p (p[p[i]]=i,
self-partner = copy-through), with S of shape [n, K, V]. This is the
bandwidth-critical step of DELEDA at production vocabulary sizes: s is K x V
(hundreds of MB for V~100k), so the mix must stream tile-by-tile rather than
materialize gathered copies.

TPU adaptation — **scalar-prefetched data-dependent blocks**: the partner
vector is a scalar-prefetch operand, so the BlockSpec index_map of the
second input reads `partners[i]` to fetch the partner's tile directly from
HBM. The kernel never materializes S[p] (no host gather, no double HBM
round-trip): each grid step streams two [K, V_blk] tiles into VMEM and
writes one averaged tile — the arithmetic-intensity floor of the op
(3 tiles moved per tile produced).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mix_kernel(partners_ref, own_ref, partner_ref, out_ref):
    """out = 0.5 * (own + partner); tiles are [1, K, V_blk]."""
    del partners_ref  # consumed by the index_map, not the body
    out_ref[...] = 0.5 * (own_ref[...] + partner_ref[...])


def mix_matching_pallas(stats: jax.Array, partners: jax.Array, *,
                        block_v: int = 512, interpret: bool = False
                        ) -> jax.Array:
    """stats [n, K, V] f32, partners [n] int32 -> mixed [n, K, V].

    Grid (n, V/block_v); the partner tile is fetched via the scalar-
    prefetched index_map (i, j) -> (partners[i], 0, j).
    """
    n, k, v = stats.shape
    if v % block_v:
        raise ValueError(f"V={v} not divisible by block_v={block_v}")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n, v // block_v),
        in_specs=[
            pl.BlockSpec((1, k, block_v), lambda i, j, p: (i, 0, j)),
            pl.BlockSpec((1, k, block_v), lambda i, j, p: (p[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((1, k, block_v), lambda i, j, p: (i, 0, j)),
    )
    return pl.pallas_call(
        _mix_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, k, v), stats.dtype),
        interpret=interpret,
        name="gossip_mix",
    )(partners, stats, stats)
