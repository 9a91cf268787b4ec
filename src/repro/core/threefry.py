"""Bit-exact Threefry-2x32 replica of jax.random's partitionable mode.

The streaming evaluator pins its PRNG contract to jax.random's
``fold_in(key, doc_id)`` / ``fold_in(doc_key, position)`` streams: golden
LL values and the chunk-invariance property are defined by those exact
bits. jax.random, however, only exposes *bulk* draws — ``uniform(key,
(P, L))`` materializes all P*L values even when a resample step consumes
a single column, and nothing in its API can run *inside* a Pallas kernel.

This module re-implements the three derivations the evaluator uses —
``fold_in``, ``split(key, 2)`` and ``uniform`` — as plain uint32/float32
jnp arithmetic that produces the SAME BITS as jax.random under jax's
default (partitionable) threefry implementation, while letting the
caller generate exactly the values it needs, where it needs them:

* :func:`uniform_column` yields column ``i`` of ``uniform(key, (P, L))``
  without touching the other L-1 columns — the fused left-to-right
  resample loop draws its per-step uniforms on the fly;
* every function is expressible with ops Pallas TPU lowers (add/xor/shift
  on uint32, iota, one same-width bitcast), so the ``kernels/lda_l2r``
  kernel derives the identical streams on-chip with no uniform inputs.

Layout (jax _src/prng.py, ``_threefry_random_bits_partitionable`` and
``_threefry_split_foldlike``): element f of a draw ciphers the 64-bit
counter f as the word pair ``(0, f)`` and outputs ``o1 ^ o2``; child j of
``split`` is the raw pair ``(o1, o2)`` of counter j; ``fold_in(key, d)``
ciphers ``(0, d)``. Keys travel as two uint32 arrays ``(k1, k2)`` with a
trailing singleton axis, so in-kernel code never slices or stacks the key
pair. Everything is asserted bitwise against jax.random in
tests/test_threefry.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "cipher", "key_data", "key_pair", "fold_in", "split2",
    "uniform_from_bits", "uniform_at", "uniform", "uniform_column",
]

_U32 = jnp.uint32
# numpy scalars, NOT jnp: module-level jax arrays are committed device
# constants, which a Pallas kernel closure cannot capture (np scalars
# inline as jaxpr literals; same bits either way)
_PARITY = np.uint32(0x1BD11BDA)
_ZERO = np.uint32(0)
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def _rotl(x, d: int):
    return (x << _U32(d)) | (x >> _U32(32 - d))


def cipher(k1, k2, x1, x2):
    """Threefry-2x32 block cipher on uint32 lanes (5x4 rounds, r=20).

    All four operands broadcast together; returns ``(o1, o2)`` with the
    broadcast shape. Mirrors jax._src.prng.threefry2x32's rolled loop:
    key schedule ``[k1, k2, k1 ^ k2 ^ PARITY]`` rotating one slot per
    4-round group, with the group index folded into the second lane.
    """
    k1 = jnp.asarray(k1).astype(_U32)
    k2 = jnp.asarray(k2).astype(_U32)
    ks = [k1, k2, k1 ^ k2 ^ _PARITY]
    x = [jnp.asarray(x1).astype(_U32) + ks[0],
         jnp.asarray(x2).astype(_U32) + ks[1]]
    rots = list(_ROTATIONS)
    ks = ks[1:] + ks[:1]
    for group in range(5):
        for d in rots[0]:
            x[0] = x[0] + x[1]
            x[1] = _rotl(x[1], d)
            x[1] = x[0] ^ x[1]
        x = [x[0] + ks[0], x[1] + ks[1] + _U32(group + 1)]
        ks = ks[1:] + ks[:1]
        rots = rots[1:] + rots[:1]
    return x[0], x[1]


def key_data(key: jax.Array) -> jax.Array:
    """[..., 2] uint32 raw words of a (typed or raw) PRNG key array."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        return jax.random.key_data(key)
    return key.astype(_U32)


def key_pair(key: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(k1, k2)``, each [..., 1] uint32, of a [...] PRNG key array."""
    kd = key_data(key)
    return kd[..., 0:1], kd[..., 1:2]


def fold_in(k1, k2, data):
    """Key pair of ``fold_in(key, data)``; ``data`` broadcasts with k."""
    return cipher(k1, k2, _ZERO, data)


def split2(k1, k2):
    """``((a1, a2), (b1, b2))``: the key pairs of ``k0, k1 = split(key)``."""
    return cipher(k1, k2, _ZERO, _ZERO), cipher(k1, k2, _ZERO, np.uint32(1))


def uniform_from_bits(bits: jax.Array) -> jax.Array:
    """uint32 random bits -> float32 in [0, 1), matching jax.random.

    Same mantissa construction as jax: keep the top 23 bits, OR in the
    exponent of 1.0, bitcast, subtract 1.0.
    """
    fb = (bits >> _U32(9)) | _U32(0x3F800000)
    return jax.lax.bitcast_convert_type(fb, jnp.float32) - jnp.float32(1.0)


def uniform_at(k1, k2, flat) -> jax.Array:
    """Uniforms at flat counter positions ``flat`` of one draw; the key
    pair broadcasts against ``flat``."""
    o1, o2 = cipher(k1, k2, _ZERO, flat)
    return uniform_from_bits(o1 ^ o2)


def _iota(k1, n: int) -> jax.Array:
    """uint32 counters 0..n-1 along a new last axis replacing k1's
    trailing singleton (a 2-D-or-more iota, which Pallas TPU lowers)."""
    shape = k1.shape[:-1] + (n,)
    return jax.lax.broadcasted_iota(jnp.int32, shape,
                                    len(shape) - 1).astype(_U32)


def uniform(k1, k2, n: int) -> jax.Array:
    """``uniform(key, (n,))`` bit-exact: keys [..., 1] -> [..., n]."""
    return uniform_at(k1, k2, _iota(k1, n))


def uniform_column(k1, k2, p: int, l: int, i: jax.Array) -> jax.Array:
    """Column ``i`` of ``uniform(key, (p, l))`` without drawing the rest.

    Keys [..., 1], i scalar (traced ok) -> [..., p] float32 equal to
    ``jax.random.uniform(key, (p, l))[..., :, i]`` bitwise: the elements
    at flat counters ``r * l + i``. The fused left-to-right inner loop
    calls this once per resample step.
    """
    flat = _iota(k1, p) * _U32(l) + jnp.asarray(i).astype(_U32)
    return uniform_at(k1, k2, flat)
