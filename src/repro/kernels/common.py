"""Shared kernel-dispatch helpers used by every Pallas kernel package."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def one_hot(z: jax.Array, k: int, dtype) -> jax.Array:
    """[..., 1] int32 -> [..., k] one-hot (iota+compare; MXU-free)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (*z.shape[:-1], k),
                                    z.ndim - 1)
    return (z == iota).astype(dtype)


def resolve_interpret(interpret: bool | None) -> bool:
    """None -> auto: compile on TPU, interpreter everywhere else.

    The kernels are Mosaic-lowered TPU code; off-TPU the interpreter is the
    only thing that can run them, but defaulting to interpret=True
    unconditionally (the old behavior) silently kept kernels OFF real
    hardware. Tests pass an explicit value to pin the mode.
    """
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """A pallas_call output varying over the manual mesh axes its operands
    vary over: inside ``shard_map`` jax checks that every output names
    them (outside, the set is empty)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
