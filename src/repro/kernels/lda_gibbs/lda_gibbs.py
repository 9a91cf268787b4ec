"""Pallas TPU kernel: collapsed-Gibbs sweeps over a block of documents.

The G-OEM E-step spends >95% of its time in the per-word resampling loop

    p(z_i = k | z_-i, w) ~ (n_dk^{(-i)} + alpha) * beta[k, w_i],

which is sequential over the L positions of a document but fully vectorizable
over documents (sublane axis) and topics (lane axis). TPU adaptation:

  * the word->topic-row gather beta[:, w_i] is hoisted OUT of the kernel
    (ops.py precomputes beta_w = beta.T[words], shape [B, L, K]) so the inner
    loop is pure VPU arithmetic on [B_blk, K] tiles — no in-kernel gather on
    the lane axis;
  * all randomness is pre-drawn as uniforms [S, B, L] and streamed into VMEM
    with the document block, so the kernel is deterministic and bit-exact
    against the pure-jnp oracle (ref.py);
  * the grid is 1-D over document blocks; each step keeps the whole
    [L, B_blk, K] working set (beta_w, uniforms, the Rao-Blackwell
    accumulator) resident in VMEM, position-major so the sequential
    position loop indexes the untiled leading axis. For the paper scale
    (L=32..64, K<=128 lanes) that is ~1 MB per block — far under the
    ~16 MB VMEM budget, so B_blk can grow until the VPU is saturated.

Sampling is the oracle's own inverse-CDF draw (`estep.sample_keepdims`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.estep import sample_keepdims
from repro.kernels.common import one_hot, out_struct, resolve_interpret


def gibbs_block_kernel(beta_w_ref, mask_ref, u_ref, z0_ref,
                       per_pos_ref, z_ref, ndk_ref,
                       *, alpha: float, n_sweeps: int, burnin: int):
    """One grid step: all Gibbs sweeps for a [B_blk] block of documents.

    Position-major blocks: the per-position index is the untiled leading
    axis, so every loop step reads and writes whole [B_blk, *] tiles of
    a ref (documents on sublanes) — Mosaic lowers no dynamic slice of a
    loaded value.

    beta_w_ref: [L, B_blk, K]    f32  per-position topic likelihood rows
    mask_ref:   [L, B_blk, 1]    f32  1.0 for real tokens
    u_ref:      [S, L, B_blk, 1] f32  pre-drawn uniforms
    z0_ref:     [L, B_blk, 1]    i32  initial topic assignments
    per_pos_ref:[L, B_blk, K]    f32  OUT mean Rao-Blackwell posterior
                                      (the accumulator while sweeping)
    z_ref:      [L, B_blk, 1]    i32  OUT final assignments (the live
                                      state while sweeping)
    ndk_ref:    [B_blk, K]       f32  OUT mean doc-topic counts (kept sweeps)
    """
    l, b_blk, k = beta_w_ref.shape
    dt = per_pos_ref.dtype
    n_keep = n_sweeps - burnin

    z_ref[...] = z0_ref[...]
    per_pos_ref[...] = jnp.zeros(per_pos_ref.shape, dt)

    def count(i, n_dk):
        return n_dk + mask_ref[i] * one_hot(z_ref[i], k, dt)

    n_dk = jax.lax.fori_loop(0, l, count, jnp.zeros((b_blk, k), dt))

    def position(i, n_dk, *, s):
        m = mask_ref[i]                                          # [B, 1]
        zi = z_ref[i]                                            # [B, 1]
        n_dk = n_dk - m * one_hot(zi, k, dt)
        probs = (n_dk + alpha) * beta_w_ref[i]                   # [B, K]
        new_z = sample_keepdims(probs, u_ref[s, i])
        new_z = jnp.where(m > 0, new_z, zi)
        n_dk = n_dk + m * one_hot(new_z, k, dt)

        post = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-30)
        collect = jnp.asarray(s >= burnin, dt)
        per_pos_ref[i] = per_pos_ref[i] + collect * m * post
        z_ref[i] = new_z
        return n_dk

    def sweep(s, carry):
        n_dk, ndk_acc = carry
        n_dk = jax.lax.fori_loop(0, l, functools.partial(position, s=s),
                                 n_dk)
        keep = jnp.asarray(s >= burnin, dt)
        return n_dk, ndk_acc + keep * n_dk

    _, ndk_acc = jax.lax.fori_loop(
        0, n_sweeps, sweep, (n_dk, jnp.zeros((b_blk, k), dt)))

    def finish(i, c):
        per_pos_ref[i] = per_pos_ref[i] / n_keep * mask_ref[i]
        return c

    jax.lax.fori_loop(0, l, finish, 0)
    ndk_ref[...] = ndk_acc / n_keep


def gibbs_sweeps_pallas(beta_w: jax.Array, maskf: jax.Array,
                        uniforms: jax.Array, z0: jax.Array, *,
                        alpha: float, n_sweeps: int, burnin: int,
                        block_docs: int = 8, interpret: bool | None = None
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """pallas_call wrapper. beta_w [B,L,K]; B must divide by block_docs.

    Returns (per_pos [B,L,K], z [B,L], ndk_mean [B,K]). The transposes to
    and from the kernel's position-major layout happen here.
    """
    b, l, k = beta_w.shape
    s = uniforms.shape[0]
    if b % block_docs:
        raise ValueError(f"B={b} not divisible by block_docs={block_docs}")
    grid = (b // block_docs,)

    kernel = functools.partial(gibbs_block_kernel, alpha=alpha,
                               n_sweeps=n_sweeps, burnin=burnin)
    ins = (jnp.swapaxes(beta_w, 0, 1), maskf.T[..., None],
           jnp.swapaxes(uniforms, 1, 2)[..., None], z0.T[..., None])
    per_pos, z, ndk = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((l, block_docs, k), lambda i: (0, i, 0)),
            pl.BlockSpec((l, block_docs, 1), lambda i: (0, i, 0)),
            pl.BlockSpec((s, l, block_docs, 1), lambda i: (0, 0, i, 0)),
            pl.BlockSpec((l, block_docs, 1), lambda i: (0, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((l, block_docs, k), lambda i: (0, i, 0)),
            pl.BlockSpec((l, block_docs, 1), lambda i: (0, i, 0)),
            pl.BlockSpec((block_docs, k), lambda i: (i, 0)),
        ],
        out_shape=[
            out_struct((l, b, k), beta_w.dtype, *ins),
            out_struct((l, b, 1), jnp.int32, *ins),
            out_struct((b, k), beta_w.dtype, *ins),
        ],
        interpret=resolve_interpret(interpret),
        name="lda_gibbs",
    )(*ins)
    return jnp.swapaxes(per_pos, 0, 1), z[..., 0].T, ndk
