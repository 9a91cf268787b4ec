"""Pallas TPU kernel: count-weighted Gibbs sweeps over unique-token docs.

The sparse corpus layer's hot loop. A document arrives as (word_id, count)
pairs padded to U slots (U = max unique tokens, typically L/4 .. L/10 on
Zipf-shaped corpora), and the per-slot move resamples ALL c copies of a
word with one count-weighted categorical draw

    p(z_u = k | z_-u, w) ~ (n_dk^{(-u)} + alpha) * beta[k, w_u],
    m_u <- c * one_hot(z_u),

so a sweep costs O(U) draws instead of the dense kernel's O(L). TPU
adaptation mirrors kernels/lda_gibbs:

  * the word->topic-row gather beta[:, w_u] is hoisted OUT of the kernel
    (ops.py precomputes beta_w = beta.T[uw], shape [B, U, K]);
  * randomness is pre-drawn as uniforms [S, B, U]; the kernel is
    deterministic and bit-exact against the pure-jnp oracle (ref.py =
    core.estep.gibbs_sweeps_sparse);
  * the grid is 1-D over document blocks, slot-major inside a block;
    each step keeps the whole segment state on-chip: the [U, B_blk, K]
    count splits m (the
    segmented representation of this block's token->topic assignment),
    the likelihood rows, uniforms and the count-weighted Rao-Blackwell
    accumulator all live in VMEM — only the final per-unique statistics
    leave the chip, and the [K, V] scatter-add of those count-weighted
    rows (``estep.stats_from_unique``) runs as a single XLA scatter where
    the per-node assembly lives.

Padding slots carry count 0: their draws still consume a uniform (keeping
the stream layout rectangular) but add zero mass everywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.estep import sample_keepdims
from repro.kernels.common import one_hot, out_struct, resolve_interpret


def sparse_block_kernel(beta_w_ref, count_ref, u_ref, z0_ref,
                        per_unique_ref, m_ref, ndk_ref,
                        *, alpha: float, n_sweeps: int, burnin: int):
    """One grid step: all count-weighted sweeps for a [B_blk] doc block.

    Slot-major blocks (the slot index is the untiled leading axis), as in
    the lda_gibbs kernel.

    beta_w_ref:    [U, B_blk, K]    f32  per-unique-word likelihood rows
    count_ref:     [U, B_blk, 1]    f32  token multiplicities (0 = padding)
    u_ref:         [S, U, B_blk, 1] f32  pre-drawn uniforms
    z0_ref:        [U, B_blk, 1]    i32  initial topic assignments
    per_unique_ref:[U, B_blk, K]    f32  OUT count-weighted mean RB
                                         posterior (the accumulator)
    m_ref:         [U, B_blk, K]    f32  OUT final count splits (the live
                                         state while sweeping)
    ndk_ref:       [B_blk, K]       f32  OUT mean doc-topic counts (kept)
    """
    u_dim, b_blk, k = beta_w_ref.shape
    dt = per_unique_ref.dtype
    n_keep = n_sweeps - burnin

    per_unique_ref[...] = jnp.zeros(per_unique_ref.shape, dt)

    def init(i, n_dk):
        m_i = count_ref[i] * one_hot(z0_ref[i], k, dt)
        m_ref[i] = m_i
        return n_dk + m_i

    n_dk = jax.lax.fori_loop(0, u_dim, init, jnp.zeros((b_blk, k), dt))

    def slot(i, n_dk, *, s):
        c = count_ref[i]                                        # [B, 1]
        n_dk = n_dk - m_ref[i]
        probs = (n_dk + alpha) * beta_w_ref[i]                  # [B, K]
        new_z = sample_keepdims(probs, u_ref[s, i])
        new_m = c * one_hot(new_z, k, dt)
        n_dk = n_dk + new_m

        post = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-30)
        collect = jnp.asarray(s >= burnin, dt)
        per_unique_ref[i] = per_unique_ref[i] + collect * c * post
        m_ref[i] = new_m
        return n_dk

    def sweep(s, carry):
        n_dk, ndk_acc = carry
        n_dk = jax.lax.fori_loop(0, u_dim, functools.partial(slot, s=s),
                                 n_dk)
        keep = jnp.asarray(s >= burnin, dt)
        return n_dk, ndk_acc + keep * n_dk

    _, ndk_acc = jax.lax.fori_loop(
        0, n_sweeps, sweep, (n_dk, jnp.zeros((b_blk, k), dt)))

    def finish(i, c):
        slotf = (count_ref[i] > 0).astype(dt)
        per_unique_ref[i] = per_unique_ref[i] / n_keep * slotf
        return c

    jax.lax.fori_loop(0, u_dim, finish, 0)
    ndk_ref[...] = ndk_acc / n_keep


def sparse_sweeps_pallas(beta_w: jax.Array, countf: jax.Array,
                         uniforms: jax.Array, z0: jax.Array, *,
                         alpha: float, n_sweeps: int, burnin: int,
                         block_docs: int = 8, interpret: bool | None = None
                         ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """pallas_call wrapper. beta_w [B,U,K]; B must divide by block_docs.

    Returns (per_unique [B,U,K], m [B,U,K], ndk_mean [B,K]). The
    transposes to and from the kernel's slot-major layout happen here.
    """
    b, u_dim, k = beta_w.shape
    s = uniforms.shape[0]
    if b % block_docs:
        raise ValueError(f"B={b} not divisible by block_docs={block_docs}")
    grid = (b // block_docs,)

    kernel = functools.partial(sparse_block_kernel, alpha=alpha,
                               n_sweeps=n_sweeps, burnin=burnin)
    ins = (jnp.swapaxes(beta_w, 0, 1), countf.T[..., None],
           jnp.swapaxes(uniforms, 1, 2)[..., None], z0.T[..., None])
    per_unique, m, ndk = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((u_dim, block_docs, k), lambda i: (0, i, 0)),
            pl.BlockSpec((u_dim, block_docs, 1), lambda i: (0, i, 0)),
            pl.BlockSpec((s, u_dim, block_docs, 1),
                         lambda i: (0, 0, i, 0)),
            pl.BlockSpec((u_dim, block_docs, 1), lambda i: (0, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((u_dim, block_docs, k), lambda i: (0, i, 0)),
            pl.BlockSpec((u_dim, block_docs, k), lambda i: (0, i, 0)),
            pl.BlockSpec((block_docs, k), lambda i: (i, 0)),
        ],
        out_shape=[
            out_struct((u_dim, b, k), beta_w.dtype, *ins),
            out_struct((u_dim, b, k), beta_w.dtype, *ins),
            out_struct((b, k), beta_w.dtype, *ins),
        ],
        interpret=resolve_interpret(interpret),
        name="lda_sparse",
    )(*ins)
    return jnp.swapaxes(per_unique, 0, 1), jnp.swapaxes(m, 0, 1), ndk
