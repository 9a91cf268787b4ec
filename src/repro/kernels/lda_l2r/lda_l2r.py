"""Pallas TPU kernel: on-chip left-to-right held-out scoring.

Wallach et al.'s algorithm 3 for a block of documents, entirely inside
one grid step: the position scan, the i < n resample loop, the predictive
scoring and the per-position particle draw all run on-chip — only the
[B_blk] per-document log-likelihood totals leave the kernel.

Unlike lda_gibbs / lda_sparse this kernel takes NO pre-drawn uniforms:
the whole point of the streaming evaluator is that pre-drawing the
resample tensor costs O(B*P*L*L) memory. Instead the kernel receives the
per-document PRNG key words ([B_blk, 2] uint32) and derives the exact
jax.random streams itself with :mod:`repro.core.threefry` — plain
uint32 add/xor/shift plus one bitcast, all ops Pallas supports — so each
resample step generates only the [B_blk, P] uniform column it is about
to consume. Stream derivation (``fold_in(doc_key, n)`` then
``split``/``uniform``) is identical to the serial and fused evaluators;
per-document results are bitwise chunk- and batch-invariant like theirs.

Grid and residency follow the house layout: a 1-D grid over document
blocks, with the position-major [L, B_blk, K] likelihood rows, the
weights and the [L, P, B_blk] assignment scratch resident in VMEM for
the whole scan.
``weights`` carries the dense layout's 0/1 mask or the unique (CSR)
layout's token counts — ``count_weighted`` picks whether slot n's score
is multiplied by its count, the ONLY difference between the two
estimators (mirroring ``evaluation._l2r_fused_core``, which is the
oracle this kernel is asserted bitwise against).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import estep as estep_mod
from repro.core import threefry as tf3
from repro.kernels.common import one_hot, out_struct, resolve_interpret


def l2r_block_kernel(kd_ref, beta_w_ref, w_ref, alpha_ref, ll_ref, z_ref,
                     *, n_particles: int, count_weighted: bool):
    """One grid step: full left-to-right estimate for a doc block.

    Position-major blocks with particles on the leading axis of the live
    state, so every per-position access indexes an untiled leading axis
    and each particle is a [B_blk, *] tile (documents on sublanes).

    kd_ref:     [B_blk, 2]    u32  per-document key data (doc-folded)
    beta_w_ref: [L, B_blk, K] f32  per-position likelihood rows beta[:, w]
    w_ref:      [L, B_blk, 1] f32  mask (dense) or counts (unique);
                                   0 = padding position/slot
    alpha_ref:  [1, 1]        f32  symmetric Dirichlet hyperparameter
                                   (an input, not a static, so traced
                                   alphas flow through the jitted chunk)
    ll_ref:     [L, B_blk, 1] f32  OUT per-POSITION scores; the caller
                                   reduces over L at the full [L, B]
                                   shape — summing inside the kernel
                                   would tie the reduction association
                                   to B_blk and drift ulps off the
                                   fused/serial oracles whenever
                                   block_docs != B
    z_ref:      [L, P, B_blk, 1] i32  scratch: particle assignments
    """
    l, b, k_dim = beta_w_ref.shape
    p = n_particles
    dt = beta_w_ref.dtype
    alpha = alpha_ref[...]                              # [1, 1]
    alpha_sum = alpha * k_dim
    k1 = kd_ref[:, 0:1][None]                           # [1, B, 1]
    k2 = kd_ref[:, 1:2][None]
    rows = jax.lax.broadcasted_iota(jnp.int32, (p, b, 1), 0).astype(
        jnp.uint32)                                     # particle index
    z_ref[...] = jnp.zeros(z_ref.shape, jnp.int32)

    def position(n_idx, n_k):                           # n_k [P, B, K]
        rs, dr = tf3.split2(*tf3.fold_in(k1, k2, n_idx))
        u_dr_n = tf3.uniform_at(*dr, rows)              # [P, B, 1]

        def resample(i, n_k):
            zi = z_ref[i]                               # [P, B, 1]
            u = tf3.uniform_at(*rs, rows * np.uint32(l)
                               + i.astype(jnp.uint32))  # column i
            wf = w_ref[i][None]                         # [1, B, 1]
            n_k = n_k - wf * one_hot(zi, k_dim, dt)
            probs = (n_k + alpha) * beta_w_ref[i][None]
            new_z = estep_mod.sample_keepdims(probs, u)
            new_z = jnp.where(wf > 0, new_z, zi)
            z_ref[i] = new_z
            return n_k + wf * one_hot(new_z, k_dim, dt)

        n_k = jax.lax.fori_loop(0, n_idx, resample, n_k)

        bw_n = beta_w_ref[n_idx][None]                  # [1, B, K]
        w_n = w_ref[n_idx]                              # [B, 1]
        n_lt = n_k.sum(-1, keepdims=True)
        theta_hat = (n_k + alpha) / (n_lt + alpha_sum)
        p_w = (theta_hat * bw_n).sum(-1, keepdims=True)  # [P, B, 1]
        raw = jnp.log(jnp.maximum(
            estep_mod.mean_seq([p_w[j] for j in range(p)]), 1e-30))
        if count_weighted:
            raw = w_n * raw
        ll_ref[n_idx] = jnp.where(w_n > 0, raw, 0.0)

        probs_n = (n_k + alpha) * bw_n
        z_n = estep_mod.sample_keepdims(probs_n, u_dr_n)
        z_ref[n_idx] = jnp.where(w_n[None] > 0, z_n, z_ref[n_idx])
        return n_k + w_n[None] * one_hot(z_n, k_dim, dt)

    jax.lax.fori_loop(0, l, position, jnp.zeros((p, b, k_dim), dt))


def l2r_scores_pallas(kd: jax.Array, beta_w: jax.Array, weights: jax.Array,
                      alpha: jax.Array, *, n_particles: int,
                      count_weighted: bool, block_docs: int = 8,
                      interpret: bool | None = None) -> jax.Array:
    """pallas_call wrapper. beta_w [B,L,K]; B must divide by block_docs.

    Returns the [L, B] per-position score matrix; the caller owns the
    final sum over positions (see l2r_block_kernel's ll_ref note).
    """
    b, l, k = beta_w.shape
    if b % block_docs:
        raise ValueError(f"B={b} not divisible by block_docs={block_docs}")
    grid = (b // block_docs,)

    kernel = functools.partial(l2r_block_kernel, n_particles=n_particles,
                               count_weighted=count_weighted)
    ins = (kd, jnp.swapaxes(beta_w, 0, 1), weights.T[..., None], alpha)
    ll = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_docs, 2), lambda i: (i, 0)),
            pl.BlockSpec((l, block_docs, k), lambda i: (0, i, 0)),
            pl.BlockSpec((l, block_docs, 1), lambda i: (0, i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((l, block_docs, 1), lambda i: (0, i, 0)),
        out_shape=out_struct((l, b, 1), beta_w.dtype, *ins),
        scratch_shapes=[pltpu.VMEM((l, n_particles, block_docs, 1),
                                   jnp.int32)],
        interpret=resolve_interpret(interpret),
        name="lda_l2r",
    )(*ins)
    return ll[..., 0]
