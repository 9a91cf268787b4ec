"""Evaluation layer: streaming, chunk-invariant held-out log-perplexity.

Wallach et al. (2009), "Evaluation Methods for Topic Models", algorithm 3:
for a test document w_{1:N} and model (beta, alpha),

    p(w | beta, alpha) ~= prod_n  (1/P) sum_p  p(w_n | z^p_{<n}, beta, alpha)

where for each particle p the topic assignments of *earlier* positions are
resampled from their conditional before each new position is scored:

    p(w_n | z_{<n}) = sum_k  (n^p_{<n,k} + alpha_k) / (n_{<n} + sum alpha)
                             * beta[k, w_n].

The inner resample is the same masked categorical move as the training
E-step and runs on the shared sweep core (`repro.core.estep`), vectorized
over particles; all documents are batched through ONE scan over positions.

This module is the fourth first-class layer next to comm/estep/scenario
(DESIGN.md section 8). Three properties define it:

* **chunk-invariant streams** — every document's PRNG stream is derived by
  ``fold_in(key, doc_id)`` and, inside the position scan, by
  ``fold_in(doc_key, position)``. A document's log-likelihood estimate is
  therefore *bitwise* independent of which documents share its batch and
  of the ``chunk_docs`` chunking of :func:`evaluate_heldout` — evaluating
  a doc alone, in a batch, or across a chunk boundary gives identical
  floats (tests/test_evaluation.py).

* **O(B*P*L) memory** — each position's resample uniforms are drawn
  *inside* the position scan from the position-folded key, so the old
  ``[B, L, P, L]`` pre-drawn uniform tensor (the O(L^2) memory term that
  made 10k-doc held-out sets impossible) never exists; the live state is
  the [B, P, L] assignments + [B, P, K] counts.

* **blocked-stats beta** — :func:`evaluate_heldout` and
  :func:`heldout_lp_from_stats` consume sufficient statistics directly
  (dense ``[K, V]`` or vocab-sharded ``[K, S, V/S]``) through
  ``estep.beta_w_from_stats``: only the O(B*L*K) beta columns the test
  words hit are gathered, bitwise-equal to materializing
  ``eta_star(stats)`` first — so Scale-layer runs are evaluable without
  un-sharding and without the dense topic-matrix temporary.

In-loop evaluation: :class:`EvalSpec` + ``DeledaConfig.eval_every`` thread
a held-out set through ``run_deleda``, on one device or a node mesh, so
the LP trajectory is recorded on-device as the training scan runs (no
host-side replay of ``trace.history``).

The paper reports the *relative* log-perplexity error LP/LP* - 1 where
LP = -log p(X | eta) averaged over (non-empty) test documents and LP*
uses the generating parameters eta*.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import estep as estep_mod
from repro.core import threefry as tf3

__all__ = [
    "EvalSpec", "EVAL_BACKENDS", "left_to_right_from_beta_w",
    "left_to_right_unique_from_beta_w", "left_to_right_fused",
    "left_to_right_unique_fused", "left_to_right_log_likelihood",
    "auto_chunk_docs", "evaluate_heldout", "heldout_lp_from_stats",
    "ll_slab_from_beta", "ll_slab_from_stats",
    "log_perplexity", "log_perplexity_from_stats",
    "relative_perplexity_error",
]


@dataclasses.dataclass(frozen=True)
class EvalSpec:
    """A held-out evaluation request threaded through the training scan.

    ``words``/``mask`` are the [B, L] held-out documents, ``key`` the
    estimator's PRNG key (fixed across checkpoints so the LP trajectory is
    comparable point-to-point). ``n_particles`` and ``probe_nodes`` (how
    many leading nodes' statistics to evaluate at each checkpoint) are
    static pytree metadata.
    """

    words: jax.Array
    mask: jax.Array
    key: jax.Array
    n_particles: int = 10
    probe_nodes: int = 3
    layout: str = "dense"    # "dense" | "unique": run the in-loop
                             # evaluator over per-position tokens or over
                             # (word_id, count) pairs (Sparse corpus layer)


jax.tree_util.register_dataclass(
    EvalSpec, data_fields=["words", "mask", "key"],
    meta_fields=["n_particles", "probe_nodes", "layout"])


def _doc_keys(key: jax.Array, doc_ids: jax.Array) -> jax.Array:
    """Per-document streams: fold_in keeps them independent of batching."""
    return jax.vmap(lambda d: jax.random.fold_in(key, d))(doc_ids)


def left_to_right_from_beta_w(key: jax.Array, doc_ids: jax.Array,
                              beta_w: jax.Array, mask: jax.Array,
                              alpha: float,
                              n_particles: int = 10) -> jax.Array:
    """[B] per-document LL estimates from pre-gathered likelihood rows.

    beta_w [B, L, K] are the rows beta[:, w] for each position (gathered
    from a dense beta or straight from a — possibly vocab-sharded —
    statistic via ``estep.beta_w_from_stats``); mask [B, L] bool;
    doc_ids [B] int32 stable document identities for the PRNG streams.

    Every per-document stream is ``fold_in(key, doc_id)`` and each scan
    step draws its own uniforms from ``fold_in(doc_key, position)``, so
    the result for a given document is bitwise-invariant to batch
    composition and the [B, L, P, L] pre-draw of the legacy path never
    materializes.
    """
    b, l, k_dim = beta_w.shape
    p = n_particles
    maskf = mask.astype(beta_w.dtype)
    alpha_sum = alpha * k_dim
    keys_d = _doc_keys(key, doc_ids)                          # [B]

    def position(carry, n_idx):
        # carry: (z [B, P, L] int32 assignments so far, n_k [B, P, K])
        z, n_k = carry
        # this position's uniforms, drawn in-scan: O(B*P*L) live, keyed by
        # (doc_id, position) only — never by batch layout or chunk index
        def draws(kd):
            k_rs, k_dr = jax.random.split(jax.random.fold_in(kd, n_idx))
            return (jax.random.uniform(k_rs, (p, l)),
                    jax.random.uniform(k_dr, (p,)))
        u_rs_n, u_dr_n = jax.vmap(draws)(keys_d)    # [B, P, L], [B, P]
        # positions < n, still masked by the document mask
        pos_maskf = jnp.where(jnp.arange(l)[None, :] < n_idx, maskf, 0.0)

        # resample z_i for i < n — the shared masked categorical move,
        # batched over documents and particles at once
        def resample(i, st):
            z, n_k = st
            new_z, n_k, _post = estep_mod.gibbs_position_update(
                n_k, z[:, :, i], beta_w[:, None, i, :],
                pos_maskf[:, i][:, None], u_rs_n[:, :, i], alpha)
            z = z.at[:, :, i].set(new_z)
            return z, n_k

        z, n_k = jax.lax.fori_loop(0, l, resample, (z, n_k))

        # predictive probability of w_n given z_<n
        bw_n = beta_w[:, n_idx, :]                             # [B, K]
        n_lt = n_k.sum(-1, keepdims=True)                      # [B, P, 1]
        theta_hat = (n_k + alpha) / (n_lt + alpha_sum)         # [B, P, K]
        p_w = (theta_hat * bw_n[:, None, :]).sum(-1)           # [B, P]
        log_p = jnp.log(jnp.maximum(
            estep_mod.mean_seq([p_w[:, j] for j in range(p)]), 1e-30))
        log_p = jnp.where(mask[:, n_idx], log_p, 0.0)

        # draw z_n for each particle and add to counts
        probs_n = (n_k + alpha) * bw_n[:, None, :]             # [B, P, K]
        z_n = estep_mod.sample_from_unnormalized(probs_n, u_dr_n)
        add = maskf[:, n_idx][:, None, None]                   # [B, 1, 1]
        n_k = n_k + add * jax.nn.one_hot(z_n, k_dim, dtype=n_k.dtype)
        z = z.at[:, :, n_idx].set(
            jnp.where(mask[:, n_idx][:, None], z_n, z[:, :, n_idx]))
        return (z, n_k), log_p

    z0 = jnp.zeros((b, p, l), jnp.int32)
    nk0 = jnp.zeros((b, p, k_dim), beta_w.dtype)
    (_, _), log_ps = jax.lax.scan(position, (z0, nk0), jnp.arange(l))
    return log_ps.sum(axis=0)                                  # [B]


def left_to_right_unique_from_beta_w(key: jax.Array, doc_ids: jax.Array,
                                     beta_w: jax.Array, counts: jax.Array,
                                     alpha: float,
                                     n_particles: int = 10) -> jax.Array:
    """[B] per-document LL estimates over the unique-token (CSR) layout.

    beta_w [B, U, K] likelihood rows per unique word, counts [B, U] int32
    multiplicities (0 = padding slot). The count-weighted twin of
    :func:`left_to_right_from_beta_w`: the position scan runs over the U
    unique slots, the earlier-slot resample moves all c copies of a word
    with one draw (``gibbs_position_update`` with ``mf = c``) and slot n
    contributes ``c * log p(w_n | z_<n)``.

    With every count in {0, 1} this is BITWISE the dense estimator run on
    the (sorted) expanded document — same streams, same op order, 1.0*x
    multiplies only (tests/test_sparse.py). With duplicates it is the
    blocked approximation of Wallach et al.'s algorithm 3: a word's c
    copies are scored against the predictive theta from before the block
    and resampled as one unit, instead of position-by-position — the same
    blocked-move approximation the sparse training sweeps make, traded
    for O(U) instead of O(L) scan steps.
    """
    b, u_dim, k_dim = beta_w.shape
    p = n_particles
    countf = counts.astype(beta_w.dtype)
    alpha_sum = alpha * k_dim
    keys_d = _doc_keys(key, doc_ids)                          # [B]

    def position(carry, n_idx):
        z, n_k = carry
        def draws(kd):
            k_rs, k_dr = jax.random.split(jax.random.fold_in(kd, n_idx))
            return (jax.random.uniform(k_rs, (p, u_dim)),
                    jax.random.uniform(k_dr, (p,)))
        u_rs_n, u_dr_n = jax.vmap(draws)(keys_d)    # [B, P, U], [B, P]
        # earlier slots keep their full token mass in play
        pos_countf = jnp.where(jnp.arange(u_dim)[None, :] < n_idx,
                               countf, 0.0)

        def resample(i, st):
            z, n_k = st
            new_z, n_k, _post = estep_mod.gibbs_position_update(
                n_k, z[:, :, i], beta_w[:, None, i, :],
                pos_countf[:, i][:, None], u_rs_n[:, :, i], alpha)
            z = z.at[:, :, i].set(new_z)
            return z, n_k

        z, n_k = jax.lax.fori_loop(0, u_dim, resample, (z, n_k))

        bw_n = beta_w[:, n_idx, :]                             # [B, K]
        n_lt = n_k.sum(-1, keepdims=True)                      # [B, P, 1]
        theta_hat = (n_k + alpha) / (n_lt + alpha_sum)         # [B, P, K]
        p_w = (theta_hat * bw_n[:, None, :]).sum(-1)           # [B, P]
        log_p = countf[:, n_idx] * jnp.log(jnp.maximum(
            estep_mod.mean_seq([p_w[:, j] for j in range(p)]), 1e-30))
        log_p = jnp.where(counts[:, n_idx] > 0, log_p, 0.0)

        probs_n = (n_k + alpha) * bw_n[:, None, :]             # [B, P, K]
        z_n = estep_mod.sample_from_unnormalized(probs_n, u_dr_n)
        add = countf[:, n_idx][:, None, None]                  # [B, 1, 1]
        n_k = n_k + add * jax.nn.one_hot(z_n, k_dim, dtype=n_k.dtype)
        z = z.at[:, :, n_idx].set(
            jnp.where((counts[:, n_idx] > 0)[:, None], z_n,
                      z[:, :, n_idx]))
        return (z, n_k), log_p

    z0 = jnp.zeros((b, p, u_dim), jnp.int32)
    nk0 = jnp.zeros((b, p, k_dim), beta_w.dtype)
    (_, _), log_ps = jax.lax.scan(position, (z0, nk0),
                                  jnp.arange(u_dim))
    return log_ps.sum(axis=0)                                  # [B]


# ---------------------------------------------------------------------------
# Fused multi-doc position grid (the fast path)
# ---------------------------------------------------------------------------

def _z_packing(n_particles: int, k_dim: int) -> tuple[int, int, int]:
    """(bits per assignment, particles per uint32 word, words per doc).

    The fused scan keeps the per-position assignments z packed into
    uint32 words — ceil(log2 K) bits per particle — so the scan carry is
    a [L, B, W] buffer instead of [L, B, P] int32. That is not (only) a
    memory nicety: XLA CPU inserts per-step whole-buffer copies around
    the read-modify-write of the z carry inside the resample loop, and
    shrinking the buffer 10x (K=5, P=10 packs into ONE word) is what
    brings the fused path under the 2x-of-legacy wall target.
    """
    bits = max(1, (k_dim - 1).bit_length())
    ppw = max(1, 32 // bits)
    return bits, ppw, -(-n_particles // ppw)


def _l2r_fused_core(keys, beta_w, weights, alpha, n_particles,
                    count_weighted):
    """Shared fused left-to-right scan over [B] docs at once.

    keys [B] per-document PRNG keys (already doc-folded);
    beta_w [B, L, K]; weights [B, L] float — the dense layout passes the
    0/1 mask, the unique layout the token counts (the two estimators
    differ ONLY in whether slot n's score is multiplied by its count,
    selected by ``count_weighted``).

    Identical PRNG streams to the serial estimators — position keys via
    ``fold_in(doc_key, n)``, resample uniforms as column n of
    ``uniform(k_rs, (P, L))``, the whole derivation replicated bit-exactly
    by :mod:`repro.core.threefry` — but restructured for wall time:

    * position-major state (z [L, B, *], beta_w_t [L, B, K]) so every
      inner-loop slice is a leading-axis row, not a strided gather;
    * per-step uniforms computed IN the resample loop via
      ``tf3.uniform_column`` (one threefry cipher per consumed value,
      instead of materializing the [B, P, L] block each position);
    * the draw is ``estep.sample_from_unnormalized`` — fixed sequential
      cumsum association, shape- and context-independent bits;
    * the inner loop runs ``fori_loop(0, n)`` — the serial paths loop
      over all L positions and mask the tail to no-ops; dropping those
      identity steps halves the sequential work without touching any
      consumed value.
    """
    b, l, k_dim = beta_w.shape
    p = n_particles
    dt = beta_w.dtype
    alpha_sum = alpha * k_dim
    k1, k2 = tf3.key_pair(keys)                     # [B, 1] uint32 each
    bits, ppw, n_words = _z_packing(p, k_dim)
    lane = jnp.arange(ppw, dtype=jnp.uint32) * jnp.uint32(bits)
    vmask = jnp.uint32((1 << bits) - 1)
    p_pad = n_words * ppw

    def pack(z):                   # [B, P] int32 -> [B, W] uint32
        if p_pad != p:
            z = jnp.concatenate(
                [z, jnp.zeros(z.shape[:-1] + (p_pad - p,), z.dtype)], -1)
        zw = z.astype(jnp.uint32).reshape(z.shape[:-1] + (n_words, ppw))
        return (zw << lane).sum(-1, dtype=jnp.uint32)

    def unpack(w):                 # [B, W] uint32 -> [B, P] int32
        z = ((w[..., None] >> lane) & vmask).astype(jnp.int32)
        return z.reshape(w.shape[:-1] + (p_pad,))[..., :p]

    beta_w_t = jnp.moveaxis(beta_w, 1, 0)           # [L, B, K]
    w_t = weights.astype(dt).T                      # [L, B]

    def position(carry, n_idx):
        z_prev, n_k = carry        # z [L, B, W] u32, n_k [B, P, K]
        rs, dr = tf3.split2(*tf3.fold_in(k1, k2, n_idx))  # [B, 1] pairs
        u_dr_n = tf3.uniform(*dr, p)                # [B, P]

        def resample(i, st):
            z, n_k = st
            zi = unpack(z[i])                       # [B, P]
            u = tf3.uniform_column(*rs, p, l, i)    # [B, P]
            wf = w_t[i][:, None]                    # [B, 1]
            bw = beta_w_t[i][:, None, :]            # [B, 1, K]
            n_k = n_k - wf[..., None] * estep_mod._one_hot(zi, k_dim, dt)
            probs = (n_k + alpha) * bw
            new_z = estep_mod.sample_from_unnormalized(probs, u)
            new_z = jnp.where(wf > 0, new_z, zi)
            n_k = n_k + wf[..., None] * estep_mod._one_hot(new_z, k_dim,
                                                           dt)
            z = z.at[i].set(pack(new_z))
            return z, n_k

        z, n_k = jax.lax.fori_loop(0, n_idx, resample, (z_prev, n_k))

        bw_n = beta_w_t[n_idx]                      # [B, K]
        n_lt = n_k.sum(-1, keepdims=True)
        theta_hat = (n_k + alpha) / (n_lt + alpha_sum)
        p_w = (theta_hat * bw_n[:, None, :]).sum(-1)
        raw = jnp.log(jnp.maximum(
            estep_mod.mean_seq([p_w[:, j] for j in range(p)]), 1e-30))
        if count_weighted:
            raw = w_t[n_idx] * raw
        log_p = jnp.where(w_t[n_idx] > 0, raw, 0.0)

        probs_n = (n_k + alpha) * bw_n[:, None, :]
        z_n = estep_mod.sample_from_unnormalized(probs_n, u_dr_n)
        add = w_t[n_idx][:, None, None]
        n_k = n_k + add * jax.nn.one_hot(z_n, k_dim, dtype=n_k.dtype)
        z = z.at[n_idx].set(pack(
            jnp.where((w_t[n_idx] > 0)[:, None], z_n, unpack(z[n_idx]))))
        return (z, n_k), log_p

    z0 = jnp.zeros((l, b, n_words), jnp.uint32)
    nk0 = jnp.zeros((b, p, k_dim), dt)
    (_, _), log_ps = jax.lax.scan(position, (z0, nk0), jnp.arange(l))
    return log_ps.sum(axis=0)                       # [B]


def left_to_right_fused(key: jax.Array, doc_ids: jax.Array,
                        beta_w: jax.Array, mask: jax.Array, alpha: float,
                        n_particles: int = 10) -> jax.Array:
    """Fused-grid twin of :func:`left_to_right_from_beta_w`.

    Same signature, same ``fold_in(key, doc_id)`` / ``fold_in(doc_key,
    position)`` stream derivation (so chunk/batch invariance is
    untouched), restructured for wall time — see :func:`_l2r_fused_core`.
    Bit-identical to the serial estimator: both draw through
    ``estep.sample_from_unnormalized`` from the same streams, asserted
    equal in tests/test_evaluation.py and by the eval goldens.
    """
    return _l2r_fused_core(_doc_keys(key, doc_ids), beta_w,
                           mask.astype(beta_w.dtype),
                           alpha, n_particles, count_weighted=False)


def left_to_right_unique_fused(key: jax.Array, doc_ids: jax.Array,
                               beta_w: jax.Array, counts: jax.Array,
                               alpha: float,
                               n_particles: int = 10) -> jax.Array:
    """Fused-grid twin of :func:`left_to_right_unique_from_beta_w`.

    The count-weighted (CSR unique-slot) layout through the same fused
    core: weights are the token counts, slot n scores ``c * log p``.
    """
    return _l2r_fused_core(_doc_keys(key, doc_ids), beta_w,
                           counts.astype(beta_w.dtype),
                           alpha, n_particles, count_weighted=True)


EVAL_BACKENDS = ("fused", "serial", "pallas")


def _ll_from_beta_w(key, doc_ids, beta_w, mask, alpha, n_particles,
                    layout, backend="fused"):
    """Layout x backend dispatch shared by the chunked and in-loop
    evaluators (the eval twin of the ``estep.get_estep`` registry).

    In the "unique" layout ``mask`` carries the [B, U] int32 counts.
    Backends: "fused" (the fast path, default), "serial" (the reference
    the fused grid and the kernel are asserted against), "pallas" (the
    kernels/lda_l2r on-chip sweep; interpret auto-detected).
    """
    if layout not in ("dense", "unique"):
        raise ValueError(f"layout must be dense|unique, got {layout!r}")
    unique = layout == "unique"
    if backend == "serial":
        fn = (left_to_right_unique_from_beta_w if unique
              else left_to_right_from_beta_w)
        return fn(key, doc_ids, beta_w, mask, alpha, n_particles)
    if backend == "fused":
        fn = left_to_right_unique_fused if unique else left_to_right_fused
        return fn(key, doc_ids, beta_w, mask, alpha, n_particles)
    if backend == "pallas":
        from repro.kernels.lda_l2r import ops as l2r_ops
        return l2r_ops.l2r_scores(key, doc_ids, beta_w,
                                  mask.astype(beta_w.dtype), alpha,
                                  n_particles=n_particles,
                                  count_weighted=unique)
    raise ValueError(f"eval backend must be one of {EVAL_BACKENDS}, "
                     f"got {backend!r}")


@partial(jax.jit, static_argnames=("n_particles", "backend"))
def left_to_right_log_likelihood(key: jax.Array, words: jax.Array,
                                 mask: jax.Array, beta: jax.Array,
                                 alpha: float,
                                 n_particles: int = 10,
                                 doc_ids: jax.Array | None = None,
                                 backend: str = "fused") -> jax.Array:
    """[B] per-document log-likelihood estimates. words/mask: [B, L].

    ``doc_ids`` (default ``arange(B)``) are the identities fed to the
    per-document ``fold_in`` streams; pass global ids when evaluating a
    slice of a larger set so the estimates match the full-batch run
    bitwise (:func:`evaluate_heldout` does this for its chunks).
    """
    b, _l = words.shape
    if doc_ids is None:
        doc_ids = jnp.arange(b, dtype=jnp.int32)
    beta_w = jnp.take(beta.T, words, axis=0)                  # [B, L, K]
    return _ll_from_beta_w(key, doc_ids, beta_w, mask, alpha, n_particles,
                           "dense", backend)


@partial(jax.jit, static_argnames=("n_particles", "layout", "backend"))
def ll_slab_from_stats(key, doc_ids, words, mask, stats, tau, alpha,
                       n_particles=10, layout="dense", backend="fused",
                       denom=None):
    """[C] per-document LLs for ONE fixed-shape slab, beta from stats.

    The serving layer's single-slab entry point (also the per-chunk body
    of :func:`evaluate_heldout`): one jit trace per (C, L) slab shape,
    per-document ``fold_in(key, doc_id)`` streams so a document's LL is
    bitwise-independent of which requests share its slab. ``denom``
    optionally passes the cached [K] row normalizer
    (``lda.eta_star_denom`` via ``serving.ServingState``) so the hot
    path skips the O(K*V) reduction — bitwise-identical output. stats
    may be dense [K, V] or vocab-sharded [K, S, V/S].
    """
    beta_w = estep_mod.beta_w_from_stats(stats, words, tau, denom=denom)
    return _ll_from_beta_w(key, doc_ids, beta_w, mask, alpha, n_particles,
                           layout, backend)


@partial(jax.jit, static_argnames=("n_particles", "layout", "backend"))
def ll_slab_from_beta(key, doc_ids, words, mask, beta, alpha,
                      n_particles=10, layout="dense", backend="fused"):
    """[C] per-document LLs for ONE fixed-shape slab, dense [K, V] beta.

    The dense-cache twin of :func:`ll_slab_from_stats`: serving keeps
    ``eta_star(stats)`` materialized (``ServingState.beta()``) and each
    slab is a pure column gather against it — bitwise-equal to the
    stats path (gather-then-divide of identical floats, the
    ``beta_w_from_stats`` contract).
    """
    beta_w = jnp.take(beta.T, words, axis=0)
    return _ll_from_beta_w(key, doc_ids, beta_w, mask, alpha, n_particles,
                           layout, backend)


# per-chunk bodies of evaluate_heldout (older internal names)
_chunk_ll_from_stats = ll_slab_from_stats
_chunk_ll_from_beta = ll_slab_from_beta


_CHUNK_BUDGET_BYTES = 64 << 20     # default live-footprint target


def auto_chunk_docs(n_docs: int, doc_len: int, n_particles: int,
                    n_topics: int,
                    budget_bytes: int = _CHUNK_BUDGET_BYTES) -> int:
    """Chunk size whose live eval footprint fits a memory budget.

    The fused scan's per-document live state is O(L) likelihood rows
    ([L, K] twice: input + position-major transpose), the packed
    assignment carry ([L, W] uint32 words), the particle counts and a
    few [P, K]-sized elementwise temporaries, plus the per-step uniform
    columns — all independent of B, so the chunk size is just
    ``budget / per_doc_bytes`` clamped to [1, n_docs]. Used by
    :func:`evaluate_heldout` when ``chunk_docs`` is not given, replacing
    the old silent "one chunk = the whole batch" default; chunk
    invariance makes the picked size a pure performance knob
    (tests/test_evaluation.py asserts the auto-picked chunking is
    bitwise-equal to chunk_docs=B).
    """
    _bits, _ppw, n_words = _z_packing(n_particles, n_topics)
    per_doc = 4 * (2 * doc_len * n_topics + doc_len * n_words
                   + 8 * n_particles * n_topics + 4 * n_particles
                   + doc_len)
    return max(1, min(int(budget_bytes) // per_doc, n_docs))


def evaluate_heldout(key: jax.Array, words: jax.Array, mask: jax.Array, *,
                     beta: jax.Array | None = None,
                     stats: jax.Array | None = None, tau: float = 1e-2,
                     alpha: float, n_particles: int = 10,
                     chunk_docs: int | None = None,
                     layout: str = "dense",
                     backend: str = "fused") -> jax.Array:
    """Streaming per-document held-out log-likelihoods, [B].

    Pass exactly one of ``beta=`` (dense [K, V] topic matrix) or
    ``stats=`` (sufficient statistics, dense [K, V] or vocab-sharded
    [K, S, V/S] — the blocked ``estep.beta_w_from_stats`` gather is used,
    so no dense beta is ever materialized and Scale-layer runs evaluate
    without un-sharding).

    ``chunk_docs=C`` scans the documents C at a time (one jit
    compilation, C-shaped), so 10k+-doc held-out sets stream through one
    host; per-document streams are keyed by the GLOBAL doc index, so the
    result is bitwise-identical for every chunking (including C=B and
    C=1). The default derives C from a memory budget
    (:func:`auto_chunk_docs`) instead of silently materializing all B
    documents at once. The last chunk is padded with empty (fully
    masked) documents, which contribute log p = 0 and are sliced off.

    The host loop is pipelined: chunk i+1's ``(doc_ids, words, mask)``
    transfer is issued (``jax.device_put``, async) before chunk i's
    scores are computed, and nothing in the loop blocks on a result —
    dispatch stays ahead of the device so host->device ingestion
    overlaps the position scans instead of serializing with them.

    ``layout="unique"`` (the Sparse corpus layer) converts the documents
    to the (word_id, count) view once up front and runs the
    count-weighted left-to-right scan over U unique slots instead of L
    positions — exact for duplicate-free documents, the blocked
    approximation otherwise. ``backend`` selects the estimator
    implementation (``EVAL_BACKENDS``: fused | serial | pallas), all
    bit-compatible per document.
    """
    if (beta is None) == (stats is None):
        raise ValueError("pass exactly ONE of beta= or stats=")
    if layout not in ("dense", "unique"):
        raise ValueError(f"layout must be dense|unique, got {layout!r}")
    if layout == "unique":
        # `mask` carries the int32 counts from here on; zero-count pad
        # slots behave exactly like masked positions
        words, mask = estep_mod.unique_view(words, mask)
    b, l = words.shape
    if chunk_docs is None:
        k_dim = (beta if beta is not None else stats).shape[0]
        c = auto_chunk_docs(b, l, n_particles, k_dim)
    else:
        c = max(1, min(int(chunk_docs), b))
    n_chunks = -(-b // c)
    if n_chunks * c > b:
        pad = n_chunks * c - b
        words = jnp.concatenate(
            [words, jnp.zeros((pad, l), words.dtype)])
        mask = jnp.concatenate(
            [mask, jnp.zeros((pad, l), mask.dtype)])
    doc_ids = jnp.arange(n_chunks * c, dtype=jnp.int32)

    def chunk_inputs(ci):
        sl = slice(ci * c, (ci + 1) * c)
        # async h2d: by the time a chunk is consumed its transfer was
        # issued one iteration ago and has overlapped the previous
        # chunk's compute
        return jax.device_put((doc_ids[sl], words[sl], mask[sl]))

    lls = []
    pending = chunk_inputs(0)
    for ci in range(n_chunks):
        ids_c, words_c, mask_c = pending
        if ci + 1 < n_chunks:
            pending = chunk_inputs(ci + 1)     # double-buffered ingest
        if stats is not None:
            lls.append(_chunk_ll_from_stats(
                key, ids_c, words_c, mask_c, stats, tau, alpha,
                n_particles, layout, backend))
        else:
            lls.append(_chunk_ll_from_beta(
                key, ids_c, words_c, mask_c, beta, alpha,
                n_particles, layout, backend))
    return jnp.concatenate(lls)[:b]


def _lp_mean(ll: jax.Array, mask: jax.Array) -> jax.Array:
    """LP = -mean log-likelihood over NON-EMPTY documents.

    An all-masked (padded) document contributes log p = 0, so including
    it in the mean silently deflates LP — same non-empty-count rule as
    ``estep.stats_from_per_pos``.
    """
    return -ll.sum() / estep_mod.count_nonempty(mask).astype(ll.dtype)


def heldout_lp_from_stats(key: jax.Array, words: jax.Array,
                          mask: jax.Array, stats: jax.Array, tau: float,
                          alpha: float, n_particles: int = 10,
                          layout: str = "dense",
                          backend: str = "fused") -> jax.Array:
    """Scalar LP straight from a (possibly vocab-sharded) statistic.

    Pure traced function — this is the in-loop evaluator that rides
    ``run_deleda``'s training scan (vmapped over probe nodes) and the
    per-chunk body of :func:`log_perplexity_from_stats`. Consumes stats
    [K, V] or [K, S, V/S] through the blocked beta gather. With
    ``layout="unique"``, ``words``/``mask`` must already be the
    (word_id, count) pair view — the caller converts once, outside any
    scan (``EvalSpec.layout`` in run_deleda does this).
    """
    doc_ids = jnp.arange(words.shape[0], dtype=jnp.int32)
    beta_w = estep_mod.beta_w_from_stats(stats, words, tau)
    ll = _ll_from_beta_w(key, doc_ids, beta_w, mask, alpha, n_particles,
                         layout, backend)
    return _lp_mean(ll, mask)


def log_perplexity(key: jax.Array, words: jax.Array, mask: jax.Array,
                   beta: jax.Array, alpha: float,
                   n_particles: int = 10,
                   backend: str = "fused") -> jax.Array:
    """Average held-out log-perplexity LP = -mean_d log p(X_d | eta),
    the mean taken over non-empty documents only."""
    ll = left_to_right_log_likelihood(key, words, mask, beta, alpha,
                                      n_particles, backend=backend)
    return _lp_mean(ll, mask)


def log_perplexity_from_stats(key: jax.Array, words: jax.Array,
                              mask: jax.Array, stats: jax.Array, *,
                              tau: float = 1e-2, alpha: float,
                              n_particles: int = 10,
                              chunk_docs: int | None = None,
                              layout: str = "dense",
                              backend: str = "fused") -> jax.Array:
    """Scalar LP via the streaming evaluator (chunked, blocked-stats)."""
    ll = evaluate_heldout(key, words, mask, stats=stats, tau=tau,
                          alpha=alpha, n_particles=n_particles,
                          chunk_docs=chunk_docs, layout=layout,
                          backend=backend)
    return _lp_mean(ll, mask)


def relative_perplexity_error(lp: jax.Array, lp_star: jax.Array) -> jax.Array:
    """The paper's reported metric: LP / LP* - 1."""
    return lp / lp_star - 1.0
