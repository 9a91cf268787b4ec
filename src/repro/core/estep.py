"""Unified G-OEM E-step layer: one categorical-sweep core, two backends.

The paper's per-iteration cost is dominated by the E-step (eq. 2): collapsed
Gibbs sweeps over each awake node's minibatch — exactly the "intractable
expectation" the paper approximates by sampling. That categorical-sweep core
(inverse-CDF draw, masked n_dk add/remove, Rao-Blackwell accumulation) used
to be implemented three separate times in this repo: ``core/gibbs.py``
(training), ``kernels/lda_gibbs`` (a Pallas kernel that defaulted to
interpreter mode even on TPU), and ``core/evaluation.py`` (the left-to-right
estimator's inner resample loop). This module is the single substrate they
all now share — the compute-side twin of :mod:`repro.core.comm`:

* the **shared sweep core** — :func:`sample_from_unnormalized` (inverse-CDF
  categorical draw), :func:`gibbs_position_update` (one masked collapsed-
  Gibbs move, broadcast over any leading batch dims) and
  :func:`gibbs_sweeps_dense` (full sweeps over a document batch). The Pallas
  kernel implements the identical update with the identical pre-drawn
  uniform stream, so both backends are bit-compatible per document.

* the **EStep registry** — :class:`DenseEStep` (pure jnp) and
  :class:`PallasEStep` (the lda_gibbs kernel; ``interpret=None``
  auto-detects, compiled on TPU), selected via
  ``DeledaConfig.estep_backend`` (the old ``use_pallas`` bool is a
  deprecated alias). The kernel is Rao-Blackwellized only, so it refuses
  ``rao_blackwell=False``.

* the **fused batch path** — :func:`estep_batch` gathers all awake nodes'
  minibatches into ONE ``[A*B, L]`` sweep call (one Pallas grid over
  ``A*B/block_docs`` document blocks instead of A degenerate ``B``-doc
  grids) and assembles per-node ``[K, V]`` statistics back out. Per-node
  PRNG streams come from the caller's ``fold_in(key, node_id)`` keys, and
  every sweep op is elementwise or a last-axis reduction, so the fused path
  is bit-identical to vmapping the single-node E-step (tests/test_estep.py).

* the **sparse corpus path** (DESIGN.md section 9) — real corpora are
  count matrices where L >> unique tokens per document. The unique-token
  (CSR) layout stores each document as ``(word_id, count)`` pairs padded
  to U slots (:func:`dense_to_unique`, a jit-able sort+segment pass);
  :func:`gibbs_sweeps_sparse` keeps the topic state per UNIQUE token as a
  ``[U, K]`` count split (how many of a word's ``c`` copies sit in each
  topic) and resamples all ``c`` copies with one count-weighted
  categorical draw per slot — O(U) work per sweep instead of O(L). With
  all counts equal to one the sparse sweep is bit-identical to the dense
  sweep on the sorted document (same uniforms, same op order); with
  duplicates it is a blocked-move approximation validated statistically
  against the dense sampler (tests/test_sparse.py).
  :func:`stats_from_unique` is the segmented scatter counterpart of
  :func:`stats_from_per_pos` — count-weighted rows into the same [K, V]
  (or blocked [K, S, V/S]) statistic through the identical scatter-add
  machinery, so given equal per-token mass the two paths produce the
  same bits. :class:`DenseSparseEStep` / :class:`PallasSparseEStep`
  mirror the dense registry (the kernel lives in ``kernels/lda_sparse``)
  and :func:`estep_batch_from_stats_unique` is the fused-across-awake-
  nodes front-end consumed by ``run_deleda(corpus_layout="unique")``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.lda import LDAConfig

LANES = 128     # the TPU vector register's minor (lane) dimension

__all__ = [
    "GibbsResult", "SparseGibbsResult", "sample_keepdims",
    "sample_from_unnormalized", "mean_seq", "gibbs_position_update",
    "gibbs_sweeps_dense", "gibbs_sweeps_sparse", "draw_gibbs_randoms",
    "stats_from_per_pos", "stats_per_node", "stats_from_unique",
    "dense_to_unique",
    "unique_view",
    "count_nonempty", "beta_w_from_stats", "theta_slab", "DenseEStep",
    "PallasEStep",
    "DenseSparseEStep", "PallasSparseEStep", "get_estep",
    "get_sparse_estep",
    "ESTEP_BACKENDS", "SPARSE_ESTEP_BACKENDS", "fused_sweeps",
    "estep_batch",
    "estep_batch_from_stats", "fused_sweeps_sparse",
    "estep_batch_from_stats_unique",
]


class GibbsResult(NamedTuple):
    stats: jax.Array      # [K, V] mean per-document sufficient statistics
    z: jax.Array          # [B, L] final topic assignments (int32)
    n_dk: jax.Array       # [B, K] final doc-topic counts
    theta: jax.Array      # [B, K] posterior-mean topic proportions


class SparseGibbsResult(NamedTuple):
    """E-step result in the unique-token (CSR) layout.

    The per-position ``z`` of :class:`GibbsResult` becomes the count
    split ``m``: ``m[b, u, k]`` is how many of unique word u's ``c``
    copies sit in topic k (``m.sum(-1) == counts``).
    """

    stats: jax.Array      # [K, V] mean per-document sufficient statistics
    m: jax.Array          # [B, U, K] final per-unique-token count splits
    n_dk: jax.Array       # [B, K] final doc-topic counts
    theta: jax.Array      # [B, K] posterior-mean topic proportions


# ----------------------------------------------------------------------------
# Shared categorical-sweep core
# ----------------------------------------------------------------------------

def _one_hot(z: jax.Array, k: int, dtype) -> jax.Array:
    """[...] int -> [..., k] one-hot via iota+compare (MXU-free)."""
    return (z[..., None] == jnp.arange(k, dtype=z.dtype)).astype(dtype)


def sample_keepdims(probs: jax.Array, u: jax.Array) -> jax.Array:
    """Inverse-CDF sample from an unnormalized probability vector [..., K].

    ``u`` [..., 1] uniforms; returns [..., 1] int32 draws. The running
    sums are built as ``((p0 + p1) + p2) + ...`` by explicit unrolled
    adds, not ``jnp.cumsum``: XLA lowers ``cumsum`` to a reduce-window
    whose float-add association varies with shape, fusion context and
    backend, so two call sites computing "the same" cumsum can disagree
    in the last ulp and flip a ``cum < u * total`` comparison. Explicit
    adds pin one association everywhere (XLA never reassociates them),
    and Pallas TPU has no cumsum lowering, so every kernel, the jnp
    sweeps and both evaluators draw through this one function and agree
    bit for bit. The trailing singleton axis is the column-vector layout
    of the Pallas kernels (documents on sublanes). K is a static trailing
    dim (K-1 adds and K compares, unrolled).
    """
    k = probs.shape[-1]
    c = probs[..., 0:1]
    cums = [c]
    for j in range(1, k):
        c = c + probs[..., j:j + 1]
        cums.append(c)
    thresh = u * cums[-1]
    z = jnp.zeros(thresh.shape, jnp.int32)
    for cj in cums:
        z = z + (cj < thresh).astype(jnp.int32)
    return z


def sample_from_unnormalized(probs: jax.Array, u: jax.Array) -> jax.Array:
    """:func:`sample_keepdims` with ``u`` [...] and draws [...]."""
    return sample_keepdims(probs, u[..., None])[..., 0]


def mean_seq(parts) -> jax.Array:
    """Mean of equal-shape arrays, summed left to right.

    The particle mean of the left-to-right evaluators: like
    :func:`sample_keepdims`, explicit adds fix one association, so the
    serial and fused evaluators and the lda_l2r kernel (which keeps
    particles on a different axis) agree bit for bit.
    """
    total = parts[0]
    for x in parts[1:]:
        total = total + x
    return total / len(parts)


def gibbs_position_update(n_dk, zi, bw, mf, u, alpha):
    """One masked collapsed-Gibbs move at a single position.

    The categorical core shared by training sweeps, the Pallas-kernel oracle
    and the left-to-right evaluator: remove the current assignment from the
    counts, draw from (n_dk + alpha) * beta[:, w_i] by inverse CDF, add the
    new assignment back, and expose the Rao-Blackwellized conditional.

    n_dk [..., K] counts; zi [...] int32 current assignments; bw [..., K]
    likelihood rows beta[:, w_i]; mf [...] float 1.0/0.0 mask; u [...]
    uniforms. Leading dims broadcast (e.g. bw/mf may carry a size-1
    particle axis). Returns (new_z, n_dk, post).
    """
    k = n_dk.shape[-1]
    n_dk = n_dk - mf[..., None] * _one_hot(zi, k, n_dk.dtype)
    probs = (n_dk + alpha) * bw                               # [..., K]
    new_z = sample_from_unnormalized(probs, u)
    new_z = jnp.where(mf > 0, new_z, zi)
    n_dk = n_dk + mf[..., None] * _one_hot(new_z, k, n_dk.dtype)
    post = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-30)
    return new_z, n_dk, post


def gibbs_sweeps_dense(beta_w: jax.Array, maskf: jax.Array,
                       uniforms: jax.Array, z0: jax.Array, *,
                       alpha: float, n_sweeps: int, burnin: int,
                       rao_blackwell: bool = True
                       ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Pure-jnp Gibbs sweeps over a batch of documents (the dense backend).

    beta_w [B, L, K], maskf [B, L] float, uniforms [S, B, L], z0 [B, L] i32.
    Returns (per_pos [B, L, K], z [B, L], ndk_mean [B, K]) where per_pos is
    the mean over kept sweeps of the Rao-Blackwellized conditional (or of
    the sampled one-hot assignment with rao_blackwell=False).

    Bit-compatible with the lda_gibbs Pallas kernel: same uniform stream,
    same per-position op order.
    """
    b, l, k = beta_w.shape
    n_keep = n_sweeps - burnin
    n_dk0 = jnp.einsum("blk,bl->bk", _one_hot(z0, k, beta_w.dtype), maskf)

    def position(i, carry, s):
        z, n_dk, acc = carry
        m = maskf[:, i]
        new_z, n_dk, post = gibbs_position_update(
            n_dk, z[:, i], beta_w[:, i], m, uniforms[s, :, i], alpha)
        collect = jnp.asarray(s >= burnin, post.dtype)
        contrib = post if rao_blackwell else _one_hot(new_z, k, post.dtype)
        acc = acc.at[:, i].add(collect * m[:, None] * contrib)
        z = z.at[:, i].set(new_z)
        return z, n_dk, acc

    def sweep(carry, s):
        z, n_dk, acc, ndk_acc = carry
        z, n_dk, acc = jax.lax.fori_loop(
            0, l, lambda i, c: position(i, c, s), (z, n_dk, acc))
        keep = jnp.asarray(s >= burnin, n_dk.dtype)
        return (z, n_dk, acc, ndk_acc + keep * n_dk), None

    # carries built from the inputs, not from constants: under shard_map
    # they then vary over the same mesh axes as the loop's outputs
    acc0 = jnp.zeros_like(beta_w)
    ndk0 = jnp.zeros_like(n_dk0)
    (z, _n_dk, acc, ndk_acc), _ = jax.lax.scan(
        sweep, (z0, n_dk0, acc0, ndk0), jnp.arange(n_sweeps))

    per_pos = acc / n_keep * maskf[..., None]
    return per_pos, z, ndk_acc / n_keep


def gibbs_sweeps_sparse(beta_w: jax.Array, countf: jax.Array,
                        uniforms: jax.Array, z0: jax.Array, *,
                        alpha: float, n_sweeps: int, burnin: int,
                        rao_blackwell: bool = True
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Count-weighted Gibbs sweeps over unique-token (CSR) documents.

    beta_w [B, U, K] likelihood rows per unique word, countf [B, U] float
    counts (0.0 on padding slots), uniforms [S, B, U], z0 [B, U] i32.
    Returns (per_unique [B, U, K], m [B, U, K], ndk_mean [B, K]).

    Topic state is the count split m[b, u] = c * one_hot(z_u): all ``c``
    copies of a unique word share one topic and are moved together by a
    single count-weighted categorical draw — remove the whole split from
    n_dk, draw z from (n_dk + alpha) * beta[:, w], add c * one_hot(z)
    back. O(U) draws per sweep instead of O(L). ``per_unique`` is the
    mean over kept sweeps of ``c *`` the Rao-Blackwellized conditional
    (or of the sampled split with rao_blackwell=False), i.e. the token
    mass is folded in: scatter it with :func:`stats_from_unique` as-is.

    With all counts in {0, 1} every op matches :func:`gibbs_sweeps_dense`
    on the (sorted) dense document bitwise — same uniform consumption,
    same add/remove order; with counts > 1 the blocked move is a
    different (faster-mixing per draw, statistically validated) kernel
    than c successive per-copy moves (tests/test_sparse.py).
    """
    b, u_dim, k = beta_w.shape
    n_keep = n_sweeps - burnin
    m0 = countf[..., None] * _one_hot(z0, k, beta_w.dtype)
    n_dk0 = jnp.einsum("buk,bu->bk", _one_hot(z0, k, beta_w.dtype), countf)

    def slot(i, carry, s):
        m, n_dk, acc = carry
        c = countf[:, i]                                       # [B]
        n_dk = n_dk - m[:, i]
        probs = (n_dk + alpha) * beta_w[:, i]                  # [B, K]
        new_z = sample_from_unnormalized(probs, uniforms[s, :, i])
        new_m = c[:, None] * _one_hot(new_z, k, n_dk.dtype)
        n_dk = n_dk + new_m
        post = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-30)
        collect = jnp.asarray(s >= burnin, post.dtype)
        contrib = post if rao_blackwell else _one_hot(new_z, k, post.dtype)
        acc = acc.at[:, i].add(collect * c[:, None] * contrib)
        m = m.at[:, i].set(new_m)
        return m, n_dk, acc

    def sweep(carry, s):
        m, n_dk, acc, ndk_acc = carry
        m, n_dk, acc = jax.lax.fori_loop(
            0, u_dim, lambda i, cc: slot(i, cc, s), (m, n_dk, acc))
        keep = jnp.asarray(s >= burnin, n_dk.dtype)
        return (m, n_dk, acc, ndk_acc + keep * n_dk), None

    acc0 = jnp.zeros_like(beta_w)
    ndk0 = jnp.zeros_like(n_dk0)
    (m, _n_dk, acc, ndk_acc), _ = jax.lax.scan(
        sweep, (m0, n_dk0, acc0, ndk0), jnp.arange(n_sweeps))

    slotf = (countf > 0).astype(beta_w.dtype)
    per_unique = acc / n_keep * slotf[..., None]
    return per_unique, m, ndk_acc / n_keep


# ----------------------------------------------------------------------------
# Front-end pieces shared by both backends and by the fused batch path
# ----------------------------------------------------------------------------

def draw_gibbs_randoms(config: LDAConfig, key: jax.Array, b: int, l: int,
                       dtype) -> tuple[jax.Array, jax.Array]:
    """The E-step PRNG stream: (uniforms [S, B, L], z0 [B, L])."""
    k_init, k_u = jax.random.split(key)
    uniforms = jax.random.uniform(k_u, (config.n_gibbs, b, l), dtype)
    z0 = jax.random.randint(k_init, (b, l), 0, config.n_topics, jnp.int32)
    return uniforms, z0


def count_nonempty(mask: jax.Array) -> jax.Array:
    """Number of documents with >= 1 unmasked position, guarded vs zero.

    mask: [..., B, L] bool or float document mask. The shared denominator
    rule for per-document means: padded all-masked documents contribute
    nothing to a masked sum, so dividing by the full batch size would
    silently bias the mean low. Used by :func:`stats_from_per_pos` and by
    the evaluation layer's held-out LP mean.
    """
    n_nonempty = (mask.astype(jnp.float32).sum(-1) > 0).sum()
    return jnp.maximum(n_nonempty, 1)


def stats_from_per_pos(words: jax.Array, per_pos: jax.Array,
                       vocab_size: int,
                       maskf: jax.Array | None = None) -> jax.Array:
    """Scatter [B, L, K] per-position stats into the per-doc-mean [K, V].

    ``maskf`` ([B, L] float document mask) sets the mean's denominator to
    the number of NON-EMPTY documents in the batch (guarded against zero):
    a batch padded with all-masked documents contributes nothing to the
    scatter, so dividing by the full batch size would silently bias the
    per-document-mean statistic low. Without ``maskf`` the legacy
    full-batch-size normalization is kept (correct only for unpadded
    batches).
    """
    with jax.named_scope("estep.scatter"):
        b, _l, k = per_pos.shape
        flat_w = words.reshape(-1)
        flat_p = per_pos.reshape(-1, k)
        stats = jnp.zeros((k, vocab_size), per_pos.dtype)
        if maskf is None:
            denom = jnp.asarray(b, per_pos.dtype)
        else:
            denom = count_nonempty(maskf).astype(per_pos.dtype)
        return stats.at[:, flat_w].add(flat_p.T) / denom


def stats_per_node(words: jax.Array, per_pos: jax.Array, vocab_size: int,
                   maskf: jax.Array,
                   axis_name: str | None = None) -> jax.Array:
    """Node-batched :func:`stats_from_per_pos`: [A, K, V] statistics.

    words [A, B, L] (or unique ids [A, B, U]), per_pos [A, B, L, K],
    maskf [A, B, L] (or float counts): bitwise-equal to vmapping
    :func:`stats_from_per_pos` over the A nodes. The vmapped scatter is one
    [K, A*V] buffer; where V is not a multiple of the TPU's 128-lane tile,
    unflattening it into [A, K, V] is no bitcast, and XLA relayouts it in
    two loops (one per topic row, one per chunk of nodes) that cost several
    times the scatter itself. So the buffer's vocabulary axis is padded to
    the next multiple of 128, which no update touches, and sliced back to V.

    With ``axis_name`` (inside ``shard_map``) that axis splits V into
    equal blocks and the result is this device's [A, K, V/blocks] block:
    each device scatters only the positions whose words it holds, at
    their ids within the block, and the mean keeps the whole documents'
    denominator.
    """
    if axis_name is not None:
        vocab_size //= jax.lax.axis_size(axis_name)
        words, held = vocab_block(words, vocab_size, axis_name)
        per_pos = jnp.where(held[..., None], per_pos, 0.0)
    width = -(-vocab_size // LANES) * LANES
    stats = jax.vmap(lambda w, p, m: stats_from_per_pos(w, p, width, m))(
        words, per_pos, maskf)
    with jax.named_scope("estep.scatter"):
        return stats[..., :vocab_size]


def vocab_block(words: jax.Array, width: int, axis_name: str
                ) -> tuple[jax.Array, jax.Array]:
    """Inside ``shard_map``: ``words``' ids within this device's block of
    ``width`` V columns along ``axis_name`` (the blocks in device order),
    clipped into it, and whether the block holds each word."""
    local = words - jax.lax.axis_index(axis_name) * width
    held = (local >= 0) & (local < width)
    return jnp.clip(local, 0, width - 1), held


def beta_w_from_stats(stats: jax.Array, words: jax.Array, tau: float,
                      denom: jax.Array | None = None,
                      axis_name: str | None = None) -> jax.Array:
    """Likelihood rows beta[:, words] gathered straight from the statistic.

    The blocked-stats gather of the Scale layer: the E-step only ever
    consumes the O(B*L) columns of the topic matrix that its minibatch
    words hit, so at large V materializing the full [K, V] ``eta_star``
    output is pure waste. This computes ``denom = sum_v (s + tau)`` as a
    fused reduction and gathers+normalizes just the needed columns —
    bitwise-equal to ``jnp.take(eta_star(stats, tau).T, words, axis=0)``
    (gather-then-divide of the identical floats).

    ``denom`` optionally supplies the [K] row normalizer precomputed by
    ``lda.eta_star_denom`` (the serving layer's staleness-aware cache):
    the per-request cost then drops to the pure column gather, with
    bitwise-identical output since the cached reduction is the same op
    on the same floats.

    stats: [K, V] or vocab-sharded [K, S, V/S] (trailing axes are flattened
    — the shard axis is a pure layout axis); words: [B, L] int32.
    Returns beta_w [B, L, K].

    With ``axis_name`` (inside ``shard_map``) ``stats`` is this device's
    block of V columns along that axis: one ``psum`` of the blocks' row
    sums (``denom``, if given, is this block's) gives the denominator,
    and one of [B, L, K] partials, each block
    giving the columns of the words it holds and zeros elsewhere, the
    rows. The dense [K, V] matrix exists on no device.
    """
    with jax.named_scope("estep.gather"):
        k = stats.shape[0]
        stats = stats.reshape(k, -1)
        if denom is None:
            denom = (stats + tau).sum(-1)                 # [K]
        if axis_name is None:
            cols = jnp.moveaxis(stats[:, words], 0, -1)   # [B, L, K]
            return (cols + tau) / denom
        local, held = vocab_block(words, stats.shape[-1], axis_name)
        cols = jnp.moveaxis(stats[:, local], 0, -1)
        part = jnp.where(held[..., None], cols + tau, 0.0)
        return (jax.lax.psum(part, axis_name)
                / jax.lax.psum(denom, axis_name))


def theta_slab(key: jax.Array, doc_ids: jax.Array, beta_w: jax.Array,
               maskf: jax.Array, *, alpha: float, n_sweeps: int,
               burnin: int) -> jax.Array:
    """Per-document posterior topic mixtures for one serving slab, [B, K].

    The mixture-query entry point of the serving layer: a few collapsed
    Gibbs sweeps over each document against fixed likelihood rows
    ``beta_w`` [B, L, K], returning the posterior-mean proportions
    ``theta = (mean_kept n_dk + alpha) / (n_d + alpha K)`` — the same
    estimate :class:`GibbsResult.theta` reports for training minibatches.

    Unlike the training front-end (whose uniforms are drawn for the whole
    batch at once), every document's stream here is ``fold_in(key,
    doc_id)``: the sweep core is elementwise/last-axis only, so a
    document's theta is BITWISE invariant to which requests share its
    slab, to arrival order and to queue depth — the serving twin of the
    evaluation layer's chunk-invariance property (tests/test_serving.py).
    """
    b, l, k = beta_w.shape
    keys_d = jax.vmap(lambda d: jax.random.fold_in(key, d))(doc_ids)

    def draws(kd):
        k_init, k_u = jax.random.split(kd)     # same split as the trainer
        u = jax.random.uniform(k_u, (n_sweeps, l), beta_w.dtype)
        z0 = jax.random.randint(k_init, (l,), 0, k, jnp.int32)
        return u, z0

    uniforms, z0 = jax.vmap(draws)(keys_d)     # [B, S, L], [B, L]
    _per_pos, _z, ndk_mean = gibbs_sweeps_dense(
        beta_w, maskf, jnp.moveaxis(uniforms, 0, 1), z0, alpha=alpha,
        n_sweeps=n_sweeps, burnin=burnin)
    theta = ndk_mean + alpha
    return theta / theta.sum(-1, keepdims=True)


# ----------------------------------------------------------------------------
# Unique-token (CSR) corpus layout
# ----------------------------------------------------------------------------

def dense_to_unique(words: jax.Array, mask: jax.Array,
                    max_unique: int | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """Dense [..., L] token lists -> per-doc (word_id, count) pairs [..., U].

    The jit-able sort+segment pass of the sparse corpus layer: sort each
    document's unmasked tokens (masked positions to a sentinel past the
    vocabulary), mark segment heads where the sorted value changes, and
    scatter segment lengths into U = ``max_unique`` padded slots (default
    U = L, always sufficient). Returns (uw [..., U] int32 ascending
    unique word ids, counts [..., U] int32 multiplicities); padding slots
    are (0, 0). Documents with more than ``max_unique`` distinct words
    silently drop the overflow — callers that can run host-side should
    use :func:`unique_view`, which trims U to the realized maximum.

    Pure function of (words, mask): corpora stay bit-identical by seed,
    the view is derived, never generated.
    """
    lead, l = words.shape[:-1], words.shape[-1]
    u_dim = l if max_unique is None else int(max_unique)
    w2 = words.reshape(-1, l).astype(jnp.int32)
    m2 = mask.reshape(-1, l).astype(bool)
    b = w2.shape[0]
    sentinel = jnp.iinfo(jnp.int32).max
    sw = jnp.sort(jnp.where(m2, w2, sentinel), axis=-1)
    valid = sw != sentinel
    first = valid & jnp.concatenate(
        [jnp.ones((b, 1), bool), sw[:, 1:] != sw[:, :-1]], axis=-1)
    seg = jnp.cumsum(first, axis=-1) - 1                  # [B, L]
    # padding / overflow tokens land in a throwaway slot u_dim
    seg = jnp.where(valid & (seg < u_dim), seg, u_dim)
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    counts = jnp.zeros((b, u_dim + 1), jnp.int32).at[rows, seg].add(
        valid.astype(jnp.int32))
    uw = jnp.zeros((b, u_dim + 1), jnp.int32).at[rows, seg].max(
        jnp.where(valid, sw, 0))
    return (uw[:, :u_dim].reshape(lead + (u_dim,)),
            counts[:, :u_dim].reshape(lead + (u_dim,)))


def unique_view(words: jax.Array, mask: jax.Array,
                max_unique: int | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """Host-facing :func:`dense_to_unique` trimmed to the realized U.

    Computes the actual maximum unique-token count across documents (a
    host sync — not for use inside jit) and slices the padded view down
    to it, so downstream sweeps do O(realized U) work, not O(L).
    """
    uw, counts = dense_to_unique(words, mask, max_unique)
    u_true = max(int((counts > 0).sum(-1).max()), 1)
    return uw[..., :u_true], counts[..., :u_true]


def stats_from_unique(uw: jax.Array, per_unique: jax.Array,
                      vocab_size: int,
                      countf: jax.Array | None = None) -> jax.Array:
    """Segmented scatter: [B, U, K] per-unique-token stats into [K, V].

    The CSR counterpart of :func:`stats_from_per_pos` — ``per_unique``
    rows already carry the full token mass of their slot (``count x`` the
    per-copy conditional, as produced by :func:`gibbs_sweeps_sparse`), so
    the scatter-add machinery is IDENTICAL: given equal per-token mass
    the dense and unique paths produce the same bits
    (tests/test_sparse.py). ``countf`` [B, U] float counts set the
    per-document-mean denominator to the non-empty-document count (a doc
    is non-empty iff it has any positive count) — the same rule as the
    dense path's ``maskf``.
    """
    return stats_from_per_pos(uw, per_unique, vocab_size, countf)


# ----------------------------------------------------------------------------
# EStep backends (registry mirrors repro.core.comm)
# ----------------------------------------------------------------------------

class _EStepBase:
    """Common front-end: PRNG stream + stats assembly around .sweeps()."""

    def __call__(self, config: LDAConfig, key: jax.Array, words: jax.Array,
                 mask: jax.Array, beta: jax.Array,
                 rao_blackwell: bool = True) -> GibbsResult:
        """Run the full E-step on a batch of documents.

        words: [B, L] int32 token ids, mask: [B, L] bool, beta: [K, V].
        Returns GibbsResult with stats = mean over documents of the expected
        per-document (topic, word) count matrix (shape [K, V]).
        """
        b, l = words.shape
        k = config.n_topics
        uniforms, z0 = draw_gibbs_randoms(config, key, b, l, beta.dtype)
        beta_w = jnp.take(beta.T, words, axis=0)             # [B, L, K]
        maskf = mask.astype(beta.dtype)
        per_pos, z, ndk_mean = self.sweeps(
            beta_w, maskf, uniforms, z0, alpha=config.alpha,
            n_sweeps=config.n_gibbs, burnin=config.n_gibbs_burnin,
            rao_blackwell=rao_blackwell)
        stats = stats_from_per_pos(words, per_pos, config.vocab_size,
                                   maskf)
        n_dk = jnp.einsum("blk,bl->bk", _one_hot(z, k, beta.dtype), maskf)
        theta = ndk_mean + config.alpha
        theta = theta / theta.sum(-1, keepdims=True)
        return GibbsResult(stats=stats, z=z, n_dk=n_dk, theta=theta)


class DenseEStep(_EStepBase):
    """Pure-jnp backend: the correctness oracle and the CPU fast path."""

    name = "dense"

    def sweeps(self, beta_w, maskf, uniforms, z0, *, alpha, n_sweeps,
               burnin, rao_blackwell=True):
        return gibbs_sweeps_dense(beta_w, maskf, uniforms, z0, alpha=alpha,
                                  n_sweeps=n_sweeps, burnin=burnin,
                                  rao_blackwell=rao_blackwell)


class PallasEStep(_EStepBase):
    """The kernels/lda_gibbs TPU kernel, bit-compatible with the dense core.

    ``interpret=None`` auto-detects: compiled on TPU, interpreter elsewhere
    (kernels/common.resolve_interpret — the same dispatch gossip_mix uses).
    The kernel is Rao-Blackwellized only; ``rao_blackwell=False`` raises.
    """

    name = "pallas"

    def __init__(self, block_docs: int = 8, interpret: bool | None = None):
        self.block_docs = block_docs
        self.interpret = interpret

    def sweeps(self, beta_w, maskf, uniforms, z0, *, alpha, n_sweeps,
               burnin, rao_blackwell=True):
        if not rao_blackwell:
            raise ValueError("the lda_gibbs kernel is Rao-Blackwellized "
                             "only; use the dense E-step for "
                             "rao_blackwell=False")
        from repro.kernels.lda_gibbs import ops as lda_gibbs_ops
        return lda_gibbs_ops.gibbs_sweeps(
            beta_w, maskf, uniforms, z0, alpha=alpha, n_sweeps=n_sweeps,
            burnin=burnin, block_docs=self.block_docs,
            interpret=self.interpret)


ESTEP_BACKENDS = ("dense", "pallas")


def get_estep(name: str, **kwargs) -> _EStepBase:
    """Factory: 'dense' | 'pallas' (kwargs go to the backend)."""
    if name == "dense":
        return DenseEStep(**kwargs)
    if name == "pallas":
        return PallasEStep(**kwargs)
    raise ValueError(f"unknown E-step backend {name!r}; "
                     f"want dense | pallas")


# ----------------------------------------------------------------------------
# Sparse (unique-token) EStep backends — same registry split, CSR layout
# ----------------------------------------------------------------------------

class _SparseEStepBase:
    """Front-end for the CSR layout: PRNG stream + segmented scatter
    around .sweeps(). Uniforms/z0 are drawn per unique SLOT ([S, B, U] /
    [B, U]) from the same two-way key split as the dense path."""

    def __call__(self, config: LDAConfig, key: jax.Array, uw: jax.Array,
                 counts: jax.Array, beta: jax.Array,
                 rao_blackwell: bool = True) -> SparseGibbsResult:
        """Full E-step on a batch of unique-token documents.

        uw: [B, U] int32 unique word ids, counts: [B, U] multiplicities
        (0 = padding), beta: [K, V]. Returns SparseGibbsResult with
        stats = the same per-document-mean [K, V] statistic as the dense
        E-step computes from the expanded documents.
        """
        b, u_dim = uw.shape
        countf = counts.astype(beta.dtype)
        uniforms, z0 = draw_gibbs_randoms(config, key, b, u_dim,
                                          beta.dtype)
        beta_w = jnp.take(beta.T, uw, axis=0)               # [B, U, K]
        per_unique, m, ndk_mean = self.sweeps(
            beta_w, countf, uniforms, z0, alpha=config.alpha,
            n_sweeps=config.n_gibbs, burnin=config.n_gibbs_burnin,
            rao_blackwell=rao_blackwell)
        stats = stats_from_unique(uw, per_unique, config.vocab_size,
                                  countf)
        theta = ndk_mean + config.alpha
        theta = theta / theta.sum(-1, keepdims=True)
        return SparseGibbsResult(stats=stats, m=m, n_dk=m.sum(axis=1),
                                 theta=theta)


class DenseSparseEStep(_SparseEStepBase):
    """Pure-jnp count-weighted sweeps: the sparse path's oracle."""

    name = "dense"

    def sweeps(self, beta_w, countf, uniforms, z0, *, alpha, n_sweeps,
               burnin, rao_blackwell=True):
        return gibbs_sweeps_sparse(beta_w, countf, uniforms, z0,
                                   alpha=alpha, n_sweeps=n_sweeps,
                                   burnin=burnin,
                                   rao_blackwell=rao_blackwell)


class PallasSparseEStep(_SparseEStepBase):
    """The kernels/lda_sparse TPU kernel (grid over doc blocks, the
    count-split segment state resident in VMEM). ``interpret=None``
    auto-detects like every other kernel backend; Rao-Blackwellized only,
    so ``rao_blackwell=False`` raises."""

    name = "pallas"

    def __init__(self, block_docs: int = 8, interpret: bool | None = None):
        self.block_docs = block_docs
        self.interpret = interpret

    def sweeps(self, beta_w, countf, uniforms, z0, *, alpha, n_sweeps,
               burnin, rao_blackwell=True):
        if not rao_blackwell:
            raise ValueError("the lda_sparse kernel is Rao-Blackwellized "
                             "only; use the dense E-step for "
                             "rao_blackwell=False")
        from repro.kernels.lda_sparse import ops as lda_sparse_ops
        return lda_sparse_ops.sparse_sweeps(
            beta_w, countf, uniforms, z0, alpha=alpha, n_sweeps=n_sweeps,
            burnin=burnin, block_docs=self.block_docs,
            interpret=self.interpret)


SPARSE_ESTEP_BACKENDS = ("dense", "pallas")


def get_sparse_estep(name: str, **kwargs) -> _SparseEStepBase:
    """Factory for the CSR layout: 'dense' | 'pallas' (same names as the
    dense registry, so ``DeledaConfig.estep_backend`` selects both)."""
    if name == "dense":
        return DenseSparseEStep(**kwargs)
    if name == "pallas":
        return PallasSparseEStep(**kwargs)
    raise ValueError(f"unknown sparse E-step backend {name!r}; "
                     f"want dense | pallas")


# ----------------------------------------------------------------------------
# Fused multi-node batch path
# ----------------------------------------------------------------------------

def fused_sweeps(backend: _EStepBase, config: LDAConfig, keys: jax.Array,
                 beta_w: jax.Array, maskf: jax.Array,
                 rao_blackwell: bool = True) -> jax.Array:
    """The fused-sweeps core: A nodes' minibatches as ONE [A*B, L] call.

    keys [A] per-node PRNG streams, beta_w [A, B, L, K] pre-gathered
    likelihood rows, maskf [A, B, L] float. Returns per-position statistics
    [A, B, L, K]. Shared by :func:`estep_batch` (dense beta) and
    :func:`estep_batch_from_stats` (blocked gather).
    """
    with jax.named_scope("estep.sweeps"):
        a, b, l, k = beta_w.shape
        s = config.n_gibbs
        uniforms, z0 = jax.vmap(
            lambda kk: draw_gibbs_randoms(config, kk, b, l,
                                          beta_w.dtype))(keys)
        per_pos, _z, _ndk = backend.sweeps(
            beta_w.reshape(a * b, l, k),
            maskf.reshape(a * b, l),
            jnp.moveaxis(uniforms, 0, 1).reshape(s, a * b, l),
            z0.reshape(a * b, l),
            alpha=config.alpha, n_sweeps=s, burnin=config.n_gibbs_burnin,
            rao_blackwell=rao_blackwell)
        return per_pos.reshape(a, b, l, k)


def estep_batch(backend: _EStepBase, config: LDAConfig, keys: jax.Array,
                words: jax.Array, mask: jax.Array, beta: jax.Array,
                rao_blackwell: bool = True) -> jax.Array:
    """All awake nodes' E-steps as ONE fused sweep call.

    keys [A] per-node PRNG keys (the caller's fold_in(key, node_id)
    streams), words/mask [A, B, L] per-node minibatches, beta [A, K, V]
    per-node topic matrices. Returns per-node statistics [A, K, V].

    The A node minibatches are flattened into one [A*B, L] document batch —
    a single Pallas grid over A*B/block_docs blocks instead of A degenerate
    B-doc grids — and the per-node [K, V] scatters are applied to the
    reshaped result, so the output is bit-identical to
    ``vmap(lambda k, w, m, bt: backend(config, k, w, m, bt).stats)``:
    every sweep op is elementwise or a last-axis reduction, independent of
    which documents share the batch.
    """
    beta_w = jax.vmap(lambda bt, w: jnp.take(bt.T, w, axis=0))(beta, words)
    maskf = mask.astype(beta.dtype)
    per_pos = fused_sweeps(backend, config, keys, beta_w, maskf,
                           rao_blackwell=rao_blackwell)
    return stats_per_node(words, per_pos, config.vocab_size, maskf)


def estep_batch_from_stats(backend: _EStepBase, config: LDAConfig,
                           keys: jax.Array, words: jax.Array,
                           mask: jax.Array, stats: jax.Array,
                           rao_blackwell: bool = True,
                           axis_name: str | None = None) -> jax.Array:
    """Fused E-steps reading the topic matrix DIRECTLY from the statistic.

    The Scale layer's blocked-stats path: instead of materializing the
    dense per-node ``eta_star(stats)`` output [A, K, V] (an O(A*K*V)
    temporary that dominates at V >= 10k), gather only the minibatch's
    ``beta[:, words]`` columns via :func:`beta_w_from_stats` — O(A*B*L*K)
    gathered values plus an [A, K] fused row-sum reduction. Bitwise-equal
    to ``estep_batch(..., beta=eta_star(stats, config.tau))``.

    stats: [A, K, V] or vocab-sharded [A, K, S, V/S] per-node statistics.
    Returns per-node statistics [A, K, V]. With ``axis_name`` (inside
    ``shard_map``) ``stats`` holds this device's block of V columns along
    that axis, and so does the result (:func:`beta_w_from_stats`,
    :func:`stats_per_node`).
    """
    beta_w = jax.vmap(lambda st, w: beta_w_from_stats(
        st, w, config.tau, axis_name=axis_name))(stats, words)
    maskf = mask.astype(beta_w.dtype)
    per_pos = fused_sweeps(backend, config, keys, beta_w, maskf,
                           rao_blackwell=rao_blackwell)
    return stats_per_node(words, per_pos, config.vocab_size, maskf,
                          axis_name)


def fused_sweeps_sparse(backend: _SparseEStepBase, config: LDAConfig,
                        keys: jax.Array, beta_w: jax.Array,
                        countf: jax.Array,
                        rao_blackwell: bool = True) -> jax.Array:
    """CSR twin of :func:`fused_sweeps`: A nodes as ONE [A*B, U] call.

    keys [A] per-node PRNG streams, beta_w [A, B, U, K] likelihood rows
    per unique word, countf [A, B, U] float counts. Returns per-unique
    statistics [A, B, U, K] (token mass folded in). The same batch-
    composition-independence argument applies: every sweep op is
    elementwise or a last-axis reduction, so fusing nodes changes no
    bits.
    """
    with jax.named_scope("estep.sweeps"):
        a, b, u_dim, k = beta_w.shape
        s = config.n_gibbs
        uniforms, z0 = jax.vmap(
            lambda kk: draw_gibbs_randoms(config, kk, b, u_dim,
                                          beta_w.dtype))(keys)
        per_unique, _m, _ndk = backend.sweeps(
            beta_w.reshape(a * b, u_dim, k),
            countf.reshape(a * b, u_dim),
            jnp.moveaxis(uniforms, 0, 1).reshape(s, a * b, u_dim),
            z0.reshape(a * b, u_dim),
            alpha=config.alpha, n_sweeps=s, burnin=config.n_gibbs_burnin,
            rao_blackwell=rao_blackwell)
        return per_unique.reshape(a, b, u_dim, k)


def estep_batch_from_stats_unique(backend: _SparseEStepBase,
                                  config: LDAConfig, keys: jax.Array,
                                  uw: jax.Array, counts: jax.Array,
                                  stats: jax.Array,
                                  rao_blackwell: bool = True,
                                  axis_name: str | None = None
                                  ) -> jax.Array:
    """Fused CSR E-steps reading beta straight from the statistic.

    The unique-layout twin of :func:`estep_batch_from_stats`: uw/counts
    [A, B, U] per-node minibatches in the (word_id, count) layout, stats
    [A, K, V] or vocab-sharded [A, K, S, V/S]. The blocked
    ``beta_w_from_stats`` gather now touches only O(A*B*U*K) columns —
    the sparse layer's win compounds with the Scale layer's — and the
    segmented scatter assembles per-node [A, K, V] statistics back out.
    ``axis_name`` splits V over a mesh axis as in
    :func:`estep_batch_from_stats`.
    """
    beta_w = jax.vmap(lambda st, w: beta_w_from_stats(
        st, w, config.tau, axis_name=axis_name))(stats, uw)
    countf = counts.astype(beta_w.dtype)
    per_unique = fused_sweeps_sparse(backend, config, keys, beta_w,
                                     countf, rao_blackwell=rao_blackwell)
    return stats_per_node(uw, per_unique, config.vocab_size, countf,
                          axis_name)
