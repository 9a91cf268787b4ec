"""Sparse corpus layer: unique-token (CSR) E-step correctness.

Three tiers of claims, matching DESIGN.md section 9:

1. EXACT: on duplicate-free documents (all counts in {0, 1}) the
   count-weighted sweeps ARE the dense sweeps — same uniform stream,
   same op order — so jitted outputs are bitwise-equal. Likewise the
   segmented scatter `stats_from_unique` is the same scatter-add as
   `stats_from_per_pos` given equal per-token mass.
2. DISTRIBUTIONAL: the count-weighted categorical draw samples the
   analytic blocked conditional (chi-square gate via tests/statutil.py),
   and with real duplicates the sparse path's expected sufficient
   statistic agrees with the dense oracle's within sampling error.
3. PLUMBING: registry, fused batching, run_deleda / evaluation wiring,
   the corpus knobs (zipf_exponent, doc_len_lognormal) and the
   length-truncation diagnostic.

Float comparisons follow the repo convention: assert_array_equal only on
integer-valued outputs, allclose(atol=1e-6) on float stats — except where
both sides run under jit, where bitwise equality genuinely holds (the
eager oracle differs from its own jitted self by ~1 ulp).
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import deleda, estep, evaluation
from repro.core.graph import complete_graph
from repro.core.lda import LDAConfig
from repro.data.lda_synthetic import (CorpusSpec, make_corpus,
                                      LENGTH_TRUNCATION_WARN_FRAC)
from repro.kernels.lda_sparse import ops as sparse_ops
from statutil import chi2_critical, chi2_statistic

CFG = LDAConfig(n_topics=4, vocab_size=60, alpha=0.5, doc_len_max=24,
                n_gibbs=6, n_gibbs_burnin=3)


def _dup_free_docs(key, b=6, l=12, v=60):
    """Sorted duplicate-free documents: the exactness regime."""
    words = jax.vmap(
        lambda k: jax.random.choice(k, v, (l,), replace=False)
    )(jax.random.split(key, b)).astype(jnp.int32)
    lens = jnp.array([l, l - 3, l - 7, 1, l, l - 1])[:b]
    mask = jnp.arange(l)[None, :] < lens[:, None]
    words = jnp.sort(jnp.where(mask, words, jnp.iinfo(jnp.int32).max),
                     axis=-1)
    return jnp.where(mask, words, 0), mask


def _dup_docs(key, b=6, l=20, v=30):
    """Documents with heavy duplication (small vocab forces collisions)."""
    words = jax.random.randint(key, (b, l), 0, v, jnp.int32)
    lens = jnp.resize(jnp.array([l, l - 5, l - 11, 3, l, l - 2]), (b,))
    mask = jnp.arange(l)[None, :] < lens[:, None]
    return jnp.where(mask, words, 0), mask


# ----------------------------------------------------------------------------
# unique view
# ----------------------------------------------------------------------------

def test_unique_view_roundtrip_multiset():
    words, mask = _dup_docs(jax.random.key(0))
    uw, counts = estep.unique_view(words, mask)
    v = int(words.max()) + 1
    dense_hist = jax.vmap(
        lambda w, m: jnp.zeros(v, jnp.int32).at[w].add(m.astype(jnp.int32))
    )(words, mask)
    uniq_hist = jax.vmap(
        lambda w, c: jnp.zeros(v, jnp.int32).at[w].add(c)
    )(uw, counts)
    np.testing.assert_array_equal(np.asarray(dense_hist),
                                  np.asarray(uniq_hist))
    # realized-U trim: at least one doc saturates its unique budget
    assert uw.shape[1] == int((counts > 0).sum(-1).max())
    # slots are sorted by word id with padding at the tail
    np.testing.assert_array_equal(np.asarray(counts > 0),
                                  np.asarray(counts > 0)[
                                      :, ::-1].cumsum(-1)[:, ::-1] > 0)


def test_unique_view_is_permutation_invariant():
    words, mask = _dup_docs(jax.random.key(1))
    perm = jax.random.permutation(jax.random.key(2), words.shape[1])
    uw1, c1 = estep.unique_view(words, mask)
    uw2, c2 = estep.unique_view(words[:, perm], mask[:, perm])
    np.testing.assert_array_equal(np.asarray(uw1), np.asarray(uw2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


# ----------------------------------------------------------------------------
# segmented scatter
# ----------------------------------------------------------------------------

def test_stats_from_unique_bitwise_matches_per_pos_scatter():
    """Same per-token mass => same bits, duplicates and permutations
    included: place each unique word's full row at its first occurrence
    (zeros at the duplicate positions) and scatter both layouts."""
    words, mask = _dup_docs(jax.random.key(3))
    b, l = words.shape
    uw, counts = estep.unique_view(words, mask)
    u_dim = uw.shape[1]
    k = CFG.n_topics
    per_unique = jax.random.uniform(jax.random.key(4), (b, u_dim, k))
    per_unique = per_unique * (counts > 0)[..., None]

    # dense layout of the identical mass: full row at the first
    # occurrence of each unique word, zero rows at the duplicates
    per_pos = np.zeros((b, l, k), np.float32)
    uw_h, pu_h = np.asarray(uw), np.asarray(per_unique)
    words_h, mask_h = np.asarray(words), np.asarray(mask)
    for d in range(b):
        for s in range(u_dim):
            if np.asarray(counts)[d, s] == 0:
                continue
            first = int(np.argmax((words_h[d] == uw_h[d, s]) & mask_h[d]))
            per_pos[d, first] = pu_h[d, s]

    countf = counts.astype(per_unique.dtype)
    maskf = mask.astype(per_unique.dtype)
    s_unique = jax.jit(estep.stats_from_unique, static_argnums=2)(
        uw, per_unique, CFG.vocab_size, countf)
    s_dense = jax.jit(estep.stats_from_per_pos, static_argnums=2)(
        words, jnp.asarray(per_pos), CFG.vocab_size, maskf)
    np.testing.assert_array_equal(np.asarray(s_unique),
                                  np.asarray(s_dense))


# ----------------------------------------------------------------------------
# sweeps: exactness on duplicate-free docs
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("rao_blackwell", [True, False])
def test_sparse_sweeps_bitwise_equal_dense_when_counts_binary(rao_blackwell):
    """counts in {0,1}: the count-weighted kernel IS the dense kernel.

    Shared uniforms/z0, both sides jitted => bitwise equality of the
    per-token stats, the topic state and the theta accumulator."""
    words, mask = _dup_free_docs(jax.random.key(5))
    b, l = words.shape
    beta = jax.random.dirichlet(jax.random.key(6),
                                jnp.ones(CFG.vocab_size), (CFG.n_topics,))
    beta_w = jnp.take(beta.T, words, axis=0)
    uniforms, z0 = estep.draw_gibbs_randoms(CFG, jax.random.key(7), b, l,
                                            beta.dtype)
    kw = dict(alpha=CFG.alpha, n_sweeps=CFG.n_gibbs,
              burnin=CFG.n_gibbs_burnin, rao_blackwell=rao_blackwell)
    dense_fn = jax.jit(lambda: estep.gibbs_sweeps_dense(
        beta_w, mask.astype(beta.dtype), uniforms, z0, **kw))
    sparse_fn = jax.jit(lambda: estep.gibbs_sweeps_sparse(
        beta_w, mask.astype(beta.dtype), uniforms, z0, **kw))
    per_pos, z, ndk_d = dense_fn()
    per_unique, m, ndk_s = sparse_fn()
    np.testing.assert_array_equal(np.asarray(per_pos),
                                  np.asarray(per_unique))
    np.testing.assert_array_equal(np.asarray(ndk_d), np.asarray(ndk_s))
    # the count split collapses to the one-hot of the final z
    one_hot = jax.nn.one_hot(z, CFG.n_topics) * mask[..., None]
    np.testing.assert_array_equal(np.asarray(m), np.asarray(one_hot))


# ----------------------------------------------------------------------------
# sweeps: distributional correctness with real duplicates
# ----------------------------------------------------------------------------

def test_count_weighted_draw_samples_blocked_conditional():
    """chi-square gate: a single count-c slot must be drawn from
    p(k) ~ (alpha + n_dk^-[k]) * beta_w[k] regardless of c — removing
    the whole split first makes the conditional count-free."""
    k = CFG.n_topics
    n_draws = 4000
    beta_row = jnp.array([0.05, 0.4, 0.25, 0.3])
    n_dk = jnp.array([2.0, 0.0, 5.0, 1.0])
    c = 3.0
    target = np.asarray((CFG.alpha + n_dk) * beta_row, np.float64)

    def draw(key):
        u = jax.random.uniform(key, (1,))
        # state: the slot currently holds c copies of topic 0
        z, _, _ = estep.gibbs_position_update(
            (n_dk + c * jax.nn.one_hot(0, k))[None], jnp.array([0]),
            beta_row[None], jnp.array([c]), u, CFG.alpha)
        return z[0]

    zs = jax.jit(jax.vmap(draw))(jax.random.split(jax.random.key(8),
                                                  n_draws))
    counts = np.bincount(np.asarray(zs), minlength=k)
    stat = chi2_statistic(counts, target)
    assert stat < chi2_critical(k - 1), (
        f"count-weighted draw off target: chi2={stat:.1f}")


def test_sparse_stats_agree_with_dense_in_expectation():
    """With duplicates the blocked chain is a different (valid) sampler;
    the gate is statistical: mean sufficient statistic over independent
    seeds within a few standard errors of the dense oracle's."""
    words, mask = _dup_docs(jax.random.key(9), v=20)
    cfg = LDAConfig(n_topics=4, vocab_size=20, alpha=0.5, doc_len_max=20,
                    n_gibbs=12, n_gibbs_burnin=6)
    beta = jax.random.dirichlet(jax.random.key(10),
                                jnp.ones(cfg.vocab_size), (cfg.n_topics,))
    uw, counts = estep.unique_view(words, mask)
    d_backend = estep.get_estep("dense")
    s_backend = estep.get_sparse_estep("dense")
    n_seeds = 48
    keys = jax.random.split(jax.random.key(11), n_seeds)
    dense_stats = jax.jit(jax.vmap(
        lambda kk: d_backend(cfg, kk, words, mask, beta).stats))(keys)
    sparse_stats = jax.jit(jax.vmap(
        lambda kk: s_backend(cfg, kk, uw, counts, beta).stats))(keys)
    d_mean = np.asarray(dense_stats, np.float64).mean(0)
    s_mean = np.asarray(sparse_stats, np.float64).mean(0)
    # both allocate exactly the corpus token mass per document-mean
    np.testing.assert_allclose(d_mean.sum(), s_mean.sum(), rtol=1e-5)
    se = (np.asarray(dense_stats, np.float64).std(0)
          + np.asarray(sparse_stats, np.float64).std(0)
          ) / np.sqrt(n_seeds) + 1e-3
    z = np.abs(d_mean - s_mean) / se
    assert z.max() < 6.0, f"max z-score {z.max():.2f}"


def test_sparse_topic_marginal_chi_square_on_binary_counts():
    """Different keys, duplicate-free docs: the two kernels are the SAME
    Markov chain, so the final-state topic marginal of the sparse path
    must pass a chi-square test against the dense path's empirical
    distribution."""
    words, mask = _dup_free_docs(jax.random.key(12), b=2, l=8)
    cfg = LDAConfig(n_topics=4, vocab_size=60, alpha=0.5, doc_len_max=8,
                    n_gibbs=8, n_gibbs_burnin=4)
    beta = jax.random.dirichlet(jax.random.key(13),
                                jnp.ones(cfg.vocab_size), (cfg.n_topics,))
    uw, counts = estep.unique_view(words, mask)
    d_backend = estep.get_estep("dense")
    s_backend = estep.get_sparse_estep("dense")
    n_seeds = 3000
    kd = jax.random.split(jax.random.key(14), n_seeds)
    ks = jax.random.split(jax.random.key(15), n_seeds)
    zd = jax.jit(jax.vmap(
        lambda kk: d_backend(cfg, kk, words, mask, beta).z[0, 0]))(kd)
    ms = jax.jit(jax.vmap(
        lambda kk: s_backend(cfg, kk, uw, counts, beta).m[0, 0]))(ks)
    zs = np.asarray(ms).argmax(-1)
    probs = np.bincount(np.asarray(zd), minlength=cfg.n_topics) / n_seeds
    counts_s = np.bincount(zs, minlength=cfg.n_topics)
    stat = chi2_statistic(counts_s, probs)
    assert stat < chi2_critical(cfg.n_topics - 1), f"chi2={stat:.1f}"


# ----------------------------------------------------------------------------
# registry + pallas backend
# ----------------------------------------------------------------------------

def test_sparse_registry_and_validation():
    assert estep.SPARSE_ESTEP_BACKENDS == ("dense", "pallas")
    assert isinstance(estep.get_sparse_estep("dense"),
                      estep.DenseSparseEStep)
    assert isinstance(estep.get_sparse_estep("pallas"),
                      estep.PallasSparseEStep)
    with pytest.raises(ValueError, match="unknown"):
        estep.get_sparse_estep("nope")


@pytest.mark.parametrize("rao_blackwell", [True, False])
def test_pallas_sparse_backend_matches_dense(rao_blackwell):
    words, mask = _dup_docs(jax.random.key(16))
    uw, counts = estep.unique_view(words, mask)
    beta = jax.random.dirichlet(jax.random.key(17),
                                jnp.ones(CFG.vocab_size), (CFG.n_topics,))
    key = jax.random.key(18)
    pallas = estep.get_sparse_estep("pallas")
    if not rao_blackwell:
        # Rao-Blackwellized only: refused, never swapped for jnp code
        with pytest.raises(ValueError, match="Rao-Blackwell"):
            pallas(CFG, key, uw, counts, beta, rao_blackwell=False)
        return
    r_pal = pallas(CFG, key, uw, counts, beta)
    r_den = estep.get_sparse_estep("dense")(CFG, key, uw, counts, beta)
    # m is integer-valued (count splits); floats follow the repo's
    # atol=1e-6 convention (eager-vs-jit differs by ~1 ulp)
    np.testing.assert_array_equal(np.asarray(r_pal.m), np.asarray(r_den.m))
    np.testing.assert_allclose(np.asarray(r_pal.stats),
                               np.asarray(r_den.stats), atol=1e-6)
    np.testing.assert_allclose(np.asarray(r_pal.theta),
                               np.asarray(r_den.theta), atol=1e-6)


def test_pallas_sparse_ops_shape_guard():
    """A [1, U] countf would silently broadcast in jnp but read out of
    bounds in a pallas BlockSpec — the wrapper must refuse loudly."""
    b, u_dim, k = 4, 6, 3
    beta_w = jnp.ones((b, u_dim, k)) / k
    uniforms = jnp.full((2, b, u_dim), 0.5)
    z0 = jnp.zeros((b, u_dim), jnp.int32)
    bad = jnp.ones((1, u_dim))
    with pytest.raises(ValueError, match="countf/z0"):
        sparse_ops.sparse_sweeps(beta_w, bad, uniforms, z0, alpha=0.5,
                                 n_sweeps=2, burnin=1)


def test_pallas_sparse_pads_non_divisible_batch():
    words, mask = _dup_docs(jax.random.key(19), b=5)
    uw, counts = estep.unique_view(words, mask)
    beta = jax.random.dirichlet(jax.random.key(20),
                                jnp.ones(CFG.vocab_size), (CFG.n_topics,))
    r5 = estep.PallasSparseEStep(block_docs=4)(
        CFG, jax.random.key(21), uw, counts, beta)
    assert r5.m.shape[0] == 5
    assert bool(jnp.isfinite(r5.stats).all())


# ----------------------------------------------------------------------------
# fused batching
# ----------------------------------------------------------------------------

def test_fused_sparse_batch_independent_of_batch_mates():
    """Node a's sparse sweep must not depend on which other nodes share
    the fused batch (the awake-set changes every round)."""
    a, b = 3, 4
    words, mask = _dup_docs(jax.random.key(22), b=a * b)
    uw, counts = estep.unique_view(words, mask)
    u_dim = uw.shape[1]
    uw = uw.reshape(a, b, u_dim)
    counts = counts.reshape(a, b, u_dim)
    beta = jax.random.dirichlet(jax.random.key(23),
                                jnp.ones(CFG.vocab_size), (CFG.n_topics,))
    stats = jnp.broadcast_to(beta * 7.0,
                             (a, CFG.n_topics, CFG.vocab_size))
    keys = jax.random.split(jax.random.key(24), a)
    backend = estep.get_sparse_estep("dense")
    full = estep.estep_batch_from_stats_unique(backend, CFG, keys, uw,
                                               counts, stats)
    solo = estep.estep_batch_from_stats_unique(
        backend, CFG, keys[1:2], uw[1:2], counts[1:2], stats[1:2])
    np.testing.assert_allclose(np.asarray(full[1]), np.asarray(solo[0]),
                               atol=1e-6)


def test_fused_sparse_pallas_matches_dense():
    a, b = 2, 4
    words, mask = _dup_docs(jax.random.key(25), b=a * b)
    uw, counts = estep.unique_view(words, mask)
    u_dim = uw.shape[1]
    uw = uw.reshape(a, b, u_dim)
    counts = counts.reshape(a, b, u_dim)
    beta = jax.random.dirichlet(jax.random.key(26),
                                jnp.ones(CFG.vocab_size), (CFG.n_topics,))
    stats = jnp.broadcast_to(beta * 5.0,
                             (a, CFG.n_topics, CFG.vocab_size))
    keys = jax.random.split(jax.random.key(27), a)
    out = {}
    for name in estep.SPARSE_ESTEP_BACKENDS:
        out[name] = estep.estep_batch_from_stats_unique(
            estep.get_sparse_estep(name), CFG, keys, uw, counts, stats)
    np.testing.assert_allclose(np.asarray(out["pallas"]),
                               np.asarray(out["dense"]), atol=1e-6)


# ----------------------------------------------------------------------------
# run_deleda / evaluation wiring
# ----------------------------------------------------------------------------

def _small_run(layout, estep_backend="dense", vocab_shards=1,
               eval_every=0, corpus=None, **cfg_kw):
    corpus = corpus or make_corpus(
        CFG, jax.random.key(28), CorpusSpec(n_nodes=6, docs_per_node=4,
                                            n_test=6))
    g = complete_graph(6)
    sched, degs = deleda.make_run_inputs(g, 16, seed=0, kind="matching")
    cfg = deleda.DeledaConfig(lda=CFG, mode="async", batch_size=2,
                              corpus_layout=layout,
                              estep_backend=estep_backend,
                              vocab_shards=vocab_shards,
                              eval_every=eval_every, **cfg_kw)
    spec = None
    if eval_every:
        spec = evaluation.EvalSpec(words=corpus.test_words,
                                   mask=corpus.test_mask,
                                   key=jax.random.key(29), n_particles=2,
                                   probe_nodes=2, layout=layout)
    return deleda.run_deleda(cfg, jax.random.key(30), corpus.words,
                             corpus.mask, sched, degs, 16,
                             record_every=8, eval_spec=spec)


def test_config_validates_corpus_layout():
    with pytest.raises(ValueError, match="corpus_layout"):
        deleda.DeledaConfig(lda=CFG, corpus_layout="csr")
    with pytest.raises(ValueError, match="max_unique"):
        deleda.DeledaConfig(lda=CFG, corpus_layout="dense", max_unique=8)


def test_run_deleda_unique_layout_runs_and_conserves_mass():
    tr_d = _small_run("dense")
    tr_u = _small_run("unique")
    assert tr_u.stats.shape == tr_d.stats.shape
    assert bool(jnp.isfinite(tr_u.stats).all())
    # both layouts allocate the same total token mass per node
    np.testing.assert_allclose(
        np.asarray(tr_u.stats[-1].sum()), np.asarray(tr_d.stats[-1].sum()),
        rtol=1e-4)


def test_run_deleda_unique_layout_with_shards_and_eval():
    tr = _small_run("unique", estep_backend="pallas", vocab_shards=4,
                    eval_every=8)
    assert bool(jnp.isfinite(tr.stats).all())
    assert bool(jnp.isfinite(tr.eval_lp).all())


def test_eval_unique_layout_exact_on_binary_counts():
    """Duplicate-free sorted docs: the count-weighted left-to-right
    estimator is the dense estimator (1.0 * x is bitwise x)."""
    words, mask = _dup_free_docs(jax.random.key(31))
    beta = jax.random.dirichlet(jax.random.key(32),
                                jnp.ones(CFG.vocab_size), (CFG.n_topics,))
    ll_d = evaluation.evaluate_heldout(jax.random.key(33), words, mask,
                                       beta=beta, alpha=CFG.alpha,
                                       n_particles=3)
    ll_u = evaluation.evaluate_heldout(jax.random.key(33), words, mask,
                                       beta=beta, alpha=CFG.alpha,
                                       n_particles=3, layout="unique")
    np.testing.assert_array_equal(np.asarray(ll_d), np.asarray(ll_u))


def test_eval_unique_layout_chunk_invariant():
    words, mask = _dup_docs(jax.random.key(34))
    beta = jax.random.dirichlet(jax.random.key(35),
                                jnp.ones(CFG.vocab_size), (CFG.n_topics,))
    lls = [evaluation.evaluate_heldout(jax.random.key(36), words, mask,
                                       beta=beta, alpha=CFG.alpha,
                                       n_particles=2, chunk_docs=cs,
                                       layout="unique")
           for cs in (2, 3, 6)]
    np.testing.assert_allclose(np.asarray(lls[0]), np.asarray(lls[1]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(lls[0]), np.asarray(lls[2]),
                               atol=1e-5)


# ----------------------------------------------------------------------------
# corpus knobs (satellites a, b)
# ----------------------------------------------------------------------------

def test_zipf_exponent_skews_word_frequencies():
    base = CorpusSpec(n_nodes=8, docs_per_node=8)
    zipf = CorpusSpec(n_nodes=8, docs_per_node=8, zipf_exponent=2.0)
    cfg = LDAConfig(n_topics=4, vocab_size=200, alpha=0.5, doc_len_max=64,
                    n_gibbs=2, n_gibbs_burnin=1)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        c0 = make_corpus(cfg, jax.random.key(37), base)
        c1 = make_corpus(cfg, jax.random.key(37), zipf)

    def top_frac(c):
        w = np.asarray(c.words)[np.asarray(c.mask)]
        hist = np.bincount(w, minlength=cfg.vocab_size)
        hist.sort()
        return hist[-10:].sum() / hist.sum()

    assert top_frac(c1) > 2.0 * top_frac(c0)
    # a Zipf corpus has far fewer unique tokens per doc than positions
    uw, counts = c1.unique_view()
    mean_len = float(np.asarray(c1.mask).sum(-1).mean())
    mean_uniq = float(np.asarray(counts > 0).sum(-1).mean())
    assert mean_len / mean_uniq > 1.5


def test_lognormal_lengths_and_truncation_diagnostic():
    cfg = LDAConfig(n_topics=3, vocab_size=50, alpha=0.5, doc_len_max=16,
                    n_gibbs=2, n_gibbs_burnin=1)
    # mu far above log(doc_len_max): almost everything clips
    spec = CorpusSpec(n_nodes=4, docs_per_node=8,
                      doc_len_lognormal=(5.0, 0.3))
    with pytest.warns(UserWarning, match="clipped"):
        c = make_corpus(cfg, jax.random.key(38), spec)
    assert c.length_truncation_frac is not None
    assert c.length_truncation_frac > LENGTH_TRUNCATION_WARN_FRAC
    # a comfortable mu must not warn and must record a small fraction
    ok = CorpusSpec(n_nodes=4, docs_per_node=8,
                    doc_len_lognormal=(1.5, 0.3))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        c2 = make_corpus(cfg, jax.random.key(38), ok)
    assert c2.length_truncation_frac <= LENGTH_TRUNCATION_WARN_FRAC


def test_corpus_spec_validates_knobs():
    with pytest.raises(ValueError, match="zipf_exponent"):
        CorpusSpec(n_nodes=2, docs_per_node=2, zipf_exponent=-1.0)
    with pytest.raises(ValueError, match="doc_len_lognormal"):
        CorpusSpec(n_nodes=2, docs_per_node=2,
                   doc_len_lognormal=(1.0, 0.0))
