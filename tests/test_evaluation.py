"""Evaluation layer: streaming estimator, chunk invariance, ground truth.

The statistical half validates the two sampling primitives against exact
targets: `estep.sample_from_unnormalized` against its categorical
distribution (chi-square), and `left_to_right_log_likelihood` against
brute-force enumeration of p(w | beta, alpha) on a tiny LDA (K=2, V=3,
L=3) within Monte-Carlo error.

The layer half asserts the Evaluation-layer contracts: per-document
PRNG streams are fold_in(key, doc_id) (bitwise chunk/batch invariance —
the old split(key, b) stream silently changed a document's estimate with
batch layout), the blocked-stats beta path is bitwise-equal to the dense
one (vocab-sharded included), empty padded documents are excluded from
the LP mean, and the in-loop evaluator riding run_deleda's scan matches
the post-hoc streaming evaluator.
"""

import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from statutil import chi2_critical, chi2_statistic

from repro.core import deleda
from repro.core import estep as estep_mod
from repro.core.evaluation import (EVAL_BACKENDS, EvalSpec,
                                   auto_chunk_docs, evaluate_heldout,
                                   left_to_right_from_beta_w,
                                   left_to_right_fused,
                                   left_to_right_log_likelihood,
                                   left_to_right_unique_from_beta_w,
                                   left_to_right_unique_fused,
                                   log_perplexity,
                                   log_perplexity_from_stats,
                                   relative_perplexity_error)
from repro.core.graph import watts_strogatz_graph
from repro.core.lda import LDAConfig, eta_star
from repro.data.lda_synthetic import CorpusSpec, make_corpus

CFG = LDAConfig(n_topics=4, vocab_size=30, alpha=0.5, doc_len_max=12,
                n_gibbs=6, n_gibbs_burnin=3)


@pytest.fixture(scope="module")
def corpus():
    return make_corpus(CFG, jax.random.key(0),
                       CorpusSpec(n_nodes=2, docs_per_node=5, n_test=16))


def test_loglik_finite_and_negative(corpus):
    ll = left_to_right_log_likelihood(
        jax.random.key(1), corpus.test_words, corpus.test_mask,
        corpus.beta_star, CFG.alpha, n_particles=5)
    assert ll.shape == (16,)
    assert bool(jnp.isfinite(ll).all())
    assert bool((ll < 0).all())


def test_true_params_beat_uniform(corpus):
    """LP under the generating beta* must beat a uniform topic matrix."""
    lp_star = log_perplexity(jax.random.key(2), corpus.test_words,
                             corpus.test_mask, corpus.beta_star, CFG.alpha,
                             n_particles=5)
    uniform = jnp.full((CFG.n_topics, CFG.vocab_size),
                       1.0 / CFG.vocab_size)
    lp_unif = log_perplexity(jax.random.key(2), corpus.test_words,
                             corpus.test_mask, uniform, CFG.alpha,
                             n_particles=5)
    assert float(lp_star) < float(lp_unif)
    assert float(relative_perplexity_error(lp_unif, lp_star)) > 0


def test_more_particles_reduce_variance(corpus):
    lps = [float(log_perplexity(jax.random.key(s), corpus.test_words,
                                corpus.test_mask, corpus.beta_star,
                                CFG.alpha, n_particles=2))
           for s in range(4)]
    lps_many = [float(log_perplexity(jax.random.key(s), corpus.test_words,
                                     corpus.test_mask, corpus.beta_star,
                                     CFG.alpha, n_particles=16))
                for s in range(4)]
    assert np.std(lps_many) <= np.std(lps) + 0.05


# ---------------------------------------------------------------------------
# Statistical ground truth I: the categorical sampling primitive
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed,weights", [
    (101, [1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0]),
    (102, [10.0, 0.5, 0.5, 0.5, 0.5, 3.0]),     # heavily skewed
    (103, [2.0, 2.0, 2.0, 2.0]),                # uniform
])
def test_sample_from_unnormalized_matches_target(seed, weights):
    """Chi-square: draws match the normalized target distribution."""
    probs = jnp.asarray(weights)
    n = 20_000
    u = jax.random.uniform(jax.random.key(seed), (n,))
    draws = estep_mod.sample_from_unnormalized(
        jnp.broadcast_to(probs, (n, len(weights))), u)
    counts = np.bincount(np.asarray(draws), minlength=len(weights))
    stat = chi2_statistic(counts, np.asarray(weights))
    assert stat < chi2_critical(len(weights) - 1), (stat, counts)


def test_sample_from_unnormalized_batch_dims_and_edges():
    """Leading batch dims broadcast; u->0+ picks the first positive cell
    (never a zero-probability leading cell); u->1 picks the last."""
    probs = jnp.asarray([[0.0, 1.0, 1.0], [1.0, 0.0, 3.0]])
    z0 = estep_mod.sample_from_unnormalized(probs, jnp.full((2,), 1e-7))
    np.testing.assert_array_equal(np.asarray(z0), [1, 0])
    z1 = estep_mod.sample_from_unnormalized(probs,
                                            jnp.full((2,), 1.0 - 1e-7))
    np.testing.assert_array_equal(np.asarray(z1), [2, 2])


# ---------------------------------------------------------------------------
# Statistical ground truth II: left-to-right vs brute-force enumeration
# ---------------------------------------------------------------------------

def _exact_lda_marginal(words, beta, alpha):
    """Brute-force p(w | beta, alpha): sum over all K^L topic vectors.

    p(z) is the Dirichlet-multinomial  Gamma(K a) / Gamma(K a + L) *
    prod_k Gamma(a + n_k) / Gamma(a);  p(w | z) = prod_l beta[z_l, w_l].
    """
    k, _v = beta.shape
    l = len(words)
    log_norm = math.lgamma(k * alpha) - math.lgamma(k * alpha + l)
    total = 0.0
    for z in itertools.product(range(k), repeat=l):
        n_k = np.bincount(z, minlength=k)
        log_pz = log_norm + sum(
            math.lgamma(alpha + c) - math.lgamma(alpha) for c in n_k)
        log_pw = sum(math.log(beta[zi, wi]) for zi, wi in zip(z, words))
        total += math.exp(log_pz + log_pw)
    return total


def test_left_to_right_matches_enumeration():
    """Tiny LDA (K=2, V=3, L=3): the estimator's mean over independent
    seeds agrees with exact enumeration within Monte-Carlo error."""
    alpha = 0.5
    beta = np.array([[0.6, 0.3, 0.1],
                     [0.2, 0.3, 0.5]])
    docs = [[0, 2, 1], [2, 2, 2], [1, 0, 0]]
    words = jnp.asarray(docs, jnp.int32)
    mask = jnp.ones_like(words, bool)

    n_seeds = 40
    p_hat = np.empty((n_seeds, len(docs)))
    for s in range(n_seeds):
        ll = left_to_right_log_likelihood(
            jax.random.key(1000 + s), words, mask, jnp.asarray(beta),
            alpha, n_particles=32)
        p_hat[s] = np.exp(np.asarray(ll))

    for d, doc in enumerate(docs):
        exact = _exact_lda_marginal(doc, beta, alpha)
        mean = p_hat[:, d].mean()
        stderr = p_hat[:, d].std(ddof=1) / np.sqrt(n_seeds)
        assert abs(mean - exact) < 4.0 * stderr + 1e-4, (
            doc, mean, exact, stderr)


def test_left_to_right_masked_positions_do_not_score():
    """A masked tail must not change the likelihood: [w0, w1] padded to
    L=4 scores identically to the unpadded document."""
    alpha, beta = 0.5, jnp.asarray([[0.6, 0.3, 0.1], [0.2, 0.3, 0.5]])
    w_short = jnp.asarray([[0, 2]], jnp.int32)
    m_short = jnp.ones_like(w_short, bool)
    w_pad = jnp.asarray([[0, 2, 1, 1]], jnp.int32)
    m_pad = jnp.asarray([[True, True, False, False]])
    lls, llp = [], []
    for s in range(20):
        lls.append(float(left_to_right_log_likelihood(
            jax.random.key(s), w_short, m_short, beta, alpha,
            n_particles=16)[0]))
        llp.append(float(left_to_right_log_likelihood(
            jax.random.key(s), w_pad, m_pad, beta, alpha,
            n_particles=16)[0]))
    # same target; estimates agree in the mean within MC error
    assert abs(np.mean(lls) - np.mean(llp)) < 0.05, (np.mean(lls),
                                                     np.mean(llp))
    exact = _exact_lda_marginal([0, 2], np.asarray(beta), alpha)
    assert abs(np.mean(np.exp(lls)) - exact) < 0.02


# ---------------------------------------------------------------------------
# Evaluation layer: chunk/batch invariance of the fold_in streams
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def eval_setup(corpus):
    stats = jax.random.uniform(jax.random.key(11),
                               (CFG.n_topics, CFG.vocab_size)) + 0.01
    return stats, eta_star(stats, CFG.tau)


@pytest.mark.parametrize("chunk", [1, 7, 16])
def test_chunk_invariance_bitwise(corpus, eval_setup, chunk):
    """chunk_docs in {1, 7, B} produce bitwise-identical per-doc LLs."""
    _stats, beta = eval_setup
    key = jax.random.key(5)
    full = evaluate_heldout(key, corpus.test_words, corpus.test_mask,
                            beta=beta, alpha=CFG.alpha, n_particles=4)
    got = evaluate_heldout(key, corpus.test_words, corpus.test_mask,
                           beta=beta, alpha=CFG.alpha, n_particles=4,
                           chunk_docs=chunk)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(got))


def test_doc_stream_independent_of_batch_layout(corpus, eval_setup):
    """The PRNG-stream regression: evaluating a document ALONE must give
    the same floats as evaluating it inside a batch (the old
    split(key, b) streams changed with batch size and position)."""
    _stats, beta = eval_setup
    key = jax.random.key(6)
    batched = left_to_right_log_likelihood(
        key, corpus.test_words, corpus.test_mask, beta, CFG.alpha,
        n_particles=4)
    for d in (0, 5, 15):
        alone = left_to_right_log_likelihood(
            key, corpus.test_words[d:d + 1], corpus.test_mask[d:d + 1],
            beta, CFG.alpha, n_particles=4,
            doc_ids=jnp.asarray([d], jnp.int32))
        np.testing.assert_array_equal(np.asarray(alone)[0],
                                      np.asarray(batched)[d])


def test_stats_path_matches_dense_beta_bitwise(corpus, eval_setup):
    """The blocked-stats gather (dense AND vocab-sharded) is bitwise-equal
    to evaluating eta_star(stats) — Scale-layer traces evaluate without
    un-sharding and with no [K, V] beta temporary."""
    stats, beta = eval_setup
    key = jax.random.key(7)
    ref = evaluate_heldout(key, corpus.test_words, corpus.test_mask,
                           beta=beta, alpha=CFG.alpha, n_particles=4)
    from_stats = evaluate_heldout(key, corpus.test_words, corpus.test_mask,
                                  stats=stats, tau=CFG.tau,
                                  alpha=CFG.alpha, n_particles=4)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(from_stats))
    for s in (2, 5):
        assert CFG.vocab_size % s == 0
        sharded = stats.reshape(CFG.n_topics, s, CFG.vocab_size // s)
        got = evaluate_heldout(key, corpus.test_words, corpus.test_mask,
                               stats=sharded, tau=CFG.tau,
                               alpha=CFG.alpha, n_particles=4,
                               chunk_docs=7)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_evaluate_heldout_requires_one_source(corpus, eval_setup):
    stats, beta = eval_setup
    with pytest.raises(ValueError, match="exactly ONE"):
        evaluate_heldout(jax.random.key(0), corpus.test_words,
                         corpus.test_mask, alpha=CFG.alpha)
    with pytest.raises(ValueError, match="exactly ONE"):
        evaluate_heldout(jax.random.key(0), corpus.test_words,
                         corpus.test_mask, beta=beta, stats=stats,
                         alpha=CFG.alpha)


def test_empty_docs_excluded_from_lp(corpus, eval_setup):
    """An all-masked (padded) document contributes log p = 0; the LP mean
    must be over NON-EMPTY documents so padding cannot deflate it."""
    _stats, beta = eval_setup
    key = jax.random.key(8)
    lp = log_perplexity(key, corpus.test_words, corpus.test_mask, beta,
                        CFG.alpha, n_particles=4)
    pad = 6
    w_pad = jnp.concatenate([corpus.test_words,
                             jnp.zeros((pad, CFG.doc_len_max),
                                       corpus.test_words.dtype)])
    m_pad = jnp.concatenate([corpus.test_mask,
                             jnp.zeros((pad, CFG.doc_len_max), bool)])
    lp_pad = log_perplexity(key, w_pad, m_pad, beta, CFG.alpha,
                            n_particles=4)
    np.testing.assert_allclose(float(lp_pad), float(lp), rtol=1e-6)
    assert float(lp) > 0


# ---------------------------------------------------------------------------
# Evaluation layer: backend registry (fused fast path, pallas kernel)
# ---------------------------------------------------------------------------

def test_fused_matches_serial_bitwise_dense(corpus, eval_setup):
    """The fused multi-doc grid changes the wall clock, not one bit of
    the estimate: same fold_in streams, same draw order."""
    _stats, beta = eval_setup
    key = jax.random.key(21)
    doc_ids = jnp.arange(corpus.test_words.shape[0], dtype=jnp.int32)
    beta_w = jnp.take(beta.T, corpus.test_words, axis=0)
    serial = jax.jit(left_to_right_from_beta_w,
                     static_argnames=("n_particles",))(
        key, doc_ids, beta_w, corpus.test_mask, CFG.alpha, n_particles=4)
    fused = jax.jit(left_to_right_fused,
                    static_argnames=("n_particles",))(
        key, doc_ids, beta_w, corpus.test_mask, CFG.alpha, n_particles=4)
    np.testing.assert_array_equal(np.asarray(serial), np.asarray(fused))


def test_fused_matches_serial_bitwise_unique(corpus, eval_setup):
    """Count-weighted twin: the unique (CSR) layout through the fused
    core equals the serial unique estimator bitwise."""
    _stats, beta = eval_setup
    key = jax.random.key(22)
    uw, uc = estep_mod.unique_view(corpus.test_words, corpus.test_mask)
    doc_ids = jnp.arange(uw.shape[0], dtype=jnp.int32)
    beta_w = jnp.take(beta.T, uw, axis=0)
    serial = jax.jit(left_to_right_unique_from_beta_w,
                     static_argnames=("n_particles",))(
        key, doc_ids, beta_w, uc, CFG.alpha, n_particles=4)
    fused = jax.jit(left_to_right_unique_fused,
                    static_argnames=("n_particles",))(
        key, doc_ids, beta_w, uc, CFG.alpha, n_particles=4)
    np.testing.assert_array_equal(np.asarray(serial), np.asarray(fused))


@pytest.mark.parametrize("layout", ["dense", "unique"])
@pytest.mark.parametrize("backend", EVAL_BACKENDS)
def test_backend_chunk_invariance_bitwise(corpus, eval_setup, layout,
                                          backend):
    """Every backend x layout: chunk_docs in {1, 7, C, B, auto} give the
    same bits, and every backend gives the SERIAL backend's bits — one
    estimator, three implementations."""
    _stats, beta = eval_setup
    key = jax.random.key(23)
    ref = evaluate_heldout(key, corpus.test_words, corpus.test_mask,
                           beta=beta, alpha=CFG.alpha, n_particles=4,
                           chunk_docs=16, layout=layout,
                           backend="serial")
    for chunk in (1, 7, 11, 16, None):
        got = evaluate_heldout(key, corpus.test_words, corpus.test_mask,
                               beta=beta, alpha=CFG.alpha, n_particles=4,
                               chunk_docs=chunk, layout=layout,
                               backend=backend)
        np.testing.assert_array_equal(
            np.asarray(ref), np.asarray(got),
            err_msg=f"{backend}/{layout}/chunk={chunk}")


def test_unknown_backend_rejected(corpus, eval_setup):
    _stats, beta = eval_setup
    with pytest.raises(ValueError, match="eval backend"):
        evaluate_heldout(jax.random.key(0), corpus.test_words,
                         corpus.test_mask, beta=beta, alpha=CFG.alpha,
                         backend="vectorized")


def test_auto_chunk_docs_bounds():
    """Explicit chunk_docs is honored verbatim; the auto pick clamps to
    [1, B] and shrinks as the per-doc footprint grows."""
    assert auto_chunk_docs(100, 32, 10, 5, budget_bytes=1) == 1
    assert auto_chunk_docs(100, 32, 10, 5) == 100          # small docs
    big = auto_chunk_docs(10**9, 64, 10, 5)
    assert 1 <= big < 10**9                                 # budget-bound
    assert auto_chunk_docs(10**9, 128, 10, 5) < big         # longer docs


def test_padded_tail_chunk_regression(corpus, eval_setup):
    """B not divisible by chunk_docs: the zero-padded tail chunk must
    neither change any real document's bits nor leak the pad docs into
    the LP mean (count_nonempty normalization)."""
    stats, beta = eval_setup
    key = jax.random.key(24)
    b = corpus.test_words.shape[0]
    assert b % 7 != 0                       # 16 docs, tail chunk of 2
    full = evaluate_heldout(key, corpus.test_words, corpus.test_mask,
                            beta=beta, alpha=CFG.alpha, n_particles=4,
                            chunk_docs=b)
    tail = evaluate_heldout(key, corpus.test_words, corpus.test_mask,
                            beta=beta, alpha=CFG.alpha, n_particles=4,
                            chunk_docs=7)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(tail))
    lp_whole = log_perplexity_from_stats(
        key, corpus.test_words, corpus.test_mask, stats, tau=CFG.tau,
        alpha=CFG.alpha, n_particles=4)
    lp_tail = log_perplexity_from_stats(
        key, corpus.test_words, corpus.test_mask, stats, tau=CFG.tau,
        alpha=CFG.alpha, n_particles=4, chunk_docs=7)
    np.testing.assert_array_equal(np.asarray(lp_whole),
                                  np.asarray(lp_tail))
    # planting genuinely empty docs must not move the LP either
    m_holes = corpus.test_mask.at[3].set(False).at[11].set(False)
    ll = evaluate_heldout(key, corpus.test_words, m_holes, beta=beta,
                          alpha=CFG.alpha, n_particles=4, chunk_docs=7)
    assert float(ll[3]) == 0.0 and float(ll[11]) == 0.0
    lp_holes = log_perplexity_from_stats(
        key, corpus.test_words, m_holes, stats, tau=CFG.tau,
        alpha=CFG.alpha, n_particles=4, chunk_docs=7)
    manual = -float(np.asarray(ll).sum()) / (b - 2)
    np.testing.assert_allclose(float(lp_holes), manual, rtol=1e-6)


# ---------------------------------------------------------------------------
# Evaluation layer: in-loop evaluation riding the training scan
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def inloop_setup():
    cfg_lda = LDAConfig(n_topics=3, vocab_size=20, alpha=0.5,
                        doc_len_max=8, n_gibbs=4, n_gibbs_burnin=2)
    corpus = make_corpus(cfg_lda, jax.random.key(0),
                         CorpusSpec(n_nodes=8, docs_per_node=4, n_test=6))
    g = watts_strogatz_graph(8, 4, 0.3, seed=0)
    sched, degs = deleda.make_run_inputs(g, 20, seed=0, kind="matching")
    spec = EvalSpec(words=corpus.test_words, mask=corpus.test_mask,
                    key=jax.random.key(7), n_particles=3, probe_nodes=2)
    return cfg_lda, corpus, sched, degs, spec


def test_inloop_eval_does_not_change_trajectory(inloop_setup):
    cfg_lda, corpus, sched, degs, spec = inloop_setup
    base = deleda.DeledaConfig(lda=cfg_lda, mode="async", batch_size=2)
    withe = deleda.DeledaConfig(lda=cfg_lda, mode="async", batch_size=2,
                                eval_every=10)
    t0 = deleda.run_deleda(base, jax.random.key(1), corpus.words,
                           corpus.mask, sched, degs, 20, record_every=10)
    t1 = deleda.run_deleda(withe, jax.random.key(1), corpus.words,
                           corpus.mask, sched, degs, 20, record_every=10,
                           eval_spec=spec)
    assert t0.eval_lp is None
    np.testing.assert_array_equal(np.asarray(t0.stats),
                                  np.asarray(t1.stats))
    np.testing.assert_array_equal(np.asarray(t0.history),
                                  np.asarray(t1.history))
    assert t1.eval_lp.shape == (2, 2)


def test_inloop_eval_matches_posthoc_streaming(inloop_setup):
    """The on-device LP trajectory equals the post-hoc streaming
    evaluation of the recorded history — any chunking (chunk invariance
    again), so history replay is now strictly redundant."""
    cfg_lda, corpus, sched, degs, spec = inloop_setup
    cfg = deleda.DeledaConfig(lda=cfg_lda, mode="async", batch_size=2,
                              eval_every=10)
    trace = deleda.run_deleda(cfg, jax.random.key(1), corpus.words,
                              corpus.mask, sched, degs, 20,
                              record_every=10, eval_spec=spec)
    for r in range(2):
        for i in range(2):
            post = log_perplexity_from_stats(
                spec.key, spec.words, spec.mask, trace.history[r, i],
                tau=cfg_lda.tau, alpha=cfg_lda.alpha, n_particles=3,
                chunk_docs=4)
            np.testing.assert_allclose(float(trace.eval_lp[r, i]),
                                       float(post), rtol=1e-6)


def test_inloop_eval_sharded_carry(inloop_setup):
    """eval_every on a vocab-sharded run: LP comes straight from the
    [n, K, S, V/S] carry (blocked gather), matching the dense run's LP
    to the few-ulp tolerance of the sharded trajectory itself."""
    cfg_lda, corpus, sched, degs, spec = inloop_setup
    dense = deleda.DeledaConfig(lda=cfg_lda, mode="async", batch_size=2,
                                eval_every=10)
    sharded = deleda.DeledaConfig(lda=cfg_lda, mode="async", batch_size=2,
                                  eval_every=10, vocab_shards=4)
    td = deleda.run_deleda(dense, jax.random.key(1), corpus.words,
                           corpus.mask, sched, degs, 20, record_every=10,
                           eval_spec=spec)
    ts = deleda.run_deleda(sharded, jax.random.key(1), corpus.words,
                           corpus.mask, sched, degs, 20, record_every=10,
                           eval_spec=spec)
    np.testing.assert_allclose(np.asarray(ts.eval_lp),
                               np.asarray(td.eval_lp), rtol=1e-4)


def test_mesh_launcher_records_eval_trajectory(inloop_setup):
    """run_deleda on a one-device node mesh records the one-device run's
    in-loop LP trajectory; eval_every still needs an eval_spec and a
    horizon it divides."""
    from jax.sharding import Mesh
    cfg_lda, corpus, sched, degs, spec = inloop_setup
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("nodes",))
    one, many = (
        deleda.run_deleda(
            deleda.DeledaConfig(lda=cfg_lda, mode="sync", batch_size=2,
                                eval_every=10, **kw),
            jax.random.key(1), corpus.words, corpus.mask, sched, degs, 20,
            record_every=10, eval_spec=spec)
        for kw in ({}, dict(comm_backend="mesh", mesh=mesh)))
    assert many.eval_lp.shape == (2, 2)
    # as close as the trajectories themselves (tests/test_mesh_train.py)
    np.testing.assert_allclose(np.asarray(many.eval_lp),
                               np.asarray(one.eval_lp), rtol=1e-6)
    cfg = deleda.DeledaConfig(lda=cfg_lda, mode="sync", batch_size=2,
                              eval_every=10, comm_backend="mesh", mesh=mesh)
    with pytest.raises(ValueError, match="needs an eval_spec"):
        deleda.run_deleda(cfg, jax.random.key(1), corpus.words, corpus.mask,
                          sched, degs, 20, record_every=10)
    with pytest.raises(ValueError, match="divisible by"):
        deleda.run_deleda(cfg, jax.random.key(1), corpus.words, corpus.mask,
                          sched[:15], degs, 15, record_every=5,
                          eval_spec=spec)


def test_eval_every_validation(inloop_setup):
    cfg_lda, corpus, sched, degs, spec = inloop_setup
    cfg = deleda.DeledaConfig(lda=cfg_lda, mode="async", batch_size=2,
                              eval_every=10)
    with pytest.raises(ValueError, match="needs an eval_spec"):
        deleda.run_deleda(cfg, jax.random.key(1), corpus.words,
                          corpus.mask, sched, degs, 20, record_every=10)
    bad = deleda.DeledaConfig(lda=cfg_lda, mode="async", batch_size=2,
                              eval_every=15)
    with pytest.raises(ValueError, match="multiple of"):
        deleda.run_deleda(bad, jax.random.key(1), corpus.words,
                          corpus.mask, sched, degs, 20, record_every=10,
                          eval_spec=spec)
    with pytest.raises(ValueError, match="eval_every must be >= 0"):
        deleda.DeledaConfig(lda=cfg_lda, eval_every=-1)
