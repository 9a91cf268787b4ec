"""DELEDA system tests: Algorithm 1 semantics, consensus, G-OEM baseline."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import deleda, gossip
from repro.core.graph import complete_graph
from repro.core.lda import LDAConfig, beta_distance, eta_star
from repro.core.oem import run_oem
from repro.data.lda_synthetic import CorpusSpec, make_corpus

CFG = LDAConfig(n_topics=4, vocab_size=40, alpha=0.5, doc_len_max=16,
                n_gibbs=6, n_gibbs_burnin=3)


@pytest.fixture(scope="module")
def corpus():
    return make_corpus(CFG, jax.random.key(0),
                       CorpusSpec(n_nodes=8, docs_per_node=8, n_test=10))


@pytest.fixture(scope="module")
def graph():
    return complete_graph(8)


def _run(corpus, graph, mode, n_steps=40, seed=0, **kw):
    cfg = deleda.DeledaConfig(lda=CFG, mode=mode, batch_size=4, **kw)
    edges, degs = deleda.make_run_inputs(graph, n_steps, seed=seed)
    return deleda.run_deleda(cfg, jax.random.key(seed), corpus.words,
                             corpus.mask, edges, degs, n_steps,
                             record_every=10), cfg


def test_async_runs_and_counts_steps(corpus, graph):
    trace, _ = _run(corpus, graph, "async")
    assert trace.stats.shape == (8, 4, 40)
    assert not bool(jnp.isnan(trace.stats).any())
    # async: exactly 2 node-updates per iteration
    assert int(trace.steps.sum()) == 2 * 40
    assert trace.history.shape == (4, 8, 4, 40)


def test_sync_updates_every_node(corpus, graph):
    trace, _ = _run(corpus, graph, "sync")
    assert bool((trace.steps == 40).all())
    assert not bool(jnp.isnan(trace.stats).any())


def test_stats_stay_nonnegative_bounded(corpus, graph):
    trace, _ = _run(corpus, graph, "async")
    assert bool((trace.stats >= 0).all())
    # per-node stats are convex combos of per-doc normalized counts ->
    # total mass stays within [0, max doc length]
    assert float(trace.stats.sum(axis=(1, 2)).max()) < CFG.doc_len_max + 1


def test_learning_beats_init(corpus, graph):
    trace, _ = _run(corpus, graph, "async", n_steps=80)
    d_init = float(beta_distance(eta_star(trace.history[0][0]),
                                 corpus.beta_star))
    d_final = float(beta_distance(eta_star(trace.stats[0]),
                                  corpus.beta_star))
    assert d_final < d_init


def test_consensus_trend(corpus, graph):
    trace, cfg = _run(corpus, graph, "async", n_steps=80)
    c = np.asarray(trace.consensus)
    assert c[-1] < c[0]           # contracting overall
    rep = deleda.consensus_report(trace, graph, cfg, 80, 10)
    assert 0 < rep["lambda2"] < 1
    assert rep["measured"].shape == rep["envelope"].shape


def test_consensus_report_gnorm_covers_all_snapshots(graph):
    """Regression: the ||G|| bound used ONLY history[0]. When the early
    iterates are small and the statistics still grow, that envelope is
    spuriously tight and falsely reports violations — the bound must take
    the max over ALL recorded snapshots."""
    n_steps, record_every, n = 20, 10, graph.n_nodes
    k, v = CFG.n_topics, CFG.vocab_size
    # snapshot 0 tiny (norm ~0 -> old bound = 1.0), snapshot 1 large
    hist = np.zeros((2, n, k, v), np.float32)
    hist[1] = 9.0 / np.sqrt(k * v)            # per-node flat norm = 9
    cfg = deleda.DeledaConfig(lda=CFG, mode="async", batch_size=4)
    from repro.core.oem import make_rho_schedule
    rho_fn = make_rho_schedule(cfg.rho_kind, kappa=cfg.rho_kappa,
                               t0=cfg.rho_t0)
    rhos = np.asarray(jax.vmap(rho_fn)(jnp.arange(1, n_steps + 1)))
    lam2 = graph.lambda2()
    env_old = gossip.consensus_envelope(
        lam2, rhos, 1.0)[record_every - 1::record_every]    # history[0] bound
    env_new = gossip.consensus_envelope(
        lam2, rhos, 10.0)[record_every - 1::record_every]   # all-snapshot
    measured = 0.9 * env_new                  # inside the TRUE envelope
    trace = deleda.DeledaTrace(
        stats=jnp.asarray(hist[1]), steps=jnp.zeros((n,), jnp.int32),
        history=jnp.asarray(hist), consensus=jnp.asarray(measured))
    # the old history[0]-only bound falsely flags these as violations
    assert float((measured <= env_old + 1e-6).mean()) < 1.0
    rep = deleda.consensus_report(trace, graph, cfg, n_steps, record_every)
    np.testing.assert_allclose(rep["envelope"], env_new, rtol=1e-6)
    assert rep["within_envelope_frac"] == 1.0


def test_mean_iterate_matches_oem_structure(corpus, graph):
    """DELEDA's network-average follows a G-OEM-like trajectory: it stays
    a convex combination of per-document statistics (mass bound) and moves
    toward the corpus statistics as rho decays."""
    trace, _ = _run(corpus, graph, "sync", n_steps=40)
    mean_final = trace.stats.mean(0)
    oem = run_oem(CFG, jax.random.key(1), corpus.flat_words,
                  corpus.flat_mask, n_steps=40, batch_size=8,
                  record_every=10)
    d_deleda = float(beta_distance(eta_star(mean_final), corpus.beta_star))
    d_oem = float(beta_distance(eta_star(oem.state.stats),
                                corpus.beta_star))
    # both land in the same ballpark (within 2.5x of each other)
    assert d_deleda < 2.5 * d_oem + 0.1


def test_degree_correction_only_async(corpus, graph):
    trace_on, _ = _run(corpus, graph, "async", degree_correction=True)
    trace_off, _ = _run(corpus, graph, "async", degree_correction=False)
    # complete graph: correction factor == 1, results identical
    np.testing.assert_allclose(np.asarray(trace_on.stats),
                               np.asarray(trace_off.stats), atol=1e-6)


def test_corpus_draw_is_chunk_invariant(corpus, monkeypatch):
    """Documents are drawn in memory-bounded chunks (a large vocabulary
    would not fit a device at once); the chunk size changes no value."""
    from repro.data import lda_synthetic
    per_doc = CFG.doc_len_max * CFG.vocab_size * 4
    monkeypatch.setattr(lda_synthetic, "_DRAW_CHUNK_BYTES", 3 * per_doc)
    chunked = make_corpus(CFG, jax.random.key(0),
                          CorpusSpec(n_nodes=8, docs_per_node=8, n_test=10))
    for name in ("words", "mask", "test_words", "test_mask"):
        np.testing.assert_array_equal(np.asarray(getattr(chunked, name)),
                                      np.asarray(getattr(corpus, name)),
                                      err_msg=name)
