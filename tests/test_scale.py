"""Scale layer: vocab-sharded statistics end-to-end + the bugfix batch.

The contract under test: splitting the vocab axis into S blocks changes
NOTHING about the trajectory — gossip is row-linear so per-shard mixing
composes to the dense averaging map, and the blocked-stats E-step gathers
the identical beta columns the dense path would materialize. Sharded runs
are asserted (near-bit) equal to the dense oracle across comm x estep
backend combos, and ``train_steps`` on a node x vocab mesh grid is
asserted against the 1-D mesh in a forced-multi-device subprocess.

Also here: regression tests for the PR's bugfix batch — legacy
`jax.random.PRNGKey` through `run_deleda` / `left_to_right_log_likelihood`,
`ring_matchings(2)`'s identity odd round, `beta_distance` on
near-collinear topics, and `stats_from_per_pos` on padded batches.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import comm, deleda, estep, gossip
from repro.core.graph import complete_graph, watts_strogatz_graph
from repro.core.lda import LDAConfig, beta_distance, eta_star
from repro.core.evaluation import left_to_right_log_likelihood
from repro.data.lda_synthetic import CorpusSpec, make_corpus

CFG = LDAConfig(n_topics=4, vocab_size=40, alpha=0.5, doc_len_max=16,
                n_gibbs=6, n_gibbs_burnin=3)


@pytest.fixture(scope="module")
def corpus():
    return make_corpus(CFG, jax.random.key(0),
                       CorpusSpec(n_nodes=8, docs_per_node=8, n_test=10))


# ---------------------------------------------------------------------------
# Blocked-stats building blocks
# ---------------------------------------------------------------------------

def test_beta_w_from_stats_bitwise_equals_dense_gather():
    stats = jax.random.uniform(jax.random.key(0), (5, 48))
    words = jax.random.randint(jax.random.key(1), (7, 9), 0, 48)
    blocked = estep.beta_w_from_stats(stats, words, tau=1e-2)
    dense = jnp.take(eta_star(stats, 1e-2).T, words, axis=0)
    np.testing.assert_array_equal(np.asarray(blocked), np.asarray(dense))


def test_beta_w_from_stats_accepts_sharded_layout():
    stats = jax.random.uniform(jax.random.key(0), (5, 48))
    words = jax.random.randint(jax.random.key(1), (7, 9), 0, 48)
    flat = estep.beta_w_from_stats(stats, words, tau=1e-2)
    sharded = estep.beta_w_from_stats(stats.reshape(5, 4, 12), words,
                                      tau=1e-2)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(flat),
                               rtol=1e-6)


def test_estep_batch_from_stats_matches_materialized_beta():
    a, b, l = 3, 4, 10
    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.key(2), i))(
        jnp.arange(a))
    words = jax.random.randint(jax.random.key(3), (a, b, l), 0,
                               CFG.vocab_size)
    mask = jax.random.uniform(jax.random.key(4), (a, b, l)) < 0.9
    stats = jax.random.uniform(jax.random.key(5),
                               (a, CFG.n_topics, CFG.vocab_size))
    backend = estep.get_estep("dense")
    blocked = estep.estep_batch_from_stats(backend, CFG, keys, words, mask,
                                           stats)
    dense = estep.estep_batch(backend, CFG, keys, words, mask,
                              eta_star(stats, CFG.tau))
    np.testing.assert_array_equal(np.asarray(blocked), np.asarray(dense))


# ---------------------------------------------------------------------------
# Vocab-sharded mixing across comm backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["dense", "pallas"])
def test_sharded_mixing_matches_dense_per_shard(backend):
    """[n, K, S, V/S] mixing == dense [n, K, V] mixing, per shard."""
    n, k, v, s = 8, 3, 32, 4
    stats = jax.random.uniform(jax.random.key(6), (n, k, v))
    sched = comm.GossipSchedule.draw_matchings(
        complete_graph(n), 4, np.random.default_rng(0))
    cx = comm.get_communicator(backend)
    dense_out = stats
    sharded_out = stats.reshape(n, k, s, v // s)
    for t in range(sched.n_rounds):
        dense_out = cx.mix_matching(dense_out, sched.data[t])
        sharded_out = cx.mix_matching(sharded_out, sched.data[t])
    np.testing.assert_allclose(
        np.asarray(sharded_out).reshape(n, k, v), np.asarray(dense_out),
        atol=1e-7)


def test_sharded_mix_edge_matches_dense():
    n, k, v, s = 6, 3, 24, 3
    stats = jax.random.normal(jax.random.key(7), (n, k, v))
    for backend in ["dense", "pallas"]:
        cx = comm.get_communicator(backend)
        dense_out = np.asarray(cx.mix_edge(stats, 1, 4))
        sharded = cx.mix_edge(stats.reshape(n, k, s, v // s), 1, 4)
        np.testing.assert_allclose(np.asarray(sharded).reshape(n, k, v),
                                   dense_out, atol=1e-7)


def test_sharded_bytes_per_round_accounting():
    n, k, v = 8, 4, 64
    p = gossip.ring_matchings(n)[0]
    itemsize = 4
    dense = comm.DenseSimComm().bytes_per_round((n, k, v), itemsize, p)
    sharded = comm.DenseSimComm().bytes_per_round((n, k, 4, v // 4),
                                                  itemsize, p)
    assert sharded == dense            # same wire total, spread over shards


# ---------------------------------------------------------------------------
# run_deleda with a sharded carry == the dense oracle
# ---------------------------------------------------------------------------

def _run(corpus, *, vocab_shards=1, comm_backend="dense",
         estep_backend="dense", kind="matching", mode="async"):
    g = watts_strogatz_graph(8, 4, 0.3, seed=0)
    sched, degs = deleda.make_run_inputs(g, 20, seed=0, kind=kind)
    cfg = deleda.DeledaConfig(lda=CFG, mode=mode, batch_size=4,
                              comm_backend=comm_backend,
                              estep_backend=estep_backend,
                              vocab_shards=vocab_shards)
    return deleda.run_deleda(cfg, jax.random.key(1), corpus.words,
                             corpus.mask, sched, degs, 20, record_every=10)


@pytest.mark.parametrize("cb", comm.SIM_BACKENDS)
@pytest.mark.parametrize("eb", estep.ESTEP_BACKENDS)
def test_run_deleda_sharded_matches_dense_oracle(corpus, cb, eb):
    """The acceptance property, across all comm x estep backend combos:
    vocab_shards only re-lays-out the carry. (Tolerance is a few ulps:
    the blocked denominator reduce may re-associate across shards.)"""
    ref = _run(corpus, vocab_shards=1, comm_backend=cb, estep_backend=eb)
    out = _run(corpus, vocab_shards=5, comm_backend=cb, estep_backend=eb)
    np.testing.assert_array_equal(np.asarray(ref.steps),
                                  np.asarray(out.steps))
    assert out.stats.shape == ref.stats.shape      # trace is densely shaped
    assert out.history.shape == ref.history.shape
    np.testing.assert_allclose(np.asarray(out.stats),
                               np.asarray(ref.stats), atol=1e-5)
    np.testing.assert_allclose(np.asarray(out.consensus),
                               np.asarray(ref.consensus), rtol=1e-4)


@pytest.mark.parametrize("kind,mode", [("edge", "async"), ("edge", "sync"),
                                       ("matching", "sync")])
def test_run_deleda_sharded_matches_dense_modes(corpus, kind, mode):
    ref = _run(corpus, vocab_shards=1, kind=kind, mode=mode)
    out = _run(corpus, vocab_shards=4, kind=kind, mode=mode)
    np.testing.assert_array_equal(np.asarray(ref.steps),
                                  np.asarray(out.steps))
    np.testing.assert_allclose(np.asarray(out.stats),
                               np.asarray(ref.stats), atol=1e-5)


def test_vocab_shards_validation():
    with pytest.raises(ValueError):
        deleda.DeledaConfig(lda=CFG, vocab_shards=0)
    with pytest.raises(ValueError):   # 7 does not divide V=40
        deleda.DeledaConfig(lda=CFG, vocab_shards=7)


# ---------------------------------------------------------------------------
# Bugfix batch regressions
# ---------------------------------------------------------------------------

def test_run_deleda_accepts_legacy_prng_keys(corpus):
    """deleda.py used to reshape split keys as [n_rec, record_every], which
    crashes on legacy PRNGKey arrays (split -> [T, 2]). Both flavors must
    run AND agree bitwise (same threefry stream under the hood)."""
    g = complete_graph(8)
    sched, degs = deleda.make_run_inputs(g, 20, seed=0, kind="matching")
    cfg = deleda.DeledaConfig(lda=CFG, mode="async", batch_size=4)
    typed = deleda.run_deleda(cfg, jax.random.key(1), corpus.words,
                              corpus.mask, sched, degs, 20,
                              record_every=10)
    legacy = deleda.run_deleda(cfg, jax.random.PRNGKey(1), corpus.words,
                               corpus.mask, sched, degs, 20,
                               record_every=10)
    np.testing.assert_array_equal(np.asarray(typed.steps),
                                  np.asarray(legacy.steps))
    np.testing.assert_array_equal(np.asarray(typed.stats),
                                  np.asarray(legacy.stats))


def test_left_to_right_accepts_legacy_prng_keys(corpus):
    beta = eta_star(jax.random.uniform(
        jax.random.key(8), (CFG.n_topics, CFG.vocab_size)))
    typed = left_to_right_log_likelihood(
        jax.random.key(3), corpus.test_words, corpus.test_mask, beta,
        CFG.alpha, n_particles=4)
    legacy = left_to_right_log_likelihood(
        jax.random.PRNGKey(3), corpus.test_words, corpus.test_mask, beta,
        CFG.alpha, n_particles=4)
    np.testing.assert_array_equal(np.asarray(typed), np.asarray(legacy))
    assert np.isfinite(np.asarray(typed)).all()


def test_ring_two_nodes_pairs_on_both_rounds():
    """ring_matchings(2) used to emit an identity odd round — half of every
    ring(2) round budget was a silent no-op."""
    r = gossip.ring_matchings(2)
    np.testing.assert_array_equal(r, [[1, 0], [1, 0]])
    sched = comm.GossipSchedule.ring(2, n_rounds=4)
    assert (sched.data != np.arange(2)).all()     # every round mixes
    # two nodes reach exact consensus after ONE ring(2) round
    stats = jnp.asarray([[1.0, 3.0], [5.0, 7.0]])
    mixed = comm.DenseSimComm().mix_matching(stats, sched.data[1])
    np.testing.assert_allclose(np.asarray(mixed),
                               [[3.0, 5.0], [3.0, 5.0]])


def test_ring_larger_n_unchanged():
    r4 = gossip.ring_matchings(4)
    np.testing.assert_array_equal(r4[0], [1, 0, 3, 2])
    np.testing.assert_array_equal(r4[1], [3, 2, 1, 0])   # ring closed
    r5 = gossip.ring_matchings(5)
    np.testing.assert_array_equal(r5[1], [0, 2, 1, 4, 3])  # odd n: 0 idles


def test_beta_distance_near_collinear_topics():
    """The old explicit Gram inverse (1e-10 ridge, float32) blows up when
    two topic rows are near-duplicates; the lstsq formulation keeps the
    minimum residual well-defined."""
    key = jax.random.key(9)
    beta = jax.random.uniform(key, (4, 30)) + 1e-3
    beta = beta / beta.sum(-1, keepdims=True)
    # make rows 0 and 1 differ by ~1 ulp: the Gram matrix is singular in
    # float32 but the subspace (and thus the distance) is fine
    beta = beta.at[1].set(beta[0] * (1.0 + 1e-7))
    d_self = float(beta_distance(beta, beta))
    assert np.isfinite(d_self) and d_self < 1e-3
    perm = beta[jnp.asarray([2, 0, 3, 1])]
    d_perm = float(beta_distance(perm, beta))
    assert np.isfinite(d_perm) and d_perm < 1e-3
    # still discriminates genuinely different topic matrices
    other = eta_star(jax.random.uniform(jax.random.key(10), (4, 30)))
    assert float(beta_distance(beta, other)) > 0.05


def test_stats_from_per_pos_padded_batch_unbiased():
    """A batch padded with empty (all-masked) documents must produce the
    same per-document-mean statistic as the unpadded batch."""
    b, l, k, v = 5, 8, 3, 20
    words = jax.random.randint(jax.random.key(11), (b, l), 0, v)
    mask = jnp.ones((b, l), bool)
    per_pos = jax.random.uniform(jax.random.key(12), (b, l, k))
    ref = estep.stats_from_per_pos(words, per_pos,
                                   v, mask.astype(per_pos.dtype))
    pad_words = jnp.concatenate([words, jnp.zeros((3, l), jnp.int32)])
    pad_mask = jnp.concatenate([mask, jnp.zeros((3, l), bool)])
    pad_pp = jnp.concatenate([per_pos, jnp.zeros((3, l, k))])
    padded = estep.stats_from_per_pos(pad_words, pad_pp, v,
                                      pad_mask.astype(per_pos.dtype))
    np.testing.assert_allclose(np.asarray(padded), np.asarray(ref),
                               rtol=1e-6)
    # all-empty batch is guarded (no division by zero)
    empty = estep.stats_from_per_pos(
        pad_words[5:], pad_pp[:3] * 0.0, v,
        pad_mask[5:].astype(per_pos.dtype))
    assert np.isfinite(np.asarray(empty)).all()


def test_estep_call_padded_batch_matches_unpadded(doc_len=12):
    """End-to-end through the E-step: padding a document batch with empty
    docs changes nothing (the old /b normalization biased stats low)."""
    words = jax.random.randint(jax.random.key(13), (6, doc_len), 0,
                               CFG.vocab_size)
    mask = jnp.ones((6, doc_len), bool).at[:, -2:].set(False)
    beta = eta_star(jax.random.uniform(jax.random.key(14),
                                       (CFG.n_topics, CFG.vocab_size)))
    backend = estep.get_estep("dense")
    key = jax.random.key(15)
    ref = backend(CFG, key, words, mask, beta).stats
    # NOTE: padding changes the sweep batch, so use the same per-doc PRNG
    # stream by comparing against scatter-normalization only: scatter the
    # reference per-position stats into a padded batch by hand
    pad_words = jnp.concatenate([words, jnp.zeros((2, doc_len),
                                                  jnp.int32)])
    pad_mask = jnp.concatenate([mask, jnp.zeros((2, doc_len), bool)])
    uniforms, z0 = estep.draw_gibbs_randoms(CFG, key, 6, doc_len,
                                            beta.dtype)
    beta_w = jnp.take(beta.T, words, axis=0)
    maskf = mask.astype(beta.dtype)
    per_pos, _, _ = backend.sweeps(beta_w, maskf, uniforms, z0,
                                   alpha=CFG.alpha, n_sweeps=CFG.n_gibbs,
                                   burnin=CFG.n_gibbs_burnin)
    pad_pp = jnp.concatenate([per_pos,
                              jnp.zeros((2, doc_len, CFG.n_topics))])
    padded = estep.stats_from_per_pos(
        pad_words, pad_pp, CFG.vocab_size,
        pad_mask.astype(beta.dtype))
    np.testing.assert_allclose(np.asarray(padded), np.asarray(ref),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# Node x vocab mesh grid (subprocess: needs XLA_FLAGS before jax init)
# ---------------------------------------------------------------------------

GRID_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    from jax.sharding import Mesh
    from repro.core import deleda
    from repro.core.graph import complete_graph
    from repro.core.lda import LDAConfig
    from repro.data.lda_synthetic import CorpusSpec, make_corpus

    # -- run_deleda on the node x vocab grid == 1-D node mesh
    lda = LDAConfig(n_topics=3, vocab_size=24, alpha=0.5, doc_len_max=8,
                    n_gibbs=4, n_gibbs_burnin=2)
    corpus = make_corpus(lda, jax.random.key(0),
                         CorpusSpec(n_nodes=8, docs_per_node=4, n_test=4))
    sched, degs = deleda.make_run_inputs(complete_graph(8), 6, seed=0,
                                         kind="matching")
    devices = np.asarray(jax.devices())
    runs = {}
    for name, mesh in (("flat", Mesh(devices[:4], ("data",))),
                       ("grid", Mesh(devices.reshape(4, 2),
                                     ("data", "vocab")))):
        cfg = deleda.DeledaConfig(lda=lda, mode="sync", batch_size=2,
                                  comm_backend="mesh", mesh=mesh)
        runs[name] = deleda.run_deleda(cfg, jax.random.key(0), corpus.words,
                                       corpus.mask, sched, degs, 6,
                                       record_every=2)
    grid = runs["grid"]
    # each device holds its own [2, K, 12] block of nodes and vocabulary
    assert grid.state.stats.sharding.spec == ("data", None, "vocab"), \
        grid.state.stats.sharding.spec
    assert {s.data.shape for s in grid.state.stats.addressable_shards} \
        == {(2, 3, 12)}
    err = np.abs(np.asarray(runs["flat"].stats)
                 - np.asarray(grid.stats)).max()
    assert err < 1e-5, err
    np.testing.assert_allclose(runs["flat"].consensus, grid.consensus,
                               rtol=1e-4)
    print("SCALE_GRID_OK")
""")


@pytest.mark.slow
def test_mesh_grid_matches_flat_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    r = subprocess.run([sys.executable, "-c", GRID_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "SCALE_GRID_OK" in r.stdout, r.stderr[-2000:]
