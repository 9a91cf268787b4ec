"""Unified E-step layer: registry, backend equivalence, fused batch path.

The contract under test (the compute-side twin of tests/test_comm.py):
DenseEStep (pure-jnp shared sweep core) and PallasEStep (lda_gibbs kernel,
interpret mode off-TPU) implement the SAME E-step for the same PRNG stream,
and the fused multi-node batch path (`estep_batch`) is bit-identical to
vmapping the single-node E-step with the same fold_in key streams.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import deleda, estep
from repro.core import gibbs as core_gibbs
from repro.core.graph import complete_graph
from repro.core.lda import LDAConfig, eta_star
from repro.core.oem import run_oem
from repro.data.lda_synthetic import CorpusSpec, make_corpus

CFG = LDAConfig(n_topics=4, vocab_size=40, alpha=0.5, doc_len_max=16,
                n_gibbs=6, n_gibbs_burnin=3)


@pytest.fixture(scope="module")
def doc_batch():
    words = jax.random.randint(jax.random.key(1), (10, 16), 0,
                               CFG.vocab_size)
    mask = jax.random.uniform(jax.random.key(2), (10, 16)) < 0.9
    beta = eta_star(jax.random.uniform(jax.random.key(3),
                                       (CFG.n_topics, CFG.vocab_size)))
    return words, mask, beta


@pytest.fixture(scope="module")
def node_batch():
    """Per-node inputs for the fused path: [A, B, L] docs, [A, K, V] betas."""
    a, b = 5, 4
    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.key(9), i))(
        jnp.arange(a))
    words = jax.random.randint(jax.random.key(4), (a, b, 16), 0,
                               CFG.vocab_size)
    mask = jax.random.uniform(jax.random.key(5), (a, b, 16)) < 0.9
    beta = eta_star(jax.random.uniform(jax.random.key(6),
                                       (a, CFG.n_topics, CFG.vocab_size)))
    return keys, words, mask, beta


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_registry_and_validation():
    assert estep.get_estep("dense").name == "dense"
    assert estep.get_estep("pallas").name == "pallas"
    assert estep.ESTEP_BACKENDS == ("dense", "pallas")
    with pytest.raises(ValueError):
        estep.get_estep("carrier-pigeon")
    with pytest.raises(ValueError):
        deleda.DeledaConfig(lda=CFG, estep_backend="carrier-pigeon")


def test_use_pallas_is_deprecated_alias():
    with pytest.warns(DeprecationWarning):
        # lint: allow(use-pallas-alias) — the deprecation test itself
        cfg = deleda.DeledaConfig(lda=CFG, use_pallas=True)
    assert cfg.estep_backend == "pallas"
    with pytest.warns(DeprecationWarning):
        # lint: allow(use-pallas-alias)
        cfg = deleda.DeledaConfig(lda=CFG, use_pallas=True,
                                  estep_backend="pallas")
    assert cfg.estep_backend == "pallas"


def test_interpret_autodetect_shared():
    from repro.kernels.common import resolve_interpret
    from repro.kernels.gossip_mix import ops as gossip_ops
    assert gossip_ops.resolve_interpret is resolve_interpret
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    assert resolve_interpret(None) is (jax.default_backend() != "tpu")


# ---------------------------------------------------------------------------
# Backend equivalence (single-node E-step)
# ---------------------------------------------------------------------------

def test_gibbs_estep_wrapper_and_legacy_trajectory(doc_batch):
    """core.gibbs.gibbs_estep is plumbing over the dense backend (same jit
    path, same defaults), and the dense backend reproduces the pinned
    values below on this exact input."""
    words, mask, beta = doc_batch
    key = jax.random.key(7)
    r_api = core_gibbs.gibbs_estep(CFG, key, words, mask, beta)
    r_backend = jax.jit(
        lambda k, w, m, b: estep.get_estep("dense")(CFG, k, w, m, b))(
            key, words, mask, beta)
    for name in r_api._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(r_api, name)),
            np.asarray(getattr(r_backend, name)), err_msg=name)
    # trajectory pin (catches semantic drift in the shared core), taken
    # under jax's partitionable threefry streams
    np.testing.assert_allclose(float(r_api.stats.sum()), 14.7000008,
                               atol=1e-5)
    np.testing.assert_allclose(float(r_api.stats[0, 7]), 0.07494333,
                               atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(r_api.theta[3]),
        [0.19444445, 0.34259260, 0.02777778, 0.43518519], atol=1e-6)
    assert int(np.asarray(r_api.z).sum()) == 250
    assert float(r_api.n_dk.sum()) == 147.0


@pytest.mark.parametrize("rao_blackwell", [True, False])
def test_pallas_backend_matches_dense(doc_batch, rao_blackwell):
    """Same draws as the dense backend; the kernel is Rao-Blackwellized
    only, so it refuses the non-RB E-step rather than swap in jnp code."""
    words, mask, beta = doc_batch
    key = jax.random.key(8)
    pallas = estep.get_estep("pallas")
    if not rao_blackwell:
        with pytest.raises(ValueError, match="Rao-Blackwell"):
            pallas(CFG, key, words, mask, beta, rao_blackwell=False)
        return
    r_d = estep.get_estep("dense")(CFG, key, words, mask, beta)
    r_p = pallas(CFG, key, words, mask, beta)
    np.testing.assert_array_equal(np.asarray(r_p.z), np.asarray(r_d.z))
    for name in ("stats", "n_dk", "theta"):
        np.testing.assert_allclose(
            np.asarray(getattr(r_p, name)), np.asarray(getattr(r_d, name)),
            atol=1e-6, err_msg=name)


def test_pallas_non_rao_blackwell_falls_back_with_warning(doc_batch):
    """Neither kernel backend falls back to the jnp sweeps in silence:
    both raise for ``rao_blackwell=False``."""
    words, mask, beta = doc_batch
    with pytest.raises(ValueError, match="Rao-Blackwell"):
        estep.PallasEStep()(CFG, jax.random.key(0), words, mask, beta,
                            rao_blackwell=False)
    uw, counts = estep.unique_view(words, mask)
    with pytest.raises(ValueError, match="Rao-Blackwell"):
        estep.PallasSparseEStep()(CFG, jax.random.key(0), uw, counts, beta,
                                  rao_blackwell=False)


@pytest.mark.parametrize("backend", estep.ESTEP_BACKENDS)
def test_mesh_update_step_traces(backend):
    """The node-sharded ``train_steps`` traces under shard_map's varying-
    axes check with either E-step backend: its scan carries and kernel
    outputs vary over the node axis like its inputs."""
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("nodes",))
    cfg = deleda.DeledaConfig(lda=CFG, mode="sync", batch_size=2,
                              comm_backend="mesh", mesh=mesh,
                              estep_backend=backend)
    n, t = 4, 2
    state = deleda.init_state(cfg, jax.random.key(0), n)
    words = jnp.zeros((n, 4, 16), jnp.int32)
    jaxpr = str(deleda.train_steps.trace(
        cfg, state, words, words >= 0,
        jnp.tile(jnp.asarray([1, 0, 3, 2], jnp.int32), (t, 1)),
        jnp.ones((t, n), jnp.float32), jnp.ones((t, n), bool),
        record_every=t).jaxpr)
    assert ("pallas_call" in jaxpr) == (backend == "pallas")


# ---------------------------------------------------------------------------
# Fused batch path
# ---------------------------------------------------------------------------

def test_fused_batch_bit_identical_to_per_node_vmap(node_batch):
    """The acceptance property: gathering all awake nodes into ONE [A*B, L]
    sweep call changes nothing — same fold_in streams, same bits."""
    keys, words, mask, beta = node_batch
    backend = estep.get_estep("dense")
    fused = estep.estep_batch(backend, CFG, keys, words, mask, beta)
    per_node = jax.vmap(
        lambda k, w, m, b: backend(CFG, k, w, m, b).stats)(
            keys, words, mask, beta)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(per_node))


def test_fused_batch_pallas_matches_dense(node_batch):
    keys, words, mask, beta = node_batch
    fused_d = estep.estep_batch(estep.get_estep("dense"), CFG, keys, words,
                                mask, beta)
    fused_p = estep.estep_batch(estep.get_estep("pallas"), CFG, keys,
                                words, mask, beta)
    np.testing.assert_allclose(np.asarray(fused_p), np.asarray(fused_d),
                               atol=1e-6)


def test_fused_batch_independent_of_batch_mates(node_batch):
    """A node's statistics depend only on its own key/docs/beta — not on
    which (or how many) nodes share the fused batch."""
    keys, words, mask, beta = node_batch
    backend = estep.get_estep("dense")
    full = estep.estep_batch(backend, CFG, keys, words, mask, beta)
    pair = estep.estep_batch(backend, CFG, keys[1:3], words[1:3],
                             mask[1:3], beta[1:3])
    np.testing.assert_array_equal(np.asarray(full[1:3]), np.asarray(pair))


SCATTER_CASES = {      # case -> (nodes, vocabulary size)
    "v1000": (8, 1000),
    "v1003": (8, 1003),
    "v1024": (8, 1024),
    "v1003-two-nodes": (2, 1003),       # the asynchronous mode's batch
    "empty-docs": (8, 1003),
    "unique": (8, 1003),
    "vocab-sharded": (8, 1002),
}


@pytest.mark.parametrize("case", list(SCATTER_CASES))
def test_stats_per_node_bitwise_matches_vmap(case):
    """The node-batched scatter, built in a lane-padded buffer, gives the
    bits of vmapping the single-node scatter: the same updates summed in
    the same order, then the same division."""
    a, v = SCATTER_CASES[case]
    b, l, k = 4, 16, CFG.n_topics
    kw, kc, kp, km = jax.random.split(jax.random.key(21), 4)
    # half the positions share the last five words: repeated columns at
    # the padded edge, summed in update order
    words = jnp.where(jax.random.bernoulli(kc, 0.5, (a, b, l)),
                      jax.random.randint(kw, (a, b, l), v - 5, v),
                      jax.random.randint(kw, (a, b, l), 0, v))
    mask = jax.random.uniform(km, (a, b, l)) < 0.8
    if case == "empty-docs":
        mask = mask.at[:, -2:].set(False).at[0].set(False)
    maskf = mask.astype(jnp.float32)
    per_pos = jax.random.uniform(kp, (a, b, l, k)) * maskf[..., None]

    def ref(w, p, m):
        return jax.vmap(
            lambda ww, pp, mm: estep.stats_from_per_pos(ww, pp, v, mm))(
                w, p, m)

    def new(w, p, m):
        return estep.stats_per_node(w, p, v, m)

    if case == "unique":
        uw, counts = estep.dense_to_unique(words, mask)
        countf = counts.astype(jnp.float32)
        words, per_pos, maskf = uw, per_pos * countf[..., None], countf
    if case == "vocab-sharded":
        # through the E-step entry the round calls, carrying [A, K, 2, V/2]
        cfg = dataclasses.replace(CFG, vocab_size=v)
        stats = jax.random.uniform(jax.random.key(22), (a, k, 2, v // 2))
        keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.key(23),
                                                     i))(jnp.arange(a))
        backend = estep.get_estep("dense")

        def ref_e(st, w, mk):
            beta_w = jax.vmap(lambda s_, w_: estep.beta_w_from_stats(
                s_, w_, cfg.tau))(st, w)
            mf = mk.astype(beta_w.dtype)
            pp = estep.fused_sweeps(backend, cfg, keys, beta_w, mf)
            return ref(w, pp, mf).reshape(st.shape)

        def new_e(st, w, mk):
            return estep.estep_batch_from_stats(
                backend, cfg, keys, w, mk, st).reshape(st.shape)

        got = jax.jit(new_e)(stats, words, mask)
        want = jax.jit(ref_e)(stats, words, mask)
    else:
        got = jax.jit(new)(words, per_pos, maskf)
        want = jax.jit(ref)(words, per_pos, maskf)
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# run_deleda / run_oem through the layer
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def corpus():
    return make_corpus(CFG, jax.random.key(0),
                       CorpusSpec(n_nodes=8, docs_per_node=8, n_test=10))


def test_run_deleda_estep_backends_agree(corpus):
    g = complete_graph(8)
    sched, degs = deleda.make_run_inputs(g, 10, seed=1, kind="matching")
    traces = {}
    for backend in estep.ESTEP_BACKENDS:
        cfg = deleda.DeledaConfig(lda=CFG, mode="async", batch_size=4,
                                  estep_backend=backend)
        traces[backend] = deleda.run_deleda(
            cfg, jax.random.key(2), corpus.words, corpus.mask, sched, degs,
            10, record_every=10)
    np.testing.assert_array_equal(np.asarray(traces["dense"].steps),
                                  np.asarray(traces["pallas"].steps))
    np.testing.assert_allclose(np.asarray(traces["dense"].stats),
                               np.asarray(traces["pallas"].stats),
                               atol=1e-5)


def test_run_oem_estep_backends_agree(corpus):
    traces = {}
    for backend in estep.ESTEP_BACKENDS:
        traces[backend] = run_oem(CFG, jax.random.key(3),
                                  corpus.flat_words, corpus.flat_mask,
                                  n_steps=10, batch_size=6,
                                  record_every=10, estep_backend=backend)
    np.testing.assert_allclose(np.asarray(traces["dense"].state.stats),
                               np.asarray(traces["pallas"].state.stats),
                               atol=1e-5)
