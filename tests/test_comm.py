"""Unified communicator layer: schedules, backend equivalence, routing.

The contract under test: DenseSimComm (pure-jnp oracle), PallasSimComm
(gossip_mix kernel, interpret mode off-TPU) and MeshComm (ppermute routing
over a device mesh) implement the SAME averaging map for the same matching
schedule, and run_deleda replays an edge schedule identically through its
one-pair-per-round matching view.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import comm, deleda, gossip
from repro.core.graph import complete_graph, watts_strogatz_graph
from repro.core.lda import LDAConfig
from repro.data.lda_synthetic import CorpusSpec, make_corpus


# ---------------------------------------------------------------------------
# GossipSchedule
# ---------------------------------------------------------------------------

def test_schedule_constructors_and_validation():
    g = watts_strogatz_graph(10, 4, 0.3, seed=0)
    rng = np.random.default_rng(0)
    es = comm.GossipSchedule.draw_edges(g, 12, rng)
    assert es.kind == comm.EDGE and es.data.shape == (12, 2)
    ms = comm.GossipSchedule.draw_matchings(g, 6, rng)
    assert ms.kind == comm.MATCHING and ms.data.shape == (6, 10)
    hc = comm.GossipSchedule.hypercube(8)
    assert hc.data.shape == (3, 8)
    ring = comm.GossipSchedule.ring(6, n_rounds=5)
    assert ring.data.shape == (5, 6)
    np.testing.assert_array_equal(ring.data[0], ring.data[2])  # tiles e/o

    with pytest.raises(ValueError):
        comm.GossipSchedule("matching", np.zeros((3, 4), np.int32), 5)
    with pytest.raises(ValueError):   # not an involution
        comm.GossipSchedule("matching", np.array([[1, 2, 0]]), 3)
    with pytest.raises(ValueError):
        comm.GossipSchedule("carrier-pigeon", np.zeros((1, 2)), 4)


def test_edge_schedule_as_matchings_applies_same_w():
    g = complete_graph(7)
    es = comm.GossipSchedule.draw_edges(g, 9, np.random.default_rng(1))
    ms = es.as_matchings()
    assert ms.data.shape == (9, 7)
    stats = jax.random.normal(jax.random.key(0), (7, 3, 5))
    s_e, s_m = stats, stats
    dense = comm.DenseSimComm()
    for t in range(9):
        s_e = dense.mix_edge(s_e, int(es.data[t, 0]), int(es.data[t, 1]))
        s_m = dense.mix_matching(s_m, ms.data[t])
    np.testing.assert_array_equal(np.asarray(s_e), np.asarray(s_m))


# ---------------------------------------------------------------------------
# Backend equivalence (single process; the mesh here is whatever devices
# exist — cross-device ppermute routing is covered by the subprocess test)
# ---------------------------------------------------------------------------

BACKENDS = ["dense", "pallas", "mesh"]


def _mix_trajectory(backend, stats, schedule):
    c = comm.get_communicator(backend)
    for t in range(schedule.n_rounds):
        stats = c.mix_matching(stats, schedule.data[t])
    return np.asarray(stats)


@pytest.mark.parametrize("backend", BACKENDS[1:])
def test_backends_match_dense_oracle(backend):
    g = watts_strogatz_graph(12, 4, 0.3, seed=0)
    sched = comm.GossipSchedule.draw_matchings(g, 6,
                                               np.random.default_rng(2))
    stats = jax.random.uniform(jax.random.key(3), (12, 5, 96))
    ref = _mix_trajectory("dense", stats, sched)
    out = _mix_trajectory(backend, stats, sched)
    np.testing.assert_allclose(out, ref, atol=1e-6)


def test_backends_preserve_mean_and_contract():
    g = complete_graph(8)
    sched = comm.GossipSchedule.draw_matchings(g, 8,
                                               np.random.default_rng(4))
    stats = jax.random.normal(jax.random.key(5), (8, 4, 64))
    d0 = float(gossip.consensus_distance(stats))
    for backend in BACKENDS:
        out = _mix_trajectory(backend, stats, sched)
        np.testing.assert_allclose(out.mean(0), np.asarray(stats).mean(0),
                                   atol=1e-5)
        assert float(gossip.consensus_distance(jnp.asarray(out))) < d0


def test_mix_edge_equivalent_across_backends():
    stats = jax.random.normal(jax.random.key(6), (6, 3, 32))
    ref = np.asarray(comm.DenseSimComm().mix_edge(stats, 1, 4))
    for backend in BACKENDS[1:]:
        out = np.asarray(comm.get_communicator(backend).mix_edge(stats, 1,
                                                                 4))
        np.testing.assert_allclose(out, ref, atol=1e-6)


def test_bytes_model_sane():
    n, k, v = 8, 4, 64
    p = gossip.ring_matchings(n)[0]          # full matching: 4 pairs
    shape, itemsize = (n, k, v), 4
    pair_block = k * v * itemsize
    dense = comm.DenseSimComm().bytes_per_round(shape, itemsize, p)
    assert dense == 8 * pair_block           # every matched node sends once
    mesh = comm.MeshComm()
    got = mesh.bytes_per_round(shape, itemsize, p)
    if mesh.n_devices == 1:
        assert got == 0                      # all pairs intra-device
    idle = np.arange(n, dtype=np.int32)
    assert comm.DenseSimComm().bytes_per_round(shape, itemsize, idle) == 0


def test_interpret_autodetect():
    from repro.kernels.gossip_mix import ops
    assert ops.resolve_interpret(True) is True
    assert ops.resolve_interpret(False) is False
    expected = jax.default_backend() != "tpu"
    assert ops.resolve_interpret(None) is expected


# ---------------------------------------------------------------------------
# Matching-round routing decomposition
# ---------------------------------------------------------------------------

def test_route_matching_single_node_per_device_is_one_pass():
    p = np.array([1, 0, 3, 2, 5, 4, 7, 6], np.int32)
    (intra_src, intra_active), passes = comm._route_matching(p, 8)
    assert not intra_active.any()
    assert len(passes) == 1                  # ONE bidirectional ppermute
    perm, remote_src, active = passes[0]
    assert sorted(perm) == [(0, 1), (1, 0), (2, 3), (3, 2), (4, 5), (5, 4),
                            (6, 7), (7, 6)]
    assert active.all()
    np.testing.assert_array_equal(remote_src, np.zeros(8, np.int32))


def test_route_matching_mixed_intra_cross():
    # 8 nodes on 4 devices (2 per device): (0,1) intra; (2,4),(3,6) cross
    p = np.array([1, 0, 4, 6, 2, 5, 3, 7], np.int32)
    (intra_src, intra_active), passes = comm._route_matching(p, 4)
    assert intra_active[0] and intra_active[1] and not intra_active[2:].any()
    assert intra_src[0] == 1 and intra_src[1] == 0
    # devices 1<->2 and 1<->3 conflict on device 1 -> two passes
    assert len(passes) == 2
    for perm, remote_src, active in passes:
        devs = [a for a, _ in perm]
        assert len(devs) == len(set(devs))   # each pass is a device matching


def test_route_matching_rejects_indivisible():
    with pytest.raises(ValueError):
        comm._route_matching(np.arange(6, dtype=np.int32), 4)


# ---------------------------------------------------------------------------
# run_deleda: matching schedule == sequential edge oracle
# ---------------------------------------------------------------------------

CFG = LDAConfig(n_topics=4, vocab_size=40, alpha=0.5, doc_len_max=16,
                n_gibbs=6, n_gibbs_burnin=3)


@pytest.fixture(scope="module")
def corpus():
    return make_corpus(CFG, jax.random.key(0),
                       CorpusSpec(n_nodes=8, docs_per_node=8, n_test=10))


@pytest.mark.parametrize("mode", ["async", "sync"])
def test_run_deleda_matching_matches_edge_oracle(corpus, mode):
    """A 1-matching-per-round schedule (each round = the activated pair)
    replays the sequential-edge oracle: same mixing map, same per-node
    PRNG streams, same step counters."""
    g = complete_graph(8)
    edges, degs = deleda.make_run_inputs(g, 20, seed=0)
    msched = comm.GossipSchedule(
        comm.EDGE, np.asarray(edges), 8).as_matchings()
    cfg = deleda.DeledaConfig(lda=CFG, mode=mode, batch_size=4)
    tr_e = deleda.run_deleda(cfg, jax.random.key(0), corpus.words,
                             corpus.mask, edges, degs, 20, record_every=10)
    tr_m = deleda.run_deleda(cfg, jax.random.key(0), corpus.words,
                             corpus.mask, jnp.asarray(msched.data), degs,
                             20, record_every=10)
    np.testing.assert_array_equal(np.asarray(tr_e.steps),
                                  np.asarray(tr_m.steps))
    np.testing.assert_allclose(np.asarray(tr_e.stats),
                               np.asarray(tr_m.stats), atol=1e-5)
    np.testing.assert_allclose(np.asarray(tr_e.history),
                               np.asarray(tr_m.history), atol=1e-5)


def test_run_deleda_comm_backends_agree(corpus):
    g = complete_graph(8)
    sched, degs = deleda.make_run_inputs(g, 10, seed=1, kind="matching")
    traces = {}
    for backend in comm.SIM_BACKENDS:
        cfg = deleda.DeledaConfig(lda=CFG, mode="sync", batch_size=4,
                                  comm_backend=backend)
        traces[backend] = deleda.run_deleda(
            cfg, jax.random.key(2), corpus.words, corpus.mask, sched, degs,
            10, record_every=10)
    np.testing.assert_allclose(np.asarray(traces["dense"].stats),
                               np.asarray(traces["pallas"].stats),
                               atol=1e-5)


def test_run_deleda_async_matching_counts_matched_nodes(corpus):
    g = watts_strogatz_graph(8, 4, 0.3, seed=2)
    sched, degs = deleda.make_run_inputs(g, 10, seed=3, kind="matching")
    cfg = deleda.DeledaConfig(lda=CFG, mode="async", batch_size=4)
    trace = deleda.run_deleda(cfg, jax.random.key(4), corpus.words,
                              corpus.mask, sched, degs, 10,
                              record_every=10)
    awake = int((np.asarray(sched) != np.arange(8)).sum())
    assert int(trace.steps.sum()) == awake


@pytest.mark.parametrize("n_dev", [1, 2, 3, 4, 6, 8])
def test_device_passes_are_a_one_factorization(n_dev):
    passes = comm.device_passes(n_dev)
    odd = n_dev % 2 and n_dev > 1          # one device rests each pass
    assert len(passes) == (n_dev if odd else n_dev - 1)
    met = []
    for perm in passes:
        peer = dict(perm)
        assert all(peer[b] == a for a, b in perm)       # pairs, both ways
        assert len(peer) == len(perm)                   # disjoint
        met += [(a, b) for a, b in perm if a < b]
    pairs = [(a, b) for a in range(n_dev) for b in range(a + 1, n_dev)]
    assert sorted(met) == pairs                         # each pair once
    if n_dev == 4:
        assert [sorted((a, b) for a, b in p if a < b) for p in passes] == [
            [(0, 1), (2, 3)], [(0, 2), (1, 3)], [(0, 3), (1, 2)]]


def test_run_deleda_passes_the_mesh_through():
    """run_deleda on a one-device mesh: shard_map, a record reduced over
    the node axis, no ppermute pass; the dense run's trajectory."""
    n, t = 4, 8
    rng = np.random.default_rng(2)
    words = jnp.asarray(rng.integers(0, CFG.vocab_size, (n, 6, 16)),
                        jnp.int32)
    mask = jnp.asarray(rng.random((n, 6, 16)) < 0.7)
    sched, degs = deleda.make_run_inputs(complete_graph(n), t, seed=3,
                                         kind="matching")
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("nodes",))
    runs = [deleda.run_deleda(
        deleda.DeledaConfig(lda=CFG, mode="sync", batch_size=2, **kw),
        jax.random.key(5), words, mask, sched, degs, t, record_every=4)
        for kw in ({}, dict(comm_backend="mesh", mesh=mesh))]
    np.testing.assert_allclose(runs[1].stats, runs[0].stats, rtol=1e-6,
                               atol=1e-7)
    np.testing.assert_allclose(runs[1].consensus, runs[0].consensus,
                               rtol=1e-6)
    np.testing.assert_array_equal(runs[1].steps, runs[0].steps)


def test_deleda_config_rejects_mesh_backend():
    """What the mesh backend still refuses: no mesh to hold the nodes, a
    mesh without the backend, a 2-D mesh, and edge schedules."""
    with pytest.raises(ValueError):
        deleda.DeledaConfig(lda=CFG, comm_backend="mesh")
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("nodes",))
    with pytest.raises(ValueError):
        deleda.DeledaConfig(lda=CFG, mesh=mesh)
    with pytest.raises(ValueError):
        deleda.DeledaConfig(lda=CFG, comm_backend="mesh",
                            mesh=Mesh(np.asarray(jax.devices()[:1]).reshape(
                                1, 1), ("nodes", "vocab")))
    cfg = deleda.DeledaConfig(lda=CFG, mode="sync", batch_size=2,
                              comm_backend="mesh", mesh=mesh)
    n, t = 4, 2
    state = deleda.init_state(cfg, jax.random.key(0), n)
    words = jnp.zeros((n, 3, CFG.doc_len_max), jnp.int32)
    with pytest.raises(ValueError):
        deleda.train_steps(cfg, state, words, words > 0,
                           jnp.zeros((t, 2), jnp.int32),
                           jnp.ones((t, n), jnp.float32),
                           jnp.ones((t, n), bool), record_every=t,
                           kind="edge")
    with pytest.raises(ValueError):
        comm.get_communicator("carrier-pigeon")


# ---------------------------------------------------------------------------
# Cross-device MeshComm (subprocess: needs XLA_FLAGS before jax init).
# Asserts backend equivalence AND the acceptance property: the compiled
# gossip path is collective-permute only — no all-gather.
# ---------------------------------------------------------------------------

MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import comm
    from repro.core.graph import complete_graph

    for n in (8, 16):                       # 1 and 2 nodes per device
        g = complete_graph(n)
        sched = comm.GossipSchedule.draw_matchings(
            g, 5, np.random.default_rng(1))
        stats = jax.random.uniform(jax.random.key(0), (n, 4, 64))
        dense, mesh = comm.DenseSimComm(), comm.MeshComm()
        s_d, s_m = stats, stats
        for t in range(5):
            s_d = dense.mix_matching(s_d, sched.data[t])
            s_m = mesh.mix_matching(s_m, sched.data[t])
        err = float(jnp.abs(s_d - s_m).max())
        assert err < 1e-6, (n, err)

    # gossip-is-ppermute-only, via the one shared invariant implementation
    from repro.analysis import trace_audit as ta
    mesh = comm.MeshComm()
    p = np.array([1, 0, 3, 2, 5, 4, 7, 6], np.int32)
    _, passes = comm._route_matching(p, 8)
    perm, _, _ = passes[0]
    compiled = mesh._get_pass_fn(perm).lower(
        jax.ShapeDtypeStruct((8, 4, 64), jnp.float32),
        jax.ShapeDtypeStruct((8,), jnp.int32),
        jax.ShapeDtypeStruct((8,), bool)).compile()
    report = ta.audit_compiled(compiled, ta.InvariantSpec(
        "gossip_pass", allowed_collectives=ta.GOSSIP_ALLOWED,
        max_counts=(("collective-permute", 1),)))
    assert report.ok, report.summary()
    assert report.inventory == {"collective-permute": 1}, report.inventory

    # the round's named scopes reach the shard-mapped programs' HLO
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.lda import LDAConfig
    from repro.launch.gossip_sim import build_update_step
    lda = LDAConfig(n_topics=4, vocab_size=64, doc_len_max=8, n_gibbs=2,
                    n_gibbs_burnin=1)
    node = NamedSharding(mesh.mesh, P("data"))
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=node)
    step_text = build_update_step(lda, 2, mesh.mesh).lower(
        sds((8, 4, 64), jnp.float32), sds((8,), jnp.int32),
        jax.random.key(0), sds((8, 3, 8), jnp.int32),
        sds((8, 3, 8), jnp.bool_), sds((8,), jnp.bool_)).compile().as_text()
    for scope in ("deleda.estep", "estep.gather", "estep.sweeps",
                  "estep.scatter", "deleda.blend"):
        assert scope in step_text, scope
    assert "deleda.mix" in compiled.as_text()
    print("COMM_MESH_OK")
""")


@pytest.mark.slow
def test_mesh_comm_cross_device_matches_dense_no_allgather():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "COMM_MESH_OK" in r.stdout, r.stderr[-2000:]
