"""Lifecycle layer: the TrainState carry, segmented training, streaming
corpora, permanent membership, and bitwise checkpoint/restore.

The load-bearing contract: per-step PRNG keys derive as
``fold_in(state.key, absolute_step)`` — a pure function of the step
INDEX — so any partition of a run into ``train_steps`` segments (for
checkpointing or mid-run corpus swaps) is bitwise invisible, and a
killed-and-restored run reproduces the uninterrupted trajectory
bit-for-bit: statistics, consensus history, in-loop eval LP, and the
threaded PRNG stream.
"""

import dataclasses
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import provenance
from repro.analysis.trace_audit import CompileCounter
from repro.core import comm, deleda, oem
from repro.core import scenario as scn
from repro.core.evaluation import EvalSpec
from repro.core.graph import complete_graph, watts_strogatz_graph
from repro.core.lda import LDAConfig, init_stats
from repro.data import lda_synthetic as synth

CFG = LDAConfig(n_topics=3, vocab_size=24, alpha=0.5, doc_len_max=10,
                n_gibbs=4, n_gibbs_burnin=2)
N, T, REC = 10, 20, 10


@pytest.fixture(scope="module")
def corpus():
    return synth.make_corpus(CFG, jax.random.key(0),
                             synth.CorpusSpec(n_nodes=N, docs_per_node=4,
                                              n_test=6))


@pytest.fixture(scope="module")
def inputs():
    g = watts_strogatz_graph(N, 4, 0.3, seed=0)
    return deleda.make_run_inputs(g, T, seed=1, kind="matching")


def _cfg(**kw):
    kw.setdefault("mode", "async")
    kw.setdefault("batch_size", 2)
    return deleda.DeledaConfig(lda=CFG, **kw)


def _assert_trace_equal(a, b, tail_only=False):
    sl = slice(-1, None) if tail_only else slice(None)
    np.testing.assert_array_equal(np.asarray(a.stats), np.asarray(b.stats))
    np.testing.assert_array_equal(np.asarray(a.steps), np.asarray(b.steps))
    np.testing.assert_array_equal(np.asarray(a.history[sl]),
                                  np.asarray(b.history[sl]))
    np.testing.assert_array_equal(np.asarray(a.consensus[sl]),
                                  np.asarray(b.consensus[sl]))
    if a.eval_lp is not None or b.eval_lp is not None:
        np.testing.assert_array_equal(np.asarray(a.eval_lp[sl]),
                                      np.asarray(b.eval_lp[sl]))


# ---------------------------------------------------------------------------
# TrainState basics
# ---------------------------------------------------------------------------

def test_train_state_is_a_pytree():
    st = deleda.init_state(_cfg(), jax.random.key(0), N)
    leaves, treedef = jax.tree_util.tree_flatten(st)
    assert len(leaves) == 7
    st2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(st2, deleda.TrainState)
    np.testing.assert_array_equal(np.asarray(st2.stats),
                                  np.asarray(st.stats))
    assert st.n_nodes == N
    assert st.member.all() and int(st.t) == 0 and int(st.cursor) == 0


def test_init_state_matches_legacy_init_stream():
    """init_state must consume the key exactly like the monolith did:
    split(key) -> per-node init draws from the first half."""
    key = jax.random.key(3)
    st = deleda.init_state(_cfg(), key, N)
    k_init, k_run = jax.random.split(key)
    stats0 = jax.vmap(lambda k: init_stats(CFG, k))(
        jax.random.split(k_init, N))
    np.testing.assert_array_equal(np.asarray(st.stats), np.asarray(stats0))
    np.testing.assert_array_equal(np.asarray(jax.random.key_data(st.key)),
                                  np.asarray(jax.random.key_data(k_run)))


def test_dense_stats_reshapes_sharded_carry():
    st = deleda.init_state(_cfg(vocab_shards=4), jax.random.key(0), N)
    assert st.stats.shape == (N, 3, 4, 6)
    dense = deleda.init_state(_cfg(), jax.random.key(0), N).stats
    np.testing.assert_array_equal(np.asarray(st.dense_stats()),
                                  np.asarray(dense))


def test_trace_carries_final_state(corpus, inputs):
    sched, degs = inputs
    tr = deleda.run_deleda(_cfg(), jax.random.key(1), corpus.words,
                           corpus.mask, sched, degs, T, record_every=REC)
    assert isinstance(tr.state, deleda.TrainState)
    assert int(tr.state.t) == T
    assert int(tr.state.stats_version) == T
    np.testing.assert_array_equal(np.asarray(tr.state.dense_stats()),
                                  np.asarray(tr.stats))


# ---------------------------------------------------------------------------
# Segmented training == single-segment training, one compiled executable
# ---------------------------------------------------------------------------

def test_segments_match_single_run_bitwise(corpus, inputs):
    """Driving train_steps over two half-segments must be bitwise equal
    to the one-segment run — the fold_in(key, absolute_step) contract."""
    sched, degs = inputs
    cfg = _cfg()
    full = deleda.run_deleda(cfg, jax.random.key(1), corpus.words,
                             corpus.mask, sched, degs, T, record_every=REC)
    state = deleda.init_state(cfg, jax.random.key(1), N)
    corr = jnp.ones((T, N), jnp.float32)
    live = jnp.ones((T, N), bool)
    parts = []
    with CompileCounter(deleda.train_steps) as cc:
        for t0 in (0, T // 2):
            sl = slice(t0, t0 + T // 2)
            state, part = deleda.train_steps(
                cfg, state, corpus.words, corpus.mask, sched[sl],
                corr[sl], live[sl], record_every=REC, kind="matching")
            parts.append(part)
    assert cc.total == 1, cc.counts          # both segments, ONE executable
    np.testing.assert_array_equal(np.asarray(state.stats),
                                  np.asarray(full.stats))
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(p.consensus) for p in parts]),
        np.asarray(full.consensus))
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(p.history) for p in parts]),
        np.asarray(full.history))
    assert int(state.t) == T


@pytest.mark.parametrize("name", ["static", "rewiring", "drop10",
                                  "churn20", "coldjoin"])
def test_segment_resume_matches_single_run_all_scenarios(corpus, name):
    """save_every=T/2 (two segments) == the unsegmented run, bitwise,
    for every dynamic-network regime including permanent join/leave."""
    sc = scn.paper_scenario(name, n=N, n_steps=T, seed=2)
    sched, degs, alive, member = sc.compile(
        np.random.default_rng(7)).run_inputs()
    cfg = _cfg()
    kw = dict(record_every=REC, alive=alive, member=member)
    one = deleda.run_deleda(cfg, jax.random.key(2), corpus.words,
                            corpus.mask, sched, degs, T, **kw)
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        seg = deleda.run_deleda(cfg, jax.random.key(2), corpus.words,
                                corpus.mask, sched, degs, T,
                                save_every=T // 2, checkpoint_dir=d, **kw)
    _assert_trace_equal(one, seg)


def test_kill_restore_bitwise_dense_and_sharded(corpus, inputs, tmp_path):
    """The tentpole golden: kill at T/2, restore from disk, finish — the
    resumed tail (stats, history, consensus, eval trace) is BITWISE
    identical to the uninterrupted run, for the dense and the
    vocab-sharded carry."""
    sched, degs = inputs
    spec = EvalSpec(words=corpus.test_words, mask=corpus.test_mask,
                    key=jax.random.key(99), n_particles=2, probe_nodes=2)
    for shards in (1, 4):
        cfg = _cfg(vocab_shards=shards, eval_every=REC)
        kw = dict(record_every=REC, eval_spec=spec)
        full = deleda.run_deleda(cfg, jax.random.key(4), corpus.words,
                                 corpus.mask, sched, degs, T, **kw)
        d = tmp_path / f"shards{shards}"
        deleda.run_deleda(cfg, jax.random.key(4), corpus.words,
                          corpus.mask, sched, degs, T,
                          save_every=T // 2, checkpoint_dir=str(d), **kw)
        shutil.rmtree(d / f"step_{T:08d}")       # the kill
        resumed = deleda.run_deleda(cfg, jax.random.key(4), corpus.words,
                                    corpus.mask, sched, degs, T,
                                    restore_from=str(d), **kw)
        _assert_trace_equal(full, resumed, tail_only=True)
        # the threaded PRNG key restores bit-identically too
        assert int(resumed.state.t) == T
        np.testing.assert_array_equal(
            np.asarray(jax.random.key_data(resumed.state.key)),
            np.asarray(jax.random.key_data(full.state.key)))


@pytest.mark.parametrize("backend", sorted(comm.SIM_BACKENDS))
def test_roundtrip_bitwise_across_comm_backends(corpus, inputs, tmp_path,
                                                backend):
    """checkpoint -> restore round-trips bitwise whichever communicator
    mixed the statistics."""
    sched, degs = inputs
    cfg = _cfg(comm_backend=backend)
    tr = deleda.run_deleda(cfg, jax.random.key(5), corpus.words,
                           corpus.mask, sched, degs, T, record_every=REC)
    d = str(tmp_path / backend)
    deleda.save_state(d, tr.state, config=cfg)
    like = deleda.init_state(cfg, jax.random.key(5), N)
    st = deleda.restore_state(d, like, config=cfg)
    for f in ("stats", "steps", "t", "stats_version", "member", "cursor"):
        np.testing.assert_array_equal(np.asarray(getattr(st, f)),
                                      np.asarray(getattr(tr.state, f)))
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(st.key)),
        np.asarray(jax.random.key_data(tr.state.key)))


def test_mesh_kill_restore_bitwise(corpus, inputs, tmp_path):
    """A node mesh run saves its sharded TrainState, restores it into
    init_state's shardings and resumes bitwise."""
    from jax.sharding import Mesh
    sched, degs = inputs
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("nodes",))
    cfg = _cfg(mode="sync", comm_backend="mesh", mesh=mesh)
    full = deleda.run_deleda(cfg, jax.random.key(5), corpus.words,
                             corpus.mask, sched, degs, T, record_every=REC)
    d = str(tmp_path / "mesh")
    deleda.run_deleda(cfg, jax.random.key(5), corpus.words, corpus.mask,
                      sched, degs, T, record_every=REC, save_every=T // 2,
                      checkpoint_dir=d)
    shutil.rmtree(os.path.join(d, f"step_{T:08d}"))
    like = deleda.init_state(cfg, jax.random.key(5), N)
    restored = deleda.restore_state(d, like, config=cfg)
    assert restored.stats.sharding == like.stats.sharding
    resumed = deleda.run_deleda(cfg, jax.random.key(5), corpus.words,
                                corpus.mask, sched, degs, T,
                                record_every=REC, restore_from=d)
    _assert_trace_equal(full, resumed, tail_only=True)
    for f in ("stats", "steps", "t", "stats_version", "member", "cursor"):
        np.testing.assert_array_equal(np.asarray(getattr(resumed.state, f)),
                                      np.asarray(getattr(full.state, f)))


def test_legacy_uint32_key_flavor_roundtrips(corpus, inputs, tmp_path):
    """PRNGKey (legacy uint32) states serialize and resume bitwise; the
    `like` flavor decides the rewrap."""
    sched, degs = inputs
    cfg = _cfg()
    full = deleda.run_deleda(cfg, jax.random.PRNGKey(6), corpus.words,
                             corpus.mask, sched, degs, T, record_every=REC)
    d = str(tmp_path / "legacy")
    deleda.run_deleda(cfg, jax.random.PRNGKey(6), corpus.words,
                      corpus.mask, sched, degs, T, record_every=REC,
                      save_every=T // 2, checkpoint_dir=d)
    shutil.rmtree(os.path.join(d, f"step_{T:08d}"))
    resumed = deleda.run_deleda(cfg, jax.random.PRNGKey(6), corpus.words,
                                corpus.mask, sched, degs, T,
                                record_every=REC, restore_from=d)
    _assert_trace_equal(full, resumed, tail_only=True)
    assert not jnp.issubdtype(resumed.state.key.dtype, jax.dtypes.prng_key)


# ---------------------------------------------------------------------------
# Streaming corpora
# ---------------------------------------------------------------------------

def test_stream_segment_zero_is_base_corpus():
    spec = synth.CorpusSpec(n_nodes=N, docs_per_node=4, n_test=6,
                            refresh_every=REC)
    stream = synth.make_corpus_stream(CFG, jax.random.key(0), spec)
    frozen = synth.make_corpus(CFG, jax.random.key(0),
                               dataclasses.replace(spec, refresh_every=0))
    w0, m0 = stream.segment(0)
    np.testing.assert_array_equal(np.asarray(w0), np.asarray(frozen.words))
    np.testing.assert_array_equal(np.asarray(m0), np.asarray(frozen.mask))
    np.testing.assert_array_equal(np.asarray(stream.base.test_words),
                                  np.asarray(frozen.test_words))
    # later segments are fresh draws of the SAME shapes, deterministic
    w1, m1 = stream.segment(1)
    assert w1.shape == w0.shape and m1.shape == m0.shape
    assert not np.array_equal(np.asarray(w1), np.asarray(w0))
    w1b, _ = stream.segment(1)
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w1b))


def test_stream_run_matches_frozen_until_first_refresh(corpus, inputs):
    sched, degs = inputs
    spec = synth.CorpusSpec(n_nodes=N, docs_per_node=4, n_test=6,
                            refresh_every=REC)
    stream = synth.make_corpus_stream(CFG, jax.random.key(0), spec)
    cfg = _cfg()
    frozen = deleda.run_deleda(cfg, jax.random.key(8), stream.base.words,
                               stream.base.mask, sched[:REC], degs, REC,
                               record_every=REC)
    streamed = deleda.run_deleda(cfg, jax.random.key(8), None, None,
                                 sched[:REC], degs, REC, record_every=REC,
                                 stream=stream)
    _assert_trace_equal(frozen, streamed)
    # ... and diverges once the corpus refreshes
    full = deleda.run_deleda(cfg, jax.random.key(8), stream.base.words,
                             stream.base.mask, sched, degs, T,
                             record_every=REC)
    full_s = deleda.run_deleda(cfg, jax.random.key(8), None, None, sched,
                               degs, T, record_every=REC, stream=stream)
    assert int(full_s.state.cursor) == 1
    assert not np.array_equal(np.asarray(full.stats),
                              np.asarray(full_s.stats))


def test_stream_kill_restore_bitwise(inputs, tmp_path):
    """A streamed run killed mid-horizon resumes bitwise: segment s is a
    pure function of the stream, re-materialized on restore."""
    sched, degs = inputs
    spec = synth.CorpusSpec(n_nodes=N, docs_per_node=4, n_test=6,
                            refresh_every=REC)
    stream = synth.make_corpus_stream(CFG, jax.random.key(0), spec)
    cfg = _cfg()
    full = deleda.run_deleda(cfg, jax.random.key(9), None, None, sched,
                             degs, T, record_every=REC, stream=stream)
    d = str(tmp_path / "stream")
    deleda.run_deleda(cfg, jax.random.key(9), None, None, sched, degs, T,
                      record_every=REC, stream=stream,
                      save_every=REC, checkpoint_dir=d)
    shutil.rmtree(os.path.join(d, f"step_{T:08d}"))
    resumed = deleda.run_deleda(cfg, jax.random.key(9), None, None, sched,
                                degs, T, record_every=REC, stream=stream,
                                restore_from=d)
    _assert_trace_equal(full, resumed, tail_only=True)
    assert int(resumed.state.cursor) == 1


def test_stream_validation():
    with pytest.raises(ValueError):
        synth.CorpusSpec(refresh_every=-1)
    with pytest.raises(ValueError):
        synth.make_corpus_stream(CFG, jax.random.key(0),
                                 synth.CorpusSpec(refresh_every=0))
    spec = synth.CorpusSpec(n_nodes=N, docs_per_node=4, n_test=6,
                            refresh_every=7)           # not % record_every
    stream = synth.make_corpus_stream(CFG, jax.random.key(0), spec)
    with pytest.raises(ValueError, match="refresh_every"):
        deleda.run_deleda(_cfg(), jax.random.key(0), None, None,
                          jnp.zeros((T, N), jnp.int32),
                          jnp.full((N,), 4), T, record_every=REC,
                          stream=stream)


# ---------------------------------------------------------------------------
# Robbins-Monro forgetting
# ---------------------------------------------------------------------------

def test_decay_validation():
    with pytest.raises(ValueError):
        _cfg(decay=(10.0,))
    with pytest.raises(ValueError):
        _cfg(decay=(10.0, 1.5))          # kappa > 1
    with pytest.raises(ValueError):
        _cfg(decay=(-1.0, 0.6))          # tau0 < 0
    with pytest.raises(ValueError):
        oem.make_decay_schedule(10.0, 0.0)


def test_forgetting_rho_is_convex_blend():
    rho = jnp.asarray([0.0, 0.3, 1.0])
    d = jnp.asarray([0.5, 0.5, 0.5])
    out = oem.forgetting_rho(rho, d)
    np.testing.assert_allclose(np.asarray(out), [0.5, 0.65, 1.0],
                               rtol=1e-6)
    assert ((out >= rho - 1e-7) & (out <= 1.0 + 1e-7)).all()


def test_decay_none_is_bitwise_unchanged(corpus, inputs):
    """decay=None must not touch the trajectory at all (the paper's plain
    eq. (2) path stays the oracle)."""
    sched, degs = inputs
    a = deleda.run_deleda(_cfg(), jax.random.key(1), corpus.words,
                          corpus.mask, sched, degs, T, record_every=REC)
    b = deleda.run_deleda(_cfg(decay=None), jax.random.key(1),
                          corpus.words, corpus.mask, sched, degs, T,
                          record_every=REC)
    _assert_trace_equal(a, b)


def test_decay_discounts_more_than_plain(corpus, inputs):
    """With forgetting on, the carried (init-heavy) mass decays faster:
    the two trajectories must differ, and the decay run's blend weight
    is strictly the larger one at every step."""
    sched, degs = inputs
    plain = deleda.run_deleda(_cfg(), jax.random.key(1), corpus.words,
                              corpus.mask, sched, degs, T,
                              record_every=REC)
    decayed = deleda.run_deleda(_cfg(decay=(5.0, 0.8)), jax.random.key(1),
                                corpus.words, corpus.mask, sched, degs, T,
                                record_every=REC)
    assert not np.array_equal(np.asarray(plain.stats),
                              np.asarray(decayed.stats))
    # per-node step counters are untouched by the forgetting knob
    np.testing.assert_array_equal(np.asarray(plain.steps),
                                  np.asarray(decayed.steps))


def test_run_oem_decay_knob(corpus):
    a = oem.run_oem(CFG, jax.random.key(0), corpus.flat_words,
                    corpus.flat_mask, n_steps=10, batch_size=4,
                    record_every=10)
    b = oem.run_oem(CFG, jax.random.key(0), corpus.flat_words,
                    corpus.flat_mask, n_steps=10, batch_size=4,
                    record_every=10, decay=(5.0, 0.8))
    assert not np.array_equal(np.asarray(a.state.stats),
                              np.asarray(b.state.stats))


# ---------------------------------------------------------------------------
# Permanent membership: cold joins and departures
# ---------------------------------------------------------------------------

def test_scenario_join_leave_validation():
    seq = scn.GraphSequence.static(complete_graph(N), T)
    with pytest.raises(ValueError):
        scn.Scenario(topology=seq, joins=((3, T),))        # past horizon
    with pytest.raises(ValueError):
        scn.Scenario(topology=seq, leaves=((3, 0),))       # leave at 0
    with pytest.raises(ValueError):
        scn.Scenario(topology=seq, joins=((3, 5), (3, 8)))  # dup node
    with pytest.raises(ValueError):
        scn.Scenario(topology=seq, joins=((3, 10),), leaves=((3, 5),))


def test_member_mask_semantics():
    seq = scn.GraphSequence.static(complete_graph(N), T)
    sc = scn.Scenario(topology=seq, joins=((2, 8),), leaves=((5, 12),))
    m = sc.member_mask()
    assert m.shape == (T, N)
    assert not m[:8, 2].any() and m[8:, 2].all()     # join inclusive
    assert m[:12, 5].all() and not m[12:, 5].any()   # leave exclusive
    assert m[:, 0].all()


def test_cold_join_gets_sponsor_and_converges(corpus):
    """The joiner: frozen at its init stats before the join, sponsored
    into the gossip at the join round, then a plain member."""
    sc = scn.paper_scenario("coldjoin", n=N, n_steps=T, seed=2)
    compiled = sc.compile(np.random.default_rng(7))
    assert compiled.n_sponsored == 1
    sched, degs, alive, member = compiled.run_inputs()
    assert member is not None
    joiner = N - 1
    join_t = T // 2
    # the compiled schedule actually pairs the joiner at its join round
    partners = np.asarray(compiled.schedule.data)
    assert partners[join_t, joiner] != joiner
    cfg = _cfg()
    key = jax.random.key(3)
    tr = deleda.run_deleda(cfg, key, corpus.words, corpus.mask, sched,
                           degs, T, record_every=REC, alive=alive,
                           member=member)
    # pre-join: bit-equal to the init row, zero local steps consumed then
    k_init, _ = jax.random.split(key)
    stats0 = jax.vmap(lambda k: init_stats(CFG, k))(
        jax.random.split(k_init, N))
    half = deleda.run_deleda(cfg, key, corpus.words, corpus.mask,
                             sched[:join_t], degs[:join_t], join_t,
                             record_every=REC, alive=alive[:join_t],
                             member=member[:join_t])
    np.testing.assert_array_equal(np.asarray(half.stats[joiner]),
                                  np.asarray(stats0[joiner]))
    assert int(half.steps[joiner]) == 0
    # post-join: the handoff moved its statistic and its clock
    assert not np.array_equal(np.asarray(tr.stats[joiner]),
                              np.asarray(stats0[joiner]))
    assert int(tr.steps[joiner]) > 0
    assert bool(tr.state.member[joiner])


def test_leaver_is_frozen_and_excluded(corpus):
    seq = scn.GraphSequence.static(complete_graph(N), T)
    sc = scn.Scenario(topology=seq, leaves=((4, T // 2),), name="leave")
    sched, degs, alive, member = sc.compile(
        np.random.default_rng(8)).run_inputs()
    cfg = _cfg()
    tr = deleda.run_deleda(cfg, jax.random.key(3), corpus.words,
                           corpus.mask, sched, degs, T, record_every=REC,
                           alive=alive, member=member)
    half = deleda.run_deleda(cfg, jax.random.key(3), corpus.words,
                             corpus.mask, sched[:T // 2], degs[:T // 2],
                             T // 2, record_every=REC, alive=None,
                             member=member[:T // 2])
    # after leaving, node 4's statistic and clock never move again
    np.testing.assert_array_equal(np.asarray(tr.stats[4]),
                                  np.asarray(half.stats[4]))
    assert int(tr.steps[4]) == int(half.steps[4])
    assert not bool(tr.state.member[4])


def test_member_none_is_bitwise_original(corpus, inputs):
    """member=None and an all-ones member mask agree on steps/stats; the
    None path is the pre-lifecycle computation bit-for-bit."""
    sched, degs = inputs
    cfg = _cfg()
    a = deleda.run_deleda(cfg, jax.random.key(1), corpus.words,
                          corpus.mask, sched, degs, T, record_every=REC)
    b = deleda.run_deleda(cfg, jax.random.key(1), corpus.words,
                          corpus.mask, sched, degs, T, record_every=REC,
                          member=jnp.ones((T, N), bool))
    np.testing.assert_array_equal(np.asarray(a.stats), np.asarray(b.stats))
    np.testing.assert_array_equal(np.asarray(a.steps), np.asarray(b.steps))
    np.testing.assert_allclose(np.asarray(a.consensus),
                               np.asarray(b.consensus), rtol=1e-6)


def test_masked_consensus_excludes_nonmembers():
    stats = jnp.asarray(np.random.default_rng(0).normal(size=(4, 2, 3)),
                        jnp.float32)
    member = jnp.asarray([True, True, True, False])
    from repro.core import gossip
    full = gossip.consensus_distance(stats)
    masked = gossip.consensus_distance(stats, member)
    expect = gossip.consensus_distance(stats[:3])
    np.testing.assert_allclose(float(masked), float(expect), rtol=1e-6)
    assert abs(float(full) - float(masked)) > 1e-6


# ---------------------------------------------------------------------------
# Checkpoint layer satellites
# ---------------------------------------------------------------------------

def test_latest_step_skips_uncommitted_dirs(tmp_path):
    from repro.checkpoint import latest_step, save_checkpoint
    d = str(tmp_path)
    save_checkpoint(d, {"x": jnp.arange(3)}, 5)
    save_checkpoint(d, {"x": jnp.arange(3)}, 10)
    assert latest_step(d) == 10
    # a planted partial dir (kill mid-write): step dir exists, no
    # committed state.npz -> must NOT be picked up
    partial = tmp_path / "step_00000015"
    partial.mkdir()
    (partial / "meta.json").write_text("{}")
    (partial / ".state.npz.tmp").write_bytes(b"garbage")
    assert latest_step(d) == 10
    from repro.checkpoint import restore_checkpoint
    out = restore_checkpoint(d, {"x": jnp.zeros(3, jnp.int32)})
    np.testing.assert_array_equal(np.asarray(out["x"]), np.arange(3))


def test_restore_shape_mismatch_is_descriptive(tmp_path):
    from repro.checkpoint import restore_checkpoint, save_checkpoint
    d = str(tmp_path)
    save_checkpoint(d, {"stats": jnp.zeros((3, 4))}, 1)
    with pytest.raises(ValueError) as e:
        restore_checkpoint(d, {"stats": jnp.zeros((3, 2, 2))})
    msg = str(e.value)
    assert "stats" in msg and "(3, 4)" in msg and "(3, 2, 2)" in msg


def test_meta_sidecar_written_and_digest_warns(tmp_path):
    from repro.checkpoint import (load_meta, restore_checkpoint,
                                  save_checkpoint)
    d = str(tmp_path)
    save_checkpoint(d, {"x": jnp.arange(3)}, 7,
                    meta={"config_digest": "abc123"})
    meta = load_meta(d)
    for k in ("git_commit", "jax_version", "config_digest"):
        assert k in meta, meta
    assert meta["config_digest"] == "abc123"
    with open(os.path.join(d, "step_00000007", "meta.json")) as f:
        assert json.load(f) == meta
    with pytest.warns(UserWarning, match="digest"):
        restore_checkpoint(d, {"x": jnp.zeros(3, jnp.int32)},
                           expect_config_digest="something-else")
    # matching digest: silent
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        restore_checkpoint(d, {"x": jnp.zeros(3, jnp.int32)},
                           expect_config_digest="abc123")
    # provenance digest is stable and config-sensitive
    assert provenance.config_digest(_cfg()) == provenance.config_digest(
        _cfg())
    assert provenance.config_digest(_cfg()) != provenance.config_digest(
        _cfg(batch_size=3))


def test_save_state_meta_records_key_flavor_and_digest(tmp_path, corpus,
                                                      inputs):
    from repro.checkpoint import load_meta
    sched, degs = inputs
    cfg = _cfg()
    tr = deleda.run_deleda(cfg, jax.random.key(5), corpus.words,
                           corpus.mask, sched, degs, T, record_every=REC)
    d = str(tmp_path)
    deleda.save_state(d, tr.state, config=cfg)
    meta = load_meta(d)
    assert meta["typed_key"] is True
    assert meta["kind"] == "deleda_train_state"
    assert meta["config_digest"] == provenance.config_digest(cfg)
