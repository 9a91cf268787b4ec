"""Node-sharded ``train_steps`` on a mesh == one-device ``train_steps``.

Four host devices exist only in a process whose ``XLA_FLAGS`` ask for them
before JAX starts, so one subprocess runs every four-device case and
prints its readings; the tests below assert on them. Sizes: K=5, V=1,003
(not a multiple of 128 lanes), L=16, two segments of four matching
rounds. What the mesh adds to the round (churn, membership, the unique
layout, the node x vocab grid) runs in this process on a one-device mesh.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import deleda
from repro.core.graph import complete_graph
from repro.core.lda import LDAConfig
from repro.core.scenario import GraphSequence, Scenario
from repro.data.lda_synthetic import CorpusSpec, make_corpus

NODES = (8, 16)

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import deleda
    from repro.core.evaluation import EvalSpec
    from repro.core.graph import watts_strogatz_graph
    from repro.core.lda import LDAConfig

    lda = LDAConfig(n_topics=5, vocab_size=1003, alpha=0.5, doc_len_max=16,
                    n_gibbs=4, n_gibbs_burnin=2)
    mesh = Mesh(np.asarray(jax.devices()), ("nodes",))
    one = deleda.DeledaConfig(lda=lda, mode="sync", batch_size=3)
    many = deleda.DeledaConfig(lda=lda, mode="sync", batch_size=3,
                               comm_backend="mesh", mesh=mesh)

    def rel(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.abs(a - b).max() / np.abs(a).max())

    for n in %(nodes)r:
        rng = np.random.default_rng(n)
        words = jnp.asarray(rng.integers(0, lda.vocab_size, (n, 6, 16)),
                            jnp.int32)
        mask = jnp.asarray(rng.random((n, 6, 16)) < 0.7)
        sched, _ = deleda.make_run_inputs(
            watts_strogatz_graph(n, 4, 0.3, 0), 8, seed=1, kind="matching")
        corr, live = jnp.ones((8, n), jnp.float32), jnp.ones((8, n), bool)
        s1 = deleda.init_state(one, jax.random.key(7), n)
        s4 = deleda.init_state(many, jax.random.key(7), n)
        row = {"n": n, "init_equal": bool(jnp.array_equal(s1.stats, s4.stats)),
               "stats": [], "steps_equal": [], "consensus": [],
               "history": [], "sharded": []}
        node = NamedSharding(mesh, P("nodes"))
        w4, m4 = jax.device_put(words, node), jax.device_put(mask, node)
        for seg in range(2):
            sl = slice(4 * seg, 4 * seg + 4)
            s1, tr1 = deleda.train_steps(one, s1, words, mask, sched[sl],
                                         corr[sl], live[sl], record_every=4)
            s4, tr4 = deleda.train_steps(many, s4, w4, m4, sched[sl],
                                         corr[sl], live[sl], record_every=4)
            row["stats"].append(rel(s1.stats, s4.stats))
            row["steps_equal"].append(bool(jnp.array_equal(s1.steps,
                                                           s4.steps)))
            row["consensus"].append(rel(tr1.consensus, tr4.consensus))
            row["history"].append(rel(tr1.history, tr4.history))
            row["sharded"].append(
                [str(x.sharding.spec) for x in (s4.stats, s4.steps,
                                                s4.member, tr4.history)])
        # in-loop eval of 5 probe nodes: more than a device's 2 or 4 rows
        spec = EvalSpec(words=words[0], mask=mask[0], key=jax.random.key(9),
                        n_particles=2, probe_nodes=5)
        lps = [deleda.run_deleda(
            deleda.DeledaConfig(lda=lda, mode="sync", batch_size=3,
                                eval_every=4, **kw),
            jax.random.key(7), words, mask, sched, jnp.full((n,), 4), 8,
            record_every=4, eval_spec=spec).eval_lp
            for kw in ({}, dict(comm_backend="mesh", mesh=mesh))]
        row["eval_shape"] = list(lps[1].shape)
        row["eval_lp"] = rel(lps[0], lps[1])
        print("ROW " + json.dumps(row), flush=True)

    try:
        deleda.init_state(many, jax.random.key(0), 6)
        print("ROW " + json.dumps({"n": 6, "raised": False}))
    except ValueError:
        print("ROW " + json.dumps({"n": 6, "raised": True}))
""") % {"nodes": NODES}


@pytest.fixture(scope="module")
def rows():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=300)
    found = {}
    for line in r.stdout.splitlines():
        if line.startswith("ROW "):
            row = json.loads(line[4:])
            found[row["n"]] = row
    assert found, r.stderr[-3000:]
    return found


@pytest.mark.parametrize("n", NODES)
def test_mesh_train_steps_matches_one_device(rows, n):
    row = rows[n]
    assert row["init_equal"]
    assert all(row["steps_equal"]), row
    # the statistic, the consensus trace and the history agree to float32
    # rounding (the per-device E-step batch fuses sums differently)
    for key in ("stats", "consensus", "history"):
        assert max(row[key]) <= 1e-6, (key, row[key])
    node = "PartitionSpec('nodes',)"
    assert row["sharded"][-1] == [node, node, node,
                                  "PartitionSpec(None, 'nodes')"]


def test_mesh_rejects_nodes_not_divisible_by_devices(rows):
    assert rows[6]["raised"]


@pytest.mark.parametrize("n", NODES)
def test_mesh_inloop_eval_matches_one_device(rows, n):
    """Each device evaluates its own rows among the first probe nodes;
    the LP trajectory comes out [E, probe_nodes] as on one device."""
    assert rows[n]["eval_shape"] == [2, 5]
    assert rows[n]["eval_lp"] <= 1e-6, rows[n]["eval_lp"]


# ---------------------------------------------------------------------------
# One-device meshes in this process
# ---------------------------------------------------------------------------

LDA = LDAConfig(n_topics=4, vocab_size=40, alpha=0.5, doc_len_max=16,
                n_gibbs=4, n_gibbs_burnin=2)
N, T = 8, 8


@pytest.fixture(scope="module")
def corpus():
    return make_corpus(LDA, jax.random.key(0),
                       CorpusSpec(n_nodes=N, docs_per_node=6, n_test=4))


def one_device_mesh(*names):
    return Mesh(np.asarray(jax.devices()[:1]).reshape((1,) * len(names)),
                names)


def one_and_mesh(corpus, mesh, sched, degs, mode="sync", layout="dense",
                 **run_kw):
    """The one-device run and the same run on ``mesh``."""
    return [deleda.run_deleda(
        deleda.DeledaConfig(lda=LDA, mode=mode, batch_size=2,
                            corpus_layout=layout, **kw),
        jax.random.key(3), corpus.words, corpus.mask, sched, degs, T,
        record_every=2, schedule_kind="matching", **run_kw)
        for kw in ({}, dict(comm_backend="mesh", mesh=mesh))]


def assert_follows(one, many):
    """As close as the four-device runs above must be."""
    np.testing.assert_array_equal(np.asarray(many.steps),
                                  np.asarray(one.steps))
    for key in ("stats", "consensus", "history"):
        a = np.asarray(getattr(one, key), np.float64)
        b = np.asarray(getattr(many, key), np.float64)
        assert np.abs(a - b).max() <= 1e-6 * np.abs(a).max(), key


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_mesh_churn_and_membership_follow_one_device(corpus, mode):
    """Churn, message drops, a cold join and a departure through the
    node-sharded round."""
    scenario = Scenario(topology=GraphSequence.static(complete_graph(N), T),
                        drop_prob=0.2, churn=0.3, joins=((3, 4),),
                        leaves=((5, 6),))
    sched, degs, alive, member = scenario.compile(
        np.random.default_rng(0)).run_inputs()
    assert not np.asarray(alive).all() and not np.asarray(member).all()
    one, many = one_and_mesh(corpus, one_device_mesh("nodes"), sched, degs,
                             mode=mode, alive=alive, member=member)
    assert_follows(one, many)


def test_mesh_unique_layout_follows_one_device(corpus):
    sched, degs = deleda.make_run_inputs(complete_graph(N), T, seed=1,
                                         kind="matching")
    one, many = one_and_mesh(corpus, one_device_mesh("nodes"), sched, degs,
                             layout="unique")
    assert_follows(one, many)


def test_grid_follows_one_device(corpus):
    """A 2-D mesh splits V over its second axis; with one device there
    the beta assembly's and the record's psums reduce nothing."""
    sched, degs = deleda.make_run_inputs(complete_graph(N), T, seed=1,
                                         kind="matching")
    one, many = one_and_mesh(corpus, one_device_mesh("nodes", "vocab"),
                             sched, degs)
    assert_follows(one, many)
    assert many.state.stats.sharding.spec == ("nodes", None, "vocab")


@pytest.mark.parametrize("knob, why", [
    ({"eval_every": 2}, "in-loop evaluation"),
    ({"vocab_shards": 2}, "not vocab_shards")],
    ids=["eval_every", "vocab_shards"])
def test_grid_refuses_eval_and_vocab_shards(knob, why):
    with pytest.raises(ValueError, match=why):
        deleda.DeledaConfig(lda=LDA, comm_backend="mesh",
                            mesh=one_device_mesh("nodes", "vocab"), **knob)
