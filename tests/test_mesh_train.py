"""Node-sharded ``train_steps`` on a 1-D mesh == one-device ``train_steps``.

Four host devices exist only in a process whose ``XLA_FLAGS`` ask for them
before JAX starts, so one subprocess runs every case and prints its
readings; the tests below assert on them. Sizes: K=5, V=1,003 (not a
multiple of 128 lanes), L=16, two segments of four matching rounds.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

NODES = (8, 16)

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import deleda
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
