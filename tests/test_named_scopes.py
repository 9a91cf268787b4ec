"""The training round's named scopes reach the compiled program.

The chip benchmark reads per-layer device times through the ``op_name``
metadata of the compiled HLO (``bench/scopes.py``), so every scope has to
survive lowering and XLA's passes: on both corpus layouts and both gossip
kinds of ``train_steps``, and in the mesh path's shard-mapped programs.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import comm, deleda
from repro.core.lda import LDAConfig

CFG = LDAConfig(n_topics=4, vocab_size=32, alpha=0.5, doc_len_max=8,
                n_gibbs=3, n_gibbs_burnin=1)
ESTEP = ("estep.gather", "estep.sweeps", "estep.scatter")
ROUND = ("deleda.estep", "deleda.mix", "deleda.blend", "deleda.record")


def scope_paths(text: str) -> list[list[str]]:
    """Each instruction's op_name as its list of names, in path order
    (``vmap(estep.gather)`` gives ``vmap``, ``estep.gather``)."""
    return [re.findall(r"[\w.\-]+", p)
            for p in re.findall(r'op_name="([^"]*)"', text)]


def assert_nested(paths, scopes):
    """Every scope appears; each E-step scope sits under ``deleda.estep``."""
    for s in scopes:
        assert any(s in p for p in paths), s
    for p in paths:
        for s in ESTEP:
            if s in p:
                assert "deleda.estep" in p[:p.index(s)], p


@pytest.mark.parametrize("kind", ["matching", "edge"])
@pytest.mark.parametrize("layout", ["dense", "unique"])
def test_train_steps_scopes_reach_compiled_hlo(layout, kind):
    n, d, seg = 4, 5, 4
    cfg = deleda.DeledaConfig(lda=CFG, mode="sync", batch_size=2,
                              corpus_layout=layout)
    state = deleda.init_state(cfg, jax.random.key(0), n)
    words = jax.random.randint(jax.random.key(1), (n, d, CFG.doc_len_max),
                               0, CFG.vocab_size)
    mask = jnp.ones(words.shape, bool)
    event = [1, 0, 3, 2] if kind == "matching" else [0, 1]
    sched = jnp.tile(jnp.asarray(event, jnp.int32), (seg, 1))
    text = deleda.train_steps.lower(
        cfg, state, words, mask, sched, jnp.ones((seg, n), jnp.float32),
        jnp.ones((seg, n), bool), record_every=seg,
        kind=kind).compile().as_text()
    assert_nested(scope_paths(text), ROUND + ESTEP)


def test_mesh_update_and_mix_scopes_reach_compiled_hlo():
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.launch.gossip_sim import build_update_step
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:1])
    node = NamedSharding(mesh, P("data"))
    n = 3

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=node)

    stats = spec((n, CFG.n_topics, CFG.vocab_size), jnp.float32)
    step = build_update_step(CFG, 2, mesh)
    text = step.lower(stats, spec((n,), jnp.int32), jax.random.key(0),
                      spec((n, 4, 8), jnp.int32), spec((n, 4, 8), jnp.bool_),
                      spec((n,), jnp.bool_)).compile().as_text()
    assert_nested(scope_paths(text), ("deleda.estep", "deleda.blend") + ESTEP)

    mix = comm.MeshComm(mesh=mesh)._get_local_fn(3)
    text = mix.lower(stats, spec((n,), jnp.int32),
                     spec((n,), jnp.bool_)).compile().as_text()
    assert any("deleda.mix" in p for p in scope_paths(text))
