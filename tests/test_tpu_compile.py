"""Compile every Pallas kernel of the main path for a TPU v5e chip.

Interpret mode cannot see tiling, layout or VMEM refusals; the TPU
compiler can, and it compiles for a chip that is described rather than
attached. Shapes are those of ``chip_smoke.py`` (the ``big`` regime of
``benchmarks/scale_bench.py``: n=1024 nodes, V=50,000, K=4, 2 docs per
node per step, L=16, 4 Gibbs sweeps); the statistic scatter's and the
gossip mix's tests take a training cell's. Nothing runs, so these tests
say nothing about results or times.

The topology is described inside a module fixture, never at import: only
the worker that runs this file may load the TPU compiler library.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import deleda, estep
from repro.core.comm import DenseSimComm
from repro.core.lda import LDAConfig
from repro.kernels.gossip_mix.ops import mix_matching
from repro.kernels.lda_gibbs import ops as gibbs_ops
from repro.kernels.lda_gibbs.lda_gibbs import gibbs_sweeps_pallas
from repro.kernels.lda_l2r.lda_l2r import l2r_scores_pallas
from repro.kernels.lda_sparse.lda_sparse import sparse_sweeps_pallas

N_NODES, V, K, B, L, S, BURNIN = 1024, 50_000, 4, 2, 16, 4, 2
DOCS = N_NODES * B          # one fused E-step over every node's minibatch
U = 16                      # unique slots of the sparse layout (<= L)
EVAL_DOCS, PARTICLES = 64, 2


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, kernel: str, *args):
    """``fn`` compiles for the chip, with ``kernel`` as a Mosaic call."""
    _assert_lowered_kernel(fn.lower(*args), kernel)


def _assert_lowered_kernel(lowered, kernel: str):
    text = lowered.compile().as_text()
    assert any("tpu_custom_call" in ln and kernel in ln
               for ln in text.splitlines()), f"{kernel} is not compiled"


def test_lda_gibbs_compiles(one_chip):
    _assert_kernel(
        jax.jit(lambda bw, m, u, z0: gibbs_sweeps_pallas(
            bw, m, u, z0, alpha=0.5, n_sweeps=S, burnin=BURNIN,
            interpret=False)), "lda_gibbs",
        _spec(one_chip, (DOCS, L, K)), _spec(one_chip, (DOCS, L)),
        _spec(one_chip, (S, DOCS, L)),
        _spec(one_chip, (DOCS, L), jnp.int32))


def test_lda_sparse_compiles(one_chip):
    _assert_kernel(
        jax.jit(lambda bw, c, u, z0: sparse_sweeps_pallas(
            bw, c, u, z0, alpha=0.5, n_sweeps=S, burnin=BURNIN,
            interpret=False)), "lda_sparse",
        _spec(one_chip, (DOCS, U, K)), _spec(one_chip, (DOCS, U)),
        _spec(one_chip, (S, DOCS, U)),
        _spec(one_chip, (DOCS, U), jnp.int32))


@pytest.mark.parametrize("count_weighted", [False, True])
def test_lda_l2r_compiles(one_chip, count_weighted):
    _assert_kernel(
        jax.jit(lambda kd, bw, w, a: l2r_scores_pallas(
            kd, bw, w, a, n_particles=PARTICLES,
            count_weighted=count_weighted, interpret=False)), "lda_l2r",
        _spec(one_chip, (EVAL_DOCS, 2), jnp.uint32),
        _spec(one_chip, (EVAL_DOCS, L, K)), _spec(one_chip, (EVAL_DOCS, L)),
        _spec(one_chip, (1, 1)))


@pytest.mark.parametrize("v", [V, 1000, 100])
def test_gossip_mix_compiles(one_chip, v):
    _assert_kernel(
        jax.jit(lambda st, p: mix_matching(st, p, interpret=False)),
        "gossip_mix",
        _spec(one_chip, (N_NODES, K, v)),
        _spec(one_chip, (N_NODES,), jnp.int32))


@pytest.mark.parametrize("grid", [False, True], ids=["4x1", "2x2"])
def test_mesh_update_step_compiles(topo, one_chip, monkeypatch, grid):
    """The node-sharded ``train_steps`` with lda_gibbs inside shard_map,
    for four chips as a 1-D node mesh and as the node x vocab grid."""
    # the E-step backend asks the platform, which is the CPU here
    monkeypatch.setattr(gibbs_ops, "resolve_interpret", lambda _: False)
    devices = np.asarray(topo.devices)
    mesh = (Mesh(devices.reshape(2, 2), ("data", "vocab")) if grid
            else Mesh(devices, ("data",)))
    _assert_lowered_kernel(
        _mesh_train_steps(mesh, N_NODES, estep_backend="pallas"),
        "lda_gibbs")


# an f32 row gather under the gossip mix's scope: the node-axis gather
# that partner_mix replaces with a contraction
_MIX_ROW_GATHER = re.compile(r"= f32\[[^\]]*\][^ ]* gather\(.*deleda\.mix")


def _mix_contractions(text: str) -> int:
    """Contractions at HIGHEST precision under the ``mix.contract`` scope."""
    return sum("operand_precision={highest,highest}" in ln
               and "mix.contract" in ln for ln in text.splitlines())


def _assert_no_reduce_with_contraction(text: str):
    """No fused computation holds both a mix contraction and a reduce:
    fused into the contraction's output, the E-step's sum over V would
    run in another order than after a gather (partner_mix's barrier)."""
    for block in text.split("\n\n"):
        if any("operand_precision={highest,highest}" in ln
               and "mix.contract" in ln for ln in block.splitlines()):
            assert " reduce(" not in block


def test_dense_mix_is_one_highest_contraction(one_chip):
    """The one-chip mix at PubMed's shape fetches partner rows with one
    f32 contraction at HIGHEST precision, not a node-axis row gather: a
    default-precision dot would be a single bf16 pass."""
    text = jax.jit(DenseSimComm().mix_matching).lower(
        _spec(one_chip, (32, 100, 141_043)),
        _spec(one_chip, (32,), jnp.int32)).compile().as_text()
    assert "gather(" not in text
    assert text.count("operand_precision={highest,highest}") == 1
    assert _mix_contractions(text) == 1


def test_train_steps_mix_is_one_highest_contraction(one_chip):
    """The one-chip ``train_steps`` segment at the cells' 32 nodes: its
    round's mix is one HIGHEST contraction and no f32 row gather."""
    n, seg = 32, 4
    lda = LDAConfig(n_topics=K, vocab_size=V, alpha=0.5, doc_len_max=L,
                    n_gibbs=S, n_gibbs_burnin=BURNIN)
    cfg = deleda.DeledaConfig(lda=lda, mode="sync", batch_size=B)
    state = deleda.TrainState(
        stats=_spec(one_chip, (n, K, V)),
        steps=_spec(one_chip, (n,), jnp.int32),
        key=_spec(one_chip, (), jax.random.key(0).dtype),
        t=_spec(one_chip, (), jnp.int32),
        stats_version=_spec(one_chip, (), jnp.int32),
        member=_spec(one_chip, (n,), jnp.bool_),
        cursor=_spec(one_chip, (), jnp.int32))
    text = deleda.train_steps.lower(
        cfg, state, _spec(one_chip, (n, 8, L), jnp.int32),
        _spec(one_chip, (n, 8, L), jnp.bool_),
        _spec(one_chip, (seg, n), jnp.int32), _spec(one_chip, (seg, n)),
        _spec(one_chip, (seg, n), jnp.bool_),
        record_every=seg).compile().as_text()
    assert not any(_MIX_ROW_GATHER.search(ln) for ln in text.splitlines())
    assert _mix_contractions(text) == 1
    _assert_no_reduce_with_contraction(text)


def _mesh_train_steps(mesh, n_nodes: int, **cfg_kw):
    """The ``train_steps`` segment on ``mesh`` (its first axis nodes, a
    second one V blocks), lowered."""
    lda = LDAConfig(n_topics=K, vocab_size=V, alpha=0.5, doc_len_max=L,
                    n_gibbs=S, n_gibbs_burnin=BURNIN)
    cfg = deleda.DeledaConfig(lda=lda, mode="sync", batch_size=B,
                              comm_backend="mesh", mesh=mesh, **cfg_kw)
    axis, *vocab = mesh.axis_names
    node, rep = NamedSharding(mesh, P(axis)), NamedSharding(mesh, P())
    block = NamedSharding(mesh, P(axis, None, *vocab)) if vocab else node
    seg = 4
    state = deleda.TrainState(
        stats=_spec(block, (n_nodes, K, V)),
        steps=_spec(node, (n_nodes,), jnp.int32),
        key=_spec(rep, (), jax.random.key(0).dtype),
        t=_spec(rep, (), jnp.int32), stats_version=_spec(rep, (), jnp.int32),
        member=_spec(node, (n_nodes,), jnp.bool_),
        cursor=_spec(rep, (), jnp.int32))
    return deleda.train_steps.lower(
        cfg, state, _spec(node, (n_nodes, 8, L), jnp.int32),
        _spec(node, (n_nodes, 8, L), jnp.bool_),
        _spec(rep, (seg, n_nodes), jnp.int32),
        _spec(rep, (seg, n_nodes)), _spec(rep, (seg, n_nodes), jnp.bool_),
        record_every=seg)


def _mesh_train_steps_text(topo, n_nodes: int) -> str:
    """The node-sharded ``train_steps`` segment for four chips, compiled."""
    text = _mesh_train_steps(Mesh(np.asarray(topo.devices), ("data",)),
                             n_nodes).compile().as_text()
    assert text.count("collective-permute-start(") == 3
    assert text.count(" all-reduce(") == 2
    assert "all-gather" not in text and "all-to-all" not in text
    return text


def test_mesh_train_steps_compiles(topo, one_chip):
    """The node-sharded ``train_steps`` segment for four chips: the round
    body's three ppermute passes and the record's two all-reduces are its
    only collectives, and the statistic is never gathered across chips.
    With 256 nodes a chip the in-block mix and each pass still fetch
    partner rows by a HIGHEST contraction, not a row gather."""
    text = _mesh_train_steps_text(topo, N_NODES)
    assert not any(_MIX_ROW_GATHER.search(ln) for ln in text.splitlines())
    assert _mix_contractions(text) == 4


def test_mesh_train_steps_mix_is_highest_contraction(topo, one_chip):
    """With the cells' 32 nodes a chip, the in-block mix and each of the
    three passes fetch partner rows by a HIGHEST contraction, and no f32
    row gather is left under ``deleda.mix``."""
    text = _mesh_train_steps_text(topo, 128)
    assert not any(_MIX_ROW_GATHER.search(ln) for ln in text.splitlines())
    assert _mix_contractions(text) == 4
    _assert_no_reduce_with_contraction(text)


def test_node_batched_scatter_has_no_relayout_loop(one_chip):
    """The node-batched statistic scatter at PubMed's vocabulary: XLA
    unflattens the lane-padded [K, A*V] buffer into [A, K, V] without the
    relayout loops (one per topic row, one per chunk of nodes) that an
    unaligned V costs."""
    a, b, l, k, v = 8, 20, 256, 100, 141_043
    text = jax.jit(lambda w, p, m: estep.stats_per_node(w, p, v, m)).lower(
        _spec(one_chip, (a, b, l), jnp.int32), _spec(one_chip, (a, b, l, k)),
        _spec(one_chip, (a, b, l))).compile().as_text()
    assert "while(" not in text
