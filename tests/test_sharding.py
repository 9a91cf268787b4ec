"""Sharding rules: spec_for_shape divisibility + axis-reuse properties."""

import jax
import numpy as np
import pytest
from hyputil import given, settings, st
from jax.sharding import AbstractMesh, AxisType, PartitionSpec as P

from repro.sharding import (FSDP_RULES, LOGICAL_RULES, logical_to_spec,
                            spec_for_shape)


@pytest.fixture(scope="module")
def mesh():
    # abstract mesh: no devices needed for spec computation
    return AbstractMesh((4, 2), ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)


def test_basic_rules(mesh):
    spec = logical_to_spec(("vocab", "embed"), mesh)
    assert spec == P("model", None)
    spec = logical_to_spec(("batch", "seq"), mesh)
    assert spec == P("data", None)   # "pod" absent on this mesh


def test_no_axis_reuse(mesh):
    # heads and kv_heads both map to model; only the first may take it
    spec = logical_to_spec(("heads", "kv_heads"), mesh)
    assert spec == P("model", None)


def test_divisibility_fallback(mesh):
    # kv_heads=3 cannot shard over model=2 -> replicated
    spec = spec_for_shape((8, 3, 16), ("embed", "kv_heads", "head_dim"),
                          mesh)
    assert spec == P(None, None, None)
    spec = spec_for_shape((8, 4, 16), ("embed", "kv_heads", "head_dim"),
                          mesh)
    assert spec == P(None, "model", None)


def test_cache_seq_fallback(mesh):
    # kv_heads indivisible -> cache_seq absorbs "model"
    spec = spec_for_shape((4, 64, 3, 8),
                          ("batch", "cache_seq", "kv_heads", "head_dim"),
                          mesh)
    assert spec == P("data", "model", None, None)
    # kv_heads divisible -> it wins, cache_seq replicated
    spec = spec_for_shape((4, 64, 4, 8),
                          ("batch", "cache_seq", "kv_heads", "head_dim"),
                          mesh)
    assert spec == P("data", None, "model", None)
    # tiny batch can't shard either -> fully replicated except cache_seq
    spec = spec_for_shape((2, 64, 3, 8),
                          ("batch", "cache_seq", "kv_heads", "head_dim"),
                          mesh)
    assert spec == P(None, "model", None, None)


@given(st.lists(st.sampled_from(
    ["batch", "seq", "vocab", "heads", "kv_heads", "mlp", "embed",
     "experts", "layers", "head_dim", "cache_seq"]),
    min_size=1, max_size=4),
    st.lists(st.integers(1, 64), min_size=4, max_size=4))
@settings(max_examples=50, deadline=None)
def test_spec_always_valid(axes, dims):
    mesh = AbstractMesh((4, 2), ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)
    axes = tuple(axes)
    shape = tuple(dims[:len(axes)])
    spec = spec_for_shape(shape, axes, mesh, LOGICAL_RULES)
    sizes = {"data": 4, "model": 2}
    used = []
    for dim, part in zip(shape, tuple(spec)):
        if part is None:
            continue
        names = (part,) if isinstance(part, str) else part
        total = 1
        for nm in names:
            assert nm not in used, "mesh axis used twice"
            used.append(nm)
            total *= sizes[nm]
        assert dim % total == 0, "invalid divisibility"


def test_dp_only_rules_batch_absorbs_mesh(mesh):
    from repro.sharding import DP_ONLY_RULES
    # batch takes BOTH axes; weight axes replicate
    spec = spec_for_shape((8, 16), ("batch", "seq"), mesh, DP_ONLY_RULES)
    assert spec == P(("data", "model"), None)
    spec = spec_for_shape((64, 32), ("embed", "mlp"), mesh, DP_ONLY_RULES)
    assert spec == P(None, None)
    # batch not divisible by the full product -> takes what divides
    spec = spec_for_shape((4, 16), ("batch", "seq"), mesh, DP_ONLY_RULES)
    assert spec == P("data", None)


def test_fsdp_rules_shard_embed(mesh):
    spec = spec_for_shape((256, 8), ("embed", "heads"), mesh, FSDP_RULES)
    assert spec == P("data", "model")
    spec_base = spec_for_shape((256, 8), ("embed", "heads"), mesh,
                               LOGICAL_RULES)
    assert spec_base == P(None, "model")
