"""Multi-device mesh gossip == simulation substrate (subprocess: needs
XLA_FLAGS device-count override before jax init, which pytest's process
has already passed)."""

import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.core import decentralized as dec

    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
    for spec_str in ["allreduce", "gossip-hypercube",
                     "gossip-hypercube[1]", "gossip-ring[2]"]:
        spec = dec.parse_sync(spec_str)
        f = lambda v: dec.sync_tree_mesh(v, spec, ("data",), (8,))
        y = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                                  out_specs=P("data")))(x)
        ysim = dec.sync_tree_sim(x, spec, 8)
        err = float(jnp.abs(y - ysim).max())
        assert err < 1e-5, (spec_str, err)
        if dec.is_exact(spec, (8,)):
            cerr = float(jnp.abs(y - x.mean(0, keepdims=True)).max())
            assert cerr < 1e-5, (spec_str, cerr)
    print("MESH_OK")
""")


@pytest.mark.slow
def test_mesh_gossip_matches_simulation():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "MESH_OK" in r.stdout, r.stderr[-2000:]


DRYRUN_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    from jax.sharding import AxisType
    from repro.configs import get_config, smoke_variant
    from repro.configs.base import InputShape
    from repro.launch import steps as steps_mod

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    cfg = smoke_variant(get_config("granite_3_8b"))
    for shape in [InputShape("t", 32, 8, "train"),
                  InputShape("d", 32, 8, "decode")]:
        step = steps_mod.build(cfg, shape, mesh)
        step.lower().compile()
    print("DRYRUN_OK")
""")


@pytest.mark.slow
def test_multipod_mesh_lowering_smoke():
    """A 3-axis (pod, data, model) mesh lowers+compiles the same steps the
    512-chip dry-run uses (scaled to 8 host devices)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", DRYRUN_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "DRYRUN_OK" in r.stdout, r.stderr[-2000:]
