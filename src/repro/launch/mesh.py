"""Production mesh builders (functions, not module constants — importing
this module never touches jax device state)."""

from __future__ import annotations

import jax

from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e: one pod = 16x16 = 256 chips; two pods add a leading axis.

    Axes: "data" (batch / FSDP), "model" (tensor/expert parallel), and
    "pod" across pods (data-parallel superaxis).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Whatever devices exist locally, as a 1-D "data" mesh (smoke/tests)."""
    n = len(jax.devices())
    return jax.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,))
