"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state - jax locks the device count on
first backend init, and only launch/dryrun.py sets the 512-device
emulation flag.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_serving_mesh(*, chain: int = 4, multi_pod: bool = False):
    """Serving mesh with an explicit chain-replication axis carved out of
    the data axis: (chain, data, model)."""
    if multi_pod:
        shape = (2, chain, 16 // chain, 16)
        axes = ("pod", "chain", "data", "model")
    else:
        shape = (chain, 16 // chain, 16)
        axes = ("chain", "data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(n: int | None = None, axis: str = "chain"):
    """Small mesh over whatever devices exist (tests/examples)."""
    n = n or len(jax.devices())
    return jax.make_mesh((n,), (axis,), axis_types=(AxisType.Auto,))
