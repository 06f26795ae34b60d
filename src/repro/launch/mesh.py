"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run must be able to set
XLA_FLAGS before the first jax initialization.

Every mesh here has Auto axes: the program places its arrays with
explicit shardings and `shard_map`, and lets the compiler propagate the
rest (`jax.make_mesh` otherwise makes Explicit axes, which type every
array's sharding and need a `jax.set_mesh` context around each jit).
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """`jax.make_mesh` with Auto axes."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips for the multi-pod
    dry-run. Axes: (pod,) data, model."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(n_devices: int = 0, model: int = 2):
    """Small mesh over however many (possibly fake) devices exist — used
    by sharding unit tests run in subprocesses with
    xla_force_host_platform_device_count."""
    n = n_devices or len(jax.devices())
    data = n // model
    return make_mesh((data, model), ("data", "model"))


def dp_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
