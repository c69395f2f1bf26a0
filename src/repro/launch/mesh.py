"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run sets the 512-host-device XLA flag
before any jax import; everything else sees the real device count).

Production target: TPU v5e pods.
  single-pod:  (16, 16)    axes ("data", "model")   = 256 chips
  multi-pod:   (2, 16, 16) axes ("pod", "data", "model") = 512 chips

sat-QFL mapping (DESIGN.md §2): a satellite = one "data" slice (16 chips of
model parallelism = the satellite's compute board); intra-pod reductions
are ISL traffic, the "pod" axis is the primary→ground feeder tier.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes GSPMD partitions from the in/out shardings and
    sharding constraints (``jax.make_mesh`` now defaults to Explicit
    axes, which this code base does not use)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Degenerate mesh on the real local device(s) — smoke tests, examples."""
    n = len(jax.devices())
    return _auto_mesh((max(n // model, 1), model), ("data", "model"))


def data_axes_for(mesh) -> tuple:
    names = mesh.axis_names
    return tuple(a for a in names if a != "model")
