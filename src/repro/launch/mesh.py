"""Mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.

Every mesh is built with Auto axis types: the model code places tensors
with ``with_sharding_constraint`` (``dist/partition.shard``) and leaves the
rest to the compiler, which Explicit axes — ``jax.make_mesh``'s default in
the installed JAX — refuse.

Mesh axes:
  data  — batch / FSDP axis
  model — TP / vocab / expert axis (maps to the high-bandwidth ring)
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices=None):
    """A mesh with every axis Auto: over all local devices
    (``jax.make_mesh``), or over ``devices`` in order when given — a
    subset, or the devices of a described topology."""
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=types)
    return jax.sharding.Mesh(np.asarray(devices).reshape(shape), axes,
                             axis_types=types)


def make_host_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever local devices exist (tests/examples)."""
    n = len(jax.devices())
    if data is None:
        data = max(1, n // model)
    return auto_mesh((data, model), ("data", "model"))
