"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run (launch/dryrun.py) sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; everything else sees the real device count.

Every mesh is built with Auto axis types: the model code places tensors
with ``with_sharding_constraint`` (``dist/partition.shard``) and leaves the
rest to the compiler, which Explicit axes — ``jax.make_mesh``'s default in
the installed JAX — refuse.

Mesh axes:
  data  — batch / FSDP axis (16-way per pod)
  model — TP / vocab / expert axis (16-way, maps to the high-bandwidth ring)
  pod   — pod super-axis (pure DP across pods; gradient all-reduce crosses it)
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


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever local devices exist (tests/examples)."""
    n = len(jax.devices())
    if data is None:
        data = max(1, n // model)
    return auto_mesh((data, model), ("data", "model"))


def describe(mesh) -> str:
    return (
        f"mesh {dict(mesh.shape)} on {mesh.devices.size} devices "
        f"({mesh.devices.flat[0].platform})"
    )
