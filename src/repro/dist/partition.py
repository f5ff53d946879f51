"""Logical-axis partitioning rules and relaxation.

Model code names tensor dimensions with *logical* axes ("batch", "heads",
"mlp", ...); this module maps them onto whatever *mesh* axes exist at run
time ("pod", "data", "model") and relaxes any mapping the current mesh or
tensor shape cannot honour:

* a logical axis whose mesh axes are absent from the mesh falls back to
  replicated (None) — the same model code runs on a laptop mesh and the
  16x16 production mesh;
* a mesh axis may appear at most once in a PartitionSpec, so duplicate
  claims (e.g. "batch" and "embed" both wanting "data") keep the first
  occurrence and replicate the rest;
* ``relaxed_pspec`` additionally drops mesh axes whose size does not divide
  the dimension (heads that don't divide the TP axis, ragged vocab, ...).

``sharding_ctx(mesh)`` installs the ambient mesh; with no ambient mesh every
helper is a no-op so uninstrumented / single-device code pays nothing.
"""
from __future__ import annotations

import contextlib
import math
import threading
from typing import Any, Mapping, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec

# Logical axis -> candidate mesh axes, in order.  Mirrors the production
# mesh of launch/mesh.py: "data" is the batch/FSDP axis, "model" the
# TP/vocab/expert axis, "pod" a pure-DP super-axis.
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "groups": ("pod", "data"),
    "embed": ("data",),
    "mlp": ("model",),
    "heads": ("model",),
    "kv_seq": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "layers": (),
    # continuous-batching serve: the decode-lane slab axis (serve/driver.py
    # shard_maps its programs over a dedicated 1-D "lanes" mesh)
    "lanes": ("lanes",),
}

_TLS = threading.local()


def current_mesh():
    """The ambient mesh installed by ``sharding_ctx`` (None outside)."""
    return getattr(_TLS, "mesh", None)


@contextlib.contextmanager
def sharding_ctx(mesh, rules: Mapping[str, tuple[str, ...]] | None = None):
    """Install ``mesh`` (and optional rule overrides) as the ambient context."""
    prev = (getattr(_TLS, "mesh", None), getattr(_TLS, "rules", None))
    _TLS.mesh = mesh
    _TLS.rules = dict(DEFAULT_RULES, **(rules or {}))
    try:
        yield mesh
    finally:
        _TLS.mesh, _TLS.rules = prev


def _rules(rules=None) -> Mapping[str, tuple[str, ...]]:
    if rules is not None:
        return {**DEFAULT_RULES, **rules}
    return getattr(_TLS, "rules", None) or DEFAULT_RULES


def _entry(axis, mesh, rules, used: set) -> Any:
    """Resolve one logical axis to a PartitionSpec entry on ``mesh``."""
    if axis is None:
        return None
    cands = rules.get(axis, (axis,) if axis in mesh.shape else ())
    picked = [a for a in cands if a in mesh.shape and a not in used]
    used.update(picked)
    if not picked:
        return None
    if len(picked) == 1:
        return picked[0]
    return tuple(picked)


def logical_to_pspec(axes: Sequence[str | None], mesh=None,
                     rules=None) -> PartitionSpec:
    """Map logical axis names to a PartitionSpec for ``mesh``.

    With no mesh (argument or ambient) the result is the empty spec —
    fully replicated, usable anywhere.
    """
    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None:
        return PartitionSpec()
    rules = _rules(rules)
    used: set = set()
    return PartitionSpec(*(_entry(a, mesh, rules, used) for a in axes))


def relaxed_pspec(shape: Sequence[int], axes: Sequence[str | None], mesh,
                  rules=None) -> PartitionSpec:
    """Like ``logical_to_pspec`` but drops mesh axes that don't divide the dim.

    The relaxation the models rely on: a 5-head attention on a 4-way TP mesh
    falls back to replicated heads instead of erroring.
    """
    rules = _rules(rules)
    used: set = set()
    entries = []
    for dim, axis in zip(shape, axes):
        e = _entry(axis, mesh, rules, used)
        if e is not None:
            names = (e,) if isinstance(e, str) else e
            total = math.prod(mesh.shape[n] for n in names)
            if total == 0 or dim % total != 0:
                used.difference_update(names)
                e = None
        entries.append(e)
    return PartitionSpec(*entries)


def shard(x, *axes, rules=None):
    """Constrain ``x`` to its logical sharding under the ambient mesh.

    Outside any ``sharding_ctx`` this returns ``x`` unchanged (identity, not
    a copy) so single-device code pays nothing.
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    ps = relaxed_pspec(x.shape, axes, mesh, rules=rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, ps))


def input_sharding(shape: Sequence[int], axes: Sequence[str | None],
                   mesh=None, rules=None) -> NamedSharding:
    """NamedSharding for a host->device input of ``shape``."""
    mesh = mesh if mesh is not None else current_mesh()
    return NamedSharding(mesh, relaxed_pspec(shape, axes, mesh, rules=rules))


def tree_shardings(abs_tree, ax_tree, mesh=None, rules=None):
    """Per-leaf NamedShardings for a tree of ShapeDtypeStructs.

    ``ax_tree`` mirrors ``abs_tree`` with tuples of logical axis names at the
    leaves (tuples are leaves here, not pytree nodes).
    """
    mesh = mesh if mesh is not None else current_mesh()
    leaves, treedef = jax.tree_util.tree_flatten(abs_tree)
    ax_leaves = treedef.flatten_up_to(ax_tree)
    shs = [
        NamedSharding(mesh, relaxed_pspec(l.shape, ax, mesh, rules=rules))
        for l, ax in zip(leaves, ax_leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, shs)


def lane_mesh(n_shards: int, axis: str = "lanes"):
    """A 1-D device mesh for lane-parallel serving (the decode slab's
    ``lanes`` axis).

    Unlike the training mesh (launch/mesh.py), a serve mesh may use a
    strict SUBSET of the local devices — a 2-way lane mesh on an 8-device
    host leaves the rest to other engines — so this builds the mesh from
    the first ``n_shards`` devices rather than from all of them.
    """
    if n_shards < 1:
        raise ValueError(f"lane mesh needs >= 1 shard, got {n_shards}")
    devs = jax.devices()
    if len(devs) < n_shards:
        raise ValueError(
            f"lane mesh needs {n_shards} devices, have {len(devs)} — "
            f"reduce ServeConfig.lane_shards (or force host devices via "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    from repro.launch.mesh import auto_mesh

    return auto_mesh((n_shards,), (axis,), devices=devs[:n_shards])


def axis_size(name: str) -> int:
    """Size of mesh axis ``name`` in the ambient mesh (1 outside any ctx)."""
    mesh = current_mesh()
    if mesh is None:
        return 1
    return int(mesh.shape.get(name, 1))


def bound_axes(names: Sequence[str]) -> tuple[str, ...]:
    """The subset of ``names`` bound as *mapped* axes in the current trace.

    A mesh axis name is only psum-able from code that runs under a
    ``shard_map``/``pmap`` binding it; under plain jit-SPMD (sharded inputs,
    no per-shard body) reductions are already global and no axis is bound.
    Call this at trace time, where a psum would be issued.
    """
    out = []
    for n in names:
        try:
            jax.lax.axis_index(n)
        except NameError:
            continue
        out.append(n)
    return tuple(out)


def counter_reduce_axes(axes="auto") -> tuple[str, ...]:
    """Resolve the mesh axes a monitor should psum counters over.

    ``"auto"``: every axis of the ambient ``sharding_ctx`` mesh that is
    actually bound in the current trace — replicated-safe on a laptop
    (no mesh, or a 1-device mesh, or plain jit: nothing to reduce).
    An explicit tuple is filtered the same way, so the same wrapped step
    traces correctly inside and outside ``shard_map``.
    """
    if axes is None:
        return ()
    if axes == "auto":
        mesh = current_mesh()
        cands: tuple[str, ...] = tuple(mesh.axis_names) if mesh is not None \
            else ()
    else:
        cands = tuple(axes)
    return bound_axes(cands)
