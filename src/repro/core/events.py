"""In-graph event library — two-stage: raw moments, then scalar finalizers.

The paper reads MSR-backed counters (DTLB_MISSES, L2_LINES_IN, RESOURCE_STALLS
...) through libpfm.  On a TPU there is no user-readable MSR file, but the
*causes* the paper is after are visible to the compiler (FLOPs / bytes /
collective traffic in the compiled HLO) and to the program itself:
statistics of the live tensors flowing through each scope.  This module is the
registry of those in-graph events.

Architecture (stage 1 → stage 2)
--------------------------------
Most events are statistics of ONE probed tensor, and every one of them is a
cheap scalar function of a shared raw *channel vector*: the sweep channels

    [sum, sum_sq, sum_abs, max_abs, zero_count, nan_count, inf_count,
     ent_sum]

(kernels/probe_reduce.py — one fused pass over the tensor, Pallas on TPU;
``ent_sum`` is the optional entropy channel) plus the trace-time-constant
channels ``numel``/``rows`` that cost nothing.  Such *moment-derived* events
declare the channels they need (``moments=``) plus a *finalizer*
``(moments: dict) -> f32 scalar``, e.g. ``ACT_RMS = sqrt(sum_sq / numel)``.

This registry only declares PER-SLOT requirements; grouping them into the
per-(scope, event set) sweep a probe call actually performs is the job of
the probe-plan compiler (core/plan.py): each event set sweeps exactly the
channels ITS slots need, never the union across sets — a scope probing six
activation statistics reads its tensor from HBM once, and a sparse active
set pays only for its own channels.  Events that are NOT per-tensor channel
functions (MOE_LOAD, SSM_STATE_RMS, ...) keep their bespoke ``fn`` path.

Every event also keeps a direct (unfused) implementation ``fn: (tensor |
tensors-dict) -> f32 scalar`` — the numerical reference the planned path is
checked against (allclose: accumulation order differs between the fused
single pass and independent reductions — tests/test_probe_reduce,
tests/test_plan).

Events are tagged EXTENSIVE (accumulates by summation across calls: counts,
bytes, flops) or INTENSIVE (accumulates as a mean across monitored calls:
rms, entropy, fractions).  report.py uses the tag to turn multiplexed samples
back into exhaustive estimates, reproducing the paper's Fig. 4 methodology.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Mapping

import jax.numpy as jnp
import numpy as np

from .context import EventSpec

Array = jnp.ndarray

EXTENSIVE = "extensive"
INTENSIVE = "intensive"

# Canonical channel vocabulary.  SWEEP_CHANNELS need a pass over the data
# (kernels/probe_reduce computes them in one fused sweep; ``ent_sum`` is the
# optional entropy channel); STATIC_CHANNELS are trace-time constants of the
# tensor's shape and are always free.  kernels/probe_reduce mirrors this
# vocabulary — tests assert the two stay in sync.
SWEEP_CHANNELS = (
    "sum",
    "sum_sq",
    "sum_abs",
    "max_abs",
    "zero_count",
    "nan_count",
    "inf_count",
    "ent_sum",
)
STATIC_CHANNELS = ("numel", "rows")
CHANNELS = SWEEP_CHANNELS + STATIC_CHANNELS


@dataclasses.dataclass(frozen=True)
class EventDef:
    name: str
    kind: str  # EXTENSIVE | INTENSIVE
    fn: Callable[..., Array]  # (tensor) or (tensors-dict) — see wants_dict
    wants_dict: bool = False  # True: fn(tensors, subevent); False: fn(tensor)
    subevents: tuple[str, ...] = ()
    requires: tuple[str, ...] = ()  # probe tensor names a dict-event needs
    # stage-2 half of moment-derived events: which raw moments stage 1 must
    # provide, and the scalar finalizer over them.  Empty/None = bespoke.
    moments: tuple[str, ...] = ()
    finalize: Callable[[Mapping[str, Array]], Array] | None = None
    doc: str = ""


_REGISTRY: dict[str, EventDef] = {}


def register(
    name: str,
    kind: str,
    *,
    wants_dict: bool = False,
    subevents: tuple[str, ...] = (),
    requires: tuple[str, ...] = (),
    moments: tuple[str, ...] = (),
    finalize: Callable[[Mapping[str, Array]], Array] | None = None,
    doc: str = "",
):
    unknown = set(moments) - set(CHANNELS)
    if unknown:
        raise ValueError(f"event {name!r}: unknown channels {sorted(unknown)}")
    if bool(moments) != (finalize is not None):
        raise ValueError(
            f"event {name!r}: moments and finalize must be given together"
        )
    if moments and wants_dict:
        raise ValueError(f"event {name!r}: dict events cannot be moment-derived")

    def deco(fn):
        if name in _REGISTRY:
            raise ValueError(f"event {name!r} already registered")
        _REGISTRY[name] = EventDef(
            name=name, kind=kind, fn=fn, wants_dict=wants_dict,
            subevents=subevents, requires=requires, moments=moments,
            finalize=finalize, doc=doc,
        )
        return fn

    return deco


def computable(spec: EventSpec, tensor_names) -> bool:
    """Can this slot be evaluated from a probe call providing ``tensor_names``?

    A scope may issue several probe() calls per invocation (e.g. MoE probes
    router stats mid-block and 'out' at the end); each call computes only the
    slots its tensors satisfy.
    """
    ev = lookup(spec.event)
    names = set(tensor_names)
    if ev.wants_dict:
        return all(r in names for r in ev.requires)
    if spec.tensor:
        return spec.tensor in names
    return len(names) == 1


def lookup(name: str) -> EventDef:
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown event {name!r}; known: {sorted(_REGISTRY)}"
        )
    return _REGISTRY[name]


def registered() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def kind_of(spec: EventSpec) -> str:
    return lookup(spec.event).kind


def compute(spec: EventSpec, tensors: dict[str, Array]) -> Array:
    """Evaluate one event slot on the probed tensors (traced)."""
    ev = lookup(spec.event)
    if ev.wants_dict:
        val = ev.fn(tensors, spec.subevent)
    else:
        if spec.tensor:
            if spec.tensor not in tensors:
                raise KeyError(
                    f"event {spec.slot_id}: probe tensor {spec.tensor!r} not "
                    f"provided (have {sorted(tensors)})"
                )
            x = tensors[spec.tensor]
        else:
            if len(tensors) != 1:
                raise KeyError(
                    f"event {spec.event} needs an explicit ':tensor' qualifier "
                    f"when the scope probes multiple tensors {sorted(tensors)}"
                )
            (x,) = tensors.values()
        val = ev.fn(x)
    return jnp.asarray(val, jnp.float32)


# --------------------------------------------------------------------------
# Two-stage evaluation helpers — consumed by the probe-plan compiler
# (core/plan.py) and the planned probe path (instrument.Collector.probe).
# --------------------------------------------------------------------------

def moment_based(spec: EventSpec) -> bool:
    """Is this slot a stage-2 finalizer over the shared channel sweep?"""
    ev = lookup(spec.event)
    return ev.finalize is not None and not ev.wants_dict


def slot_channels(spec: EventSpec) -> tuple[str, ...]:
    """The raw channels ONE slot needs (empty for bespoke events)."""
    return lookup(spec.event).moments


def channels_for(specs) -> tuple[str, ...]:
    """Exact channels the given slot group needs, in canonical order.

    The probe-plan compiler (core/plan.py) calls this PER EVENT SET — the
    resulting sweep covers only what the active set's slots finalize from,
    not the union across every set of the scope.
    """
    need: set[str] = set()
    for s in specs:
        need.update(lookup(s.event).moments)
    return tuple(m for m in CHANNELS if m in need)


def finalize_event(spec: EventSpec, moments: Mapping[str, Array]) -> Array:
    """Stage 2: one event value from the shared moment vector (traced)."""
    ev = lookup(spec.event)
    if ev.finalize is None:
        raise TypeError(f"event {spec.event!r} is not moment-derived")
    missing = [m for m in ev.moments if m not in moments]
    if missing:
        raise KeyError(
            f"event {spec.event}: moments {missing} not provided "
            f"(have {sorted(moments)})"
        )
    return jnp.asarray(ev.finalize(moments), jnp.float32)


# --------------------------------------------------------------------------
# Generic per-tensor events (apply to any probed tensor via "NAME:tensor").
# --------------------------------------------------------------------------

def _f32(x: Array) -> Array:
    return x.astype(jnp.float32)


@register(
    "ACT_RMS", INTENSIVE, moments=("sum_sq", "numel"),
    finalize=lambda m: jnp.sqrt(m["sum_sq"] / m["numel"] + 1e-30),
    doc="root-mean-square of the tensor",
)
def _act_rms(x):
    return jnp.sqrt(jnp.mean(jnp.square(_f32(x))) + 1e-30)


@register(
    "ACT_MEAN_ABS", INTENSIVE, moments=("sum_abs", "numel"),
    finalize=lambda m: m["sum_abs"] / m["numel"],
    doc="mean |x|",
)
def _act_mean_abs(x):
    return jnp.mean(jnp.abs(_f32(x)))


@register(
    "ACT_MAX_ABS", INTENSIVE, moments=("max_abs",),
    finalize=lambda m: m["max_abs"],
    doc="max |x| (overflow watch)",
)
def _act_max_abs(x):
    return jnp.max(jnp.abs(_f32(x)))


@register(
    "ACT_ZERO_FRAC", INTENSIVE, moments=("zero_count", "numel"),
    finalize=lambda m: m["zero_count"] / m["numel"],
    doc="fraction of exact zeros (sparsity)",
)
def _act_zero_frac(x):
    return jnp.mean((x == 0).astype(jnp.float32))


@register(
    "NAN_COUNT", EXTENSIVE, moments=("nan_count",),
    finalize=lambda m: m["nan_count"],
    doc="number of NaN entries",
)
def _nan_count(x):
    return jnp.sum(jnp.isnan(_f32(x)).astype(jnp.float32))


@register(
    "INF_COUNT", EXTENSIVE, moments=("inf_count",),
    finalize=lambda m: m["inf_count"],
    doc="number of +-Inf entries",
)
def _inf_count(x):
    return jnp.sum(jnp.isinf(_f32(x)).astype(jnp.float32))


@register(
    "NUMEL", EXTENSIVE, moments=("numel",),
    finalize=lambda m: m["numel"],
    doc="number of elements seen (token/elt count)",
)
def _numel(x):
    return jnp.float32(np.prod(x.shape) if x.shape else 1)


@register(
    "L2NORM", INTENSIVE, moments=("sum_sq",),
    finalize=lambda m: jnp.sqrt(m["sum_sq"] + 1e-30),
    doc="L2 norm of the tensor",
)
def _l2norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(_f32(x))) + 1e-30)


@register(
    "MEAN", INTENSIVE, moments=("sum", "numel"),
    finalize=lambda m: m["sum"] / m["numel"],
    doc="mean value",
)
def _mean(x):
    return jnp.mean(_f32(x))


# --------------------------------------------------------------------------
# Specialized events (bind to specific probe names).
# --------------------------------------------------------------------------

@register(
    "ATTN_ENTROPY", INTENSIVE, moments=("ent_sum", "rows"),
    finalize=lambda m: -m["ent_sum"] / m["rows"],
    doc="mean entropy (nats) of attention rows; probe tensor = probabilities "
        "over the last axis.  Fused: rides the sweep's optional ent_sum "
        "channel (sum of p*log(p+eps)) divided by the static row count",
)
def _attn_entropy(p):
    p = _f32(p)
    return jnp.mean(-jnp.sum(p * jnp.log(p + 1e-9), axis=-1))


@register(
    "MOE_LOAD", INTENSIVE, wants_dict=True,
    subevents=("MAX_FRAC", "MIN_FRAC", "CV", "AUX_LOSS"),
    requires=("router_probs",),
    doc="expert load statistics; needs probe 'router_probs' "
        "[tokens, experts] and optionally 'expert_mask' [tokens, experts]",
)
def _moe_load(tensors, subevent):
    probs = _f32(tensors["router_probs"])  # [tokens, experts]
    if "expert_mask" in tensors:
        load = jnp.mean(_f32(tensors["expert_mask"]), axis=0)  # frac per expert
    else:
        load = jnp.mean(probs, axis=0)
    n_e = load.shape[-1]
    if subevent == "MAX_FRAC":
        return jnp.max(load) * n_e  # 1.0 == perfectly balanced
    if subevent == "MIN_FRAC":
        return jnp.min(load) * n_e
    if subevent == "CV":
        return jnp.std(load) / (jnp.mean(load) + 1e-9)
    if subevent == "AUX_LOSS":
        # Switch-transformer style load-balancing loss.
        importance = jnp.mean(probs, axis=0)
        return jnp.float32(n_e) * jnp.sum(load * importance)
    raise KeyError(f"MOE_LOAD subevent {subevent!r}")


@register(
    "SSM_STATE_RMS", INTENSIVE,
    doc="RMS of the recurrent state (probe 'state')",
)
def _ssm_state_rms(x):
    return jnp.sqrt(jnp.mean(jnp.square(_f32(x))) + 1e-30)


@register(
    "GRAD_GLOBAL_NORM", INTENSIVE, moments=("sum_sq",),
    finalize=lambda m: jnp.sqrt(m["sum_sq"] + 1e-30),
    doc="global norm of a gradient tensor (probe per-group flattened grads)",
)
def _grad_global_norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(_f32(x))) + 1e-30)


# --------------------------------------------------------------------------
# Static "cost-model" events: per-call constants supplied by the scope at
# probe time (e.g. a Pallas kernel reporting its schedule's HBM->VMEM traffic).
# These are the closest analogue of the paper's Table-2 counters for the GEMM
# case study: the *cause* metrics of a kernel schedule.
# --------------------------------------------------------------------------

def _sum_finalizer(m):
    return m["sum"]


@register("FLOPS", EXTENSIVE, moments=("sum",), finalize=_sum_finalizer,
          doc="floating-point ops (probe provides scalar)")
def _flops(x):
    return jnp.sum(_f32(x))


@register("HBM_BYTES", EXTENSIVE, moments=("sum",), finalize=_sum_finalizer,
          doc="bytes moved HBM<->VMEM by the schedule (scalar probe) — "
              "analogue of L2_LINES_IN")
def _hbm_bytes(x):
    return jnp.sum(_f32(x))


@register("VMEM_TILE_REFILLS", EXTENSIVE, moments=("sum",),
          finalize=_sum_finalizer,
          doc="number of HBM->VMEM tile fetches — analogue of DTLB_MISSES")
def _vmem_refills(x):
    return jnp.sum(_f32(x))


@register("MXU_PASSES", EXTENSIVE, moments=("sum",), finalize=_sum_finalizer,
          doc="number of 128x128 MXU systolic passes — analogue of "
              "SIMD_INST_RETIRED")
def _mxu_passes(x):
    return jnp.sum(_f32(x))


@register("EST_STALL_CYCLES", EXTENSIVE, moments=("sum",),
          finalize=_sum_finalizer,
          doc="estimated memory-stall cycles (max(0, mem_time-compute_time) "
              "* clock) — analogue of RESOURCE_STALLS")
def _stall_cycles(x):
    return jnp.sum(_f32(x))
