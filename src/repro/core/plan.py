"""Probe-plan compiler — per-(scope, event set) moment plans, spec → kernel.

ScALPEL's core claim is *selective* monitoring: the active event set of a
function changes at run time, yet the monitored path should only pay for
what that set needs.  Before this layer the probe path computed the UNION
of raw moments across every event set inside every ``lax.switch`` branch —
a sparse active set (say ACT_MAX_ABS alone) still swept six channels over
the tensor.  The same per-function-selectivity discipline LIKWID and Scaler
apply to keep always-on monitoring cheap applies here: compile, per (scope,
event set), exactly the work that set performs.

Compiled artifacts (all static / trace-time, cached on the hashable frozen
context objects):

* ``MomentPlan`` — one per (scope context, available probe tensors, event
  set): which slots are live, which finalize from the shared channel sweep
  (and from which probe tensor), which run their bespoke ``fn``, and the
  EXACT per-tensor channel tuples to sweep — including the optional
  ``ent_sum`` entropy channel that folds ATTN_ENTROPY into the same pass.
* ``ScopePlans`` — the per-scope bundle of MomentPlans plus the scope's slot
  width (the dense vector a probe branch scatters into).
* ``SlotLayout`` — the spec-wide dense slot→scatter layout: each scope's
  slots packed contiguously into one flat vector of ``total`` live slots.
  ``CompactDelta`` rides this layout through ``lax.scan`` carries so stacked
  layers sum ``total`` lanes per iteration instead of a padded
  ``[n_scopes, max_slots]`` block, and expands to a full ``CounterState``
  once at region exit.
* ``spec_fingerprint`` — a stable hash over the compiled plans; part of the
  spec's identity so reports/telemetry can attest which plan produced a
  counter stream, and config hot-swaps (mask/period changes — dynamic
  inputs) demonstrably leave it, and the traced graph, untouched.

``union=True`` compiles the pre-plan behaviour (every set sweeps the union
of channels across all sets) — kept as the reference the tests compare
per-set plans against (``tests/test_probe_reduce.py``), not a supported hot
path.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from . import events as events_lib
from .context import MonitorSpec, ScopeContext
from .counters import CounterState


@dataclasses.dataclass(frozen=True)
class PlanSlot:
    """One live slot of a plan: where it scatters and how it is evaluated.

    ``tensor``: the probe tensor a fused slot finalizes from ("" for bespoke
    slots, which receive the full probe-tensor dict).
    """

    index: int          # slot index within the scope context
    tensor: str
    fused: bool         # True: finalizer over the channel sweep


@dataclasses.dataclass(frozen=True)
class TensorSweep:
    """One probed tensor and the exact channels this event set sweeps."""

    tensor: str
    channels: tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class MomentPlan:
    """The compiled probe work of ONE (scope, event set) pair."""

    scope: str
    set_index: int
    slots: tuple[PlanSlot, ...]     # live slots, ascending index
    sweeps: tuple[TensorSweep, ...]  # per-tensor exact channel requirements

    @property
    def members(self) -> tuple[int, ...]:
        return tuple(s.index for s in self.slots)

    @property
    def sweep_channel_count(self) -> int:
        """Data-pass channels this set pays for (static channels are free)."""
        return sum(
            1 for sw in self.sweeps for c in sw.channels
            if c in events_lib.SWEEP_CHANNELS
        )

    def describe(self) -> str:
        slots = ", ".join(
            ("~" if not s.fused else "") + str(s.index) for s in self.slots
        )
        sweeps = "; ".join(
            f"{sw.tensor or '<probe>'}:[{','.join(sw.channels)}]"
            for sw in self.sweeps
        )
        return f"set {self.set_index}: slots [{slots}] sweeps {sweeps or '-'}"


@dataclasses.dataclass(frozen=True)
class ScopePlans:
    """Per-scope bundle: one MomentPlan per event set + the branch width.

    ``bodies``/``branch_index`` carry the *deduplicated* branch table: sets
    whose plans perform identical work — same slot events in the same order,
    same exact channel sweeps — share ONE ``lax.switch`` branch body; only
    the scatter footprint (the member indices) differs between them and is
    threaded through the switch as data.  Compile time per scope grows with
    ``n_branches``, not ``n_sets``.
    """

    scope: str
    width: int                      # len(ctx.slots): the branch vector width
    plans: tuple[MomentPlan, ...]
    # dedup table: branch_index[k] names the body plan set k executes
    bodies: tuple[MomentPlan, ...] = ()
    branch_index: tuple[int, ...] = ()

    @property
    def n_sets(self) -> int:
        return len(self.plans)

    @property
    def n_branches(self) -> int:
        return len(self.bodies)

    @property
    def plans_deduped(self) -> int:
        """Event sets that reuse another set's branch body."""
        return self.n_sets - self.n_branches

    @property
    def any_live(self) -> bool:
        return any(p.slots for p in self.plans)

    @property
    def member_table(self) -> tuple[tuple[int, ...], ...]:
        """Per-set member indices, zero-padded to the widest set.

        The dynamic operand of the deduped switch: a shared branch body
        reads its set's scatter indices from this table instead of baking
        them in (``midx[:len(body.slots)]`` — the count is static per body).
        """
        w = max((len(p.members) for p in self.plans), default=0)
        return tuple(
            p.members + (0,) * (w - len(p.members)) for p in self.plans
        )


def _bind_tensor(spec, avail: frozenset | None) -> str:
    """The probe tensor a per-tensor slot binds to (static describe mode
    binds unqualified slots to the anonymous '<probe>' tensor '')."""
    if spec.tensor:
        return spec.tensor
    if avail is None:
        return ""
    (name,) = tuple(avail)
    return name


@functools.lru_cache(maxsize=None)
def compile_scope_plans(
    ctx: ScopeContext, avail: frozenset | None = None, union: bool = False
) -> ScopePlans:
    """Compile one MomentPlan per event set of ``ctx``.

    ``avail``: the probe tensor names this probe call provides (a scope may
    probe several times per invocation with different tensors; only the
    slots those tensors satisfy are live).  ``None`` = static mode: assume
    every slot computable — used for fingerprints and description, where no
    concrete probe call exists.

    ``union=True`` widens every set's sweeps to the union of channels over
    ALL sets (the pre-plan behaviour, kept as the tests' reference).
    """
    def live(i) -> bool:
        if avail is None:
            return True
        return events_lib.computable(ctx.slots[i], avail)

    # per-tensor channel union across ALL sets (the baseline's sweep)
    union_channels: dict[str, tuple[str, ...]] = {}
    if union:
        by_tensor: dict[str, list] = {}
        for i, s in enumerate(ctx.slots):
            if live(i) and events_lib.moment_based(s):
                by_tensor.setdefault(_bind_tensor(s, avail), []).append(s)
        union_channels = {
            t: events_lib.channels_for(ss) for t, ss in by_tensor.items()
        }

    plans = []
    for k, members in enumerate(ctx.event_sets):
        slots: list[PlanSlot] = []
        set_by_tensor: dict[str, list] = {}
        for i in sorted(members):
            if not live(i):
                continue
            s = ctx.slots[i]
            if events_lib.moment_based(s):
                t = _bind_tensor(s, avail)
                slots.append(PlanSlot(index=i, tensor=t, fused=True))
                set_by_tensor.setdefault(t, []).append(s)
            else:
                slots.append(PlanSlot(index=i, tensor="", fused=False))
        sweeps = tuple(
            TensorSweep(
                tensor=t,
                channels=(
                    union_channels[t] if union
                    else events_lib.channels_for(ss)
                ),
            )
            for t, ss in sorted(set_by_tensor.items())
        )
        plans.append(
            MomentPlan(scope=ctx.scope, set_index=k, slots=tuple(slots),
                       sweeps=sweeps)
        )
    # Dedup: two sets share a branch body iff they evaluate the same events
    # over the same tensors with the same exact sweeps — everything except
    # WHERE the results scatter, which the switch receives as data.
    bodies: list[MomentPlan] = []
    body_of: dict = {}
    branch_index: list[int] = []
    for p in plans:
        key = (
            tuple((ctx.slots[s.index], s.tensor, s.fused) for s in p.slots),
            p.sweeps,
        )
        j = body_of.get(key)
        if j is None:
            j = len(bodies)
            body_of[key] = j
            bodies.append(p)
        branch_index.append(j)
    return ScopePlans(
        scope=ctx.scope, width=max(1, len(ctx.slots)), plans=tuple(plans),
        bodies=tuple(bodies), branch_index=tuple(branch_index),
    )


# ---------------------------------------------------------------------------
# Spec-wide dense slot layout + compact scan-carry counters
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SlotLayout:
    """Dense slot→scatter layout of a MonitorSpec.

    Scope ``i``'s slots occupy ``[offsets[i], offsets[i] + widths[i])`` of a
    flat ``total``-lane vector — the live-slot footprint a scan carry sums
    per iteration, instead of the padded ``[n_scopes, max_slots]`` block.
    """

    offsets: tuple[int, ...]
    widths: tuple[int, ...]
    total: int

    @functools.cached_property
    def scatter_indices(self) -> tuple[np.ndarray, np.ndarray]:
        """(scope_ids, slot_ids) mapping flat lanes to [n_scopes, max_slots]."""
        scope_ids = np.concatenate(
            [np.full((w,), i, np.int32) for i, w in enumerate(self.widths)]
        ) if self.total else np.zeros((0,), np.int32)
        slot_ids = np.concatenate(
            [np.arange(w, dtype=np.int32) for w in self.widths]
        ) if self.total else np.zeros((0,), np.int32)
        return scope_ids, slot_ids


@functools.lru_cache(maxsize=None)
def spec_layout(spec: MonitorSpec) -> SlotLayout:
    """The spec's dense lane layout.

    **Lane ordering is a wire contract**: lanes run in ``spec.contexts``
    declaration order, each scope contributing its slots in ``ctx.slots``
    order.  The fleet wire format (repro.telemetry.wire) ships flat
    ``CompactDelta`` payloads in exactly this order and identifies the
    producing layout by ``spec_fingerprint`` — any change to this ordering
    is a wire-format break and must change the fingerprint (it does: the
    fingerprint hashes ``describe_plans``, which walks the same order).
    """
    widths = tuple(len(c.slots) for c in spec.contexts)
    offsets, off = [], 0
    for w in widths:
        offsets.append(off)
        off += w
    return SlotLayout(offsets=tuple(offsets), widths=widths, total=off)


@functools.lru_cache(maxsize=None)
def lane_slot_ids(spec: MonitorSpec) -> tuple[tuple[str, str], ...]:
    """Per flat lane, the (scope, slot_id) it carries — the human-readable
    side of the wire contract above.  ``lane_slot_ids(spec)[i]`` labels
    lane ``i`` of any ``CompactDelta``/wire frame produced under ``spec``.
    """
    out = []
    for ctx in spec.contexts:
        for slot in ctx.slots:
            out.append((ctx.scope, slot.slot_id))
    return tuple(out)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CompactDelta:
    """Counter delta in the dense slot layout — the scan-carry form.

    calls    [n_scopes]  i32
    values   [total]     f32  (SlotLayout order)
    samples  [total]     i32
    """

    calls: jnp.ndarray
    values: jnp.ndarray
    samples: jnp.ndarray

    @staticmethod
    def zeros(spec: MonitorSpec) -> "CompactDelta":
        lay = spec_layout(spec)
        return CompactDelta(
            calls=jnp.zeros((spec.n_scopes,), jnp.int32),
            values=jnp.zeros((lay.total,), jnp.float32),
            samples=jnp.zeros((lay.total,), jnp.int32),
        )

    def add(self, other: "CompactDelta") -> "CompactDelta":
        return CompactDelta(
            calls=self.calls + other.calls,
            values=self.values + other.values,
            samples=self.samples + other.samples,
        )

    def sub(self, other: "CompactDelta") -> "CompactDelta":
        """Delta-decode (telemetry): counters accumulated since ``other``."""
        return CompactDelta(
            calls=self.calls - other.calls,
            values=self.values - other.values,
            samples=self.samples - other.samples,
        )

    def psum(self, axis_names) -> "CompactDelta":
        """Cross-shard reduction over mapped mesh axes (shard_map/pmap)."""
        return CompactDelta(
            calls=jax.lax.psum(self.calls, axis_names),
            values=jax.lax.psum(self.values, axis_names),
            samples=jax.lax.psum(self.samples, axis_names),
        )

    def expand(self, spec: MonitorSpec) -> CounterState:
        """Scatter the flat footprint back into a full CounterState."""
        lay = spec_layout(spec)
        n, m = spec.n_scopes, spec.max_slots
        values = jnp.zeros((n, m), jnp.float32)
        samples = jnp.zeros((n, m), jnp.int32)
        if lay.total:
            sids, slids = lay.scatter_indices
            values = values.at[sids, slids].set(self.values)
            samples = samples.at[sids, slids].set(self.samples)
        return CounterState(calls=self.calls, values=values, samples=samples)

    @staticmethod
    def compress(spec: MonitorSpec, state: CounterState) -> "CompactDelta":
        """Gather a full CounterState into the dense layout (one gather)."""
        lay = spec_layout(spec)
        if not lay.total:
            return CompactDelta(
                calls=state.calls,
                values=jnp.zeros((0,), jnp.float32),
                samples=jnp.zeros((0,), jnp.int32),
            )
        sids, slids = lay.scatter_indices
        return CompactDelta(
            calls=state.calls,
            values=state.values[sids, slids],
            samples=state.samples[sids, slids],
        )


# ---------------------------------------------------------------------------
# Sentinel sets — the compiled detector-lane table of the adaptive ladder
# ---------------------------------------------------------------------------

# detector kinds the adaptive controller runs over drained deltas
DETECT_TRIPWIRE = "tripwire"    # any positive delta trips (NaN/Inf counts)
DETECT_SPIKE = "spike"          # |x - EWMA| > sigma * MAD (zero fractions)
DETECT_COLLAPSE = "collapse"    # EWMA - x > sigma * MAD (entropy collapse)

_DETECTOR_BY_EVENT = {
    "NAN_COUNT": DETECT_TRIPWIRE,
    "INF_COUNT": DETECT_TRIPWIRE,
    "ACT_ZERO_FRAC": DETECT_SPIKE,
    "ATTN_ENTROPY": DETECT_COLLAPSE,
}


@dataclasses.dataclass(frozen=True)
class SentinelLane:
    """One anomaly-detector lane of a scope: which flat dense-layout lane
    to read off a drained ``CompactDelta`` and which detector to run."""

    scope: str
    scope_index: int
    slot_index: int     # slot index within the scope context
    lane: int           # flat SlotLayout lane (compact values/samples index)
    slot_id: str
    detector: str       # DETECT_TRIPWIRE | DETECT_SPIKE | DETECT_COLLAPSE

    @property
    def key(self) -> int:
        """Stable baseline key (the flat lane is unique spec-wide)."""
        return self.lane


@dataclasses.dataclass(frozen=True)
class SentinelSet:
    """A scope's compiled detector lanes — empty when the scope computes no
    detector-capable events (such scopes can only be woken by the global
    step-time detector)."""

    scope: str
    scope_index: int
    lanes: tuple[SentinelLane, ...]


@functools.lru_cache(maxsize=None)
def compile_sentinels(spec: MonitorSpec) -> tuple[SentinelSet, ...]:
    """Compile the spec's sentinel sets: per scope, the detector lanes the
    adaptive controller watches on every drained snapshot.

    Like the probe plans, this is static/trace-free and cached on the
    hashable spec: the controller pays O(#detector lanes) host arithmetic
    per drain — no report construction, no device work.  The lane index
    targets the spec-wide dense layout (``spec_layout``), i.e. the compact
    ``CompactDelta`` carriers Monitor rings snapshot; padded CounterState
    deltas are addressed via ``(scope_index, slot_index)`` instead.
    """
    lay = spec_layout(spec)
    out = []
    for si, ctx in enumerate(spec.contexts):
        lanes = []
        for i, slot in enumerate(ctx.slots):
            det = _DETECTOR_BY_EVENT.get(slot.event)
            if det is None:
                continue
            lanes.append(SentinelLane(
                scope=ctx.scope, scope_index=si, slot_index=i,
                lane=lay.offsets[si] + i, slot_id=slot.slot_id,
                detector=det,
            ))
        out.append(SentinelSet(scope=ctx.scope, scope_index=si,
                               lanes=tuple(lanes)))
    return tuple(out)


# ---------------------------------------------------------------------------
# Spec fingerprint — plans are part of the spec's identity
# ---------------------------------------------------------------------------

def describe_plans(spec: MonitorSpec, union: bool = False) -> str:
    """Human-readable plan table: scope → per-set slots + exact sweeps.

    Slot IDENTITIES (event:tensor/subevent) are spelled out per scope — the
    fingerprint hashes this text, and two specs whose slots differ only in
    which event a slot runs (e.g. two bespoke events with empty sweeps)
    must not collide.
    """
    lay = spec_layout(spec)
    lines = []
    deduped = 0
    for i, ctx in enumerate(spec.contexts):
        sp = compile_scope_plans(ctx, None, union)
        deduped += sp.plans_deduped
        ids = ", ".join(ctx.slot_ids)
        lines.append(
            f"{ctx.scope}: width {len(ctx.slots)}, {sp.n_sets} set(s), "
            f"{sp.n_branches} branch bodies, "
            f"footprint [{lay.offsets[i]}:{lay.offsets[i] + lay.widths[i]}]"
            f" slots [{ids}]"
        )
        for k, p in enumerate(sp.plans):
            lines.append(f"  {p.describe()} [body {sp.branch_index[k]}]")
    lines.append(f"total live footprint: {lay.total} slot(s)")
    lines.append(f"plans_deduped: {deduped}")
    return "\n".join(lines)


@functools.lru_cache(maxsize=None)
def spec_fingerprint(spec: MonitorSpec) -> str:
    """Stable hash over the compiled plans (scopes, sets, slots, sweeps).

    Anything that changes the traced probe graph changes this string;
    runtime mask/period/cadence swaps (dynamic inputs) do not.  Reports and
    telemetry streams carry it so a counter row is attributable to the plan
    that produced it.
    """
    text = describe_plans(spec)
    return hashlib.sha1(text.encode()).hexdigest()
