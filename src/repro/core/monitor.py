"""Functional ``Monitor`` transformation — one pytree, compact end-to-end.

The paper's promise is *transparent* monitoring: no source modifications
beyond naming scopes.  A bare collection region leaves a seam open — every
call site has to hand-thread counters (``state = state.add(col.delta)``) and
nobody aggregates them across devices.  This module closes both:

* ``mon = Monitor(spec, params, telemetry=...)`` and ``step = mon.wrap(fn)``
  (or ``@monitored(spec)``) turn an ordinary step function into a pure
  function of ONE ``MonitorState`` pytree: compact dense counters
  (``plan.SlotLayout`` lanes — never the padded ``[n_scopes, max_slots]``
  block), the telemetry snapshot ring, the step stamp, and the runtime
  ``MonitorParams``/``TelemetryParams``.  The pytree threads through ``jit``,
  ``scan_with_counters`` and nested calls; user code never touches
  ``col.delta`` again.

* Inside ``wrap`` the step's counter delta is cross-device-reduced with
  ``lax.psum`` over whatever mesh axes ``dist/partition.py`` resolves AND the
  current trace actually binds (``counter_reduce_axes``): under ``shard_map``
  each shard's counters sum into cluster-wide totals — the paper's "MPI
  support", now in the transport; under plain jit (already-global semantics)
  or on a 1-device laptop mesh the reduction resolves to a no-op, so the
  same wrapped step runs anywhere.

* Counters stay COMPACT end-to-end: the collector's delta, the accumulate,
  the ring snapshot, and ``report.build``/``estimates`` all work in the
  spec-wide dense layout; the per-step expand to the padded block that the
  legacy path paid per ``capture()`` is gone (``CounterState`` survives as a
  convertible view — ``Monitor.counter_state``/``CounterState.from_compact``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

# NOTE: the package re-exports a *function* named ``instrument``, which
# shadows the module attribute — import the needed symbols directly.
from .instrument import Collector, _stack
from . import plan as plan_lib
from . import report as report_lib
from . import telemetry as telemetry_lib
from .context import MonitorSpec
from .counters import CounterState, MonitorParams

Array = Any


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("calls", "values", "samples", "sched_calls", "step", "ring",
                 "params", "tparams"),
    meta_fields=("fingerprint",),
)
@dataclasses.dataclass
class MonitorState:
    """The one pytree a wrapped step threads — counters compact end-to-end.

    calls       [n_scopes] i32 — interception counts, mesh-REDUCED (what
                reports scale estimates by: cluster-wide totals)
    values      [total]    f32 — accumulated event values, SlotLayout lanes
    samples     [total]    i32 — monitored-call counts, SlotLayout lanes
    sched_calls [n_scopes] i32 | None — PER-SHARD interception counts: the
                multiplex schedule base.  Never mesh-reduced — under
                ``shard_map`` every shard advances its own schedule by its
                own calls; feeding the psum-reduced totals back into
                ``(calls // period) % n_sets`` would skip event sets on
                every multi-device mesh.  ``None`` (and ``calls`` doubles
                as the base) when the monitor performs no reduction
                (``counter_axes=()``): with nothing reduced the two would
                be identical lanes, and the state should not pay for both.
    step        scalar     i32 — wrapped-step stamp (telemetry cadence input)
    ring        SnapshotRing | None — compact-layout telemetry ring
    params      MonitorParams    — runtime masks/periods (dynamic: no re-trace)
    tparams     TelemetryParams  — ring-append cadence (dynamic: no re-trace)

    ``fingerprint`` is static metadata (a jit-constant string): the hash of
    the compiled probe plans that produced these counters — carried so
    checkpoints can attest plan identity at resume (``save_metadata``).
    """

    calls: Array
    values: Array
    samples: Array
    sched_calls: Array | None
    step: Array
    ring: telemetry_lib.SnapshotRing | None
    params: MonitorParams
    tparams: telemetry_lib.TelemetryParams
    fingerprint: str = ""

    @property
    def counters(self) -> plan_lib.CompactDelta:
        """The cumulative counters as a compact (dense-layout) pytree."""
        return plan_lib.CompactDelta(
            calls=self.calls, values=self.values, samples=self.samples
        )

    def save_metadata(self) -> dict:
        """Checkpoint metadata attesting which compiled plans produced the
        counters — checked against the live spec at resume
        (``ScalpelRuntime.check_resume_metadata`` / ``Monitor.check_resume``).
        """
        return {
            "plan_fingerprint": self.fingerprint,
            "monitor_step": int(jax.device_get(self.step)),
            "slot_lanes": int(self.values.shape[0]),
        }


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("lane_calls", "lane_values", "lane_samples", "lane_sched",
                 "calls", "values", "samples", "step", "ring", "params",
                 "tparams"),
    meta_fields=("fingerprint",),
)
@dataclasses.dataclass
class LaneMonitorState:
    """Per-batch-lane monitor state — the continuous-batching serve engine's
    carry.

    Scopes stay the compile-time spec; the LANE axis is what's new: every
    decode lane (one request slot in the packed slab) accumulates its own
    copy of the compact counters, so anomalies attribute to individual
    requests under load.  The aggregate lanes-summed counters are kept
    alongside in the spec's ordinary compact shapes — they are what the
    telemetry ring snapshots and the adaptive controller drains, so the
    whole existing reporting/adaptive stack works unchanged.

    lane_calls   [n_lanes, n_scopes] i32 — per-lane interception counts
    lane_values  [n_lanes, total]    f32 — per-lane event values
    lane_samples [n_lanes, total]    i32 — per-lane monitored-call counts
    lane_sched   [n_lanes, n_scopes] i32 — per-lane multiplex schedule base
                 (each lane advances its own event-set schedule; resets with
                 the lane at admission — and, like ``sched_calls``, is never
                 mesh-reduced)
    calls/values/samples — lane-summed cumulative counters (compact layout)
    step         scalar i32 — decode-step stamp (every inner megastep step)
    ring         SnapshotRing | None — aggregate-counter telemetry ring
    params/tparams — runtime knobs (dynamic inputs; megastep constants)
    """

    lane_calls: Array
    lane_values: Array
    lane_samples: Array
    lane_sched: Array
    calls: Array
    values: Array
    samples: Array
    step: Array
    ring: telemetry_lib.SnapshotRing | None
    params: MonitorParams
    tparams: telemetry_lib.TelemetryParams
    fingerprint: str = ""

    @property
    def n_lanes(self) -> int:
        return int(self.lane_calls.shape[0])

    @property
    def counters(self) -> plan_lib.CompactDelta:
        """Aggregate (lane-summed) cumulative counters — what reports, the
        ring, and the adaptive controller consume."""
        return plan_lib.CompactDelta(
            calls=self.calls, values=self.values, samples=self.samples
        )

    def lane_counters(self, lane: int) -> plan_lib.CompactDelta:
        """One lane's cumulative counters (prefill + decode so far) — the
        per-request attribution view.  Device arrays; eager slicing, so
        calling this off the host loop is async until materialized."""
        return plan_lib.CompactDelta(
            calls=self.lane_calls[lane],
            values=self.lane_values[lane],
            samples=self.lane_samples[lane],
        )


class Monitor:
    """The functional monitoring transformation over a compile-time spec.

    ``wrap(fn)`` returns ``wrapped(mstate, *args, **kw) -> (out, mstate')``:
    ``fn`` runs under a collector (its ``scalpel.function``/``probe`` calls
    land in-graph), the step's delta is mesh-reduced and folded into the
    compact counters, the step stamp advances, and — when the monitor owns a
    telemetry plane — the counters ring-append at the dynamic cadence.

    ``counter_axes``: mesh axes to ``psum`` counter deltas over.  The
    default ``"auto"`` resolves the ambient ``dist.partition`` mesh and
    reduces over whichever of its axes the trace actually binds — i.e. the
    reduction engages inside ``shard_map``/``pmap`` and melts away under
    plain jit or with no mesh (replicated-safe on a laptop).  Pass an
    explicit tuple to restrict, or ``()`` to disable.
    """

    def __init__(self, spec: MonitorSpec, params: MonitorParams | None = None,
                 *, telemetry: telemetry_lib.TelemetryPlane | None = None,
                 counter_axes="auto", plan_mode: str = "per_set"):
        self.spec = spec
        self.params = params if params is not None \
            else MonitorParams.all_on(spec)
        self.telemetry = telemetry
        self.counter_axes = counter_axes
        self.plan_mode = plan_mode

    @property
    def _carries_sched(self) -> bool:
        """Whether states carry a separate per-shard schedule base — only
        monitors that may reduce need one (otherwise ``calls`` IS it)."""
        return self.counter_axes not in ((), None)

    # -- state construction ----------------------------------------------
    def init(self, step: int = 0) -> MonitorState:
        """A fresh MonitorState (zero counters, ring from the plane)."""
        lay = plan_lib.spec_layout(self.spec)
        if self.telemetry is not None:
            ring = self.telemetry.make_ring(compact=True)
            tparams = self.telemetry.params
        else:
            ring = None
            tparams = telemetry_lib.TelemetryParams.of(0)
        return MonitorState(
            calls=jnp.zeros((self.spec.n_scopes,), jnp.int32),
            values=jnp.zeros((lay.total,), jnp.float32),
            samples=jnp.zeros((lay.total,), jnp.int32),
            sched_calls=(jnp.zeros((self.spec.n_scopes,), jnp.int32)
                         if self._carries_sched else None),
            step=jnp.asarray(int(step), jnp.int32),
            ring=ring,
            params=self.params,
            tparams=tparams,
            fingerprint=self.spec.fingerprint,
        )

    def sync(self, mstate: MonitorState,
             params: MonitorParams | None = None,
             tparams: telemetry_lib.TelemetryParams | None = None,
             runtime=None, controller=None) -> MonitorState:
        """Refresh the dynamic knobs riding in the state (host-side swap —
        same shapes, never a re-trace).  Pass a ``ScalpelRuntime`` to pick
        up both its live params and telemetry cadence in one call, or an
        ``AdaptiveController`` (adaptive.py) to pick up the closed loop's
        latest mask/period/cadence decisions without a runtime."""
        if controller is not None:
            params = controller.params if params is None else params
            tparams = controller.tparams if tparams is None else tparams
        if runtime is not None:
            params = runtime.params if params is None else params
            tparams = runtime.telemetry.params if tparams is None else tparams
        return dataclasses.replace(
            mstate,
            params=mstate.params if params is None else params,
            tparams=mstate.tparams if tparams is None else tparams,
        )

    # -- the raw collection region ----------------------------------------
    @contextlib.contextmanager
    def open(self, params: MonitorParams | None = None, calls_base=None):
        """Open a collection region; yields the Collector.

        The low-level primitive ``wrap`` is built on: callers that need
        custom threading — e.g. collection inside a ``value_and_grad`` aux —
        use this and fold ``col.compact_delta()`` through ``commit``
        themselves.  ``calls_base`` (the calls of earlier regions; zeros by
        default) continues the multiplex schedule across regions.
        """
        params = params if params is not None else self.params
        base = calls_base if calls_base is not None else jnp.zeros(
            (self.spec.n_scopes,), jnp.int32
        )
        col = Collector(
            self.spec, params, calls_base=base, plan_mode=self.plan_mode
        )
        _stack().append(col)
        try:
            yield col
        finally:
            _stack().pop()

    # -- delta folding ----------------------------------------------------
    def reduce_delta(self, delta: plan_lib.CompactDelta
                     ) -> plan_lib.CompactDelta:
        """Cross-device-reduce a compact delta over the resolved mesh axes
        (trace-time decision; a no-op when no mapped axis is bound)."""
        from repro.dist import partition

        axes = partition.counter_reduce_axes(self.counter_axes)
        return delta.psum(axes) if axes else delta

    @jax.named_scope("scalpel.commit")
    def commit(self, mstate: MonitorState, delta: plan_lib.CompactDelta,
               reduce: bool = True) -> MonitorState:
        """Fold a region's compact delta into the state: mesh-reduce,
        accumulate, advance the step stamp, ring-append at the cadence.

        The schedule base (``sched_calls``) accumulates the UNREDUCED
        per-shard call delta — the multiplex set index must follow this
        shard's own call count, not the cluster-wide psum (which would
        advance the schedule N× per call on an N-way mesh and silently
        skip event sets).
        """
        sched_calls = None if mstate.sched_calls is None \
            else mstate.sched_calls + delta.calls
        if reduce:
            delta = self.reduce_delta(delta)
        calls = mstate.calls + delta.calls
        values = mstate.values + delta.values
        samples = mstate.samples + delta.samples
        step = mstate.step + 1
        ring = mstate.ring
        if ring is not None:
            ring = telemetry_lib.ring_append(
                ring,
                plan_lib.CompactDelta(calls=calls, values=values,
                                      samples=samples),
                mstate.tparams, step,
            )
        return dataclasses.replace(
            mstate, calls=calls, values=values, samples=samples,
            sched_calls=sched_calls, step=step, ring=ring,
        )

    # -- per-lane states (continuous-batching serving) ---------------------
    def lane_init(self, n_lanes: int, step: int = 0) -> LaneMonitorState:
        """A fresh LaneMonitorState: ``n_lanes`` zeroed counter rows plus
        zeroed aggregate lanes (ring templated on the aggregate — compact
        spec shapes, so drains/reports/adaptive see the usual layout)."""
        if n_lanes < 1:
            raise ValueError(f"n_lanes must be >= 1, got {n_lanes}")
        lay = plan_lib.spec_layout(self.spec)
        if self.telemetry is not None:
            ring = self.telemetry.make_ring(compact=True)
            tparams = self.telemetry.params
        else:
            ring = None
            tparams = telemetry_lib.TelemetryParams.of(0)
        n, t = self.spec.n_scopes, lay.total
        return LaneMonitorState(
            lane_calls=jnp.zeros((n_lanes, n), jnp.int32),
            lane_values=jnp.zeros((n_lanes, t), jnp.float32),
            lane_samples=jnp.zeros((n_lanes, t), jnp.int32),
            lane_sched=jnp.zeros((n_lanes, n), jnp.int32),
            calls=jnp.zeros((n,), jnp.int32),
            values=jnp.zeros((t,), jnp.float32),
            samples=jnp.zeros((t,), jnp.int32),
            step=jnp.asarray(int(step), jnp.int32),
            ring=ring,
            params=self.params,
            tparams=tparams,
            fingerprint=self.spec.fingerprint,
        )

    @jax.named_scope("scalpel.commit")
    def commit_lanes(self, lstate: LaneMonitorState,
                     delta: plan_lib.CompactDelta,
                     active) -> LaneMonitorState:
        """Fold one decode step's LANE-STACKED delta into the state.

        ``delta`` leaves carry a leading ``[n_lanes]`` axis (a vmapped
        collector's output); ``active`` is the ``[n_lanes]`` i32 lane mask.
        Inactive lanes decode garbage under vmap — their deltas are masked
        to zero, so retired/empty lanes contribute nothing to either the
        per-lane rows or the aggregate.  The aggregate is the lane sum,
        mesh-reduced like ``commit``; ``lane_sched`` advances by the
        UNREDUCED masked calls (the per-shard schedule invariant).  The
        step stamp advances once per decode step and the aggregate
        cumulative counters ring-append at the dynamic cadence.
        """
        m = jnp.asarray(active, jnp.int32)
        d_calls = delta.calls * m[:, None]
        d_values = delta.values * m[:, None].astype(delta.values.dtype)
        d_samples = delta.samples * m[:, None]
        agg = self.reduce_delta(plan_lib.CompactDelta(
            calls=d_calls.sum(axis=0),
            values=d_values.sum(axis=0),
            samples=d_samples.sum(axis=0),
        ))
        calls = lstate.calls + agg.calls
        values = lstate.values + agg.values
        samples = lstate.samples + agg.samples
        step = lstate.step + 1
        ring = lstate.ring
        if ring is not None:
            ring = telemetry_lib.ring_append(
                ring,
                plan_lib.CompactDelta(calls=calls, values=values,
                                      samples=samples),
                lstate.tparams, step,
            )
        return dataclasses.replace(
            lstate,
            lane_calls=lstate.lane_calls + d_calls,
            lane_values=lstate.lane_values + d_values,
            lane_samples=lstate.lane_samples + d_samples,
            lane_sched=lstate.lane_sched + d_calls,
            calls=calls, values=values, samples=samples,
            step=step, ring=ring,
        )

    def admit_lane(self, lstate: LaneMonitorState, lane,
                   delta: plan_lib.CompactDelta,
                   owned=None) -> LaneMonitorState:
        """Seed lane ``lane`` with an admitted request's prefill delta.

        Pure and trace-safe (``lane`` may be a traced i32 scalar — the
        serve driver jits this into its admission program, so admitting
        never re-traces or runs eager device ops).

        The lane's counter rows RESET to the delta (the previous occupant
        was harvested at retirement), its schedule base restarts with it,
        and the delta folds into the aggregate — so the aggregate matches
        what a serial engine would have accumulated over the same
        requests.  Advances the step stamp (an admission is a monitored
        event, like the serial engine's wrapped prefill).

        ``owned`` (traced bool scalar, sharded serving): the lane rows are
        PER-SHARD under ``shard_map`` — only the shard owning the (local,
        clamped) ``lane`` index takes the row reset; the REPLICATED
        aggregate/ring still folds the delta in unconditionally, exactly
        once per shard's own copy (the prefill delta is replicated, never
        psum-reduced — a psum would count it N times).
        """

        def seed(rows, row):
            if owned is None:
                return rows.at[lane].set(row)
            return rows.at[lane].set(jnp.where(owned, row, rows[lane]))

        calls = lstate.calls + delta.calls
        values = lstate.values + delta.values
        samples = lstate.samples + delta.samples
        step = lstate.step + 1
        ring = lstate.ring
        if ring is not None:
            ring = telemetry_lib.ring_append(
                ring,
                plan_lib.CompactDelta(calls=calls, values=values,
                                      samples=samples),
                lstate.tparams, step,
            )
        return dataclasses.replace(
            lstate,
            lane_calls=seed(lstate.lane_calls, delta.calls),
            lane_values=seed(lstate.lane_values, delta.values),
            lane_samples=seed(lstate.lane_samples, delta.samples),
            lane_sched=seed(lstate.lane_sched, delta.calls),
            calls=calls, values=values, samples=samples,
            step=step, ring=ring,
        )

    @staticmethod
    def lane_counters_host(delta: plan_lib.CompactDelta
                           ) -> plan_lib.CompactDelta:
        """Materialize a (possibly still in-flight) lane delta to host
        numpy — the request-completion sync point."""
        return plan_lib.CompactDelta(
            calls=np.asarray(delta.calls),
            values=np.asarray(delta.values),
            samples=np.asarray(delta.samples),
        )

    # -- the transformation ----------------------------------------------
    def scan(self, body: Callable, steps_per_commit: int | None = None, *,
             wrapped: bool = False, unroll: int = 1) -> Callable:
        """The K-step **megastep** driver: one commit-boundary crossing per
        ``steps_per_commit`` monitored steps.

        ``wrap`` pays the per-call fixed cost — open a collector, commit,
        round-trip the host dispatch path — once per step; once steps are
        short (~100µs) that cost dominates.  ``scan`` drives K steps inside
        ONE ``lax.scan`` over a single ``MonitorState`` carry instead:

        * compact ``CompactDelta`` counters accumulate in-carry (the same
          dense-lane machinery ``scan_with_counters`` rides);
        * the multiplex schedule base ``sched_calls`` advances K× PER-SHARD
          inside the scan — the mesh-reduced totals never feed the schedule
          (the ROADMAP invariant);
        * ``ring_append`` runs INSIDE the scan body, once per inner step, so
          ``TelemetryParams.cadence`` snapshots land on their true step
          stamps even when the cadence does not divide K.

        ``body(carry, x) -> (carry', y)`` is an ordinary scan body using
        ``scalpel.function``/``probe``; the driver opens the collection
        region and commits per inner step.  With ``wrapped=True`` the body
        instead has the wrapped signature ``body(mstate, carry, x) ->
        ((carry', y), mstate')`` and owns its regions — it must fold its
        delta through ``commit`` exactly once (custom threading, e.g. the
        train step's ``value_and_grad`` aux collection).

        Returns ``mega(mstate, carry, xs=None) -> ((carry', ys), mstate')``.
        ``xs`` (per-step inputs stacked on a leading axis) sets the step
        count when given; otherwise ``steps_per_commit`` does.  Dynamic
        knob swaps (``mon.sync``) take effect at the next megastep boundary
        — params/tparams are scan constants, so the adaptive loop reacts at
        megastep granularity (see README).
        """
        if steps_per_commit is not None and steps_per_commit < 1:
            raise ValueError(
                f"steps_per_commit must be >= 1, got {steps_per_commit}")

        def mega(mstate: MonitorState, carry, xs=None):
            if xs is None and steps_per_commit is None:
                raise ValueError(
                    "Monitor.scan needs steps_per_commit or per-step xs")
            # params/tparams are loop constants, not carries: they cannot
            # change inside a megastep, and keeping them out of the carry
            # is what lets the jit boundary drop them from the outputs
            params, tparams = mstate.params, mstate.tparams

            def rebuild(leaves):
                calls, values, samples, sched, step, ring = leaves
                return MonitorState(
                    calls=calls, values=values, samples=samples,
                    sched_calls=sched, step=step, ring=ring,
                    params=params, tparams=tparams,
                    fingerprint=self.spec.fingerprint,
                )

            def sbody(c, x):
                leaves, cur = c
                ms = rebuild(leaves)
                if wrapped:
                    (cur2, y), ms2 = body(ms, cur, x)
                else:
                    base = ms.sched_calls if ms.sched_calls is not None \
                        else ms.calls
                    with self.open(params, calls_base=base) as col:
                        cur2, y = body(cur, x)
                    ms2 = self.commit(ms, col.compact_delta())
                return ((ms2.calls, ms2.values, ms2.samples,
                         ms2.sched_calls, ms2.step, ms2.ring), cur2), y

            init = ((mstate.calls, mstate.values, mstate.samples,
                     mstate.sched_calls, mstate.step, mstate.ring), carry)
            (leaves, carry2), ys = jax.lax.scan(
                sbody, init, xs,
                length=steps_per_commit if xs is None else None,
                unroll=unroll,
            )
            return (carry2, ys), rebuild(leaves)

        mega.__name__ = f"scalpel_megastep[{getattr(body, '__name__', 'fn')}]"
        mega.monitor = self
        return mega

    def wrap(self, fn: Callable, steps_per_commit: int = 1) -> Callable:
        """``fn(*args, **kw) -> out``  ⟶  ``(mstate, *args, **kw) -> (out,
        mstate')`` — the functional monitored step.

        ``fn`` is ordinary model/step code using ``scalpel.function`` /
        ``probe`` / ``scan_with_counters``; nested wrapped calls compose
        (the inner region folds into the outer collector's stack).

        ``steps_per_commit > 1`` turns the wrapped call into a K-step
        megastep on the ``scan`` driver: ``fn`` must then be a self-map of
        ONE positional argument (``fn(x) -> x'`` with the output matching
        the input's structure — a step function whose result feeds the next
        step), and one wrapped call advances the state by K steps while
        crossing the commit/dispatch boundary once.
        """
        if steps_per_commit > 1:
            mega = self.scan(lambda c, _: (fn(c), None),
                             steps_per_commit=steps_per_commit)

            def wrapped(mstate: MonitorState, x):
                (x2, _), ms2 = mega(mstate, x)
                return x2, ms2

            wrapped.__name__ = \
                f"scalpel_monitor[{getattr(fn, '__name__', 'fn')}" \
                f"/K={steps_per_commit}]"
            wrapped.monitor = self
            return wrapped

        def wrapped(mstate: MonitorState, *args, **kwargs):
            # the collector's call-count base is the PER-SHARD schedule
            # base, never the mesh-reduced totals (``calls`` doubles as it
            # for monitors that never reduce)
            base = mstate.sched_calls if mstate.sched_calls is not None \
                else mstate.calls
            with self.open(mstate.params, calls_base=base) as col:
                out = fn(*args, **kwargs)
            return out, self.commit(mstate, col.compact_delta())

        wrapped.__name__ = f"scalpel_monitor[{getattr(fn, '__name__', 'fn')}]"
        wrapped.monitor = self
        return wrapped

    def jit(self, fn: Callable, *, steps_per_commit: int = 1,
            donate_argnums=(), donate_state: bool = False,
            **jit_kwargs) -> Callable:
        """``jax.jit(wrap(fn))`` with the state boundary drawn leaf-wise.

        ``wrap`` alone returns the whole MonitorState from the jitted
        program — including the runtime ``params``/``tparams`` it only
        READS, which jit must then copy into fresh output buffers every
        call.  ``Monitor.jit`` keeps those knobs as inputs only and
        reattaches the caller's objects outside the graph, so the compiled
        step outputs exactly what changed: the compact counter lanes, the
        step stamp, and the ring.  Semantically identical to
        ``jax.jit(mon.wrap(fn))``; measurably cheaper per call.

        ``donate_argnums`` refer to ``fn``'s OWN positional args (e.g. a
        decode cache), and are remapped past the state leaves.
        ``donate_state=True`` additionally donates the counter lanes and
        step stamp (XLA reuses their buffers for the outputs — the
        steady-state loop allocates nothing for counters).  Only safe when
        nothing else holds the previous state's counter arrays: runtime
        observers (``runtime.on_step(mstate.counters)``) keep such
        references, so leave it off in loops that publish to a runtime.
        The ring is NEVER donated (the telemetry drain thread reads it).

        ``steps_per_commit > 1`` compiles the K-step megastep form of
        ``wrap`` (see there for the self-map contract): one dispatch per K
        steps, with the same leaf-wise boundary.
        """
        return self.jit_wrapped(
            self.wrap(fn, steps_per_commit=steps_per_commit),
            donate_argnums=donate_argnums, donate_state=donate_state,
            _name=getattr(fn, "__name__", "fn"), **jit_kwargs,
        )

    def jit_wrapped(self, wrapped: Callable, *, donate_argnums=(),
                    donate_state: bool = False, _name: str | None = None,
                    **jit_kwargs) -> Callable:
        """Draw the leaf-wise jit boundary around an ALREADY-wrapped step.

        ``wrapped(mstate, *args) -> (out, mstate')`` — anything with the
        wrapped signature: ``mon.wrap(fn)``, a ``mon.scan`` megastep, or a
        hand-built step (e.g. ``train.make_train_megastep``) that opens its
        own regions and commits itself.  The compiled program takes the
        state leaf-wise, keeps the read-only ``params``/``tparams`` as
        inputs only (reattached outside the graph — they stop round-tripping
        the step), and outputs exactly what changed: counter lanes, step
        stamp, ring.  Donation semantics as in ``jit``.

        The returned callable exposes the underlying ``jax.jit`` object as
        ``._cjit`` (for cache-stats/no-retrace assertions), and
        ``.lower(mstate, *args)`` — the jit's ``lower`` with the wrapped
        signature, for HLO inspection.
        """

        def core(calls, values, samples, sched_calls, step, ring, params,
                 tparams, *args):
            ms = MonitorState(
                calls=calls, values=values, samples=samples,
                sched_calls=sched_calls, step=step, ring=ring,
                params=params, tparams=tparams,
                fingerprint=self.spec.fingerprint,
            )
            out, ms2 = wrapped(ms, *args)
            return out, (ms2.calls, ms2.values, ms2.samples,
                         ms2.sched_calls, ms2.step, ms2.ring)

        n_state = 8
        donate = tuple(n_state + int(i) for i in donate_argnums)
        if donate_state:
            # counters + step (+ the schedule base when carried — a None
            # leaf has no buffers to donate)
            sched = (3,) if self._carries_sched else ()
            donate = (0, 1, 2) + sched + (4,) + donate
        cjit = jax.jit(core, donate_argnums=donate, **jit_kwargs)

        def leaves(mstate: MonitorState):
            return (mstate.calls, mstate.values, mstate.samples,
                    mstate.sched_calls, mstate.step, mstate.ring,
                    mstate.params, mstate.tparams)

        def stepped(mstate: MonitorState, *args):
            out, (calls, values, samples, sched_calls, step, ring) = cjit(
                *leaves(mstate), *args)
            # direct construction (not dataclasses.replace): this wrapper
            # runs once per step on the host, keep it lean
            return out, MonitorState(
                calls=calls, values=values, samples=samples,
                sched_calls=sched_calls, step=step, ring=ring,
                params=mstate.params, tparams=mstate.tparams,
                fingerprint=mstate.fingerprint,
            )

        stepped.__name__ = "scalpel_monitor_jit[{}]".format(
            _name if _name is not None
            else getattr(wrapped, "__name__", "fn"))
        stepped.monitor = self
        stepped._cjit = cjit
        stepped.lower = lambda mstate, *args: cjit.lower(
            *leaves(mstate), *args)
        return stepped

    def shard_wrap(self, fn: Callable, mesh, in_specs, out_specs) -> Callable:
        """``wrap(fn)`` run per-shard under ``shard_map`` with cluster-wide
        counters.

        ``in_specs``/``out_specs`` describe ``fn``'s own args/outputs; the
        MonitorState is replicated automatically (counters are identical on
        every shard after the in-body ``psum``).  ``check_vma=False`` is
        required: the probe path's mask ``lax.cond`` confuses shard_map's
        replication checker (a JAX limitation, not a semantic one — the
        2-device test asserts exact equality with the per-shard sum).
        """
        import copy

        from jax.sharding import PartitionSpec

        mon = self
        if self.counter_axes == "auto":
            # resolve against THIS mesh, not the ambient partition context
            mon = copy.copy(self)
            mon.counter_axes = tuple(mesh.axis_names)
        wrapped = mon.wrap(fn)
        # NB: PartitionSpec subclasses tuple — a bare spec is ONE spec, not
        # a sequence of per-arg specs
        if isinstance(in_specs, PartitionSpec) or not isinstance(
                in_specs, (tuple, list)):
            in_specs = (in_specs,)
        rep = PartitionSpec()
        sharded = jax.shard_map(
            wrapped, mesh=mesh, in_specs=(rep,) + tuple(in_specs),
            out_specs=(out_specs, rep), check_vma=False,
        )
        sharded.monitor = mon
        return sharded

    # -- views / reporting -------------------------------------------------
    def counter_state(self, mstate: MonitorState) -> CounterState:
        """The legacy padded-view CounterState (for interop only — reports
        read the compact layout directly)."""
        return mstate.counters.expand(self.spec)

    def reports(self, mstate) -> list[report_lib.ScopeReport]:
        return report_lib.build(self.spec, mstate)

    def report(self, mstate, title: str = "ScALPEL report") -> str:
        return report_lib.format_text(self.reports(mstate), title=title)

    def estimates(self, mstate) -> dict[str, dict[str, float]]:
        return report_lib.estimates(self.spec, mstate)

    # -- checkpoint integration -------------------------------------------
    def checkpoint_payload(self, mstate: MonitorState) -> dict:
        """The array leaves worth persisting (counters + the per-shard
        schedule base + step; the ring is transient device state, params
        are config)."""
        payload = {
            "calls": mstate.calls,
            "values": mstate.values,
            "samples": mstate.samples,
            "step": mstate.step,
        }
        if mstate.sched_calls is not None:
            payload["sched_calls"] = mstate.sched_calls
        return payload

    def restore(self, mstate: MonitorState, payload: dict) -> MonitorState:
        """Graft a checkpoint payload back onto a live state pytree."""
        return dataclasses.replace(
            mstate,
            calls=payload["calls"], values=payload["values"],
            samples=payload["samples"],
            sched_calls=payload.get("sched_calls", mstate.sched_calls),
            step=payload["step"],
        )

    def check_resume(self, meta: dict | None, strict: bool = True):
        """Validate checkpoint metadata against the live compiled plans
        (see ``check_plan_metadata`` for the contract)."""
        return check_plan_metadata(self.spec.fingerprint, meta,
                                   strict=strict)


def check_plan_metadata(fingerprint: str, meta: dict | None,
                        strict: bool = True):
    """The shared resume-time plan attestation.

    Returns True on match, None when the metadata carries no fingerprint
    (pre-Monitor checkpoints — the caller decides whether the rest of the
    payload is even readable).  On mismatch: raises (``strict=True``) or
    warns and returns False — resuming counters produced by different
    probe plans silently mis-attributes every accumulated lane.
    """
    fp = (meta or {}).get("plan_fingerprint")
    if not fp:
        return None
    if fp == fingerprint:
        return True
    msg = (
        f"resume plan mismatch: checkpoint counters come from plan "
        f"{fp[:12]}, live spec compiles to {fingerprint[:12]} — the "
        "monitoring spec changed since the checkpoint was written"
    )
    if strict:
        raise RuntimeError(msg)
    import warnings

    warnings.warn(msg, RuntimeWarning, stacklevel=3)
    return False


def monitored(spec: MonitorSpec, params: MonitorParams | None = None, **kw):
    """Decorator form: ``@scalpel.monitored(spec)`` over a step function.

    The decorated function gains the wrapped signature
    ``(mstate, *args, **kwargs) -> (out, mstate')`` plus ``.monitor`` (the
    Monitor) and ``.init`` (fresh-state constructor)::

        @scalpel.monitored(spec)
        def step(x):
            with scalpel.function("f"):
                scalpel.probe(x=x)
            return x * 2

        mstate = step.init()
        out, mstate = jax.jit(step)(mstate, x)
    """

    def deco(fn: Callable) -> Callable:
        mon = Monitor(spec, params, **kw)
        wrapped = mon.wrap(fn)
        wrapped.init = mon.init
        return wrapped

    return deco
