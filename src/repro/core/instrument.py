"""Trace-time scope instrumentation — the paper's compiler-directed callbacks.

GCC planted entry/exit handlers in the object code; we plant event
computations in the traced JAX program.  Model code stays unmodified in the
paper's sense: it only names its scopes (``with scalpel.function("attn")`` or
the decorator/auto-walker) — which events run, for which scopes, with which
multiplex schedule is decided by the MonitorSpec/MonitorParams, not the model.

Execution model
---------------
* ``monitor.Monitor`` opens a root Collector for a step (``Monitor.wrap``,
  or the raw region ``Monitor.open``).
* ``function(name)`` pushes a scope; entering a scope that is in the
  compile-time set increments its call counter *in-graph* (interception).
* ``probe(**tensors)`` evaluates the current scope's context: a ``lax.cond``
  on the runtime scope mask (un-monitored scopes pay only the predicated
  branch — the paper's cheap interception), then a ``lax.switch`` over the
  scope's event sets keyed by ``(calls // period) % n_sets`` — call-count
  multiplexing, phase-exact even inside ``lax.scan`` loops.  Each branch
  executes its compiled ``MomentPlan`` (core/plan.py): exactly the channels
  THAT event set finalizes from, swept once per probed tensor
  (kernels/probe_reduce.py — the optional ``ent_sum`` channel folds
  ATTN_ENTROPY into the same pass), landing via one batched scatter over
  the set's live slots.  A sparse active set never pays for the union.
* ``capture(fn, ...)`` runs ``fn`` under a child collector and returns
  ``(out, CounterState delta)`` — the bridge that lets ``lax.scan`` carry
  counters through stacked layers (in compact form: the scan carry sums
  only the spec's live-slot footprint, ``plan.CompactDelta``).

When no collector is active every call here is a no-op: an uninstrumented
("vanilla") program pays nothing.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from . import events as events_lib
from . import plan as plan_lib
from .context import EventSpec, MonitorSpec, ScopeContext
from .counters import CounterState, MonitorParams

_TLS = threading.local()
_KOPS = None


def _kernel_ops():
    """repro.kernels.ops, resolved once (imported lazily: kernels are an
    optional heavyweight import and must not load at repro.core import
    time), then cached so the per-probe trace path skips the module lookup.
    """
    global _KOPS
    if _KOPS is None:
        from repro.kernels import ops as _KOPS  # noqa: N811
    return _KOPS


def _partitioned_trace() -> bool:
    """True while tracing under an ambient multi-device mesh
    (``dist.partition.sharding_ctx``) with some axis of size > 1 that no
    ``shard_map`` binds: the compiler will partition the program, and a
    Mosaic kernel cannot be partitioned.  Probes then take XLA's fused
    reduction, which the partitioner splits for whatever sharding the
    tensor has — no reshard to fit a kernel's per-shard layout."""
    from repro.dist import partition

    mesh = partition.current_mesh()
    if mesh is None:
        return False
    split = [a for a in mesh.axis_names if mesh.shape[a] > 1]
    return len(partition.bound_axes(split)) < len(split)


def _stack() -> list:
    if not hasattr(_TLS, "stack"):
        _TLS.stack = []
    return _TLS.stack


def current_collector():
    st = _stack()
    return st[-1] if st else None


SEP = "/"


class Collector:
    """Accumulates an in-graph CounterState delta during tracing.

    Counter updates are COALESCED: per-call event values are collected in
    trace-time Python lists and materialized as ONE scatter-add per scope
    when the region finalizes (``delta``).  A scope probed k times per step
    costs k event computations but only one dynamic-update-slice — without
    this, the per-call scatters dominated the monitoring overhead.

    Event evaluation is PLAN-DRIVEN: every (scope, event set) pair executes
    its compiled ``plan.MomentPlan`` — the exact channel sweep per probed
    tensor that set's slots finalize from, plus the set's bespoke slots,
    landing through one batched scatter over the set's live-slot footprint.
    ``plan_mode="union"`` widens each set's sweeps to the cross-set union
    (the pre-plan behaviour) — the reference the tests compare per-set
    plans against, not a hot path.
    """

    def __init__(self, spec: MonitorSpec, params: MonitorParams,
                 calls_base, plan_mode: str = "per_set"):
        if plan_mode not in ("per_set", "union"):
            raise ValueError(f"unknown plan_mode {plan_mode!r}")
        self.spec = spec
        self.params = params
        # calls_base: i32[n_scopes] — global call counts *before* this
        # collector's region (threading through scan carries keeps the
        # multiplex schedule exact across iterations).
        self.calls_base = calls_base
        self.scope_path: list[str] = []
        self._extended: list[bool] = []
        self.plan_mode = plan_mode
        # deferred accumulators (trace-time); _vals/_smps hold per-scope
        # vectors of the SCOPE's width (dense plan layout), not max_slots
        self._counts: dict[int, int] = {}
        self._vals: dict[int, list] = {}
        self._smps: dict[int, list] = {}
        self._ingested: list[CounterState] = []
        self._final: CounterState | None = None

    # -- scope management -------------------------------------------------
    def push(self, name: str) -> str:
        # Paper §3.3: the context is *retained* across recursive calls to the
        # same function — direct re-entry does not open a new scope path, so
        # a recursive `foo` accumulates into one "foo" context rather than
        # foo/foo/foo (which would fall outside the compile-time set).
        if self.scope_path and self.scope_path[-1] == name:
            self._extended.append(False)
            return SEP.join(self.scope_path)
        self.scope_path.append(name)
        self._extended.append(True)
        return SEP.join(self.scope_path)

    def pop(self) -> None:
        if self._extended.pop():
            self.scope_path.pop()

    @property
    def current_scope(self) -> str:
        return SEP.join(self.scope_path)

    # -- in-graph counter updates -----------------------------------------
    def _counts_arrays(self):
        idxs = sorted(self._counts)
        return (
            jnp.asarray(idxs, jnp.int32),
            jnp.asarray([self._counts[i] for i in idxs], jnp.int32),
        )

    def total_calls(self):
        c = self.calls_base
        for d in self._ingested:
            c = c + d.calls
        if self._counts:
            idxs, cnts = self._counts_arrays()
            c = c.at[idxs].add(cnts)
        return c

    def intercept(self, scope: str) -> None:
        """Count a call of ``scope`` (always-on, cheap — paper's 'all').

        The count is a trace-time Python increment — interception of
        statically-unrolled calls is FREE in the compiled program (one
        scatter of constants at region exit)."""
        if scope not in self.spec:
            return
        idx = self.spec.scope_index(scope)
        self._counts[idx] = self._counts.get(idx, 0) + 1
        self._final = None

    def probe(self, scope: str, tensors: dict[str, Any]) -> None:
        if scope not in self.spec:
            return
        idx = self.spec.scope_index(scope)
        ctx = self.spec.context(scope)
        if not ctx.slots:
            return
        params = self.params
        # call count *before* this call was intercepted (python-side count
        # of prior interceptions in this region + carried base).
        calls_here = self.calls_base[idx] + (self._counts.get(idx, 1) - 1)

        tensors = {k: jax.lax.stop_gradient(v) for k, v in tensors.items()}
        # Compile (or fetch the cached) per-set plans for this probe call:
        # a scope may probe several times per invocation with different
        # tensors, so plans are keyed on the available tensor names too.
        plans = plan_lib.compile_scope_plans(
            ctx, frozenset(tensors), self.plan_mode == "union"
        )
        if not plans.any_live:
            return
        w = plans.width

        def _body_branch(pl):
            # ``pl`` is a deduped branch BODY: it fixes the computation
            # (slot events, exact sweeps) while the scatter indices arrive
            # as data (``midx``), so sets that do identical work over
            # different slots share one traced branch.
            def br(ops):
                ts, midx = ops
                vals = jnp.zeros((w,), jnp.float32)
                smp = jnp.zeros((w,), jnp.int32)
                if not pl.slots:
                    return vals, smp
                # THIS set's sweeps only: each probed tensor is read once,
                # computing exactly the channels this set finalizes from
                # (sets-dependent graphs are the price; only the selected
                # branch executes at run time).
                _kops = _kernel_ops()
                use_pallas = False if _partitioned_trace() else None
                moms = {
                    sw.tensor: _kops.tensor_moments(ts[sw.tensor],
                                                    sw.channels,
                                                    use_pallas=use_pallas)
                    for sw in pl.sweeps
                }
                vs = []
                for s in pl.slots:
                    if s.fused:
                        vs.append(events_lib.finalize_event(
                            ctx.slots[s.index], moms[s.tensor]
                        ))
                    else:
                        vs.append(events_lib.compute(ctx.slots[s.index], ts))
                # one batched scatter over the set's live-slot footprint
                idxs = midx[: len(pl.slots)]
                sms = params.slot_mask[idx, idxs]
                vals = vals.at[idxs].set(jnp.stack(vs) * sms)
                smp = smp.at[idxs].set((sms > 0).astype(jnp.int32))
                return vals, smp

            return br

        def _monitored(ts):
            if ctx.n_sets == 1:
                pl = plans.plans[0]
                midx = jnp.asarray(pl.members, jnp.int32)
                return _body_branch(pl)((ts, midx))
            set_idx = (calls_here // jnp.maximum(params.period[idx], 1)) % ctx.n_sets
            midx = jnp.asarray(plans.member_table, jnp.int32)[set_idx]
            if plans.n_branches == 1:
                # every set runs the same body; only the scatter footprint
                # (already selected into ``midx``) differs — no switch at all
                return _body_branch(plans.bodies[0])((ts, midx))
            bidx = jnp.asarray(plans.branch_index, jnp.int32)[set_idx]
            return jax.lax.switch(
                bidx, [_body_branch(b) for b in plans.bodies], (ts, midx)
            )

        def _skipped(ts):
            del ts
            return jnp.zeros((w,), jnp.float32), jnp.zeros((w,), jnp.int32)

        vals, smp = jax.lax.cond(
            params.scope_mask[idx] > 0, _monitored, _skipped, tensors
        )
        self._vals.setdefault(idx, []).append(vals)
        self._smps.setdefault(idx, []).append(smp)
        self._final = None

    def ingest(self, delta) -> None:
        """Fold a child region's delta (e.g. a scan's summed carry).

        Accepts either layout — a padded ``CounterState`` or a compact
        ``plan.CompactDelta`` — and defers the conversion to whichever
        finalization runs: ``compact_delta()`` keeps compact ingests
        compact (a scan feeding a Monitor-wrapped step never touches the
        padded block), while ``delta`` expands them once."""
        self._ingested.append(delta)
        self._final = None

    # -- finalization -------------------------------------------------------
    @property
    def delta(self) -> CounterState:
        """The region's CounterState delta (coalesced, built lazily)."""
        if self._final is not None:
            return self._final
        n, m = self.spec.n_scopes, self.spec.max_slots
        calls = jnp.zeros((n,), jnp.int32)
        if self._counts:
            idxs, cnts = self._counts_arrays()
            calls = calls.at[idxs].add(cnts)
        values = jnp.zeros((n, m), jnp.float32)
        samples = jnp.zeros((n, m), jnp.int32)
        for idx, lst in self._vals.items():
            tot = lst[0]
            for v in lst[1:]:
                tot = tot + v
            values = values.at[idx, : tot.shape[0]].add(tot)
        for idx, lst in self._smps.items():
            tot = lst[0]
            for v in lst[1:]:
                tot = tot + v
            samples = samples.at[idx, : tot.shape[0]].add(tot)
        d = CounterState(calls=calls, values=values, samples=samples)
        for ing in self._ingested:
            if isinstance(ing, plan_lib.CompactDelta):
                ing = ing.expand(self.spec)
            d = d.add(ing)
        self._final = d
        return d

    def compact_delta(self) -> plan_lib.CompactDelta:
        """The region's delta in the dense slot layout (plan.SlotLayout).

        The scan-carry form: ``lax.scan`` bodies sum only the spec's
        live-slot footprint per iteration and expand to a full CounterState
        once at region exit (scan_with_counters) — instead of carrying the
        padded ``[n_scopes, max_slots]`` block through every iteration.
        """
        lay = plan_lib.spec_layout(self.spec)
        n = self.spec.n_scopes
        calls = jnp.zeros((n,), jnp.int32)
        if self._counts:
            idxs, cnts = self._counts_arrays()
            calls = calls.at[idxs].add(cnts)
        values = jnp.zeros((lay.total,), jnp.float32)
        samples = jnp.zeros((lay.total,), jnp.int32)
        for idx, lst in self._vals.items():
            tot = lst[0]
            for v in lst[1:]:
                tot = tot + v
            off = lay.offsets[idx]
            values = values.at[off : off + tot.shape[0]].add(tot)
        for idx, lst in self._smps.items():
            tot = lst[0]
            for v in lst[1:]:
                tot = tot + v
            off = lay.offsets[idx]
            samples = samples.at[off : off + tot.shape[0]].add(tot)
        d = plan_lib.CompactDelta(calls=calls, values=values, samples=samples)
        for ing in self._ingested:
            if not isinstance(ing, plan_lib.CompactDelta):
                ing = plan_lib.CompactDelta.compress(self.spec, ing)
            d = d.add(ing)
        return d


class DiscoveryCollector:
    """Records scope/probe structure without computing anything.

    Used under ``jax.eval_shape`` to enumerate the compile-time set — the
    analogue of the paper's 'instrument all functions' compiler pass.
    """

    def __init__(self):
        self.scope_path: list[str] = []
        self._extended: list[bool] = []
        self.seen: dict[str, tuple[str, ...]] = {}

    def push(self, name: str) -> str:
        if self.scope_path and self.scope_path[-1] == name:
            self._extended.append(False)
        else:
            self.scope_path.append(name)
            self._extended.append(True)
        scope = SEP.join(self.scope_path)
        self.seen.setdefault(scope, ())
        return scope

    def pop(self) -> None:
        if self._extended.pop():
            self.scope_path.pop()

    @property
    def current_scope(self) -> str:
        return SEP.join(self.scope_path)

    def intercept(self, scope: str) -> None:
        self.seen.setdefault(scope, ())

    def probe(self, scope: str, tensors: dict[str, Any]) -> None:
        old = self.seen.get(scope, ())
        merged = tuple(dict.fromkeys(list(old) + sorted(tensors)))
        self.seen[scope] = merged

    def ingest(self, delta) -> None:  # pragma: no cover - structure only
        del delta

    total_calls = None  # discovery has no call counts


# --------------------------------------------------------------------------
# Public API used by model / application code.
# --------------------------------------------------------------------------

@contextlib.contextmanager
def discovering():
    col = DiscoveryCollector()
    _stack().append(col)
    try:
        yield col
    finally:
        _stack().pop()


@contextlib.contextmanager
def function(name: str):
    """Scope context manager — the entry/exit callback pair (paper C1).

    Entering counts one interception of the full scope path.  Also opens a
    ``jax.named_scope`` so the scope name lands in the HLO op metadata of
    the scope's ops, where a device trace reads it.
    """
    col = current_collector()
    if col is None:
        yield None
        return
    scope = col.push(name)
    try:
        with jax.named_scope(name):
            col.intercept(scope)
            yield scope
    finally:
        col.pop()


def probe(**tensors) -> None:
    """Evaluate the current scope's monitoring context on named tensors.

    The probe's ops carry ``scalpel.probe`` in their HLO op metadata, so a
    device trace tells them from the model's."""
    col = current_collector()
    if col is None:
        return
    with jax.named_scope("scalpel.probe"):
        col.probe(col.current_scope, tensors)


def probe_scope(name: str, **tensors) -> None:
    """One-shot scope: function(name) + probe(**tensors)."""
    with function(name):
        probe(**tensors)


def instrument(fn: Callable, name: str, probes: Callable | None = None):
    """Wrap ``fn`` so each call is an intercepted scope (decorator form).

    ``probes(out, *args, **kwargs) -> dict`` optionally derives probe tensors
    from the call; by default the output tensor is probed as 'out'.
    """

    def wrapped(*args, **kwargs):
        with function(name):
            out = fn(*args, **kwargs)
            if current_collector() is not None:
                if probes is not None:
                    t = probes(out, *args, **kwargs)
                else:
                    t = {"out": out} if isinstance(out, jax.Array) else {}
                if t:
                    probe(**t)
            return out

    wrapped.__name__ = f"scalpel[{name}]"
    return wrapped


def capture(fn: Callable, calls_base=None, compact: bool = False):
    """Run ``fn`` under a child collector; returns ``fn' -> (out, delta)``.

    The bridge for ``lax.scan``: the scan body wraps its work in ``capture``
    with ``calls_base = outer_base + carried_delta.calls`` so call-count
    multiplexing stays exact across iterations.  ``compact=True`` returns
    the delta as a ``plan.CompactDelta`` (the dense live-slot layout) — the
    form scan carries sum per iteration.
    """
    parent = current_collector()

    def run(*args, **kwargs):
        if parent is None or isinstance(parent, DiscoveryCollector):
            # Discovery or vanilla: no counters; keep structure cheap.
            if isinstance(parent, DiscoveryCollector):
                out = fn(*args, **kwargs)
                return out, None
            return fn(*args, **kwargs), None
        base = calls_base if calls_base is not None else parent.total_calls()
        child = Collector(parent.spec, parent.params, calls_base=base,
                          plan_mode=parent.plan_mode)
        child.scope_path = list(parent.scope_path)
        _stack().append(child)
        try:
            out = fn(*args, **kwargs)
        finally:
            _stack().pop()
        return out, (child.compact_delta() if compact else child.delta)

    return run


def scan_with_counters(body: Callable, init, xs, length: int | None = None,
                       unroll: int | bool = 1, remat=None):
    """``lax.scan`` that threads ScALPEL counters through the carry.

    ``body(carry, x) -> (carry, y)`` is ordinary scan-body code that may call
    ``function``/``probe``.  Counter deltas from every iteration are summed
    and folded into the ambient collector.  With no active collector this is
    a plain ``lax.scan``.

    ``remat`` (optional): a rematerialization decorator (e.g.
    ``jax.checkpoint`` with a policy).  It is applied *inside* the counter
    capture so the counter delta is an explicit output of the checkpointed
    region — counters never leak across the remat boundary.

    The per-iteration delta rides the carry in COMPACT form
    (``plan.CompactDelta``): the scan sums only the spec's live-slot
    footprint each step — the dense slot layout the probe-plan layer
    compiles — and expands to a full ``CounterState`` once, at scan exit.
    """
    col = current_collector()
    if col is None or isinstance(col, DiscoveryCollector):
        b = body if remat is None else (lambda c, x: remat(body)(c, x))
        return jax.lax.scan(b, init, xs, length=length, unroll=unroll)

    spec = col.spec
    base = col.total_calls()

    def work(inner, x, calls_base):
        run = capture(lambda: body(inner, x), calls_base=calls_base,
                      compact=True)
        (inner2, y), d = run()
        return inner2, y, d

    if remat is not None:
        work = remat(work)

    def wrapped(carry, x):
        inner, dsum = carry
        inner2, y, d = work(inner, x, base + dsum.calls)
        return (inner2, dsum.add(d)), y

    (out, dtotal), ys = jax.lax.scan(
        wrapped, (init, plan_lib.CompactDelta.zeros(spec)), xs,
        length=length, unroll=unroll,
    )
    # ingest the summed carry in COMPACT form: a collector finalized
    # compactly (Monitor.wrap) never materializes the padded block at all;
    # the legacy padded delta expands it once here instead.
    col.ingest(dtotal)
    return out, ys


# --------------------------------------------------------------------------
# Discovery — build the compile-time set by walking the traced program.
# --------------------------------------------------------------------------

def discover(fn: Callable, *args, **kwargs) -> dict[str, tuple[str, ...]]:
    """Trace ``fn`` abstractly and return {scope: probed tensor names}."""
    with discovering() as col:
        jax.eval_shape(fn, *args, **kwargs)
    return dict(col.seen)


DEFAULT_TENSOR_EVENTS = ("ACT_RMS", "ACT_MEAN_ABS")


def spec_from_discovery(
    seen: dict[str, tuple[str, ...]],
    tensor_events: Sequence[str] = DEFAULT_TENSOR_EVENTS,
    include: Callable[[str], bool] | None = None,
) -> MonitorSpec:
    """Auto-build a MonitorSpec: every discovered scope becomes interceptable,
    every probed tensor gets the generic ``tensor_events`` — the analogue of
    compiling with '-finstrument-functions' on everything."""
    ctxs = []
    for scope, tnames in sorted(seen.items()):
        if include is not None and not include(scope):
            continue
        slots = [
            EventSpec(event=ev, tensor=t)
            for t in tnames
            for ev in tensor_events
        ]
        ctxs.append(ScopeContext.exhaustive(scope, slots))
    return MonitorSpec.of(ctxs)
