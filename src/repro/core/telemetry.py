"""Asynchronous telemetry plane — decouple counter collection from consumption.

The paper's runtime pays a synchronous full-``CounterState`` device→host
transfer every time a report/adapt decision is made (the "host readback
cadence" cost in ROADMAP's probe cost model).  Production monitoring stacks
split measurement from collection with an agent/transport/collector design
(LIKWID Monitoring Stack, PerSyst); this module brings that split to the
jitted hot path:

* **Device side** — ``SnapshotRing``: ``[depth, ...]`` copies of the counter
  pytree plus a step stamp, written by a ``lax.cond``-guarded
  ``ring_append`` at a runtime-configurable cadence.  The cadence lives in
  ``TelemetryParams`` — a dynamic input to the jitted step (MonitorParams
  style), so changing it never re-traces.  Appends are pure device work: the
  step loop never blocks on the ring.

* **Host side** — ``TelemetryPlane``: a background drain thread pulls ring
  slots incrementally past its drain cursor — an idle tick costs one scalar
  head probe; a drain that kept up (one new slot) copies the ring's O(1)
  ``last`` mirror instead of the depth-sized ring; only a multi-slot
  catch-up copies the stacked ring, whose slots are then mostly live.  All
  of it is pure buffer transfer (``copy_to_host_async`` then a
  ``device_get`` on the *drain* thread, never the step loop) — never
  device-side compute, which would queue behind in-flight steps.  Slots are
  delta-decoded into consecutive snapshots and fanned out to pluggable
  ``Sink``s
  (stdout text, buffered JSONL, in-process callbacks — the mechanism behind
  ``ScalpelRuntime.add_hook``).

Two integration modes:

* carried ring — the jitted step threads a ``SnapshotRing`` through its
  carry (``ring_append`` in-graph) and the loop hands the fresh ring to
  ``plane.publish``; the ring argument must NOT be donated so the drain
  thread can read the previous buffers while the next step runs.
* host-driven — ``plane.append(counters)`` dispatches a tiny jitted append
  against a plane-owned ring (what ``ScalpelRuntime.on_step`` uses when the
  caller does not carry a ring).
"""
from __future__ import annotations

import atexit
import dataclasses
import os
import sys
import threading
import time
import traceback
import weakref
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from . import report as report_lib
from .context import MonitorSpec
from .counters import CounterState

Array = Any


# ---------------------------------------------------------------------------
# Device side: snapshot ring + dynamic telemetry params
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TelemetryParams:
    """Runtime-mutable telemetry knobs (dynamic jit inputs — no re-trace).

    cadence  scalar i32 — ring-append every ``cadence`` steps; 0 disables.
    """

    cadence: Array

    @staticmethod
    def of(cadence: int) -> "TelemetryParams":
        return TelemetryParams(cadence=jnp.asarray(max(0, int(cadence)),
                                                   jnp.int32))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SnapshotRing:
    """Device-side ring of counter snapshots + step stamps.

    The ring is generic over the counter pytree it snapshots — anything with
    ``calls``/``values``/``samples`` leaves: the legacy padded
    ``CounterState`` ([n_scopes, max_slots] values) or the compact
    dense-layout ``plan.CompactDelta`` ([total] lanes) that ``Monitor``
    threads, in which case telemetry snapshots stay compact end-to-end and
    reports read the dense layout directly.

    steps     [depth]                 i32 — step stamp per slot (-1 empty)
    calls     [depth, *calls_shape]   i32
    values    [depth, *values_shape]  f32
    samples   [depth, *samples_shape] i32
    last      counter pytree — O(1) mirror of the NEWEST snapshot
    last_step scalar i32 — step stamp of ``last``
    head      scalar i32 — total writes ever (monotonic; slot = seq % depth)

    ``last`` duplicates the most recent append into fixed, depth-independent
    buffers.  It exists for the drain's incremental fast path: when exactly
    one slot is newer than the drain cursor (the steady state of a drain
    that keeps up — and the case where re-copying a deep ring wastes
    (depth-1)/depth of the transfer), the host copies the mirror alone.
    Pure buffer transfers either way: the drain must never dispatch device
    computation (e.g. a gather of pending slots), because new device work
    queues behind every in-flight training step and delays snapshots — and
    the adaptive hooks riding them — by the whole dispatch window.
    """

    steps: Array
    calls: Array
    values: Array
    samples: Array
    last: CounterState
    last_step: Array
    head: Array

    @staticmethod
    def for_counters(counters, depth: int = 8) -> "SnapshotRing":
        """A ring templated on an arbitrary counter pytree (zeroed)."""
        d = int(depth)
        if d < 1:
            raise ValueError(f"ring depth must be >= 1, got {depth}")
        zero = jax.tree.map(jnp.zeros_like, counters)
        stack = jax.tree.map(
            lambda x: jnp.zeros((d,) + x.shape, x.dtype), zero
        )
        return SnapshotRing(
            steps=jnp.full((d,), -1, jnp.int32),
            calls=stack.calls,
            values=stack.values,
            samples=stack.samples,
            last=zero,
            last_step=jnp.full((), -1, jnp.int32),
            head=jnp.zeros((), jnp.int32),
        )

    @staticmethod
    def zeros(spec: MonitorSpec, depth: int = 8) -> "SnapshotRing":
        """Legacy padded template ([n_scopes, max_slots] CounterState)."""
        return SnapshotRing.for_counters(CounterState.zeros(spec), depth)

    @staticmethod
    def zeros_compact(spec: MonitorSpec, depth: int = 8) -> "SnapshotRing":
        """Compact dense-layout template (what ``Monitor`` states carry)."""
        from . import plan as plan_lib

        return SnapshotRing.for_counters(
            plan_lib.CompactDelta.zeros(spec), depth
        )

    @property
    def depth(self) -> int:
        return int(self.steps.shape[0])

    def slot_state(self, slot: int):
        """The counter pytree stored in ring slot ``slot`` (host or device),
        of the same type as the ring's template."""
        return type(self.last)(
            calls=self.calls[slot],
            values=self.values[slot],
            samples=self.samples[slot],
        )


@jax.named_scope("scalpel.ring_append")
def ring_append(ring: SnapshotRing, counters,
                tparams: TelemetryParams, step) -> SnapshotRing:
    """``lax.cond``-guarded ring append — pure device work, jit/scan safe.

    Writes a snapshot of ``counters`` (any counter pytree matching the
    ring's template — CounterState or compact CompactDelta) stamped
    ``step`` when ``step`` is a multiple of the (dynamic) cadence;
    otherwise a no-op.  ``step`` is a traced i32 scalar (e.g.
    ``tstate.step + 1``), so neither the cadence nor the step value ever
    re-traces the caller.  Besides the ring slot, the O(1) ``last`` mirror
    is refreshed — the drain's one-slot fast path.
    """
    step = jnp.asarray(step, jnp.int32)
    cadence = jnp.maximum(tparams.cadence, 1)
    do = (tparams.cadence > 0) & (step % cadence == 0)

    def write(r: SnapshotRing) -> SnapshotRing:
        slot = r.head % r.steps.shape[0]
        return SnapshotRing(
            steps=jax.lax.dynamic_update_index_in_dim(
                r.steps, step, slot, 0),
            calls=jax.lax.dynamic_update_index_in_dim(
                r.calls, counters.calls, slot, 0),
            values=jax.lax.dynamic_update_index_in_dim(
                r.values, counters.values, slot, 0),
            samples=jax.lax.dynamic_update_index_in_dim(
                r.samples, counters.samples, slot, 0),
            last=counters,
            last_step=step,
            head=r.head + 1,
        )

    return jax.lax.cond(do, write, lambda r: r, ring)


# ---------------------------------------------------------------------------
# Token egress (serving): per-lane sampled tokens ride the telemetry plane
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TokenRing:
    """Device-side ring of per-lane sampled tokens — the serve engine's
    egress lane.

    Counters tolerate a cadence (``ring_append`` samples the cumulative
    state); sampled tokens do NOT — dropping one corrupts the request's
    output stream.  So the token ring is appended UNCONDITIONALLY once per
    decode step inside the megastep scan, and the engine sizes ``depth``
    to cover more inner steps than ever elapse between drains.

    steps  [depth]           i32 — decode-step stamp per slot (-1 empty)
    toks   [depth, n_lanes]  i32 — the token each lane CONSUMED this step
    live   [depth, n_lanes]  i32 — 1 where the lane was active (the other
                                   lanes' slots are decode garbage)
    head   scalar            i32 — total appends ever (slot = seq % depth)
    """

    steps: Array
    toks: Array
    live: Array
    head: Array

    @staticmethod
    def zeros(n_lanes: int, depth: int = 32) -> "TokenRing":
        d, n = int(depth), int(n_lanes)
        if d < 1 or n < 1:
            raise ValueError(f"token ring needs depth/lanes >= 1, got "
                             f"{depth}/{n_lanes}")
        return TokenRing(
            steps=jnp.full((d,), -1, jnp.int32),
            toks=jnp.zeros((d, n), jnp.int32),
            live=jnp.zeros((d, n), jnp.int32),
            head=jnp.zeros((), jnp.int32),
        )

    @property
    def depth(self) -> int:
        return int(self.steps.shape[0])

    @property
    def n_lanes(self) -> int:
        return int(self.toks.shape[1])


def token_ring_append(ring: TokenRing, toks, live, step) -> TokenRing:
    """Unconditional token append — pure device work, jit/scan safe.

    ``toks``/``live``: [n_lanes] i32; ``step``: traced i32 scalar.
    """
    slot = ring.head % ring.steps.shape[0]
    return TokenRing(
        steps=jax.lax.dynamic_update_index_in_dim(
            ring.steps, jnp.asarray(step, jnp.int32), slot, 0),
        toks=jax.lax.dynamic_update_index_in_dim(
            ring.toks, jnp.asarray(toks, jnp.int32), slot, 0),
        live=jax.lax.dynamic_update_index_in_dim(
            ring.live, jnp.asarray(live, jnp.int32), slot, 0),
        head=ring.head + 1,
    )


# ---------------------------------------------------------------------------
# Host side: snapshots and sinks
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TelemetrySnapshot:
    """One drained ring slot, delta-decoded against its predecessor.

    state/delta are host (numpy) counter pytrees — CounterState for legacy
    padded rings, compact ``plan.CompactDelta`` for Monitor rings (reports
    are built straight off the dense layout either way): ``state`` is the
    cumulative counters at ``step``; ``delta`` is the increment since the
    previously drained snapshot (== ``state`` for the first one).
    """

    step: int
    seq: int                    # monotonic ring sequence number
    state: Any
    delta: Any
    spec: MonitorSpec

    def __post_init__(self):
        self._reports: list | None = None

    @property
    def reports(self) -> list[report_lib.ScopeReport]:
        """Cumulative per-scope reports (built lazily, cached)."""
        if self._reports is None:
            self._reports = report_lib.build(self.spec, self.state)
        return self._reports

    @property
    def delta_reports(self) -> list[report_lib.ScopeReport]:
        return report_lib.build(self.spec, self.delta)


class Sink:
    """Pluggable consumer of drained snapshots (emit on the drain thread)."""

    def emit(self, snap: TelemetrySnapshot) -> None:  # pragma: no cover
        raise NotImplementedError

    def flush(self) -> None:
        pass

    def close(self) -> None:
        self.flush()

    def stats(self) -> dict:
        """Sink-specific health counters, merged into
        ``TelemetryPlane.stats()['sinks']`` — e.g. the fleet agent's
        ``dropped_frames``/``reconnects``.  Default: nothing to report."""
        return {}


class TextSink(Sink):
    """Paper's default sink — human-readable text, one block per snapshot."""

    def __init__(self, stream=None, title: str = "ScALPEL telemetry"):
        self.stream = stream
        self.title = title

    def emit(self, snap: TelemetrySnapshot) -> None:
        out = self.stream or sys.stdout
        text = report_lib.format_text(
            snap.reports, title=f"{self.title} @ step {snap.step}"
        )
        print(text, file=out)


class JsonlSink(Sink):
    """Buffered JSONL sink — one open file handle, writes off the hot path
    (replaces ``report_lib.write_jsonl``'s per-call ``open()``)."""

    def __init__(self, path: str, buffer_lines: int = 64):
        self._writer = report_lib.JsonlWriter(path, buffer_lines=buffer_lines)

    def emit(self, snap: TelemetrySnapshot) -> None:
        # stamp each line with the producing spec's plan fingerprint so the
        # stream stays attributable across config hot-swaps
        self._writer.write(snap.step, snap.reports,
                           plan=snap.spec.fingerprint[:12])

    def flush(self) -> None:
        self._writer.flush()

    def close(self) -> None:
        self._writer.close()


class CallbackSink(Sink):
    """In-process adaptive hook: ``fn(snapshot)`` per drained snapshot."""

    def __init__(self, fn: Callable[[TelemetrySnapshot], None]):
        self.fn = fn

    def emit(self, snap: TelemetrySnapshot) -> None:
        self.fn(snap)


# ---------------------------------------------------------------------------
# The plane: background drain + fan-out
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _SinkRecord:
    """Per-sink failure accounting (drain-thread hardening).

    ``retry_at`` is in units of ``drain_count`` — exponential backoff in
    drains, not wall time, so a paused producer doesn't burn retries."""

    name: str
    errors: int = 0
    consecutive: int = 0
    retry_at: int = 0
    dropped: bool = False
    logged: bool = False


_PLANES: "weakref.WeakSet[TelemetryPlane]" = weakref.WeakSet()
_ATEXIT_INSTALLED = False


def exit_hook(fn: Callable[[], Any]) -> Callable[[], None]:
    """``fn`` as an atexit hook whose failure fails the process.

    Python prints an exception raised in an atexit hook and still exits 0,
    so a drain that failed during the final flush would pass unseen: the
    hook prints the traceback and ends the process with status 1."""

    def hook() -> None:
        try:
            fn()
        except Exception:
            traceback.print_exc()
            sys.stdout.flush()
            sys.stderr.flush()
            os._exit(1)

    return hook


def _close_all_planes() -> None:
    """Close every live plane; raise the first failure after all closed."""
    first = None
    for p in list(_PLANES):
        try:
            p.close()
        except Exception as e:
            first = first or e
    if first is not None:
        raise first


def _start_host_copies(tree) -> None:
    """Start non-blocking device→host copies of every leaf of ``tree``; the
    caller gathers them with ``np.asarray`` (pure transfers, no compute)."""
    for x in jax.tree.leaves(tree):
        x.copy_to_host_async()


class TelemetryPlane:
    """Owns the telemetry cadence, the drain thread, and the sink fan-out.

    The step loop only ever (a) dispatches a device-side ring append and
    (b) swaps a ring reference into the plane — no host synchronization.
    The drain thread performs every device→host transfer.
    """

    def __init__(self, spec: MonitorSpec, depth: int = 8, cadence: int = 1,
                 sinks: tuple = (), interval_s: float = 0.02):
        self.spec = spec
        self.depth = max(1, int(depth))
        self.interval_s = float(interval_s)
        self.sinks: list[Sink] = []
        self._cadence = max(0, int(cadence))
        self.params = TelemetryParams.of(self._cadence)

        # drain-thread hardening: per-sink failure records — a raising sink
        # is retried with exponential backoff and dropped after
        # ``max_sink_failures`` consecutive failures, never killing drains
        self._sink_records: dict[int, _SinkRecord] = {}
        self._sink_seq = 0
        self.max_sink_failures = 5
        self.dropped_sinks: list[str] = []
        for s in sinks:
            self.add_sink(s)

        self._ring: SnapshotRing | None = None      # latest published ring
        self._own_ring: SnapshotRing | None = None  # host-driven mode
        self._append_fn = jax.jit(ring_append)
        self._appends = 0

        # token-egress lineage (serving): independent ring + cursor — the
        # engine's host loop drains it explicitly (pipelined one megastep
        # behind the dispatch), it never rides the background drain thread
        self._tok_ring: TokenRing | None = None
        self._tok_cursor = 0
        self.tok_slots_copied = 0
        self.dropped_tokens = 0
        self.token_drains = 0

        self._drained_head = 0
        self._prev_state: CounterState | None = None  # last drained (host)
        self._last_step = -1
        self.dropped_snapshots = 0
        self.drain_count = 0
        # device→host transfer accounting: ring slots actually copied (the
        # incremental drain copies only slots newer than the cursor, so at
        # depth ≫ pending this is far below drain_count * depth)
        self.slots_copied = 0
        # host seconds of drain WORK: snapshots built from transfers already
        # on the host, plus sink emits — the adaptive budget loop's measured
        # monitoring overhead (span ``scalpel.drain``)
        self.drain_seconds = 0.0
        # host seconds the drains spent WAITING: for the ring's head (its
        # producing step still running on the device) and for the host
        # copies of pending slots — device time, not monitoring work
        self.drain_wait_seconds = 0.0
        self._in_work = False  # a sink's emit re-entering the drain

        self._lock = threading.Lock()          # ring ref + counters
        # RLock: a hook/sink may call runtime.report()/flush() from inside
        # its own emit (on the drain thread) — the re-entrant drain sees an
        # up-to-date cursor and returns empty instead of deadlocking.
        self._drain_lock = threading.RLock()   # serializes drains
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._drain_error: Exception | None = None
        self._closed = False

        global _ATEXIT_INSTALLED
        _PLANES.add(self)
        if not _ATEXIT_INSTALLED:
            atexit.register(exit_hook(_close_all_planes))
            _ATEXIT_INSTALLED = True

    # -- configuration ----------------------------------------------------
    @property
    def cadence(self) -> int:
        return self._cadence

    def set_cadence(self, cadence: int) -> None:
        """Swap the ring-append cadence — a dynamic-input swap, no re-trace."""
        self._cadence = max(0, int(cadence))
        self.params = TelemetryParams.of(self._cadence)

    def add_sink(self, sink: Sink) -> Sink:
        self.sinks.append(sink)
        self._sink_seq += 1
        self._sink_records.setdefault(
            id(sink),
            _SinkRecord(name=f"{type(sink).__name__}#{self._sink_seq}"),
        )
        return sink

    @property
    def sink_errors(self) -> dict[str, int]:
        """Cumulative emit/flush failures per sink (empty when healthy)."""
        return {
            r.name: r.errors for r in self._sink_records.values()
            if r.errors
        }

    def stats(self) -> dict:
        """One uniform health dict for the whole plane (fleet-inspectable).

        Fixes the old accounting asymmetry: drain counters, per-sink error
        records, AND sink-specific extras (``Sink.stats()`` — the fleet
        agent's ``dropped_frames``/``reconnects``) all surface here, so
        ``report()`` and the fleet head read one shape.
        """
        sinks: dict[str, dict] = {}
        for s in list(self.sinks):
            rec = self._sink_records.get(id(s))
            name = rec.name if rec is not None else type(s).__name__
            entry = {"errors": rec.errors if rec is not None else 0,
                     "dropped": False}
            try:
                entry.update(s.stats() or {})
            except Exception:  # pragma: no cover - sink bug isolation
                entry["stats_error"] = True
            sinks[name] = entry
        for name in self.dropped_sinks:
            sinks.setdefault(name, {})["dropped"] = True
            rec = next((r for r in self._sink_records.values()
                        if r.name == name), None)
            if rec is not None:
                sinks[name].setdefault("errors", rec.errors)
        return {
            "cadence": self._cadence,
            "drain_count": self.drain_count,
            "drain_seconds": round(self.drain_seconds, 6),
            "drain_wait_seconds": round(self.drain_wait_seconds, 6),
            "slots_copied": self.slots_copied,
            "dropped_snapshots": self.dropped_snapshots,
            "dropped_tokens": self.dropped_tokens,
            "sink_errors": dict(self.sink_errors),
            "dropped_sinks": list(self.dropped_sinks),
            "sinks": sinks,
        }

    def _sink_failed(self, sink: Sink, rec: _SinkRecord,
                     where: str = "emit") -> None:
        rec.errors += 1
        rec.consecutive += 1
        if not rec.logged:
            rec.logged = True
            print(
                f"scalpel telemetry: sink {rec.name} raised in {where} "
                f"({sys.exc_info()[0].__name__}: {sys.exc_info()[1]}); "
                "retrying with backoff (logged once)",
                file=sys.stderr,
            )
        if rec.consecutive >= self.max_sink_failures:
            rec.dropped = True
            self.dropped_sinks.append(rec.name)
            try:
                self.sinks.remove(sink)
            except ValueError:
                pass
            print(
                f"scalpel telemetry: sink {rec.name} dropped after "
                f"{rec.consecutive} consecutive failures",
                file=sys.stderr,
            )
            try:
                sink.close()
            except Exception:
                pass
        else:
            # exponential backoff in drains: skip 2, 4, 8, ... drains
            rec.retry_at = self.drain_count + (1 << rec.consecutive)

    def _reset_epoch(self) -> None:
        """Drain pending slots, then reset the drain cursor + delta base."""
        self._drain_once()
        with self._lock:
            self._ring = None
            self._own_ring = None
            self._drained_head = 0
            self._prev_state = None

    def make_ring(self, compact: bool = False) -> SnapshotRing:
        """A fresh device ring for loops that carry it through their step.

        ``compact=True`` templates the ring on the spec's dense slot layout
        (what ``Monitor`` states carry) instead of the padded CounterState.

        Starts a new ring *epoch*: pending slots of the previously published
        ring are drained first, then the drain cursor and delta base reset —
        a fresh ring's ``head`` restarts at 0, so carrying the old cursor
        over would silently stop draining.  The plane tracks one ring
        lineage at a time; producers that need independent lineages (e.g.
        two serve engines) should each own a runtime/plane.
        """
        self._reset_epoch()
        if compact:
            return SnapshotRing.zeros_compact(self.spec, self.depth)
        return SnapshotRing.zeros(self.spec, self.depth)

    def make_token_ring(self, n_lanes: int, depth: int = 32) -> TokenRing:
        """A fresh token-egress ring; starts a new token lineage (the
        cursor resets — a fresh ring's head restarts at 0)."""
        with self._lock:
            self._tok_ring = None
            self._tok_cursor = 0
        return TokenRing.zeros(n_lanes, depth)

    def publish_tokens(self, ring: TokenRing) -> None:
        """Hand the latest carried token ring to the plane (ref swap only).

        Same contract as ``publish``: the ring's buffers must never be
        donated to a later megastep — ``drain_tokens`` reads them while the
        next megastep runs.
        """
        with self._lock:
            self._tok_ring = ring

    def drain_tokens(self, step: int | None = None
                     ) -> list[tuple[int, int, np.ndarray, np.ndarray]]:
        """Drain pending token-ring slots past the token cursor.

        Returns ``(seq, step, toks[n_lanes], live[n_lanes])`` per slot, in
        append order.  Pure buffer transfers, exactly like the counter
        drain: one scalar head probe when idle, one stacked copy
        (``copy_to_host_async`` + host gather) when slots are pending —
        NEVER device computation (the ROADMAP drain invariant; the np
        materialization blocks only until the producing megastep retires,
        which is the engine's sanctioned request-completion sync point).

        Spans (``step``, the caller's megastep index, rides as their
        argument): ``scalpel.tokens.wait`` until the head and the host
        copies are ready, ``scalpel.tokens`` for the copy-out after that.
        """
        with self._lock:
            ring = self._tok_ring
        self.token_drains += 1
        if ring is None:
            return []
        args = {} if step is None else {"step": int(step)}
        with TraceAnnotation("scalpel.tokens.wait", **args):
            head = int(jax.device_get(ring.head))
            if head < self._tok_cursor:
                # fresh lineage published without make_token_ring()
                self._tok_cursor = 0
            if head <= self._tok_cursor:
                return []
            _start_host_copies((ring.steps, ring.toks, ring.live))
            steps_h = np.asarray(ring.steps)
            toks_h = np.asarray(ring.toks)
            live_h = np.asarray(ring.live)
        with TraceAnnotation("scalpel.tokens", **args):
            depth = ring.depth
            first = max(self._tok_cursor, head - depth)
            # tokens are outputs, not samples: an overrun is data loss, so
            # the engine sizes depth > steps-per-drain; account it loudly
            self.dropped_tokens += first - self._tok_cursor
            out = []
            for seq in range(first, head):
                s = seq % depth
                out.append((seq, int(steps_h[s]), toks_h[s], live_h[s]))
            self.tok_slots_copied += depth
            self._tok_cursor = head
        return out

    # -- producer side (step loop; never blocks on device) ----------------
    def publish(self, ring: SnapshotRing) -> None:
        """Hand the latest carried ring to the drain thread (ref swap only).

        Deliberately does NOT wake the drain thread: draining is paced by
        ``interval_s`` (and by explicit ``flush()``), so a hot step loop
        publishing every step never induces per-step drain work.  The ring's
        buffers must not be donated to a later step — the drain thread reads
        them concurrently with subsequent dispatches.
        """
        with self._lock:
            self._ring = ring
        self._ensure_thread()

    def append(self, counters, step: int | None = None) -> None:
        """Host-driven mode: dispatch a jitted ring append (async, device).

        The plane-owned ring is templated on the first ``counters`` pytree
        appended (padded CounterState or compact), so either layout works.
        """
        if self._own_ring is None:
            # outside the lock: the reset drains (its own locks) first
            self._reset_epoch()
            ring = SnapshotRing.for_counters(counters, self.depth)
            with self._lock:
                self._own_ring = ring
        with self._lock:
            self._appends += 1
            stamp = self._appends if step is None else int(step)
            self._own_ring = self._append_fn(
                self._own_ring, counters, self.params,
                jnp.asarray(stamp, jnp.int32),
            )
            self._ring = self._own_ring
        self._ensure_thread()

    # -- consumer side ----------------------------------------------------
    @property
    def last_state(self) -> CounterState | None:
        """Most recently drained cumulative CounterState (host numpy)."""
        return self._prev_state

    @property
    def last_step(self) -> int:
        return self._last_step

    def flush(self) -> list[TelemetrySnapshot]:
        """Synchronously drain every pending ring slot and flush sinks.

        Raises if the background drain thread died since the last call."""
        self._raise_drain_error()
        snaps = self._drain_once()
        for s in list(self.sinks):
            try:
                s.flush()
            except Exception:
                rec = self._sink_records.get(id(s))
                if rec is not None:
                    self._sink_failed(s, rec, where="flush")
        return snaps

    def close(self) -> None:
        """Stop the drain thread, flush remaining slots, close sinks.

        Raises, after closing the sinks, if the background drain thread
        died since the last ``flush()``."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        self._wake.set()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout=5.0)
        try:
            self._raise_drain_error()
            self._drain_once()
        finally:
            for s in list(self.sinks):
                try:
                    s.close()
                except Exception:
                    pass

    # -- drain machinery ---------------------------------------------------
    def _ensure_thread(self) -> None:
        if self._closed or self._drain_error is not None or (
                self._thread is not None and self._thread.is_alive()):
            return
        self._thread = threading.Thread(
            target=self._drain_loop, name="scalpel-telemetry-drain",
            daemon=True,
        )
        self._thread.start()

    def _drain_loop(self) -> None:
        while not self._stop.is_set():
            self._wake.wait(timeout=self.interval_s)
            self._wake.clear()
            try:
                self._drain_once()
            except Exception as e:
                # kept for the caller's thread: flush()/close() re-raise it
                self._drain_error = e
                return

    def _raise_drain_error(self) -> None:
        """Re-raise (once) an exception the background drain died with."""
        err, self._drain_error = self._drain_error, None
        if err is not None:
            raise RuntimeError(
                "scalpel telemetry: the background drain thread failed"
            ) from err

    def _drain_once(self) -> list[TelemetrySnapshot]:
        # times are taken INSIDE the lock: lock-wait is neither the device's
        # time nor drain work, and two threads racing a drain must not
        # double-count the same wall time
        with self._drain_lock:
            return self._drain_once_inner()

    def _drain_once_inner(self) -> list[TelemetrySnapshot]:
        # a drain re-entered from a sink's emit is part of that emit: the
        # outer drain times it as work, and it writes no span of its own
        outer = not self._in_work
        t0 = time.perf_counter()
        try:
            pending = self._gather()
        finally:
            if outer:
                self.drain_wait_seconds += time.perf_counter() - t0
        if pending is None:
            return []
        if not outer:
            return self._fan_out(*pending)
        newest_step = pending[-1][-1][1]
        self._in_work = True
        t1 = time.perf_counter()
        try:
            with TraceAnnotation("scalpel.drain", step=newest_step):
                return self._fan_out(*pending)
        finally:
            self._in_work = False
            self.drain_seconds += time.perf_counter() - t1

    def _gather(self):
        """The drain's wait: probe the ring's head and bring the pending
        slots to the host.  ``None`` when nothing is pending, else
        ``(head, first, slots copied, [(seq, step, host state), ...])``."""
        with self._lock:
            ring = self._ring
        if ring is None:
            return None
        # Probe the scalar head first: an idle tick (nothing appended
        # since the last drain) costs one scalar transfer, not a full
        # depth x CounterState ring copy.
        head = int(jax.device_get(ring.head))
        if head < self._drained_head:
            # a fresh ring lineage was published without make_ring():
            # its head restarted below our cursor — start a new epoch
            # rather than silently never draining again.
            self._drained_head = 0
            self._prev_state = None
        if head <= self._drained_head:
            return None
        depth = ring.depth
        first = max(self._drained_head, head - depth)
        # Incremental drain, as pure buffer transfers (never device
        # compute — new device work queues behind in-flight steps and
        # delays snapshots by the whole dispatch window):
        #   pending == 1 — the steady state of a drain keeping up with
        #     the append cadence: copy the O(1) ``last`` mirror alone,
        #     one slot's worth of bytes no matter how deep the ring is.
        #   pending > 1 — catching up: copy the stacked ring once; the
        #     pending slots are the bulk of it anyway.
        # Non-blocking device→host: start the copies, then gather on
        # THIS (drain) thread — the step loop never waits.
        if head - first == 1:
            _start_host_copies((ring.last, ring.last_step))
            state = jax.tree.map(np.asarray, ring.last)
            return head, first, 1, [
                (head - 1, int(np.asarray(ring.last_step)), state)]
        _start_host_copies((ring.steps, ring.calls, ring.values,
                            ring.samples))
        steps_h = np.asarray(ring.steps)
        calls_h = np.asarray(ring.calls)
        values_h = np.asarray(ring.values)
        samples_h = np.asarray(ring.samples)
        mk = type(ring.last)  # ring template: padded or compact
        slots = []
        for seq in range(first, head):
            s = seq % depth  # host-side slicing of the host copy
            slots.append((seq, int(steps_h[s]),
                          mk(calls=calls_h[s], values=values_h[s],
                             samples=samples_h[s])))
        return head, first, depth, slots

    def _fan_out(self, head: int, first: int, copied: int,
                 slots: list) -> list[TelemetrySnapshot]:
        """The drain's work: delta-decode the host slots into snapshots,
        advance the cursor, emit to every sink."""
        self.dropped_snapshots += first - self._drained_head
        out: list[TelemetrySnapshot] = []
        for seq, step_no, state in slots:
            prev = self._prev_state
            delta = state if prev is None else state.sub(prev)
            out.append(TelemetrySnapshot(
                step=step_no, seq=seq, state=state, delta=delta,
                spec=self.spec,
            ))
            self._prev_state = state
            self._last_step = step_no
        self.slots_copied += copied
        self._drained_head = head
        self.drain_count += 1
        # hardened fan-out: a raising sink never kills the drain loop —
        # its failure is recorded, it backs off exponentially (in
        # drains), and after max_sink_failures consecutive failures it
        # is dropped; healthy sinks are untouched either way.
        for s in list(self.sinks):
            rec = self._sink_records.get(id(s))
            if rec is None:     # registered behind add_sink's back
                self._sink_seq += 1
                rec = _SinkRecord(
                    name=f"{type(s).__name__}#{self._sink_seq}")
                self._sink_records[id(s)] = rec
            if rec.retry_at > self.drain_count:
                continue        # backing off
            for snap in out:
                try:
                    s.emit(snap)
                    rec.consecutive = 0
                    rec.retry_at = 0
                except Exception:
                    self._sink_failed(s, rec)
                    break       # this drain is over for this sink
        return out
