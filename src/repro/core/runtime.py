"""ScALPEL runtime — config reload via SIGUSR1, async counter access,
adaptive hooks (paper §3.3 + C5), now pull-based on the telemetry plane.

The runtime owns the live (MonitorSpec, MonitorParams, CounterState) triple
plus a ``TelemetryPlane`` (telemetry.py).  The jitted step receives
``params`` (and optionally ``telemetry.params`` + a carried ``SnapshotRing``)
as ordinary inputs, so everything the runtime mutates is swap-in-place
between steps — never a re-trace.

* ``SIGUSR1`` (or ``reload()``) re-reads the config file and rebuilds the
  masks/periods — the paper's "a new configuration file may be loaded at any
  time by sending a signal to the application".
* ``on_step(state[, ring])`` records the step WITHOUT host synchronization:
  it swaps the state reference and either publishes the carried ring or
  dispatches a device-side ring append.  All device→host transfers happen on
  the plane's background drain thread.
* ``add_hook(fn)`` registers an adaptive callback ``fn(runtime, reports)``
  that now runs on *drained snapshots* (a CallbackSink on the drain thread)
  instead of stalling the step loop — the mechanism the paper motivates for
  "runtime decisions based on performance characteristics" (straggler
  detection and NaN tripwires in train/loop.py).
* ``snapshot()``/``report()`` remain synchronous conveniences: they flush
  the ring (so sinks and hooks catch up) and read the current state.
* at exit (or ``report()``) counters are written to stdout, the paper's
  default sink.
"""
from __future__ import annotations

import atexit
import signal
import threading
import traceback
from typing import Callable

import jax

from . import config_file, report as report_lib, telemetry as telemetry_lib
from .context import MonitorSpec
from .counters import CounterState, MonitorParams


class ScalpelRuntime:
    def __init__(
        self,
        spec: MonitorSpec,
        params: MonitorParams | None = None,
        config_path: str | None = None,
        install_signal: bool = False,
        report_at_exit: bool = False,
        jsonl_path: str | None = None,
        hook_every: int = 1,
        ring_depth: int = 8,
        sinks: tuple = (),
        drain_interval_s: float = 0.01,
        graceful_shutdown: bool = False,
    ):
        self.spec = spec
        self._lock = threading.Lock()
        self.config_path = config_path
        self.jsonl_path = jsonl_path
        self._hooks: list[Callable] = []
        self._step = 0
        self._closed = False
        self.controller = None
        self.fleet_agent = None
        self._shutdown_installed = False
        self._prev_handlers: dict[int, object] = {}
        self.state = CounterState.zeros(spec)
        self.reload_count = 0
        self.last_reload_errors: list[str] = []

        self.telemetry = telemetry_lib.TelemetryPlane(
            spec, depth=ring_depth, cadence=max(1, hook_every),
            sinks=sinks, interval_s=drain_interval_s,
        )
        if jsonl_path:
            self.telemetry.add_sink(telemetry_lib.JsonlSink(jsonl_path))

        if params is not None:
            self.params = params
        elif config_path is not None:
            self.params = self._params_from_file(config_path)
        else:
            self.params = MonitorParams.all_on(spec)

        if install_signal:
            signal.signal(signal.SIGUSR1, self._on_sigusr1)
        if report_at_exit:
            atexit.register(telemetry_lib.exit_hook(self._exit_report))
        if graceful_shutdown:
            self.install_shutdown()

    # -- config reload ----------------------------------------------------
    def _params_from_file(self, path: str) -> MonitorParams:
        cfg = config_file.parse_file(path)
        params, missing = config_file.apply_config(self.spec, cfg)
        self.last_reload_errors = missing
        return params

    def _on_sigusr1(self, signum, frame):
        del signum, frame
        self.reload()

    def reload(self, path: str | None = None) -> None:
        """Swap in a new config — masks/periods only, never a re-trace."""
        path = path or self.config_path
        if path is None:
            raise ValueError("no config path to reload from")
        with self._lock:
            self.params = self._params_from_file(path)
            self.config_path = path
            self.reload_count += 1

    def set_params(self, params: MonitorParams) -> None:
        with self._lock:
            self.params = params

    # -- probe plans (static — the traced half the runtime can NOT swap) ---
    @property
    def plan_fingerprint(self) -> str:
        """Hash of the compiled probe plans (plan.py).  Constant across
        reload()/set_params()/cadence swaps — the attestation that runtime
        reconfiguration re-selects among compiled per-set plans instead of
        re-tracing."""
        return self.spec.fingerprint

    def describe_plans(self) -> str:
        """The live spec's per-(scope, event set) plan table."""
        from . import plan as plan_lib

        return plan_lib.describe_plans(self.spec)

    # -- telemetry cadence (dynamic — swapping it never re-traces) --------
    @property
    def hook_every(self) -> int:
        return self.telemetry.cadence

    @hook_every.setter
    def hook_every(self, n: int) -> None:
        self.telemetry.set_cadence(max(1, int(n)))

    # -- step bookkeeping ---------------------------------------------------
    def on_step(self, new_state,
                ring: telemetry_lib.SnapshotRing | None = None) -> None:
        """Record a step's carried state — no host synchronization.

        ``new_state``: the padded CounterState or any compact carrier
        (``MonitorState.counters``) — reports read either layout directly.

        ``ring``: the loop-carried SnapshotRing if the jitted step appends
        in-graph (train/loop.py, serve/engine.py); its buffers are handed to
        the drain thread, so the ring argument must never be donated.
        Without one, a device-side append is dispatched against a
        plane-owned ring (host-driven mode).
        """
        self.state = new_state
        self._step += 1
        if ring is not None:
            self.telemetry.publish(ring)
        else:
            self.telemetry.append(new_state, step=self._step)

    def observe(self, state: CounterState) -> None:
        """Update the live state reference without ticking telemetry (used
        by consumers that accumulate counters outside on_step cadence)."""
        self.state = state

    # -- async access (C5) --------------------------------------------------
    def flush(self) -> list[telemetry_lib.TelemetrySnapshot]:
        """Drain every pending ring slot through the sinks, synchronously."""
        return self.telemetry.flush()

    def snapshot(self, flush: bool = True) -> list[report_lib.ScopeReport]:
        if flush:
            self.flush()
        state = jax.tree.map(jax.device_get, self.state)
        return report_lib.build(self.spec, state)

    def estimates(self) -> dict[str, dict[str, float]]:
        state = jax.tree.map(jax.device_get, self.state)
        return report_lib.estimates(self.spec, state)

    def add_hook(self, fn: Callable) -> None:
        """Register ``fn(runtime, reports)`` to run on drained snapshots."""
        if not self._hooks:
            self.telemetry.add_sink(
                telemetry_lib.CallbackSink(self._dispatch_hooks)
            )
        self._hooks.append(fn)

    def _dispatch_hooks(self, snap: telemetry_lib.TelemetrySnapshot) -> None:
        reports = snap.reports
        for fn in list(self._hooks):
            fn(self, reports)

    # -- adaptive controller (core/adaptive.py) ---------------------------
    def attach_controller(self, config=None):
        """Attach and install an ``AdaptiveController`` on this runtime's
        telemetry plane — the closed adaptive loop (escalate / de-escalate /
        budget) driving ``set_params``/``set_cadence`` from drained
        snapshots.  Returns the controller; the step loop's existing
        ``mon.sync(mstate, runtime=runtime)`` picks up its decisions."""
        from . import adaptive as adaptive_lib

        ctl = adaptive_lib.AdaptiveController(self, config=config)
        ctl.install()
        self.controller = ctl
        if self.fleet_agent is not None:
            # a fleet agent attached first still delivers downlink hints
            self.fleet_agent.controller = ctl
        return ctl

    # -- fleet telemetry (repro.telemetry) ---------------------------------
    def attach_fleet_agent(self, host_id: str, address, **kwargs):
        """Attach a ``repro.telemetry.FleetAgent`` as a sink on this
        runtime's plane: every drained snapshot ships one wire frame to the
        aggregator at ``address``.

        Rides the existing idempotent close path — ``close()``/
        ``shutdown()`` (and the SIGTERM/atexit route when
        ``graceful_shutdown`` is on) flush the agent's buffered frames and
        emit its final ``shutdown=true`` frame exactly once, because the
        plane closes each sink exactly once.  The current controller (if
        any) receives head-level escalation hints from the downlink.
        Returns the agent (also kept as ``self.fleet_agent``).
        """
        from repro.telemetry.agent import FleetAgent

        kwargs.setdefault("fingerprint", self.spec.fingerprint)
        kwargs.setdefault("controller", self.controller)
        agent = FleetAgent(host_id, address, **kwargs)
        self.telemetry.add_sink(agent)
        self.fleet_agent = agent
        return agent

    # -- graceful shutdown -------------------------------------------------
    def install_shutdown(self, signals=(signal.SIGTERM,)) -> None:
        """Install a SIGTERM + atexit path through ``shutdown()``.

        The signal handler chains to whatever handler was installed before
        (including re-raising a default-disposition signal after the flush,
        so the process still dies of SIGTERM).  Idempotent; a no-op off the
        main thread (signal.signal raises there)."""
        if self._shutdown_installed:
            return
        self._shutdown_installed = True
        atexit.register(telemetry_lib.exit_hook(self.shutdown))
        for sig in signals:
            try:
                self._prev_handlers[int(sig)] = signal.signal(
                    sig, self._on_shutdown_signal)
            except (ValueError, OSError):  # non-main thread / exotic signal
                pass

    def _on_shutdown_signal(self, signum, frame):
        try:
            self.shutdown()
        except Exception:
            # the process is ending on this signal either way: print the
            # failed final drain before the chained handler ends it
            traceback.print_exc()
        prev = self._prev_handlers.get(int(signum), signal.SIG_DFL)
        if callable(prev):
            prev(signum, frame)
        elif prev == signal.SIG_DFL:
            # restore the default disposition and re-deliver: the process
            # must still terminate from SIGTERM, just after the flush
            signal.signal(signum, signal.SIG_DFL)
            import os

            os.kill(os.getpid(), signum)

    def shutdown(self) -> str | None:
        """Graceful shutdown: flush the ring, drain pending snapshots,
        emit a final report, then close.  Idempotent with ``close()`` —
        whichever runs first wins and the other is a no-op.  Returns the
        final report text (None if already closed); a failed drain is
        raised after the close."""
        if self._closed:
            return None
        try:
            report = self.report("ScALPEL final report")
            print(report)
        finally:
            self.close()
        return report

    def close(self) -> None:
        """Stop the drain thread and flush/close every sink.

        Idempotent: a second close is a no-op, and the ``report_at_exit``
        atexit hook skips after an explicit close — without the guard the
        exit path re-flushed already-closed sinks (double-flush)."""
        if self._closed:
            return
        self._closed = True
        self.telemetry.close()

    @property
    def closed(self) -> bool:
        return self._closed

    # -- checkpoint attestation (plan identity across restarts) -----------
    def save_metadata(self) -> dict:
        """Metadata for checkpoint manifests: which compiled probe plans
        produced the counters being saved."""
        return {
            "plan_fingerprint": self.spec.fingerprint,
            "n_scopes": self.spec.n_scopes,
        }

    def check_resume_metadata(self, meta: dict | None, strict: bool = True):
        """Resume-time plan check: raise (or warn, ``strict=False``) when a
        checkpoint's counters were produced by different compiled plans
        than the live spec.  Returns True on match, None when the metadata
        predates fingerprinting (one shared implementation —
        ``monitor.check_plan_metadata`` — backs this and
        ``Monitor.check_resume``)."""
        from .monitor import check_plan_metadata

        return check_plan_metadata(self.spec.fingerprint, meta,
                                   strict=strict)

    # -- reporting ----------------------------------------------------------
    def report(self, title: str = "ScALPEL report") -> str:
        text = report_lib.format_text(self.snapshot(), title=title)
        return text + "\n" + self._telemetry_footer()

    def _telemetry_footer(self) -> str:
        """One-line plane-health footer: the drop-accounting surface the
        fleet tier inspects (``TelemetryPlane.stats()``), human-readable."""
        st = self.telemetry.stats()
        parts = [
            f"drains={st['drain_count']}",
            f"drain_s={st['drain_seconds']:.3f}",
            f"drain_wait_s={st['drain_wait_seconds']:.3f}",
            f"dropped_snapshots={st['dropped_snapshots']}",
        ]
        if st["sink_errors"]:
            errs = ",".join(f"{k}:{v}" for k, v in st["sink_errors"].items())
            parts.append(f"sink_errors=[{errs}]")
        if st["dropped_sinks"]:
            parts.append(f"dropped_sinks={st['dropped_sinks']}")
        agent = self.fleet_agent
        if agent is not None:
            a = agent.stats()
            parts.append(
                f"fleet[sent={a['frames_sent']} "
                f"dropped={a['dropped_frames']} "
                f"reconnects={a['reconnects']}]")
        return "telemetry: " + " ".join(parts)

    def _exit_report(self) -> None:
        if self._closed:
            # an explicit close() already flushed and closed the sinks; the
            # atexit pass must not re-drive them
            return
        print(self.report())
