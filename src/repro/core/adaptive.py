"""Self-retuning monitor controller — the closed adaptive loop (paper §3.3).

ScALPEL's pitch is *adaptive* monitoring: spend measurement budget only
where anomalies live, at run time, per function.  Every mechanism for that
already exists in this library — plan hot-swap without re-trace
(MonitorParams as dynamic jit inputs), drained-snapshot hooks
(telemetry.CallbackSink), dynamic ring cadence (TelemetryParams) — but the
policy was manual (SIGUSR1 + a hand-edited config file).  This module
closes the loop with an ``AdaptiveController`` in the Scalene/PerSyst
shape: watch cheap statistics, escalate on thresholds, decay when quiet.

The controller runs entirely ON THE TELEMETRY DRAIN THREAD, as a
``CallbackSink`` over drained ``CompactDelta`` snapshots.  It NEVER
dispatches device work (the ROADMAP invariant: new device work queues
behind in-flight steps and delays the very snapshots it reads) — every
action is a host-side reference swap (``runtime.set_params`` /
``TelemetryPlane.set_cadence``) that the step loop picks up at its next
``mon.sync``.

Three loops close per drained snapshot:

* **escalate** — a scope trips an anomaly detector (NaN/Inf tripwires,
  zero-fraction spikes, entropy collapse — all against running EWMA+MAD
  baselines from ``plan.compile_sentinels`` lanes; plus a global step-time
  outlier detector): widen that scope's event set (scope+slot masks all-on,
  multiplex period 1) and drop the ring cadence to ``escalated_cadence`` so
  snapshots arrive densely while the anomaly is live.
* **de-escalate** — a scope quiet for ``quiet_steps`` monitored STEPS
  (measured by the drained deltas' step-stamp spans, so a K-step megastep
  publishing one snapshot per K steps does not make the ladder K× more
  patient) steps DOWN the degradation ladder: WIDE → CONFIGURED (the
  params the controller was installed with) → SENTINEL.  The sentinel
  level is ``scope_mask = 0``: the probe path's ``lax.cond`` skips every
  event sweep while interception still counts calls — presence counters
  only, near-zero overhead.  Sentinel scopes are blind to tensor
  anomalies by construction; the global step-time detector wakes them
  back to CONFIGURED when the workload misbehaves.
* **budget** — a proportional controller retunes the global ring cadence
  to hold the measured monitoring overhead (the drain's work seconds from
  ``TelemetryPlane.drain_seconds``, which leave out its wait for the
  device, against wall time between step stamps) within
  ``overhead_budget`` of step time.

The step-time and budget loops measure per-DRAIN, normalized by the step
span: snapshots drained in one batch arrive back-to-back (a K-step
megastep flushes several cadence snapshots at once) with ~zero wall time
between them, so per-snapshot intervals would feed the EWMA+MAD baselines
garbage.  Deltas accumulate into a window keyed by the plane's
``drain_count`` and the detectors tick once per closed window — the
wall-clock and step spans both cover the full drain interval, and the
per-step baselines survive a steps-per-commit swap.

Hysteresis: every level change arms a per-scope cooldown of
``cooldown_steps`` monitored steps during which further changes for that
scope are suppressed — a flapping scope cannot thrash plans.  The one
asymmetry: tripwire escalations (NaN/Inf) bypass the cooldown; losing a
step's NaN localization to hysteresis would defeat the point.
"""
from __future__ import annotations

import dataclasses
import threading
import time

import jax.numpy as jnp
import numpy as np

from . import plan as plan_lib
from . import telemetry as telemetry_lib
from .context import MonitorSpec
from .counters import MonitorParams

# degradation ladder levels, ordered: higher == more monitoring
SENTINEL, CONFIGURED, WIDE = 0, 1, 2
LEVEL_NAMES = {SENTINEL: "sentinel", CONFIGURED: "configured", WIDE: "wide"}


@dataclasses.dataclass
class AdaptiveConfig:
    """Controller knobs (all host-side; none affect the traced graph).

    Detector thresholds are in MAD-scaled deviations from a running EWMA
    baseline: trip when ``|x - mean| > sigma * max(mad, floor)``.
    """

    # -- baselines / detectors -------------------------------------------
    ewma_alpha: float = 0.25        # baseline update rate
    warmup_drains: int = 3          # snapshots before a baseline can trip
    spike_sigma: float = 8.0        # zero-fraction spike threshold
    spike_floor: float = 0.02       # MAD floor for fraction-valued lanes
    collapse_sigma: float = 8.0     # entropy-collapse threshold
    collapse_floor: float = 0.05    # MAD floor for entropy lanes (nats)
    step_time_sigma: float = 6.0    # global step-time outlier threshold
    step_time_floor_s: float = 1e-3  # MAD floor for step time (seconds)

    # -- hysteresis ladder ------------------------------------------------
    # Quiet/cooldown accounting is in monitored STEPS (snapshot step-stamp
    # spans), not drained snapshots: with one snapshot per K-step megastep
    # the ladder's patience stays constant in steps across a K swap.  The
    # legacy ``*_drains`` names remain the defaults for the step-valued
    # knobs (at cadence 1, one drain == one step — identical behavior).
    cooldown_drains: int = 3        # default for cooldown_steps (legacy name)
    quiet_drains: int = 8           # default for quiet_steps (legacy name)
    cooldown_steps: int | None = None  # suppress level changes this many
                                       # steps after a change
    quiet_steps: int | None = None     # consecutive quiet steps to step down
    sentinel_enabled: bool = True   # allow CONFIGURED → SENTINEL decay

    # -- escalated monitoring ---------------------------------------------
    escalated_period: int = 1       # multiplex period while WIDE
    escalated_cadence: int = 1      # ring cadence floor while any scope WIDE

    # -- overhead budget --------------------------------------------------
    overhead_budget: float = 0.05   # target monitoring fraction of step
                                    # time; >= 1.0 disables the budget loop
    max_cadence: int = 256          # cadence ceiling the budget loop may reach

    # -- fleet hints (repro.telemetry head → agents downlink) -------------
    accept_fleet_hints: bool = True  # apply head-level escalation hints
                                     # arriving via a FleetAgent downlink


@dataclasses.dataclass(frozen=True)
class Transition:
    """One level change on the degradation ladder (controller audit trail)."""

    drain: int          # controller drain index when it happened
    step: int           # step stamp of the triggering snapshot
    scope: str
    frm: str            # level name before
    to: str             # level name after
    reason: str


class _Baseline:
    """Running EWMA mean + EWMA absolute deviation (MAD-style scale)."""

    __slots__ = ("mean", "dev", "n")

    def __init__(self):
        self.mean = 0.0
        self.dev = 0.0
        self.n = 0

    def update(self, x: float, alpha: float) -> None:
        if self.n == 0:
            self.mean = x
        else:
            self.dev += alpha * (abs(x - self.mean) - self.dev)
            self.mean += alpha * (x - self.mean)
        self.n += 1

    def outlier(self, x: float, sigma: float, floor: float,
                warmup: int) -> bool:
        if self.n < warmup:
            return False
        return abs(x - self.mean) > sigma * max(self.dev, floor)

    def low_outlier(self, x: float, sigma: float, floor: float,
                    warmup: int) -> bool:
        if self.n < warmup:
            return False
        return (self.mean - x) > sigma * max(self.dev, floor)


class AdaptiveController:
    """The closed loop: drained snapshots in, mask/cadence swaps out.

    Construct from a ``ScalpelRuntime`` (or pass ``spec``/``params``/
    ``telemetry`` explicitly for standalone use) and ``install()`` — the
    controller registers itself as a ``CallbackSink`` on the plane and from
    then on runs once per drained snapshot, on the drain thread.  Step
    loops pick up its decisions through ``mon.sync(mstate,
    runtime=runtime)`` (or ``mon.sync(mstate, controller=ctl)`` when no
    runtime is involved) — the controller itself never touches the device.
    """

    def __init__(self, runtime=None, *, spec: MonitorSpec | None = None,
                 params: MonitorParams | None = None,
                 telemetry: telemetry_lib.TelemetryPlane | None = None,
                 config: AdaptiveConfig | None = None):
        if runtime is not None:
            spec = runtime.spec if spec is None else spec
            params = runtime.params if params is None else params
            telemetry = runtime.telemetry if telemetry is None else telemetry
        if spec is None or telemetry is None:
            raise ValueError(
                "AdaptiveController needs a runtime or explicit "
                "spec+telemetry"
            )
        self.spec = spec
        self.cfg = config or AdaptiveConfig()
        self.runtime = runtime
        self.telemetry = telemetry
        self.sentinels = plan_lib.compile_sentinels(spec)

        # the CONFIGURED rung: whatever params were live at install time
        self._base = params if params is not None else MonitorParams.all_on(
            spec)
        self._base_scope = np.asarray(self._base.scope_mask, np.float32)
        self._base_slot = np.asarray(self._base.slot_mask, np.float32)
        self._base_period = np.asarray(self._base.period, np.int32)
        self._params = self._base
        self._base_cadence = max(1, telemetry.cadence)

        n = spec.n_scopes
        self._level = np.full((n,), CONFIGURED, np.int32)
        # quiet/cooldown ride step stamps, not drain counts (megastep-safe)
        self._quiet = np.zeros((n,), np.int64)
        self._cooldown_until_step = np.zeros((n,), np.int64)
        self._baselines: dict[int, _Baseline] = {}
        self._step_time = _Baseline()
        self._drains = 0
        self._last_stamp = 0
        self._prev_wall: float | None = None
        self._prev_step: int | None = None
        self._prev_drain_s = float(getattr(telemetry, "drain_seconds", 0.0))
        # the per-drain measurement window (see module docstring): deltas
        # accumulate here; the step-time/budget detectors tick on close
        self._win_id: int | None = None
        self._acc_wall = 0.0
        self._acc_steps = 0
        self._acc_drain_s = 0.0
        self._overhead_frac = 0.0

        self._lock = threading.Lock()
        self._installed = False
        self.transitions: list[Transition] = []
        self.events: list[str] = []
        self.stats = {
            "drains": 0, "escalations": 0, "deescalations": 0,
            "plan_swaps": 0, "cadence_changes": 0, "suppressed": 0,
            "step_time_wakes": 0, "fleet_hints": 0, "fleet_hints_ignored": 0,
        }

    # -- wiring -----------------------------------------------------------
    def install(self) -> "AdaptiveController":
        """Register on the telemetry plane (idempotent)."""
        if not self._installed:
            self._installed = True
            self.telemetry.add_sink(telemetry_lib.CallbackSink(self.on_snapshot))
        return self

    @property
    def params(self) -> MonitorParams:
        """The live MonitorParams — what ``Monitor.sync(controller=...)``
        picks up each step."""
        return self._params

    @property
    def tparams(self) -> telemetry_lib.TelemetryParams:
        return self.telemetry.params

    @property
    def levels(self) -> dict[str, str]:
        return {
            s: LEVEL_NAMES[int(lv)]
            for s, lv in zip(self.spec.scopes, self._level)
        }

    @property
    def overhead_frac(self) -> float:
        """EWMA of measured monitoring overhead as a fraction of wall time."""
        return self._overhead_frac

    def escalate(self, scope: str, reason: str = "manual") -> None:
        """Force a scope to WIDE (same path the detectors take)."""
        with self._lock:
            self._escalate(self.spec.scope_index(scope), reason,
                           step=-1, tripwire=True)

    def apply_fleet_hint(self, scope: str | None, *,
                         reason: str = "fleet-hint",
                         tripwire: bool = False) -> bool:
        """Apply a fleet-head escalation hint (FleetAgent downlink path).

        Another host saw an anomaly the head judged fleet-relevant; this
        process escalates in sympathy so the anomaly's next occurrence is
        observed WIDE everywhere.  ``scope=None`` (a global hint) wakes
        sentinel scopes to CONFIGURED — the same move as the step-time
        wake.  A named scope takes the detectors' ``_escalate`` path;
        tripwire hints pierce cooldown exactly like local tripwires.
        Gated by ``AdaptiveConfig.accept_fleet_hints``; returns whether the
        hint was applied.  Runs on the agent's reader thread — host work
        only, same rule as ``on_snapshot``.
        """
        if not self.cfg.accept_fleet_hints:
            with self._lock:
                self.stats["fleet_hints_ignored"] += 1
            return False
        with self._lock:
            step = self._last_stamp
            if scope is None:
                self.stats["fleet_hints"] += 1
                for idx in range(self.spec.n_scopes):
                    if self._level[idx] == SENTINEL and \
                            (tripwire or
                             step >= self._cooldown_until_step[idx]):
                        self._set_level(idx, CONFIGURED, reason, step)
                return True
            try:
                idx = self.spec.scope_index(scope)
            except (KeyError, ValueError):
                # the hint names a scope this process doesn't monitor (a
                # heterogeneous fleet) — nothing to escalate here
                self.stats["fleet_hints_ignored"] += 1
                return False
            self.stats["fleet_hints"] += 1
            self._escalate(idx, reason, step=step, tripwire=tripwire)
            return True

    # -- resolved ladder knobs (legacy *_drains names are the defaults) ---
    @property
    def _quiet_steps(self) -> int:
        cfg = self.cfg
        return cfg.quiet_steps if cfg.quiet_steps is not None \
            else cfg.quiet_drains

    @property
    def _cooldown_steps(self) -> int:
        cfg = self.cfg
        return cfg.cooldown_steps if cfg.cooldown_steps is not None \
            else cfg.cooldown_drains

    # -- the drain-thread callback ----------------------------------------
    def on_snapshot(self, snap: telemetry_lib.TelemetrySnapshot) -> None:
        """One controller tick.  Runs on the drain thread; host work only."""
        now = time.perf_counter()
        with self._lock:
            self._drains += 1
            self.stats["drains"] = self._drains
            step = int(snap.step)
            # the step span this snapshot's delta covers — the stamp
            # distance to the previously drained snapshot (>= 1: a K-step
            # megastep at cadence K spans K steps per snapshot)
            span = max(1, step - self._last_stamp)
            anomalies = self._detect(snap)
            for idx, (reason, trip) in anomalies.items():
                self._escalate(idx, reason, step=step, tripwire=trip)
            self._decay(anomalies, step, span)
            self._interval_tick(snap, now)
            self._last_stamp = max(self._last_stamp, step)

    # -- detectors --------------------------------------------------------
    def _lane_value(self, delta, lane: int, scope_idx: int, slot_idx: int):
        vals = np.asarray(delta.values)
        smps = np.asarray(delta.samples)
        if vals.ndim == 1:       # compact dense layout
            return float(vals[lane]), int(smps[lane])
        return float(vals[scope_idx, slot_idx]), int(smps[scope_idx,
                                                         slot_idx])

    def _detect(self, snap) -> dict[int, tuple[str, bool]]:
        """Per-scope anomaly verdicts over the snapshot's counter DELTA.

        Reads raw detector lanes straight off the drained CompactDelta
        (no report construction): O(#detector lanes) host arithmetic.
        Returns {scope_index: (reason, is_tripwire)}.
        """
        out: dict[int, tuple[str, bool]] = {}
        delta = snap.delta
        cfg = self.cfg
        for sset in self.sentinels:
            if self._level[sset.scope_index] == SENTINEL:
                continue          # masked off — lanes carry nothing
            for lane in sset.lanes:
                v, s = self._lane_value(delta, lane.lane, sset.scope_index,
                                        lane.slot_index)
                if lane.detector == plan_lib.DETECT_TRIPWIRE:
                    if v > 0:
                        out[sset.scope_index] = (
                            f"{lane.slot_id} +{v:g}", True)
                        break
                    continue
                if s <= 0:
                    continue      # slot not sampled this interval
                x = v / s
                bl = self._baselines.setdefault(lane.key, _Baseline())
                if lane.detector == plan_lib.DETECT_SPIKE:
                    hit = bl.outlier(x, cfg.spike_sigma, cfg.spike_floor,
                                     cfg.warmup_drains)
                else:             # DETECT_COLLAPSE
                    hit = bl.low_outlier(x, cfg.collapse_sigma,
                                         cfg.collapse_floor,
                                         cfg.warmup_drains)
                if hit:
                    out[sset.scope_index] = (
                        f"{lane.slot_id} {x:.4g} vs baseline "
                        f"{bl.mean:.4g}±{bl.dev:.4g}", False)
                    break
                bl.update(x, cfg.ewma_alpha)   # only clean values feed it
        return out

    # -- per-drain measurement window -------------------------------------
    def _interval_tick(self, snap, now: float) -> None:
        """Accumulate this snapshot's wall/step/drain-seconds deltas into
        the current measurement window; close the window when the plane's
        ``drain_count`` moves on.

        Snapshots drained in one batch (a K-step megastep flushes several
        cadence appends at once) share a ``drain_count`` and arrive
        back-to-back — their per-snapshot wall deltas are ~0 and would
        poison the per-step baselines.  Summed over a whole window the
        deltas cover the full drain interval: total wall over total steps
        is the true per-step time, total drain seconds over total wall is
        the true overhead fraction, whatever steps-per-commit is.
        """
        step = int(snap.step)
        drain_s_total = float(getattr(self.telemetry, "drain_seconds", 0.0))
        win = getattr(self.telemetry, "drain_count", None)
        if self._prev_wall is None:
            self._prev_wall = now
            self._prev_step = step
            self._prev_drain_s = drain_s_total
            self._win_id = win
            return
        if win != self._win_id and self._acc_steps > 0 \
                and self._acc_wall > 0:
            self._step_time_tick(self._acc_wall / self._acc_steps, step)
            self._budget_tick(self._acc_drain_s, self._acc_wall)
            self._acc_wall = 0.0
            self._acc_steps = 0
            self._acc_drain_s = 0.0
        self._win_id = win
        self._acc_wall += now - self._prev_wall
        self._acc_steps += max(0, step - self._prev_step)
        self._acc_drain_s += max(0.0, drain_s_total - self._prev_drain_s)
        self._prev_wall = now
        self._prev_step = step
        self._prev_drain_s = drain_s_total

    def _step_time_tick(self, per_step: float, step: int) -> None:
        """Global step-time outlier detector — the wake path for sentinel
        scopes (which are blind to tensor anomalies by construction)."""
        cfg = self.cfg
        if self._step_time.outlier(per_step, cfg.step_time_sigma,
                                   cfg.step_time_floor_s, cfg.warmup_drains):
            self.stats["step_time_wakes"] += 1
            reason = (f"step time {per_step * 1e3:.1f}ms vs baseline "
                      f"{self._step_time.mean * 1e3:.1f}ms")
            woke = False
            for idx in range(self.spec.n_scopes):
                if self._level[idx] == SENTINEL and \
                        step >= self._cooldown_until_step[idx]:
                    self._set_level(idx, CONFIGURED, reason, step)
                    woke = True
            if not woke:
                self.events.append(
                    f"[drain {self._drains}] step-time outlier ({reason}), "
                    "no sentinel scopes to wake")
        else:
            self._step_time.update(per_step, cfg.ewma_alpha)

    # -- transitions ------------------------------------------------------
    def _escalate(self, idx: int, reason: str, step: int,
                  tripwire: bool) -> None:
        self._quiet[idx] = 0
        if self._level[idx] >= WIDE:
            return
        if not tripwire and step < self._cooldown_until_step[idx]:
            self.stats["suppressed"] += 1
            return
        self._set_level(idx, WIDE, reason, step)

    def _decay(self, anomalies: dict, step: int, span: int) -> None:
        cfg = self.cfg
        floor = SENTINEL if cfg.sentinel_enabled else CONFIGURED
        for idx in range(self.spec.n_scopes):
            if idx in anomalies:
                continue
            if self._level[idx] <= floor:
                continue
            # a scope whose CONFIGURED rung never monitors can't produce
            # detector evidence; don't cycle it through the ladder
            if self._level[idx] == CONFIGURED and \
                    self._base_scope[idx] == 0.0:
                continue
            # quiet accrues the snapshot's STEP span, not one-per-drain:
            # a K-step megastep's snapshot attests K quiet steps
            self._quiet[idx] += span
            if self._quiet[idx] >= self._quiet_steps and \
                    step >= self._cooldown_until_step[idx]:
                self._set_level(idx, int(self._level[idx]) - 1,
                                f"quiet for {int(self._quiet[idx])} steps",
                                step)
                self._quiet[idx] = 0

    def _set_level(self, idx: int, level: int, reason: str,
                   step: int) -> None:
        prev = int(self._level[idx])
        if level == prev:
            return
        self._level[idx] = level
        # manual escalate() passes step=-1 — anchor on the last stamp then
        self._cooldown_until_step[idx] = \
            max(int(step), self._last_stamp) + self._cooldown_steps
        t = Transition(
            drain=self._drains, step=int(step),
            scope=self.spec.scopes[idx],
            frm=LEVEL_NAMES[prev], to=LEVEL_NAMES[level], reason=reason,
        )
        self.transitions.append(t)
        self.events.append(
            f"[drain {t.drain}] {t.scope}: {t.frm} -> {t.to} ({t.reason})")
        if level > prev:
            self.stats["escalations"] += 1
        else:
            self.stats["deescalations"] += 1
        self._rebuild_params()
        self._retune_cadence_for_levels()

    def _rebuild_params(self) -> None:
        """Materialize the ladder into fresh MonitorParams and swap them in
        (host-side; the step loop's next ``mon.sync`` picks them up)."""
        scope_mask = self._base_scope.copy()
        slot_mask = self._base_slot.copy()
        period = self._base_period.copy()
        for idx, lv in enumerate(self._level):
            if lv == WIDE:
                scope_mask[idx] = 1.0
                slot_mask[idx, :] = 1.0
                period[idx] = max(1, self.cfg.escalated_period)
            elif lv == SENTINEL:
                scope_mask[idx] = 0.0
        self._params = MonitorParams(
            scope_mask=jnp.asarray(scope_mask),
            slot_mask=jnp.asarray(slot_mask),
            period=jnp.asarray(period),
        )
        self.stats["plan_swaps"] += 1
        if self.runtime is not None:
            self.runtime.set_params(self._params)

    # -- budget loop ------------------------------------------------------
    def _cadence_floor(self) -> int:
        if np.any(self._level == WIDE):
            return max(1, self.cfg.escalated_cadence)
        return self._base_cadence

    def _budget_tick(self, drain_s: float, wall: float) -> None:
        """Proportional cadence retune holding measured monitoring overhead
        within ``overhead_budget`` of wall time.

        Ticks once per closed measurement window (``_interval_tick``):
        overhead = drain work seconds accumulated over the window
        (``TelemetryPlane.drain_seconds`` deltas: snapshots built and sink
        emits, not the wait for the ring's producing step), over the
        window's wall time.

        A budget of 1.0 (100% of wall time) or more means "no budget":
        the loop is disabled outright rather than left one measurement
        blip away from firing — synchronous flush-per-step harnesses on
        trivial workloads measure drain fractions that legitimately graze
        (and, with tick/drain interval skew, transiently exceed) 1.0.
        """
        if self.cfg.overhead_budget >= 1.0 or wall <= 0:
            return
        frac = drain_s / wall
        a = self.cfg.ewma_alpha
        self._overhead_frac += a * (frac - self._overhead_frac)

        cadence = self.telemetry.cadence
        floor = self._cadence_floor()
        target = cadence
        if self._overhead_frac > self.cfg.overhead_budget:
            # proportional: scale cadence by the overshoot, clipped to 2x
            ratio = min(2.0, self._overhead_frac / self.cfg.overhead_budget)
            target = min(self.cfg.max_cadence,
                         max(cadence + 1, int(round(cadence * ratio))))
        elif self._overhead_frac < 0.5 * self.cfg.overhead_budget and \
                cadence > floor:
            # decay back toward the floor (halving, never below it)
            target = max(floor, cadence // 2)
        elif cadence < floor:
            pass  # an escalation lowered it on purpose; leave it
        if target != cadence:
            self.telemetry.set_cadence(target)
            self.stats["cadence_changes"] += 1
            self.events.append(
                f"[drain {self._drains}] cadence {cadence} -> {target} "
                f"(overhead {self._overhead_frac:.1%} vs budget "
                f"{self.cfg.overhead_budget:.0%})")

    def _retune_cadence_for_levels(self) -> None:
        """Escalations want dense snapshots NOW, not at the budget loop's
        pace: any WIDE scope pins cadence at ``escalated_cadence``; once
        the last one steps down, the base cadence is restored (the budget
        loop may still push it higher afterwards)."""
        cur = self.telemetry.cadence
        if np.any(self._level == WIDE):
            tgt = min(cur, max(1, self.cfg.escalated_cadence))
        else:
            tgt = max(cur, self._base_cadence)
        if tgt != cur:
            self.telemetry.set_cadence(tgt)
            self.stats["cadence_changes"] += 1
            self.events.append(
                f"[drain {self._drains}] cadence {cur} -> {tgt} "
                "(escalation ladder)")

    def describe(self) -> str:
        lines = [f"adaptive controller: {self._drains} drains, "
                 f"overhead {self._overhead_frac:.2%}"]
        for scope, lv in self.levels.items():
            lines.append(f"  {scope}: {lv}")
        lines.extend(f"  {e}" for e in self.events[-8:])
        return "\n".join(lines)
