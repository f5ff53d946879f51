"""Training loop: data pipeline + jitted step + ScALPEL runtime + fault
tolerance (checkpoint/restart, straggler detection via the host_time
backend, NaN tripwire via in-graph counters).

The monitored hot path is fully asynchronous: the jitted step appends its
counters to a device-side SnapshotRing in-graph (telemetry plane), the loop
keeps a bounded window of in-flight steps instead of blocking every step,
and the adaptive hooks (NaN tripwire, straggler detection) run on drained
snapshots on the telemetry drain thread — never a synchronous
full-CounterState device→host transfer inside the step loop.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Any, Callable

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro import core as scalpel
from repro.checkpoint import CheckpointManager
from repro.core.backends.host_time import HostTimer
from repro.data import DataConfig, SyntheticLM, prefetch, shard_batch
from repro.dist.partition import sharding_ctx
from repro.models.registry import Arch
from repro.optim import OptConfig
from .step import TrainState, build_monitor_spec, make_train_megastep


@dataclasses.dataclass
class TrainLoopConfig:
    steps: int = 100
    log_every: int = 10
    ckpt_every: int = 50
    ckpt_dir: str | None = None
    ckpt_keep: int = 3
    microbatches: int = 1
    seed: int = 0
    straggler_sigma: float = 3.0
    monitor_config_path: str | None = None  # ScALPEL config file (reloadable)
    jsonl_path: str | None = None
    hook_every: int = 10       # telemetry ring-append cadence (steps)
    ring_depth: int = 8        # device-side snapshot ring depth
    max_in_flight: int = 2     # bounded dispatch window (megasteps)
    # steps per commit/dispatch: K>1 fuses K train steps into one compiled
    # megastep (lax.scan) — one host dispatch, one counter commit boundary,
    # ring snapshots still on true per-step stamps.  mon.sync (and so the
    # adaptive controller's decisions) applies at megastep boundaries.
    steps_per_commit: int = 1
    strict_plan_resume: bool = True  # raise (vs warn) on plan mismatch
    # closed adaptive loop: True (default AdaptiveConfig) or an
    # AdaptiveConfig — installs an AdaptiveController on the runtime; the
    # loop's existing mon.sync picks up its escalation/cadence decisions
    adaptive: Any = None
    graceful_shutdown: bool = False  # SIGTERM/atexit flush + final report


def fit(arch: Arch, opt_cfg: OptConfig, data_cfg: DataConfig,
        loop_cfg: TrainLoopConfig, mesh=None,
        on_report: Callable | None = None) -> dict[str, Any]:
    """Train; returns summary dict (final loss, step times, reports, and
    ``step``: the compiled megastep, whose ``lower`` gives its HLO)."""
    data = SyntheticLM(data_cfg)
    sample = data.batch_at(0)
    spec = build_monitor_spec(arch, sample)

    runtime = scalpel.ScalpelRuntime(
        spec,
        config_path=loop_cfg.monitor_config_path,
        jsonl_path=loop_cfg.jsonl_path,
        hook_every=loop_cfg.hook_every,
        ring_depth=loop_cfg.ring_depth,
        graceful_shutdown=loop_cfg.graceful_shutdown,
    )
    controller = None
    if loop_cfg.adaptive:
        controller = runtime.attach_controller(
            None if loop_cfg.adaptive is True else loop_cfg.adaptive
        )
    timer = HostTimer()
    events: list[str] = []

    # fault-tolerance hooks driven by drained telemetry snapshots (the hook
    # runs on the drain thread — it must not touch in-flight device buffers)
    nan_seen: set[str] = set()
    stragglers_seen: set[int] = set()

    def tripwire(rt, reports):
        for r in reports:
            for s in r.slots:
                if (s.slot_id.startswith("NAN_COUNT") and s.raw > 0
                        and r.scope not in nan_seen):
                    nan_seen.add(r.scope)
                    events.append(f"NaN detected in scope {r.scope}")
        # HostTimer.outliers re-reports the same indices every invocation;
        # dedupe so `events` records each straggler step once.
        bad = [i for i in timer.outliers("train_step",
                                         loop_cfg.straggler_sigma)
               if i not in stragglers_seen]
        if bad:
            stragglers_seen.update(bad)
            events.append(f"straggler steps (>{loop_cfg.straggler_sigma}σ): "
                          f"{bad[-3:]}")
        if on_report is not None:
            on_report(rt, reports)

    runtime.add_hook(tripwire)

    # the functional monitor: ONE pytree threads compact counters, the
    # telemetry ring, the step stamp and the runtime params through the step
    mon = scalpel.Monitor(spec, telemetry=runtime.telemetry)
    step_fn = make_train_megastep(arch, opt_cfg, spec,
                                  microbatches=loop_cfg.microbatches,
                                  monitor=mon)
    # leaf-wise jit boundary (the serve engine's): the read-only
    # MonitorParams/TelemetryParams enter the compiled megastep but are
    # never outputs — they stop round-tripping the step.  Donate the train
    # state only (argnum 1 past mstate: batches sit at 0) — the
    # MonitorState's ring buffers are read by the drain thread while later
    # steps run and must stay valid.
    jit_step = mon.jit_wrapped(step_fn, donate_argnums=(1,))

    mgr = (CheckpointManager(loop_cfg.ckpt_dir, keep=loop_cfg.ckpt_keep)
           if loop_cfg.ckpt_dir else None)

    # -- init or restore (crash recovery / elastic resume) -----------------
    tstate = TrainState.create(arch, opt_cfg,
                               jax.random.PRNGKey(loop_cfg.seed))
    mstate = mon.init()
    start_step = 0
    if mgr is not None and mgr.latest() is not None:
        latest = mgr.latest()
        # plan attestation FIRST, from the manifest alone: counters from
        # different compiled probe plans must not silently resume — and a
        # changed spec would otherwise surface as an opaque shape error
        # mid-restore rather than this diagnostic.
        attested = runtime.check_resume_metadata(
            mgr.metadata(latest), strict=loop_cfg.strict_plan_resume
        )
        if attested is None:
            # no fingerprint ⇒ the checkpoint predates the Monitor layout
            # ({'model', 'monitor'} tree) and CANNOT restore into it; fail
            # with a migration diagnostic, not a mid-restore KeyError.
            raise RuntimeError(
                f"checkpoint step_{latest} in {loop_cfg.ckpt_dir} predates "
                "the Monitor checkpoint layout (no plan fingerprint in "
                "meta.json); restart training or migrate the checkpoint"
            )
        saved_tree = {"model": tstate,
                      "monitor": mon.checkpoint_payload(mstate)}
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), saved_tree
        )
        saved, meta = mgr.restore(latest, abstract)
        tstate = saved["model"]
        mstate = mon.restore(mstate, saved["monitor"])
        start_step = int(meta["step"])
        events.append(f"restored from step {start_step}")

    losses: list[float] = []
    last_logged: dict[str, float] = {}
    max_in_flight = max(1, loop_cfg.max_in_flight)
    inflight: collections.deque = collections.deque()

    def retire(window: int) -> None:
        """Block on megasteps beyond the in-flight window, oldest first.
        ``out`` leaves are stacked per-step ``[K]`` arrays."""
        while len(inflight) > window:
            rstep, out = inflight.popleft()
            jax.block_until_ready(out["loss"])
            losses.extend(
                float(v) for v in np.asarray(out["loss"]).reshape(-1))
            last_logged.update(
                step=rstep, loss=losses[-1],
                gnorm=float(np.asarray(out["grad_norm"]).reshape(-1)[-1]),
                lr=float(np.asarray(out["lr"]).reshape(-1)[-1]),
            )

    K = max(1, loop_cfg.steps_per_commit)

    # Host spans (``jax.profiler.TraceAnnotation``, read back from a
    # profile on the device trace's clock): one per leaf phase of a
    # megastep, never one inside another on the same thread, each carrying
    # the megastep index as ``step``.
    def megabatches():
        """Host batches grouped into K-step leading-axis stacks (the final
        chunk may be ragged — a shorter stack traces once per distinct K).
        Runs on the prefetch thread (span ``scalpel.train.build``)."""
        for first in range(start_step, loop_cfg.steps, K):
            last = min(first + K, loop_cfg.steps) - 1
            with TraceAnnotation("scalpel.train.build", step=first // K):
                stacked = jax.tree.map(
                    lambda *xs: np.stack(xs),
                    *[data.batch_at(s) for s in range(first, last + 1)])
            yield first, last, stacked

    it = prefetch(megabatches(), 2)
    mega = start_step // K
    while True:
        with TraceAnnotation("scalpel.train.batch", step=mega):
            nxt = next(it, None)
        if nxt is None:
            break
        first_step, last_step, host_batches = nxt
        k_actual = last_step - first_step + 1
        # the per-step batch axis now sits under the stacked step axis
        with TraceAnnotation("scalpel.train.put", step=mega):
            batches = shard_batch(
                host_batches, mesh,
                axes={name: (None, "batch") + (None,) * (np.ndim(v) - 2)
                      for name, v in host_batches.items()},
            )
        t0 = time.perf_counter()
        # refresh the dynamic knobs riding in the state (mask/period/cadence
        # — reference swaps, never a re-trace); swaps take effect at the
        # NEXT megastep boundary, so the adaptive loop reacts with up to K
        # steps of latency
        with TraceAnnotation("scalpel.train.sync", step=mega):
            mstate = mon.sync(mstate, runtime=runtime)
        # the mesh is ambient while the step traces: activations follow the
        # logical-axis rules, and probes take XLA's partitionable reduction
        # instead of the Mosaic kernel
        with TraceAnnotation("scalpel.train.dispatch", step=mega), \
                sharding_ctx(mesh) if mesh is not None else \
                contextlib.nullcontext():
            (tstate, out), mstate = jit_step(mstate, batches, tstate)
        inflight.append((last_step, out))
        # bounded in-flight dispatch: only the megastep leaving the window
        # is synchronized, so device and host overlap up to max_in_flight
        # megasteps (amortized, the recorded time still equals the true
        # per-step time).
        with TraceAnnotation("scalpel.train.retire", step=mega):
            retire(max_in_flight - 1)
        with TraceAnnotation("scalpel.train.publish", step=mega):
            runtime.on_step(mstate.counters, ring=mstate.ring)
            # recorded PER STEP (megastep wall / K): straggler baselines
            # and step_stats survive a steps_per_commit swap
            timer.record("train_step",
                         (time.perf_counter() - t0) / k_actual)
            if loop_cfg.log_every and last_logged and any(
                    s % loop_cfg.log_every == 0
                    for s in range(first_step, last_step + 1)):
                # metrics belong to the most recently RETIRED megastep (the
                # window lags dispatch) — label them with its last step
                print(f"step {last_logged['step']:5d} "
                      f"loss {last_logged['loss']:.4f} "
                      f"gnorm {last_logged['gnorm']:.3f} "
                      f"lr {last_logged['lr']:.2e} "
                      f"dt {timer.stats('train_step').mean_s*1e3:.1f}ms "
                      f"(dispatched {last_step}, window {len(inflight)})")
        if mgr is not None and loop_cfg.ckpt_every and \
                (last_step + 1) // loop_cfg.ckpt_every \
                > first_step // loop_cfg.ckpt_every:
            # the cadence can only fire on megastep boundaries; save the
            # state that exists — after last_step+1 steps
            with TraceAnnotation("scalpel.train.retire", step=mega):
                retire(0)
            with TraceAnnotation("scalpel.train.ckpt", step=mega):
                mgr.save(last_step + 1,
                         {"model": tstate,
                          "monitor": mon.checkpoint_payload(mstate)},
                         extra=runtime.save_metadata())
        mega += 1
    with TraceAnnotation("scalpel.train.retire", step=mega):
        retire(0)
    if mgr is not None:
        mgr.save(loop_cfg.steps,
                 {"model": tstate,
                  "monitor": mon.checkpoint_payload(mstate)},
                 extra=runtime.save_metadata(), block=True)
        mgr.wait()

    report = runtime.report()  # flushes the ring through every sink
    if controller is not None:
        events.extend(controller.events)
    runtime.close()  # stop the drain thread; sinks are flushed + closed
    return {
        "controller": controller,
        "losses": losses,
        "final_loss": losses[-1] if losses else float("nan"),
        "step_stats": timer.stats("train_step"),
        "events": events,
        "report": report,
        "runtime": runtime,
        "state": tstate,
        "monitor": mstate,
        "step": jit_step,
        "spec": spec,
    }
