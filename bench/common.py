"""What the runners of every kind of cell share: the run's context, the
compile listener, the traced part of a run and the model configuration as
the program takes it."""
from __future__ import annotations

import dataclasses
import shutil
import threading
import time
from pathlib import Path
from typing import Any

import jax

from bench import trace as trace_lib

# XLA's compile of a lowered program (tracing and lowering nest inside)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


@dataclasses.dataclass
class RunContext:
    cell: dict
    config: dict            # configs/<config>.json
    traffic: dict           # traffic/<traffic>.json
    reference: Any          # configs/<config>.py, the plain reference
    monitor_cfg: Path | None
    seed: int
    seconds: float
    trace: bool
    devices: list
    t0: float               # perf_counter() at the start of the process
    work_dir: Path          # scratch inside the checkout (traces)
    compiles: "Compiles"


class Compiles:
    """Counts XLA compiles and compile-cache hits from JAX's monitoring
    events, for the whole process."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == COMPILE_EVENT:
            with self._lock:
                self.compiles += 1
                self.compile_s += secs

    def _event(self, event, **_):
        with self._lock:
            if event == CACHE_HIT:
                self.hits += 1
            elif event == CACHE_MISS:
                self.misses += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"compiles": self.compiles, "compile_s": self.compile_s,
                    "cache_hits": self.hits, "cache_misses": self.misses}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class TracedPart:
    """Profiles ``seconds`` of a running workload from a thread of its own,
    so that the caller (a drain callback, a timer) never blocks on the
    profiler.  The window is the span ``bench.traced_part``, opened
    ``settle`` seconds after the profile starts, once the device tracer
    runs.  Host tracing is on, Python function tracing off."""

    def __init__(self, work_dir: Path, settle: float = 0.5):
        self.dir = work_dir / "trace"
        shutil.rmtree(self.dir, ignore_errors=True)
        self.seconds = 0.0
        self.settle = float(settle)
        self._thread: threading.Thread | None = None
        self.error: BaseException | None = None
        self.times: dict[str, float] = {}   # perf_counter marks, for logs

    def start(self, seconds: float) -> None:
        """Profile the next ``seconds`` (after the settling time)."""
        if self._thread is not None:
            return
        self.seconds = float(seconds)
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        try:
            self.times["start_called"] = time.perf_counter()
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self.times["started"] = time.perf_counter()
            time.sleep(self.settle)
            with jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN):
                time.sleep(self.seconds)
            self.times["stop_called"] = time.perf_counter()
            jax.profiler.stop_trace()
            self.times["stopped"] = time.perf_counter()
        except Exception as e:  # noqa: BLE001 - re-raised by reduce()
            self.error = e

    def reduce(self, timeout: float = 300.0) -> dict:
        """Wait for the profile, read it, delete it."""
        if self._thread is None:
            raise RuntimeError("the traced part never started")
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise RuntimeError("the profiler did not stop in time")
        if self.error is not None:
            raise self.error
        t0 = time.perf_counter()
        tr = trace_lib.Trace.load(trace_lib.find_xplane(self.dir))
        shutil.rmtree(self.dir, ignore_errors=True)
        return {"trace": tr, "busy_s": tr.busy_s(),
                "window_s": tr.window_ns / 1e9, "breakdown": tr.breakdown(),
                "reduce_s": time.perf_counter() - t0}


def model_config(config: dict):
    """The program's ``ModelConfig`` holding the file's ``model`` sizes."""
    from repro.models.spec import (HybridConfig, ModelConfig, MoEConfig,
                                   SSMConfig)

    m = dict(config["model"])
    nested = {"ssm": SSMConfig, "moe": MoEConfig, "hybrid": HybridConfig}
    for key, cls in nested.items():
        if key in m:
            m[key] = cls(**m[key])
    return ModelConfig(**m)


def check(value: float, limit: float) -> dict:
    """One compared number beside its limit; a NaN never passes."""
    return {"value": float(value), "limit": float(limit)}
