"""Host spans of the train and serve loops and of the telemetry drain, read
back the way the benchmark reads them; the drain's wait counted apart from
its work; the device-side scopes of the monitoring ops; and the benchmark's
readers of the serving spans.

The spans are ``jax.profiler.TraceAnnotation``s: profiled here on the CPU
inside the harness's own window span ``bench.traced_part`` and loaded with
``bench.trace.Trace.load``, they must come back under their bare names, on
the window's clock, and no span of the program may hold another on the same
thread (the benchmark names an idle gap by the host event that overlaps it
most, and an enclosing span would always win)."""
from __future__ import annotations

import dataclasses
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import core as scalpel
from repro.configs import model_config
from repro.core import telemetry as T
from repro.core.context import EventSpec, MonitorSpec, ScopeContext
from repro.core.counters import CounterState
from repro.data import DataConfig
from repro.models.registry import Arch
from repro.optim import OptConfig
from repro.serve.engine import ContinuousEngine, ServeConfig
from repro.train.loop import TrainLoopConfig, fit

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run as bench_run  # noqa: E402
from bench import trace as trace_lib  # noqa: E402

TRAIN_SPANS = {"scalpel.train.batch", "scalpel.train.put",
               "scalpel.train.sync", "scalpel.train.dispatch",
               "scalpel.train.retire", "scalpel.train.publish",
               "scalpel.train.build", "scalpel.drain"}
SERVE_SPANS = {"scalpel.serve.sync", "scalpel.serve.admit",
               "scalpel.serve.dispatch", "scalpel.serve.advance",
               "scalpel.serve.publish", "scalpel.serve.attribute",
               "scalpel.tokens.wait", "scalpel.tokens", "scalpel.drain"}


@pytest.fixture(scope="module")
def tiny():
    return Arch(model_config("xlstm_125m", smoke=True))


def _profiled(tmp_path, work):
    """Run ``work()`` inside ``bench.traced_part`` under the profiler; the
    loaded ``Trace`` and the raw profile (for the spans' arguments)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN):
            work()
    finally:
        jax.profiler.stop_trace()
    path = trace_lib.find_xplane(tmp_path)
    return trace_lib.Trace.load(path), ProfileData.from_file(str(path))


def _program_spans(tr):
    """Per host line, the program's spans as (start, end, name)."""
    out = []
    for line in tr.host_lines:
        spans = sorted((s, e, n) for s, e, n in
                       zip(line.starts, line.ends, line.names)
                       if n.startswith("scalpel."))
        if spans:
            out.append(spans)
    return out


def _span_args(pd):
    """Name -> list of argument dicts of every program span."""
    out: dict[str, list[dict]] = {}
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("scalpel."):
                    out.setdefault(e.name, []).append(dict(e.stats))
    return out


def _check_spans(tr, pd, want):
    lines = _program_spans(tr)
    names = {n for spans in lines for _, _, n in spans}
    # bare names: the arguments ride as stats, never in the name
    assert want <= names, sorted(want - names)
    assert all("#" not in n and "=" not in n for n in names), names
    lo, hi = tr.start_ns, tr.start_ns + tr.window_ns
    for spans in lines:
        for (s0, e0, n0), (s1, e1, n1) in zip(spans, spans[1:]):
            assert s1 >= e0, f"{n1} starts inside {n0} on one thread"
        # the window's clock: every span lies inside bench.traced_part
        assert all(lo <= s <= e <= hi for s, e, _ in spans)
    args = _span_args(pd)
    for name in names:
        assert all("step" in a for a in args[name]), name
    return args


def test_fit_writes_every_train_span_bare_and_unnested(tiny, tmp_path):
    opt = OptConfig(lr=3e-3, warmup_steps=2, total_steps=200)
    data = DataConfig(vocab=512, seq_len=32, global_batch=4)
    loop = TrainLoopConfig(steps=4, steps_per_commit=2, hook_every=2,
                           log_every=0, ckpt_every=0)
    out = {}
    tr, pd = _profiled(tmp_path, lambda: out.update(
        fit(tiny, opt, data, loop)))
    args = _check_spans(tr, pd, TRAIN_SPANS)
    # megastep indices: both megasteps dispatched, on the main thread
    assert sorted(a["step"] for a in args["scalpel.train.dispatch"]) \
        == [0, 1]
    assert sorted(a["step"] for a in args["scalpel.train.build"]) == [0, 1]
    assert len(out["losses"]) == 4


def test_engine_run_writes_every_serve_span_bare_and_unnested(tiny,
                                                              tmp_path):
    params = tiny.init(jax.random.PRNGKey(0))
    eng = ContinuousEngine(tiny, params,
                           ServeConfig(cache_len=64, n_lanes=2,
                                       steps_per_commit=2))
    prompts = [jax.random.randint(jax.random.PRNGKey(i), (1, 5 + i), 0, 512)
               for i in range(3)]
    rids = [eng.submit(p, max_new=4) for p in prompts]
    res = {}

    def work():
        res.update(eng.run())
        eng.runtime.flush()

    tr, pd = _profiled(tmp_path, work)
    args = _check_spans(tr, pd, SERVE_SPANS)
    admits = args["scalpel.serve.admit"]
    assert sorted(a["rid"] for a in admits) == sorted(rids)
    assert sorted(a["width"] for a in admits) == sorted(
        eng.sched.width(p.shape[1]) for p in prompts)
    assert all(len(res[r].tokens) == 4 for r in rids)


def _spec():
    return MonitorSpec.of([ScopeContext.exhaustive(
        "f", [EventSpec("MEAN", "x")])])


def _bump(cs):
    return dataclasses.replace(cs, calls=cs.calls + 1)


def test_drain_counts_its_wait_for_the_ring_head_apart_from_its_work():
    """A ring whose head comes out of a step still running on the device
    (about 0.4 s of matmuls): the drain waits for it
    (``drain_wait_seconds``), then does a little work (``drain_seconds``,
    the adaptive budget's monitoring overhead).  The CPU runs a program
    with a host callback inline at dispatch, so a sleeping callback would
    never leave a drain anything to wait for; matmuls are dispatched
    asynchronously."""
    spec = _spec()
    # the background thread stays asleep: flush() drains on this thread
    plane = T.TelemetryPlane(spec, depth=4, cadence=1, interval_s=60.0)
    ring = T.SnapshotRing.zeros(spec, 4)
    counters = _bump(CounterState.zeros(spec))
    x = jnp.full((1024, 1024), 1e-3, jnp.float32)

    @jax.jit
    def produce(ring, counters, n):
        r = T.ring_append(ring, counters, T.TelemetryParams.of(1), 1)
        m = jax.lax.fori_loop(0, n, lambda i, m: m @ x, x)
        # the head waits for the matmuls and stays what it was
        return dataclasses.replace(
            r, head=r.head + jnp.isnan(m.sum()).astype(jnp.int32))

    def seconds(n):
        t0 = time.perf_counter()
        jax.block_until_ready(produce(ring, counters, n))
        return time.perf_counter() - t0

    seconds(1)  # compiled
    n = max(16, int(np.ceil(16 * 0.4 / seconds(16))))
    plane.publish(produce(ring, counters, n))
    snaps = plane.flush()
    assert [s.step for s in snaps] == [1]
    assert plane.drain_wait_seconds >= 0.15
    assert plane.drain_seconds < 0.05
    st = plane.stats()
    assert st["drain_wait_seconds"] >= 0.15 and st["drain_seconds"] < 0.05
    plane.close()


def test_report_footer_shows_the_drain_wait():
    spec = _spec()
    rt = scalpel.ScalpelRuntime(spec)
    rt.on_step(_bump(CounterState.zeros(spec)))
    footer = rt.report().splitlines()[-1]
    assert "drain_s=" in footer and "drain_wait_s=" in footer
    rt.close()


def _work(x):
    with scalpel.function("f"):
        scalpel.probe(x=x)
    return x * 2


@pytest.mark.parametrize("what", ["step", "lanes"])
def test_monitoring_ops_carry_their_device_scopes(what):
    """Probe evaluation, the counter commit and the ring append carry
    ``scalpel.probe`` / ``scalpel.commit`` / ``scalpel.ring_append`` in
    their op metadata, so a device trace can tell them from the model's."""
    spec = _spec()
    plane = T.TelemetryPlane(spec, cadence=1)
    mon = scalpel.Monitor(spec, telemetry=plane, counter_axes=())
    x = jnp.ones((4,), jnp.float32)
    if what == "step":
        lowered = jax.jit(mon.wrap(_work)).lower(mon.init(), x)
    else:
        lstate = mon.lane_init(2)

        def lanes(ls, xs):
            def one(x):
                with mon.open(ls.params) as col:
                    _work(x)
                return col.compact_delta()

            delta = jax.vmap(one)(xs)
            return mon.commit_lanes(ls, delta, jnp.ones((2,), jnp.int32))

        lowered = jax.jit(lanes).lower(lstate, jnp.stack([x, x]))
    text = lowered.as_text(debug_info=True)
    for name in ("scalpel.probe", "scalpel.commit", "scalpel.ring_append"):
        assert name in text, name
    plane.close()


def _reader(name):
    return bench_run.load_module(ROOT / "bench" / "metrics" / f"{name}.py")


def _hand_trace():
    """A traced window [1000, 2000) ns: the serving loop's thread and the
    drain thread with spans whose unions are known."""
    L = trace_lib.Line
    main = L("python3", *zip(*[
        (900, 1100, "scalpel.serve.sync"),      # 100 inside the window
        (1200, 1300, "scalpel.serve.dispatch"),  # 100
        (1200, 1800, "PjitFunction(megastep_core)"),  # not a program span
        (1300, 1500, "scalpel.serve.wait"),     # a wait: not work
        (1500, 1700, "scalpel.tokens.wait"),    # a wait: not work
        (1700, 1750, "scalpel.tokens"),         # 50
        (1950, 2100, "scalpel.serve.admit"),    # 50 inside the window
    ]))
    drain = L("scalpel-telemetry-drain", *zip(*[
        (1400, 1500, "scalpel.drain"),
        (1450, 1600, "scalpel.drain"),          # union with the above: 200
        (2500, 2600, "scalpel.drain"),          # after the window
        (1000, 2000, "np.asarray(jax.Array)"),  # the wait has no span
    ]))
    return trace_lib.Trace(1000.0, [], [main, drain], start_ns=1000.0)


@pytest.mark.parametrize("metric,want", [("host_busy_frac.serve", 30.0),
                                         ("drain_work_frac.serve", 20.0)])
def test_span_metric_readers(metric, want):
    read = _reader(metric).read
    r = {"kind": "serve", "trace": _hand_trace()}
    assert read(r) == pytest.approx(want)
    # a program that writes no such span: no reading
    empty = trace_lib.Trace(1000.0, [], [trace_lib.Line(
        "python3", [1000.0], [2000.0], ["np.asarray(jax.Array)"])], 1000.0)
    assert read({**r, "trace": empty}) is None
    assert read({**r, "kind": "train"}) is None
    assert read({"kind": "serve"}) is None
