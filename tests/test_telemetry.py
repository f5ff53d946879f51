"""Telemetry plane: device-side snapshot ring, background drain, sinks,
and runtime reconfiguration through the plane.

Covers the async-monitoring contract: ring appends are cond-guarded device
work at a *dynamic* cadence (changing it never re-traces — asserted via
jax.jit cache stats), drained snapshots are delta-decoded and value-equal
to synchronous snapshots, and the drain thread flushes everything on
shutdown.
"""
import json
import os
import signal
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core as scalpel
from repro.core import report as report_lib
from repro.core import telemetry as T
from repro.core.context import EventSpec, MonitorSpec, ScopeContext
from repro.core.counters import CounterState, MonitorParams


def _spec():
    return MonitorSpec.of([
        ScopeContext.exhaustive("f", [EventSpec("MEAN", "x"),
                                      EventSpec("NUMEL", "x")]),
        ScopeContext.exhaustive("g", [EventSpec("MEAN", "x")]),
    ])


def _bump(cs: CounterState, v: float = 1.0) -> CounterState:
    return CounterState(calls=cs.calls + 1, values=cs.values + v,
                        samples=cs.samples + 1)


def _run_steps(spec, params, state, values):
    for v in values:
        with scalpel.Monitor(spec, params=params, counter_axes=()).open(
                params, calls_base=state.calls) as col:
            with scalpel.function("f"):
                scalpel.probe(x=jnp.full((4,), v))
            with scalpel.function("g"):
                scalpel.probe(x=jnp.full((2,), v))
        state = state.add(col.delta)
    return state


# ---------------------------------------------------------------------------
# device side: ring semantics
# ---------------------------------------------------------------------------

def test_ring_append_cadence_and_stamp():
    spec = _spec()
    ring = T.SnapshotRing.zeros(spec, depth=4)
    cs = CounterState.zeros(spec)
    for step in range(1, 7):
        cs = _bump(cs)
        ring = T.ring_append(ring, cs, T.TelemetryParams.of(2), step)
    assert int(ring.head) == 3
    written = sorted(int(s) for s in np.asarray(ring.steps) if s >= 0)
    assert written == [2, 4, 6]
    # slot for step 6 holds the cumulative counters at step 6
    slot = (int(ring.head) - 1) % ring.depth
    assert int(ring.calls[slot][0]) == 6


def test_ring_append_wraps_and_zero_cadence_disables():
    spec = _spec()
    ring = T.SnapshotRing.zeros(spec, depth=2)
    cs = CounterState.zeros(spec)
    for step in range(1, 6):
        cs = _bump(cs)
        ring = T.ring_append(ring, cs, T.TelemetryParams.of(1), step)
    assert int(ring.head) == 5          # monotonic, beyond depth
    assert sorted(np.asarray(ring.steps).tolist()) == [4, 5]  # last two
    off = T.ring_append(ring, cs, T.TelemetryParams.of(0), 6)
    assert int(off.head) == 5           # cadence 0: never writes


def test_ring_append_cadence_is_dynamic_no_retrace():
    """Cadence changes ride a dynamic input — the jitted append never
    re-traces (asserted with jax.jit cache stats AND a trace counter)."""
    spec = _spec()
    traces = []

    def append(ring, cs, tp, step):
        traces.append(1)
        return T.ring_append(ring, cs, tp, step)

    f = jax.jit(append)
    ring = T.SnapshotRing.zeros(spec, depth=4)
    cs = _bump(CounterState.zeros(spec))
    for step, cadence in enumerate([1, 1, 2, 5, 0, 3], start=1):
        ring = f(ring, cs, T.TelemetryParams.of(cadence),
                 jnp.asarray(step, jnp.int32))
    assert len(traces) == 1
    assert f._cache_size() == 1


# ---------------------------------------------------------------------------
# host side: drain, delta decode, sinks
# ---------------------------------------------------------------------------

def test_plane_drains_and_delta_decodes():
    spec = _spec()
    plane = T.TelemetryPlane(spec, depth=8, cadence=1)
    got = []
    plane.add_sink(T.CallbackSink(got.append))
    cs = CounterState.zeros(spec)
    for step in range(1, 5):
        cs = _bump(cs, v=2.0)
        plane.append(cs, step=step)
    plane.flush()
    assert [s.step for s in got] == [1, 2, 3, 4]
    # cumulative state at step k has calls == k; delta is one step's worth
    for k, s in enumerate(got, start=1):
        assert int(s.state.calls[0]) == k
        assert int(s.delta.calls[0]) == 1
        assert float(s.delta.values[0, 0]) == pytest.approx(2.0)
    plane.close()


def test_plane_counts_dropped_snapshots_on_overrun():
    spec = _spec()
    plane = T.TelemetryPlane(spec, depth=2, cadence=1)
    seen = []
    plane.add_sink(T.CallbackSink(lambda s: seen.append(s.step)))
    cs = CounterState.zeros(spec)
    ring = plane.make_ring()
    for step in range(1, 6):   # 5 appends into a depth-2 ring, no drain
        cs = _bump(cs)
        ring = T.ring_append(ring, cs, plane.params, step)
    plane.publish(ring)
    plane.flush()
    assert seen == [4, 5]                  # only the surviving slots
    assert plane.dropped_snapshots == 3    # the overwritten ones are counted
    plane.close()


def test_make_ring_starts_new_epoch():
    """A fresh ring restarts head at 0 — make_ring() must reset the drain
    cursor and delta base, or the plane silently stops draining (the drain
    loop also self-heals if a restarted ring is published directly)."""
    spec = _spec()
    plane = T.TelemetryPlane(spec, depth=8, cadence=1, interval_s=60.0)
    got = []
    plane.add_sink(T.CallbackSink(
        lambda s: got.append((s.step, int(s.state.calls[0]),
                              int(s.delta.calls[0])))))
    for _ in range(2):
        ring = plane.make_ring()
        cs = CounterState.zeros(spec)
        for step in range(1, 4):
            cs = _bump(cs)
            ring = T.ring_append(ring, cs, plane.params, step)
        plane.publish(ring)
        plane.flush()
    # the second epoch drains again, with its delta base reset (first
    # snapshot's delta == its cumulative state, not state - old epoch)
    assert got == [(1, 1, 1), (2, 2, 1), (3, 3, 1)] * 2
    # self-heal: a shorter restarted ring published without make_ring()
    ring = T.SnapshotRing.zeros(spec, plane.depth)
    cs = _bump(CounterState.zeros(spec))
    ring = T.ring_append(ring, cs, plane.params, 1)
    plane.publish(ring)
    plane.flush()
    assert got[-1] == (1, 1, 1)
    plane.close()


def test_drain_copies_one_slot_when_caught_up():
    """Incremental drain: a drain that kept up (one new slot since the
    cursor) copies the ring's O(1) ``last`` mirror — one slot's worth of
    transfer regardless of ring depth — and an idle flush copies none.
    Only a multi-slot catch-up pays a stacked-ring copy."""
    spec = _spec()
    plane = T.TelemetryPlane(spec, depth=16, cadence=1, interval_s=60.0)
    got = []
    plane.add_sink(T.CallbackSink(lambda s: got.append(s.step)))
    cs = CounterState.zeros(spec)
    ring = plane.make_ring()
    for step in (1, 2, 3):                 # keeping up: one append per drain
        cs = _bump(cs)
        ring = T.ring_append(ring, cs, plane.params, step)
        plane.publish(ring)
        plane.flush()
    assert got == [1, 2, 3]
    assert plane.slots_copied == 3          # one mirror copy each, not 3*16
    plane.flush()                           # idle: head probe only
    assert plane.slots_copied == 3
    # falling behind: 3 new slots → one stacked-ring copy (depth slots)
    for step in range(4, 7):
        cs = _bump(cs)
        ring = T.ring_append(ring, cs, plane.params, step)
    plane.publish(ring)
    plane.flush()
    assert got == [1, 2, 3, 4, 5, 6]
    assert plane.slots_copied == 3 + 16
    # overrun still decodes the surviving slots and counts the drops
    for step in range(7, 27):
        cs = _bump(cs)
        ring = T.ring_append(ring, cs, plane.params, step)
    plane.publish(ring)
    plane.flush()
    assert got[-1] == 26 and plane.slots_copied == 3 + 16 + 16
    assert plane.dropped_snapshots == 4
    # drained deltas stayed exact across both copy paths
    assert int(plane.last_state.calls[0]) == 26
    plane.close()


def test_token_drain_is_pure_transfer_with_exact_accounting(monkeypatch):
    """The token-egress drain NEVER dispatches device computation — only
    the scalar head probe plus buffer copies (the ROADMAP drain
    invariant, extended to the serve path).  Attested by swapping the
    module's ``jnp`` for a guard that raises on ANY op, and by the same
    slots-copied accounting the counter drain uses."""
    plane = T.TelemetryPlane(_spec(), depth=16, cadence=1, interval_s=60.0)
    ring = plane.make_token_ring(3, depth=4)
    append = jax.jit(T.token_ring_append)
    toks = jnp.asarray([5, 6, 7], jnp.int32)
    live = jnp.asarray([1, 0, 1], jnp.int32)
    ring = append(ring, toks, live, jnp.asarray(1, jnp.int32))
    ring = append(ring, toks + 1, live, jnp.asarray(2, jnp.int32))
    plane.publish_tokens(ring)

    class _NoDeviceOps:
        def __getattr__(self, name):
            raise AssertionError(
                f"token drain dispatched a device op: jnp.{name}")

    monkeypatch.setattr(T, "jnp", _NoDeviceOps())
    out = plane.drain_tokens()
    assert [(seq, step) for seq, step, _, _ in out] == [(0, 1), (1, 2)]
    np.testing.assert_array_equal(out[0][2], [5, 6, 7])
    np.testing.assert_array_equal(out[0][3], [1, 0, 1])
    np.testing.assert_array_equal(out[1][2], [6, 7, 8])
    assert plane.tok_slots_copied == 4      # one stacked copy, depth slots
    assert plane.token_drains == 1
    # idle drain: scalar head probe only — no slot copy
    assert plane.drain_tokens() == []
    assert plane.tok_slots_copied == 4 and plane.token_drains == 2
    assert plane.dropped_tokens == 0
    plane.close()


def test_token_ring_overrun_counts_losses_and_epoch_resets():
    """Tokens are outputs, not samples: slots lost to an overrun are
    counted loudly (the engine raises on any).  A fresh lineage via
    make_token_ring restarts the cursor at head 0."""
    plane = T.TelemetryPlane(_spec(), depth=16, cadence=1, interval_s=60.0)
    ring = plane.make_token_ring(2, depth=4)
    live = jnp.asarray([1, 1], jnp.int32)
    for step in range(1, 7):               # 6 appends into a depth-4 ring
        ring = T.token_ring_append(
            ring, jnp.asarray([step, -step], jnp.int32), live,
            jnp.asarray(step, jnp.int32))
    plane.publish_tokens(ring)
    out = plane.drain_tokens()
    assert plane.dropped_tokens == 2       # seqs 0-1 overwritten
    assert [seq for seq, _, _, _ in out] == [2, 3, 4, 5]
    np.testing.assert_array_equal(out[-1][2], [6, -6])
    # new lineage: cursor self-resets to the fresh ring's head
    ring2 = plane.make_token_ring(2, depth=4)
    ring2 = T.token_ring_append(
        ring2, jnp.asarray([9, 9], jnp.int32), live,
        jnp.asarray(1, jnp.int32))
    plane.publish_tokens(ring2)
    out2 = plane.drain_tokens()
    assert [seq for seq, _, _, _ in out2] == [0]
    np.testing.assert_array_equal(out2[0][2], [9, 9])
    assert plane.dropped_tokens == 2       # unchanged by the new epoch
    plane.close()


def test_background_drain_thread_runs_without_flush():
    spec = _spec()
    plane = T.TelemetryPlane(spec, depth=8, cadence=1, interval_s=0.005)
    done = threading.Event()
    plane.add_sink(T.CallbackSink(lambda s: done.set()))
    plane.append(_bump(CounterState.zeros(spec)), step=1)
    assert done.wait(timeout=5.0), "drain thread never delivered snapshot"
    plane.close()


@pytest.mark.parametrize("surface", ["flush", "close"])
def test_background_drain_failure_reraised_on_caller(monkeypatch, surface):
    """A drain that dies on the background thread is not dropped: the next
    flush()/close() re-raises it on the caller's thread, exactly once."""
    spec = _spec()
    plane = T.TelemetryPlane(spec, depth=8, cadence=1, interval_s=0.005)
    cs = _bump(CounterState.zeros(spec))
    plane.append(cs, step=1)  # the first append drains on this thread

    def broken():
        raise ValueError("transfer failed")

    monkeypatch.setattr(plane, "_drain_once_inner", broken)
    plane.append(cs, step=2)
    deadline = time.time() + 5.0
    while plane._drain_error is None and time.time() < deadline:
        time.sleep(0.005)
    with pytest.raises(RuntimeError, match="drain thread failed") as ei:
        getattr(plane, surface)()
    assert isinstance(ei.value.__cause__, ValueError)
    monkeypatch.undo()
    plane.close()  # reported once: a later close is clean


EXIT_SCRIPT = """
import sys, time
from repro.core.context import EventSpec, MonitorSpec, ScopeContext
from repro.core.counters import CounterState
from repro.core.runtime import ScalpelRuntime

route, broken = sys.argv[1], sys.argv[2] == "broken"
spec = MonitorSpec.of([ScopeContext.exhaustive("f", [EventSpec("MEAN", "x")])])
rt = ScalpelRuntime(spec, drain_interval_s=0.005,
                    report_at_exit=route == "exit_report",
                    graceful_shutdown=route == "shutdown")
plane = rt.telemetry
cs = CounterState.zeros(spec)
rt.on_step(cs)  # the first append drains on this thread
if broken:
    def fail():
        raise ValueError("transfer failed")
    plane._drain_once_inner = fail
    rt.on_step(cs)
    deadline = time.time() + 5.0
    while plane._drain_error is None and time.time() < deadline:
        time.sleep(0.005)
    assert plane._drain_error is not None
print("main done", flush=True)
"""


@pytest.mark.parametrize("route,broken", [
    ("plane", True), ("shutdown", True), ("exit_report", True),
    ("plane", False),
], ids=["plane-broken", "shutdown-broken", "exit-report-broken",
        "plane-clean"])
def test_drain_failure_at_exit_fails_the_process(route, broken):
    """A drain error nobody collected before exit is printed at exit and the
    process exits 1, whichever exit hook meets it first (the plane's own
    close, the runtime's graceful shutdown, its exit report)."""
    import subprocess
    import sys

    env = dict(os.environ)
    # a plain host process: never contends for an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run(
        [sys.executable, "-c", EXIT_SCRIPT, route,
         "broken" if broken else "clean"],
        env=env, capture_output=True, text=True, timeout=120)
    assert "main done" in out.stdout, out.stderr[-3000:]
    if broken:
        assert out.returncode == 1, out.stderr[-3000:]
        assert "transfer failed" in out.stderr
    else:
        assert out.returncode == 0, out.stderr[-3000:]


def test_jsonl_sink_buffers_and_flushes(tmp_path):
    spec = _spec()
    path = str(tmp_path / "t.jsonl")
    plane = T.TelemetryPlane(spec, depth=8, cadence=1)
    plane.add_sink(T.JsonlSink(path, buffer_lines=10_000))
    state = _run_steps(spec, MonitorParams.all_on(spec),
                       CounterState.zeros(spec), [1.0, 2.0])
    plane.append(state, step=1)
    plane.flush()  # buffered writer must hit the disk on flush
    lines = [json.loads(x) for x in open(path).read().splitlines()]
    assert {ln["scope"] for ln in lines} == {"f", "g"}
    assert all(ln["step"] == 1 for ln in lines)
    plane.close()


def test_plane_close_flushes_pending(tmp_path):
    """Shutdown semantics: close() drains un-drained slots + closes sinks."""
    spec = _spec()
    path = str(tmp_path / "t.jsonl")
    plane = T.TelemetryPlane(spec, depth=8, cadence=1, interval_s=60.0)
    plane.add_sink(T.JsonlSink(path, buffer_lines=10_000))
    cs = _bump(CounterState.zeros(spec))
    plane.append(cs, step=1)
    plane.close()   # no explicit flush: close must deliver + write
    lines = open(path).read().splitlines()
    assert lines and json.loads(lines[0])["step"] == 1
    # close is idempotent and further flushes are harmless
    plane.close()
    assert plane.flush() == []


def test_text_sink_prints_reports(capsys):
    spec = _spec()
    plane = T.TelemetryPlane(spec, depth=4, cadence=1)
    plane.add_sink(T.TextSink(title="probe"))
    state = _run_steps(spec, MonitorParams.all_on(spec),
                       CounterState.zeros(spec), [3.0])
    plane.append(state, step=9)
    plane.flush()
    out = capsys.readouterr().out
    assert "probe @ step 9" in out and "MEAN:x" in out
    plane.close()


# ---------------------------------------------------------------------------
# runtime reconfiguration through the plane
# ---------------------------------------------------------------------------

CONFIG_A = """
BINARY=test
NO_FUNCTIONS=1
[FUNCTION]
FUNC_NAME=f
NO_EVENTS=0
[/FUNCTION]
"""

CONFIG_B = """
BINARY=test
NO_FUNCTIONS=1
[FUNCTION]
FUNC_NAME=g
NO_EVENTS=0
[/FUNCTION]
"""


def test_runtime_reload_and_cadence_swap_never_retrace(tmp_path):
    """Config reload() AND telemetry cadence changes are dynamic-input
    swaps: one trace, one jit cache entry, across both reconfigurations."""
    spec = _spec()
    cfgp = tmp_path / "mon.cfg"
    cfgp.write_text(CONFIG_A)
    rt = scalpel.ScalpelRuntime(spec, config_path=str(cfgp), hook_every=1)
    traces = []

    def step(state, mparams, tparams, ring, step_no):
        traces.append(1)
        with scalpel.Monitor(spec, params=mparams, counter_axes=()).open(
                mparams, calls_base=state.calls) as col:
            with scalpel.function("f"):
                scalpel.probe(x=jnp.ones(3))
            with scalpel.function("g"):
                scalpel.probe(x=jnp.ones(3))
        new = state.add(col.delta)
        return new, T.ring_append(ring, new, tparams, step_no)

    f = jax.jit(step)
    s = CounterState.zeros(spec)
    ring = rt.telemetry.make_ring()
    for i in range(1, 3):
        s, ring = f(s, rt.params, rt.telemetry.params, ring,
                    jnp.asarray(i, jnp.int32))
    cfgp.write_text(CONFIG_B)
    rt.reload()                      # mask swap
    rt.hook_every = 3                # cadence swap through the plane
    assert rt.telemetry.cadence == 3
    for i in range(3, 7):
        s, ring = f(s, rt.params, rt.telemetry.params, ring,
                    jnp.asarray(i, jnp.int32))
    assert len(traces) == 1
    assert f._cache_size() == 1
    # ring reflects the live cadence: steps 1,2 at cadence 1, then 3,6
    rt.telemetry.publish(ring)
    snaps = rt.flush()
    assert [sn.step for sn in snaps] == [1, 2, 3, 6]
    rt.close()


def test_runtime_sigusr1_direct_handler_call(tmp_path):
    """The SIGUSR1 path, exercised by invoking the installed handler
    directly (what the OS would do on os.kill)."""
    spec = _spec()
    cfgp = tmp_path / "mon.cfg"
    cfgp.write_text(CONFIG_A)
    rt = scalpel.ScalpelRuntime(spec, config_path=str(cfgp),
                                install_signal=True)
    try:
        cfgp.write_text(CONFIG_B)
        handler = signal.getsignal(signal.SIGUSR1)
        assert callable(handler)
        handler(signal.SIGUSR1, None)   # direct call — no process signal
        assert rt.reload_count == 1
        assert float(rt.params.scope_mask[spec.scope_index("g")]) == 1.0
        # and the real-signal path still works on top of it
        os.kill(os.getpid(), signal.SIGUSR1)
        assert rt.reload_count == 2
    finally:
        signal.signal(signal.SIGUSR1, signal.SIG_DFL)
        rt.close()


def test_runtime_hooks_run_on_drained_snapshots():
    spec = _spec()
    rt = scalpel.ScalpelRuntime(spec, hook_every=2)
    seen = []
    rt.add_hook(lambda r, reports: seen.append(reports))
    state = _run_steps(spec, rt.params, CounterState.zeros(spec), [1.0, 2.0])
    rt.on_step(state)   # step 1: below cadence, no ring write
    rt.on_step(state)   # step 2: ring write
    rt.flush()
    assert len(seen) == 1
    assert {r.scope for r in seen[0]} == {"f", "g"}
    rt.close()


def test_hook_may_reenter_flush_without_deadlock():
    """A hook that calls runtime.report()/snapshot() (which flush, hence
    re-enter the drain) must not deadlock on the drain lock."""
    spec = _spec()
    rt = scalpel.ScalpelRuntime(spec, hook_every=1)
    texts = []
    rt.add_hook(lambda r, reports: texts.append(r.report()))
    state = _run_steps(spec, rt.params, CounterState.zeros(spec), [1.0])
    rt.on_step(state)
    done = threading.Event()

    def _flush():
        rt.flush()
        done.set()

    t = threading.Thread(target=_flush, daemon=True)
    t.start()
    assert done.wait(timeout=20.0), "flush deadlocked on re-entrant hook"
    assert texts and "ScALPEL report" in texts[0]
    rt.close()


def test_drained_reports_value_equal_to_sync_snapshot():
    """Acceptance: ring-drained reports == synchronous snapshots (allclose),
    driven through the real jitted train step — now a wrapped Monitor step
    threading one MonitorState pytree with a COMPACT telemetry ring."""
    from repro.configs import model_config
    from repro.data import DataConfig, SyntheticLM
    from repro.models.registry import Arch
    from repro.optim import OptConfig
    from repro.train.step import TrainState, build_monitor_spec, \
        make_train_step

    arch = Arch(model_config("xlstm_125m", smoke=True))
    data = SyntheticLM(DataConfig(vocab=512, seq_len=32, global_batch=4))
    batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}
    spec = build_monitor_spec(arch, batch)
    rt = scalpel.ScalpelRuntime(spec, hook_every=1, ring_depth=8)
    mon = scalpel.Monitor(spec, telemetry=rt.telemetry)
    step_fn = make_train_step(arch, OptConfig(lr=1e-3, warmup_steps=0), spec,
                              monitor=mon)
    jit_step = jax.jit(step_fn)   # no donation: we compare states below
    tstate = TrainState.create(arch, OptConfig(lr=1e-3, warmup_steps=0),
                               jax.random.PRNGKey(0))
    mstate = mon.init()
    drained = {}
    rt.telemetry.add_sink(T.CallbackSink(lambda s: drained.setdefault(
        s.step, s)))
    sync_states = []
    for _ in range(3):
        tstate, out, mstate = jit_step(tstate, batch, mstate)
        rt.on_step(mstate.counters, ring=mstate.ring)
        sync_states.append(jax.tree.map(jax.device_get, mstate.counters))
    rt.flush()
    assert sorted(drained) == [1, 2, 3]
    for k, sync in enumerate(sync_states, start=1):
        ring_state = drained[k].state
        # drained snapshots are COMPACT (dense slot layout) end-to-end
        assert np.asarray(ring_state.values).ndim == 1
        np.testing.assert_allclose(np.asarray(ring_state.values),
                                   np.asarray(sync.values),
                                   rtol=1e-6, atol=1e-8)
        np.testing.assert_array_equal(np.asarray(ring_state.calls),
                                      np.asarray(sync.calls))
        np.testing.assert_array_equal(np.asarray(ring_state.samples),
                                      np.asarray(sync.samples))
        # drained reports match reports built from the sync snapshot
        a = report_lib.estimates(spec, ring_state)
        b = report_lib.estimates(spec, sync)
        for scope in b:
            for slot, v in b[scope].items():
                np.testing.assert_allclose(a[scope][slot], v, rtol=1e-6,
                                           equal_nan=True)
    rt.close()


def test_jsonl_writer_single_open_buffered(tmp_path):
    p = str(tmp_path / "w.jsonl")
    spec = _spec()
    state = _run_steps(spec, MonitorParams.all_on(spec),
                       CounterState.zeros(spec), [1.0])
    reports = report_lib.build(spec, state)
    with report_lib.JsonlWriter(p, buffer_lines=10_000) as w:
        w.write(1, reports)
        w.write(2, reports)
        assert open(p).read() == ""     # buffered: nothing on disk yet
        w.flush()
        n = len(open(p).read().splitlines())
        assert n == 2 * len(reports)
        w.write(3, reports)
    # context exit closes (and flushes the tail)
    assert len(open(p).read().splitlines()) == 3 * len(reports)


def test_counterstate_sub_delta():
    spec = _spec()
    a = _bump(_bump(CounterState.zeros(spec), 2.0), 3.0)
    b = _bump(CounterState.zeros(spec), 2.0)
    d = a.sub(b)
    assert int(d.calls[0]) == 1
    assert float(d.values[0, 0]) == pytest.approx(3.0)


def test_plane_hot_loop_never_blocks_long():
    """publish() is a ref swap: a burst of publishes returns quickly even
    with a slow sink on the drain side."""
    spec = _spec()
    plane = T.TelemetryPlane(spec, depth=4, cadence=1)
    plane.add_sink(T.CallbackSink(lambda s: time.sleep(0.05)))
    cs = _bump(CounterState.zeros(spec))
    ring = plane.make_ring()
    ring = T.ring_append(ring, cs, plane.params, 1)
    jax.block_until_ready(ring.head)
    t0 = time.perf_counter()
    for _ in range(50):
        plane.publish(ring)
    assert time.perf_counter() - t0 < 1.0
    plane.close()
