"""Functional Monitor transformation: one MonitorState pytree, compact
counters end-to-end, plan dedup, checkpoint attestation, the raw region."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import core as scalpel
from repro.core import plan as plan_lib
from repro.core import report as report_lib
from repro.core import telemetry as T
from repro.core.context import EventSpec, MonitorSpec, ScopeContext
from repro.core.counters import CounterState, MonitorParams


def _spec():
    return MonitorSpec.of([
        ScopeContext.multiplexed("hot", [
            [EventSpec("MEAN", "x")],
            [EventSpec("L2NORM", "x")],
        ]),
        ScopeContext.exhaustive("cold", [EventSpec("ACT_RMS", "x"),
                                         EventSpec("NUMEL", "x")]),
    ])


def _work(x):
    for i in range(4):
        with scalpel.function("hot"):
            scalpel.probe(x=x * (i + 1))
    with scalpel.function("cold"):
        scalpel.probe(x=x + 1)
    return x * 2


def _manual_state(spec, params, x, steps=1):
    """The hand-threaded baseline: raw ``Monitor.open`` regions whose
    padded deltas the caller folds itself."""
    s = CounterState.zeros(spec)

    @jax.jit
    def step(s, params, x):
        with scalpel.Monitor(spec, params=params, counter_axes=()).open(
                params, calls_base=s.calls) as col:
            _work(x)
        return s.add(col.delta)

    for _ in range(steps):
        s = step(s, params, x)
    return s


# ---------------------------------------------------------------------------
# wrap: the functional transformation
# ---------------------------------------------------------------------------

def test_wrap_matches_manual_collecting_path():
    spec = _spec()
    mon = scalpel.Monitor(spec, counter_axes=())
    step = jax.jit(mon.wrap(_work))
    ms = mon.init()
    x = jnp.arange(6.0)
    for _ in range(3):
        out, ms = step(ms, x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x * 2))
    want = _manual_state(spec, mon.params, x, steps=3)
    got = mon.counter_state(ms)
    np.testing.assert_array_equal(np.asarray(got.calls),
                                  np.asarray(want.calls))
    np.testing.assert_allclose(np.asarray(got.values),
                               np.asarray(want.values), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got.samples),
                                  np.asarray(want.samples))
    assert int(ms.step) == 3


def test_wrap_state_is_compact_not_padded():
    # uneven scope widths: the padded block would be 3x6=18 lanes; the
    # MonitorState carries exactly the 7 live lanes
    spec = MonitorSpec.of([
        ScopeContext.exhaustive("wide", [
            EventSpec(e, "x") for e in
            ("MEAN", "L2NORM", "ACT_RMS", "ACT_MAX_ABS", "NAN_COUNT",
             "INF_COUNT")
        ]),
        ScopeContext.exhaustive("narrow", [EventSpec("MEAN", "x")]),
        ScopeContext.exhaustive("dark", []),
    ])
    lay = plan_lib.spec_layout(spec)
    mon = scalpel.Monitor(spec, counter_axes=())
    ms = mon.init()
    assert ms.values.shape == (lay.total,)
    assert ms.samples.shape == (lay.total,)
    assert lay.total == 7
    assert lay.total < spec.n_scopes * spec.max_slots
    assert ms.fingerprint == spec.fingerprint


def test_wrap_param_swap_in_state_never_retraces():
    spec = _spec()
    mon = scalpel.Monitor(spec, counter_axes=())
    traces = []

    def fn(x):
        traces.append(1)
        return _work(x)

    step = jax.jit(mon.wrap(fn))
    ms = mon.init()
    x = jnp.ones(4)
    _, ms = step(ms, x)
    # flip the monitored subset INSIDE the state pytree: same compiled step
    ms = mon.sync(ms, params=MonitorParams.selective(spec, ["cold"]))
    _, ms = step(ms, x)
    ms = mon.sync(ms, params=MonitorParams.all_off(spec))
    _, ms = step(ms, x)
    assert len(traces) == 1
    assert step._cache_size() == 1
    # the masked-off step intercepted but sampled nothing new
    est = mon.estimates(ms)
    assert int(ms.calls[0]) == 12      # 4 hot calls x 3 steps
    assert np.isfinite(est["cold"]["ACT_RMS:x"])


def test_wrap_multiplex_schedule_continues_across_steps():
    spec = _spec()
    mon = scalpel.Monitor(spec, counter_axes=())
    step = jax.jit(mon.wrap(_work))
    ms = mon.init()
    for _ in range(2):
        _, ms = step(ms, jnp.ones(4))
    # 8 hot calls alternate sets exactly: 4 MEAN samples, 4 L2NORM samples
    lane = spec.slot_lane
    assert int(ms.samples[lane("hot", "MEAN:x")]) == 4
    assert int(ms.samples[lane("hot", "L2NORM:x")]) == 4


def test_wrap_threads_scan_with_counters():
    spec = _spec()
    mon = scalpel.Monitor(spec, counter_axes=())

    def fn(xs):
        def body(c, x):
            with scalpel.function("hot"):
                scalpel.probe(x=x)
            return c + 1.0, x

        c, _ = scalpel.scan_with_counters(body, jnp.zeros(()), xs)
        return c

    step = jax.jit(mon.wrap(fn))
    ms = mon.init()
    out, ms = step(ms, jnp.ones((6, 2)))
    assert float(out) == 6.0
    assert int(ms.calls[0]) == 6
    assert int(ms.samples[0] + ms.samples[1]) == 6


def test_monitor_jit_matches_wrap_and_reuses_knob_objects():
    """Monitor.jit == jax.jit(wrap) semantically, but the runtime knobs
    (params/tparams) come back as the caller's SAME objects — they never
    round-trip the compiled graph as outputs."""
    spec = _spec()
    mon = scalpel.Monitor(spec, counter_axes=())
    jstep = mon.jit(_work)
    wstep = jax.jit(mon.wrap(_work))
    a, b = mon.init(), mon.init()
    x = jnp.arange(4.0)
    for _ in range(2):
        out_j, a = jstep(a, x)
        out_w, b = wstep(b, x)
    np.testing.assert_array_equal(np.asarray(out_j), np.asarray(out_w))
    for la, lb in zip((a.calls, a.values, a.samples),
                      (b.calls, b.values, b.samples)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-6)
    assert int(a.step) == int(b.step) == 2
    # identity: the knob objects pass through the host-side wrapper
    ms0 = mon.init()
    _, ms1 = jstep(ms0, x)
    assert ms1.params is ms0.params
    assert ms1.tparams is ms0.tparams


def test_monitored_decorator():
    spec = _spec()

    @scalpel.monitored(spec, counter_axes=())
    def step(x):
        return _work(x)

    ms = step.init()
    out, ms = jax.jit(step)(ms, jnp.ones(3))
    assert int(ms.calls[1]) == 1
    assert step.monitor.spec is spec


def test_wrap_with_telemetry_ring_drains_compact_snapshots():
    spec = _spec()
    plane = T.TelemetryPlane(spec, depth=4, cadence=1, interval_s=60.0)
    drained = []
    plane.add_sink(T.CallbackSink(drained.append))
    mon = scalpel.Monitor(spec, telemetry=plane, counter_axes=())
    step = jax.jit(mon.wrap(_work))
    ms = mon.init()
    assert ms.ring is not None
    for _ in range(3):
        _, ms = step(ms, jnp.ones(4))
        plane.publish(ms.ring)
        plane.flush()
    assert [s.step for s in drained] == [1, 2, 3]
    # snapshots are compact and reports read them directly
    last = drained[-1]
    assert np.asarray(last.state.values).ndim == 1
    est = report_lib.estimates(spec, last.state)
    # NUMEL is extensive: 4 elements/call x 3 calls, exhaustively covered
    assert est["cold"]["NUMEL:x"] == pytest.approx(12.0)
    # delta decoding works on the compact layout too
    assert int(last.delta.calls[0]) == 4
    plane.close()


def test_wrap_cadence_rides_in_state_no_retrace():
    spec = _spec()
    plane = T.TelemetryPlane(spec, depth=8, cadence=1, interval_s=60.0)
    mon = scalpel.Monitor(spec, telemetry=plane, counter_axes=())
    traces = []

    def fn(x):
        traces.append(1)
        return x

    step = jax.jit(mon.wrap(fn))
    ms = mon.init()
    for i in range(2):
        _, ms = step(ms, jnp.ones(2))
    plane.set_cadence(3)
    ms = mon.sync(ms, tparams=plane.params)
    for i in range(4):
        _, ms = step(ms, jnp.ones(2))
    assert len(traces) == 1 and step._cache_size() == 1
    plane.publish(ms.ring)
    steps = sorted(s.step for s in plane.flush())
    assert steps == [1, 2, 3, 6]
    plane.close()


# ---------------------------------------------------------------------------
# plan deduplication (identical sweeps share a switch branch body)
# ---------------------------------------------------------------------------

def test_identical_sets_share_branch_body():
    ctx = ScopeContext.multiplexed("s", [
        [EventSpec("ACT_RMS", "x")],
        [EventSpec("ACT_RMS", "x")],
        [EventSpec("ACT_MAX_ABS", "x")],
        [EventSpec("ACT_RMS", "x")],
    ])
    sp = plan_lib.compile_scope_plans(ctx, frozenset({"x"}))
    assert sp.n_sets == 4
    assert sp.n_branches == 2
    assert sp.plans_deduped == 2
    assert sp.branch_index == (0, 0, 1, 0)
    # the member table still points every set at its own scatter lane
    assert [p.members for p in sp.plans] == [(0,), (1,), (2,), (3,)]


def test_deduped_plans_count_in_describe():
    spec = MonitorSpec.of([ScopeContext.multiplexed("s", [
        [EventSpec("MEAN", "x")], [EventSpec("MEAN", "x")],
    ])])
    text = plan_lib.describe_plans(spec)
    assert "plans_deduped: 1" in text
    assert "1 branch bodies" in text


def test_deduped_execution_matches_schedule():
    """Sets sharing one branch body must still scatter into their OWN slots
    on the exact multiplex schedule."""
    spec = MonitorSpec.of([ScopeContext.multiplexed("s", [
        [EventSpec("MEAN", "x")],
        [EventSpec("MEAN", "x")],
        [EventSpec("MEAN", "x")],
    ])])
    mon = scalpel.Monitor(spec, counter_axes=())

    def fn(x):
        for i in range(7):
            with scalpel.function("s"):
                scalpel.probe(x=x * (i + 1))
        return x

    _, ms = jax.jit(mon.wrap(fn))(mon.init(), jnp.ones(2))
    # call c lands in set c % 3; MEAN of x*(c+1) over ones is c+1
    want = [[1.0, 4.0, 7.0], [2.0, 5.0], [3.0, 6.0]]
    for k in range(3):
        assert float(ms.values[k]) == pytest.approx(sum(want[k]))
        assert int(ms.samples[k]) == len(want[k])


def test_dedup_table_is_part_of_plan_identity():
    """Two specs that differ only in whether their sets dedup must not
    collide (the fingerprint hashes the branch-body table)."""
    dup = MonitorSpec.of([ScopeContext.multiplexed("s", [
        [EventSpec("MEAN", "x")], [EventSpec("MEAN", "x")],
    ])])
    distinct = MonitorSpec.of([ScopeContext.multiplexed("s", [
        [EventSpec("MEAN", "x")], [EventSpec("L2NORM", "x")],
    ])])
    assert dup.fingerprint != distinct.fingerprint


# ---------------------------------------------------------------------------
# compact layout round-trips (property tests)
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(
    st.lists(st.integers(0, 4), min_size=1, max_size=5),  # slots per scope
    st.integers(0, 2 ** 31 - 1),                          # value seed
)
def test_compact_roundtrip_property(widths, seed):
    """CounterState -> compact -> CounterState is the identity for ANY
    scope-width profile (including empty scopes)."""
    events = ["MEAN", "L2NORM", "ACT_RMS", "ACT_MAX_ABS"]
    ctxs = [
        ScopeContext.exhaustive(
            f"s{i}", [EventSpec(events[j % len(events)], f"t{j}")
                      for j in range(w)]
        )
        for i, w in enumerate(widths)
    ]
    spec = MonitorSpec.of(ctxs)
    rng = np.random.RandomState(seed % (2 ** 32 - 1))
    n, m = spec.n_scopes, spec.max_slots
    state = CounterState(
        calls=jnp.asarray(rng.randint(0, 100, (n,)), jnp.int32),
        values=jnp.asarray(rng.randn(n, m), jnp.float32),
        samples=jnp.asarray(rng.randint(0, 50, (n, m)), jnp.int32),
    )
    # zero the padding lanes: they are not representable compactly (and the
    # probe path never writes them)
    lay = plan_lib.spec_layout(spec)
    mask = np.zeros((n, m), np.float32)
    for i, w in enumerate(lay.widths):
        mask[i, :w] = 1.0
    state = CounterState(
        calls=state.calls,
        values=state.values * mask,
        samples=(state.samples * mask).astype(jnp.int32),
    )
    compact = state.compact(spec)
    assert compact.values.shape == (lay.total,)
    back = CounterState.from_compact(spec, compact)
    np.testing.assert_array_equal(np.asarray(back.calls),
                                  np.asarray(state.calls))
    np.testing.assert_allclose(np.asarray(back.values),
                               np.asarray(state.values))
    np.testing.assert_array_equal(np.asarray(back.samples),
                                  np.asarray(state.samples))
    # and reports built from either carrier agree slot-for-slot
    a = report_lib.estimates(spec, state)
    b = report_lib.estimates(spec, compact)
    for scope in a:
        for slot, v in a[scope].items():
            np.testing.assert_allclose(b[scope][slot], v, rtol=1e-6,
                                       equal_nan=True)


def test_monitorstate_roundtrips_through_legacy_counterstate():
    spec = _spec()
    mon = scalpel.Monitor(spec, counter_axes=())
    _, ms = jax.jit(mon.wrap(_work))(mon.init(), jnp.ones(4))
    padded = mon.counter_state(ms)
    again = padded.compact(spec)
    np.testing.assert_allclose(np.asarray(again.values),
                               np.asarray(ms.values))
    np.testing.assert_array_equal(np.asarray(again.samples),
                                  np.asarray(ms.samples))
    np.testing.assert_array_equal(np.asarray(again.calls),
                                  np.asarray(ms.calls))


# ---------------------------------------------------------------------------
# checkpoint attestation + runtime close semantics (satellites)
# ---------------------------------------------------------------------------

def test_sched_calls_base_and_checkpoint_roundtrip():
    """A non-reducing monitor needs no separate schedule base (``calls``
    IS per-shard); a reducible one carries ``sched_calls``, equal to
    ``calls`` when no axis ends up bound — and either way the checkpoint
    payload resumes the multiplex phase exactly."""
    spec = _spec()
    # no reduction: calls doubles as the base, no redundant lanes carried
    mon0 = scalpel.Monitor(spec, counter_axes=())
    assert mon0.init().sched_calls is None
    # reducible monitor on an unbound axis: sched tracks calls exactly
    mon = scalpel.Monitor(spec, counter_axes=("data",))
    step = jax.jit(mon.wrap(_work))
    ms = mon.init()
    for _ in range(3):
        _, ms = step(ms, jnp.ones(4))
    np.testing.assert_array_equal(np.asarray(ms.sched_calls),
                                  np.asarray(ms.calls))
    payload = mon.checkpoint_payload(ms)
    assert "sched_calls" in payload
    back = mon.restore(mon.init(), payload)
    np.testing.assert_array_equal(np.asarray(back.sched_calls),
                                  np.asarray(ms.sched_calls))
    # resumed schedule continues exactly where the original left off
    _, a = step(ms, jnp.ones(4))
    _, b = step(back, jnp.ones(4))
    np.testing.assert_array_equal(np.asarray(a.samples),
                                  np.asarray(b.samples))


def test_save_metadata_and_check_resume():
    spec = _spec()
    mon = scalpel.Monitor(spec, counter_axes=())
    ms = mon.init()
    meta = ms.save_metadata()
    assert meta["plan_fingerprint"] == spec.fingerprint
    assert mon.check_resume(meta) is True
    assert mon.check_resume({}) is None          # pre-fingerprint ckpt
    bad = dict(meta, plan_fingerprint="0" * 40)
    with pytest.raises(RuntimeError, match="plan"):
        mon.check_resume(bad)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert mon.check_resume(bad, strict=False) is False
    assert any("plan" in str(x.message) for x in w)


def test_runtime_resume_metadata_check():
    spec = _spec()
    rt = scalpel.ScalpelRuntime(spec)
    meta = rt.save_metadata()
    assert rt.check_resume_metadata(meta) is True
    assert rt.check_resume_metadata(None) is None
    with pytest.raises(RuntimeError, match="plan mismatch"):
        rt.check_resume_metadata({"plan_fingerprint": "f" * 40})
    rt.close()


def test_runtime_close_idempotent_and_exit_report_skips(capsys):
    spec = _spec()
    rt = scalpel.ScalpelRuntime(spec, report_at_exit=True)
    rt.on_step(CounterState.zeros(spec))
    rt.close()
    assert rt.closed
    rt.close()                    # second close: no-op, no error
    capsys.readouterr()
    rt._exit_report()             # the atexit pass after an explicit close
    assert capsys.readouterr().out == ""   # ...prints nothing (no re-flush)


def test_exit_report_still_prints_without_close(capsys):
    spec = _spec()
    rt = scalpel.ScalpelRuntime(spec, report_at_exit=True)
    rt.on_step(CounterState.zeros(spec))
    capsys.readouterr()
    rt._exit_report()
    assert "ScALPEL report" in capsys.readouterr().out
    rt.close()


# ---------------------------------------------------------------------------
# the raw region
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plan_mode", ["per_set", "union"])
def test_open_continues_schedule_from_calls_base(plan_mode):
    """A region opened with ``calls_base`` at an earlier region's calls
    continues its multiplex schedule: two regions that probe once each
    count what one region that probes twice counts."""
    spec = _spec()
    params = MonitorParams.all_on(spec)
    mon = scalpel.Monitor(spec, params=params, counter_axes=(),
                          plan_mode=plan_mode)
    x = jnp.arange(1.0, 5.0)

    def probe_hot():
        with scalpel.function("hot"):
            scalpel.probe(x=x)

    with mon.open(params, calls_base=None) as col:
        probe_hot()
    first = col.delta
    with mon.open(params, calls_base=first.calls) as col:
        probe_hot()
    second = col.delta
    with mon.open(params) as col:
        probe_hot()
        probe_hot()
    whole = col.delta

    hot = spec.scope_index("hot")
    # period 1, two sets: the first region samples set 0 (MEAN, slot 0),
    # the second set 1 (L2NORM, slot 1)
    assert np.asarray(first.samples)[hot, :2].tolist() == [1, 0]
    assert np.asarray(second.samples)[hot, :2].tolist() == [0, 1]
    split = first.add(second)
    np.testing.assert_array_equal(split.calls, whole.calls)
    np.testing.assert_array_equal(split.samples, whole.samples)
    np.testing.assert_allclose(split.values, whole.values, rtol=1e-6)
