"""Runtime (SIGUSR1 reload, snapshots, hooks) + report estimates.

The report tests encode the paper's Fig. 4 methodology: a call-count
multiplexed run must reconstruct the exhaustive counters within sampling
error (EXTENSIVE events scaled by calls/samples; INTENSIVE as per-call mean).
"""
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core as scalpel
from repro.core import report as report_lib
from repro.core.context import EventSpec, MonitorSpec, ScopeContext
from repro.core.counters import CounterState, MonitorParams


def _spec():
    return MonitorSpec.of([
        ScopeContext.multiplexed(
            "f",
            [[EventSpec("NUMEL", "x")], [EventSpec("MEAN", "x")]],
            period=3,
        ),
        ScopeContext.exhaustive("g", [EventSpec("MEAN", "x")]),
    ])


def _run(spec, params, state, values):
    with scalpel.Monitor(spec, params=params, counter_axes=()).open(
            params, calls_base=state.calls) as col:
        for v in values:
            with scalpel.function("f"):
                scalpel.probe(x=jnp.full((4,), v))
            with scalpel.function("g"):
                scalpel.probe(x=jnp.full((2,), v))
    return state.add(col.delta)


def test_extensive_estimate_scales_to_exhaustive():
    spec = _spec()
    state = _run(spec, MonitorParams.all_on(spec), CounterState.zeros(spec),
                 [1.0] * 12)
    reports = {r.scope: r for r in report_lib.build(spec, state)}
    f = {s.slot_id: s for s in reports["f"].slots}
    # NUMEL is extensive: sampled on 6 of 12 calls, 4 elements each ->
    # raw 24, estimate 48 (the exhaustive total)
    assert f["NUMEL:x"].samples == 6
    assert f["NUMEL:x"].raw == pytest.approx(24.0)
    assert f["NUMEL:x"].estimate == pytest.approx(48.0)
    assert f["NUMEL:x"].coverage == pytest.approx(0.5)


def test_intensive_estimate_is_per_call_mean():
    spec = _spec()
    vals = [float(i) for i in range(12)]
    state = _run(spec, MonitorParams.all_on(spec), CounterState.zeros(spec),
                 vals)
    reports = {r.scope: r for r in report_lib.build(spec, state)}
    f = {s.slot_id: s for s in reports["f"].slots}
    # MEAN sampled on calls 3,4,5,9,10,11 (period 3, set 1)
    sampled = [vals[c] for c in [3, 4, 5, 9, 10, 11]]
    assert f["MEAN:x"].estimate == pytest.approx(np.mean(sampled), rel=1e-6)
    g = {s.slot_id: s for s in reports["g"].slots}
    assert g["MEAN:x"].estimate == pytest.approx(np.mean(vals), rel=1e-6)


def test_multiplexed_vs_exhaustive_error_marginal():
    """Paper Fig. 4: sampling error of call-count multiplexing is marginal
    for stationary-ish workloads."""
    rng = np.random.default_rng(0)
    vals = rng.normal(5.0, 0.3, size=200).tolist()
    spec = _spec()
    mux = _run(spec, MonitorParams.all_on(spec), CounterState.zeros(spec),
               vals)
    est = report_lib.estimates(spec, mux)
    exhaustive = np.mean(vals)
    assert est["f"]["MEAN:x"] == pytest.approx(exhaustive, rel=0.02)
    assert est["f"]["NUMEL:x"] == pytest.approx(4 * 200, rel=0.02)


def test_unsampled_slot_reports_nan():
    spec = _spec()
    state = _run(spec, MonitorParams.all_on(spec), CounterState.zeros(spec),
                 [1.0, 1.0])  # only set 0 ever active (period 3)
    reports = {r.scope: r for r in report_lib.build(spec, state)}
    f = {s.slot_id: s for s in reports["f"].slots}
    assert np.isnan(f["MEAN:x"].estimate)


def test_report_text_and_json_roundtrip(tmp_path):
    spec = _spec()
    state = _run(spec, MonitorParams.all_on(spec), CounterState.zeros(spec),
                 [2.0] * 6)
    reports = report_lib.build(spec, state)
    text = report_lib.format_text(reports)
    assert "[f] calls=6" in text and "NUMEL:x" in text
    js = report_lib.to_json(reports)
    assert "estimate" in js
    p = tmp_path / "log.jsonl"
    report_lib.write_jsonl(str(p), 7, reports)
    import json

    lines = [json.loads(x) for x in p.read_text().splitlines()]
    assert lines[0]["step"] == 7 and len(lines) == 2


# ---------------------------------------------------------------------------
# runtime
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


def test_runtime_reload_swaps_masks_without_retrace(tmp_path):
    spec = _spec()
    cfgp = tmp_path / "mon.cfg"
    cfgp.write_text(CONFIG_A)
    rt = scalpel.ScalpelRuntime(spec, config_path=str(cfgp))
    fi, gi = spec.scope_index("f"), spec.scope_index("g")
    assert float(rt.params.scope_mask[fi]) == 1.0
    assert float(rt.params.scope_mask[gi]) == 0.0

    traces = []

    @jax.jit
    def step(state, params):
        traces.append(1)
        with scalpel.Monitor(spec, params=params, counter_axes=()).open(
                params, calls_base=state.calls) as col:
            with scalpel.function("f"):
                scalpel.probe(x=jnp.ones(3))
            with scalpel.function("g"):
                scalpel.probe(x=jnp.ones(3))
        return state.add(col.delta)

    s = CounterState.zeros(spec)
    s = step(s, rt.params)
    cfgp.write_text(CONFIG_B)
    rt.reload()
    assert rt.reload_count == 1
    assert float(rt.params.scope_mask[fi]) == 0.0
    assert float(rt.params.scope_mask[gi]) == 1.0
    s = step(s, rt.params)
    assert len(traces) == 1  # reload is a data swap, not a re-trace
    assert int(s.samples[gi, 0]) == 1


@pytest.mark.skipif(not hasattr(signal, "SIGUSR1"), reason="no SIGUSR1")
def test_runtime_sigusr1_reload(tmp_path):
    spec = _spec()
    cfgp = tmp_path / "mon.cfg"
    cfgp.write_text(CONFIG_A)
    rt = scalpel.ScalpelRuntime(spec, config_path=str(cfgp),
                                install_signal=True)
    cfgp.write_text(CONFIG_B)
    os.kill(os.getpid(), signal.SIGUSR1)
    assert rt.reload_count == 1
    assert float(rt.params.scope_mask[spec.scope_index("g")]) == 1.0


def test_runtime_hooks_and_snapshot():
    spec = _spec()
    rt = scalpel.ScalpelRuntime(spec, hook_every=2)
    seen = []
    rt.add_hook(lambda r, reports: seen.append(reports))
    state = _run(spec, rt.params, CounterState.zeros(spec), [1.0, 2.0])
    rt.on_step(state)   # step 1: below cadence, no ring write
    rt.on_step(state)   # step 2: ring write -> hook on drained snapshot
    rt.flush()          # hooks run asynchronously on the drain thread
    assert len(seen) == 1
    assert seen[0][0].scope == "f"
    est = rt.estimates()
    assert "f" in est and "g" in est
    rt.close()


def test_runtime_unsatisfiable_config_reported(tmp_path):
    spec = _spec()
    cfgp = tmp_path / "mon.cfg"
    cfgp.write_text(
        "NO_FUNCTIONS=1\n[FUNCTION]\nFUNC_NAME=nope\nNO_EVENTS=0\n"
        "[/FUNCTION]\n"
    )
    rt = scalpel.ScalpelRuntime(spec, config_path=str(cfgp))
    assert rt.last_reload_errors == ["scope:nope"]
