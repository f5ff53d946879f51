"""The reduction from a profiler trace to the benchmark's numbers: interval
arithmetic on hand-made timelines, and the whole reduction on a small trace
recorded on a TPU v5e (``data/tiny.xplane.pb``: three calls of a jitted
probe-moments kernel plus a matmul, 2 ms sleeps between them, inside a
``bench.window`` span)."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import flops, peaks, trace  # noqa: E402

FIXTURE = Path(__file__).parent / "data" / "tiny.xplane.pb"


def test_union_merges_overlaps_and_clips():
    merged = trace.union(np.array([0.0, 5.0, 8.0, 30.0]),
                         np.array([10.0, 7.0, 12.0, 50.0]), 2.0, 40.0)
    assert merged == [(2.0, 12.0), (30.0, 40.0)]
    assert trace.gaps_of(merged, 0.0, 45.0) == [(0.0, 2.0), (12.0, 30.0),
                                                (40.0, 45.0)]


def test_union_matches_a_plain_merge_on_random_intervals():
    rng = np.random.default_rng(2**31 + 5)
    for _ in range(200):
        n = int(rng.integers(0, 30))
        starts = rng.uniform(0, 100, n)
        ends = starts + rng.uniform(-5, 30, n)
        lo, hi = sorted(rng.uniform(-10, 120, 2))
        want: list[list[float]] = []
        for a, b in sorted(zip(np.clip(starts, lo, hi),
                               np.clip(ends, lo, hi))):
            if b <= a:
                continue
            if want and a <= want[-1][1]:
                want[-1][1] = max(want[-1][1], b)
            else:
                want.append([a, b])
        merged = trace.union(starts, ends, lo, hi)
        assert merged == [tuple(w) for w in want]
        covered = sum(b - a for a, b in merged + trace.gaps_of(merged, lo, hi))
        assert covered == pytest.approx(hi - lo)


def test_nesting_finds_leaves_and_top_level_ops():
    # a while loop [0, 100) holding two ops, then a lone op
    line = trace.Line("XLA Ops", [0, 10, 50, 120], [100, 40, 90, 130],
                      ["while.1", "fusion.2", "fusion.3", "copy.4"])
    leaf, top = line.nesting()
    assert leaf.tolist() == [False, True, True, True]
    assert top.tolist() == [True, False, False, True]


def _trace_of(ops: trace.Line, window_ns: float, host=()) -> trace.Trace:
    leaf, top = ops.nesting()
    return trace.Trace(window_ns, [{"plane": "/device:TPU:0", "ops": ops,
                                    "leaf": leaf, "top": top}], list(host))


def test_busy_counts_leaf_union_and_gaps_take_the_span_they_fall_in():
    ops = trace.Line("XLA Ops", [0, 10, 50, 120], [100, 40, 90, 130],
                     ["while.1", "fusion.2", "fusion.3", "copy.4"])
    host = trace.Line("main", [0, 85, 95], [200, 110, 105],
                      ["bench.window", "PjitFunction(step)", "bench.inner"])
    tr = _trace_of(ops, 200.0, [host])
    # leaves 10-40, 50-90, 120-130: 80 ns busy of 200
    assert tr.busy_s() == pytest.approx(80e-9)
    assert tr.op_seconds() == {"while.1": 100e-9, "copy.4": 10e-9}
    gaps = sorted(tr.idle_gaps(), key=lambda g: g[0])
    assert gaps == [(0.0, 10.0), (40.0, 50.0), (90.0, 120.0), (130.0, 200.0)]
    # 90-120: inner span bench.inner (95-105) holds its middle, the dispatch
    # overlaps it most
    assert tr.name_gap(90.0, 120.0) == "bench.inner: PjitFunction(step)"
    bd = tr.breakdown()
    assert bd["idle_gaps"][0] == ["bench.window: no host event", 70e-9]
    assert bd["device_ops"][0] == ["while.1", 100e-9]


def test_operand_shapes_read_from_hlo_text():
    text = ("%probe_moments.3 = f32[1,8]{1,0} custom-call(bf16[8,256,768]"
            "{2,1,0:T(8,128)(2,1)} %p.1), custom_call_target=\"tpu_custom_"
            "call\"")
    assert trace.operand_shapes(text) == [("bf16", (8, 256, 768))]
    assert trace.shape_bytes(trace.operand_shapes(text)) == 2 * 8 * 256 * 768
    assert trace.op_short_name(text) == "probe_moments.3"


def test_kernel_roofline_arithmetic():
    peak = peaks.peaks("TPU v5 lite")
    cost = flops.moments_kernel_cost([("bf16", (8, 2048, 768))])
    assert cost["bytes"] == 2 * 8 * 2048 * 768
    # memory bound: bytes over HBM bandwidth
    assert flops.roofline_seconds(cost, peak) == pytest.approx(
        cost["bytes"] / 819e9)


@pytest.fixture(scope="module")
def tiny():
    if not FIXTURE.is_file():
        pytest.fail(f"{FIXTURE} is missing")
    return trace.Trace.load(FIXTURE, span="bench.window")


def test_recorded_trace_has_one_tpu_and_busy_below_window(tiny):
    assert [d["plane"] for d in tiny.devices] == ["/device:TPU:0"]
    busy = tiny.busy_s()
    assert 0 < busy < tiny.window_ns / 1e9


def test_recorded_trace_finds_the_probe_kernel(tiny):
    calls = tiny.kernel_calls(trace.PROBE_KERNEL)
    assert len(calls) == 3
    for c in calls:
        assert c["operands"] == [("bf16", (8, 256, 768))]
        assert c["seconds"] > 0


def test_recorded_trace_breakdown_names_gaps_by_harness_span(tiny):
    bd = tiny.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10
    assert 0 < len(bd["idle_gaps"]) <= 10
    names = [g[0] for g in bd["idle_gaps"]]
    assert any(n.startswith("bench.sleep") for n in names), names


@pytest.mark.parametrize("cell", ["xlstm_l12_d768.train.all",
                                  "qwen3_14b_l8.serve.all"])
def test_a_traced_run_reads_its_span_as_the_window(tmp_path, cell):
    # training traces trace_megasteps times a megastep's time as the window
    # measured it; serving its traffic's trace_seconds
    sys.path.insert(0, str(Path(__file__).parent))
    import tiny

    _, kind, ctx = tiny.context(cell, 2**31 + 41, tmp_path, trace=True)
    out = kind.run(ctx)
    r = out["readings"]
    if kind.KIND == "train":
        k = ctx.traffic["steps_per_commit"]
        want = (ctx.traffic["trace_megasteps"] * r["window"]["seconds"] * k
                / out["attempted"])
    else:
        want = ctx.traffic["trace_seconds"]
    assert r["window_s"] == pytest.approx(want, rel=0.1, abs=0.01)
    assert r["trace"].start_ns > 0
