"""Paper Figs. 2-3: monitoring overhead of {vanilla, perfmon, all, selective}.

Reproduction mapping (DESIGN.md §2):
  vanilla    — the uninstrumented program (scopes exist, no collector)
  perfmon    — breakpoint_mode: an ordered io_callback host round-trip on
               every monitored-scope entry+exit (the ptrace analogue)
  all        — collector over the FULL compile-time scope set; only one
               scope's events are unmasked (paper: intercept all functions,
               monitor one)
  selective  — collector whose compile-time set contains ONLY the monitored
               scope

Probe evaluation is plan-driven (core/plan.py): every (scope, event set)
executes its compiled MomentPlan — exactly the channels that set finalizes
from, swept once per probed tensor.  A dedicated sparse-active-set sweep
(``run_plan_sweep``) measures the point of the plan layer: a multiplexed
scope whose every set needs a strict SUBSET of the union of channels, run
once with per-set plans and once with the ``plan_mode="union"`` baseline
(the pre-plan behaviour: each branch sweeps the cross-set union), with an
allclose check that both accumulate identical counters.

Workloads mirror the paper's two axes:
  * real apps (reduced NAS stand-ins): smoke configs of a dense, an SSM and
    an MoE arch, one training step each;
  * a synthetic call-count sweep (Fig. 3's axis; tens of calls in fast/CI
    mode, up to 1024 in full mode — the unrolled 6-event graphs there cost
    minutes of XLA CPU compile): a small function called k times per step,
    probing the motivation's six activation statistics.

``run_monitor_sweep`` measures the functional API redesign: a
``Monitor.wrap``-ped step threading ONE compact MonitorState pytree vs the
manual deprecated ``collecting()`` + ``state.add(col.delta)`` path on the
same workload (counters asserted allclose), and ``run_monitor_psum_check``
(a 2-forced-host-device subprocess) asserts that a ``shard_wrap``-ped step's
psum-reduced counters EXACTLY equal the sum of per-shard manual runs.

Additionally, a readback-stall sweep (``run_readback_sweep``) measures the
cost of CONSUMING counters: a synchronous full-CounterState ``device_get``
every ``hook_every`` steps (the pre-telemetry runtime) vs the telemetry
plane's device-side snapshot ring drained incrementally (cursor-based slot
copies) by a background thread, across ``hook_every`` and ring-depth
settings, with an allclose check that drained counters equal synchronous
snapshots.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import core as scalpel
from repro.configs import model_config
from repro.core import plan as plan_lib
from repro.core import telemetry as telemetry_lib
from repro.core.backends import host_callback as hc
from repro.core.context import EventSpec, MonitorSpec, ScopeContext
from repro.core.counters import CounterState, MonitorParams
from repro.models.registry import Arch
from repro.train.step import build_monitor_spec

from .common import bench, fmt_table, save_json

# The motivation's six per-tensor statistics — all moment-derived, so the
# planned path reads each probed tensor exactly once for all of them.
PROBE_EVENTS = (
    "ACT_RMS", "ACT_MEAN_ABS", "ACT_MAX_ABS", "ACT_ZERO_FRAC",
    "NAN_COUNT", "INF_COUNT",
)

CASE_ORDER = ("vanilla", "selective", "all", "perfmon")


# ---------------------------------------------------------------------------
# builders for the test cases
# ---------------------------------------------------------------------------

def _arch_loss(arch):
    def loss(params, batch):
        return arch.loss_fn(params, batch)
    return loss


def build_cases(loss_fn, params, batch, spec_all: MonitorSpec,
                monitored_scope: str):
    """Returns {case: builder}; builder() -> (fn, monitor).  Monitored-case
    ``fn`` returns a tuple whose LAST element is the accumulated
    CounterState."""
    grad = jax.grad(lambda p, b: loss_fn(p, b))

    def vanilla():
        f = jax.jit(lambda p, b: (loss_fn(p, b), grad(p, b)))
        return lambda: f(params, batch), None

    def perfmon():
        mon = hc.global_monitor()

        def step(p, b):
            return loss_fn(p, b), grad(p, b)

        with scalpel.breakpoint_mode(mon, scopes=[monitored_scope.split("/")[-1]]):
            f = jax.jit(step)
            f.lower(params, batch)  # trace inside the ctx so bps are planted
            # keep ctx open through first real call:
            return (lambda: f(params, batch)), mon

    def collector_case(spec_case, mp):
        def step(p, b, state, mp):
            with scalpel.collecting(spec_case, mp, state) as col:
                l = loss_fn(p, b)
                g = jax.grad(lambda pp: loss_fn(pp, b))(p)
            return l, g, state.add(col.delta)

        f = jax.jit(step)
        s0 = CounterState.zeros(spec_case)
        return (lambda: f(params, batch, s0, mp)), None

    def all_case():
        mp = MonitorParams.selective(spec_all, [monitored_scope])
        return collector_case(spec_all, mp)

    def selective():
        ctx = spec_all.context(monitored_scope)
        spec_sel = MonitorSpec.of([ctx])
        return collector_case(spec_sel, MonitorParams.all_on(spec_sel))

    return {
        "vanilla": vanilla,
        "perfmon": perfmon,
        "all": all_case,
        "selective": selective,
    }


def run_arch_workloads(arch_ids=("qwen3_14b", "xlstm_125m", "dbrx_132b"),
                       iters: int = 5, seq: int = 64, batch_size: int = 4):
    rows = []
    for aid in arch_ids:
        cfg = model_config(aid, smoke=True)
        arch = Arch(cfg)
        params = arch.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(
            jax.random.PRNGKey(1), (batch_size, seq), 0, cfg.vocab
        )
        batch = {"tokens": toks,
                 "targets": jax.random.randint(
                     jax.random.PRNGKey(2), (batch_size, seq), 0, cfg.vocab)}
        spec_all = build_monitor_spec(arch, batch, tensor_events=PROBE_EVENTS)
        # monitor the mlp/ffn-ish scope (called n_layers times per step)
        cand = [s for s in spec_all.scopes
                if s.endswith(("mlp", "moe", "ssm", "mlstm", "ffn"))]
        scope = cand[0] if cand else spec_all.scopes[0]
        loss_fn = _arch_loss(arch)
        case_builders = build_cases(loss_fn, params, batch, spec_all, scope)
        built = {}
        for case in CASE_ORDER:
            fn, mon = case_builders[case]()
            built[case] = fn
            if case == "perfmon":
                hc.global_monitor().reset()
        # two round-robin measurement passes (min taken): a host load spike
        # then skews every case equally instead of poisoning one row.
        results = {c: [] for c in CASE_ORDER}
        for rnd in range(2):
            for case in CASE_ORDER:
                if case == "perfmon" and rnd == 1:
                    # reset so bp_calls reflects ONE bench (2 warmups +
                    # iters), keeping the count comparable across PRs.
                    hc.global_monitor().reset()
                results[case].append(bench(built[case], iters=iters))
        base = None
        for case in CASE_ORDER:
            t = min(r["min_s"] for r in results[case])
            med = min(r["median_s"] for r in results[case])
            if case == "vanilla":
                base = t
            rows.append({
                "workload": aid, "case": case, "scope": scope,
                "n_scopes": spec_all.n_scopes,
                "median_ms": round(med * 1e3, 2),
                "min_ms": round(t * 1e3, 3),
                "overhead_pct": round(100 * (t - base) / base, 1),
                "bp_calls": sum(hc.global_monitor().calls.values())
                if case == "perfmon" else 0,
            })
    return rows


def run_callcount_sweep(counts=(64, 256, 512), iters: int = 7,
                        probe_size: int = 4096, rounds: int = 2):
    """Fig. 3's axis: overhead vs number of function calls per run.

    Every case is measured ``rounds`` times round-robin (min taken) so a
    transient load spike on the host doesn't poison one case's timing.
    """
    rows = []
    for k in counts:
        slots = [EventSpec(e, "x") for e in PROBE_EVENTS]
        spec = MonitorSpec.of([
            ScopeContext.exhaustive("hot", slots),
            ScopeContext.exhaustive("cold", slots),
        ])

        def fresh_work():
            # one function object PER CASE: jax.jit's global cache keys on
            # the function identity, so sharing `work` across cases would
            # let the breakpoint-instrumented perfmon trace alias the
            # vanilla one (and vice versa), corrupting both measurements.
            def work(x):
                # a cheap body so the instrumentation cost is visible
                for _ in range(k):
                    with scalpel.function("hot"):
                        x = x * 1.0001 + 0.1
                        scalpel.probe(x=x)
                with scalpel.function("cold"):
                    scalpel.probe(x=x)
                return x

            return work

        x0 = jnp.ones((probe_size,))

        def monitored(sp):
            mp = MonitorParams.selective(sp, ["hot"])
            s0 = CounterState.zeros(sp)

            work = fresh_work()

            def step(x, s, mp, sp=sp, work=work):
                with scalpel.collecting(sp, mp, s) as col:
                    y = work(x)
                return y, s.add(col.delta)

            f = jax.jit(step)
            return lambda f=f, s0=s0, mp=mp: f(x0, s0, mp)

        spec_sel = MonitorSpec.of([spec.context("hot")])
        built = {}
        for case in CASE_ORDER:
            if case == "vanilla":
                f = jax.jit(fresh_work())
                fn = lambda f=f: f(x0)
            elif case == "perfmon":
                mon = hc.global_monitor()
                mon.reset()
                with scalpel.breakpoint_mode(mon, scopes=["hot"]):
                    f = jax.jit(fresh_work())
                    f.lower(x0)
                fn = lambda f=f: f(x0)
            else:
                fn = monitored(spec if case == "all" else spec_sel)
            built[case] = fn
        results = {c: [] for c in CASE_ORDER}
        for _ in range(rounds):
            for case in CASE_ORDER:
                results[case].append(bench(built[case], iters=iters))
        base = None
        for case in CASE_ORDER:
            t = min(r["min_s"] for r in results[case])
            med = min(r["median_s"] for r in results[case])
            if case == "vanilla":
                base = t
            rows.append({
                "workload": f"calls={k}", "case": case,
                "median_ms": round(med * 1e3, 3),
                "min_ms": round(t * 1e3, 3),
                "overhead_pct": round(100 * (t - base) / base, 1),
                "per_call_us": round(1e6 * (t - base) / max(k, 1), 3),
            })
    return rows


# ---------------------------------------------------------------------------
# sparse-active-set plan sweep: per-set MomentPlans vs the union baseline
# ---------------------------------------------------------------------------

# Every multiplexed set needs a strict SUBSET of the union of channels —
# the configuration the probe-plan compiler exists for.  Union sweep: 6
# data channels per branch; per-set sweeps: 1 / 1 / 1 / 3 channels.
PLAN_SETS = (
    ("ACT_MAX_ABS:x",),
    ("ACT_ZERO_FRAC:x",),
    ("NAN_COUNT:x",),
    ("ACT_RMS:x", "ACT_MEAN_ABS:x", "MEAN:x"),
)


def _plan_spec(period: int = 1) -> MonitorSpec:
    sets = [[EventSpec.parse(s) for s in grp] for grp in PLAN_SETS]
    return MonitorSpec.of([
        ScopeContext.multiplexed("hot", sets, period=period)
    ])


def run_plan_sweep(probe_sizes=(1 << 14, 1 << 16), k: int = 24,
                   iters: int = 7, rounds: int = 3):
    """Per-set plans vs the union baseline on a sparse-active-set workload.

    A scope multiplexed over PLAN_SETS is called ``k`` times per jitted
    step; each call's active set sweeps only its own channels under
    ``plan_mode="per_set"`` and the full cross-set union under
    ``plan_mode="union"`` (the pre-plan hot path).  Identical schedules,
    identical counters (asserted allclose) — only the per-branch sweep
    width differs, which is exactly the cost the plan layer removes.
    """
    spec = _plan_spec()
    ctx = spec.context("hot")
    plans = plan_lib.compile_scope_plans(ctx, frozenset({"x"}))
    union_plans = plan_lib.compile_scope_plans(ctx, frozenset({"x"}), True)
    per_set_chans = [p.sweep_channel_count for p in plans.plans]
    union_chans = [p.sweep_channel_count for p in union_plans.plans]

    rows = []
    for n in probe_sizes:
        x0 = jnp.ones((n,)) * 1.5
        mp = MonitorParams.all_on(spec)

        def make(plan_mode):
            def work(x):
                for _ in range(k):
                    with scalpel.function("hot"):
                        x = x * 1.0001 + 0.1
                        scalpel.probe(x=x)
                return x

            def step(x, s, mp, plan_mode=plan_mode, work=work):
                with scalpel.collecting(spec, mp, s,
                                        plan_mode=plan_mode) as col:
                    y = work(x)
                return y, s.add(col.delta)

            f = jax.jit(step)
            s0 = CounterState.zeros(spec)
            return lambda f=f, s0=s0: f(x0, s0, mp)

        built = {m: make(m) for m in ("per_set", "union")}
        sa = built["per_set"]()[-1]
        sb = built["union"]()[-1]
        allclose = bool(
            np.allclose(np.asarray(sa.values), np.asarray(sb.values),
                        rtol=1e-4, atol=1e-6, equal_nan=True)
            and np.array_equal(np.asarray(sa.samples),
                               np.asarray(sb.samples))
        )
        results = {m: [] for m in built}
        for _ in range(rounds):
            for m in built:
                results[m].append(bench(built[m], iters=iters))
        mins = {m: min(r["min_s"] for r in results[m]) for m in built}
        workload = f"plan n={n}"
        rows.append({
            "workload": workload, "case": "plan_union",
            "min_ms": round(mins["union"] * 1e3, 3),
            "calls": k, "probe_size": n,
            "sweep_channels": union_chans,
        })
        rows.append({
            "workload": workload, "case": "plan_per_set",
            "min_ms": round(mins["per_set"] * 1e3, 3),
            "calls": k, "probe_size": n,
            "sweep_channels": per_set_chans,
            "union_min_ms": round(mins["union"] * 1e3, 3),
            "plan_gain_pct": round(
                100.0 * (mins["union"] - mins["per_set"]) / mins["union"], 1
            ),
            "plan_allclose": allclose,
        })
    return rows


def _plan_summary(rows: list[dict]) -> dict:
    """Aggregate per-set-plan vs union verdicts for the trajectory JSON."""
    per_set = [r for r in rows if r.get("case") == "plan_per_set"]
    return {
        "compared": len(per_set),
        "per_set_faster": sum(
            1 for r in per_set if r["min_ms"] < r["union_min_ms"]
        ),
        "strictly_faster": bool(per_set) and all(
            r["min_ms"] < r["union_min_ms"] for r in per_set
        ),
        "allclose_all": all(
            r.get("plan_allclose", False) for r in per_set
        ),
        "max_gain_pct": max(
            (r["plan_gain_pct"] for r in per_set), default=None
        ),
    }


# ---------------------------------------------------------------------------
# Monitor.wrap vs the manual collecting() path (functional API redesign)
# ---------------------------------------------------------------------------

def _monitor_spec() -> MonitorSpec:
    """One hot scope probing the six statistics + many narrow scopes: the
    padded [n_scopes, max_slots] block (96 lanes) is ~4.5x the compact
    dense footprint (21 lanes) — the per-step padded build/add the Monitor
    path deletes."""
    ctxs = [ScopeContext.exhaustive("hot",
                                    [EventSpec(e, "x") for e in PROBE_EVENTS])]
    ctxs += [
        ScopeContext.exhaustive(f"aux{i}", [EventSpec("MEAN", "x")])
        for i in range(15)
    ]
    return MonitorSpec.of(ctxs)


def run_monitor_sweep(probe_sizes=(1 << 12, 1 << 14), k: int = 16,
                      iters: int = 7, rounds: int = 3):
    """Functional ``Monitor.jit`` (one MonitorState pytree, compact
    counters end-to-end) vs the manual ``collecting()`` + ``state.add``
    baseline, on identical workloads at 16-64 KiB probes.

    The workload stacks ``k`` monitored layers inside
    ``scan_with_counters`` (the production shape) plus 15 narrow scopes:
    the wrapped step keeps the scan's compact carry compact through
    finalization and outputs only the dense footprint, while the manual
    path expands to — and accumulates in — the padded
    ``[n_scopes, max_slots]`` block every step.  Counters are asserted
    allclose after expanding the compact lanes back to the padded view.
    """
    import warnings

    spec = _monitor_spec()
    lay = plan_lib.spec_layout(spec)

    rows = []
    for n in probe_sizes:
        x0 = jnp.ones((n,)) * 1.5
        mp = MonitorParams.all_on(spec)

        def work(x):
            def layer(c, _):
                with scalpel.function("hot"):
                    c = c * 1.0001 + 0.1
                    scalpel.probe(x=c)
                return c, None

            x, _ = scalpel.scan_with_counters(layer, x, None, length=k)
            for i in range(15):
                with scalpel.function(f"aux{i}"):
                    scalpel.probe(x=x)
            return x

        # manual baseline: the deprecated hand-threaded path, threaded and
        # donated exactly like the pre-Monitor train loop donated its
        # counter-carrying TrainState
        def man_step(x, s, mp):
            with scalpel.collecting(spec, mp, s) as col:
                y = work(x)
            return y, s.add(col.delta)

        f_man = jax.jit(man_step, donate_argnums=(1,))
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            f_man(x0, CounterState.zeros(spec), mp)  # trace quietly

        # wrapped path: one MonitorState pytree, leaf-wise jit boundary,
        # state donated the same way (no telemetry ring here — the manual
        # baseline carries none)
        mon = scalpel.Monitor(spec, mp, counter_axes=())
        f_wrap = mon.jit(work, donate_state=True)

        def manual(s):
            return f_man(x0, s, mp)[-1]

        def wrapped(ms):
            return f_wrap(ms, x0)[-1]

        s_man = manual(CounterState.zeros(spec))
        ms1 = wrapped(mon.init())
        s_wrap = mon.counter_state(ms1)
        allclose = bool(
            np.allclose(np.asarray(s_wrap.values), np.asarray(s_man.values),
                        rtol=1e-4, atol=1e-6, equal_nan=True)
            and np.array_equal(np.asarray(s_wrap.samples),
                               np.asarray(s_man.samples))
            and np.array_equal(np.asarray(s_wrap.calls),
                               np.asarray(s_man.calls))
        )
        # Single steps are ~0.3-1 ms here and a shared CPU host jitters
        # per-dispatch by ±25%: time BLOCKS of back-to-back THREADED steps
        # (state carried call to call, one block_until_ready at the end,
        # donation live on both sides — the production steady state),
        # alternate the order every round, and judge on the median of
        # per-round block times — the long windows amortize scheduler
        # noise below the effect size.
        import statistics
        import time as time_lib

        def block_time(step, fresh, calls):
            s = fresh()
            for _ in range(3):
                s = step(s)
            jax.block_until_ready(s)
            t0 = time_lib.perf_counter()
            for _ in range(calls):
                s = step(s)
            jax.block_until_ready(s)
            return (time_lib.perf_counter() - t0) / calls

        built = {
            "monitor_manual": (manual, lambda: CounterState.zeros(spec)),
            "monitor_wrap": (wrapped, mon.init),
        }
        results = {m: [] for m in built}
        order = list(built)
        # steps here are sub-millisecond, so generous windows are cheap:
        # ~40-step blocks x 2-3x the requested rounds keeps the median
        # stable against minute-scale drift on a shared host
        block = max(40, iters * 6)
        for rnd in range(max(10, rounds * 2)):
            for m in (order if rnd % 2 == 0 else reversed(order)):
                step, fresh = built[m]
                results[m].append(block_time(step, fresh, block))
        med = {m: statistics.median(results[m]) for m in built}
        best = {m: min(results[m]) for m in built}
        # The VERDICT is the median of per-round PAIRED ratios: the two
        # blocks of a round run back-to-back, so minute-scale host drift
        # (which moves absolute medians by ±15% between trials) hits both
        # sides of each ratio almost equally and cancels.
        ratios = [w / m for w, m in zip(results["monitor_wrap"],
                                        results["monitor_manual"])]
        med_ratio = statistics.median(ratios)
        workload = f"monitor n={n}"
        kib = n * 4 // 1024
        rows.append({
            "workload": workload, "case": "monitor_manual",
            "min_ms": round(best["monitor_manual"] * 1e3, 3),
            "med_ms": round(med["monitor_manual"] * 1e3, 3),
            "calls": k, "probe_size": n, "probe_kib": kib,
            "steps_per_commit": 1,
            "state_lanes": spec.n_scopes * spec.max_slots,
        })
        rows.append({
            "workload": workload, "case": "monitor_wrap",
            "min_ms": round(best["monitor_wrap"] * 1e3, 3),
            "med_ms": round(med["monitor_wrap"] * 1e3, 3),
            "calls": k, "probe_size": n, "probe_kib": kib,
            "steps_per_commit": 1,
            "state_lanes": lay.total,
            "manual_med_ms": round(med["monitor_manual"] * 1e3, 3),
            "wrap_over_manual_ratio": round(med_ratio, 4),
            "wrap_gain_pct": round(100.0 * (1.0 - med_ratio), 1),
            "wrap_allclose": allclose,
        })
    return rows


def run_megastep_sweep(probe_size: int = 1 << 10, ks=(1, 4, 16),
                       steps_per_round: int = 64, rounds: int = 3):
    """Steps-per-commit sweep: ``mon.jit(work, steps_per_commit=K)`` — the
    K-step ``Monitor.scan`` megastep — against K=1, per-step, on a
    SHORT-step workload (single hot scope, 4 KiB probe, ~100µs steps).

    Short steps are where the per-call fixed cost — host dispatch, open a
    collector, commit, rebuild the state wrapper — dominates; the megastep
    amortizes all of it over K steps inside one ``lax.scan``.  Every case
    runs the same TOTAL number of monitored steps per timed block (a K=16
    block makes 16x fewer host dispatches, not less work), and the K>1
    counters are asserted exactly against K unrolled K=1 steps from the
    same init — fused and unrolled megasteps are the same program.
    """
    import statistics
    import time as time_lib

    spec = MonitorSpec.of([
        ScopeContext.exhaustive("hot",
                                [EventSpec(e, "x") for e in PROBE_EVENTS]),
    ])
    x0 = jnp.ones((probe_size,)) * 1.5
    mon = scalpel.Monitor(spec, counter_axes=())

    def work(x):
        with scalpel.function("hot"):
            x = x * 1.0001 + 0.1
            scalpel.probe(x=x)
        return x

    ks = tuple(sorted(set(ks)))
    assert 1 in ks and all(steps_per_round % K == 0 for K in ks)
    built = {K: mon.jit(work, steps_per_commit=K, donate_state=True)
             for K in ks}

    # exactness first: one K-step megastep == K unrolled commits
    plain = mon.jit(work)   # un-donated K=1 reference
    allclose = {}
    for K in ks:
        ms_a = mon.init()
        _, ms_a = built[K](ms_a, x0)
        ms_b, xb = mon.init(), x0
        for _ in range(K):
            xb, ms_b = plain(ms_b, xb)
        allclose[K] = bool(
            np.allclose(np.asarray(ms_a.values), np.asarray(ms_b.values),
                        rtol=1e-5, atol=1e-7)
            and np.array_equal(np.asarray(ms_a.samples),
                               np.asarray(ms_b.samples))
            and np.array_equal(np.asarray(ms_a.calls),
                               np.asarray(ms_b.calls))
            and int(ms_a.step) == int(ms_b.step) == K
        )

    def block_time(K) -> float:
        """Seconds per MONITORED STEP over a block of steps_per_round."""
        f, ms, x = built[K], mon.init(), x0
        for _ in range(2):
            x, ms = f(ms, x)
        jax.block_until_ready((x, ms.step))
        t0 = time_lib.perf_counter()
        for _ in range(steps_per_round // K):
            x, ms = f(ms, x)
        jax.block_until_ready((x, ms.step))
        return (time_lib.perf_counter() - t0) / steps_per_round

    results = {K: [] for K in ks}
    order = list(ks)
    for rnd in range(max(6, rounds * 2)):
        for K in (order if rnd % 2 == 0 else reversed(order)):
            results[K].append(block_time(K))
    med = {K: statistics.median(results[K]) for K in ks}

    rows = []
    for K in ks:
        row = {
            "workload": f"megastep n={probe_size}", "case": "monitor_scan",
            "steps_per_commit": K, "probe_size": probe_size,
            "per_step_us": round(med[K] * 1e6, 2),
            "min_per_step_us": round(min(results[K]) * 1e6, 2),
            "scan_allclose": allclose[K],
        }
        if K != 1:
            # paired per-round ratios: both block times of a round run
            # close together, so host drift cancels (same verdict rule as
            # the wrap-vs-manual sweep)
            ratios = [a / b for a, b in zip(results[K], results[1])]
            med_ratio = statistics.median(ratios)
            row["k1_per_step_us"] = round(med[1] * 1e6, 2)
            row["scan_over_k1_ratio"] = round(med_ratio, 4)
            row["scan_gain_pct"] = round(100.0 * (1.0 - med_ratio), 1)
        rows.append(row)
    return rows


def run_train_boundary_check(k: int = 4) -> list[dict]:
    """The leaf-wise TRAIN jit boundary: the compiled megastep takes the
    read-only ``MonitorParams``/``TelemetryParams`` as inputs but never
    outputs them (the host wrapper reattaches the caller's objects), and
    the ``TrainState`` is donated — checked on the smoke xlstm via object
    identity, compiled output-leaf accounting, and the HLO's
    input_output_alias table.
    """
    from repro.configs import model_config
    from repro.models.registry import Arch
    from repro.optim import OptConfig
    from repro.train.step import (TrainState, build_monitor_spec,
                                  make_train_megastep)

    cfg = model_config("xlstm_125m", smoke=True)
    arch = Arch(cfg)
    rng = np.random.default_rng(0)
    b, s = 2, 16
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32),
        "targets": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)),
                               jnp.int32),
    }
    spec = build_monitor_spec(arch, batch)
    mon = scalpel.Monitor(spec, counter_axes=())
    step = make_train_megastep(arch, OptConfig(), spec, monitor=mon)
    jit_step = mon.jit_wrapped(step, donate_argnums=(1,))  # donate tstate

    tstate = TrainState.create(arch, OptConfig(), jax.random.PRNGKey(0))
    ms = mon.init()
    batches = jax.tree.map(lambda v: jnp.stack([v] * k), batch)
    core_args = (ms.calls, ms.values, ms.samples, ms.sched_calls, ms.step,
                 ms.ring, ms.params, ms.tparams, batches, tstate)
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype), core_args)
    n_out_leaves = len(jax.tree.leaves(
        jax.eval_shape(jit_step._cjit, *abstract)))
    n_param_leaves = len(jax.tree.leaves((ms.params, ms.tparams)))
    hlo = jit_step._cjit.lower(*abstract).compile().as_text()
    tstate_donated = "input_output_alias" in hlo

    (tstate2, outs), ms2 = jit_step(ms, batches, tstate)
    return [{
        "workload": "train xlstm_125m smoke",
        "case": "train_megastep_boundary", "steps_per_commit": k,
        # the boundary claim: the SAME host objects come back — params
        # never leave (or re-enter through) the compiled program
        "params_reattached": bool(ms2.params is ms.params
                                  and ms2.tparams is ms.tparams),
        "compiled_out_leaves": n_out_leaves,
        "param_leaves_excluded": n_param_leaves,
        "tstate_donated": bool(tstate_donated),
        "loss_finite": bool(np.isfinite(np.asarray(outs["loss"])).all()),
        "steps_taken": int(ms2.step),
    }]


_PSUM_2DEV_SCRIPT = r"""
import json
import jax
import jax.numpy as jnp
import numpy as np

from repro import core as scalpel
from repro.core.context import EventSpec, MonitorSpec, ScopeContext
from repro.dist.partition import sharding_ctx
from repro.launch.mesh import auto_mesh

assert jax.device_count() == 2, jax.devices()
EVENTS = %r
spec = MonitorSpec.of([
    ScopeContext.exhaustive("hot", [EventSpec(e, "x") for e in EVENTS]),
])


def work(x):
    with scalpel.function("hot"):
        x = x * 1.0001 + 0.1
        scalpel.probe(x=x)
    return x


from jax.sharding import PartitionSpec as P

mon = scalpel.Monitor(spec)
mesh = auto_mesh((2,), ("data",))
with sharding_ctx(mesh):
    step = jax.jit(mon.shard_wrap(work, mesh, in_specs=P("data"),
                                  out_specs=P("data")))
    x = jnp.arange(8192.0) / 8192.0
    out, ms = step(mon.init(), x)

# per-shard manual baseline, summed on the host
mon1 = scalpel.Monitor(spec, counter_axes=())
w1 = mon1.wrap(work)
a = mon1.init()
b = mon1.init()
_, a = w1(a, x[:4096])
_, b = w1(b, x[4096:])
calls = np.asarray(a.calls) + np.asarray(b.calls)
values = np.asarray(a.values) + np.asarray(b.values)
samples = np.asarray(a.samples) + np.asarray(b.samples)
print(json.dumps({
    "devices": jax.device_count(),
    "counters_equal": bool(
        np.array_equal(np.asarray(ms.calls), calls)
        and np.array_equal(np.asarray(ms.values), values)
        and np.array_equal(np.asarray(ms.samples), samples)
    ),
    "psum_calls": np.asarray(ms.calls).tolist(),
    "shard_sum_calls": calls.tolist(),
}))
"""


def run_monitor_psum_check() -> list[dict]:
    """The 2-device forced-host acceptance check: a ``shard_wrap``-ped step
    on a (2,) data mesh must produce counters EXACTLY equal to the sum of
    two per-shard manual runs — ScALPEL reports become cluster-wide sums.

    Runs in a subprocess because the forced device count must be set
    before JAX initializes.
    """
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    # two simulated host devices: pinned to the CPU, off any accelerator
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in sys.path if p
    )
    proc = subprocess.run(
        [sys.executable, "-c", _PSUM_2DEV_SCRIPT % (PROBE_EVENTS,)],
        env=env, capture_output=True, text=True, timeout=600,
    )
    row = {"workload": "monitor 2dev", "case": "monitor_psum_2dev"}
    if proc.returncode != 0:
        row.update(error=proc.stderr[-1000:], counters_equal=False)
        return [row]
    row.update(json.loads(proc.stdout.strip().splitlines()[-1]))
    return [row]


def _monitor_summary(rows: list[dict]) -> dict:
    """Aggregate Monitor.wrap vs manual verdicts for the trajectory JSON."""
    wrap = [r for r in rows if r.get("case") == "monitor_wrap"]
    psum = [r for r in rows if r.get("case") == "monitor_psum_2dev"]
    scan = [r for r in rows if r.get("case") == "monitor_scan"]
    k16 = [r for r in scan if r.get("steps_per_commit") == 16]
    train = [r for r in rows if r.get("case") == "train_megastep_boundary"]
    return {
        # megastep (steps-per-commit) verdicts
        "megastep_k16_gain_pct": max(
            (r["scan_gain_pct"] for r in k16), default=None
        ),
        "megastep_speedup_15pct": bool(k16) and all(
            r["scan_over_k1_ratio"] <= 0.85 for r in k16
        ),
        "megastep_allclose": bool(scan) and all(
            r.get("scan_allclose", False) for r in scan
        ),
        "train_params_not_output": bool(train) and all(
            r.get("params_reattached", False) for r in train
        ),
        "train_tstate_donated": bool(train) and all(
            r.get("tstate_donated", False) for r in train
        ),
        "compared": len(wrap),
        "wrap_not_slower": sum(
            1 for r in wrap if r["wrap_over_manual_ratio"] <= 1.0
        ),
        # the honest verdict on a noisy shared host: the paired-ratio
        # medians repeatedly land within ~±3% of 1.0 (the wrapped step's
        # compiled module is strictly SMALLER — ~14% fewer HLO ops — but
        # both are dominated by the identical probe sweeps)
        "wrap_parity_3pct": all(
            r["wrap_over_manual_ratio"] <= 1.03 for r in wrap
        ),
        "allclose_all": all(r.get("wrap_allclose", False) for r in wrap),
        "max_gain_pct": max(
            (r["wrap_gain_pct"] for r in wrap), default=None
        ),
        "psum_2dev_equal": bool(psum) and all(
            r.get("counters_equal", False) for r in psum
        ),
    }


def run_readback_sweep(hook_everys=(1, 4), depths=(4, 16), steps: int = 32,
                       rounds: int = 3, k: int = 16, probe_size: int = 4096):
    """Readback-stall sweep (telemetry plane): synchronous full-CounterState
    ``device_get`` every ``hook_every`` steps vs an in-graph snapshot-ring
    append drained by the background telemetry thread.

    ``readback_sync`` is what the pre-telemetry runtime paid per report/adapt
    decision; ``readback_ring`` is the async plane.  The ring rows also check
    that the drained cumulative counters are allclose to the synchronous
    snapshot at the same step, and record how many ring slots the
    incremental (cursor-based) drain actually copied.
    """
    slots = [EventSpec(e, "x") for e in PROBE_EVENTS]
    spec = MonitorSpec.of([ScopeContext.exhaustive("hot", slots)])
    mp = MonitorParams.all_on(spec)
    x0 = jnp.ones((probe_size,))

    def work(x):
        for _ in range(k):
            with scalpel.function("hot"):
                x = x * 1.0001 + 0.1
                scalpel.probe(x=x)
        return x

    def step_sync(x, s, mp):
        with scalpel.collecting(spec, mp, s) as col:
            y = work(x)
        return y, s.add(col.delta)

    def step_ring(x, s, ring, step, mp, tp):
        with scalpel.collecting(spec, mp, s) as col:
            y = work(x)
        s2 = s.add(col.delta)
        step = step + 1  # stamp carried on device: no per-step host traffic
        return y, s2, telemetry_lib.ring_append(ring, s2, tp, step), step

    f_sync = jax.jit(step_sync)
    f_ring = jax.jit(step_ring)

    rows = []
    for he in hook_everys:

        def run_sync():
            x, s = x0, CounterState.zeros(spec)
            for i in range(1, steps + 1):
                x, s = f_sync(x, s, mp)
                if i % he == 0:
                    s_host = jax.tree.map(jax.device_get, s)  # the stall
            jax.block_until_ready(x)
            return s_host

        sync_state = run_sync()  # warmup (compile) + reference counters
        ts = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            run_sync()
            ts.append(time.perf_counter() - t0)
        sync_ms = min(ts) * 1e3
        rows.append({
            "workload": f"readback he={he}", "case": "readback_sync",
            "hook_every": he, "ring_depth": 0, "steps": steps,
            "min_ms": round(sync_ms, 3),
            "per_step_us": round(1e3 * sync_ms / steps, 3),
        })

        for depth in depths:
            plane = telemetry_lib.TelemetryPlane(
                spec, depth=depth, cadence=he, interval_s=0.002,
            )
            drained = []
            plane.add_sink(
                telemetry_lib.CallbackSink(lambda s: drained.append(s.step))
            )

            def run_ring():
                x, s = x0, CounterState.zeros(spec)
                ring = plane.make_ring()
                i = jnp.zeros((), jnp.int32)
                for _ in range(steps):
                    x, s, ring, i = f_ring(x, s, ring, i, mp, plane.params)
                    plane.publish(ring)  # ref swap; drain is off-thread
                jax.block_until_ready(x)
                return s

            run_ring()  # warmup (compile per ring depth)
            ts = []
            for _ in range(rounds):
                t0 = time.perf_counter()
                run_ring()
                ts.append(time.perf_counter() - t0)
            ring_ms = min(ts) * 1e3
            plane.flush()
            last = plane.last_state
            ok = last is not None and bool(
                np.allclose(np.asarray(last.values),
                            np.asarray(sync_state.values),
                            rtol=1e-5, atol=1e-7)
                and np.array_equal(np.asarray(last.calls),
                                   np.asarray(sync_state.calls))
            )
            slots_copied = plane.slots_copied
            plane.close()
            rows.append({
                "workload": f"readback he={he}", "case": "readback_ring",
                "hook_every": he, "ring_depth": depth, "steps": steps,
                "min_ms": round(ring_ms, 3),
                "per_step_us": round(1e3 * ring_ms / steps, 3),
                "sync_min_ms": round(sync_ms, 3),
                "readback_gain_pct": round(
                    100.0 * (sync_ms - ring_ms) / sync_ms, 1),
                "readback_allclose": ok,
                "snapshots_drained": len(drained),
                "snapshots_dropped": plane.dropped_snapshots,
                "ring_slots_copied": slots_copied,
            })
    return rows


def _readback_summary(rows: list[dict]) -> dict:
    """Aggregate sync-vs-ring verdicts for the trajectory JSON."""
    ring = [r for r in rows if r.get("case") == "readback_ring"]
    at1 = [r for r in ring if r.get("hook_every") == 1]
    return {
        "compared": len(ring),
        "ring_faster": sum(
            1 for r in ring if r["min_ms"] < r["sync_min_ms"]
        ),
        "ring_faster_at_hook1": bool(at1) and all(
            r["min_ms"] < r["sync_min_ms"] for r in at1
        ),
        "allclose_all": all(r.get("readback_allclose", False) for r in ring),
        "max_gain_pct": max(
            (r["readback_gain_pct"] for r in ring), default=None
        ),
    }


# ---------------------------------------------------------------------------
# adaptive-controller sweep: the closed loop's steady-state overhead
# ---------------------------------------------------------------------------

ADAPTIVE_EVENTS = ("ACT_RMS", "ACT_ZERO_FRAC", "NAN_COUNT", "INF_COUNT")


def _adaptive_spec(n_aux: int = 4) -> MonitorSpec:
    scopes = ("layer/attn", "layer/mlp") + tuple(
        f"aux{i}" for i in range(n_aux))
    return MonitorSpec.of([
        ScopeContext.exhaustive(s, [EventSpec(e, "x")
                                    for e in ADAPTIVE_EVENTS])
        for s in scopes
    ])


def run_adaptive_sweep(probe_size: int = 1 << 15, settle_steps: int = 48,
                       block: int = 32, rounds: int = 6,
                       nan_step: int = 2) -> list[dict]:
    """The closed adaptive loop (core/adaptive.py), three ways on one
    monitored workload with CONSTANT probed tensors:

      adaptive_off   MonitorParams.all_off + cadence 0 — the interception-
                     only floor the controller's sentinel rung approaches
      adaptive_ctl   AdaptiveController on; a NaN injected into ONE scope
                     at a known step during a deterministic settle phase
                     (escalate → wide → decay back to sentinel), then the
                     steady state is timed
      adaptive_wide  everything all-on at cadence 1, controller off — the
                     ceiling, and the counter-exactness reference

    Timed paired round-robin (blocks of back-to-back steps, median of
    per-round ratios) like the monitor sweep.  The row records the
    acceptance criteria: NaN localized to the right scope within K=5
    drained snapshots, steady-state ctl overhead vs off, and anomaly-free
    scopes' estimates allclose (+ calls equal) vs the always-wide run —
    constant probed tensors make the estimates invariant to WHICH calls
    each schedule sampled.
    """
    import statistics

    from repro.core.adaptive import AdaptiveConfig
    from repro.testing.faults import FaultInjector, TensorFault

    spec = _adaptive_spec()
    fault_scope = "layer/attn"
    k_drains = 5
    # the NaN must land while scopes still monitor: quiet scopes hibernate
    # at drain quiet_drains (sentinel scopes are blind to tensor anomalies
    # by design), so the fault fires early in the settle phase
    quiet_drains = 4
    assert nan_step + 1 < quiet_drains, (nan_step, quiet_drains)
    # a workload body heavy enough (~0.5ms on CPU) that per-dispatch host
    # jitter doesn't dominate the steady-state ratio being measured
    w_mix = jax.random.normal(jax.random.PRNGKey(3), (256, 256)) * 0.05

    def build(kind: str):
        runtime = scalpel.ScalpelRuntime(spec, hook_every=1)
        ctl = None
        injector = None
        if kind == "ctl":
            ctl = runtime.attach_controller(AdaptiveConfig(
                quiet_drains=quiet_drains, cooldown_drains=2,
                warmup_drains=2,
                # budget parked through the settle phase: its flush-per-
                # step drains are synchronous by construction, so the
                # measured drain fraction there is an artifact; the budget
                # is enabled for the steady state below
                escalated_cadence=1, overhead_budget=1e9,
                # the wake path is not under test here, and the timed
                # blocks run much faster than the flushing settle steps —
                # an honest step-time detector would read that as outliers
                step_time_sigma=1e9,
            ))
            injector = FaultInjector(
                [TensorFault(fault_scope, "x", step=nan_step)])
        elif kind == "off":
            runtime.set_params(MonitorParams.all_off(spec))
            runtime.telemetry.set_cadence(0)
        mon = scalpel.Monitor(spec, telemetry=runtime.telemetry,
                              counter_axes=())
        const = jnp.full((probe_size,), 1.5)

        def work(x, step):
            for _ in range(2):
                x = jnp.tanh(x @ w_mix)
            for s in spec.scopes:
                v = const
                if injector is not None:
                    v = injector.corrupt(s, "x", step, v)
                with scalpel.function(s):
                    scalpel.probe(x=v)
            return x, step + 1

        fn = mon.jit(work)
        st = {"m": mon.init(), "x": jnp.ones((128, 256)),
              "s": jnp.zeros((), jnp.int32)}

        def step(flush: bool = False):
            st["m"] = mon.sync(st["m"], runtime=runtime)
            (st["x"], st["s"]), st["m"] = fn(st["m"], st["x"], st["s"])
            runtime.on_step(st["m"].counters, ring=st["m"].ring)
            if flush:
                runtime.flush()

        return {"step": step, "state": st, "mon": mon, "runtime": runtime,
                "ctl": ctl}

    cases = {kind: build(kind) for kind in ("off", "ctl", "wide")}
    # settle: deterministic controller ticks (flush per step) — the fault
    # fires, the ladder runs its full cycle, quiet scopes hibernate
    for kind, c in cases.items():
        for _ in range(settle_steps):
            c["step"](flush=True)
        jax.block_until_ready(c["state"]["x"])

    # steady-state warm-in, every case (equal step totals keep the calls
    # comparison exact): the controller's budget loop is enabled here, fed
    # by the REAL background-drain overhead — it ramps the cadence while
    # the settle-phase EWMA drains off, then halves back to the floor
    import dataclasses as _dc

    ctl_obj = cases["ctl"]["ctl"]
    ctl_obj.cfg = _dc.replace(ctl_obj.cfg, overhead_budget=0.05)
    for c in cases.values():
        for _ in range(4 * block):
            c["step"]()
        jax.block_until_ready(c["state"]["x"])

    def block_time(c) -> float:
        t0 = time.perf_counter()
        for _ in range(block):
            c["step"]()
        jax.block_until_ready(c["state"]["x"])
        return (time.perf_counter() - t0) / block

    order = list(cases)
    times = {kind: [] for kind in cases}
    for rnd in range(rounds):
        for kind in (order if rnd % 2 == 0 else reversed(order)):
            times[kind].append(block_time(cases[kind]))
    med = {kind: statistics.median(ts) for kind, ts in times.items()}
    ratio_ctl = statistics.median(
        [c / o for c, o in zip(times["ctl"], times["off"])])
    ratio_wide = statistics.median(
        [w / o for w, o in zip(times["wide"], times["off"])])

    ctl = cases["ctl"]["ctl"]
    wide_t = [t for t in ctl.transitions if t.to == "wide"]
    localized = bool(
        wide_t and all(t.scope == fault_scope for t in wide_t)
        and wide_t[0].step - nan_step <= k_drains
    )
    levels = ctl.levels
    steady_sentinel = all(lv == "sentinel" for lv in levels.values())

    # counter exactness: anomaly-free scopes, ctl run vs always-wide run
    est_ctl = cases["ctl"]["mon"].estimates(cases["ctl"]["state"]["m"])
    est_wide = cases["wide"]["mon"].estimates(cases["wide"]["state"]["m"])
    counters_ok = True
    for scope in spec.scopes:
        if scope == fault_scope:
            continue
        for slot_id, vw in est_wide[scope].items():
            vc = est_ctl[scope][slot_id]
            if np.isfinite(vw) != np.isfinite(vc) or (
                    np.isfinite(vw)
                    and not np.isclose(vc, vw, rtol=1e-6)):
                counters_ok = False
    calls_equal = bool(np.array_equal(
        np.asarray(cases["ctl"]["state"]["m"].calls),
        np.asarray(cases["wide"]["state"]["m"].calls),
    ))

    rows = [{
        "workload": f"adaptive n={probe_size}", "case": "adaptive_off",
        "per_step_us": round(med["off"] * 1e6, 2),
        "min_ms": round(min(times["off"]) * 1e3 * block, 3),
        "steps": settle_steps + rounds * block,
    }, {
        "workload": f"adaptive n={probe_size}", "case": "adaptive_ctl",
        "per_step_us": round(med["ctl"] * 1e6, 2),
        "min_ms": round(min(times["ctl"]) * 1e3 * block, 3),
        "steps": settle_steps + rounds * block,
        "ctl_over_off_ratio": round(ratio_ctl, 4),
        "ctl_within_5pct": bool(ratio_ctl <= 1.05),
        "nan_localized_k5": localized,
        "steady_levels_sentinel": steady_sentinel,
        "final_cadence": cases["ctl"]["runtime"].telemetry.cadence,
        "escalations": ctl.stats["escalations"],
        "deescalations": ctl.stats["deescalations"],
        "plan_swaps": ctl.stats["plan_swaps"],
        "overhead_frac": round(ctl.overhead_frac, 4),
        "counters_allclose_vs_wide": counters_ok,
        "calls_equal_vs_wide": calls_equal,
    }, {
        "workload": f"adaptive n={probe_size}", "case": "adaptive_wide",
        "per_step_us": round(med["wide"] * 1e6, 2),
        "min_ms": round(min(times["wide"]) * 1e3 * block, 3),
        "steps": settle_steps + rounds * block,
        "wide_over_off_ratio": round(ratio_wide, 4),
    }]
    for c in cases.values():
        c["runtime"].close()
    return rows


def _adaptive_summary(rows: list[dict]) -> dict:
    """Aggregate adaptive-loop verdicts for the trajectory JSON."""
    ctl = [r for r in rows if r.get("case") == "adaptive_ctl"]
    return {
        "compared": len(ctl),
        "nan_localized_k5": bool(ctl) and all(
            r.get("nan_localized_k5", False) for r in ctl),
        "ctl_within_5pct": bool(ctl) and all(
            r.get("ctl_within_5pct", False) for r in ctl),
        "counters_allclose": bool(ctl) and all(
            r.get("counters_allclose_vs_wide", False)
            and r.get("calls_equal_vs_wide", False) for r in ctl),
        "steady_levels_sentinel": bool(ctl) and all(
            r.get("steady_levels_sentinel", False) for r in ctl),
        "max_ctl_over_off_ratio": max(
            (r["ctl_over_off_ratio"] for r in ctl), default=None),
    }


# ---------------------------------------------------------------------------
# plan-dedup compile sweep: identical multiplexed sets share one branch body
# ---------------------------------------------------------------------------

def run_plan_dedup_sweep(m: int = 6, k: int = 8, probe_size: int = 4096,
                         rounds: int = 2) -> list[dict]:
    """Compile-time cost of the deduplicated branch table: a scope
    multiplexed over ``m`` IDENTICAL event sets traces ONE shared branch
    body (``ScopePlans.bodies``), while ``m`` DISTINCT sets trace ``m``.
    Duplicate (event, tensor) slots across sets are legal — event_sets only
    partition slot indices — so the dup spec is a real configuration (the
    same probe at every multiplex phase), not a degenerate one.

    Measured: jit trace (``lower``) + XLA compile wall time of an identical
    monitored step over each spec, fresh function objects per round (the
    jit cache keys on identity, so every round re-traces).
    """
    def spec_of(kind: str) -> MonitorSpec:
        if kind == "dup":
            sets = [[EventSpec("ACT_RMS", "x")] for _ in range(m)]
        else:
            sets = [[EventSpec(e, "x")] for e in PROBE_EVENTS[:m]]
        return MonitorSpec.of(
            [ScopeContext.multiplexed("hot", sets, period=1)])

    x0 = jnp.ones((probe_size,))
    rows = []
    for kind in ("dup", "distinct"):
        spec = spec_of(kind)
        plans = plan_lib.compile_scope_plans(spec.context("hot"),
                                             frozenset({"x"}))
        mon = scalpel.Monitor(spec, counter_axes=())
        lowers, compiles = [], []
        for _ in range(rounds):
            def work(x):
                for _ in range(k):
                    with scalpel.function("hot"):
                        x = x * 1.0001 + 0.1
                        scalpel.probe(x=x)
                return x

            t0 = time.perf_counter()
            lowered = jax.jit(mon.wrap(work)).lower(mon.init(), x0)
            t1 = time.perf_counter()
            lowered.compile()
            t2 = time.perf_counter()
            lowers.append(t1 - t0)
            compiles.append(t2 - t1)
        rows.append({
            "workload": f"plan_dedup m={m}", "case": f"plan_dedup_{kind}",
            "n_sets": plans.n_sets, "n_branches": plans.n_branches,
            "plans_deduped": plans.plans_deduped,
            "lower_ms": round(min(lowers) * 1e3, 1),
            "compile_ms": round(min(compiles) * 1e3, 1),
            "min_ms": round((min(lowers) + min(compiles)) * 1e3, 1),
        })
    dup, dis = rows
    dup["distinct_min_ms"] = dis["min_ms"]
    dup["dedup_gain_pct"] = round(
        100.0 * (dis["min_ms"] - dup["min_ms"]) / max(dis["min_ms"], 1e-9),
        1)
    return rows


# ---------------------------------------------------------------------------
# continuous-batching serve sweep: lane-packed megastep engine vs serial
# ---------------------------------------------------------------------------

def run_serve_throughput_sweep(streams=(1, 4, 16), prompt_len: int = 16,
                               max_new: int = 32, n_lanes: int = 16,
                               steps_per_commit: int = 8) -> list[dict]:
    """Continuous-batching serve engine (serve/driver.py) vs the serial
    per-request oracle, at increasing concurrent-stream counts.

    serve_serial      one static Engine, requests generated back to back —
                      one dispatch + host sample per token (the pre-lane
                      engine; per-request wall times summed, the counter
                      harvest between requests untimed).
    serve_continuous  ContinuousEngine: all streams submitted up front,
                      lane-packed K-token megasteps with on-device
                      sampling, tokens egressing through the telemetry
                      token ring a megastep behind.

    Exactness is asserted IN-SWEEP, not just reported: greedy tokens must
    be bitwise equal to the serial oracle per stream, and each request's
    per-lane counter attribution must match the serial engine's
    before/after counter delta for the same request.
    """
    from repro.serve.engine import ContinuousEngine, Engine, ServeConfig

    cfg = model_config("xlstm_125m", smoke=True)
    arch = Arch(cfg)
    params = arch.init(jax.random.PRNGKey(0))
    scfg = ServeConfig(cache_len=prompt_len + max_new + 16,
                       max_new_tokens=max_new, temperature=0.0,
                       n_lanes=n_lanes, steps_per_commit=steps_per_commit)
    serial = Engine(arch, params, scfg)
    cont = ContinuousEngine(arch, params, scfg, spec=serial.spec)
    n_max = max(streams)
    prompts = [
        jax.random.randint(jax.random.PRNGKey(100 + i), (1, prompt_len),
                           0, cfg.vocab)
        for i in range(n_max)
    ]

    def counters_np(eng):
        c = eng.counters
        return (np.asarray(c.calls).copy(), np.asarray(c.values).copy(),
                np.asarray(c.samples).copy())

    # warmup both paths (compile prefill/decode/admit/megastep once; the
    # engines persist across sweep points, so nothing recompiles below)
    serial.generate({"tokens": prompts[0]})
    cont.submit(prompts[0])
    cont.run()

    rows = []
    for n in streams:
        want, serial_ctrs, serial_s = [], [], 0.0
        for p in prompts[:n]:
            before = counters_np(serial)
            t0 = time.perf_counter()
            out, _ = serial.generate({"tokens": p})
            serial_s += time.perf_counter() - t0
            after = counters_np(serial)  # untimed harvest between requests
            want.append(np.asarray(out)[0])
            serial_ctrs.append(tuple(a - b for a, b in zip(after, before)))
        toks = n * max_new
        mega0 = cont.stats["megasteps"]
        t0 = time.perf_counter()
        rids = [cont.submit(p) for p in prompts[:n]]
        res = cont.run()
        cont_s = time.perf_counter() - t0
        tokens_exact = all(
            np.array_equal(res[r].tokens, w) for r, w in zip(rids, want))
        counters_allclose = all(
            np.array_equal(np.asarray(res[r].counters.calls), sc[0])
            and np.allclose(np.asarray(res[r].counters.values), sc[1],
                            rtol=1e-4, atol=1e-6)
            and np.array_equal(np.asarray(res[r].counters.samples), sc[2])
            for r, sc in zip(rids, serial_ctrs)
        )
        workload = f"serve N={n}"
        rows.append({
            "workload": workload, "case": "serve_serial", "streams": n,
            "toks": toks, "min_ms": round(serial_s * 1e3, 1),
            "toks_per_s": round(toks / serial_s, 1),
            "n_lanes": 1, "steps_per_commit": 1,
        })
        rows.append({
            "workload": workload, "case": "serve_continuous", "streams": n,
            "toks": toks, "min_ms": round(cont_s * 1e3, 1),
            "toks_per_s": round(toks / cont_s, 1),
            "n_lanes": n_lanes, "steps_per_commit": steps_per_commit,
            "megasteps": cont.stats["megasteps"] - mega0,
            "serial_toks_per_s": round(toks / serial_s, 1),
            "speedup_x": round(serial_s / cont_s, 2),
            "tokens_exact": bool(tokens_exact),
            "counters_allclose": bool(counters_allclose),
            "dropped_tokens": cont.runtime.telemetry.dropped_tokens,
        })
    return rows


_SERVE_SHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import time
import jax
import numpy as np

from repro.configs import model_config
from repro.models.registry import Arch
from repro.serve.engine import ContinuousEngine, ServeConfig

assert len(jax.devices()) == 2
N_LANES = %d
MAX_NEW = %d
N_REQ = %d

arch = Arch(model_config("xlstm_125m", smoke=True))
params = arch.init(jax.random.PRNGKey(0))
prompts = [np.asarray(jax.random.randint(jax.random.PRNGKey(100 + i),
                                         (1, 16), 0, arch.cfg.vocab))
           for i in range(N_REQ)]


def run(shards, spec=None):
    cfg = ServeConfig(cache_len=16 + MAX_NEW + 16, max_new_tokens=MAX_NEW,
                      n_lanes=N_LANES, steps_per_commit=8,
                      lane_shards=shards)
    eng = ContinuousEngine(arch, params, cfg, spec=spec)
    # warmup: compile all three programs before the timed run
    eng.submit(prompts[0])
    eng.run()
    t0 = time.perf_counter()
    rids = [eng.submit(p) for p in prompts]
    res = eng.run()
    dt = time.perf_counter() - t0
    return eng, [res[r] for r in rids], dt

e1, res1, dt1 = run(1)
e2, res2, dt2 = run(2, spec=e1.spec)

tokens_exact = all(np.array_equal(a.tokens, b.tokens)
                   for a, b in zip(res1, res2))
counters_exact = all(
    np.array_equal(np.asarray(a.counters.calls),
                   np.asarray(b.counters.calls))
    and np.array_equal(np.asarray(a.counters.samples),
                       np.asarray(b.counters.samples))
    for a, b in zip(res1, res2))
values_allclose = all(
    np.allclose(np.asarray(a.counters.values),
                np.asarray(b.counters.values), rtol=1e-5, atol=1e-6)
    for a, b in zip(res1, res2))

toks = N_REQ * MAX_NEW
print(json.dumps({
    "toks": toks,
    "ms_1shard": round(dt1 * 1e3, 1),
    "ms_2shard": round(dt2 * 1e3, 1),
    "toks_per_s_1shard": round(toks / dt1, 1),
    "toks_per_s_2shard": round(toks / dt2, 1),
    "tokens_exact": bool(tokens_exact),
    "counters_exact": bool(counters_exact),
    "values_allclose": bool(values_allclose),
    "megastep_traces": e2.compile_stats()["megastep_traces"],
}))
"""


def run_serve_shard_sweep(n_lanes: int = 8, max_new: int = 32,
                          n_req: int = 12) -> list[dict]:
    """Lane-sharded serve engine on a forced 2-host-device mesh: the SAME
    total lane count split 1 vs 2 ways (``ServeConfig.lane_shards``), all
    other knobs equal.

    The contract is exactness, not host-CPU speed (two forced host devices
    share the same cores — tokens/s parity is all one can ask): greedy
    tokens bitwise equal across shardings, integer counters (calls,
    samples) exactly equal, values allclose under psum reassociation.

    Runs in a subprocess because the forced device count must be set
    before JAX initializes.
    """
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    # two simulated host devices: pinned to the CPU, off any accelerator
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.pathsep.join(p for p in sys.path if p)
    proc = subprocess.run(
        [sys.executable, "-c",
         _SERVE_SHARD_SCRIPT % (n_lanes, max_new, n_req)],
        env=env, capture_output=True, text=True, timeout=1200,
    )
    row = {"workload": f"serve shard N={n_req}", "case": "serve_shard",
           "streams": n_req, "n_lanes": n_lanes, "lane_shards": 2}
    if proc.returncode != 0:
        row.update(error=proc.stderr[-1000:], tokens_exact=False,
                   counters_exact=False)
        return [row]
    row.update(json.loads(proc.stdout.strip().splitlines()[-1]))
    row["min_ms"] = row.get("ms_2shard")
    return [row]


def run_prefill_bucket_sweep(n_req: int = 100, max_new: int = 4,
                             n_lanes: int = 8) -> list[dict]:
    """Prompt-length bucketing vs per-length re-tracing, end to end.

    ``n_req`` requests with prompt lengths cycling over every value in
    [3, 40] hit the admission path of two engines: one with pow2 buckets
    (compiles once per BUCKET), one with exact-length prefill (compiles
    once per DISTINCT LENGTH).  Both runs include compile time — that is
    the point: the bucketed engine's trace count is bounded by its bucket
    count, so it amortizes, while the baseline pays XLA per length.
    """
    from repro.serve.engine import ContinuousEngine, ServeConfig

    cfg = model_config("xlstm_125m", smoke=True)
    arch = Arch(cfg)
    params = arch.init(jax.random.PRNGKey(0))
    lengths = [3 + (i % 38) for i in range(n_req)]
    prompts = [
        jax.random.randint(jax.random.PRNGKey(200 + i), (1, s), 0,
                           cfg.vocab)
        for i, s in enumerate(lengths)
    ]
    scfg = dict(cache_len=64 + max_new + 16, max_new_tokens=max_new,
                n_lanes=n_lanes, steps_per_commit=4)

    def run(buckets):
        eng = ContinuousEngine(
            arch, params,
            ServeConfig(prefill_buckets=buckets, **scfg))
        t0 = time.perf_counter()
        rids = [eng.submit(p) for p in prompts]
        res = eng.run()
        dt = time.perf_counter() - t0
        return eng, res, rids, dt

    import warnings

    with warnings.catch_warnings():
        # the exact-length baseline intentionally trips the re-trace alarm
        warnings.simplefilter("ignore", RuntimeWarning)
        b_eng, b_res, b_rids, b_dt = run(None)
    eng, res, rids, dt = run("pow2")
    tokens_exact = all(
        np.array_equal(res[r].tokens, b_res[br].tokens)
        for r, br in zip(rids, b_rids))
    cs, bcs = eng.compile_stats(), b_eng.compile_stats()
    n_buckets = len(cs["buckets_used"])
    toks = n_req * max_new
    workload = f"prefill bucket N={n_req}"
    rows = [{
        "workload": workload, "case": "prefill_bucket_baseline",
        "streams": n_req, "toks": toks, "min_ms": round(b_dt * 1e3, 1),
        "toks_per_s": round(toks / b_dt, 1),
        "prefill_traces": bcs["prefill_traces"],
        "distinct_lengths": len(set(lengths)),
    }, {
        "workload": workload, "case": "prefill_bucket",
        "streams": n_req, "toks": toks, "min_ms": round(dt * 1e3, 1),
        "toks_per_s": round(toks / dt, 1),
        "prefill_traces": cs["prefill_traces"],
        "n_buckets": n_buckets,
        "buckets_used": cs["buckets_used"],
        "pad_waste_frac": round(cs["pad_waste_frac"], 4),
        "traces_bounded": bool(cs["prefill_traces"] <= n_buckets),
        "speedup_x": round(b_dt / dt, 2),
        "tokens_exact": bool(tokens_exact),
    }]
    return rows


def _serve_summary(rows: list[dict]) -> dict:
    """Aggregate continuous-vs-serial serve verdicts for the trajectory
    JSON (the acceptance bar: >=3x at the 16-stream point, exact tokens,
    allclose per-request counters)."""
    cont = [r for r in rows if r.get("case") == "serve_continuous"]
    wide = [r for r in cont if r.get("streams", 0) >= 16]
    shard = [r for r in rows if r.get("case") == "serve_shard"]
    bucket = [r for r in rows if r.get("case") == "prefill_bucket"]
    return {
        "compared": len(cont),
        "tokens_exact_all": bool(cont) and all(
            r.get("tokens_exact", False) for r in cont),
        "counters_allclose_all": bool(cont) and all(
            r.get("counters_allclose", False) for r in cont),
        "no_dropped_tokens": all(
            r.get("dropped_tokens", 0) == 0 for r in cont),
        "speedup_at_16": max(
            (r["speedup_x"] for r in wide), default=None),
        "speedup_3x_at_16": bool(wide) and all(
            r["speedup_x"] >= 3.0 for r in wide),
        # lane-sharding: 2-shard mesh == single device, exactly
        "shard_tokens_exact": bool(shard) and all(
            r.get("tokens_exact", False) for r in shard),
        "shard_counters_exact": bool(shard) and all(
            r.get("counters_exact", False) for r in shard),
        # bucketing: traces bounded by buckets, >=2x vs per-length retrace
        "bucket_traces_bounded": bool(bucket) and all(
            r.get("traces_bounded", False) for r in bucket),
        "bucket_tokens_exact": bool(bucket) and all(
            r.get("tokens_exact", False) for r in bucket),
        "bucket_speedup_x": max(
            (r["speedup_x"] for r in bucket), default=None),
        "bucket_speedup_2x": bool(bucket) and all(
            r["speedup_x"] >= 2.0 for r in bucket),
    }


# ---------------------------------------------------------------------------
# fleet telemetry sweep: encode cost per drain, aggregator merge throughput,
# wire compactness vs raw JSONL
# ---------------------------------------------------------------------------

def run_fleet_agg_sweep(host_counts=(4, 16, 64), frames_per_host: int = 200,
                        steps: int = 48) -> list[dict]:
    """The fleet tier (repro.telemetry), three measurements:

    fleet_encode  a live monitored workload with a ``FleetAgent`` sink on
                  the plane: the agent's frame-encode time as a fraction of
                  total drain time (the acceptance bar: < 5% — shipping a
                  drained delta must be nearly free next to draining it)
    fleet_merge   aggregator fan-in throughput over pre-encoded frames from
                  4/16/64 simulated hosts (decode + fingerprint check +
                  sum + reservoir per frame), with an f64 exactness check
                  of the merged sums against the encoding-side oracle
    fleet_wire    bytes per delta frame vs the same payload as raw JSONL
                  (what shipping per-host JsonlSink lines would cost)
    """
    import json as json_lib

    from repro.telemetry import wire
    from repro.telemetry.aggregator import Aggregator

    spec = _adaptive_spec()           # 6 scopes x 4 events = 24 lanes
    lay = plan_lib.spec_layout(spec)
    rng = np.random.default_rng(0)
    rows = []

    # -- encode cost per drain, on a live monitored workload ---------------
    agg = Aggregator(("127.0.0.1", 0), node_id="bench").serve()
    runtime = scalpel.ScalpelRuntime(spec, hook_every=1)
    agent = runtime.attach_fleet_agent("bench-host", agg.address)
    mon = scalpel.Monitor(spec, telemetry=runtime.telemetry,
                          counter_axes=())
    const = jnp.full((1 << 14,), 1.5)

    def work(x):
        for s in spec.scopes:
            with scalpel.function(s):
                scalpel.probe(x=const)
        return x * 1.0001

    fn = mon.jit(work)
    ms, x = mon.init(), jnp.ones((256,))

    # shadow capture of every drained payload: the codec measurement
    # below re-encodes EXACTLY what the agent shipped
    payloads = []

    def _capture(snap):
        d = snap.delta
        payloads.append((np.asarray(d.calls).reshape(-1).copy(),
                         np.asarray(d.values, np.float32).reshape(-1)
                         .copy(),
                         np.asarray(d.samples).reshape(-1).copy(),
                         int(snap.step)))

    runtime.telemetry.add_sink(scalpel.CallbackSink(_capture))

    def run(n):
        nonlocal ms, x
        for _ in range(n):
            ms = mon.sync(ms, runtime=runtime)
            x, ms = fn(ms, x)
            runtime.on_step(ms.counters, ring=ms.ring)
            runtime.flush()

    # steady state only: the first few frames pay one-time costs (compile,
    # codec/struct caches) that a long-running host never sees again
    run(6)
    agent.flush(2.0)        # lazy sender-side encodes must have run
    drain0 = runtime.telemetry.drain_seconds
    st0 = agent.stats()
    run(steps)
    agent.flush(2.0)
    drain_s = runtime.telemetry.drain_seconds - drain0
    st = agent.stats()
    runtime.close()
    agg.close()
    emit_s = st["emit_seconds"] - st0["emit_seconds"]
    frames = st["frames_encoded"] - st0["frames_encoded"]

    # codec cost per frame: tight-loop re-encode of the captured drained
    # payloads (the encode runs on the link's SENDER thread in
    # production, off the drain path entirely — what rides the drain is
    # the emit row below)
    sample = payloads[-max(frames, 1):]
    reps = max(1, 400 // max(len(sample), 1))
    enc = wire.DeltaStreamEncoder("bench-host", spec.fingerprint)
    best = float("inf")
    for _ in range(5):      # min-of-5: preemption noise only ever adds
        t0 = time.perf_counter()
        for _ in range(reps):
            for i, (c, v, smp, stp) in enumerate(sample):
                enc.encode(c, v, smp, seq=i, step_lo=stp - 1, step_hi=stp)
        best = min(best, time.perf_counter() - t0)
    encode_per_frame = best / reps / max(len(sample), 1)
    drain_per_frame = drain_s / max(frames, 1)
    encode_s = encode_per_frame * frames
    rows.append({
        "workload": "fleet encode", "case": "fleet_encode",
        "frames": frames, "lanes": lay.total, "steps": steps,
        "drain_ms": round(drain_s * 1e3, 3),
        "drain_us_per_frame": round(1e6 * drain_per_frame, 2),
        "encode_us_per_frame": round(1e6 * encode_per_frame, 2),
        "encode_frac_pct": round(
            100 * encode_per_frame / max(drain_per_frame, 1e-12), 2),
        "encode_under_5pct": bool(
            encode_per_frame <= 0.05 * drain_per_frame),
        # what the agent sink actually costs the drain thread per frame
        # (normalize + lazy enqueue; the encode itself is deferred)
        "emit_us_per_frame": round(1e6 * emit_s / max(frames, 1), 2),
        "emit_frac_pct": round(100 * emit_s / max(drain_s, 1e-12), 2),
        # sender-thread codec CPU as accounted live by the agent
        "sender_encode_us_per_frame": round(
            1e6 * (st["encode_seconds"] - st0["encode_seconds"])
            / max(frames, 1), 2),
        "frames_dropped": st["dropped_frames"],
    })

    # -- merge throughput at 4/16/64 simulated hosts -----------------------
    for n_hosts in host_counts:
        packed = []
        want_calls = np.zeros((spec.n_scopes,), np.int64)
        want_values = np.zeros((lay.total,), np.float64)
        for h in range(n_hosts):
            for s in range(frames_per_host):
                calls = rng.integers(0, 100, spec.n_scopes)
                values = (rng.normal(size=lay.total) * 3.0).astype(
                    np.float32)
                samples = rng.integers(0, 50, lay.total)
                want_calls += calls
                want_values += values.astype(np.float64)
                packed.append(wire.encode_delta(
                    calls, values, samples, host_id=f"h{h}", seq=s,
                    fingerprint=spec.fingerprint,
                    step_lo=2 * s, step_hi=2 * (s + 1)))
        agg2 = Aggregator(("127.0.0.1", 0), node_id=f"merge{n_hosts}")
        t0 = time.perf_counter()
        for buf in packed:
            agg2.ingest(wire.decode_frame(buf))
        dt = time.perf_counter() - t0
        view = agg2.merged()
        merge_ok = bool(
            np.array_equal(view.calls, want_calls)
            and np.allclose(view.values, want_values, rtol=1e-9)
            and view.dropped == 0 and view.n_hosts == n_hosts)
        rows.append({
            "workload": f"fleet merge H={n_hosts}", "case": "fleet_merge",
            "hosts": n_hosts, "frames": len(packed), "lanes": lay.total,
            "merge_ms": round(dt * 1e3, 1),
            "frames_per_s": int(len(packed) / dt),
            "merge_us_per_frame": round(1e6 * dt / len(packed), 2),
            "merge_allclose": merge_ok,
            "p50_lane0": round(float(view.reservoirs[0].percentile(50.0)),
                               4) if view.reservoirs else None,
        })

    # -- wire compactness vs raw JSONL of the same payload -----------------
    wire_b, jsonl_b = [], []
    for s in range(32):
        calls = rng.integers(0, 100, spec.n_scopes)
        values = (rng.normal(size=lay.total) * 0.1).astype(np.float32)
        samples = rng.integers(0, 50, lay.total)
        frame = wire.encode_delta(
            calls, values, samples, host_id="h0", seq=s,
            fingerprint=spec.fingerprint, step_lo=2 * s,
            step_hi=2 * (s + 1))
        wire_b.append(len(frame) + 4)   # + the stream length prefix
        jsonl_b.append(len(json_lib.dumps({
            "host": "h0", "seq": s, "step": [2 * s, 2 * (s + 1)],
            "fingerprint": spec.fingerprint,
            "calls": calls.tolist(),
            "values": [float(v) for v in values],
            "samples": samples.tolist(),
        }) + "\n"))
    wb, jb = float(np.mean(wire_b)), float(np.mean(jsonl_b))
    rows.append({
        "workload": "fleet wire", "case": "fleet_wire",
        "lanes": lay.total, "frames": len(wire_b),
        "wire_bytes": round(wb, 1), "jsonl_bytes": round(jb, 1),
        "wire_over_jsonl": round(wb / jb, 3),
        "wire_smaller": bool(wb < jb),
    })
    return rows


def _fleet_summary(rows: list[dict]) -> dict:
    """Aggregate fleet-tier verdicts for the trajectory JSON."""
    enc = [r for r in rows if r.get("case") == "fleet_encode"]
    mrg = [r for r in rows if r.get("case") == "fleet_merge"]
    wr = [r for r in rows if r.get("case") == "fleet_wire"]
    return {
        "encode_frac_pct": max(
            (r["encode_frac_pct"] for r in enc), default=None),
        "encode_under_5pct": bool(enc) and all(
            r["encode_under_5pct"] for r in enc),
        "merge_allclose": bool(mrg) and all(
            r["merge_allclose"] for r in mrg),
        "min_frames_per_s": min(
            (r["frames_per_s"] for r in mrg), default=None),
        "max_hosts": max((r["hosts"] for r in mrg), default=None),
        "wire_over_jsonl": min(
            (r["wire_over_jsonl"] for r in wr), default=None),
        "wire_smaller_than_jsonl": bool(wr) and all(
            r["wire_smaller"] for r in wr),
    }


def main(fast: bool = False):
    iters = 3 if fast else 5
    # the Monitor-vs-manual comparison runs FIRST, on a fresh process: the
    # arch/callcount sweeps leave hundreds of live compiled executables
    # behind, and the resulting allocator/cache pressure skews the tiny
    # paired steps by ~10% (measured: in-driver-last ratios 1.03-1.13 vs
    # fresh-process 0.83-1.04 for identical code).
    rows = run_monitor_sweep(
        probe_sizes=(1 << 12, 1 << 14),   # 16 and 64 KiB probes
        k=12 if fast else 16,
        iters=5 if fast else 7,
        rounds=6 if fast else 8,
    )
    # still fresh-process territory: the megastep ratios compare ~100µs
    # steps and need the same clean allocator the wrap/manual pairs get
    rows += run_megastep_sweep(
        ks=(1, 4, 16),
        steps_per_round=32 if fast else 64,
        rounds=3 if fast else 4,
    )
    rows += run_monitor_psum_check()
    rows += run_train_boundary_check()
    rows += run_arch_workloads(iters=iters)
    # Fig. 3's axis spans tens to thousands of calls; full mode keeps the
    # 1024-call point (its 6-event unrolled graphs take minutes of XLA CPU
    # compile time, so fast/CI mode stops at 256).
    rows += run_callcount_sweep(
        counts=(64, 256) if fast else (64, 256, 1024),
        iters=5 if fast else 7,
    )
    rows += run_plan_sweep(
        probe_sizes=(1 << 14, 1 << 16) if fast else (1 << 14, 1 << 16,
                                                     1 << 18),
        k=16 if fast else 24,
        iters=5 if fast else 7,
        rounds=2 if fast else 3,
    )
    rows += run_readback_sweep(
        hook_everys=(1, 4) if fast else (1, 2, 8),
        depths=(4, 16),
        steps=24 if fast else 32,
        rounds=2 if fast else 3,
    )
    rows += run_adaptive_sweep(
        probe_size=1 << 14 if fast else 1 << 15,
        settle_steps=40 if fast else 48,
        block=24 if fast else 32,
        rounds=4 if fast else 6,
    )
    rows += run_plan_dedup_sweep(rounds=2 if fast else 3)
    rows += run_serve_throughput_sweep(
        streams=(1, 4, 16),
        max_new=16 if fast else 32,
    )
    rows += run_serve_shard_sweep(
        max_new=8 if fast else 32,
        n_req=8 if fast else 12,
    )
    rows += run_prefill_bucket_sweep(
        n_req=40 if fast else 100,
    )
    rows += run_fleet_agg_sweep(
        host_counts=(4, 16, 64),
        frames_per_host=80 if fast else 200,
        steps=32 if fast else 48,
    )
    save_json("overhead.json", rows, sub="bench")
    print(fmt_table(
        rows,
        ["workload", "case", "min_ms", "overhead_pct", "per_call_us",
         "bp_calls"],
        title="ScALPEL overhead: vanilla / selective / all / perfmon "
              "(paper Figs. 2-3)",
    ))
    print(fmt_table(
        [r for r in rows if str(r.get("case", "")).startswith("plan_")],
        ["workload", "case", "min_ms", "sweep_channels", "union_min_ms",
         "plan_gain_pct", "plan_allclose"],
        title="Sparse-active-set sweep: per-set MomentPlans vs union "
              "baseline (probe-plan compiler)",
    ))
    print(fmt_table(
        [r for r in rows if str(r.get("case", "")).startswith("monitor_")],
        ["workload", "case", "min_ms", "med_ms", "state_lanes",
         "manual_med_ms", "wrap_gain_pct", "wrap_allclose",
         "counters_equal"],
        title="Functional Monitor.wrap (one compact MonitorState pytree) "
              "vs manual collecting() baseline + 2-device psum check",
    ))
    print(fmt_table(
        [r for r in rows
         if r.get("case") in ("monitor_scan", "train_megastep_boundary")],
        ["workload", "case", "steps_per_commit", "per_step_us",
         "scan_over_k1_ratio", "scan_gain_pct", "scan_allclose",
         "params_reattached", "tstate_donated", "loss_finite"],
        title="Megastep driver: K steps per commit/dispatch (Monitor.scan) "
              "+ leaf-wise train jit boundary",
    ))
    print(fmt_table(
        [r for r in rows if str(r.get("case", "")).startswith("readback_")],
        ["workload", "case", "hook_every", "ring_depth", "min_ms",
         "per_step_us", "readback_gain_pct", "readback_allclose",
         "snapshots_drained", "ring_slots_copied"],
        title="Readback stall: sync CounterState device_get vs telemetry "
              "ring + incremental background drain",
    ))
    print(fmt_table(
        [r for r in rows if str(r.get("case", "")).startswith("adaptive_")],
        ["workload", "case", "per_step_us", "ctl_over_off_ratio",
         "nan_localized_k5", "steady_levels_sentinel", "final_cadence",
         "counters_allclose_vs_wide", "calls_equal_vs_wide"],
        title="Closed adaptive loop: controller steady state vs "
              "monitoring-off floor vs always-wide ceiling",
    ))
    print(fmt_table(
        [r for r in rows
         if str(r.get("case", "")).startswith("plan_dedup_")],
        ["workload", "case", "n_sets", "n_branches", "plans_deduped",
         "lower_ms", "compile_ms", "min_ms", "dedup_gain_pct"],
        title="Plan-dedup compile sweep: m identical multiplexed sets "
              "(1 shared branch body) vs m distinct sets (m bodies)",
    ))
    print(fmt_table(
        [r for r in rows if str(r.get("case", "")).startswith("serve_")],
        ["workload", "case", "streams", "toks", "min_ms", "toks_per_s",
         "megasteps", "speedup_x", "tokens_exact", "counters_allclose"],
        title="Continuous-batching serve: lane-packed K-token megasteps "
              "(on-device sampling, token-ring egress) vs serial engine",
    ))
    print(fmt_table(
        [r for r in rows if r.get("case") == "serve_shard"],
        ["workload", "case", "streams", "n_lanes", "lane_shards",
         "ms_1shard", "ms_2shard", "tokens_exact", "counters_exact",
         "values_allclose"],
        title="Lane-sharded serve (2 forced host devices): shard_map "
              "megasteps, 1 vs 2 shards over the same slab",
    ))
    print(fmt_table(
        [r for r in rows
         if str(r.get("case", "")).startswith("prefill_bucket")],
        ["workload", "case", "streams", "min_ms", "toks_per_s",
         "prefill_traces", "n_buckets", "pad_waste_frac", "speedup_x",
         "tokens_exact"],
        title="Prompt-length bucketing: pow2 pad buckets vs per-length "
              "prefill re-trace (compile time included — that's the point)",
    ))
    print(fmt_table(
        [r for r in rows if str(r.get("case", "")).startswith("fleet_")],
        ["workload", "case", "hosts", "frames", "lanes", "encode_frac_pct",
         "merge_us_per_frame", "frames_per_s", "merge_allclose",
         "wire_bytes", "jsonl_bytes", "wire_over_jsonl"],
        title="Fleet telemetry tier: frame encode cost per drain, "
              "aggregator merge throughput, wire bytes vs raw JSONL",
    ))
    # the paper's hierarchy, asserted softly (plan/readback rows carry no
    # perfmon case)
    by = {}
    for r in rows:
        if "min_ms" not in r:   # e.g. the subprocess psum-equality row
            continue
        by.setdefault(r["workload"], {})[r["case"]] = r["min_ms"]
    hier = {w: c for w, c in by.items() if "perfmon" in c}
    ok = sum(
        1 for w, c in hier.items()
        if c["perfmon"] >= max(c["selective"], c["all"]) * 0.9
    )
    plans = _plan_summary(rows)
    readback = _readback_summary(rows)
    monitor = _monitor_summary(rows)
    adaptive = _adaptive_summary(rows)
    serve = _serve_summary(rows)
    fleet = _fleet_summary(rows)
    print(f"\nhierarchy check: perfmon slowest in {ok}/{len(hier)} workloads")
    print(
        f"Monitor.wrap vs manual: not-slower in "
        f"{monitor['wrap_not_slower']}/{monitor['compared']} configs "
        f"(max gain {monitor['max_gain_pct']}%); counters allclose: "
        f"{monitor['allclose_all']}; 2-device psum == per-shard sum: "
        f"{monitor['psum_2dev_equal']}"
    )
    print(
        f"megastep: K=16 gain {monitor['megastep_k16_gain_pct']}% per step "
        f"(>=15%: {monitor['megastep_speedup_15pct']}); counters == "
        f"unrolled: {monitor['megastep_allclose']}; train boundary "
        f"params-not-output: {monitor['train_params_not_output']} "
        f"(tstate donated: {monitor['train_tstate_donated']})"
    )
    print(
        f"per-set plans vs union: faster in {plans['per_set_faster']}/"
        f"{plans['compared']} configs "
        f"(strict: {plans['strictly_faster']}, max gain "
        f"{plans['max_gain_pct']}%); counters allclose: "
        f"{plans['allclose_all']}"
    )
    print(
        f"readback: ring faster in {readback['ring_faster']}/"
        f"{readback['compared']} configs "
        f"(strict at hook_every=1: {readback['ring_faster_at_hook1']}); "
        f"drained counters allclose: {readback['allclose_all']}"
    )
    print(
        f"adaptive: NaN localized within K=5: "
        f"{adaptive['nan_localized_k5']}; steady-state ctl/off ratio "
        f"{adaptive['max_ctl_over_off_ratio']} "
        f"(within 5%: {adaptive['ctl_within_5pct']}); quiet-scope "
        f"counters allclose vs always-wide: {adaptive['counters_allclose']}"
    )
    print(
        f"serve: continuous speedup at 16 streams "
        f"{serve['speedup_at_16']}x (>=3x: {serve['speedup_3x_at_16']}); "
        f"greedy tokens == serial: {serve['tokens_exact_all']}; "
        f"per-request counters allclose: {serve['counters_allclose_all']}"
    )
    print(
        f"serve shard: 2-shard tokens == 1-shard: "
        f"{serve['shard_tokens_exact']}; integer counters exact: "
        f"{serve['shard_counters_exact']}"
    )
    print(
        f"prefill bucketing: traces bounded by buckets: "
        f"{serve['bucket_traces_bounded']}; speedup vs per-length retrace "
        f"{serve['bucket_speedup_x']}x (>=2x: {serve['bucket_speedup_2x']}); "
        f"tokens exact: {serve['bucket_tokens_exact']}"
    )
    print(
        f"fleet: encode {fleet['encode_frac_pct']}% of drain time "
        f"(<5%: {fleet['encode_under_5pct']}); merge exact at up to "
        f"{fleet['max_hosts']} hosts: {fleet['merge_allclose']} "
        f"(>= {fleet['min_frames_per_s']} frames/s); wire/jsonl bytes "
        f"{fleet['wire_over_jsonl']}"
    )
    return {
        "schema": "scalpel-overhead-v10",
        "backend": jax.default_backend(),
        "probe_events": list(PROBE_EVENTS),
        "plan_sets": [list(s) for s in PLAN_SETS],
        "plan_fingerprint": _plan_spec().fingerprint,
        "rows": rows,
        "per_mode_min_ms": by,
        "overhead_ratio": {
            w: {c: round(t / cs["vanilla"], 4) for c, t in cs.items()}
            for w, cs in by.items() if cs.get("vanilla")
        },
        "plans": plans,
        "monitor": monitor,
        "readback": readback,
        "adaptive": adaptive,
        "serve": serve,
        "fleet": fleet,
        "hierarchy_ok": ok,
    }


if __name__ == "__main__":
    import sys

    main(fast="--fast" in sys.argv)
