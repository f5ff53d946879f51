"""Paper §4.2 case study (Table 2 / Fig. 4): two GEMM implementations
compared through counters, with call-count event multiplexing.

LINPACK's dominant kernel is DGEMM; the paper instruments ATLAS's
``ATL_dgemm`` vs GotoBLAS's ``dgemm_`` and cycles through 5 event sets every
100 calls, showing (a) the sampled counters match 5 exhaustive runs within
marginal error, and (b) the counters explain WHY one implementation is
faster (Goto: more TLB misses, but 65% fewer L2 misses / 75% fewer stalls).

TPU adaptation: the implementations are the two Pallas GEMM schedules
(cache_blocked ≙ ATLAS default, cache_blocked@256 ≙ ATLAS full-search,
panel_streaming ≙ GotoBLAS) and the counters are the schedule cost events:
  VMEM_TILE_REFILLS ≙ DTLB_MISSES     HBM_BYTES ≙ L2_LINES_IN
  MXU_PASSES        ≙ SIMD_INST_RETIRED  FLOPS  ≙ INST_RETIRED
  EST_STALL_CYCLES  ≙ RESOURCE_STALLS
plus data-dependent events (ACT_RMS / L2NORM of C) that genuinely need the
live tensors.  The multiplex period is the paper's 100 calls.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro import core as scalpel
from repro.core.context import EventSpec, MonitorSpec, ScopeContext
from repro.core.counters import CounterState, MonitorParams
from repro.kernels import ops

from .common import bench, fmt_table, save_json

# the five multiplexed event sets (paper: five sets, one per exhaustive run)
EVENT_SETS = [
    ["VMEM_TILE_REFILLS:refills", "HBM_BYTES:hbm"],
    ["MXU_PASSES:mxu", "FLOPS:flops"],
    ["EST_STALL_CYCLES:stalls"],
    ["ACT_RMS:out", "L2NORM:out"],
    ["NUMEL:out"],
]

IMPLS = {
    "atlas_default": dict(schedule="cache_blocked", bm=128, bn=128, bk=128),
    "atlas_full": dict(schedule="cache_blocked", bm=256, bn=256, bk=256),
    "goto": dict(schedule="panel_streaming", bm=128, bn=256),
}


def _spec(multiplexed: bool, period: int = 100) -> MonitorSpec:
    sets = [[EventSpec.parse(s) for s in group] for group in EVENT_SETS]
    if multiplexed:
        ctx = ScopeContext.multiplexed("dgemm", sets, period=period)
    else:
        ctx = ScopeContext.exhaustive("dgemm", [e for g in sets for e in g])
    return MonitorSpec.of([ctx])


def _dgemm_step(impl_cfg: dict, m: int, n: int, k: int, spec: MonitorSpec):
    """One instrumented DGEMM call: counters threaded through the carry."""
    cost = ops.matmul_cost(
        impl_cfg["schedule"], m, n, k,
        bm=impl_cfg.get("bm", 256), bn=impl_cfg.get("bn", 256),
        bk=impl_cfg.get("bk", 256),
    )
    kw = {kk: vv for kk, vv in impl_cfg.items() if kk != "schedule"}

    def step(a, b, state, mp):
        with scalpel.collecting(spec, mp, state) as col:
            with scalpel.function("dgemm"):
                c = ops.matmul(a, b, impl_cfg["schedule"], interpret=True,
                               **kw)
                scalpel.probe(
                    out=c,
                    refills=jnp.float32(cost["VMEM_TILE_REFILLS"]),
                    hbm=jnp.float32(cost["HBM_BYTES"]),
                    mxu=jnp.float32(cost["MXU_PASSES"]),
                    flops=jnp.float32(cost["FLOPS"]),
                    stalls=jnp.float32(cost["EST_STALL_CYCLES"]),
                )
        return c, state.add(col.delta)

    return jax.jit(step), cost


def run_impl(impl: str, n_calls: int, m: int, n: int, k: int,
             multiplexed: bool, period: int = 100) -> dict:
    spec = _spec(multiplexed, period)
    step, cost = _dgemm_step(IMPLS[impl], m, n, k, spec)
    mp = MonitorParams.all_on(spec)
    state = CounterState.zeros(spec)
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (m, k), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
    # per-call input drift (LINPACK's DGEMM calls see varying panels):
    # deterministic scale so the sampled subset differs from the full set —
    # the data-dependent events then exercise the Fig. 4 error claim.
    import time

    t0 = time.perf_counter()
    for i in range(n_calls):
        scale = 1.0 + 0.1 * np.sin(0.37 * i)
        c, state = step(a * np.float32(scale), b, state, mp)
    jax.block_until_ready(c)
    wall = time.perf_counter() - t0
    est = scalpel.estimates(spec, state)["dgemm"]
    return {
        "impl": impl,
        "mode": "sampling" if multiplexed else "exhaustive",
        "calls": n_calls,
        "wall_s": round(wall, 3),
        "estimates": est,
        "analytic": cost,
    }


def main(fast: bool = False):
    m = n = k = 256
    n_calls = 200 if fast else 500
    period = 20 if fast else 100  # >= 2 full cycles over 5 sets
    results = []
    for impl in IMPLS:
        results.append(run_impl(impl, n_calls, m, n, k, multiplexed=False))
        results.append(run_impl(impl, n_calls, m, n, k, multiplexed=True,
                                period=period))
    save_json("case_study.json", results, sub="bench")

    # ---- Table 2: counter values per impl (sampling run) -----------------
    slot_ids = [s for g in EVENT_SETS for s in g]
    rows = []
    for sid in slot_ids:
        row = {"event": sid}
        for impl in IMPLS:
            samp = next(r for r in results
                        if r["impl"] == impl and r["mode"] == "sampling")
            row[impl] = f"{samp['estimates'][sid]:.3e}"
        rows.append(row)
    print(fmt_table(rows, ["event"] + list(IMPLS),
                    title="Table 2 analogue: per-call counters, "
                          f"multiplexed sampling run (period={period})"))

    # ---- Fig. 4: sampling vs exhaustive error + impl ratios ---------------
    err_rows = []
    for impl in IMPLS:
        ex = next(r for r in results
                  if r["impl"] == impl and r["mode"] == "exhaustive")
        sa = next(r for r in results
                  if r["impl"] == impl and r["mode"] == "sampling")
        for sid in slot_ids:
            e, s = ex["estimates"][sid], sa["estimates"][sid]
            if not np.isfinite(e) or e == 0:
                continue
            err_rows.append({
                "impl": impl, "event": sid,
                "exhaustive": f"{e:.4e}", "sampled": f"{s:.4e}",
                "err_pct": round(100 * abs(s - e) / abs(e), 3),
            })
    print()
    print(fmt_table(err_rows,
                    ["impl", "event", "exhaustive", "sampled", "err_pct"],
                    title="Fig. 4 analogue: multiplexed sampling vs "
                          "exhaustive (error should be marginal)"))
    max_err = max(r["err_pct"] for r in err_rows)
    print(f"\nmax sampling error: {max_err:.3f}% "
          f"(paper: 'the error introduced by sampling is marginal')")

    # ---- the case-study argument: counters explain the trade-off ----------
    g = next(r for r in results if r["impl"] == "goto"
             and r["mode"] == "sampling")["estimates"]
    a0 = next(r for r in results if r["impl"] == "atlas_default"
              and r["mode"] == "sampling")["estimates"]
    print("\ncase-study conclusion (goto vs atlas_default):")
    print(f"  HBM_BYTES        (≙L2_LINES_IN):   "
          f"{100 * (g['HBM_BYTES:hbm'] / a0['HBM_BYTES:hbm'] - 1):+.1f}%")
    print(f"  VMEM_TILE_REFILLS(≙DTLB_MISSES):   "
          f"{100 * (g['VMEM_TILE_REFILLS:refills'] / a0['VMEM_TILE_REFILLS:refills'] - 1):+.1f}%")
    print(f"  EST_STALL_CYCLES (≙RESOURCE_STALLS): "
          f"{100 * (g['EST_STALL_CYCLES:stalls'] / max(a0['EST_STALL_CYCLES:stalls'], 1e-9) - 1):+.1f}%")
    print(f"  FLOPS identical: "
          f"{g['FLOPS:flops'] == a0['FLOPS:flops']}")
    return results


if __name__ == "__main__":
    import sys

    main(fast="--fast" in sys.argv)
