"""Benchmark driver: python -m benchmarks.run [--fast]

One benchmark per paper table/figure + the scale deliverables:
  overhead    — paper Figs. 2-3 (vanilla/perfmon/all/selective, per-set
                probe plans vs the union baseline, readback sweeps).  Its
                structured result is written to ``BENCH_overhead.json`` at
                the repo root so the monitoring overhead trajectory is
                machine-readable across PRs.
  case_study  — paper Table 2 + Fig. 4 (two GEMM schedules through counters)
  kernels     — Pallas kernel vs oracle timings + cost-model table
  roofline    — per (arch x shape) three-term roofline from the dry-run
"""
from __future__ import annotations

import json
import os
import sys
import traceback

# anchored to the repo root (parent of benchmarks/), not the CWD, so the
# trajectory file lands where CI and git expect it from any launch dir
OVERHEAD_JSON = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_overhead.json",
)


def _write_overhead_json(payload: dict) -> None:
    with open(OVERHEAD_JSON, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    print(f"\nwrote {OVERHEAD_JSON} "
          f"(plans: {payload.get('plans')}; "
          f"monitor: {payload.get('monitor')}; "
          f"readback: {payload.get('readback')}; "
          f"adaptive: {payload.get('adaptive')}; "
          f"serve: {payload.get('serve')})")


def main() -> int:
    fast = "--fast" in sys.argv
    failures = []
    print("=" * 72)
    print("ScALPEL-JAX benchmark suite")
    print("=" * 72)

    from repro.launch.compile_cache import enable_compile_cache

    from . import case_study, kernels_bench, overhead, roofline

    enable_compile_cache()

    def run_overhead():
        _write_overhead_json(overhead.main(fast=fast))

    for name, fn in [
        ("overhead (paper Figs. 2-3)", run_overhead),
        ("case study (paper Table 2 / Fig. 4)",
         lambda: case_study.main(fast=fast)),
        ("kernel microbench", lambda: kernels_bench.main(fast=fast)),
        ("roofline 16x16", lambda: roofline.main(mesh="16x16")),
        ("roofline 2x16x16", lambda: roofline.main(mesh="2x16x16")),
    ]:
        print("\n" + "=" * 72)
        print(f"--- {name}")
        print("=" * 72)
        try:
            fn()
        except Exception:
            failures.append(name)
            traceback.print_exc()
    print("\n" + "=" * 72)
    if failures:
        print(f"FAILED benchmarks: {failures}")
        return 1
    print("all benchmarks completed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
