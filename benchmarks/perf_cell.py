"""Single-cell perf analysis for the §Perf hillclimb loop.

    PYTHONPATH=src python -m benchmarks.perf_cell <arch> <shape> [--multi]
        [--tag NAME] [--breakdown]

Lowers + compiles one (arch x shape x mesh) cell, runs the while-aware HLO
analysis, prints the three roofline terms, and appends a JSON line to
experiments/perf/<arch>__<shape>.jsonl so before/after iterations are
recorded side by side.
"""
import os

# a 512-device pod simulated on the host CPU: pinned there, so the script
# never starts on (or waits for) an attached accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402

from repro.core.backends import hlo_graph  # noqa: E402
from repro.dist.partition import sharding_ctx  # noqa: E402
from repro.launch.dryrun import build_cell  # noqa: E402

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def analyze(arch, shape, multi=False, tag="", show_breakdown=False,
            policy_overrides=None):
    t0 = time.time()
    fn, args, shardings, donate, mesh, meta = build_cell(
        arch, shape, multi, policy_overrides=policy_overrides
    )
    with mesh, sharding_ctx(mesh):
        lowered = jax.jit(
            fn, in_shardings=shardings, donate_argnums=donate
        ).lower(*args)
        compiled = lowered.compile()
    text = compiled.as_text()
    g = hlo_graph.analyze_text(text, default_group=meta["n_devices"])
    ma = compiled.memory_analysis()
    terms = {
        "compute_s": g["flops"] / PEAK_FLOPS,
        "memory_s": g["hbm_bytes"] / HBM_BW,
        "collective_s": g["collective_link_bytes"] / ICI_BW,
    }
    dom = max(terms, key=terms.get)
    rec = {
        "tag": tag or "baseline",
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi else "16x16",
        **{k: round(v, 4) for k, v in terms.items()},
        "dominant": dom,
        "flops_per_chip": g["flops"],
        "hbm_per_chip": g["hbm_bytes"],
        "coll_per_chip": g["collective_link_bytes"],
        "coll_by_kind": g["collectives_by_kind"],
        "temp_gib": round(ma.temp_size_in_bytes / 2**30, 2),
        "wall_s": round(time.time() - t0, 1),
    }
    print(json.dumps(rec, indent=1))
    os.makedirs("experiments/perf", exist_ok=True)
    with open(f"experiments/perf/{arch}__{shape}.jsonl", "a") as f:
        f.write(json.dumps(rec) + "\n")
    if show_breakdown:
        bd = hlo_graph.breakdown(text, default_group=meta["n_devices"],
                                 top=12)
        print("\n=== top by HBM ===")
        for e in bd["by_hbm"]:
            print(f"{e['hbm'] / 1e9:9.1f} GB x{e['mult']:6.0f} "
                  f"{e['kind']:16s} {e['path'][:48]}")
            print("     ", e["line"][:140])
        print("\n=== top by FLOPs ===")
        for e in bd["by_flops"]:
            print(f"{e['flops'] / 1e12:9.2f} TF x{e['mult']:6.0f} "
                  f"{e['kind']:16s} {e['path'][:48]}")
            print("     ", e["line"][:140])
        print("\n=== collectives ===")
        for k, v in sorted(g["collectives_by_kind"].items()):
            print(f"  {k:20s} {v / 1e9:9.2f} GB/chip")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("arch")
    ap.add_argument("shape")
    ap.add_argument("--multi", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--breakdown", action="store_true")
    a = ap.parse_args()
    analyze(a.arch, a.shape, a.multi, a.tag, a.breakdown)


if __name__ == "__main__":
    main()
