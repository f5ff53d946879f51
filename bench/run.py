#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Everything
else is found by name under ``bench/``: the configuration
``configs/<config>.json`` with its plain reference ``configs/<config>.py``,
the traffic mix ``traffic/<traffic>.json``, whose ``kind`` names the runner
``kinds/<kind>.py`` and whose ``monitor`` (if any) names a monitor mode
``monitor/<mode>.cfg``, and the reader ``metrics/<metric>.py`` of each
per-layer metric.

A run sets up (weights from the seed, every shape of the cell compiled or
loaded from the persistent cache in ``.jax_cache`` of the checkout),
measures one window of about ``--seconds``, checks what the timed path
produced against the plain reference, and prints the result as the last
line of standard output: the end-to-end metrics with ``--trace 0``, the
per-layer metrics with ``--trace 1``, where a part of the run after the
window is profiled.  The numbers the check compared, each beside its
limit, end standard error and end the result line.  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
WORK_DIR = ROOT / ".bench_work"


def process_start_time() -> float:
    """Wall-clock time at which this process started (``time.time()``
    scale); the time of the first line of this module where ``/proc`` is
    not readable."""
    try:
        ticks = os.sysconf("SC_CLK_TCK")
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        start_ticks = int(fields[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + start_ticks / ticks
    except (OSError, ValueError, IndexError):
        return _MODULE_T0


_MODULE_T0 = time.time()


def load_module(path: Path):
    """Import a file of the benchmark by its path."""
    if not path.is_file():
        raise FileNotFoundError(f"{path} not found")
    name = "_".join(("bench", path.parent.name, path.stem)).replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"{path} not found")
    with open(path) as f:
        return json.load(f)


def find_cell(benchmark: dict, name: str) -> dict:
    for w in benchmark["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in benchmark['workloads']]}")


def cell_metrics(benchmark: dict, cell: dict, trace: bool) -> list[dict]:
    """The metrics this cell reports: its end-to-end metrics, or with
    ``trace`` the per-layer metrics whose ``workloads`` list it."""
    name = cell["name"]
    if not trace:
        return [m for m in benchmark["end_to_end"]
                if "workloads" not in m or name in m["workloads"]]
    for m in benchmark["per_layer"]:
        if "workloads" not in m:
            raise KeyError(f"per-layer metric {m['name']!r} lists no "
                           f"workloads")
    return [m for m in benchmark["per_layer"] if name in m["workloads"]]


def load_cell(benchmark: dict, name: str, bench_dir: Path = BENCH) -> dict:
    """Everything a run of cell ``name`` needs, found by name."""
    cell = find_cell(benchmark, name)
    config = read_json(bench_dir / "configs" / f"{cell['config']}.json")
    traffic = read_json(bench_dir / "traffic" / f"{cell['traffic']}.json")
    kind = load_module(bench_dir / "kinds" / f"{traffic['kind']}.py")
    reference = load_module(bench_dir / "configs" / f"{cell['config']}.py")
    monitor_cfg = None
    if traffic.get("monitor"):
        monitor_cfg = bench_dir / "monitor" / f"{traffic['monitor']}.cfg"
        if not monitor_cfg.is_file():
            raise FileNotFoundError(f"{monitor_cfg} not found")
    return {"cell": cell, "config": config, "traffic": traffic,
            "kind": kind, "reference": reference,
            "monitor_cfg": monitor_cfg}


def check_devices(chips: int):
    """The cell's chips, or an error message: the benchmark never falls
    back to another platform."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return None, (f"needs a TPU; JAX found {devices[0].platform!r} "
                      f"devices")
    if len(devices) < chips:
        return None, (f"the cell asks for {chips} chips; JAX found "
                      f"{len(devices)}")
    return devices[:chips], None


def read_metrics(wanted: list[dict], readings: dict,
                 bench_dir: Path = BENCH) -> dict:
    """Each per-layer metric from its own reader; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in wanted:
        reader = load_module(bench_dir / "metrics" / f"{m['name']}.py")
        value = reader.read(readings)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t0 = time.perf_counter() - (time.time() - process_start_time())

    # the compile cache lives in the checkout, whatever the environment
    # says; set before JAX is first imported, which reads it then
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # the TPU runtime's logs stay in the checkout too
    WORK_DIR.mkdir(exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(WORK_DIR / "tpu_logs"))
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    benchmark = read_json(ROOT / "BENCHMARK.json")
    run = load_cell(benchmark, args.workload)
    cell = run["cell"]
    wanted = cell_metrics(benchmark, cell, bool(args.trace))
    devices, err = check_devices(int(cell["chips"]))
    if err:
        print(f"bench/run.py: {err}", file=sys.stderr)
        return 2

    import jax

    from bench import common
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out = run["kind"].run(common.RunContext(
        cell=cell, config=run["config"], traffic=run["traffic"],
        reference=run["reference"], monitor_cfg=run["monitor_cfg"],
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        devices=devices, t0=t0, work_dir=WORK_DIR,
        compiles=common.Compiles()))
    return report(out, wanted, devices, int(cell["chips"]), bool(args.trace))


def report(out: dict, wanted: list[dict], devices, chips: int,
           trace: bool) -> int:
    """Print the compared numbers to standard error and the result line."""
    readings = out["readings"]
    if trace:
        metrics = read_metrics(wanted, readings)
    else:
        metrics = {m["name"]: {"value": float(out["e2e"][m["name"]]),
                               "unit": m["unit"]} for m in wanted}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": chips,
              "memory_peak_bytes": int(out["memory_peak_bytes"])}
    if trace:
        device["busy_s"] = readings["busy_s"]
        device["window_s"] = readings["window_s"]
    checks = out["checks"]
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = readings["breakdown"]
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
