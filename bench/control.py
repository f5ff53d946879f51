#!/usr/bin/env python3
"""Readings of the comparison's control and faults at a cell's own size.

    python3 bench/control.py --workload <cell> --seed <n> [--seconds <s>]

Benchmark runs never run this.  It prints, as one JSON line, the numbers
the cell's check compares when the program's place is taken by

* the control: the plain reference computed in the precision below the
  configuration's (float8 e4m3 matmul operands for a bfloat16 model);
* for training, the faults a step can have: half of the batch left out
  (``half_batch``); a step that returns its state unchanged reads 1 on
  ``update_gap`` by construction and needs no run.

A training cell needs no window: the control and the faults are the
reference run over the cell's first ``K`` batches.  A serving cell runs a
short window at the cell's own load and reads, at each position of the
served tokens, the gap of the token that the control puts first.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def train_readings(loaded: dict, seed: int) -> dict:
    from bench import gen
    from bench.kinds import train as train_kind

    tr, m = loaded["traffic"], loaded["config"]["model"]
    k = int(tr["steps_per_commit"])
    batch = int(tr["batch_per_chip"]) * int(loaded["cell"]["chips"])
    batches = [gen.lm_batch(seed, i, vocab=m["vocab"], seq_len=tr["seq_len"],
                            batch=batch, **tr["data"]) for i in range(k)]
    ref_mod = loaded["reference"]
    ref = ref_mod.train_steps(m, tr["opt"], seed, batches)
    out = {}
    for name, kw in (("control", {"control": True}),
                     ("half_batch", {"fault": "half_batch"})):
        got = ref_mod.train_steps(m, tr["opt"], seed, batches, **kw)
        out[name] = train_kind.compare(
            tr, ref, losses=got["losses"], redrive_losses=got["losses"],
            gnorms=got["gnorms"], delta=got["delta_norms"],
            snapshot={"step": k, "values": {
                "MEAN:loss_value": sum(got["losses"]),
                "MEAN:gnorm": sum(got["gnorms"])}},
            drained_step=0, steps=0, window_compiles=0, dormant=False)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import run as run_mod

    benchmark = run_mod.read_json(ROOT / "BENCHMARK.json")
    loaded = run_mod.load_cell(benchmark, args.workload)
    import os

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run_mod.CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(run_mod.CACHE_DIR))
    for seed in args.seed:
        out = seed_readings(loaded, seed, args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed, **out}),
              flush=True)
    return 0


def seed_readings(loaded: dict, seed: int, seconds: float) -> dict:
    if loaded["traffic"]["kind"] == "train":
        return train_readings(loaded, seed)
    import jax

    from bench import common
    from bench import run as run_mod

    run_mod.WORK_DIR.mkdir(exist_ok=True)
    chips = int(loaded["cell"]["chips"])
    res = loaded["kind"].run(common.RunContext(
        cell=loaded["cell"], config=loaded["config"],
        traffic=loaded["traffic"], reference=loaded["reference"],
        monitor_cfg=loaded["monitor_cfg"], seed=seed, seconds=seconds,
        trace=False, devices=jax.devices()[:chips], t0=time.perf_counter(),
        work_dir=run_mod.WORK_DIR, compiles=common.Compiles()),
        control=True)
    return {"control": res["checks"]}


if __name__ == "__main__":
    sys.exit(main())
