"""Tiny cells for the CPU tests: the benchmark's own runners and
references, at the program's smoke widths, found by the same names."""
from __future__ import annotations

import copy
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run as run_mod  # noqa: E402


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def context(cell_name: str, seed: int, work_dir: Path, *, seconds=0.2,
            trace=False, compute_dtype="float32"):
    """The run context of ``cell_name`` with the configuration cut to the
    program's smoke preset and the traffic shrunk to match."""
    import jax

    from bench import common
    from repro.configs import model_config

    loaded = run_mod.load_cell(benchmark(), cell_name)
    config = copy.deepcopy(loaded["config"])
    program_id = {"xlstm_l12_d768": "xlstm_125m",
                  "qwen3_14b_l8": "qwen3_14b"}[loaded["cell"]["config"]]
    smoke = model_config(program_id, smoke=True).replace(
        compute_dtype=compute_dtype)
    config["model"] = dataclasses.asdict(smoke)
    tr = copy.deepcopy(loaded["traffic"])
    if tr["kind"] == "train":
        tr.update(seq_len=32, batch_per_chip=4, warmup_commits=1,
                  nominal_step_s=0.05, trace_commits=2, trace_megasteps=1)
        tr["data"]["mean_doc_len"] = 16
    else:
        tr.update(n_lanes=2, cache_len=96, steps_per_commit=2,
                  nominal_tokens_per_s=40, trace_requests=2,
                  trace_delay_s=0.0, trace_seconds=0.05, check_requests=2)
        tr["prompt"].update(median=12, min=4, max=40)
        tr["output"].update(median=6, min=2, max=16)
    return run_mod, loaded["kind"], common.RunContext(
        cell=loaded["cell"], config=config, traffic=tr,
        reference=loaded["reference"], monitor_cfg=loaded["monitor_cfg"],
        seed=seed, seconds=seconds, trace=trace,
        devices=jax.devices()[:1], t0=time.perf_counter(),
        work_dir=work_dir, compiles=common.Compiles())
