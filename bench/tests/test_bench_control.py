"""The comparison's control, at a size a test run can hold: the plain
reference computed in the precision below the configuration's (float8
matmul operands) in the program's place must come out not correct, as
must the faults a training step can have.  On the chip the same readings
come from ``bench/control.py`` at each cell's own size."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))

import tiny  # noqa: E402
from bench import control  # noqa: E402


def _fails(checks: dict) -> bool:
    return any(not c["value"] <= c["limit"] for c in checks.values())


def test_training_control_and_half_batch_are_caught(tmp_path):
    _, _, ctx = tiny.context("xlstm_l12_d768.train.all", 2**31 + 31, tmp_path)
    loaded = {"traffic": ctx.traffic, "config": ctx.config,
              "cell": ctx.cell, "reference": ctx.reference}
    out = control.train_readings(loaded, ctx.seed)
    assert _fails(out["control"]), out["control"]
    assert _fails(out["half_batch"]), out["half_batch"]


def test_serving_control_is_caught(tmp_path):
    _, kind, ctx = tiny.context("qwen3_14b_l8.serve.all", 2**31 + 31,
                                tmp_path)
    checks = kind.run(ctx, control=True)["checks"]
    assert checks["logit_gap"]["value"] > checks["logit_gap"]["limit"], \
        checks
