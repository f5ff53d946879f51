"""Operations and bytes the benchmark credits to a step or a kernel call.

Model FLOPs follow the usual convention: ``6 N`` per trained token and
``2 N`` per served token, with ``N`` the parameters a token passes through,
leaving out the input embedding (a gather, not a matmul).  Recomputation,
attention scores and the sLSTM's element-wise recurrence are not counted.
``N`` comes from the configuration's plain reference, not from the program.
"""
from __future__ import annotations

import math

from bench.trace import shape_bytes


def matmul_params(reference, model: dict) -> int:
    """Parameters a token passes through: all but the input embedding."""
    counts = reference.param_count(model)
    return counts["total"] - counts["embed"]


def train_flops_per_token(reference, model: dict) -> float:
    return 6.0 * matmul_params(reference, model)


def serve_flops_per_token(reference, model: dict) -> float:
    return 2.0 * matmul_params(reference, model)


def moments_kernel_cost(operands) -> dict:
    """Operations and bytes of one probe-moments call over ``operands``
    (``[(dtype, dims)]``): it reads each element once and does a handful of
    vector operations on it; the output is a few floats."""
    elems = sum(math.prod(d) for _, d in operands)
    return {"flops": 8.0 * elems, "bytes": float(shape_bytes(operands))}


def roofline_seconds(cost: dict, peak: dict) -> float:
    """Least time the chip could take: the larger of the compute and the
    memory bound."""
    return max(cost["flops"] / peak["bf16_flops_per_s"],
               cost["bytes"] / peak["hbm_bytes_per_s"])
