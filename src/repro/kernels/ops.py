"""Public jit'd wrappers for the Pallas kernels.

Every kernel lowers to Mosaic, for a TPU.  Interpret mode (the kernel body
executed by the Pallas interpreter, for correctness checks off the chip)
runs only when a caller passes ``interpret=True`` — there is no backend
guess, so a kernel that cannot run where it was called fails loudly instead
of silently becoming an interpreter.  Wrappers also adapt model-layer
calling conventions (GQA [b,s,h,d]) to the kernel contracts ([bh,s,d]).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import flash_attn as _fa
from . import gemm as _gemm
from . import probe_reduce as _pr
from . import ssm_scan as _ssm

SCHEDULES = ("cache_blocked", "panel_streaming")


# ---------------------------------------------------------------------------
# fused probe-moment reduction (the monitoring hot path)
# ---------------------------------------------------------------------------

# Below this many elements the grid bookkeeping outweighs the fused
# sweep; the probe path uses the jnp fallback instead.
MIN_PALLAS_MOMENT_NUMEL = 1 << 15


@functools.partial(
    jax.jit, static_argnames=("block_elems", "interpret", "with_entropy")
)
def probe_moments(x, *, block_elems: int = _pr.BLOCK_ELEMS,
                  interpret: bool = False, with_entropy: bool = False):
    """Raw probe-moment vector f32[8] (f32[9] with the plan-requested
    ``ent_sum`` channel; see probe_reduce.MOMENTS/MOMENTS_ENT) of ``x``.

    Single tiled pass over the tensor, lowered to Mosaic (``interpret=True``
    runs the Pallas interpreter instead, for checks off the chip).
    ``block_elems`` bounds each grid step's block (``probe_reduce.block_shape``).
    """
    return _pr.moments_pallas(x, block_elems=block_elems,
                              interpret=interpret, with_entropy=with_entropy)


def tensor_moments(x, names, *, use_pallas: bool | None = None) -> dict:
    """{channel: f32 scalar} for the probe path — the ONE sweep per tensor.

    ``names`` is the exact channel tuple a MomentPlan (core/plan.py) compiled
    for the active event set — the sweep computes nothing outside it (plus
    the free trace-time constants ``numel``/``rows``).

    Policy (``use_pallas=None``): the Pallas kernel on TPU for large float
    tensors; the fused-jnp fallback for tiny/oddly-shaped/non-float tensors
    and on CPU, where interpret-mode Pallas would be a correctness tool, not
    a fast path.  A caller tracing a program the compiler will partition
    passes ``use_pallas=False``: a Mosaic kernel cannot be partitioned.
    """
    if use_pallas is None:
        use_pallas = (
            jax.default_backend() == "tpu"
            and jnp.issubdtype(x.dtype, jnp.floating)
            and x.size >= MIN_PALLAS_MOMENT_NUMEL
        )
    if use_pallas:
        with_entropy = "ent_sum" in set(names)
        vec = probe_moments(x, with_entropy=with_entropy)
        chans = _pr.MOMENTS_ENT if with_entropy else _pr.MOMENTS
        out = dict(zip(chans, vec))
        out.update(_pr.static_channel_values(x.shape))  # exact numel + rows
        return out
    return _pr.named_moments_jnp(x, names)


# ---------------------------------------------------------------------------
# GEMM (case-study subject)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("schedule", "bm", "bn", "bk", "interpret")
)
def matmul(a, b, schedule: str = "panel_streaming", *, bm: int = 256,
           bn: int = 256, bk: int = 256, interpret: bool = False):
    """C = A @ B via the named Pallas schedule (f32 out)."""
    if schedule == "cache_blocked":
        return _gemm.cache_blocked_matmul(
            a, b, bm=bm, bn=bn, bk=bk, interpret=interpret
        )
    if schedule == "panel_streaming":
        return _gemm.panel_streaming_matmul(
            a, b, bm=bm, bn=bn, interpret=interpret
        )
    raise KeyError(f"unknown schedule {schedule!r}; have {SCHEDULES}")


def matmul_cost(schedule: str, m: int, n: int, k: int, *, bm: int = 256,
                bn: int = 256, bk: int = 256, dtype_bytes: int = 2) -> dict:
    """Analytical counters for one matmul call (the case-study events)."""
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    return _gemm.schedule_cost(schedule, m, n, k, bm, bn, bk, dtype_bytes)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_kv", "scale",
                     "interpret"),
)
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 512, block_kv: int = 1024,
                    scale: float | None = None, interpret: bool = False):
    """Model-layer convention: q [b,sq,h,d]; k,v [b,sk,kvh,d] (GQA ok)."""
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    if kvh != h:
        rep = h // kvh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    out = _fa.flash_attention_bhsd(
        qf, kf, vf, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, scale=scale, interpret=interpret,
    )
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


def flash_attention_cost(b, sq, sk, h, d, *, causal=True, block_q=512,
                         block_kv=1024, dtype_bytes=2) -> dict:
    """Analytical counters: tiles actually computed after causal skipping."""
    bq, bkv = min(block_q, sq), min(block_kv, sk)
    nq, nk = sq // bq, (sk + bkv - 1) // bkv
    offs = sk - sq
    live = 0
    for i in range(nq):
        for j in range(nk):
            if not causal or j * bkv <= i * bq + bq - 1 + offs:
                live += 1
    flops = 4.0 * b * h * live * bq * bkv * d  # qk^T + pv
    hbm = (
        b * h * (sq * d * dtype_bytes                # q read once
                 + live * bkv * d * 2 * dtype_bytes  # k+v per live tile
                 + sq * d * dtype_bytes)             # out write
    )
    return {
        "FLOPS": flops,
        "HBM_BYTES": float(hbm),
        "VMEM_TILE_REFILLS": float(b * h * (nq + 2 * live)),
        "MXU_PASSES": float(
            b * h * live * (bq // 128 or 1) * (bkv // 128 or 1)
            * 2 * max(1, d // 128)
        ),
        "live_tiles": live,
        "total_tiles": nq * nk,
    }


# ---------------------------------------------------------------------------
# chunked SSM scan
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("chunk", "bd", "interpret")
)
def ssm_scan(log_a, b_in, *, chunk: int = 256, bd: int = 512,
             interpret: bool = False):
    """h_t = exp(log_a_t)*h_{t-1} + b_t over axis 1. [B,S,D] -> [B,S,D]."""
    return _ssm.ssm_scan_chunked(
        log_a, b_in, chunk=chunk, bd=bd, interpret=interpret
    )
