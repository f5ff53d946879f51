"""Fused single-pass probe reduction — the monitoring hot path's kernel.

A scope probing ACT_RMS, ACT_MEAN_ABS, ACT_MAX_ABS, ACT_ZERO_FRAC, NAN_COUNT
and INF_COUNT used to sweep the same activation once *per event*: six HBM
reads of one tensor to produce six scalars.  This kernel computes the raw
*moment vector*

    [sum, sum_sq, sum_abs, max_abs, zero_count, nan_count, inf_count, numel]

in ONE tiled sweep with a VMEM accumulator; every moment-derived event is
then a cheap scalar finalizer over this vector (events.py stage 2).  The
probe-plan layer (core/plan.py) may additionally request the optional
``ent_sum`` channel (sum of x*log(x+eps), the raw accumulator behind
ATTN_ENTROPY) — a static kernel variant with one extra lane of the same
sweep, so even entropy-bearing scopes read their tensor exactly once.  The
same batching-of-counter-collection argument appears in Scaler and LIKWID:
monitoring stays lightweight only if counter reads share their passes over
the data.

Layout: the input is viewed as (lead, rows, cols) — its leading axes
collapsed, which moves no data — and a 3-D grid walks it in blocks of about
``BLOCK_ELEMS`` elements (``block_shape``); partial moments accumulate into
a (1, 8) f32 output block that every grid step maps to (revisiting
semantics keep it VMEM-resident).  Edge blocks may run ragged past the end
of the array; out-of-bounds elements are masked via their index, so
non-tile-aligned shapes are exact — and never pay a pad copy.  NaNs propagate through sum/sum_sq/sum_abs/max_abs
exactly as they do through the unfused ``jnp`` reductions, so fused and
legacy event values agree even on poisoned tensors.

``jax.experimental.pallas`` is imported lazily so this module (which owns
the moment-vector contract) stays importable from the core event registry
without dragging the full kernel stack in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Canonical moment order — the contract between this kernel, the jnp
# reference/fallback, and the event finalizers in core/events.py.
MOMENTS = (
    "sum",
    "sum_sq",
    "sum_abs",
    "max_abs",
    "zero_count",
    "nan_count",
    "inf_count",
    "numel",
)
(
    M_SUM,
    M_SUM_SQ,
    M_SUM_ABS,
    M_MAX_ABS,
    M_ZERO,
    M_NAN,
    M_INF,
    M_NUMEL,
) = range(len(MOMENTS))

# Optional fused channel (probe-plan layer): sum of x*log(x+eps), the raw
# accumulator behind ATTN_ENTROPY.  Appended AFTER the base vector so every
# M_* index above stays valid whether or not a plan requests entropy.
ENT_EPS = 1e-9
MOMENTS_ENT = MOMENTS + ("ent_sum",)
M_ENT = len(MOMENTS)

# Trace-time-constant channels the sweep never has to compute: element count
# and last-axis row count (prod(shape[:-1]) — the divisor of a row-mean such
# as attention entropy).  core/events.CHANNELS = sweep channels + these.
STATIC_CHANNELS = ("numel", "rows")

LANES = 128  # TPU vector lane count; last-axis tile width


def static_channel_values(shape) -> dict:
    """{static channel: f32 constant} for a tensor of ``shape`` (free)."""
    import numpy as np

    numel = int(np.prod(shape)) if shape else 1
    last = shape[-1] if shape else 1
    rows = numel // last if last else 0
    return {"numel": jnp.float32(numel), "rows": jnp.float32(rows)}


def _moment_kernel(x_ref, o_ref, *, dims: tuple, block: tuple,
                   with_entropy: bool):
    """One grid step: fold one (lead, rows, cols) block into the
    accumulator.

    Grid axes are (lead block, row block, column block).  Blocks on the
    far edge of an axis the block does not tile may run past the end of
    the array (ragged tail) — out-of-bounds elements carry unspecified
    values, so every use of ``x`` is then select-masked by the element's
    index along each ragged axis before any reduction.
    """
    import jax.experimental.pallas as pl

    n_chan = len(MOMENTS_ENT) if with_entropy else len(MOMENTS)
    pids = [pl.program_id(a) for a in range(3)]

    @pl.when((pids[0] == 0) & (pids[1] == 0) & (pids[2] == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    valid = None
    for axis, (n, b, pid) in enumerate(zip(dims, block, pids)):
        if n % b:
            idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis) + pid * b
            valid = idx < n if valid is None else valid & (idx < n)
    # NaN/Inf survive in valid elements
    xm = x if valid is None else jnp.where(valid, x, 0.0)

    def count(pred):
        if valid is not None:
            pred = valid & pred
        return jnp.sum(jnp.where(pred, 1.0, 0.0))

    ax = jnp.abs(xm)
    zero = jnp.float32(0.0)
    channels = [
        jnp.sum(xm),
        jnp.sum(xm * xm),
        jnp.sum(ax),
        zero,  # max channel handled below (max, not add)
        count(x == 0),
        count(jnp.isnan(x)),
        count(jnp.isinf(x)),
        zero,  # numel is a trace-time constant, written by the wrapper:
        # accumulating the mask sum in f32 would round above 2^24 elements
    ]
    if with_entropy:
        # masked lanes contribute 0*log(eps) == 0; NaN/-x propagate exactly
        # like the unfused reference p*log(p+eps)
        channels.append(jnp.sum(xm * jnp.log(xm + jnp.float32(ENT_EPS))))
    part = jnp.stack(channels).reshape(1, n_chan)

    acc = o_ref[...]
    chan = jax.lax.broadcasted_iota(jnp.int32, (1, n_chan), 1)
    new_max = jnp.maximum(acc[0, M_MAX_ABS], jnp.max(ax))
    o_ref[...] = jnp.where(chan == M_MAX_ABS, new_max, acc + part)


# Default block budget, in elements of the block's VMEM footprint (its two
# minor dims padded to the (sublane, lane) tile): 1 MiB of f32 per buffer.
BLOCK_ELEMS = 1 << 18


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _fit(n: int, limit: int, align: int) -> int:
    """Block length along a dim of ``n``: ``n`` itself when it fits in
    ``limit``, else the largest multiple of ``align`` within ``limit``
    (at least ``align``) — preferring, within a factor of two, one that
    divides ``n``, since a ragged tail costs a mask on every block."""
    top = max(align, limit // align * align)
    if n <= max(limit, top):
        return n
    for b in range(top, top // 2, -align):
        if n % b == 0:
            return b
    return top


def block_shape(dims: tuple, itemsize: int,
                block_elems: int = BLOCK_ELEMS) -> tuple[int, int, int]:
    """(lead, rows, cols) block of a ``dims`` view for the moment kernel.

    Mosaic wants each of the two minor block dims to be either the whole
    array dim or a multiple of its hardware tile (``LANES`` columns; 8 rows
    of 32-bit, 16 of 16-bit, 32 of 8-bit data).  Within that, the block
    takes as much as ``block_elems`` of padded VMEM holds: whole rows when
    one row tile of them fits (else lane-aligned column tiles, which widen
    as rows get fewer), then whole slabs of rows, then as many whole slabs
    along ``lead`` as fit — so a tensor of many small slabs (per-head
    q/k/v) runs in few grid steps.  A budget below one hardware tile is
    raised to one tile.
    """
    lead, rows, cols = dims
    sub = max(8, 32 // itemsize)
    bc = _fit(cols, block_elems // sub, LANES)
    pbc = _round_up(bc, LANES)
    br = _fit(rows, block_elems // pbc, sub) if bc == cols else min(rows, sub)
    if (br, bc) != (rows, cols):
        return 1, br, bc
    return _fit(lead, max(1, block_elems // (_round_up(rows, sub) * pbc)),
                1), br, bc


def moments_pallas(x, *, block_elems: int = BLOCK_ELEMS,
                   interpret: bool = False, with_entropy: bool = False):
    """Raw moment vector f32[8] (f32[9] with entropy) in a single tiled pass.

    The input is viewed as ``(lead, rows, cols)`` — every axis before the
    last two collapsed into ``lead``, which is a layout-preserving reshape
    on TPU (its tiled layout covers only the two minor dims), never a copy.
    The grid walks that view in ``block_shape`` blocks; blocks past the end
    of a ragged dim are masked in-kernel — no ``jnp.pad``, which would
    re-materialize the whole tensor and double the HBM traffic the kernel
    exists to remove.  bf16 and f32 inputs lower alike, and ``jax.vmap``
    (the serve engine probes each lane inside one) only prepends a grid
    axis.  ``with_entropy`` (static, plan-driven) appends the ``ent_sum``
    channel to the same sweep.
    """
    n = int(x.size)
    if n == 0:
        return moments_ref(x, with_entropy=with_entropy)
    n_chan = len(MOMENTS_ENT) if with_entropy else len(MOMENTS)
    shape = (1, 1) + tuple(x.shape)
    rows, cols = shape[-2], shape[-1]
    x3 = x.reshape(-1, rows, cols)
    dims = tuple(x3.shape)
    block = block_shape(dims, jnp.dtype(x.dtype).itemsize, block_elems)
    grid = tuple(-(-d // b) for d, b in zip(dims, block))

    import jax.experimental.pallas as pl

    out = pl.pallas_call(
        functools.partial(_moment_kernel, dims=dims, block=block,
                          with_entropy=with_entropy),
        grid=grid,
        in_specs=[pl.BlockSpec(block, lambda l, i, j: (l, i, j))],
        out_specs=pl.BlockSpec((1, n_chan), lambda l, i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, n_chan), jnp.float32),
        interpret=interpret,
    )(x3)
    return out[0].at[M_NUMEL].set(jnp.float32(n))


def moments_ref(x, *, with_entropy: bool = False):
    """Pure-jnp oracle: the same moment vector from unfused reductions."""
    xf = x.astype(jnp.float32).reshape(-1)
    ax = jnp.abs(xf)
    n = xf.size
    chans = [
        jnp.sum(xf),
        jnp.sum(xf * xf),
        jnp.sum(ax),
        jnp.max(ax) if n else jnp.float32(0.0),
        jnp.sum((xf == 0).astype(jnp.float32)),
        jnp.sum(jnp.isnan(xf).astype(jnp.float32)),
        jnp.sum(jnp.isinf(xf).astype(jnp.float32)),
        jnp.float32(n),
    ]
    if with_entropy:
        chans.append(jnp.sum(xf * jnp.log(xf + jnp.float32(ENT_EPS))))
    return jnp.stack(chans)


def named_moments_jnp(x, names) -> dict:
    """Only the requested channels, as a {name: f32 scalar} dict.

    The fallback the probe path uses off-TPU.  The probe-plan layer hands in
    the EXACT per-event-set channel tuple, so the sweep computes nothing an
    inactive slot would need.  All requested accumulators ride ONE variadic
    ``lax.reduce`` — XLA:CPU lowers this to a single loop over the data with
    k accumulator updates (measured ~3x faster than k sibling ``jnp``
    reductions at 1 MiB), so the single-pass property holds even where the
    Pallas kernel doesn't run.  ``numel``/``rows`` are trace-time constants
    and cost nothing (always included).
    """
    sweep = MOMENTS_ENT[:M_NUMEL] + ("ent_sum",)
    need = [n for n in sweep if n in set(names)]
    out: dict = dict(static_channel_values(x.shape))  # constants, free
    if not need:
        return out
    if x.size == 0:
        ref = moments_ref(x, with_entropy=True)
        out.update((n, ref[MOMENTS_ENT.index(n)]) for n in need)
        return out
    xf = x.astype(jnp.float32).reshape(-1)
    ax = jnp.abs(xf)  # shared producer; fused into the reduce by XLA
    producers = {
        "sum": lambda: xf,
        "sum_sq": lambda: xf * xf,
        "sum_abs": lambda: ax,
        "max_abs": lambda: ax,
        "zero_count": lambda: (xf == 0).astype(jnp.float32),
        "nan_count": lambda: jnp.isnan(xf).astype(jnp.float32),
        "inf_count": lambda: jnp.isinf(xf).astype(jnp.float32),
        "ent_sum": lambda: xf * jnp.log(xf + jnp.float32(ENT_EPS)),
    }
    operands = tuple(producers[n]() for n in need)
    inits = tuple(jnp.float32(0.0) for _ in need)
    is_max = tuple(n == "max_abs" for n in need)

    def combine(acc, val):
        return tuple(
            jnp.maximum(a, v) if mx else a + v
            for a, v, mx in zip(acc, val, is_max)
        )

    res = jax.lax.reduce(operands, inits, combine, (0,))
    out.update(zip(need, res))
    return out
