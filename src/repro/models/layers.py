"""Shared model layers, instrumented with ScALPEL scopes.

Every block opens a ``scalpel.function`` scope and probes its live tensors —
the analogue of compiling the application with ``-finstrument-functions``:
the *set* of monitorable functions is fixed by the model code, but whether
anything is computed for a scope is decided by the runtime MonitorParams
(mask) and the call-count multiplexer.

Attention has three execution paths:
  * ``reference``  — materialized probs (smoke tests; probes ATTN_ENTROPY)
  * ``flash_xla``  — chunked online-softmax in pure JAX (lax.scan over KV
                     blocks), bounded memory, TPU-lowerable; the dry-run path
  * ``flash_xla_tri`` — triangle-pair scan that skips fully-masked causal
                     blocks (≈2x fewer attention FLOPs; see §Perf)
  * ``pallas``     — kernels/flash_attn.py (real-TPU hot path)
Decode attention shards the KV cache along *sequence* over the model axis
(flash-decoding style); GSPMD inserts the small max/sum all-reduces.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro import core as scalpel
from repro.dist.partition import shard
from .params import P
from .spec import ModelConfig


def dt(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def remat_policy(cfg: ModelConfig):
    """Remat decorator per config — pass to scan_with_counters(remat=...)."""
    import functools

    if cfg.remat == "none":
        return None
    if cfg.remat == "dots":
        return functools.partial(
            jax.checkpoint, policy=jax.checkpoint_policies.checkpoint_dots
        )
    return jax.checkpoint


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm_spec(d: int) -> P:
    return P((d,), ("embed",), init="ones")


def rms_norm(x, scale, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(
        x.dtype
    )


def head_rms_norm(x, scale, eps: float = 1e-6):
    """qk-norm: normalize over head_dim (qwen3)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(
        x.dtype
    )


# ---------------------------------------------------------------------------
# rope
# ---------------------------------------------------------------------------

def rope(x, positions, theta: float):
    """x: [..., s, h, d]; positions: [..., s] int32."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # [..., s, half]
    cos = jnp.cos(ang)[..., :, None, :]  # broadcast over heads
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def attention_specs(cfg: ModelConfig) -> dict:
    d, h, k, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    sp = {
        "wq": P((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": P((d, k, hd), ("embed", "kv_heads", "head_dim")),
        "wv": P((d, k, hd), ("embed", "kv_heads", "head_dim")),
        "wo": P((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.use_bias:
        sp["bq"] = P((h, hd), ("heads", "head_dim"), init="zeros")
        sp["bo"] = P((d,), ("embed",), init="zeros")
    if cfg.qk_norm:
        sp["q_norm"] = P((hd,), ("head_dim",), init="ones")
        sp["k_norm"] = P((hd,), ("head_dim",), init="ones")
    return sp


def _qkv(cfg: ModelConfig, p, x, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.use_bias:
        q = q + p["bq"].astype(x.dtype)
    if cfg.qk_norm:
        q = head_rms_norm(q, p["q_norm"])
        k = head_rms_norm(k, p["k_norm"])
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    # heads that don't divide the TP axis are relaxed to replicated here;
    # run_attention() pads them to a shardable count before the mixing
    q = shard(q, "batch", None, "heads", None)
    return q, k, v


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(
        k[:, :, :, None, :], (b, s, h, n_rep, d)
    ).reshape(b, s, h * n_rep, d)


def reference_attention(cfg: ModelConfig, q, k, v, causal: bool = True,
                        window: int = 0, scale: float | None = None):
    """Materialized-probs attention (smoke-scale only).  Probes entropy.
    ``scale`` multiplies the scores (default ``1/sqrt(head_dim)``)."""
    k = _repeat_kv(k, q.shape[2] // k.shape[2])
    v = _repeat_kv(v, q.shape[2] // v.shape[2])
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    sq, sk = q.shape[1], k.shape[1]
    qpos = jnp.arange(sq)[:, None] + (sk - sq)
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    scalpel.probe(probs=probs)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v)
    return out


def flash_attention_xla(cfg: ModelConfig, q, k, v, causal: bool = True,
                        window: int = 0, triangle: bool | None = None,
                        scale: float | None = None):
    """Chunked online-softmax attention, pure JAX (lowerable everywhere).

    ``triangle=True`` (default for causal self-attention): one scan over the
    (q_block, kv_block) lower-triangle pairs — exact causal FLOPs, O(1)
    graph size in sequence length.  ``triangle=False``: scan over all KV
    blocks for every Q block with masking (~2x causal FLOP waste; kept as
    the naive baseline measured in §Perf) — and the only path for
    non-square/non-causal attention.
    """
    if triangle is None:
        triangle = causal and q.shape[1] == k.shape[1]
    b, sq, h, d = q.shape
    sk = k.shape[1]
    kvh = k.shape[2]
    n_rep = h // kvh
    bq = min(cfg.flash_block_q, sq)
    bkv = min(cfg.flash_block_kv, sk)
    nq = (sq + bq - 1) // bq
    nk = (sk + bkv - 1) // bkv
    assert sq % bq == 0 and sk % bkv == 0, (sq, bq, sk, bkv)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    offs = sk - sq  # query i attends keys <= i + offs

    qb = q.reshape(b, nq, bq, h, d)
    kb = k.reshape(b, nk, bkv, kvh, d)
    vb = v.reshape(b, nk, bkv, kvh, d)

    def block_scores(qi, kj, iq, jk):
        # qi: [b,bq,h,d] kj: [b,bkv,kvh,d] -> [b,h,bq,bkv]
        kj = _repeat_kv(kj, n_rep)
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, kj).astype(jnp.float32) * scale
        qpos = iq * bq + jnp.arange(bq)[:, None] + offs
        kpos = jk * bkv + jnp.arange(bkv)[None, :]
        m = jnp.ones((bq, bkv), bool)
        if causal:
            m &= kpos <= qpos
        if window:
            m &= kpos > qpos - window
        return jnp.where(m[None, None], s, -1e30)

    def one_q_block(iq, qi):
        def body(carry, jk):
            acc, mx, lse = carry
            kj = jax.lax.dynamic_index_in_dim(kb, jk, 1, keepdims=False)
            vj = jax.lax.dynamic_index_in_dim(vb, jk, 1, keepdims=False)
            s = block_scores(qi, kj, iq, jk)  # [b,h,bq,bkv]
            mx2 = jnp.maximum(mx, jnp.max(s, axis=-1))
            corr = jnp.exp(mx - mx2)
            # guard fully-masked rows: exp(-1e30 - (-1e30)) would be 1
            p = jnp.exp(s - mx2[..., None]) * (s > -1e29)
            vj = _repeat_kv(vj, n_rep)
            pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(q.dtype), vj)
            acc = acc * corr[..., None].astype(q.dtype) + pv
            lse = lse * corr + jnp.sum(p, axis=-1)
            return (acc, mx2, lse), None

        acc0 = jnp.zeros((b, h, bq, d), q.dtype)
        m0 = jnp.full((b, h, bq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, bq), jnp.float32)
        (acc, mx, lse), _ = jax.lax.scan(
            body, (acc0, m0, l0), jnp.arange(nk)
        )
        out = acc / jnp.maximum(lse, 1e-30)[..., None].astype(q.dtype)
        return out.transpose(0, 2, 1, 3)  # [b,bq,h,d]

    if triangle and causal and sq == sk:
        # lower-triangle pair scan: iterate (iq, jk<=iq) pairs once.
        pairs = [(i, j) for i in range(nq) for j in range(i + 1)]
        iqs = jnp.array([p[0] for p in pairs])
        jks = jnp.array([p[1] for p in pairs])

        def body(carry, t):
            acc, mx, lse, outbuf = carry
            iq, jk = iqs[t], jks[t]
            qi = jax.lax.dynamic_index_in_dim(qb, iq, 1, keepdims=False)
            kj = jax.lax.dynamic_index_in_dim(kb, jk, 1, keepdims=False)
            vj = jax.lax.dynamic_index_in_dim(vb, jk, 1, keepdims=False)
            fresh = jk == 0
            acc = jnp.where(fresh, 0.0, acc)
            mx = jnp.where(fresh, -jnp.inf, mx)
            lse = jnp.where(fresh, 0.0, lse)
            s = block_scores(qi, kj, iq, jk)
            mx2 = jnp.maximum(mx, jnp.max(s, axis=-1))
            corr = jnp.exp(mx - mx2)
            p = jnp.exp(s - mx2[..., None]) * (s > -1e29)
            vjr = _repeat_kv(vj, n_rep)
            pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(q.dtype), vjr)
            acc = acc * corr[..., None].astype(q.dtype) + pv
            lse = lse * corr + jnp.sum(p, axis=-1)
            done = jk == iq
            out = (acc / jnp.maximum(lse, 1e-30)[..., None].astype(q.dtype)
                   ).transpose(0, 2, 1, 3)
            outbuf = jnp.where(
                done,
                jax.lax.dynamic_update_index_in_dim(outbuf, out, iq, 1),
                outbuf,
            )
            return (acc, mx2, lse, outbuf), None

        acc0 = jnp.zeros((b, h, bq, d), q.dtype)
        m0 = jnp.full((b, h, bq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, bq), jnp.float32)
        ob0 = jnp.zeros((b, nq, bq, h, d), q.dtype)
        (_, _, _, outbuf), _ = jax.lax.scan(
            body, (acc0, m0, l0, ob0), jnp.arange(len(pairs))
        )
        return outbuf.reshape(b, sq, h, d)

    # masked path: scan over q blocks, full kv scan inside (O(1) graph size)
    def outer(_, iq):
        qi = jax.lax.dynamic_index_in_dim(qb, iq, 1, keepdims=False)
        return None, one_q_block(iq, qi)

    _, outs = jax.lax.scan(outer, None, jnp.arange(nq))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, d)
    return out


def run_attention(cfg: ModelConfig, q, k, v, causal: bool = True,
                  window: int = 0, scale: float | None = None):
    """Dispatch the sequence-mixing implementation, with head padding.
    ``scale`` multiplies the scores (default ``1/sqrt(head_dim)``).

    When n_heads does not divide the TP axis (qwen3-14b: 40 heads on a
    16-way model axis) the heads are PADDED to the next multiple so the
    attention itself stays head-sharded — +hpad/h extra attention work vs
    the tp-times redundant replicated fallback it replaces (EXPERIMENTS.md
    §Perf, qwen3_14b iteration).
    """
    from repro.dist.partition import axis_size

    impl = cfg.attn_impl
    sq = q.shape[1]
    if impl == "reference" or sq <= 256:
        return reference_attention(cfg, q, k, v, causal, window, scale)

    tp = axis_size("model")
    h = q.shape[2]
    hpad = -(-h // tp) * tp if tp > 1 else h
    sliced = False
    if hpad != h:
        n_rep = h // k.shape[2]
        k = _repeat_kv(k, n_rep)
        v = _repeat_kv(v, n_rep)
        padh = ((0, 0), (0, 0), (0, hpad - h), (0, 0))
        q = jnp.pad(q, padh)
        k = jnp.pad(k, padh)
        v = jnp.pad(v, padh)
        q = shard(q, "batch", None, "heads", None)
        k = shard(k, "batch", None, "heads", None)
        v = shard(v, "batch", None, "heads", None)
        sliced = True

    if impl == "pallas":
        from repro.kernels import ops as kops

        out = kops.flash_attention(
            q, k, v, causal=causal,
            block_q=cfg.flash_block_q, block_kv=cfg.flash_block_kv,
            scale=scale,
        )
    elif impl == "flash_xla_naive":
        out = flash_attention_xla(cfg, q, k, v, causal, window,
                                  triangle=False, scale=scale)
    elif impl == "flash_xla_tri":
        out = flash_attention_xla(cfg, q, k, v, causal, window,
                                  triangle=True, scale=scale)
    else:  # "flash_xla" and default: custom-VJP memory-optimal path
        out = flash_attention_cvjp(cfg, q, k, v, causal, window, scale)
    if sliced:
        out = out[:, :, :h]
    return out


def attention(cfg: ModelConfig, p, x, positions, causal: bool = True,
              window: int = 0):
    """Full attention block: projections + mixing + output projection."""
    with scalpel.function("attn"):
        q, k, v = _qkv(cfg, p, x, positions)
        scalpel.probe(q=q, k=k, v=v)
        out = run_attention(cfg, q, k, v, causal, window)
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
        if cfg.use_bias:
            y = y + p["bo"].astype(x.dtype)
        y = shard(y, "batch", None, None)
        scalpel.probe(out=y)
        return y


def decode_qkv(cfg: ModelConfig, p, x, pos):
    """The decode token's q, k and v. x: [b, 1, d]; ``pos`` its position."""
    positions = jnp.broadcast_to(pos, (x.shape[0], 1)).astype(jnp.int32)
    return _qkv(cfg, p, x, positions)


def decode_attend(cfg: ModelConfig, p, x, q, cache_k, cache_v, pos,
                  scale: float | None = None, layout: str = "bkgd"):
    """Score and mix one query token against a cache that already holds it.

    q: [b, 1, h, hd]; cache_{k,v} laid out as ``layout`` names it: "bkgd"
    is [b, S, kv, hd], "bgkd" is [b, kv, S, hd], "bgdk" is [b, kv, hd, S];
    slots past ``pos`` are masked.  ``scale`` multiplies the scores (default ``1/sqrt(head_dim)``).
    Returns y [b, 1, d] after the output projection.

    Grouped-query form: the query heads are grouped by the KV head they
    share and contracted against the cache as stored, so the cache is never
    repeated to the query heads (decode is bound by its KV reads).
    """
    b, _, h, hd = q.shape
    S, kv = cache_k.shape[layout.index("k")], cache_k.shape[layout.index("g")]
    qg = q.reshape(b, 1, kv, h // kv, hd)  # head g*(h/kv)+r -> (g, r)
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum(f"bqgrd,{layout}->bgrqk", qg, cache_k.astype(x.dtype),
                   preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(S)
    valid = kpos <= pos
    if cfg.sliding_window:
        valid = valid & (kpos > pos - cfg.sliding_window)
    s = jnp.where(valid, s, -1e30)
    p_attn = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(f"bgrqk,{layout}->bqgrd", p_attn.astype(x.dtype),
                     cache_v.astype(x.dtype)).reshape(b, 1, h, hd)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    if cfg.use_bias:
        y = y + p["bo"].astype(x.dtype)
    scalpel.probe(out=y)
    return y


def decode_attention(cfg: ModelConfig, p, x, cache_k, cache_v, pos,
                     scale: float | None = None):
    """One-token decode against one layer's sequence-sharded KV cache.

    x: [b, 1, d]; cache_{k,v}: [b, S, kv, hd] with S sharded over 'model'
    (flash-decoding-style sequence parallelism — GSPMD inserts the small
    softmax all-reduces); ``pos``: the new token's write position, int32,
    scalar for the batch or one per lane under ``vmap``.
    Returns (y [b,1,d], cache_k', cache_v').

    For a caller that holds one cache per attention site.  The dense layer
    stack writes and reads its own stacked cache (``transformer.
    decode_step``) through ``decode_qkv`` and ``decode_attend``.
    """
    with scalpel.function("attn"):
        q, k_new, v_new = decode_qkv(cfg, p, x, pos)
        cache_k = jax.lax.dynamic_update_slice_in_dim(
            cache_k, k_new.astype(cache_k.dtype), pos, axis=1
        )
        cache_v = jax.lax.dynamic_update_slice_in_dim(
            cache_v, v_new.astype(cache_v.dtype), pos, axis=1
        )
        cache_k = shard(cache_k, "batch", "kv_seq", None, None)
        cache_v = shard(cache_v, "batch", "kv_seq", None, None)
        y = decode_attend(cfg, p, x, q, cache_k, cache_v, pos, scale)
        return y, cache_k, cache_v


# ---------------------------------------------------------------------------
# flash attention v2: custom-VJP (memory-optimal backward)
#
# The scan-based flash_attention_xla above is exact but its reverse-mode
# stores every block's probability tile across the pair scan — the dry-run
# breakdown showed stacked f32[n_pairs, b, h, bq, bkv] residual buffers
# dominating the memory roofline term (EXPERIMENTS.md §Perf, memory
# iteration).  This version severs the residual chain with jax.custom_vjp:
# the forward saves only (q, k, v, out, lse) and the backward recomputes
# probability tiles blockwise — the standard flash-attention backward,
# expressed in pure JAX so it lowers everywhere (Pallas kernels/flash_attn
# is the real-TPU fast path of the same algorithm).
# ---------------------------------------------------------------------------

def _fa_blocks(x, blk):
    b, s, h, d = x.shape
    return x.reshape(b, s // blk, blk, h, d)


def _tile_mask(iq, jk, bq, bkv, offs, causal, window, sk):
    qpos = iq * bq + jnp.arange(bq)[:, None] + offs
    kpos = jk * bkv + jnp.arange(bkv)[None, :]
    m = kpos < sk
    if causal:
        m &= kpos <= qpos
    if window:
        m &= kpos > qpos - window
    return m


def _tile_live(iq, jk, bq, bkv, offs, causal, window):
    live = jnp.bool_(True)
    if causal:
        live &= (jk * bkv) <= (iq * bq + bq - 1 + offs)
    if window:
        live &= (jk * bkv + bkv - 1) > (iq * bq + offs - window)
    return live


def _flash_fwd_scan(q, k, v, causal, window, bq, bkv, scale):
    """Returns (out [b,sq,h,d], lse [b,h,sq//bq,bq]) — q-block outer scan
    (stacked outputs, no growing carry), kv-block inner scan with tile
    skipping."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    offs = sk - sq
    qb = _fa_blocks(q, bq)
    kb = _fa_blocks(k, bkv)
    vb = _fa_blocks(v, bkv)
    nq, nk = sq // bq, sk // bkv

    def q_block(_, iq):
        qi = jax.lax.dynamic_index_in_dim(qb, iq, 1, False)  # [b,bq,h,d]
        qi_f = qi.astype(jnp.float32)

        def kv_step(carry, jk):
            acc, mx, lse = carry

            def work(args):
                acc, mx, lse = args
                kj = jax.lax.dynamic_index_in_dim(kb, jk, 1, False)
                vj = jax.lax.dynamic_index_in_dim(vb, jk, 1, False)
                s = jnp.einsum("bqhd,bkhd->bhqk", qi_f,
                               kj.astype(jnp.float32)) * scale
                m = _tile_mask(iq, jk, bq, bkv, offs, causal, window, sk)
                s = jnp.where(m[None, None], s, -1e30)
                mx2 = jnp.maximum(mx, jnp.max(s, axis=-1))
                corr = jnp.exp(mx - mx2)
                p = jnp.exp(s - mx2[..., None])
                p = jnp.where(m[None, None], p, 0.0)
                pv = jnp.einsum("bhqk,bkhd->bhqd", p,
                                vj.astype(jnp.float32))
                return (acc * corr[..., None] + pv,
                        mx2, lse * corr + jnp.sum(p, axis=-1))

            return jax.lax.cond(
                _tile_live(iq, jk, bq, bkv, offs, causal, window),
                work, lambda a: a, (acc, mx, lse),
            ), None

        acc0 = jnp.zeros((b, h, bq, d), jnp.float32)
        m0 = jnp.full((b, h, bq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, bq), jnp.float32)
        (acc, mx, lse), _ = jax.lax.scan(kv_step, (acc0, m0, l0),
                                         jnp.arange(nk))
        out = (acc / jnp.maximum(lse, 1e-30)[..., None]).transpose(0, 2, 1, 3)
        # signed lse for the backward: log(sum exp(s - 0)) = mx + log(lse)
        lse_log = mx + jnp.log(jnp.maximum(lse, 1e-30))
        return None, (out.astype(q.dtype), lse_log)

    _, (outs, lses) = jax.lax.scan(q_block, None, jnp.arange(nq))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, d)
    return out, lses.transpose(1, 2, 0, 3)  # [b,h,nq,bq]


def _flash_bwd_scan(res, dout, causal, window, bq, bkv, scale):
    q, k, v, out, lse = res          # lse: [b,h,nq,bq]
    b, sq, h, d = q.shape
    sk = k.shape[1]
    offs = sk - sq
    qb = _fa_blocks(q, bq)
    kb = _fa_blocks(k, bkv)
    vb = _fa_blocks(v, bkv)
    dob = _fa_blocks(dout.astype(jnp.float32), bq)
    ob = _fa_blocks(out.astype(jnp.float32), bq)
    nq, nk = sq // bq, sk // bkv
    # D_i = rowsum(dO * O)  [b,nq,bq,h] -> [b,h,nq,bq]
    Dfull = jnp.sum(dob * ob, axis=-1).transpose(0, 3, 1, 2)

    def p_tile(iq, jk):
        qi = jax.lax.dynamic_index_in_dim(qb, iq, 1, False)
        kj = jax.lax.dynamic_index_in_dim(kb, jk, 1, False)
        s = jnp.einsum("bqhd,bkhd->bhqk", qi.astype(jnp.float32),
                       kj.astype(jnp.float32)) * scale
        m = _tile_mask(iq, jk, bq, bkv, offs, causal, window, sk)
        s = jnp.where(m[None, None], s, -1e30)
        lse_i = jax.lax.dynamic_index_in_dim(lse, iq, 2, False)  # [b,h,bq]
        p = jnp.exp(s - lse_i[..., None])
        p = jnp.where(m[None, None], p, 0.0)
        return p, qi, kj

    # ---- dq: scan over q blocks, inner over kv ---------------------------
    def dq_block(_, iq):
        doi = jax.lax.dynamic_index_in_dim(dob, iq, 1, False)  # [b,bq,h,d]
        doi_t = doi.transpose(0, 2, 1, 3)                      # [b,h,bq,d]
        Di = jax.lax.dynamic_index_in_dim(Dfull, iq, 2, False)  # [b,h,bq]

        def kv_step(dq, jk):
            def work(dq):
                p, qi, kj = p_tile(iq, jk)
                dp = jnp.einsum("bhqd,bkhd->bhqk", doi_t,
                                jax.lax.dynamic_index_in_dim(
                                    vb, jk, 1, False).astype(jnp.float32))
                ds = p * (dp - Di[..., None]) * scale
                return dq + jnp.einsum("bhqk,bkhd->bqhd", ds,
                                       kj.astype(jnp.float32))

            return jax.lax.cond(
                _tile_live(iq, jk, bq, bkv, offs, causal, window),
                work, lambda x: x, dq,
            ), None

        dq0 = jnp.zeros((b, bq, h, d), jnp.float32)
        dqi, _ = jax.lax.scan(kv_step, dq0, jnp.arange(nk))
        return None, dqi

    _, dqs = jax.lax.scan(dq_block, None, jnp.arange(nq))
    dq = dqs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, d)

    # ---- dk/dv: scan over kv blocks, inner over q -------------------------
    def dkv_block(_, jk):
        vj = jax.lax.dynamic_index_in_dim(vb, jk, 1, False)

        def q_step(carry, iq):
            dk_j, dv_j = carry

            def work(args):
                dk_j, dv_j = args
                p, qi, kj = p_tile(iq, jk)
                doi = jax.lax.dynamic_index_in_dim(
                    dob, iq, 1, False).transpose(0, 2, 1, 3)
                Di = jax.lax.dynamic_index_in_dim(Dfull, iq, 2, False)
                dv_j = dv_j + jnp.einsum("bhqk,bhqd->bkhd", p, doi)
                dp = jnp.einsum("bhqd,bkhd->bhqk", doi,
                                vj.astype(jnp.float32))
                ds = p * (dp - Di[..., None]) * scale
                dk_j = dk_j + jnp.einsum("bhqk,bqhd->bkhd", ds,
                                         qi.astype(jnp.float32))
                return dk_j, dv_j

            return jax.lax.cond(
                _tile_live(iq, jk, bq, bkv, offs, causal, window),
                work, lambda a: a, (dk_j, dv_j),
            ), None

        z = jnp.zeros((b, bkv, h, d), jnp.float32)
        (dk_j, dv_j), _ = jax.lax.scan(q_step, (z, z), jnp.arange(nq))
        return None, (dk_j, dv_j)

    _, (dks, dvs) = jax.lax.scan(dkv_block, None, jnp.arange(nk))
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(b, sk, h, d)
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(b, sk, h, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_cvjp(q, k, v, causal, window, bq, bkv, scale):
    out, _ = _flash_fwd_scan(q, k, v, causal, window, bq, bkv, scale)
    return out


def _flash_cvjp_fwd(q, k, v, causal, window, bq, bkv, scale):
    out, lse = _flash_fwd_scan(q, k, v, causal, window, bq, bkv, scale)
    return out, (q, k, v, out, lse)


def _flash_cvjp_bwd(causal, window, bq, bkv, scale, res, dout):
    return _flash_bwd_scan(res, dout, causal, window, bq, bkv, scale)


_flash_cvjp.defvjp(_flash_cvjp_fwd, _flash_cvjp_bwd)


def flash_attention_cvjp(cfg: ModelConfig, q, k, v, causal: bool = True,
                         window: int = 0, scale: float | None = None):
    """Flash attention with the memory-optimal custom-VJP backward.

    GQA is handled by repeating KV up front (the repeat is elementwise and
    fuses; the backward sums gradient over the repeat groups).  ``scale``
    multiplies the scores (default ``1/sqrt(head_dim)``).
    """
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    n_rep = h // kvh
    if n_rep > 1:
        k = _repeat_kv(k, n_rep)
        v = _repeat_kv(v, n_rep)
    sk = k.shape[1]
    bq = min(cfg.flash_block_q, sq)
    bkv = min(cfg.flash_block_kv, sk)
    assert sq % bq == 0 and sk % bkv == 0, (sq, bq, sk, bkv)
    out = _flash_cvjp(q, k, v, causal, window, bq, bkv,
                      1.0 / math.sqrt(d) if scale is None else scale)
    return out


# ---------------------------------------------------------------------------
# mlp
# ---------------------------------------------------------------------------

def mlp_specs(cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "wi": P((d, f), ("embed", "mlp")),
        "wg": P((d, f), ("embed", "mlp")),
        "wo": P((f, d), ("mlp", "embed")),
    }


def mlp(cfg: ModelConfig, p, x):
    with scalpel.function("mlp"):
        h = jnp.einsum("bsd,df->bsf", x, p["wi"].astype(x.dtype))
        g = jnp.einsum("bsd,df->bsf", x, p["wg"].astype(x.dtype))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * h
        h = shard(h, "batch", None, "mlp")
        y = jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(x.dtype))
        y = shard(y, "batch", None, None)
        scalpel.probe(out=y)
        return y


# ---------------------------------------------------------------------------
# embedding / unembedding / loss
# ---------------------------------------------------------------------------

def embed_specs(cfg: ModelConfig) -> dict:
    # std 0.02 (GPT-2 convention) keeps tied-unembedding logits at a sane
    # scale: rms_norm output has unit per-dim RMS, so logit std ~ 0.02*sqrt(d).
    sp = {"table": P((cfg.vocab, cfg.d_model), ("vocab", "embed"),
                     scale=0.02)}
    if not cfg.tie_embeddings:
        sp["unembed"] = P((cfg.d_model, cfg.vocab), ("embed", "vocab"))
    return sp


def embed(cfg: ModelConfig, p, tokens):
    with scalpel.function("embed"):
        x = jnp.take(p["table"].astype(dt(cfg)), tokens, axis=0)
        x = shard(x, "batch", None, None)
        scalpel.probe(out=x)
        return x


def unembed(cfg: ModelConfig, p, x):
    with scalpel.function("logits"):
        if cfg.tie_embeddings:
            w = p["table"].astype(x.dtype).T
        else:
            w = p["unembed"].astype(x.dtype)
        logits = jnp.einsum("bsd,dv->bsv", x, w)
        logits = shard(logits, "batch", None, "vocab")
        scalpel.probe(out=logits)
        return logits


def cross_entropy(logits, targets, mask=None):
    """logits [b,s,V] (possibly vocab-sharded), targets [b,s] int32."""
    lf = logits.astype(jnp.float32)
    m = jnp.max(lf, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
    ll = jnp.sum(
        lf * jax.nn.one_hot(targets, lf.shape[-1], dtype=jnp.float32),
        axis=-1,
    )
    nll = lse - ll
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    with scalpel.function("loss"):
        scalpel.probe(loss=loss[None])
    return loss
