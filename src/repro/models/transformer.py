"""Decoder-only transformer backbone (families: dense, moe, vlm).

Layer stack is a ``lax.scan`` over layer-stacked parameters (compile-time
O(1) in depth), with ScALPEL counters threaded through the scan carry
(core.scan_with_counters) and configurable activation rematerialization.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro import core as scalpel
from repro.dist.partition import shard
from . import layers as L
from . import moe as moe_lib
from .params import stacked
from .spec import ModelConfig


# bucketed serving: prefill accepts a traced ``length`` with right-padded
# tokens — causal attention already guarantees valid positions never read
# the pad tail, and the decode path masks cache slots past ``pos``
SUPPORTS_PREFILL_LENGTH = True


def layer_specs(cfg: ModelConfig) -> dict:
    sp = {
        "ln1": L.rms_norm_spec(cfg.d_model),
        "attn": L.attention_specs(cfg),
        "ln2": L.rms_norm_spec(cfg.d_model),
    }
    if cfg.family == "moe":
        sp["ffn"] = moe_lib.moe_specs(cfg)
    else:
        sp["ffn"] = L.mlp_specs(cfg)
    return sp


def specs(cfg: ModelConfig) -> dict:
    return {
        "embed": L.embed_specs(cfg),
        "layers": stacked(lambda: layer_specs(cfg), cfg.n_layers),
        "final_norm": L.rms_norm_spec(cfg.d_model),
    }


def _ffn(cfg: ModelConfig, lp, x):
    if cfg.family == "moe":
        return moe_lib.moe_ffn(cfg, lp["ffn"], x)
    return L.mlp(cfg, lp["ffn"], x)


def block(cfg: ModelConfig, lp, x, positions):
    with scalpel.function("layer"):
        h = L.rms_norm(x, lp["ln1"])
        x = x + L.attention(cfg, lp["attn"], h, positions,
                            window=cfg.sliding_window)
        h = L.rms_norm(x, lp["ln2"])
        x = x + _ffn(cfg, lp, h)
        x = shard(x, "batch", None, None)
        return x


def backbone(cfg: ModelConfig, params, x, positions):
    """Run the layer stack. x: [b,s,d] -> [b,s,d] (pre-final-norm)."""

    def body(carry, lp):
        return block(cfg, lp, carry, positions), None

    x, _ = scalpel.scan_with_counters(body, x, params["layers"],
                                      remat=L.remat_policy(cfg))
    return x


def forward(cfg: ModelConfig, params, tokens, prefix_embeds=None):
    """Training/prefill forward. tokens: [b,s] -> logits [b,s(,+p),V]."""
    x = L.embed(cfg, params["embed"], tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    positions = jnp.broadcast_to(
        jnp.arange(x.shape[1], dtype=jnp.int32), x.shape[:2]
    )
    x = backbone(cfg, params, x, positions)
    x = L.rms_norm(x, params["final_norm"])
    return L.unembed(cfg, params["embed"], x)


def loss_fn(cfg: ModelConfig, params, batch) -> jnp.ndarray:
    prefix = batch.get("img_embeds")
    logits = forward(cfg, params, batch["tokens"], prefix_embeds=prefix)
    if prefix is not None:
        logits = logits[:, prefix.shape[1]:]
    mask = batch.get("mask")
    return L.cross_entropy(logits, batch["targets"], mask)


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------

def prefill(cfg: ModelConfig, params, tokens, cache_len: int,
            prefix_embeds=None, length=None):
    """Run the full prompt, build a KV cache of size ``cache_len``.

    Returns (cache, last_logits).  cache: {"k","v": [nL,b,kv,S,hd], "pos"}.

    ``length`` (traced i32, None => full width): tokens beyond it are
    right-pad.  Causal attention keeps valid positions exact (they never
    attend forward into the pad), the logits are read at ``length - 1``,
    and ``pos = length`` — decode overwrites the pad K/V slots one per
    step and masks everything past ``pos``, so they are never read.
    """
    x = L.embed(cfg, params["embed"], tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    kvd = jnp.dtype(cfg.compute_dtype)

    def body(carry, lp):
        xx = carry
        with scalpel.function("layer"):
            h = L.rms_norm(xx, lp["ln1"])
            with scalpel.function("attn"):
                q, k, v = L._qkv(cfg, lp["attn"], h, positions)
                a = L.run_attention(cfg, q, k, v, True, cfg.sliding_window)
                y = jnp.einsum("bshk,hkd->bsd", a,
                               lp["attn"]["wo"].astype(xx.dtype))
                if cfg.use_bias:
                    y = y + lp["attn"]["bo"].astype(xx.dtype)
            xx = xx + y
            h = L.rms_norm(xx, lp["ln2"])
            xx = xx + _ffn(cfg, lp, h)
        return xx, {"k": _to_cache(k.astype(kvd), cache_len),
                    "v": _to_cache(v.astype(kvd), cache_len)}

    x, kvs = scalpel.scan_with_counters(body, x, params["layers"])
    x = L.rms_norm(x, params["final_norm"])
    if length is None:
        xl = x[:, -1:, :]
        pos = jnp.asarray(s, jnp.int32)
    else:
        xl = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
        pos = jnp.asarray(length, jnp.int32)
    logits = L.unembed(cfg, params["embed"], xl)
    cache = {"k": kvs["k"], "v": kvs["v"], "pos": pos}
    return cache, logits


def _to_cache(a, cache_len: int):
    """One layer's prompt K or V [b, s, kv, hd] -> its cache [b, kv, S, hd]."""
    a = jnp.swapaxes(a, 1, 2)
    a = jnp.pad(a, ((0, 0), (0, 0), (0, cache_len - a.shape[2]), (0, 0)))
    return shard(a, "batch", None, "kv_seq", None)


def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               abstract: bool = False):
    """The stacked cache, head-major: ``[layers, b, kv, S, hd]``.

    Each KV head's positions lie contiguous, in the order the grouped
    score and mix contract them, so the decode loop reads layer ``i``
    where it lies (a sequence-major cache costs a per-layer copy)."""
    kvd = jnp.dtype(cfg.compute_dtype)
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, cache_len,
             cfg.resolved_head_dim)
    if abstract:
        arr = jax.ShapeDtypeStruct(shape, kvd)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
    else:
        arr = jnp.zeros(shape, kvd)
        pos = jnp.asarray(0, jnp.int32)
    return {"k": arr, "v": arr, "pos": pos}


def cache_axes(cfg: ModelConfig):
    kv = ("layers", "batch", None, "kv_seq", None)
    return {"k": kv, "v": kv, "pos": ()}


def decode_step(cfg: ModelConfig, params, cache, tokens):
    """One decode step for the whole batch. tokens: [b,1] int32.

    The stacked cache rides the layer loop's carry, not its xs/ys: layer
    ``i`` writes the new token's K and V as one row at ``(i, :, :, pos)``
    and its score and mix read layer ``i`` where it lies, so a step never
    copies, slices out or restacks the cache (under the serve driver's
    lane ``vmap`` the row write is a scatter of one row a lane, in place).
    """
    x = L.embed(cfg, params["embed"], tokens)
    pos = cache["pos"]

    def write(stack, new, i):  # new: [b, 1, kv, hd] -> row (i, :, :, pos)
        row = jnp.swapaxes(new, 1, 2)[None].astype(stack.dtype)
        stack = jax.lax.dynamic_update_slice(stack, row, (i, 0, 0, pos, 0))
        return shard(stack, "layers", "batch", None, "kv_seq", None)

    def body(carry, lp):
        xx, ks, vs, i = carry
        with scalpel.function("layer"):
            h = L.rms_norm(xx, lp["ln1"])
            with scalpel.function("attn"):
                q, k_new, v_new = L.decode_qkv(cfg, lp["attn"], h, pos)
                ks, vs = write(ks, k_new, i), write(vs, v_new, i)
                y = L.decode_attend(
                    cfg, lp["attn"], h, q,
                    jax.lax.dynamic_index_in_dim(ks, i, keepdims=False),
                    jax.lax.dynamic_index_in_dim(vs, i, keepdims=False),
                    pos, layout="bgkd")
            xx = xx + y
            h = L.rms_norm(xx, lp["ln2"])
            xx = xx + _ffn(cfg, lp, h)
        return (xx, ks, vs, i + 1), None

    (x, ks, vs, _), _ = scalpel.scan_with_counters(
        body, (x, cache["k"], cache["v"], jnp.int32(0)), params["layers"])
    x = L.rms_norm(x, params["final_norm"])
    logits = L.unembed(cfg, params["embed"], x)
    return logits, {"k": ks, "v": vs, "pos": pos + 1}
