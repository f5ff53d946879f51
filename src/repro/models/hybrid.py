"""Zamba2 hybrid (family 'hybrid'): a Mamba2 backbone with shared
attention+MLP blocks, as published for Zamba2-7B (arXiv:2411.15242;
https://huggingface.co/Zyphra/Zamba2-7B-Instruct/blob/main/config.json).

Every layer runs one Mamba2 mixer, ``x <- x + mamba2(rms_norm(x + t))``,
where ``t`` is 0 except at the layers named in ``hybrid.hybrid_layer_ids``
("sites").  The k-th site runs shared block ``k % num_mem_blocks`` (A, B,
A, B, ... for two blocks) on the hidden state ``x`` and the embedding
output ``x0`` (in decode, the current token's embedding):

    h = rms_norm(concat(x, x0))                   # 2 * d_model wide
    a = attn(h)      # q, k, v: 2d -> heads x head_dim; RoPE; o: -> d
    m = mlp(rms_norm(a))  # gate_up + the site's rank-r adapter, gelu * up
    t = linear_site(m)                            # d -> d, one per site

The block's output is added to the input of that layer's Mamba2 (before
its norm), not to the residual.  Attention scores are scaled by
``(head_dim / 2) ** -0.5``, as Zamba2's attention (which runs at twice the
model width) does.

The layers are kept as runs: run 0 from layer 0 up to the first site, each
later run from a site up to the next.  A run's Mamba2 parameters (and, in
serving, its SSD and conv states) are stacked and scanned; each block,
site and site KV cache is its own leaf, so no program slices a weight or a
cache out of a larger one.  In decode a run's state stacks ride the layer
loop's carry: layer ``i`` reads its state where it lies and writes the new
one back in place (``_decode_run``).

A site's KV cache is stored ``[batch, kv_heads, head_dim, seq]``,
sequence-minor, the order the chip lays the entry buffer out in (head_dim
224 is not padded there); decode writes one column at ``pos`` and attends
through ``layers.decode_attend`` in that order, so no step re-lays it out.

Sub-quadratic backbone -> runs long_500k; the sites' KV caches are
sequence-sharded in decode (``cache_axes``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import core as scalpel
from repro.dist.partition import shard
from . import layers as L
from . import ssm
from .params import P, stacked
from .spec import ModelConfig

# bucketed serving: positions past a traced ``length`` take dt = 0 in every
# Mamba2 layer (the SSD state passes through them unchanged), the conv state
# is sliced at ``length``, attention is causal, and decode overwrites the
# sites' pad KV slots one per step and masks everything past ``pos``
SUPPORTS_PREFILL_LENGTH = True

NORM_EPS = ssm.NORM_EPS


def runs(cfg: ModelConfig) -> list[tuple[int, int, int | None]]:
    """(first layer, end, site index or None) of each run of layers."""
    ids = cfg.hybrid.hybrid_layer_ids
    if list(ids) != sorted(set(ids)) or (ids and not
                                         0 <= ids[0] <= ids[-1] < cfg.n_layers):
        raise ValueError(f"hybrid_layer_ids {ids} must increase within "
                         f"[0, {cfg.n_layers})")
    bounds = sorted({0, *ids, cfg.n_layers})
    return [(a, b, ids.index(a) if a in ids else None)
            for a, b in zip(bounds[:-1], bounds[1:])]


def attn_cfg(cfg: ModelConfig) -> ModelConfig:
    """The shared blocks' attention reads concat(x, x0), 2 * d_model wide."""
    return cfg.replace(name=cfg.name + "-shared", d_model=2 * cfg.d_model,
                       family="dense")


def attn_scale(cfg: ModelConfig) -> float:
    return (attn_cfg(cfg).resolved_head_dim / 2) ** -0.5


def block_specs(cfg: ModelConfig) -> dict:
    acfg = attn_cfg(cfg)
    d, f = cfg.d_model, cfg.d_ff
    h, kv, hd = acfg.n_heads, acfg.n_kv_heads, acfg.resolved_head_dim
    return {
        "ln_attn": L.rms_norm_spec(2 * d),
        "attn": {
            "wq": P((2 * d, h, hd), ("embed", "heads", "head_dim")),
            "wk": P((2 * d, kv, hd), ("embed", "kv_heads", "head_dim")),
            "wv": P((2 * d, kv, hd), ("embed", "kv_heads", "head_dim")),
            "wo": P((h, hd, d), ("heads", "head_dim", "embed")),
        },
        "ln_mlp": L.rms_norm_spec(d),
        "gate_up": P((d, 2 * f), ("embed", "mlp")),
        "down": P((f, d), ("mlp", "embed")),
    }


def site_specs(cfg: ModelConfig) -> dict:
    d, f, r = cfg.d_model, cfg.d_ff, cfg.hybrid.adapter_rank
    return {
        "adapter_in": P((d, r), ("embed", None)),
        "adapter_out": P((r, 2 * f), (None, "mlp")),
        "linear": P((d, d), (None, "embed")),
    }


def specs(cfg: ModelConfig) -> dict:
    def run(n):
        mix = ssm.mamba2_specs(cfg)
        # in_proj is stored [out, in]: it keeps the scale that the stacked
        # [in, out] form takes by the default fan-in, 1/sqrt(n * d_model)
        mix["in_proj"] = dataclasses.replace(
            mix["in_proj"], scale=(n * cfg.d_model) ** -0.5)
        return stacked(lambda: {"ln": L.rms_norm_spec(cfg.d_model),
                                "mix": mix}, n)

    return {
        "embed": L.embed_specs(cfg),
        "mamba": [run(b - a) for a, b, _ in runs(cfg)],
        "blocks": [block_specs(cfg)
                   for _ in range(cfg.hybrid.num_mem_blocks)],
        "sites": [site_specs(cfg) for _ in cfg.hybrid.hybrid_layer_ids],
        "final_norm": L.rms_norm_spec(cfg.d_model),
    }


def _mamba_layer(cfg: ModelConfig, lp, x, t, state=None, length=None):
    with scalpel.function("layer"):
        h = L.rms_norm(x + t, lp["ln"], NORM_EPS)
        if state is None:
            y, st = ssm.mamba2(cfg, lp["mix"], h, length=length)
        else:
            y, st = ssm.mamba2_decode(cfg, lp["mix"], h, *state)
        return x + y, st


def _run(cfg: ModelConfig, lp, x, t, length=None, remat=None):
    """One run's stacked layers over a sequence; ``t`` enters the first
    layer only.  Returns x and the run's stacked (ssm, conv) states."""

    def body(carry, lpi):
        xx, tt = carry
        xx, st = _mamba_layer(cfg, lpi, xx, tt, length=length)
        return (xx, jnp.zeros_like(tt)), st

    (x, _), st = scalpel.scan_with_counters(body, (x, t), lp, remat=remat)
    return x, st


def _decode_run(cfg: ModelConfig, lp, x, t, s_ssm, s_conv):
    """One run's stacked layers for one token.  The run's state stacks ride
    the loop's carry, not its xs/ys: layer ``i`` reads its state where it
    lies and writes the new one back at ``i``, so a step never restacks
    them (under the serve driver's lane ``vmap`` ``i`` is unbatched, and
    the write is in place)."""

    def body(carry, lpi):
        xx, tt, ss, cs, i = carry
        st = (jax.lax.dynamic_index_in_dim(ss, i, keepdims=False),
              jax.lax.dynamic_index_in_dim(cs, i, keepdims=False))
        xx, (s2, c2) = _mamba_layer(cfg, lpi, xx, tt, st)
        ss = jax.lax.dynamic_update_index_in_dim(ss, s2.astype(ss.dtype), i, 0)
        cs = jax.lax.dynamic_update_index_in_dim(cs, c2.astype(cs.dtype), i, 0)
        return (xx, jnp.zeros_like(tt), ss, cs, i + 1), None

    (x, _, ss, cs, _), _ = scalpel.scan_with_counters(
        body, (x, t, s_ssm, s_conv, jnp.int32(0)), lp)
    return x, (ss, cs)


def _site(cfg: ModelConfig, bp, sp, x, x0, attend):
    """One site's shared block: ``t`` for its layer's Mamba2, and what
    ``attend`` (the attention, in its own ``attn`` scope) returns beside
    its output: the site's KV."""
    with jax.named_scope("scalpel.shared_attn"), \
            scalpel.function("shared_attn"):
        h = L.rms_norm(jnp.concatenate([x, x0], axis=-1), bp["ln_attn"],
                       NORM_EPS)
        a, kv = attend(attn_cfg(cfg), bp["attn"], h)
        h = L.rms_norm(a, bp["ln_mlp"], NORM_EPS)
        with scalpel.function("mlp"):
            gu = jnp.einsum("bsd,df->bsf", h, bp["gate_up"].astype(x.dtype))
            low = jnp.einsum("bsd,dr->bsr", h,
                             sp["adapter_in"].astype(x.dtype))
            gu = gu + jnp.einsum("bsr,rf->bsf", low,
                                 sp["adapter_out"].astype(x.dtype))
            g, u = jnp.split(gu, 2, axis=-1)
            m = jax.nn.gelu(g.astype(jnp.float32),
                            approximate=False).astype(x.dtype) * u
            m = shard(m, "batch", None, "mlp")
            m = jnp.einsum("bsf,fd->bsd", m, bp["down"].astype(x.dtype))
            scalpel.probe(out=m)
        t = jnp.einsum("bsd,de->bse", m, sp["linear"].astype(x.dtype))
        t = shard(t, "batch", None, None)
        scalpel.probe(out=t)
        return t, kv


def _backbone(cfg: ModelConfig, params, x, length=None, remat=None):
    """Every layer over a whole sequence: (x, [(ssm, conv) states per run],
    [(k, v) per site])."""
    x0 = x
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    scale = attn_scale(cfg)

    def attend(acfg, p, h):
        with scalpel.function("attn"):
            q, k, v = L._qkv(acfg, p, h, positions)
            scalpel.probe(q=q, k=k, v=v)
            o = L.run_attention(acfg, q, k, v, True, scale=scale)
            y = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(h.dtype))
            y = shard(y, "batch", None, None)
            scalpel.probe(out=y)
            return y, (k, v)

    t = jnp.zeros_like(x)
    states, kvs = [], []
    for (_, _, site), lp in zip(runs(cfg), params["mamba"]):
        if site is not None:
            bp = params["blocks"][site % cfg.hybrid.num_mem_blocks]
            t, kv = _site(cfg, bp, params["sites"][site], x, x0, attend)
            kvs.append(kv)
        x, st = _run(cfg, lp, x, t, length=length, remat=remat)
        states.append(st)
    return x, states, kvs


def forward(cfg: ModelConfig, params, tokens, prefix_embeds=None):
    x = L.embed(cfg, params["embed"], tokens)
    x, _, _ = _backbone(cfg, params, x, remat=L.remat_policy(cfg))
    x = L.rms_norm(x, params["final_norm"], NORM_EPS)
    return L.unembed(cfg, params["embed"], x)


def loss_fn(cfg: ModelConfig, params, batch):
    logits = forward(cfg, params, batch["tokens"])
    return L.cross_entropy(logits, batch["targets"], batch.get("mask"))


# -- serving ---------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               abstract: bool = False):
    acfg = attn_cfg(cfg)
    kvd = jnp.dtype(cfg.compute_dtype)
    m = ssm.mamba2_state_specs(cfg, batch)

    def stack_n(sd, n):
        return jax.ShapeDtypeStruct((n,) + sd.shape, sd.dtype)

    kv = jax.ShapeDtypeStruct(
        (batch, acfg.n_kv_heads, acfg.resolved_head_dim, cache_len), kvd)
    sites = cfg.hybrid.hybrid_layer_ids
    cache = {
        "mamba_ssm": [stack_n(m["ssm"], b - a) for a, b, _ in runs(cfg)],
        "mamba_conv": [stack_n(m["conv"], b - a) for a, b, _ in runs(cfg)],
        "site_k": [kv for _ in sites],
        "site_v": [kv for _ in sites],
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }
    if abstract:
        return cache
    return jax.tree.map(
        lambda sd: jnp.zeros(sd.shape, sd.dtype), cache,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )


def cache_axes(cfg: ModelConfig):
    n_runs, n_sites = len(runs(cfg)), len(cfg.hybrid.hybrid_layer_ids)
    kv = ("batch", None, None, "kv_seq")
    return {
        "mamba_ssm": [("layers", "batch", "heads", None, None)] * n_runs,
        "mamba_conv": [("layers", "batch", None, None)] * n_runs,
        "site_k": [kv] * n_sites,
        "site_v": [kv] * n_sites,
        "pos": (),
    }


def decode_step(cfg: ModelConfig, params, cache, tokens):
    """One decode step.  Each site writes the token's K and V as one column
    at ``pos`` of its ``[b, kv, hd, S]`` cache and attends in that order;
    each run updates its state stacks in place (``_decode_run``)."""
    x = L.embed(cfg, params["embed"], tokens)
    # zamba's global skip uses the *current token's* embedding in decode
    x0 = x
    pos = cache["pos"]
    scale = attn_scale(cfg)

    def write(c, new):  # new: [b, 1, kv, hd] -> column (:, :, :, pos)
        col = jnp.transpose(new, (0, 2, 3, 1)).astype(c.dtype)
        c = jax.lax.dynamic_update_slice(c, col, (0, 0, 0, pos))
        return shard(c, "batch", None, None, "kv_seq")

    t = jnp.zeros_like(x)
    new = {"mamba_ssm": [], "mamba_conv": [], "site_k": [], "site_v": []}
    for (_, _, site), lp, s_ssm, s_conv in zip(
            runs(cfg), params["mamba"], cache["mamba_ssm"],
            cache["mamba_conv"]):
        if site is not None:
            def attend(acfg, p, h, site=site):
                with scalpel.function("attn"):
                    q, k_new, v_new = L.decode_qkv(acfg, p, h, pos)
                    kc = write(cache["site_k"][site], k_new)
                    vc = write(cache["site_v"][site], v_new)
                    y = L.decode_attend(acfg, p, h, q, kc, vc, pos,
                                        scale=scale, layout="bgdk")
                    return y, (kc, vc)

            bp = params["blocks"][site % cfg.hybrid.num_mem_blocks]
            t, (kc, vc) = _site(cfg, bp, params["sites"][site], x, x0,
                                attend)
            new["site_k"].append(kc)
            new["site_v"].append(vc)
        x, (s2, c2) = _decode_run(cfg, lp, x, t, s_ssm, s_conv)
        new["mamba_ssm"].append(s2)
        new["mamba_conv"].append(c2)
    x = L.rms_norm(x, params["final_norm"], NORM_EPS)
    logits = L.unembed(cfg, params["embed"], x)
    return logits, dict(new, pos=pos + 1)


def prefill(cfg: ModelConfig, params, tokens, cache_len: int,
            prefix_embeds=None, length=None):
    """Prompt pass building every Mamba2 state and the sites' KV caches.

    ``length`` (traced i32, None => full width): tokens beyond it are
    right-pad.  Every state leaving the pass is exactly that of the
    unpadded prompt (``SUPPORTS_PREFILL_LENGTH``), the logits are read at
    ``length - 1`` and ``pos = length``."""
    x = L.embed(cfg, params["embed"], tokens)
    s = x.shape[1]
    x, states, kvs = _backbone(cfg, params, x, length=length)
    x = L.rms_norm(x, params["final_norm"], NORM_EPS)
    if length is None:
        xl = x[:, -1:, :]
        pos = jnp.asarray(s, jnp.int32)
    else:
        xl = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
        pos = jnp.asarray(length, jnp.int32)
    logits = L.unembed(cfg, params["embed"], xl)
    kvd = jnp.dtype(cfg.compute_dtype)

    def to_cache(a):  # [b, s, kv, hd] -> [b, kv, hd, cache_len]
        a = jnp.transpose(a.astype(kvd), (0, 2, 3, 1))
        a = jnp.pad(a, ((0, 0), (0, 0), (0, 0), (0, cache_len - s)))
        return shard(a, "batch", None, None, "kv_seq")

    cache = {
        "mamba_ssm": [st[0] for st in states],
        "mamba_conv": [st[1] for st in states],
        "site_k": [to_cache(k) for k, _ in kvs],
        "site_v": [to_cache(v) for _, v in kvs],
        "pos": pos,
    }
    return cache, logits
