"""Plain reference of the dense decoder in ``qwen3_14b_l8.json``.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
a full causal forward pass over prompt and served tokens, with no KV
cache, no bucketing and no batching; it imports nothing of the program.
Weights come from the seed by the rule the program's parameter tree
documents: one ``jax.random.split`` of ``PRNGKey(seed)`` over the leaves in
sorted-key order, each leaf ``normal * scale`` rounded to the configured
``param_dtype``, where a leaf without a stated scale takes
``1/sqrt(prod(shape[:-1]))`` of its stored (layer-stacked) shape.  They are
made in one jitted call and kept in that dtype; each layer is widened to
float32 as it is used.

A layer: RMS norm, grouped-query attention (query head ``i`` reads
key/value head ``i // (heads / kv_heads)``) with per-head RMS norm of q
and k, rotary embedding over two halves of the head, causal softmax scaled
by ``1/sqrt(head_dim)``; then RMS norm and a SiLU-gated MLP.  Attention is
computed in blocks of query rows so that the scores fit.

``control=True`` is the lower-precision control: every matmul operand is
rounded to float8 e4m3 with a per-tensor scale.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0
Q_BLOCK = 1024
READ_BLOCK = 256


def _leaf_specs(m: dict) -> dict:
    d, h, kv, v = m["d_model"], m["n_heads"], m["n_kv_heads"], m["vocab"]
    hd, f, n = m["head_dim"], m["d_ff"], m["n_layers"]
    ones, normal = "ones", "normal"
    specs = {
        "embed/table": ((v, d), normal, 0.02),
        "embed/unembed": ((d, v), normal, None),
        "final_norm": ((d,), ones, None),
        "layers/attn/wq": ((n, d, h, hd), normal, None),
        "layers/attn/wk": ((n, d, kv, hd), normal, None),
        "layers/attn/wv": ((n, d, kv, hd), normal, None),
        "layers/attn/wo": ((n, h, hd, d), normal, None),
        "layers/ffn/wi": ((n, d, f), normal, None),
        "layers/ffn/wg": ((n, d, f), normal, None),
        "layers/ffn/wo": ((n, f, d), normal, None),
        "layers/ln1": ((n, d), ones, None),
        "layers/ln2": ((n, d), ones, None),
    }
    if m.get("qk_norm"):
        specs["layers/attn/q_norm"] = ((n, hd), ones, None)
        specs["layers/attn/k_norm"] = ((n, hd), ones, None)
    if m.get("tie_embeddings"):
        del specs["embed/unembed"]
    return specs


def param_count(m: dict) -> dict:
    """Parameters in all, in the input embedding and in the unembedding."""
    specs = _leaf_specs(m)
    total = sum(int(np.prod(s[0])) for s in specs.values())
    table = int(np.prod(specs["embed/table"][0]))
    unembed = int(np.prod(specs["embed/unembed"][0])) \
        if "embed/unembed" in specs else table
    return {"total": total, "embed": table, "unembed": unembed}


def init_params(m: dict, seed: int) -> dict:
    """{path: array in the configured dtype}, made in one jitted call."""
    specs = _leaf_specs(m)
    paths = sorted(specs, key=lambda p: tuple(p.split("/")))
    dtype = jnp.dtype(m["param_dtype"])

    def make(key):
        keys = jax.random.split(key, len(paths))
        out = {}
        for path, k in zip(paths, keys):
            shape, init, scale = specs[path]
            if init == "ones":
                out[path] = jnp.ones(shape, dtype)
                continue
            if scale is None:
                fan_in = int(np.prod(shape[:-1])) if len(shape) > 1 \
                    else shape[0]
                scale = 1.0 / math.sqrt(max(1, fan_in))
            out[path] = (jax.random.normal(k, shape, jnp.float32)
                         * scale).astype(dtype)
        return out

    return jax.jit(make)(jax.random.PRNGKey(seed))


def _f8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, control):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if control:
        a, b = _f8(a), _f8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = pos[:, None].astype(jnp.float32) * freqs          # [s, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(m, lp, x, control):
    s = x.shape[0]
    h, kv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    pos = jnp.arange(s)
    q = _mm("sd,dhk->shk", x, lp["attn/wq"], control)
    k = _mm("sd,dhk->shk", x, lp["attn/wk"], control)
    v = _mm("sd,dhk->shk", x, lp["attn/wv"], control)
    if "attn/q_norm" in lp:
        q = _rms(q, lp["attn/q_norm"])
        k = _rms(k, lp["attn/k_norm"])
    q = _rope(q, pos, m["rope_theta"])
    k = _rope(k, pos, m["rope_theta"])
    rep = h // kv
    k = jnp.repeat(k, rep, axis=1)                          # [s, h, hd]
    v = jnp.repeat(v, rep, axis=1)
    nb = s // Q_BLOCK

    def block(_, i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        sc = _mm("qhd,khd->hqk", qi, k, control) / math.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(pos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        return None, _mm("hqk,khd->qhd", pr, v, control)

    _, out = jax.lax.scan(block, None, jnp.arange(nb))
    out = out.reshape(s, h, hd)
    return _mm("shk,hkd->sd", out, lp["attn/wo"], control)


def _layer(m, lp, x, control):
    x = x + _attention(m, lp, _rms(x, lp["ln1"]), control)
    hh = _rms(x, lp["ln2"])
    g = _mm("sd,df->sf", hh, lp["ffn/wg"], control)
    u = _mm("sd,df->sf", hh, lp["ffn/wi"], control)
    return x + _mm("sf,fd->sd", jax.nn.silu(g) * u, lp["ffn/wo"], control)


def logits_at(m: dict, params: dict, tokens, reads, control: bool = False):
    """Logits of one sequence at the positions ``reads``.

    tokens: [s] int32 with ``s`` a multiple of ``Q_BLOCK``; reads: [r]
    int32.  Returns [r, vocab] float32."""
    x = params["embed/table"][tokens].astype(jnp.float32)
    layers = {k[len("layers/"):]: v for k, v in params.items()
              if k.startswith("layers/")}

    def body(x, lp):
        return _layer(m, lp, x, control), None

    x, _ = jax.lax.scan(body, x, layers)
    x = _rms(x[reads], params["final_norm"])
    w = params.get("embed/unembed", params["embed/table"].T)
    return _mm("rd,dv->rv", x, w, control)


def _padded(n: int, block: int) -> int:
    return -(-n // block) * block


def request_gaps(logits_fn, params: dict, prompt, served,
                 control: bool = False) -> np.ndarray:
    """One finished request: the reference runs once over the prompt and
    its served tokens and, at each position that produced a served token,
    reads how far that token's logit lies below the reference's best.

    ``control`` computes the same positions in the lower-precision control
    and reads the gap of the token it puts first instead."""
    p, n = len(prompt), len(served)
    seq = np.concatenate([prompt, served]).astype(np.int32)
    tokens = np.zeros(_padded(len(seq), Q_BLOCK), np.int32)
    tokens[:len(seq)] = seq
    reads = np.full(_padded(n, READ_BLOCK), p - 1, np.int32)
    reads[:n] = np.arange(p - 1, p + n - 1, dtype=np.int32)
    tokens, reads = jnp.asarray(tokens), jnp.asarray(reads)
    ref = logits_fn(params, tokens, reads, False)[:n]
    if control:
        picked = jnp.argmax(logits_fn(params, tokens, reads, True)[:n], -1)
    else:
        picked = jnp.asarray(served)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, picked[:, None], axis=-1)[:, 0]
    return np.asarray(best - got)


def check_requests(m: dict, seed: int, served: list[dict],
                   control: bool = False) -> list[np.ndarray]:
    """``request_gaps`` of each served request, with the weights made once
    from the seed."""
    params = init_params(m, seed)
    fn = jax.jit(functools.partial(logits_at, m), static_argnums=(3,))
    return [request_gaps(fn, params, s["prompt"], s["tokens"], control)
            for s in served]
