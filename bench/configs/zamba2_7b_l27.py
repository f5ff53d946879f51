"""Plain reference of the Zamba2 stage in ``zamba2_7b_l27.json``.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
a full causal forward pass over prompt and served tokens, with no cache,
no bucketing and no batching; it imports nothing of the program.  Weights
come from the seed by the rule the program's parameter tree documents: one
``jax.random.split`` of ``PRNGKey(seed)`` over the leaves in the tree's
order (dictionary keys sorted, list entries in order), each leaf
``normal * scale`` rounded to the configured ``param_dtype``, where a leaf
without a stated scale takes ``1/sqrt(prod(shape[:-1]))`` of its stored
shape.  The tree holds the layers as runs (run 0 up to the first site,
each later run from a site to the next), each run's leaves stacked over
its layers; the two shared blocks and each site's adapter and ``linear``
are leaves of their own.  They are made in one jitted call and kept in
that dtype; each layer is widened to float32 as it is used.

The model (Zamba2-7B, arXiv:2411.15242 and its config.json): every layer
is ``x <- x + mamba2(rms_norm(x + t))``, with ``t = 0`` except at a site,
where the shared block ``k % num_mem_blocks`` of the k-th site gives

    h = rms_norm(concat(x, x0));  a = attention(h)      (x0: the embedding)
    t = linear_k(down(gelu(gate) * up)),  [gate | up] = (W + U_k D_k) rms_norm(a)

Attention: q, k, v from the 2d-wide ``h``, rotary embedding over two
halves of each head (theta ``rope_theta``), causal softmax of the scores
scaled by ``(head_dim / 2) ** -0.5``, computed in blocks of query rows.
Mamba2: in_proj (stored ``[out, in]``, scale ``(n * d_model) ** -0.5`` in
a run of n layers, as its ``[in, out]`` form takes) to [z | x, B, C | dt];
a causal depthwise conv of width ``d_conv`` (zero initial state, with
bias) and SiLU over [x, B, C];
``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the SSD by its
sequential recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
``y_t = S_t C_t + D x_t``, the heads split evenly over the groups of B and
C; then ``y * silu(z)`` normalized over each group's channels, and
out_proj.  RMS norms take eps ``rms_norm_eps``; the unembedding is the
tied embedding table.

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
EPS = 1e-5
Q_BLOCK = 1024
READ_BLOCK = 256


def _runs(m: dict) -> list[tuple[int, int, int | None]]:
    ids = list(m["hybrid"]["hybrid_layer_ids"])
    bounds = sorted({0, *ids, m["n_layers"]})
    return [(a, b, ids.index(a) if a in ids else None)
            for a, b in zip(bounds[:-1], bounds[1:])]


def _dims(m: dict) -> dict:
    d, s = m["d_model"], m["ssm"]
    di = s["expand"] * d
    return {"d": d, "f": m["d_ff"], "v": m["vocab"], "h": m["n_heads"],
            "kv": m["n_kv_heads"], "hd": m["head_dim"] or 2 * d // m["n_heads"],
            "di": di, "p": s["head_dim"], "nh": di // s["head_dim"],
            "g": s["n_groups"], "N": s["d_state"], "kw": s["d_conv"],
            "r": m["hybrid"]["adapter_rank"]}


def _leaf_specs(m: dict) -> dict:
    """{path tuple: (shape, init, scale)}; list entries are int keys."""
    z = _dims(m)
    d, f, di, nh, g, N = z["d"], z["f"], z["di"], z["nh"], z["g"], z["N"]
    ones, normal, zeros = "ones", "normal", "zeros"
    specs = {("embed", "table"): ((z["v"], d), normal, 0.02),
             ("final_norm",): ((d,), ones, None)}
    if not m.get("tie_embeddings"):
        specs[("embed", "unembed")] = ((d, z["v"]), normal, None)
    ch = di + 2 * g * N
    for i, (a, b, _) in enumerate(_runs(m)):
        n = b - a
        layer = {
            ("ln",): ((n, d), ones, None),
            ("mix", "in_proj"): ((n, 2 * di + 2 * g * N + nh, d), normal,
                                 (n * d) ** -0.5),
            ("mix", "conv_w"): ((n, z["kw"], ch), normal, 0.5),
            ("mix", "conv_b"): ((n, ch), zeros, None),
            ("mix", "A_log"): ((n, nh), zeros, None),
            ("mix", "dt_bias"): ((n, nh), zeros, None),
            ("mix", "D"): ((n, nh), ones, None),
            ("mix", "norm"): ((n, di), ones, None),
            ("mix", "out_proj"): ((n, di, d), normal, None),
        }
        specs.update({("mamba", i) + k: v for k, v in layer.items()})
    h, kv, hd = z["h"], z["kv"], z["hd"]
    for j in range(m["hybrid"]["num_mem_blocks"]):
        block = {
            ("ln_attn",): ((2 * d,), ones, None),
            ("attn", "wq"): ((2 * d, h, hd), normal, None),
            ("attn", "wk"): ((2 * d, kv, hd), normal, None),
            ("attn", "wv"): ((2 * d, kv, hd), normal, None),
            ("attn", "wo"): ((h, hd, d), normal, None),
            ("ln_mlp",): ((d,), ones, None),
            ("gate_up",): ((d, 2 * f), normal, None),
            ("down",): ((f, d), normal, None),
        }
        specs.update({("blocks", j) + k: v for k, v in block.items()})
    for k in range(len(m["hybrid"]["hybrid_layer_ids"])):
        site = {
            ("adapter_in",): ((d, z["r"]), normal, None),
            ("adapter_out",): ((z["r"], 2 * f), normal, None),
            ("linear",): ((d, d), normal, None),
        }
        specs.update({("sites", k) + key: v for key, v in site.items()})
    return specs


def param_count(m: dict) -> dict:
    """Parameters a served token passes through (``total``: each shared
    block once per site it serves, and the tied table as embedding and as
    unembedding), in the input embedding and in the unembedding, and the
    parameters held (``stored``)."""
    specs = _leaf_specs(m)
    size = {k: int(np.prod(s[0])) for k, s in specs.items()}
    stored = sum(size.values())
    nb = m["hybrid"]["num_mem_blocks"]
    n_sites = len(m["hybrid"]["hybrid_layer_ids"])
    total = stored
    for j in range(nb):
        uses = len(range(j, n_sites, nb))
        block = sum(v for k, v in size.items() if k[:2] == ("blocks", j))
        total += (uses - 1) * block
    table = size[("embed", "table")]
    unembed = size.get(("embed", "unembed"), table)
    if m.get("tie_embeddings"):
        total += table
    return {"total": total, "embed": table, "unembed": unembed,
            "stored": stored}


def init_params(m: dict, seed: int) -> dict:
    """{path tuple: array in the configured dtype}, made in one jitted
    call."""
    specs = _leaf_specs(m)
    paths = sorted(specs)
    dtype = jnp.dtype(m["param_dtype"])

    def make(key):
        keys = jax.random.split(key, len(paths))
        out = {}
        for path, k in zip(paths, keys):
            shape, init, scale = specs[path]
            if init in ("ones", "zeros"):
                out[path] = (jnp.ones if init == "ones" else jnp.zeros)(
                    shape, dtype)
                continue
            if scale is None:
                scale = 1.0 / math.sqrt(max(1, int(np.prod(shape[:-1]))))
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


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) \
        * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = pos[:, None].astype(jnp.float32) * freqs          # [s, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(m, bp, h, control):
    s = h.shape[0]
    hd = _dims(m)["hd"]
    pos = jnp.arange(s)
    q = _rope(_mm("sd,dhk->shk", h, bp["attn/wq"], control), pos,
              m["rope_theta"])
    k = _rope(_mm("sd,dhk->shk", h, bp["attn/wk"], control), pos,
              m["rope_theta"])
    v = _mm("sd,dhk->shk", h, bp["attn/wv"], control)
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scale = (hd / 2) ** -0.5

    def block(_, i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        sc = _mm("qhd,khd->hqk", qi, k, control) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(pos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        return None, _mm("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v,
                         control)

    _, out = jax.lax.scan(block, None, jnp.arange(s // Q_BLOCK))
    return _mm("shk,hkd->sd", out.reshape(q.shape), bp["attn/wo"], control)


def _shared(m, bp, sp, x, x0, control):
    """A site: the shared block's output ``t`` for its layer's Mamba2."""
    h = _rms(jnp.concatenate([x, x0], -1), bp["ln_attn"])
    a = _rms(_attention(m, bp, h, control), bp["ln_mlp"])
    gu = _mm("sd,df->sf", a, bp["gate_up"], control) + _mm(
        "sr,rf->sf", _mm("sd,dr->sr", a, sp["adapter_in"], control),
        sp["adapter_out"], control)
    gate, up = jnp.split(gu, 2, axis=-1)
    y = _mm("sf,fd->sd", jax.nn.gelu(gate, approximate=False) * up,
            bp["down"], control)
    return _mm("sd,de->se", y, sp["linear"], control)


def _mamba2(m, lp, h, control):
    z_ = _dims(m)
    s = h.shape[0]
    di, nh, p, g, N, kw = (z_["di"], z_["nh"], z_["p"], z_["g"], z_["N"],
                           z_["kw"])
    proj = _mm("sd,ed->se", h, lp["mix/in_proj"], control)
    z, xbc, dt = jnp.split(proj, [di, 2 * di + 2 * g * N], axis=-1)
    w = lp["mix/conv_w"].astype(jnp.float32)
    xp = jnp.concatenate([jnp.zeros((kw - 1, xbc.shape[1])), xbc], 0)
    xbc = sum(xp[i:i + s] * w[i] for i in range(kw)) \
        + lp["mix/conv_b"].astype(jnp.float32)
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :di].reshape(s, nh, p)
    B = jnp.repeat(xbc[:, di:di + g * N].reshape(s, g, N), nh // g, axis=1)
    C = jnp.repeat(xbc[:, di + g * N:].reshape(s, g, N), nh // g, axis=1)
    dt = jax.nn.softplus(dt + lp["mix/dt_bias"].astype(jnp.float32))
    A = -jnp.exp(lp["mix/A_log"].astype(jnp.float32))

    def step(S, inp):
        xt, dtt, Bt, Ct = inp                     # [nh,p] [nh] [nh,N] [nh,N]
        S = S * jnp.exp(dtt * A)[:, None, None] \
            + (dtt[:, None] * xt)[:, :, None] * Bt[:, None, :]
        return S, _mm("hpn,hn->hp", S, Ct, control)

    _, y = jax.lax.scan(step, jnp.zeros((nh, p, N), jnp.float32),
                        (x, dt, B, C))
    y = (y + x * lp["mix/D"].astype(jnp.float32)[:, None]).reshape(s, di)
    y = (y * jax.nn.silu(z)).reshape(s, g, di // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + EPS)
    y = y.reshape(s, di) * lp["mix/norm"].astype(jnp.float32)
    return _mm("se,ed->sd", y, lp["mix/out_proj"], control)


def _group(params: dict, prefix: tuple) -> dict:
    n = len(prefix)
    return {"/".join(map(str, k[n:])): v for k, v in params.items()
            if k[:n] == prefix}


def logits_at(m: dict, params: dict, tokens, reads, control: bool = False):
    """Logits of one sequence at the positions ``reads``.

    tokens: [s] int32 with ``s`` a multiple of ``Q_BLOCK``; reads: [r]
    int32.  Returns [r, vocab] float32."""
    table = params[("embed", "table")]
    x = table[tokens].astype(jnp.float32)
    x0 = x
    t = jnp.zeros_like(x)
    nb = m["hybrid"]["num_mem_blocks"]
    for i, (_, _, site) in enumerate(_runs(m)):
        if site is not None:
            t = _shared(m, _group(params, ("blocks", site % nb)),
                        _group(params, ("sites", site)), x, x0, control)

        def layer(carry, lp):
            xx, tt = carry
            xx = xx + _mamba2(m, lp, _rms(xx + tt, lp["ln"]), control)
            return (xx, jnp.zeros_like(tt)), None

        (x, t), _ = jax.lax.scan(layer, (x, t), _group(params, ("mamba", i)))
    x = _rms(x[reads], params[("final_norm",)])
    w = params.get(("embed", "unembed"), table.T)
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
