"""Plain reference of the xLSTM language model in ``xlstm_l12_d768.json``.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
with no kernel, no chunking and no cache; it imports nothing of the
program.  Weights come from the seed by the same rule the program's
parameter tree documents: one ``jax.random.split`` of ``PRNGKey(seed)``
over the leaves in sorted-key order, each leaf ``normal * scale`` rounded
to the configured ``param_dtype``, where a leaf without a stated scale
takes ``1/sqrt(prod(shape[:-1]))`` of its stored (layer-stacked) shape.

The blocks (as the configuration runs them, pairs of one mLSTM and one
sLSTM block, each behind an RMS norm on the residual stream):

* mLSTM, whole-sequence parallel form: ``D[i, j] = F_i - F_j + log_i[j]``
  for ``j <= i`` with ``F`` the running sum of ``log_f``, stabiliser
  ``m_i = max(F_i, max_j D[i, j])``, ``h_i = sum_j S_ij e^(D_ij - m_i) v_j
  / max(|sum_j S_ij e^(D_ij - m_i)|, 1)`` with ``S_ij = q_i.k_j / sqrt(p)``.
* sLSTM, one time step after another: exponential input gate and
  sigmoid forget gate with stabiliser ``m``, ``h = o * c / max(|n|, 1)``,
  then a GELU-gated feed-forward of width ``int(4 d / 3)`` rounded down to
  even.

``control=True`` is the lower-precision control: every matmul operand is
rounded to float8 e4m3 with a per-tensor scale (straight through for the
gradient), the rest as above.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0  # largest finite float8_e4m3fn


def _leaf_specs(m: dict) -> dict:
    """{path: (shape, init, scale)} of the parameter tree, stacked over
    the pairs of blocks as the program stores them."""
    d, nh, v = m["d_model"], m["n_heads"], m["vocab"]
    n = m["n_layers"] // 2
    di, kw = 2 * d, m["ssm"]["d_conv"]
    dh = d // nh
    f = int(d * 4 / 3) // 2 * 2
    ones, zeros, normal = "ones", "zeros", "normal"
    specs = {
        "embed/table": ((v, d), normal, 0.02),
        "embed/unembed": ((d, v), normal, None),
        "final_norm": ((d,), ones, None),
        "pairs/m_ln": ((n, d), ones, None),
        "pairs/m/up": ((n, d, 2 * di), normal, None),
        "pairs/m/conv_w": ((n, kw, di), normal, 0.5),
        "pairs/m/conv_b": ((n, di), zeros, None),
        "pairs/m/wq": ((n, di, di), normal, None),
        "pairs/m/wk": ((n, di, di), normal, None),
        "pairs/m/wv": ((n, di, di), normal, None),
        "pairs/m/w_if": ((n, di, 2 * nh), normal, 0.02),
        "pairs/m/b_if": ((n, 2 * nh), zeros, None),
        "pairs/m/norm": ((n, di), ones, None),
        "pairs/m/down": ((n, di, d), normal, None),
        "pairs/m/skip": ((n, di), ones, None),
        "pairs/s_ln": ((n, d), ones, None),
        "pairs/s/w": ((n, d, 4 * d), normal, None),
        "pairs/s/r": ((n, nh, dh, 4 * dh), normal, 0.02),
        "pairs/s/b": ((n, 4 * d), zeros, None),
        "pairs/s/norm": ((n, d), ones, None),
        "pairs/s/up_g": ((n, d, f), normal, None),
        "pairs/s/up_h": ((n, d, f), normal, None),
        "pairs/s/down": ((n, f, d), normal, None),
    }
    if m.get("tie_embeddings"):
        del specs["embed/unembed"]
    return specs


def _sorted_paths(specs: dict) -> list[str]:
    """Leaf order of a nested dict flattened with its keys sorted."""
    def key(path):
        return tuple(path.split("/"))
    return sorted(specs, key=key)


def param_count(m: dict) -> dict:
    """Parameters in all, in the input embedding and in the unembedding."""
    specs = _leaf_specs(m)
    total = sum(int(np.prod(s[0])) for s in specs.values())
    table = int(np.prod(specs["embed/table"][0]))
    unembed = int(np.prod(specs["embed/unembed"][0])) \
        if "embed/unembed" in specs else table
    return {"total": total, "embed": table, "unembed": unembed}


def init_params(m: dict, seed: int) -> dict:
    """{path: float32 array} holding the configured-dtype weights."""
    specs = _leaf_specs(m)
    paths = _sorted_paths(specs)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(paths))
    dtype = jnp.dtype(m["param_dtype"])
    out = {}
    for path, k in zip(paths, keys):
        shape, init, scale = specs[path]
        if init == "ones":
            w = jnp.ones(shape, jnp.float32)
        elif init == "zeros":
            w = jnp.zeros(shape, jnp.float32)
        else:
            if scale is None:
                fan_in = int(np.prod(shape[:-1])) if len(shape) > 1 \
                    else shape[0]
                scale = 1.0 / math.sqrt(max(1, fan_in))
            w = jax.random.normal(k, shape, jnp.float32) * scale
        out[path] = w.astype(dtype).astype(jnp.float32)
    return out


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

def _f8(x):
    """Round to float8 e4m3 with a per-tensor scale; identity gradient."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec, a, b, control):
    if control:
        a, b = _f8(a), _f8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                   * (x + 0.044715 * x ** 3)))


def _mlstm(m, p, x, control):
    b, s, d = x.shape
    nh = m["n_heads"]
    di = 2 * d
    hd = di // nh
    up = _mm("bsd,de->bse", x, p["up"], control)
    xb, z = up[..., :di], up[..., di:]
    w = p["conv_w"]
    kw = w.shape[0]
    xp = jnp.pad(xb, ((0, 0), (kw - 1, 0), (0, 0)))
    xc = sum(xp[:, i:i + s] * w[i] for i in range(kw)) + p["conv_b"]
    xc = jax.nn.silu(xc)
    q = _mm("bse,ef->bsf", xc, p["wq"], control).reshape(b, s, nh, hd)
    k = _mm("bse,ef->bsf", xc, p["wk"], control).reshape(b, s, nh, hd)
    v = _mm("bse,ef->bsf", xb, p["wv"], control).reshape(b, s, nh, hd)
    gates = _mm("bse,eg->bsg", xc, p["w_if"], control) + p["b_if"]
    log_i = jax.nn.log_sigmoid(gates[..., :nh])        # [b,s,h]
    log_f = jax.nn.log_sigmoid(gates[..., nh:])
    F = jnp.cumsum(log_f, axis=1)
    D = F[:, :, None, :] - F[:, None, :, :] + log_i[:, None, :, :]
    causal = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
    D = jnp.where(causal, D, -jnp.inf)                   # [b,i,j,h]
    mstab = jnp.maximum(jnp.max(D, axis=2), F)           # [b,i,h]
    S = _mm("bihp,bjhp->bijh", q, k, control) / math.sqrt(hd)
    W = S * jnp.exp(D - mstab[:, :, None, :])
    num = _mm("bijh,bjhp->bihp", W, v, control)
    den = jnp.abs(jnp.sum(W, axis=2))                    # [b,i,h]
    h = num / jnp.maximum(den, 1.0)[..., None]
    h = _rms(h, 1.0).reshape(b, s, di) * p["norm"]
    h = (h + xb * p["skip"]) * jax.nn.silu(z)
    return _mm("bse,ed->bsd", h, p["down"], control)


def _slstm(m, p, x, control):
    b, s, d = x.shape
    nh = m["n_heads"]
    dh = d // nh
    wx = _mm("bsd,dk->bsk", x, p["w"], control).reshape(b, s, nh, 4 * dh)
    bias = p["b"].reshape(nh, 4 * dh)
    r = p["r"]

    def cell(state, wxt):
        c, n, h, mm = state
        pre = wxt + _mm("bhd,hdk->bhk", h, r, control) + bias
        ip, fp, zp, op = jnp.split(pre, 4, axis=-1)
        log_f = jax.nn.log_sigmoid(fp)
        m_new = jnp.maximum(log_f + mm, ip)
        i_g = jnp.exp(ip - m_new)
        f_g = jnp.exp(log_f + mm - m_new)
        c = f_g * c + i_g * jnp.tanh(zp)
        n = f_g * n + i_g
        h = jax.nn.sigmoid(op) * c / jnp.maximum(jnp.abs(n), 1.0)
        return (c, n, h, m_new), h

    zero = jnp.zeros((b, nh, dh), jnp.float32)
    _, hs = jax.lax.scan(cell, (zero, zero, zero, zero - 10.0),
                         wx.transpose(1, 0, 2, 3))
    h = _rms(hs.transpose(1, 0, 2, 3).reshape(b, s, d), p["norm"])
    g = _mm("bsd,df->bsf", h, p["up_g"], control)
    u = _mm("bsd,df->bsf", h, p["up_h"], control)
    return _mm("bsf,fd->bsd", _gelu_tanh(g) * u, p["down"], control)


def _split_tree(params: dict, prefix: str) -> dict:
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def loss(m: dict, params: dict, tokens, targets, control: bool = False):
    """Mean next-token cross-entropy of one batch."""
    x = params["embed/table"][tokens]
    pairs = _split_tree(params, "pairs/")

    @jax.checkpoint
    def pair(x, lp):
        mp = _split_tree(lp, "m/")
        sp = _split_tree(lp, "s/")
        x = x + _mlstm(m, mp, _rms(x, lp["m_ln"]), control)
        x = x + _slstm(m, sp, _rms(x, lp["s_ln"]), control)
        return x, None

    x, _ = jax.lax.scan(pair, x, pairs)
    x = _rms(x, params["final_norm"])
    w = params.get("embed/unembed", params["embed/table"].T)
    logits = _mm("bsd,dv->bsv", x, w, control)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


def lr_at(opt: dict, count):
    """Linear warm-up from 0, then cosine decay to ``min_lr_frac``."""
    c = jnp.asarray(count, jnp.float32)
    warm = jnp.minimum(1.0, c / opt["warmup_steps"]) \
        if opt["warmup_steps"] > 0 else 1.0
    prog = jnp.clip((c - opt["warmup_steps"])
                    / max(1, opt["total_steps"] - opt["warmup_steps"]),
                    0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(math.pi * prog))
    return opt["lr"] * warm * (opt["min_lr_frac"]
                               + (1 - opt["min_lr_frac"]) * cos)


def adamw(opt: dict, count, params, grads, mom, vel):
    """One AdamW step with global-norm clipping; weight decay on every
    leaf.  ``count`` is the number of steps taken before this one."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
    clip = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-12))
    t = count + 1.0
    lr = lr_at(opt, count)
    new_p, new_m, new_v = {}, {}, {}
    for k in params:
        g = grads[k] * clip
        new_m[k] = opt["b1"] * mom[k] + (1 - opt["b1"]) * g
        new_v[k] = opt["b2"] * vel[k] + (1 - opt["b2"]) * g * g
        mh = new_m[k] / (1 - opt["b1"] ** t)
        vh = new_v[k] / (1 - opt["b2"] ** t)
        new_p[k] = params[k] - lr * (mh / (jnp.sqrt(vh) + opt["eps"])
                                     + opt["weight_decay"] * params[k])
    return new_p, new_m, new_v, gnorm


def train_steps(m: dict, opt: dict, seed: int, batches,
                control: bool = False, fault: str | None = None) -> dict:
    """Follow the program's first ``len(batches)`` steps from the seed.

    Returns each step's loss and gradient global norm (as the optimizer
    gets it, before clipping), each leaf's gradient norm at the first step,
    and each leaf's change over all the steps.  ``fault`` plants one of the
    faults a training step can have, for reading them: ``"state_unchanged"``
    (the update is dropped) or ``"half_batch"`` (loss and gradient over the
    first half of the rows only).
    """
    params = init_params(m, seed)
    start = dict(params)
    mom = {k: jnp.zeros_like(v) for k, v in params.items()}
    vel = {k: jnp.zeros_like(v) for k, v in params.items()}

    @jax.jit
    def step(count, params, mom, vel, tokens, targets):
        if fault == "half_batch":
            half = tokens.shape[0] // 2
            tokens, targets = tokens[:half], targets[:half]
        lval, grads = jax.value_and_grad(
            lambda p: loss(m, p, tokens, targets, control))(params)
        new_p, new_m, new_v, gnorm = adamw(opt, count, params, grads, mom,
                                           vel)
        if fault == "state_unchanged":
            new_p, new_m, new_v = params, mom, vel
        gleaf = {k: jnp.sqrt(jnp.sum(g * g)) for k, g in grads.items()}
        return lval, gnorm, gleaf, new_p, new_m, new_v

    losses, gnorms, grad1 = [], [], None
    for i, bt in enumerate(batches):
        lval, gnorm, gleaf, params, mom, vel = step(
            jnp.float32(i), params, mom, vel,
            jnp.asarray(bt["tokens"]), jnp.asarray(bt["targets"]))
        losses.append(float(lval))
        gnorms.append(float(gnorm))
        if grad1 is None:
            grad1 = {k: float(v) for k, v in gleaf.items()}
    delta = {k: float(jnp.sqrt(jnp.sum((params[k] - start[k]) ** 2)))
             for k in params}
    return {"losses": losses, "gnorms": gnorms, "grad1_norms": grad1,
            "delta_norms": delta}
