"""State-space / recurrent sequence mixers: Mamba2 (SSD), mLSTM, sLSTM.

All three expose a chunked/parallel *train* form and an O(1)-state *decode*
form — these are the sub-quadratic archs that run the ``long_500k`` cells.

Mamba2 follows the SSD chunked decomposition (intra-chunk quadratic term +
inter-chunk recurrent state), adapted to TPU as einsums over MXU-friendly
chunk sizes.  mLSTM is the xLSTM matrix-memory cell in its stabilized
chunk-parallel form; sLSTM is the scalar-memory cell with recurrent gate
connections — inherently sequential, implemented as a time scan.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import core as scalpel
from repro.dist.partition import shard
from .params import P
from .spec import ModelConfig


# ---------------------------------------------------------------------------
# depthwise causal conv1d (shared by mamba2 / mLSTM branches)
# ---------------------------------------------------------------------------

def causal_conv1d(x, w, state=None, length=None):
    """x: [b,s,c], w: [k,c] depthwise. Returns (y, new_state [b,k-1,c]).

    ``length`` (traced i32, None => s): with right-padded input, the carried
    state must be the last k-1 REAL positions — the window ending at
    ``length``, not at the pad tail.
    """
    k = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    y = sum(
        xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(k)
    )
    if k <= 1:
        new_state = state
    elif length is None:
        new_state = xp[:, -(k - 1):, :]
    else:
        # xp index i holds x index i-(k-1): the k-1 inputs preceding
        # position ``length`` live at xp[length : length + k-1]
        new_state = jax.lax.dynamic_slice_in_dim(xp, length, k - 1, axis=1)
    return y, new_state


# ---------------------------------------------------------------------------
# Mamba2 (SSD)
# ---------------------------------------------------------------------------

# the gated RMSNorm's epsilon: Zamba2's rms_norm_eps (Zamba2 is the one
# family that runs Mamba2)
NORM_EPS = 1e-5


def _mamba2_dims(cfg: ModelConfig):
    """(d_inner, heads, head_dim, groups, d_state)."""
    di = cfg.ssm.expand * cfg.d_model
    hd = cfg.ssm.head_dim
    nh = di // hd
    g = cfg.ssm.n_groups
    if nh % g:
        raise ValueError(f"{nh} mamba2 heads do not split over {g} groups")
    return di, nh, hd, g, cfg.ssm.d_state


def mamba2_specs(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    di, nh, _, g, N = _mamba2_dims(cfg)
    kw = cfg.ssm.d_conv
    conv_ch = di + 2 * g * N  # x + B + C go through the conv
    return {
        # z | x | B | C | dt, stored [out, in] (as the published checkpoint
        # holds it): the layout the TPU's decode matmul reads, so no decode
        # megastep lays the weights out anew
        "in_proj": P((2 * di + 2 * g * N + nh, d), ("heads", "embed")),
        "conv_w": P((kw, conv_ch), ("conv", None), scale=0.5),
        "conv_b": P((conv_ch,), (None,), init="zeros"),
        "A_log": P((nh,), (None,), init="zeros", scale=1.0),
        "dt_bias": P((nh,), (None,), init="zeros"),
        "D": P((nh,), (None,), init="ones"),
        "norm": P((di,), ("heads",), init="ones"),
        "out_proj": P((di, d), ("heads", "embed")),
    }


def _ssd_chunked(xh, dt, da_log, B, C, S0=None, chunk=256):
    """SSD scan. xh:[b,s,h,p] dt:[b,s,h] da_log:[b,s,h] (log decay per step)
    B,C: [b,s,g,N], the h heads split evenly over the g groups in order
    (head i reads group i // (h/g)).  Returns (y [b,s,h,p], S_final
    [b,h,p,N])."""
    b, s, h, p = xh.shape
    g, N = B.shape[2], B.shape[3]
    r = h // g
    Q = min(chunk, s)
    if s % Q:
        # pad to a chunk multiple with identity steps (dt=0, da_log=0 keeps
        # the state; padded y rows are sliced off below)
        pad = Q - s % Q
        pad4 = ((0, 0), (0, pad), (0, 0), (0, 0))
        y, Sf = _ssd_chunked(
            jnp.pad(xh, pad4), jnp.pad(dt, pad4[:3]), jnp.pad(da_log, pad4[:3]),
            jnp.pad(B, pad4), jnp.pad(C, pad4), S0=S0, chunk=Q)
        return y[:, :s], Sf
    nc = s // Q
    xc = xh.reshape(b, nc, Q, g, r, p)
    dtc = dt.reshape(b, nc, Q, g, r)
    alc = da_log.reshape(b, nc, Q, g, r)
    Bc = B.reshape(b, nc, Q, g, N)
    Cc = C.reshape(b, nc, Q, g, N)

    def chunk_step(S, inp):
        xq, dtq, alq, Bq, Cq = inp  # [b,Q,...]; S [b,g,r,p,N]
        cum = jnp.cumsum(alq, axis=1)  # [b,Q,g,r] log decay from chunk start
        total = cum[:, -1]  # [b,g,r]
        # intra-chunk: L[i,j] = exp(cum_i - cum_j) for j<=i
        li = cum[:, :, None] - cum[:, None, :]  # [b,Q,Q,g,r]
        mask = jnp.tril(jnp.ones((Q, Q), bool))
        L = jnp.where(mask[None, :, :, None, None], jnp.exp(li), 0.0)
        G = jnp.einsum("bign,bjgn->bijg", Cq, Bq)  # [b,Q,Q,g]
        M = G[..., None] * L * dtq[:, None]  # [b,i,j,g,r]
        y_intra = jnp.einsum("bijgr,bjgrp->bigrp", M.astype(xq.dtype), xq)
        # inter-chunk: contribution of incoming state
        decay_in = jnp.exp(cum)  # [b,Q,g,r]
        y_inter = jnp.einsum(
            "bign,bgrpn,bigr->bigrp", Cq.astype(jnp.float32), S, decay_in,
        ).astype(xq.dtype)
        # state update: S' = S*exp(total) + sum_j exp(total-cum_j) dt_j B_j x_j
        w = jnp.exp(total[:, None] - cum) * dtq  # [b,Q,g,r]
        dS = jnp.einsum(
            "bjgn,bjgrp,bjgr->bgrpn", Bq.astype(jnp.float32),
            xq.astype(jnp.float32), w,
        )
        S2 = S * jnp.exp(total)[..., None, None] + dS
        return S2, y_intra + y_inter

    S0 = (jnp.zeros((b, h, p, N), jnp.float32) if S0 is None else S0)
    inputs = tuple(jnp.moveaxis(a, 1, 0) for a in (xc, dtc, alc, Bc, Cc))
    Sf, ys = jax.lax.scan(chunk_step, S0.reshape(b, g, r, p, N), inputs)
    y = jnp.moveaxis(ys, 0, 1).reshape(b, s, h, p)
    return y, Sf.reshape(b, h, p, N)


def _mamba2_in(cfg: ModelConfig, p, x, conv_state, length=None):
    """in_proj, causal conv and SiLU: (z, x [.., g, r, p], B, C [.., g, N],
    dt [.., g, r], A [g, r], conv_state)."""
    di, nh, hd, g, N = _mamba2_dims(cfg)
    zxbcdt = jnp.einsum("bsd,ed->bse", x, p["in_proj"].astype(x.dtype))
    z, xbc, dtp = jnp.split(zxbcdt, [di, 2 * di + 2 * g * N], axis=-1)
    xbc, conv_state = causal_conv1d(
        xbc, p["conv_w"].astype(x.dtype), conv_state, length=length
    )
    xbc = jax.nn.silu(
        (xbc + p["conv_b"].astype(x.dtype)).astype(jnp.float32)
    ).astype(x.dtype)
    xi, B, C = jnp.split(xbc, [di, di + g * N], axis=-1)
    lead = x.shape[:2]
    dt = jax.nn.softplus(
        dtp.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32)
    )  # [b,s,nh]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))  # [nh] negative
    return (z, xi.reshape(lead + (g, nh // g, hd)), B.reshape(lead + (g, N)),
            C.reshape(lead + (g, N)), dt.reshape(lead + (g, nh // g)),
            A.reshape(g, nh // g), conv_state)


def _mamba2_out(cfg: ModelConfig, p, y, xg, z):
    """D skip, gated RMSNorm over each group's channels, out_proj.
    y, xg: [b,s,g,r,p]; z: [b,s,d_inner]."""
    b, s, g, r, hd = y.shape
    D = p["D"].astype(y.dtype).reshape(g, r, 1)
    y = (y + xg * D).reshape(b, s, g, r * hd)
    gate = jax.nn.silu(z.astype(jnp.float32)).reshape(b, s, g, r * hd)
    yf = y.astype(jnp.float32) * gate
    yf = yf * jax.lax.rsqrt(jnp.mean(yf * yf, -1, keepdims=True) + NORM_EPS)
    y = (yf.reshape(b, s, g * r * hd)
         * p["norm"].astype(jnp.float32)).astype(z.dtype)
    return jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(z.dtype))


def mamba2(cfg: ModelConfig, p, x, state=None, conv_state=None,
           length=None):
    """Full-sequence Mamba2 mixer. x: [b,s,d] -> (y, (S, conv_state)).

    ``length`` (traced i32, None => s): positions >= length are right-pad.
    They take dt = 0, so the SSD state passes through them unchanged, and
    the conv state is the window ending at ``length``: the carried state is
    exactly that of an unpadded run (pad rows emit outputs nothing reads).
    """
    with scalpel.function("ssm"):
        b, s, d = x.shape
        z, xg, B, C, dt, A, conv_state = _mamba2_in(cfg, p, x, conv_state,
                                                    length)
        if length is not None:
            valid = (jnp.arange(s) < length)[None, :, None, None]
            dt = jnp.where(valid, dt, 0.0)
        g, r, hd = xg.shape[2:]
        xh = shard(xg.reshape(b, s, g * r, hd), "batch", None, "heads", None)
        with jax.named_scope("scalpel.ssm.scan"):
            y, S = _ssd_chunked(xh, dt.reshape(b, s, g * r),
                                (dt * A).reshape(b, s, g * r), B, C,
                                S0=state, chunk=cfg.ssm.chunk)
        scalpel.probe(state=S)
        out = _mamba2_out(cfg, p, y.reshape(xg.shape), xg, z)
        out = shard(out, "batch", None, None)
        scalpel.probe(out=out)
        return out, (S, conv_state)


def mamba2_decode(cfg: ModelConfig, p, x, state, conv_state):
    """One-token decode. x: [b,1,d]; state [b,h,p,N]; conv [b,k-1,ch]."""
    with scalpel.function("ssm"):
        z, xg, B, C, dt, A, conv_state = _mamba2_in(cfg, p, x, conv_state)
        b, _, g, r, hd = xg.shape
        with jax.named_scope("scalpel.ssm.step"):
            S = state.reshape(b, g, r, hd, -1)
            dt1 = dt[:, 0]  # [b,g,r]
            S = S * jnp.exp(dt1 * A)[..., None, None] + jnp.einsum(
                "bgn,bgrp,bgr->bgrpn", B[:, 0].astype(jnp.float32),
                xg[:, 0].astype(jnp.float32), dt1,
            )
            y = jnp.einsum(
                "bgn,bgrpn->bgrp", C[:, 0].astype(jnp.float32), S
            ).astype(x.dtype)
            state = S.reshape(state.shape)
        scalpel.probe(state=state)
        out = _mamba2_out(cfg, p, y[:, None], xg, z)
        scalpel.probe(out=out)
        return out, (state, conv_state)


def mamba2_state_specs(cfg: ModelConfig, batch: int):
    di, nh, hd, g, N = _mamba2_dims(cfg)
    return {
        "ssm": jax.ShapeDtypeStruct((batch, nh, hd, N), jnp.float32),
        "conv": jax.ShapeDtypeStruct(
            (batch, cfg.ssm.d_conv - 1, di + 2 * g * N),
            jnp.dtype(cfg.compute_dtype)
        ),
    }


# ---------------------------------------------------------------------------
# mLSTM (xLSTM matrix-memory cell, chunk-parallel stabilized form)
# ---------------------------------------------------------------------------

def mlstm_specs(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    di = 2 * d  # xLSTM proj factor 2
    nh = cfg.n_heads
    kw = cfg.ssm.d_conv
    return {
        "up": P((d, 2 * di), ("embed", "heads")),       # x | z
        "conv_w": P((kw, di), ("conv", None), scale=0.5),
        "conv_b": P((di,), (None,), init="zeros"),
        "wq": P((di, di), ("heads", "heads")),
        "wk": P((di, di), ("heads", "heads")),
        "wv": P((di, di), ("heads", "heads")),
        "w_if": P((di, 2 * nh), ("heads", None), scale=0.02),
        "b_if": P((2 * nh,), (None,), init="zeros"),
        "norm": P((di,), ("heads",), init="ones"),
        "down": P((di, d), ("heads", "embed")),
        "skip": P((di,), (None,), init="ones"),
    }


def _mlstm_chunked(q, k, v, log_i, log_f, chunk, C0=None, n0=None, m0=None):
    """Stabilized chunkwise mLSTM.  q,k,v: [b,s,h,p]; log_i/log_f: [b,s,h].
    Returns (h [b,s,h,p], (C [b,h,p,p], n [b,h,p], m [b,h]))."""
    b, s, h, p = q.shape
    Q = min(chunk, s)
    if s % Q:
        # identity padding: log_f=0 keeps the state, log_i=-1e30 adds nothing
        pad = Q - s % Q
        pad4 = ((0, 0), (0, pad), (0, 0), (0, 0))
        pad3 = ((0, 0), (0, pad), (0, 0))
        h_out, st = _mlstm_chunked(
            jnp.pad(q, pad4), jnp.pad(k, pad4), jnp.pad(v, pad4),
            jnp.pad(log_i, pad3, constant_values=-1e30),
            jnp.pad(log_f, pad3), Q, C0, n0, m0,
        )
        return h_out[:, :s], st
    nc = s // Q
    scale = p ** -0.5

    qc = q.reshape(b, nc, Q, h, p).transpose(1, 0, 2, 3, 4)
    kc = k.reshape(b, nc, Q, h, p).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nc, Q, h, p).transpose(1, 0, 2, 3, 4)
    lic = log_i.reshape(b, nc, Q, h).transpose(1, 0, 2, 3)
    lfc = log_f.reshape(b, nc, Q, h).transpose(1, 0, 2, 3)

    def step(carry, inp):
        C, n, m = carry  # [b,h,p,p], [b,h,p], [b,h]
        qq, kk, vv, li, lf = inp
        cumf = jnp.cumsum(lf, axis=1)  # [b,Q,h]
        total_f = cumf[:, -1]
        # log weights for source position j as seen at chunk end / position i
        # a_j = cumf_total - cumf_j + li_j   (state update weight)
        a = total_f[:, None, :] - cumf + li  # [b,Q,h]
        # b_i = cumf_i + m_prev  (inter-chunk read weight)
        b_read = cumf + m[:, None, :]
        # intra matrix: D[i,j] = cumf_i - cumf_j + li_j  (j<=i)
        Dm = cumf[:, :, None, :] - cumf[:, None, :, :] + li[:, None, :, :]
        tri = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]
        Dm = jnp.where(tri, Dm, -jnp.inf)
        # stabilizer per target position i
        m_intra = jnp.max(Dm, axis=2)  # [b,Q,h]
        m_new_pos = jnp.maximum(m_intra, b_read)  # running stabilizer per i
        Dstab = jnp.exp(Dm - m_new_pos[:, :, None, :])
        inter_w = jnp.exp(b_read - m_new_pos)  # [b,Q,h]

        S = jnp.einsum("bihp,bjhp->bijh", qq, kk).astype(jnp.float32) * scale
        W = S * Dstab  # [b,i,j,h]
        h_intra = jnp.einsum("bijh,bjhp->bihp", W.astype(qq.dtype), vv)
        h_inter = jnp.einsum(
            "bihp,bhpo,bih->biho", qq.astype(jnp.float32), C, inter_w
        ).astype(qq.dtype) * scale
        denom_intra = jnp.einsum("bijh,bjhp->bihp", W.astype(qq.dtype), kk)
        # normalizer: n dot q
        denom_inter = jnp.einsum(
            "bihp,bhp,bih->bih", qq.astype(jnp.float32), n, inter_w
        ) * scale
        denom = jnp.abs(
            jnp.einsum("bihp,bihp->bih", qq.astype(jnp.float32),
                       denom_intra.astype(jnp.float32)) * scale
            + denom_inter
        )
        hh = (h_intra + h_inter) / jnp.maximum(
            denom, 1.0
        )[..., None].astype(qq.dtype)

        # state update (stabilized by m_next = max(total_f + m, max_j a_j))
        m_next = jnp.maximum(total_f + m, jnp.max(a, axis=1))
        wj = jnp.exp(a - m_next[:, None, :])  # [b,Q,h]
        C2 = C * jnp.exp(total_f + m - m_next)[:, :, None, None] + jnp.einsum(
            "bjhp,bjho,bjh->bhpo", kk.astype(jnp.float32),
            vv.astype(jnp.float32), wj,
        )
        n2 = n * jnp.exp(total_f + m - m_next)[:, :, None] + jnp.einsum(
            "bjhp,bjh->bhp", kk.astype(jnp.float32), wj
        )
        return (C2, n2, m_next), hh

    C0 = jnp.zeros((b, h, p, p), jnp.float32) if C0 is None else C0
    n0 = jnp.zeros((b, h, p), jnp.float32) if n0 is None else n0
    m0 = jnp.zeros((b, h), jnp.float32) if m0 is None else m0
    (C, n, m), hs = jax.lax.scan(step, (C0, n0, m0),
                                 (qc, kc, vc, lic, lfc))
    hout = hs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, p)
    return hout, (C, n, m)


def mlstm_block(cfg: ModelConfig, p, x, state=None, length=None):
    """mLSTM mixer. x: [b,s,d] -> (y, state).

    ``length`` (traced i32, None => s): positions >= length are right-pad.
    They are neutralized with the SAME identity trick ``_mlstm_chunked``
    uses for its own chunk padding — log_f=0 keeps the state, log_i=-1e30
    adds nothing — so the carried state and every valid position's output
    are exactly what an unpadded run produces (pad rows emit garbage that
    the caller must never read).
    """
    with scalpel.function("mlstm"):
        b, s, d = x.shape
        di = 2 * d
        nh = cfg.n_heads
        hd = di // nh
        up = jnp.einsum("bsd,de->bse", x, p["up"].astype(x.dtype))
        xb, z = jnp.split(up, 2, axis=-1)
        conv_state = state[3] if state is not None else None
        xc, conv_state = causal_conv1d(xb, p["conv_w"].astype(x.dtype),
                                       conv_state, length=length)
        xc = jax.nn.silu(
            (xc + p["conv_b"].astype(x.dtype)).astype(jnp.float32)
        ).astype(x.dtype)
        q = jnp.einsum("bse,ef->bsf", xc, p["wq"].astype(x.dtype))
        k = jnp.einsum("bse,ef->bsf", xc, p["wk"].astype(x.dtype))
        v = jnp.einsum("bse,ef->bsf", xb, p["wv"].astype(x.dtype))
        gates = jnp.einsum(
            "bse,eg->bsg", xc, p["w_if"].astype(x.dtype)
        ).astype(jnp.float32) + p["b_if"].astype(jnp.float32)
        li_pre, lf_pre = jnp.split(gates, 2, axis=-1)  # [b,s,nh]
        log_i = -jax.nn.softplus(-li_pre)   # log sigmoid
        log_f = -jax.nn.softplus(-lf_pre)
        if length is not None:
            valid = (jnp.arange(s) < length)[None, :, None]
            log_i = jnp.where(valid, log_i, -1e30)
            log_f = jnp.where(valid, log_f, 0.0)
        qh = q.reshape(b, s, nh, hd)
        kh = k.reshape(b, s, nh, hd)
        vh = v.reshape(b, s, nh, hd)
        qh = shard(qh, "batch", None, "heads", None)
        C0 = n0 = m0 = None
        if state is not None:
            C0, n0, m0 = state[0], state[1], state[2]
        h, (C, n, m) = _mlstm_chunked(
            qh, kh, vh, log_i, log_f, cfg.ssm.chunk, C0, n0, m0
        )
        scalpel.probe(state=C)
        from .layers import head_rms_norm

        h = head_rms_norm(h, jnp.ones((hd,), jnp.float32))
        h = h.reshape(b, s, di) * p["norm"].astype(x.dtype)
        h = h + xb * p["skip"].astype(x.dtype)
        h = h * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
        y = jnp.einsum("bse,ed->bsd", h, p["down"].astype(x.dtype))
        y = shard(y, "batch", None, None)
        scalpel.probe(out=y)
        return y, (C, n, m, conv_state)


def mlstm_state_specs(cfg: ModelConfig, batch: int):
    d = cfg.d_model
    di = 2 * d
    nh = cfg.n_heads
    hd = di // nh
    cdt = jnp.dtype(cfg.compute_dtype)
    return (
        jax.ShapeDtypeStruct((batch, nh, hd, hd), jnp.float32),
        jax.ShapeDtypeStruct((batch, nh, hd), jnp.float32),
        jax.ShapeDtypeStruct((batch, nh), jnp.float32),
        jax.ShapeDtypeStruct((batch, cfg.ssm.d_conv - 1, di), cdt),
    )


# ---------------------------------------------------------------------------
# sLSTM (scalar memory, recurrent gates — sequential scan)
# ---------------------------------------------------------------------------

def slstm_specs(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    nh = cfg.n_heads
    dh = d // nh
    f = int(d * 4 / 3) // 2 * 2
    return {
        "w": P((d, 4 * d), ("embed", "heads")),       # i,f,z,o pre-acts
        "r": P((nh, dh, 4 * dh), (None, None, None), scale=0.02),
        "b": P((4 * d,), (None,), init="zeros"),
        "norm": P((d,), ("embed",), init="ones"),
        "up_g": P((d, f), ("embed", "mlp")),
        "up_h": P((d, f), ("embed", "mlp")),
        "down": P((f, d), ("mlp", "embed")),
    }


def _slstm_cell(cfg: ModelConfig, p, wx, state):
    """One time step.  wx: [b, 4d] precomputed W@x_t; state tuple."""
    nh = cfg.n_heads
    d = cfg.d_model
    dh = d // nh
    c, n, hprev, m = state  # [b,nh,dh], [b,nh,dh], [b,nh,dh], [b,nh,dh]
    r = p["r"].astype(jnp.float32)  # [nh, dh, 4dh]
    rh = jnp.einsum("bhd,hdk->bhk", hprev, r)  # [b,nh,4dh]
    pre = wx.reshape(-1, nh, 4 * dh).astype(jnp.float32) + rh + \
        p["b"].astype(jnp.float32).reshape(nh, 4 * dh)
    ip, fp, zp, op = jnp.split(pre, 4, axis=-1)  # [b,nh,dh]
    # exponential gating with stabilizer m
    log_f = -jax.nn.softplus(-fp)  # log sigmoid(f)
    m_new = jnp.maximum(log_f + m, ip)
    i_g = jnp.exp(ip - m_new)
    f_g = jnp.exp(log_f + m - m_new)
    z_g = jnp.tanh(zp)
    o_g = jax.nn.sigmoid(op)
    c2 = f_g * c + i_g * z_g
    n2 = f_g * n + i_g
    h2 = o_g * (c2 / jnp.maximum(jnp.abs(n2), 1.0))
    return (c2, n2, h2, m_new), h2


def slstm_block(cfg: ModelConfig, p, x, state=None, length=None):
    """sLSTM mixer + gated FFN. x: [b,s,d] -> (y, state).

    ``length`` (traced i32, None => s): pad positions run identity scan
    steps — the cell computes but the carried state keeps its old value —
    so the recurrent state leaving the block is exactly the unpadded one.
    """
    with scalpel.function("slstm"):
        b, s, d = x.shape
        nh = cfg.n_heads
        dh = d // nh
        wx = jnp.einsum("bsd,dk->bsk", x, p["w"].astype(x.dtype))
        if state is None:
            z = jnp.zeros((b, nh, dh), jnp.float32)
            state = (z, z, z, z - 10.0)

        if length is None:
            def step(carry, wxt):
                return _slstm_cell(cfg, p, wxt, carry)

            state, hs = jax.lax.scan(step, state, wx.transpose(1, 0, 2))
        else:
            def step(carry, inp):
                wxt, keep = inp
                new, h2 = _slstm_cell(cfg, p, wxt, carry)
                new = jax.tree.map(
                    lambda a, o: jnp.where(keep, a, o), new, carry)
                return new, h2

            valid = jnp.arange(s) < length
            state, hs = jax.lax.scan(
                step, state, (wx.transpose(1, 0, 2), valid))
        h = hs.transpose(1, 0, 2, 3).reshape(b, s, d).astype(x.dtype)
        scalpel.probe(state=state[0])
        from .layers import rms_norm

        h = rms_norm(h, p["norm"])
        g = jnp.einsum("bsd,df->bsf", h, p["up_g"].astype(x.dtype))
        u = jnp.einsum("bsd,df->bsf", h, p["up_h"].astype(x.dtype))
        u = jax.nn.gelu(g.astype(jnp.float32)).astype(x.dtype) * u
        y = jnp.einsum("bsf,fd->bsd", u, p["down"].astype(x.dtype))
        y = shard(y, "batch", None, None)
        scalpel.probe(out=y)
        return y, state


def slstm_state_specs(cfg: ModelConfig, batch: int):
    nh = cfg.n_heads
    dh = cfg.d_model // nh
    sd = jax.ShapeDtypeStruct((batch, nh, dh), jnp.float32)
    return (sd, sd, sd, sd)
