"""Grouped-query decode attention against the repeat-KV formulation.

``layers.decode_attention`` contracts the query heads, grouped by the KV
head they share, against the cache as stored.  The reference below is the
formulation it replaced: the cache repeated to every query head, scores
from a compute-dtype einsum cast to f32.  The structural test keeps the
repeat from coming back into a traced decode step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L
from repro.models.params import init_tree
from repro.models.registry import Arch
from repro.models.spec import ModelConfig

S, KV, HD, D = 16, 2, 16, 48


def _cfg(n_rep: int, window: int, dtype: str, **kw) -> ModelConfig:
    fields = dict(name="gqa", family="dense", n_layers=1, d_model=D,
                  n_heads=KV * n_rep, n_kv_heads=KV, d_ff=64, vocab=64,
                  head_dim=HD, qk_norm=True, sliding_window=window,
                  param_dtype=dtype, compute_dtype=dtype)
    fields.update(kw)
    return ModelConfig(**fields)


def _repeat_kv_attention(cfg, p, x, cache_k, cache_v, pos):
    """The repeat-KV decode step: the cache broadcast to the query heads."""
    b = x.shape[0]
    positions = jnp.broadcast_to(pos, (b, 1)).astype(jnp.int32)
    q, k_new, v_new = L._qkv(cfg, p, x, positions)
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k_new.astype(cache_k.dtype), pos, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v_new.astype(cache_v.dtype), pos, axis=1)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    kr = jnp.repeat(cache_k.astype(x.dtype), n_rep, axis=2)
    vr = jnp.repeat(cache_v.astype(x.dtype), n_rep, axis=2)
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr).astype(jnp.float32) * scale
    kpos = jnp.arange(cache_k.shape[1])[None, None, None, :]
    valid = kpos <= pos
    if cfg.sliding_window:
        valid = valid & (kpos > pos - cfg.sliding_window)
    s = jnp.where(valid, s, -1e30)
    probs = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(x.dtype), vr)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, cache_k, cache_v


@pytest.mark.parametrize("lanes", ["batch2", "vmap3"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("window", [0, 5])
@pytest.mark.parametrize("pos", [0, S // 2, S - 1])
@pytest.mark.parametrize("n_rep", [1, 2, 5])
def test_grouped_matches_repeat_kv(n_rep, pos, window, dtype, lanes):
    cfg = _cfg(n_rep, window, dtype)
    dt = jnp.dtype(dtype)
    kp, kx, kk, kv = jax.random.split(jax.random.PRNGKey(n_rep * 100 + pos), 4)
    p = init_tree(L.attention_specs(cfg), kp, dt)
    if lanes == "batch2":
        x = jax.random.normal(kx, (2, 1, D), jnp.float32).astype(dt)
        ck = jax.random.normal(kk, (2, S, KV, HD), jnp.float32).astype(dt)
        cv = jax.random.normal(kv, (2, S, KV, HD), jnp.float32).astype(dt)
        args = (x, ck, cv, jnp.int32(pos))

        def run(fn):
            return jax.jit(lambda *a: fn(cfg, p, *a))(*args)
    else:
        # each lane a batch-1 request at its own position
        x = jax.random.normal(kx, (3, 1, 1, D), jnp.float32).astype(dt)
        ck = jax.random.normal(kk, (3, 1, S, KV, HD), jnp.float32).astype(dt)
        cv = jax.random.normal(kv, (3, 1, S, KV, HD), jnp.float32).astype(dt)
        lane_pos = jnp.array([pos, (pos + 7) % S, S - 1 - pos], jnp.int32)
        args = (x, ck, cv, lane_pos)

        def run(fn):
            return jax.jit(jax.vmap(lambda *a: fn(cfg, p, *a)))(*args)

    y, k_out, v_out = run(L.decode_attention)
    y_ref, k_ref, v_ref = run(_repeat_kv_attention)

    # the cache: bit-identical, the input with the new token written at pos
    np.testing.assert_array_equal(np.asarray(k_out, np.float32),
                                  np.asarray(k_ref, np.float32))
    np.testing.assert_array_equal(np.asarray(v_out, np.float32),
                                  np.asarray(v_ref, np.float32))
    written = np.zeros(k_out.shape, bool)
    seq = written.ndim - 3
    positions = np.broadcast_to(
        np.reshape(args[3], (-1,) + (1,) * (seq - 1)), k_out.shape[:seq])
    for idx in np.ndindex(*positions.shape):
        written[idx + (positions[idx],)] = True
    for new, old in ((k_out, ck), (v_out, cv)):
        np.testing.assert_array_equal(np.asarray(new, np.float32)[~written],
                                      np.asarray(old, np.float32)[~written])

    y, y_ref = np.asarray(y, np.float32), np.asarray(y_ref, np.float32)
    if dt == jnp.float32:
        np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)
    else:
        # the reference rounds its scores to bf16 before the softmax; the
        # grouped form accumulates them in f32: a few bf16 ulps of the output
        tol = 4 * float(jnp.finfo(dt).eps) * float(np.abs(y_ref).max())
        np.testing.assert_allclose(y, y_ref, rtol=0, atol=tol)


def _out_shapes(jaxpr):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield eqn.primitive.name, tuple(getattr(v.aval, "shape", ()))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _out_shapes(sub)


def test_decode_step_never_repeats_the_cache():
    """A vmapped decode step of a GQA model (h = 4 kv) builds no array
    that spans the cache length and every query head."""
    cache_len, n_lanes = 24, 3
    cfg = _cfg(4, 0, "bfloat16", n_layers=2)
    assert cfg.n_heads != cfg.n_kv_heads
    arch = Arch(cfg)
    params = jax.eval_shape(arch.init, jax.random.PRNGKey(0))
    slab = arch.init_lane_cache(n_lanes, cache_len, abstract=True)
    tokens = jax.ShapeDtypeStruct((n_lanes, 1, 1), jnp.int32)
    jaxpr = jax.make_jaxpr(
        jax.vmap(arch.decode_step, in_axes=(None, 0, 0)))(params, slab, tokens)
    shapes = list(_out_shapes(jaxpr.jaxpr))
    # the guard sees the cache itself and the step's per-head queries
    assert any(cache_len in s and cfg.n_kv_heads in s for _, s in shapes)
    assert any(cfg.n_heads in s for _, s in shapes)
    repeated = [(n, s) for n, s in shapes
                if cache_len in s and cfg.n_heads in s]
    assert not repeated, repeated


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_decode_step_writes_one_row_a_layer_in_place():
    """A vmapped dense decode step carries the stacked cache through its
    layer loop: no scan takes or returns an array that spans the cache
    length as xs or ys (a per-layer slice and restack), and every write
    into the cache is one position per lane per layer."""
    cache_len, n_lanes = 24, 3
    cfg = _cfg(4, 0, "bfloat16", n_layers=2)
    arch = Arch(cfg)
    params = jax.eval_shape(arch.init, jax.random.PRNGKey(0))
    slab = arch.init_lane_cache(n_lanes, cache_len, abstract=True)
    tokens = jax.ShapeDtypeStruct((n_lanes, 1, 1), jnp.int32)
    jaxpr = jax.make_jaxpr(
        jax.vmap(arch.decode_step, in_axes=(None, 0, 0)))(params, slab, tokens)
    eqns = list(_eqns(jaxpr.jaxpr))

    scans = [e for e in eqns if e.primitive.name == "scan"]
    assert scans
    for e in scans:
        n_in = e.params["num_consts"] + e.params["num_carry"]
        xs_ys = e.invars[n_in:] + e.outvars[e.params["num_carry"]:]
        spans = [v.aval.shape for v in xs_ys if cache_len in v.aval.shape]
        assert not spans, spans

    row = n_lanes * cfg.n_kv_heads * cfg.resolved_head_dim
    writes = [e for e in eqns
              if e.primitive.name in ("dynamic_update_slice", "scatter")
              and cache_len in e.invars[0].aval.shape]
    assert len(writes) == 2  # K and V, once in the layer loop's body
    for e in writes:
        update = e.invars[1 if e.primitive.name == "dynamic_update_slice"
                          else 2]
        assert update.aval.size == row, (e.primitive.name, update.aval)
