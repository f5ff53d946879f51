"""Custom-VJP flash attention: forward and gradients vs the reference
(memory-optimal backward — §Perf memory iteration)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import model_config
from repro.kernels import ref
from repro.models import layers as L


@pytest.fixture(scope="module")
def cfg():
    return model_config("qwen3_14b", smoke=True).replace(
        flash_block_q=64, flash_block_kv=64, attn_impl="flash_xla"
    )


CASES = [
    # b, sq, sk, h, kvh, d, causal, window
    (2, 128, 128, 4, 2, 64, True, 0),
    (1, 128, 256, 2, 2, 32, True, 0),      # kv prefix
    (2, 128, 128, 4, 4, 64, False, 0),     # bidirectional
    (1, 256, 256, 2, 1, 64, True, 64),     # window + MQA
]


def _mk(b, sq, sk, h, kvh, d):
    q = jax.random.normal(jax.random.PRNGKey(0), (b, sq, h, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, sk, kvh, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, sk, kvh, d), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("b,sq,sk,h,kvh,d,causal,win", CASES)
def test_cvjp_forward(cfg, b, sq, sk, h, kvh, d, causal, win):
    q, k, v = _mk(b, sq, sk, h, kvh, d)
    kr, vr = jnp.repeat(k, h // kvh, 2), jnp.repeat(v, h // kvh, 2)
    want = ref.attention(q, kr, vr, causal=causal, window=win)
    got = L.flash_attention_cvjp(cfg, q, k, v, causal=causal, window=win)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)


@pytest.mark.parametrize("b,sq,sk,h,kvh,d,causal,win", CASES)
def test_cvjp_grads(cfg, b, sq, sk, h, kvh, d, causal, win):
    q, k, v = _mk(b, sq, sk, h, kvh, d)

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(ref.attention(
            q, jnp.repeat(k, h // kvh, 2), jnp.repeat(v, h // kvh, 2),
            causal=causal, window=win)))

    def loss_new(q, k, v):
        return jnp.sum(jnp.sin(L.flash_attention_cvjp(
            cfg, q, k, v, causal=causal, window=win)))

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_new = jax.grad(loss_new, argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("qkv", g_ref, g_new):
        np.testing.assert_allclose(
            np.asarray(b_), np.asarray(a), atol=2e-5,
            err_msg=f"d{name} mismatch",
        )


def test_cvjp_block_shape_invariance(cfg):
    q, k, v = _mk(1, 256, 256, 2, 2, 64)
    outs = []
    for bq, bkv in [(64, 64), (128, 64), (256, 128)]:
        c = cfg.replace(flash_block_q=bq, flash_block_kv=bkv)
        outs.append(L.flash_attention_cvjp(c, q, k, v, causal=True))
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   atol=3e-6)


def test_run_attention_head_padding_slices_back(cfg):
    """Padded heads (TP divisibility) must not change the result."""
    from repro.dist.partition import sharding_ctx
    from repro.launch.mesh import auto_mesh

    q, k, v = _mk(1, 128, 128, 5, 5, 32)  # 5 heads: never divides 2
    want = ref.attention(q, k, v, causal=True)
    mesh = auto_mesh((1,), ("model",))
    with sharding_ctx(mesh):  # tp=1 -> no pad; sanity
        got = L.run_attention(cfg, q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)
    # force the padded path directly (hpad > h)
    hpad = 8
    padh = ((0, 0), (0, 0), (0, hpad - 5), (0, 0))
    out_pad = L.flash_attention_cvjp(
        cfg, jnp.pad(q, padh), jnp.pad(k, padh), jnp.pad(v, padh),
        causal=True,
    )[:, :, :5]
    np.testing.assert_allclose(np.asarray(out_pad), np.asarray(want),
                               atol=3e-6)
