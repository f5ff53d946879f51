"""Pallas kernel sweeps vs the pure-jnp oracles (interpret mode, requested
explicitly)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref

TOL = {jnp.float32: 2e-4, jnp.bfloat16: 2e-1}


# ---------------------------------------------------------------------------
# GEMM: both schedules, shape x dtype sweep
# ---------------------------------------------------------------------------

GEMM_SHAPES = [
    (128, 128, 128),
    (256, 128, 384),
    (512, 256, 128),
    (128, 512, 256),
]


@pytest.mark.parametrize("schedule", ops.SCHEDULES)
@pytest.mark.parametrize("m,n,k", GEMM_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm_allclose(schedule, m, n, k, dtype):
    a = jax.random.normal(jax.random.PRNGKey(0), (m, k), dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n), dtype)
    want = ref.matmul(a, b)
    got = ops.matmul(a, b, schedule, bm=128, bn=128, bk=128, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=TOL[dtype] * np.sqrt(k),
        rtol=TOL[dtype],
    )


def test_gemm_schedules_agree():
    a = jax.random.normal(jax.random.PRNGKey(2), (256, 256), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(3), (256, 256), jnp.float32)
    c1 = ops.matmul(a, b, "cache_blocked", bm=128, bn=128, bk=128,
                   interpret=True)
    c2 = ops.matmul(a, b, "panel_streaming", bm=128, bn=128, interpret=True)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=2e-4)


def test_gemm_cost_model_properties():
    """The case-study claim: identical FLOPs, different memory traffic."""
    m = n = k = 2048
    cb = ops.matmul_cost("cache_blocked", m, n, k, bm=256, bn=256, bk=256)
    ps = ops.matmul_cost("panel_streaming", m, n, k, bm=256, bn=256)
    assert cb["FLOPS"] == ps["FLOPS"] == 2.0 * m * n * k
    # panel streaming reads A exactly once; cache_blocked refetches it
    assert ps["HBM_BYTES"] < cb["HBM_BYTES"]
    assert ps["VMEM_TILE_REFILLS"] < cb["VMEM_TILE_REFILLS"]
    # but its VMEM working set is larger (the Goto trade-off)
    assert ps["vmem_working_set_bytes"] > cb["vmem_working_set_bytes"]
    assert ps["arithmetic_intensity"] > cb["arithmetic_intensity"]


@settings(max_examples=10, deadline=None)
@given(
    st.sampled_from([128, 256, 384]),
    st.sampled_from([128, 256]),
    st.sampled_from([128, 384]),
)
def test_gemm_property_any_blocking(m, n, k):
    """Property: every legal blocking yields the same product."""
    a = jax.random.normal(jax.random.PRNGKey(4), (m, k), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(5), (k, n), jnp.float32)
    want = np.asarray(ref.matmul(a, b))
    got = ops.matmul(a, b, "cache_blocked", bm=128, bn=128, bk=128,
                     interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, atol=5e-4)


# ---------------------------------------------------------------------------
# flash attention sweep
# ---------------------------------------------------------------------------

FA_CASES = [
    # b, sq, sk, h, kvh, d, causal, window
    (2, 128, 128, 4, 4, 64, True, 0),
    (2, 128, 128, 4, 2, 64, True, 0),      # GQA
    (1, 256, 256, 2, 1, 32, True, 0),      # MQA
    (1, 128, 384, 2, 2, 64, True, 0),      # kv prefix (prefill-with-cache)
    (2, 128, 128, 4, 4, 64, False, 0),     # bidirectional (encoder)
    (1, 256, 256, 2, 2, 64, True, 64),     # sliding window
    (1, 64, 192, 1, 1, 128, True, 0),      # single head, tall kv
]


@pytest.mark.parametrize("b,sq,sk,h,kvh,d,causal,win", FA_CASES)
def test_flash_attention_allclose(b, sq, sk, h, kvh, d, causal, win):
    q = jax.random.normal(jax.random.PRNGKey(0), (b, sq, h, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, sk, kvh, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, sk, kvh, d), jnp.float32)
    kr = jnp.repeat(k, h // kvh, axis=2)
    vr = jnp.repeat(v, h // kvh, axis=2)
    want = ref.attention(q, kr, vr, causal=causal, window=win)
    got = ops.flash_attention(q, k, v, causal=causal, window=win,
                              block_q=64, block_kv=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_flash_attention_bf16(dtype):
    b, s, h, d = 1, 128, 2, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, d), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, d), dtype)
    want = ref.attention(q, k, v, causal=True)
    got = ops.flash_attention(q, k, v, causal=True, block_q=64, block_kv=64,
                              interpret=True)
    assert got.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=3e-2
    )


def test_flash_attention_block_shape_invariance():
    b, s, h, d = 1, 256, 2, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, d), jnp.float32)
    outs = [
        ops.flash_attention(q, k, v, block_q=bq, block_kv=bkv, interpret=True)
        for bq, bkv in [(64, 64), (128, 64), (64, 128), (256, 256)]
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(outs[0]), atol=2e-5
        )


def test_flash_attention_cost_causal_skip():
    c = ops.flash_attention_cost(1, 1024, 1024, 1, 64, causal=True,
                                 block_q=128, block_kv=128)
    full = ops.flash_attention_cost(1, 1024, 1024, 1, 64, causal=False,
                                    block_q=128, block_kv=128)
    assert c["live_tiles"] == 8 * 9 // 2      # lower triangle of 8x8
    assert full["live_tiles"] == 64
    assert c["FLOPS"] < full["FLOPS"]


# ---------------------------------------------------------------------------
# chunked SSM scan sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,D,chunk,bd", [
    (1, 128, 32, 32, 32),
    (2, 256, 64, 64, 32),
    (2, 512, 96, 128, 96),
    (1, 1024, 16, 256, 16),
])
def test_ssm_scan_allclose(B, S, D, chunk, bd):
    la = -jnp.abs(
        jax.random.normal(jax.random.PRNGKey(0), (B, S, D))
    ) * 0.3
    bb = jax.random.normal(jax.random.PRNGKey(1), (B, S, D))
    want = ref.ssm_scan(None, la, bb)
    got = ops.ssm_scan(la, bb, chunk=chunk, bd=bd, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4
    )


def test_ssm_scan_chunk_invariance():
    B, S, D = 1, 256, 32
    la = -jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (B, S, D))) * 0.5
    bb = jax.random.normal(jax.random.PRNGKey(3), (B, S, D))
    o1 = ops.ssm_scan(la, bb, chunk=64, bd=32, interpret=True)
    o2 = ops.ssm_scan(la, bb, chunk=256, bd=16, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4)


def test_ssm_scan_decay_identity():
    """log_a = -inf-ish -> h_t == b_t; log_a = 0 -> h_t = cumsum(b)."""
    B, S, D = 1, 64, 8
    bb = jax.random.normal(jax.random.PRNGKey(4), (B, S, D))
    h_dead = ops.ssm_scan(jnp.full((B, S, D), -40.0), bb, chunk=32, bd=8,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(h_dead), np.asarray(bb), atol=1e-5)
    h_int = ops.ssm_scan(jnp.zeros((B, S, D)), bb, chunk=32, bd=8,
                         interpret=True)
    np.testing.assert_allclose(
        np.asarray(h_int), np.cumsum(np.asarray(bb), axis=1), atol=1e-4
    )
