"""Fused probe reductions: Pallas moment kernel (incl. the optional entropy
channel) vs jnp reference, and per-set-planned vs union-planned event
evaluation through a real ``Monitor.open`` region."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core as scalpel
from repro.core import events
from repro.core.context import EventSpec, MonitorSpec, ScopeContext
from repro.core.counters import CounterState, MonitorParams
from repro.kernels import ops, probe_reduce as pr

MOMENT_EVENTS = (
    "ACT_RMS", "ACT_MEAN_ABS", "ACT_MAX_ABS", "ACT_ZERO_FRAC",
    "NAN_COUNT", "INF_COUNT", "NUMEL", "L2NORM", "MEAN",
)


def test_channel_vocabulary_in_sync():
    # kernel dense vector = sweep channels (minus static) + numel slot
    assert pr.MOMENTS[:7] == events.SWEEP_CHANNELS[:7]
    assert pr.MOMENTS_ENT == pr.MOMENTS + ("ent_sum",)
    assert set(events.CHANNELS) == set(pr.MOMENTS_ENT) | set(
        pr.STATIC_CHANNELS
    )
    assert events.CHANNELS == events.SWEEP_CHANNELS + events.STATIC_CHANNELS


# ---------------------------------------------------------------------------
# stage 1: the kernel vs the unfused jnp reference
# ---------------------------------------------------------------------------

# a small block budget (one or two hardware tiles) splits these shapes
# into several blocks along every axis the comment names
SMALL_BLOCK = 2048


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    (1024,),          # 1-D, tile-aligned
    (1000,),          # 1-D, non-tile-aligned
    (64, 129),        # 2-D, ragged row blocks
    (7, 33, 65),      # 3-D, nothing aligned
    (1, 1),           # degenerate
    (3, 40, 2500),    # ragged column blocks and row blocks, lead > 1
    (9, 5, 20),       # several whole slabs per block, ragged lead blocks
])
def test_pallas_moments_match_reference(shape, dtype):
    rng = np.random.default_rng(hash(shape) % 2**32)
    x = rng.normal(size=shape).astype(np.float32)
    x.flat[:: max(1, x.size // 17)] = 0.0  # some exact zeros
    xj = jnp.asarray(x).astype(dtype)
    got = np.asarray(ops.probe_moments(xj, block_elems=SMALL_BLOCK,
                                       interpret=True))
    want = np.asarray(pr.moments_ref(xj))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-5)
    # numel is exact (static constant, never a rounded f32 accumulation);
    # zero_count doubles as the mask check — zero padding would inflate it
    assert got[pr.M_NUMEL] == x.size
    assert got[pr.M_ZERO] == want[pr.M_ZERO]


@pytest.mark.parametrize("shape", [(128,), (5, 33), (3, 7, 17)])
def test_pallas_entropy_channel_matches_reference(shape):
    """The optional ent_sum channel rides the same masked sweep."""
    rng = np.random.default_rng(11)
    p = jax.nn.softmax(jnp.asarray(rng.normal(size=shape), jnp.float32), -1)
    got = np.asarray(
        ops.probe_moments(p, block_elems=SMALL_BLOCK, interpret=True,
                          with_entropy=True)
    )
    want = np.asarray(pr.moments_ref(p, with_entropy=True))
    assert got.shape == (len(pr.MOMENTS_ENT),)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-5)
    # without the flag the vector stays 8 wide — plans only pay on request
    assert ops.probe_moments(p, interpret=True).shape == (len(pr.MOMENTS),)


def test_pallas_moments_nan_inf_propagation():
    a = np.array([np.nan, 1.5, np.inf, -np.inf, 0.0] * 64, np.float32)
    got = np.asarray(ops.probe_moments(jnp.asarray(a), interpret=True))
    want = np.asarray(pr.moments_ref(jnp.asarray(a)))
    np.testing.assert_allclose(got, want, equal_nan=True)
    assert got[pr.M_NAN] == 64 and got[pr.M_INF] == 128


@pytest.mark.parametrize("dims,itemsize,budget,want", [
    ((8, 2048, 768), 2, pr.BLOCK_ELEMS, (1, 256, 768)),   # row blocks
    ((1024, 8, 128), 2, pr.BLOCK_ELEMS, (128, 8, 128)),   # whole slabs
    ((1, 1, 151936), 2, pr.BLOCK_ELEMS, (1, 1, 16384)),   # wide row
    ((1, 1, 151936), 4, pr.BLOCK_ELEMS, (1, 1, 32768)),
    ((3, 40, 2500), 4, SMALL_BLOCK, (1, 8, 256)),          # col tiles
    ((9, 5, 20), 4, SMALL_BLOCK, (2, 5, 20)),               # ragged lead
    ((2, 3, 5), 4, 1, (1, 3, 5)),       # a budget below one tile: one tile
])
def test_block_shape_fills_budget_with_legal_tiles(dims, itemsize, budget,
                                                   want):
    """Blocks take as much of the budget as the (sublane, lane) tiling
    allows: few grid steps for many small slabs and for wide rows."""
    got = pr.block_shape(dims, itemsize, budget)
    assert got == want
    sub = 32 // itemsize
    for n, b, tile in zip(dims[1:], got[1:], (sub, pr.LANES)):
        assert b == n or b % tile == 0


def test_named_moments_jnp_subset_matches_reference():
    x = jax.random.normal(jax.random.PRNGKey(3), (513,))
    ref = pr.moments_ref(x)
    d = ops.tensor_moments(x, ("sum_sq", "max_abs", "zero_count"),
                           use_pallas=False)
    for name in ("sum_sq", "max_abs", "zero_count", "numel"):
        np.testing.assert_allclose(
            float(d[name]), float(ref[pr.MOMENTS.index(name)]), rtol=1e-5
        )
    assert "sum_abs" not in d  # only the exact plan channels, nothing more
    # static channels ride along for free: one row along the last axis
    assert float(d["rows"]) == 1.0
    d2 = ops.tensor_moments(jnp.ones((4, 5, 8)), ("sum",), use_pallas=False)
    assert float(d2["rows"]) == 20.0 and float(d2["numel"]) == 160.0


# ---------------------------------------------------------------------------
# stage 2: finalizers reproduce every moment-derived event
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", MOMENT_EVENTS)
def test_finalizer_matches_direct_event(name):
    x = jax.random.normal(jax.random.PRNGKey(7), (37, 11))
    x = x.at[0, 0].set(0.0)
    spec = EventSpec(name, tensor="x")
    assert events.moment_based(spec)
    moms = ops.tensor_moments(x, events.channels_for([spec]),
                              use_pallas=False)
    got = float(events.finalize_event(spec, moms))
    want = float(events.compute(spec, {"x": x}))
    assert got == pytest.approx(want, rel=1e-5, abs=1e-7)


def test_entropy_finalizer_matches_direct_event():
    """ATTN_ENTROPY is moment-derived now: ent_sum/rows off the shared sweep."""
    p = jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(9), (6, 5, 32)), -1
    )
    spec = EventSpec("ATTN_ENTROPY", tensor="p")
    assert events.moment_based(spec)
    assert events.channels_for([spec]) == ("ent_sum", "rows")
    moms = ops.tensor_moments(p, ("ent_sum", "rows"), use_pallas=False)
    got = float(events.finalize_event(spec, moms))
    want = float(events.compute(spec, {"p": p}))
    assert got == pytest.approx(want, rel=1e-5)


def test_bespoke_events_not_moment_based():
    for name in ("MOE_LOAD", "SSM_STATE_RMS"):
        assert not events.moment_based(EventSpec(name))


def test_channels_for_is_per_group_not_per_registry():
    a = events.channels_for([EventSpec("ACT_MAX_ABS", "x")])
    b = events.channels_for([EventSpec("ACT_RMS", "x"),
                             EventSpec("MEAN", "x")])
    assert a == ("max_abs",)
    assert b == ("sum", "sum_sq", "numel")


# ---------------------------------------------------------------------------
# end to end: per-set plans vs the union reference under Monitor.open
# ---------------------------------------------------------------------------

def _run(spec, params, prog, *args, plan_mode):
    state = CounterState.zeros(spec)
    mon = scalpel.Monitor(spec, params=params, counter_axes=(),
                          plan_mode=plan_mode)
    with mon.open(params, calls_base=state.calls) as col:
        prog(*args)
    return state.add(col.delta)


def test_per_set_equals_union_exhaustive_scope():
    slots = [EventSpec(e, "x") for e in MOMENT_EVENTS]
    spec = MonitorSpec.of([ScopeContext.exhaustive("f", slots)])
    params = MonitorParams.all_on(spec)
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 33))
    x = x.at[0, 0].set(0.0).at[1, 1].set(jnp.inf)

    def prog(x):
        for i in range(4):
            with scalpel.function("f"):
                scalpel.probe(x=x * (i + 1))

    a = _run(spec, params, prog, x, plan_mode="per_set")
    b = _run(spec, params, prog, x, plan_mode="union")
    np.testing.assert_allclose(np.asarray(a.values), np.asarray(b.values),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(a.samples),
                                  np.asarray(b.samples))


def test_per_set_equals_union_multiplexed_mixed_events():
    """Moment-derived, entropy-channel and bespoke slots across event sets."""
    spec = MonitorSpec.of([
        ScopeContext.multiplexed("g", [
            [EventSpec("ACT_RMS", "y"), EventSpec("ACT_MAX_ABS", "y")],
            [EventSpec("ATTN_ENTROPY", "p"), EventSpec("MEAN", "y")],
            [EventSpec("SSM_STATE_RMS", "y")],
        ], period=2),
    ])
    params = MonitorParams.all_on(spec)
    y = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    p = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(2), (8, 16)), -1)

    def prog(y, p):
        for _ in range(9):
            with scalpel.function("g"):
                scalpel.probe(y=y, p=p)

    a = _run(spec, params, prog, y, p, plan_mode="per_set")
    b = _run(spec, params, prog, y, p, plan_mode="union")
    np.testing.assert_allclose(np.asarray(a.values), np.asarray(b.values),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(a.samples),
                                  np.asarray(b.samples))
    # and both match the unfused direct reference on the entropy slot
    # (every sampled call probed the same p, so value/samples == one call)
    want = float(events.compute(EventSpec("ATTN_ENTROPY", "p"), {"p": p}))
    got = float(a.values[0, 2]) / max(1, int(a.samples[0, 2]))
    assert got == pytest.approx(want, rel=1e-5)


def test_per_set_equals_union_under_jit_and_masks():
    slots = [EventSpec(e, "x") for e in ("ACT_RMS", "ACT_ZERO_FRAC",
                                         "NAN_COUNT")]
    spec = MonitorSpec.of([
        ScopeContext.exhaustive("hot", slots),
        ScopeContext.exhaustive("cold", slots),
    ])
    params = MonitorParams.selective(spec, ["hot"]).set_slot(
        spec, "hot", "ACT_ZERO_FRAC:x", False
    )
    x = jax.random.normal(jax.random.PRNGKey(5), (256,))

    def make(plan_mode):
        def step(x, s, mp):
            mon = scalpel.Monitor(spec, params=mp, counter_axes=(),
                                  plan_mode=plan_mode)
            with mon.open(mp, calls_base=s.calls) as col:
                with scalpel.function("hot"):
                    scalpel.probe(x=x)
                with scalpel.function("cold"):
                    scalpel.probe(x=x * 2)
            return s.add(col.delta)

        return jax.jit(step)

    s0 = CounterState.zeros(spec)
    a = make("per_set")(x, s0, params)
    b = make("union")(x, s0, params)
    np.testing.assert_allclose(np.asarray(a.values), np.asarray(b.values),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(a.samples),
                                  np.asarray(b.samples))
    # masked slot stayed dark, un-monitored scope stayed dark
    assert int(a.samples[0, 1]) == 0
    assert not np.any(np.asarray(a.values[1]))


def test_unknown_plan_mode_rejected():
    spec = MonitorSpec.of(
        [ScopeContext.exhaustive("f", [EventSpec("MEAN", "x")])]
    )
    params = MonitorParams.all_on(spec)
    mon = scalpel.Monitor(spec, params=params, counter_axes=(),
                          plan_mode="legacy")
    with pytest.raises(ValueError, match="plan_mode"):
        with mon.open(params, calls_base=CounterState.zeros(spec).calls):
            pass
