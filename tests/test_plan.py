"""Probe-plan compiler: per-(scope, event set) moment plans, the dense
slot layout / compact scan carry, spec fingerprints, and runtime event-set
hot-swap through the plan layer without re-tracing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core as scalpel
from repro.core import plan as plan_lib
from repro.core.context import EventSpec, MonitorSpec, ScopeContext
from repro.core.counters import CounterState, MonitorParams

SIX = ("ACT_RMS", "ACT_MEAN_ABS", "ACT_MAX_ABS", "ACT_ZERO_FRAC",
       "NAN_COUNT", "INF_COUNT")


def _sparse_ctx(scope="hot", period=1):
    """A multiplexed scope whose every set needs a strict SUBSET of the
    union: the workload per-set plans exist for."""
    return ScopeContext.multiplexed(scope, [
        [EventSpec("ACT_MAX_ABS", "x")],
        [EventSpec("ACT_ZERO_FRAC", "x")],
        [EventSpec("ACT_RMS", "x"), EventSpec("MEAN", "x")],
    ], period=period)


# ---------------------------------------------------------------------------
# plan compilation
# ---------------------------------------------------------------------------

def test_per_set_plans_sweep_exact_subsets():
    sp = plan_lib.compile_scope_plans(_sparse_ctx(), frozenset({"x"}))
    assert sp.n_sets == 3 and sp.width == 4
    chans = [p.sweeps[0].channels for p in sp.plans]
    assert chans[0] == ("max_abs",)
    assert chans[1] == ("zero_count", "numel")
    assert chans[2] == ("sum", "sum_sq", "numel")
    assert [p.members for p in sp.plans] == [(0,), (1,), (2, 3)]
    # sweep_channel_count excludes the free static channels
    assert [p.sweep_channel_count for p in sp.plans] == [1, 1, 2]


def test_union_plans_widen_every_set():
    sp = plan_lib.compile_scope_plans(_sparse_ctx(), frozenset({"x"}),
                                      True)
    union = ("sum", "sum_sq", "max_abs", "zero_count", "numel")
    for p in sp.plans:
        assert p.sweeps[0].channels == union
    # membership (and therefore the scatter footprint) is still per-set
    assert [p.members for p in sp.plans] == [(0,), (1,), (2, 3)]


def test_plans_split_fused_and_bespoke_slots():
    ctx = ScopeContext.exhaustive("g", [
        EventSpec("ACT_RMS", "y"),
        EventSpec("ATTN_ENTROPY", "p"),          # fused via ent_sum channel
        EventSpec("MOE_LOAD", subevent="CV"),    # bespoke (dict event)
    ])
    sp = plan_lib.compile_scope_plans(
        ctx, frozenset({"y", "p", "router_probs"})
    )
    (p0,) = sp.plans
    kinds = {s.index: s.fused for s in p0.slots}
    assert kinds == {0: True, 1: True, 2: False}
    sweeps = {sw.tensor: sw.channels for sw in p0.sweeps}
    assert sweeps == {"y": ("sum_sq", "numel"), "p": ("ent_sum", "rows")}


def test_plans_respect_available_tensors():
    ctx = _sparse_ctx()
    sp = plan_lib.compile_scope_plans(ctx, frozenset({"other"}))
    assert not sp.any_live
    # and the cache keys on availability, not just the context
    sp2 = plan_lib.compile_scope_plans(ctx, frozenset({"x"}))
    assert sp2.any_live


# ---------------------------------------------------------------------------
# dense slot layout + compact scan carry
# ---------------------------------------------------------------------------

def _spec_uneven():
    return MonitorSpec.of([
        ScopeContext.exhaustive("wide", [EventSpec(e, "x") for e in SIX]),
        ScopeContext.exhaustive("narrow", [EventSpec("MEAN", "x")]),
        ScopeContext.exhaustive("dark", []),
    ])


def test_slot_layout_packs_scopes_contiguously():
    lay = plan_lib.spec_layout(_spec_uneven())
    assert lay.widths == (6, 1, 0)
    assert lay.offsets == (0, 6, 7)
    assert lay.total == 7
    sids, slids = lay.scatter_indices
    assert sids.tolist() == [0] * 6 + [1]
    assert slids.tolist() == [0, 1, 2, 3, 4, 5, 0]


def test_compact_delta_roundtrip():
    spec = _spec_uneven()
    state = CounterState.zeros(spec)
    state = CounterState(
        calls=state.calls.at[0].set(3),
        values=state.values.at[0, 2].set(5.0).at[1, 0].set(7.0),
        samples=state.samples.at[0, 2].set(2).at[1, 0].set(1),
    )
    compact = plan_lib.CompactDelta.compress(spec, state)
    assert compact.values.shape == (7,)
    back = compact.expand(spec)
    np.testing.assert_array_equal(np.asarray(back.values),
                                  np.asarray(state.values))
    np.testing.assert_array_equal(np.asarray(back.samples),
                                  np.asarray(state.samples))
    np.testing.assert_array_equal(np.asarray(back.calls),
                                  np.asarray(state.calls))


def test_scan_carries_compact_footprint_and_matches_unrolled():
    """The scan carry is [total] wide (the live footprint), not
    [n_scopes, max_slots]; the result is identical to an unrolled loop."""
    spec = _spec_uneven()
    params = MonitorParams.all_on(spec)
    xs = jnp.arange(8.0).reshape(8, 1)
    lay = plan_lib.spec_layout(spec)
    assert lay.total < spec.n_scopes * spec.max_slots  # 7 vs 18

    def body(c, x):
        with scalpel.function("wide"):
            scalpel.probe(x=x + c)
        with scalpel.function("narrow"):
            scalpel.probe(x=x * 2)
        return c + 1.0, x

    state = CounterState.zeros(spec)
    with scalpel.Monitor(spec, params=params, counter_axes=()).open(
            params, calls_base=state.calls) as col:
        scalpel.scan_with_counters(body, jnp.zeros(()), xs)
    scanned = state.add(col.delta)

    state2 = CounterState.zeros(spec)
    with scalpel.Monitor(spec, params=params, counter_axes=()).open(
            params, calls_base=state2.calls) as col2:
        c = jnp.zeros(())
        for i in range(8):
            c, _ = body(c, xs[i])
    unrolled = state2.add(col2.delta)

    np.testing.assert_array_equal(np.asarray(scanned.calls),
                                  np.asarray(unrolled.calls))
    np.testing.assert_allclose(np.asarray(scanned.values),
                               np.asarray(unrolled.values), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(scanned.samples),
                                  np.asarray(unrolled.samples))


# ---------------------------------------------------------------------------
# spec fingerprint
# ---------------------------------------------------------------------------

def test_fingerprint_stable_and_plan_sensitive():
    a = MonitorSpec.of([_sparse_ctx()])
    b = MonitorSpec.of([_sparse_ctx()])
    assert a.fingerprint == b.fingerprint          # structural, not id-based
    c = MonitorSpec.of([_sparse_ctx(period=5)])
    assert a.fingerprint == c.fingerprint          # period is runtime-dynamic
    d = a.with_context(
        ScopeContext.exhaustive("hot", [EventSpec("MEAN", "x")])
    )
    assert a.fingerprint != d.fingerprint          # different compiled plans


def test_fingerprint_distinguishes_bespoke_events():
    """Two bespoke slots both compile to empty sweeps — the fingerprint
    must still tell them apart (it hashes slot identities, not just the
    sweep table), or telemetry would attribute two different traced probe
    graphs to the same plan."""
    a = MonitorSpec.of(
        [ScopeContext.exhaustive("s", [EventSpec("SSM_STATE_RMS", "h")])]
    )
    b = MonitorSpec.of(
        [ScopeContext.exhaustive("s", [EventSpec("MOE_LOAD",
                                                 subevent="CV")])]
    )
    assert a.fingerprint != b.fingerprint


def test_describe_plans_lists_sets_and_footprint():
    text = plan_lib.describe_plans(_spec_uneven())
    assert "wide: width 6" in text
    assert "ACT_RMS:x" in text            # slot identities are spelled out
    assert "total live footprint: 7 slot(s)" in text


# ---------------------------------------------------------------------------
# runtime event-set hot-swap through the plan layer (paper SIGUSR1 reload)
# ---------------------------------------------------------------------------

CONFIG_SET_A = """
BINARY=test
NO_FUNCTIONS=1
[FUNCTION]
FUNC_NAME=hot
MULTIPLEX_PERIOD=1
NO_EVENTS=0
[/FUNCTION]
"""

CONFIG_SET_B = """
BINARY=test
NO_FUNCTIONS=2
[FUNCTION]
FUNC_NAME=hot
MULTIPLEX_PERIOD=3
NO_EVENTS=1
[EVENT]
ID=ACT_MAX_ABS:x
NO_SUBEVENTS=0
[/EVENT]
[/FUNCTION]
[FUNCTION]
FUNC_NAME=cold
NO_EVENTS=0
[/FUNCTION]
"""


def test_config_hot_swap_switches_plans_without_retrace(tmp_path):
    """A config-file reload (the SIGUSR1 path) re-selects among the compiled
    per-set plans — masks/periods swap as dynamic inputs, the jitted step
    never re-traces, untouched sets keep their plans (one jit cache entry,
    fingerprint constant), and the counters follow the new selection."""
    spec = MonitorSpec.of([
        _sparse_ctx("hot"),
        ScopeContext.exhaustive("cold", [EventSpec("MEAN", "x")]),
    ])
    cfgp = tmp_path / "mon.cfg"
    cfgp.write_text(CONFIG_SET_A)
    rt = scalpel.ScalpelRuntime(spec, config_path=str(cfgp))
    fp0 = rt.plan_fingerprint
    traces = []

    def step(state, mparams, x):
        traces.append(1)
        with scalpel.Monitor(spec, params=mparams, counter_axes=()).open(
                mparams, calls_base=state.calls) as col:
            with scalpel.function("hot"):
                scalpel.probe(x=x)
            with scalpel.function("cold"):
                scalpel.probe(x=x)
        return state.add(col.delta)

    f = jax.jit(step)
    x = jnp.ones((64,)) * 2.0
    s = CounterState.zeros(spec)
    for _ in range(6):
        s = f(s, rt.params, x)
    # config A: hot fully on, 6 calls cycle sets 0,1,2,0,1,2
    assert np.asarray(s.samples)[0, :4].tolist() == [2, 2, 2, 2]
    assert int(s.samples[1, 0]) == 0          # cold not in config A

    cfgp.write_text(CONFIG_SET_B)
    rt.reload()                               # the paper's SIGUSR1 swap
    assert rt.plan_fingerprint == fp0         # plans: compiled, re-selected
    for _ in range(6):
        s = f(s, rt.params, x)
    assert len(traces) == 1                   # ONE trace across both configs
    assert f._cache_size() == 1
    # config B: only ACT_MAX_ABS live in hot (slot 0), cold fully on
    smp = np.asarray(s.samples)
    assert smp[0, 0] > 2                      # set-0 slot kept sampling
    assert smp[0, 1:4].tolist() == [2, 2, 2]  # other sets' slots masked off
    assert smp[1, 0] == 6                     # cold now monitored
    # the max-abs slot's estimate follows its own per-set plan (1 channel)
    est = scalpel.estimates(spec, s)
    assert est["hot"]["ACT_MAX_ABS:x"] == pytest.approx(2.0)
    rt.close()


def test_plan_mode_inherited_by_scan_children():
    """capture()/scan children compile against the parent's plan mode."""
    spec = MonitorSpec.of([_sparse_ctx("hot")])
    params = MonitorParams.all_on(spec)
    xs = jnp.ones((6, 8))

    def body(c, x):
        with scalpel.function("hot"):
            scalpel.probe(x=x)
        return c, x

    outs = {}
    for mode in ("per_set", "union"):
        state = CounterState.zeros(spec)
        mon = scalpel.Monitor(spec, params=params, counter_axes=(),
                              plan_mode=mode)
        with mon.open(params, calls_base=state.calls) as col:
            assert col.plan_mode == mode
            scalpel.scan_with_counters(body, jnp.zeros(()), xs)
        outs[mode] = state.add(col.delta)
    np.testing.assert_allclose(np.asarray(outs["per_set"].values),
                               np.asarray(outs["union"].values),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(outs["per_set"].samples),
                                  np.asarray(outs["union"].samples))
