"""Continuous-batching serve engine (serve/driver.py + serve/engine.py).

The contracts under test, in decreasing order of subtlety:

* EXACTNESS — greedy tokens from the lane-packed megastep engine are
  bitwise equal to the serial per-request oracle, and per-lane counter
  attribution matches a fresh serial engine run of the same request
  (vmap stacked-equals-individual + the emit-then-decode ordering).

* SEEDED RNG INDEPENDENCE — a seeded request's sampling stream derives
  from PRNGKey(seed) alone, so two same-seed requests sample identical
  tokens regardless of which lane they land on or how much unseeded
  traffic runs concurrently (the serial engine's documented contract,
  inherited through the per-lane key columns).

* HOST-SYNC DISCIPLINE — the decode hot loop performs zero blocking
  readbacks per token: megasteps, admissions, and ring publishes are all
  async; tokens leave through the telemetry token ring drained one
  megastep behind.  Attested by counting ``jax.block_until_ready`` calls
  and by the engine's own dispatch/drain accounting.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import model_config
from repro.models.registry import Arch
from repro.serve.engine import ContinuousEngine, Engine, ServeConfig


@pytest.fixture(scope="module")
def tiny():
    return Arch(model_config("xlstm_125m", smoke=True))


@pytest.fixture(scope="module")
def params(tiny):
    return tiny.init(jax.random.PRNGKey(0))


def _prompt(seed, s=8, vocab=512):
    return jax.random.randint(jax.random.PRNGKey(seed), (1, s), 0, vocab)


def _serial(arch, params, prompt, max_new, seed=None, temperature=0.0):
    """Fresh serial oracle: one request, returns (tokens[n], counters)."""
    eng = Engine(arch, params,
                 ServeConfig(cache_len=64, max_new_tokens=max_new,
                             temperature=temperature))
    out, _ = eng.generate({"tokens": prompt}, seed=seed)
    return np.asarray(out)[0], eng.counters


def test_continuous_matches_serial_greedy_with_lane_reuse(tiny, params):
    """4 requests over 3 lanes (forces one lane reuse): greedy tokens
    exactly equal the serial oracle and per-lane counters attribute the
    full prefill+decode cost of each request."""
    prompts = [_prompt(i) for i in range(4)]
    eng = ContinuousEngine(
        tiny, params,
        ServeConfig(cache_len=64, max_new_tokens=6, n_lanes=3,
                    steps_per_commit=4))
    rids = [eng.submit(p) for p in prompts]
    res = eng.run()
    agg_calls = np.zeros_like(np.asarray(eng.counters.calls))
    for rid, prompt in zip(rids, prompts):
        want_toks, want_ctr = _serial(tiny, params, prompt, max_new=6)
        np.testing.assert_array_equal(res[rid].tokens, want_toks)
        got = res[rid].counters
        # attribution: the lane row carries this request's whole cost
        np.testing.assert_array_equal(np.asarray(got.calls),
                                      np.asarray(want_ctr.calls))
        np.testing.assert_array_equal(np.asarray(got.samples),
                                      np.asarray(want_ctr.samples))
        np.testing.assert_allclose(np.asarray(got.values),
                                   np.asarray(want_ctr.values), rtol=1e-5)
        agg_calls += np.asarray(got.calls)
        assert 0 <= res[rid].lane < 3
    # the lane-summed aggregate equals the sum of attributions
    np.testing.assert_array_equal(np.asarray(eng.counters.calls), agg_calls)
    assert eng.sched.admitted == 4 and eng.sched.completed == 4


def test_seeded_streams_independent_of_lane_and_traffic(tiny, params):
    """Satellite: same-seed sampled requests produce identical tokens no
    matter which lane serves them or what unseeded traffic interleaves —
    and both match the serial engine's stream bitwise."""
    prompt = _prompt(11)
    eng = ContinuousEngine(
        tiny, params,
        ServeConfig(cache_len=64, max_new_tokens=5, n_lanes=2,
                    steps_per_commit=2, temperature=0.8))
    r_a = eng.submit(prompt, seed=7)
    _ = eng.submit(_prompt(12))          # unseeded noise
    _ = eng.submit(_prompt(13))          # unseeded noise
    r_b = eng.submit(prompt, seed=7)     # same seed, later admission
    res = eng.run()
    np.testing.assert_array_equal(res[r_a].tokens, res[r_b].tokens)
    want, _ = _serial(tiny, params, prompt, max_new=5, seed=7,
                      temperature=0.8)
    np.testing.assert_array_equal(res[r_a].tokens, want)


def test_oversubscribed_admission_and_varying_lengths(tiny, params):
    """7 requests over 2 lanes with max_new 1..7: lanes recycle through
    admission/retirement and every request's tokens are the right greedy
    prefix (same prompt => shorter runs are prefixes of the longest)."""
    prompt = _prompt(3)
    want, _ = _serial(tiny, params, prompt, max_new=7)
    eng = ContinuousEngine(
        tiny, params,
        ServeConfig(cache_len=64, n_lanes=2, steps_per_commit=3))
    rids = [eng.submit(prompt, max_new=n) for n in range(1, 8)]
    res = eng.run()
    for n, rid in zip(range(1, 8), rids):
        np.testing.assert_array_equal(res[rid].tokens, want[:n])
    assert eng.sched.admitted == 7 and eng.sched.completed == 7
    assert eng.stats["tokens_out"] == sum(range(1, 8))


def test_decode_loop_makes_zero_host_syncs(tiny, params, monkeypatch):
    """The zero-syncs-per-token attestation: run() never calls
    ``jax.block_until_ready``, dispatches exactly ceil(max_new/K)
    megasteps, and drains the token ring once per megastep plus the one
    final (blocking) completion drain."""
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: (calls.append(1), real(x))[1])
    eng = ContinuousEngine(
        tiny, params,
        ServeConfig(cache_len=64, max_new_tokens=6, n_lanes=3,
                    steps_per_commit=4))
    for i in range(3):
        eng.submit(_prompt(20 + i))
    res = eng.run()
    assert not calls, "decode loop performed a blocking host sync"
    assert len(res) == 3 and all(len(r.tokens) == 6 for r in res.values())
    # all three admitted up front => lockstep retirement: ceil(6/4) = 2
    assert eng.stats["megasteps"] == math.ceil(6 / 4)
    assert eng.stats["token_drains"] == eng.stats["megasteps"] + 1
    assert eng.stats["prefills"] == 3 and eng.stats["admissions"] == 3
    assert eng.stats["tokens_out"] == 18
    assert eng.runtime.telemetry.dropped_tokens == 0


def test_max_new_zero_is_an_empty_result(tiny, params):
    """Satellite: explicit max_new=0 is honored (not treated as the config
    default) by both engines."""
    prompt = _prompt(5)
    eng = Engine(tiny, params, ServeConfig(cache_len=64, max_new_tokens=4))
    out, stats = eng.generate({"tokens": prompt}, max_new=0)
    assert out.shape == (1, 0)
    assert stats["decode_total_s"] == 0.0 and stats["decode_p50_s"] == 0.0
    assert eng.step_times == {}  # no timing bucket was touched
    ceng = ContinuousEngine(
        tiny, params,
        ServeConfig(cache_len=64, n_lanes=2, steps_per_commit=2))
    r0 = ceng.submit(prompt, max_new=0)
    r1 = ceng.submit(prompt, max_new=3)
    res = ceng.run()
    assert res[r0].tokens.shape == (0,) and res[r0].lane == -1
    want, _ = _serial(tiny, params, prompt, max_new=3)
    np.testing.assert_array_equal(res[r1].tokens, want)
    # an empty-only workload dispatches nothing
    ceng2 = ContinuousEngine(
        tiny, params, ServeConfig(cache_len=64, n_lanes=2),
        spec=ceng.spec)
    r2 = ceng2.submit(prompt, max_new=0)
    res2 = ceng2.run()
    assert res2[r2].tokens.shape == (0,)
    assert ceng2.stats["megasteps"] == 0 and ceng2.stats["prefills"] == 0


def test_decode_p50_keyed_by_shape_and_resettable(tiny, params):
    """Satellite: per-token decode timings bucket by (batch, max_new) so
    medians of different regimes never mix, and reset_stats() drops them."""
    eng = Engine(tiny, params, ServeConfig(cache_len=64, max_new_tokens=4))
    p1 = _prompt(30, s=8)
    p2 = jnp.concatenate([_prompt(31, s=8)] * 2, axis=0)  # batch of 2
    _, s1 = eng.generate({"tokens": p1})
    _, s2 = eng.generate({"tokens": p2})
    assert set(eng.step_times) == {(1, 4), (2, 4)}
    assert s1["decode_p50_s"] == eng.step_times[(1, 4)][0]
    assert s2["decode_p50_s"] == eng.step_times[(2, 4)][0]
    _, s3 = eng.generate({"tokens": p1})
    assert len(eng.step_times[(1, 4)]) == 2
    assert s3["decode_p50_s"] == pytest.approx(
        float(np.median(eng.step_times[(1, 4)])))
    # a different max_new is a different bucket too
    eng.generate({"tokens": p1}, max_new=2)
    assert (1, 2) in eng.step_times
    eng.reset_stats()
    assert eng.step_times == {}


def test_bucketed_prefill_mixed_lengths_exact_and_bounded_traces(
        tiny, params):
    """Prompt-length bucketing: mixed lengths 3..16 pad to pow2 buckets
    {8, 16}; greedy tokens stay exactly serial (mask-correct prefill), the
    prefill program compiles once per BUCKET (not per length), and the
    scheduler accounts the pad waste."""
    lengths = [3, 5, 8, 11, 13, 16]
    prompts = [_prompt(50 + i, s=s) for i, s in enumerate(lengths)]
    eng = ContinuousEngine(
        tiny, params,
        ServeConfig(cache_len=64, max_new_tokens=5, n_lanes=3,
                    steps_per_commit=4))
    assert eng._buckets == (8, 16, 32, 64)
    rids = [eng.submit(p, max_new=5) for p in prompts]
    res = eng.run()
    for rid, prompt in zip(rids, prompts):
        want, _ = _serial(tiny, params, prompt, max_new=5)
        np.testing.assert_array_equal(res[rid].tokens, want)
    cs = eng.compile_stats()
    assert cs["buckets_used"] == [8, 16]
    # the bucketing win: 6 distinct lengths, TWO prefill traces
    assert cs["prefill_traces"] == 2, cs
    assert cs["admission_traces"] == 1 and cs["megastep_traces"] == 1
    pad = sum(8 - s if s <= 8 else 16 - s for s in lengths)
    assert cs["pad_waste_frac"] == pytest.approx(
        pad / (pad + sum(lengths)))
    assert "pad_waste_frac" in eng.report()


def test_bucketed_prefill_kv_family_exact(params):
    """Bucketing on the KV-slab family: pad K/V slots sit past ``pos`` and
    are overwritten/masked by decode — tokens stay exactly serial."""
    arch = Arch(model_config("mistral_nemo_12b", smoke=True))
    tparams = arch.init(jax.random.PRNGKey(1))
    prompts = [jax.random.randint(jax.random.PRNGKey(60 + i), (1, s), 0,
                                  arch.cfg.vocab) for i, s in
               enumerate([5, 12])]
    eng = ContinuousEngine(
        arch, tparams,
        ServeConfig(cache_len=64, max_new_tokens=4, n_lanes=2,
                    steps_per_commit=2))
    rids = [eng.submit(p) for p in prompts]
    res = eng.run()
    for rid, prompt in zip(rids, prompts):
        want, _ = _serial(arch, tparams, prompt, max_new=4)
        np.testing.assert_array_equal(res[rid].tokens, want)
    assert eng.compile_stats()["buckets_used"] == [8, 16]
    assert eng.compile_stats()["prefill_traces"] == 2


def test_unbucketed_prefill_warns_on_per_length_retrace(tiny, params):
    """Satellite: with bucketing disabled, the third distinct prompt
    length trips the one-shot compile-churn warning pointing at
    ServeConfig.prefill_buckets."""
    eng = ContinuousEngine(
        tiny, params,
        ServeConfig(cache_len=64, max_new_tokens=2, n_lanes=3,
                    steps_per_commit=2, prefill_buckets=None))
    assert eng._buckets is None
    for i, s in enumerate([4, 6, 9]):
        eng.submit(_prompt(70 + i, s=s), max_new=2)
    with pytest.warns(RuntimeWarning, match="prefill_buckets"):
        res = eng.run()
    assert eng.compile_stats()["prefill_traces"] == 3  # one per length
    assert eng.compile_stats()["pad_waste_frac"] == 0.0
    assert len(res) == 3
    # the warning is one-shot: another retracing admission stays silent
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        eng.submit(_prompt(73, s=11), max_new=2)
        eng.run()


@pytest.mark.parametrize("arch_id", ["mistral_nemo_12b", "dbrx_132b",
                                     "pixtral_12b"],
                         ids=["dense", "moe", "vlm"])
def test_transformer_kv_slab_family(params, arch_id):
    """The KV-cache slab path (dense, moe and vlm families share
    ``transformer.decode_step``): lanes at different positions each write
    one row a layer into the carried stack and read their own, and greedy
    tokens stay bitwise equal to serial decode.  Three prompts of
    different lengths over two lanes: one lane is reused."""
    arch = Arch(model_config(arch_id, smoke=True))
    tparams = arch.init(jax.random.PRNGKey(1))
    prompts = [jax.random.randint(jax.random.PRNGKey(40 + i), (1, s), 0,
                                  arch.cfg.vocab)
               for i, s in enumerate([8, 5, 13])]
    max_new = [4, 7, 3]
    eng = ContinuousEngine(
        arch, tparams,
        ServeConfig(cache_len=64, max_new_tokens=8, n_lanes=2,
                    steps_per_commit=2))
    rids = [eng.submit(p, max_new=n) for p, n in zip(prompts, max_new)]
    res = eng.run()
    assert len({res[r].lane for r in rids}) == 2
    for rid, prompt, n in zip(rids, prompts, max_new):
        want, _ = _serial(arch, tparams, prompt, max_new=n)
        np.testing.assert_array_equal(res[rid].tokens, want)
