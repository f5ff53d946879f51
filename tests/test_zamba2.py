"""Zamba2 as published (models/hybrid.py, the Mamba2 of models/ssm.py)
against the plain reference of the benchmark's ``zamba2_7b_l27`` cell, at
the program's smoke widths on the CPU with seeded weights: the forward
pass; prefill then decode, directly and through ``ContinuousEngine``'s lane
slab; bucketed prefill against exact-length prefill; two-group Mamba2
against a hand computation; where each shared block and adapter acts; and
the whole benchmark cell (sound, float8 control, altered token)."""
from __future__ import annotations

import copy
import dataclasses
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run as run_mod  # noqa: E402
from repro.configs import model_config  # noqa: E402
from repro.models import hybrid, ssm  # noqa: E402
from repro.models.registry import Arch  # noqa: E402
from repro.serve.engine import ContinuousEngine, ServeConfig  # noqa: E402

CELL = "zamba2_7b_l27.serve.chat4k"
SEED = 2**31 + 17
REF = run_mod.load_module(run_mod.BENCH / "configs" / "zamba2_7b_l27.py")
# f32 smoke model against the f32 reference: agreement to rounding
TOL = 2e-4


@pytest.fixture(scope="module")
def model():
    cfg = model_config("zamba2_7b", smoke=True)
    arch = Arch(cfg)
    m = dataclasses.asdict(cfg)
    return arch, m, jax.jit(arch.init)(jax.random.PRNGKey(SEED))


@pytest.fixture(scope="module")
def ref_logits(model):
    """Reference logits of a whole sequence, at every position."""
    _, m, _ = model
    params = REF.init_params(m, SEED)
    fn = jax.jit(lambda t, r: REF.logits_at(m, params, t, r))

    def run(tokens):
        s = len(tokens)
        padded = np.zeros(REF.Q_BLOCK, np.int32)
        padded[:s] = tokens
        return np.asarray(fn(jnp.asarray(padded), jnp.arange(s)))

    return run


def _tokens(seed, s, vocab=512):
    return np.random.default_rng(seed).integers(1, vocab, s).astype(np.int32)


def test_parameters_follow_the_references_rule(model):
    arch, m, params = model
    ref = REF.init_params(m, SEED)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    assert [tuple(getattr(q, "key", getattr(q, "idx", None)) for q in p)
            for p, _ in flat] == sorted(ref)
    for (_, leaf), key in zip(flat, sorted(ref)):
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(ref[key]))
    counts = REF.param_count(m)
    assert counts["stored"] == arch.n_params()
    # three sites over two blocks: block A serves twice; the tied table
    # counts as embedding and as unembedding
    block_a = sum(int(np.prod(x.shape))
                  for x in jax.tree.leaves(params["blocks"][0]))
    assert counts["total"] == counts["stored"] + block_a + counts["embed"]


def test_forward_logits_match_the_reference(model, ref_logits):
    arch, _, params = model
    toks = _tokens(1, 45)
    got = arch.forward(params, {"tokens": jnp.asarray(toks)[None]})[0]
    np.testing.assert_allclose(np.asarray(got), ref_logits(toks), atol=TOL)


def test_prefill_then_decode_match_the_reference(model, ref_logits):
    """Bucketed prefill (right-padded, traced ``length``), then decode
    through the cache, against the reference's full forward pass."""
    arch, _, params = model
    toks = _tokens(2, 30)
    p = 21
    want = ref_logits(toks)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :p] = toks[:p]
    cache, logits = arch.prefill(params, {"tokens": jnp.asarray(padded)},
                                 cache_len=48, length=jnp.int32(p))
    np.testing.assert_allclose(np.asarray(logits[0, -1]), want[p - 1],
                               atol=TOL)
    for i in range(p, len(toks)):
        logits, cache = arch.decode_step(params, cache,
                                         jnp.asarray(toks[None, i:i + 1]))
        np.testing.assert_allclose(np.asarray(logits[0, -1]), want[i],
                                   atol=TOL)
    assert int(cache["pos"]) == len(toks)


def test_engine_lanes_at_different_positions_match_the_reference(
        model, ref_logits):
    """Three requests of different lengths on two lanes of the lane slab
    (one lane reused), greedy: each served token is the reference's best
    at its position."""
    arch, _, params = model
    eng = ContinuousEngine(arch, params, ServeConfig(
        cache_len=64, n_lanes=2, steps_per_commit=3, temperature=0.0))
    prompts = [_tokens(10 + i, s) for i, s in enumerate((9, 23, 14))]
    news = (7, 5, 11)
    rids = [eng.submit(p[None], max_new=n) for p, n in zip(prompts, news)]
    res = eng.run()
    assert eng.compile_stats()["buckets_used"] == [16, 32]
    for rid, prompt, n in zip(rids, prompts, news):
        served = np.asarray(res[rid].tokens)
        assert len(served) == n
        want = ref_logits(np.concatenate([prompt, served]))
        at = want[len(prompt) - 1:len(prompt) + n - 1]
        gap = at.max(-1) - np.take_along_axis(at, served[:, None], -1)[:, 0]
        assert gap.max() <= TOL, gap


def _oracle_decode_step(cfg, params, cache, tokens):
    """One decode step layer by layer: each layer's ``mamba2_decode`` on
    its own state, each site's ``decode_attention`` on a ``[b, S, kv, hd]``
    copy of its cache; states and caches come back in the stored order."""
    from repro.models import layers as L

    x = L.embed(cfg, params["embed"], tokens)
    x0, pos, t = x, cache["pos"], jnp.zeros_like(x)
    new = {"mamba_ssm": [], "mamba_conv": [], "site_k": [], "site_v": []}
    for r, ((a, b, site), lp) in enumerate(zip(hybrid.runs(cfg),
                                               params["mamba"])):
        if site is not None:
            kc, vc = (jnp.transpose(cache[k][site], (0, 3, 1, 2))
                      for k in ("site_k", "site_v"))

            def attend(acfg, p, h, kc=kc, vc=vc):
                y, k2, v2 = L.decode_attention(
                    acfg, p, h, kc, vc, pos, scale=hybrid.attn_scale(cfg))
                return y, (k2, v2)

            bp = params["blocks"][site % cfg.hybrid.num_mem_blocks]
            t, kv = hybrid._site(cfg, bp, params["sites"][site], x, x0,
                                 attend)
            new["site_k"].append(jnp.transpose(kv[0], (0, 2, 3, 1)))
            new["site_v"].append(jnp.transpose(kv[1], (0, 2, 3, 1)))
        states = []
        for i in range(b - a):
            lpi = jax.tree.map(lambda w: w[i], lp)
            h = L.rms_norm(x + t, lpi["ln"], hybrid.NORM_EPS)
            y, st = ssm.mamba2_decode(cfg, lpi["mix"], h,
                                      cache["mamba_ssm"][r][i],
                                      cache["mamba_conv"][r][i])
            x, t = x + y, jnp.zeros_like(t)
            states.append(st)
        new["mamba_ssm"].append(jnp.stack([st[0] for st in states]))
        new["mamba_conv"].append(jnp.stack([st[1] for st in states]))
    x = L.rms_norm(x, params["final_norm"], hybrid.NORM_EPS)
    return L.unembed(cfg, params["embed"], x), dict(new, pos=pos + 1)


def test_lane_vmapped_decode_step_matches_a_layer_by_layer_oracle(model):
    """``decode_step`` under the serve driver's lane ``vmap``, three lanes
    at different positions over seeded states and caches: the same logits
    and the same updated states, K and V as the oracle's, lane by lane."""
    arch, _, params = model
    cfg, lanes, cache_len = arch.cfg, 3, 32
    shapes = arch.init_lane_cache(lanes, cache_len, abstract=True)
    leaves, tree = jax.tree.flatten(shapes)
    rng = np.random.default_rng(6)
    cache = jax.tree.unflatten(tree, [
        jnp.asarray(rng.normal(size=sd.shape), sd.dtype) for sd in leaves])
    cache["pos"] = jnp.asarray([5, 17, 30], jnp.int32)
    tokens = jnp.asarray(_tokens(6, lanes))[:, None, None]
    logits, got = jax.jit(jax.vmap(
        lambda c, tok: hybrid.decode_step(cfg, params, c, tok)))(cache,
                                                                 tokens)
    for lane in range(lanes):
        one = jax.tree.map(lambda a: a[lane], cache)
        want_logits, want = _oracle_decode_step(cfg, params, one,
                                                tokens[lane])
        for a, b in zip(jax.tree.leaves((want_logits, want)),
                        jax.tree.leaves(jax.tree.map(
                            lambda a: a[lane], (logits, got)))):
            a, b = np.asarray(a), np.asarray(b)
            np.testing.assert_allclose(b, a, rtol=1e-5,
                                       atol=1e-5 * max(np.abs(a).max(), 1))


def test_bucketed_prefill_equals_exact_length_prefill(model):
    arch, _, params = model
    toks = _tokens(3, 13)
    exact, le = arch.prefill(params, {"tokens": jnp.asarray(toks)[None]},
                             cache_len=40)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :13] = toks
    bucket, lb = arch.prefill(params, {"tokens": jnp.asarray(padded)},
                              cache_len=40, length=jnp.int32(13))
    assert int(bucket["pos"]) == int(exact["pos"]) == 13
    # equal to float32 rounding, not bitwise: the padded width changes the
    # shapes of the matmuls, and with them XLA's order of summation
    for key in ("mamba_ssm", "mamba_conv", "site_k"):
        for a, b in zip(exact[key], bucket[key]):
            a, b = np.asarray(a), np.asarray(b)
            if key == "site_k":  # [b, kv, hd, S]: the real positions
                a, b = a[..., :13], b[..., :13]
            np.testing.assert_allclose(a, b, rtol=1e-4,
                                       atol=1e-5 * np.abs(a).max())
    np.testing.assert_allclose(np.asarray(le), np.asarray(lb), rtol=1e-4,
                               atol=1e-5 * np.abs(np.asarray(le)).max())


def test_two_group_mamba2_matches_a_hand_computation():
    """``n_groups`` = 2: heads 0..h/2-1 read group 0's B and C, the rest
    group 1's, and the gated norm runs over each group's channels."""
    cfg = model_config("zamba2_7b", smoke=True)
    di, nh, p, g, N = ssm._mamba2_dims(cfg)
    assert g == 2
    from repro.models.params import init_tree

    lp = init_tree(ssm.mamba2_specs(cfg), jax.random.PRNGKey(4),
                   jnp.float32)
    rng = np.random.default_rng(4)
    lp = dict(lp, A_log=jnp.asarray(rng.normal(size=nh), jnp.float32),
              dt_bias=jnp.asarray(rng.normal(size=nh), jnp.float32),
              D=jnp.asarray(rng.normal(size=nh), jnp.float32),
              norm=jnp.asarray(rng.normal(size=di), jnp.float32))
    s = 37  # three chunks of 16, the last one ragged
    x = rng.normal(size=(1, s, cfg.d_model)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        got, (S, _) = ssm.mamba2(cfg, lp, jnp.asarray(x))
    w = {k: np.asarray(v, np.float64) for k, v in lp.items()}
    proj = x[0].astype(np.float64) @ w["in_proj"].T
    z, xbc, dt = proj[:, :di], proj[:, di:2 * di + 2 * g * N], \
        proj[:, 2 * di + 2 * g * N:]
    xp = np.concatenate([np.zeros((3, xbc.shape[1])), xbc])
    conv = sum(xp[i:i + s] * w["conv_w"][i] for i in range(4)) + w["conv_b"]
    conv = conv / (1 + np.exp(-conv))
    xs = conv[:, :di].reshape(s, nh, p)
    B = conv[:, di:di + g * N].reshape(s, g, N)
    C = conv[:, di + g * N:].reshape(s, g, N)
    dt = np.log1p(np.exp(dt + w["dt_bias"]))
    A = -np.exp(w["A_log"])
    y = np.zeros((s, nh, p))
    state = np.zeros((nh, p, N))
    for head in range(nh):
        grp = head // (nh // g)
        Sh = np.zeros((p, N))
        for t in range(s):
            Sh = Sh * np.exp(dt[t, head] * A[head]) \
                + dt[t, head] * np.outer(xs[t, head], B[t, grp])
            y[t, head] = Sh @ C[t, grp] + w["D"][head] * xs[t, head]
        state[head] = Sh
    y = y.reshape(s, di) * (z / (1 + np.exp(-z)))
    yg = y.reshape(s, g, di // g)
    yg = yg / np.sqrt(np.mean(yg * yg, -1, keepdims=True) + ssm.NORM_EPS)
    want = (yg.reshape(s, di) * w["norm"]) @ w["out_proj"]
    np.testing.assert_allclose(np.asarray(S)[0], state, rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(got)[0], want, rtol=2e-3,
                               atol=2e-4)


def _states(arch, params, toks):
    cache, _ = arch.prefill(params, {"tokens": jnp.asarray(toks)[None]},
                            cache_len=32)
    return cache


def _perturbed(params, path):
    out = jax.tree.map(lambda a: a, params)
    node = out
    for k in path[:-1]:
        node = node[k]
    node[path[-1]] = node[path[-1]] * 1.5
    return out


@pytest.mark.parametrize("path,site", [
    (("blocks", 1, "attn", "wq"), 1),          # block B acts first at site 1
    (("blocks", 1, "attn", "wk"), 1),
    (("blocks", 1, "gate_up"), 1),
    (("sites", 1, "adapter_out"), 1),          # site 1's own adapter
    (("sites", 2, "linear"), 2),
    (("blocks", 0, "down"), 0),                # block A acts at site 0
])
def test_shared_blocks_alternate_and_each_site_keeps_its_adapter(
        model, path, site):
    """Sites 0, 1, 2 (layers 2, 3, 5) run blocks A, B, A.  Perturbing a
    weight of block B or of one site leaves every state before the first
    site it serves unchanged and changes the state right after it."""
    arch, _, params = model
    toks = _tokens(5, 20)
    base = _states(arch, params, toks)
    moved = _states(arch, _perturbed(params, path), toks)
    runs = hybrid.runs(arch.cfg)
    for i, (_, _, run_site) in enumerate(runs):
        same = np.array_equal(np.asarray(base["mamba_ssm"][i]),
                              np.asarray(moved["mamba_ssm"][i]))
        before = run_site is None or run_site < site
        assert same == before, (path, i)
    # the site's own KV moves only with its block's key projection
    for k in range(len(base["site_k"])):
        same = np.array_equal(np.asarray(base["site_k"][k]),
                              np.asarray(moved["site_k"][k]))
        moved_here = k > site or (k == site and path[-1] == "wk")
        assert same == (not moved_here), (path, k)


def test_site_kv_is_sequence_sharded_and_axes_mirror_the_cache(model):
    from repro.dist.partition import tree_shardings
    from jax.sharding import Mesh

    arch, _, _ = model
    cache = arch.init_cache(2, 32, abstract=True)
    axes = arch.cache_axes()
    assert jax.tree.structure(cache) == jax.tree.structure(
        axes, is_leaf=lambda a: isinstance(a, tuple))
    assert all(ax == ("batch", None, None, "kv_seq")
               for ax in axes["site_k"] + axes["site_v"])
    acfg = hybrid.attn_cfg(arch.cfg)
    assert cache["site_k"][0].shape == (2, acfg.n_kv_heads,
                                        acfg.resolved_head_dim, 32)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    shs = tree_shardings(cache, axes, mesh)
    assert len(jax.tree.leaves(shs)) == len(jax.tree.leaves(cache))


# -- the benchmark cell, at the smoke widths -------------------------------

def _cell_context(tmp_path, seed):
    from bench import common

    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    loaded = run_mod.load_cell(benchmark, CELL)
    config = copy.deepcopy(loaded["config"])
    config["model"] = dataclasses.asdict(model_config("zamba2_7b",
                                                      smoke=True))
    tr = copy.deepcopy(loaded["traffic"])
    tr.update(n_lanes=2, cache_len=96, steps_per_commit=2,
              nominal_tokens_per_s=40, trace_requests=2, trace_delay_s=0.0,
              trace_seconds=0.05, check_requests=2)
    tr["prompt"].update(median=12, min=4, max=40)
    tr["output"].update(median=6, min=2, max=16)
    # the gap is in logits, which spread as 0.02 * sqrt(d_model): the smoke
    # model's are a seventh as wide as the cell's, so its limit is too (the
    # f32 smoke model reads 0, the float8 control 0.21-0.33 on two seeds)
    tr["limits"]["logit_gap"] = 0.1
    return loaded["kind"], common.RunContext(
        cell=loaded["cell"], config=config, traffic=tr,
        reference=loaded["reference"], monitor_cfg=loaded["monitor_cfg"],
        seed=seed, seconds=0.2, trace=False, devices=jax.devices()[:1],
        t0=time.perf_counter(), work_dir=tmp_path,
        compiles=common.Compiles())


def test_the_cell_is_correct_and_its_control_and_an_altered_token_are_not(
        tmp_path, monkeypatch):
    kind, ctx = _cell_context(tmp_path, 2**31 + 41)
    checks = kind.run(ctx)["checks"]
    assert all(c["value"] <= c["limit"] for c in checks.values()), checks

    kind, ctx = _cell_context(tmp_path, 2**31 + 41)
    checks = kind.run(ctx, control=True)["checks"]
    assert checks["logit_gap"]["value"] > checks["logit_gap"]["limit"], \
        checks

    from repro.serve import driver as driver_mod

    sample = driver_mod.DecodeDriver.sample

    def altered(self, logits, rng):
        return (sample(self, logits, rng) + 1) % logits.shape[-1]

    def fault(mod):
        monkeypatch.setattr(mod.DecodeDriver, "sample", altered)

    kind, ctx = _cell_context(tmp_path, 2**31 + 41)
    checks = kind.run(ctx, fault=fault)["checks"]
    assert checks["logit_gap"]["value"] > checks["logit_gap"]["limit"]


def test_state_probe_roofline_takes_only_the_ssd_state_calls():
    """The reader of the cell's new per-layer metric, on a stand-in trace:
    probe calls over the f32 [.., 112, 64, 64] state count, others do not;
    the share is bytes over seconds over the HBM peak."""
    from types import SimpleNamespace

    calls = [
        {"seconds": 2e-3, "operands": [("f32", (12, 112, 64, 64))]},
        {"seconds": 1e-3, "operands": [("f32", (112, 64, 64))]},
        {"seconds": 5e-3, "operands": [("bf16", (12, 1, 3584))]},
        {"seconds": 4e-3, "operands": [("f32", (4, 384, 384))]},
    ]
    trace = SimpleNamespace(kernel_calls=lambda pattern: calls)
    r = {"kind": "serve", "trace": trace, "window_s": 1.0,
         "devices": [SimpleNamespace(device_kind="TPU v5 lite")]}
    roof = run_mod.load_module(run_mod.BENCH / "metrics"
                               / "state_probe_roofline.serve.py")
    moved = 13 * 112 * 64 * 64 * 4
    assert roof.read(r) == pytest.approx(100 * moved / 3e-3 / 819e9)
    none = dict(r, trace=SimpleNamespace(kernel_calls=lambda p: calls[2:]))
    assert roof.read(none) is None
    assert roof.read(dict(r, kind="train")) is None
