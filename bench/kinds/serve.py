"""Runs serving cells: an offline batch through the program's
``ContinuousEngine``.

Set-up makes the weights on the device from the seed in one jitted call,
builds the engine, and warms it with one request per lane, spread over the
prefill buckets that the window's prompts use, so that every program the
window runs is compiled or loaded from the persistent cache.  The window is
one ``run()`` over a queue of requests whose number follows from
``--seconds`` and the traffic's nominal rate: a fixed amount of work for a
given ``--seconds``.  With ``--trace 1`` a second ``run()`` over
``trace_requests`` more requests of the same sizes follows, of which
``trace_seconds`` are profiled.

Then the check: the engine and the weights are freed, and the plain
reference runs over a seeded sample of the finished requests, the one with
most served tokens among them.  It compares how far each served token's
logit lies below the reference's best, and each request's drained calls of
the logits scope with the positions at which the server computed logits
(the prefill's last and one per decode step).
"""
from __future__ import annotations

import gc
import threading
import time

import jax
import numpy as np

from bench import common, flops, gen

KIND = "serve"
LOGITS_SCOPE = "logits"


def window_requests(tr: dict, seconds: float) -> int:
    mean_out = gen.mean_lognormal_size(tr["output"])
    n = round(seconds * tr["nominal_tokens_per_s"] / mean_out)
    return max(int(tr["n_lanes"]), int(n))


def run(ctx: common.RunContext, fault=None, control: bool = False) -> dict:
    """Set up, measure, check.  ``fault`` (tests only) is called with the
    program's decode module before the engine is built, to break the timed
    path; ``control`` (``bench/control.py``) puts the reference's
    lower-precision control in the program's place in the comparison."""
    from repro.models.registry import Arch
    from repro.serve import driver as driver_mod
    from repro.serve.engine import ContinuousEngine, ServeConfig

    tr = ctx.traffic
    cfg = common.model_config(ctx.config)
    arch = Arch(cfg)
    params = jax.jit(arch.init)(jax.random.PRNGKey(ctx.seed))
    scfg = ServeConfig(cache_len=int(tr["cache_len"]),
                       n_lanes=int(tr["n_lanes"]),
                       steps_per_commit=int(tr["steps_per_commit"]),
                       temperature=0.0, seed=0)
    if ctx.monitor_cfg is not None:
        raise ValueError("serving cells run with every scope probed; a "
                         "monitor mode is not supported here yet")
    if fault is not None:
        fault(driver_mod)
    eng = ContinuousEngine(arch, params, scfg)

    n = window_requests(tr, ctx.seconds)
    reqs = gen.serve_requests(tr, n, cfg.vocab, ctx.seed)
    buckets = sorted({gen.pow2_bucket(p.shape[1], scfg.prefill_bucket_min)
                      for p, _ in reqs})
    # warm-up: every lane, every bucket in use, a few megasteps
    rng = np.random.default_rng(ctx.seed)
    warm_new = 2 * scfg.steps_per_commit + 1
    for i in range(scfg.n_lanes):
        width = buckets[i % len(buckets)]
        eng.submit(rng.integers(1, cfg.vocab, size=(1, width),
                                dtype=np.int32), max_new=warm_new)
    eng.run()

    rids = [eng.submit(prompt, max_new=max_new) for prompt, max_new in reqs]
    sched = eng.sched
    pad0, prompt0 = sched.pad_tokens, sched.prompt_tokens
    c0 = ctx.compiles.snapshot()
    t0 = time.perf_counter()
    finished = eng.run()
    t1 = time.perf_counter()
    # the engine returns every request of its life, warm-up included
    results = {rid: finished[rid] for rid in rids if rid in finished}
    c1 = ctx.compiles.snapshot()
    seconds = t1 - t0
    generated = sum(len(r.tokens) for r in results.values())
    prompt_tokens = sum(int(p.shape[1]) for p, _ in reqs)
    pads = sched.pad_tokens - pad0
    window = {
        "seconds": seconds, "generated": generated,
        "prompt_tokens": prompt_tokens,
        "model_flops": (prompt_tokens + generated)
        * flops.serve_flops_per_token(ctx.reference, ctx.config["model"]),
        "pad_waste_frac": pads / (pads + sched.prompt_tokens - prompt0),
    }
    e2e = {"serve_tokens_per_s": generated / seconds, "setup_s": t0 - ctx.t0}

    readings = None
    if ctx.trace:
        # the same sizes in another order, from a third of the way down the
        # queue: all lanes busy for longer than the traced seconds
        extra = gen.serve_requests(tr, n, cfg.vocab, ctx.seed + 1)
        first = len(extra) // 3
        for prompt, max_new in extra[first:first + int(tr["trace_requests"])]:
            eng.submit(prompt, max_new=max_new)
        traced = common.TracedPart(ctx.work_dir)
        timer = threading.Timer(tr["trace_delay_s"], traced.start,
                                args=(tr["trace_seconds"],))
        timer.start()
        eng.run()
        timer.join()
        readings = traced.reduce()
    mem = common.memory_peak_bytes(ctx.devices)

    # a seeded sample of the finished requests, with the longest among them
    by_len = sorted(results, key=lambda rid: -len(results[rid].tokens))
    pick = np.random.default_rng(ctx.seed).permutation(by_len[1:])
    sample = [by_len[0]] + [int(r) for r in
                            pick[:int(tr["check_requests"]) - 1]]
    rid_to_req = dict(zip(rids, reqs))
    failed = sum(1 for rid, (_, max_new) in rid_to_req.items()
                 if rid not in results or len(results[rid].tokens) != max_new)
    scope = eng.spec.scope_index(LOGITS_SCOPE)
    served = []
    for rid in sample:
        res = results[rid]
        served.append({
            "prompt": rid_to_req[rid][0][0],
            "tokens": np.asarray(res.tokens, np.int32),
            "calls": int(res.counters.calls[scope]),
        })
    del eng, params, results, finished
    gc.collect()

    checks = compare(tr, ctx.reference.check_requests(
        ctx.config["model"], ctx.seed, served, control), served,
        window_compiles=c1["compiles"] - c0["compiles"])
    r = {"kind": KIND, "chips": int(ctx.cell["chips"]),
         "devices": ctx.devices, "window": window}
    if readings is not None:
        r.update(readings)
    return {"e2e": e2e, "readings": r, "checks": checks,
            "attempted": n, "failed": failed,
            "memory_peak_bytes": mem}


def compare(tr: dict, ref: list[np.ndarray], served: list[dict], *,
            window_compiles: int) -> dict:
    """Each compared number beside its limit (``tr["limits"]``)."""
    lim = tr["limits"]
    gap = max(float(np.max(g)) for g in ref)
    calls_off = max(abs(s["calls"] - (len(s["tokens"]) + 1)) for s in served)
    return {
        "logit_gap": common.check(gap, lim["logit_gap"]),
        "logits_calls_off": common.check(calls_off, 0.0),
        "window_compiles": common.check(window_compiles, 0.0),
    }
