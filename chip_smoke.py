#!/usr/bin/env python3
"""Smoke run of the monitored train and serve paths on a TPU.

    python3 chip_smoke.py              # one chip: kernel, train, serve
    python3 chip_smoke.py --chips 4    # four chips: sharded serve and fit,
                                       # each against its one-device run

One process drives every phase through the entry points a user calls
(``ops.probe_moments``, ``train.loop.fit``, ``serve.ContinuousEngine``)
with seeded random weights, and checks what comes out:

* kernel — the Pallas probe-moment kernel on bf16 and f32 activations, under
  ``jax.vmap`` (as the serve megastep calls it) and with the entropy
  channel, against the unfused ``moments_ref``;
* train  — ``fit`` on the full ``xlstm_125m`` config (seq 2048, batch 8,
  8 steps in megasteps of 4, default monitor spec): finite losses, drained
  probe counters, and the probe kernel inside the compiled megastep;
* serve  — ``ContinuousEngine`` on ``qwen3_14b`` at its published widths,
  cut to 2 of its 40 layers: 6 requests of 128-1024 prompt tokens on 4
  lanes, exact token and counter accounting, and a greedy request against
  the serial ``Engine``.

``--chips 4`` runs only what needs the four chips: lane-sharded serving
(4 shards, 8 lanes) against one shard, and 4 ``fit`` steps on a (4, 1) data
mesh against one device.

Earlier lines report each phase's checks, its compile and run seconds and
the device's peak memory; the last line is one JSON object naming the
device.  With no TPU the script exits non-zero and prints no result.  The
persistent compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache`` in this checkout.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
TRAIN_ARCH, TRAIN_SEQ, TRAIN_BATCH = "xlstm_125m", 2048, 8
SERVE_ARCH, SERVE_LAYERS = "qwen3_14b", 2
# the prompt compared with the serial engine is a whole pow2 bucket, so the
# bucketed prefill and the serial exact-length prefill run the same shapes
GREEDY_PROMPT = 512


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok, info) -> None:
    """A check that holds under ``python -O`` too."""
    if not ok:
        raise RuntimeError(f"check failed: {info}")


# XLA's compile of a lowered program (tracing and lowering, which nest, are
# left in the run seconds)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Phase:
    """Times one phase: wall seconds, the part XLA spent compiling (from
    JAX's monitoring events), and compile-cache hits."""

    _active: "Phase | None" = None
    _installed = False

    def __init__(self, name: str, device):
        self.name, self.device = name, device
        self.compile_s = 0.0
        self.cache_hits = self.cache_misses = 0

    @classmethod
    def _install(cls) -> None:
        import jax

        def on_duration(event, secs, **_):
            p = cls._active
            if p is not None and event == COMPILE_EVENT:
                p.compile_s += secs

        def on_event(event, **_):
            p = cls._active
            if p is None:
                return
            if event == "/jax/compilation_cache/cache_hits":
                p.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                p.cache_misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        cls._installed = True

    def __enter__(self):
        if not Phase._installed:
            Phase._install()
        Phase._active = self
        self.t0 = time.perf_counter()
        log(f"[{self.name}] start")
        return self

    def __exit__(self, *exc):
        Phase._active = None
        if exc[0] is not None:
            return False
        wall = time.perf_counter() - self.t0
        mem = self.device.memory_stats() or {}
        log(f"[{self.name}] compile_s={self.compile_s:.2f} "
            f"run_s={wall - self.compile_s:.2f} wall_s={wall:.2f} "
            f"cache_hits={self.cache_hits} cache_misses={self.cache_misses} "
            f"peak_bytes_in_use={mem.get('peak_bytes_in_use', 'n/a')}")
        return False


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def check_moments(got, want, what: str) -> None:
    """Moment vectors agree: counts and max exactly, sums within f32."""
    import numpy as np

    from repro.kernels import probe_reduce as pr

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    exact = [pr.M_MAX_ABS, pr.M_ZERO, pr.M_NAN, pr.M_INF, pr.M_NUMEL]
    require(np.array_equal(got[exact], want[exact]), (what, got, want))
    summed = [pr.M_SUM_SQ, pr.M_SUM_ABS] + ([pr.M_ENT] if len(got) > 8
                                            else [])
    rel = np.abs(got[summed] - want[summed]) / np.abs(want[summed])
    # the plain sum cancels: bound its error by the sum of magnitudes
    rel_sum = abs(got[pr.M_SUM] - want[pr.M_SUM]) / want[pr.M_SUM_ABS]
    require(rel.max() < 1e-4 and rel_sum < 1e-5, (what, rel, rel_sum))
    log(f"  {what}: moments match moments_ref (max rel err "
        f"{rel.max():.2e}, sum err/sum_abs {rel_sum:.2e})")


def kernel_phase(interpret: bool = False, shape=(8, 2048, 768),
                 logits=(4, 1, 1, 151936), probs=(2, 4, 512, 512)) -> None:
    """The probe kernel where the paths call it: bf16 and f32 activations,
    vmapped per-lane logits, and attention probabilities with entropy."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, probe_reduce as pr

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(SEED), 3)
    x = jax.random.normal(k1, shape, jnp.float32)
    x = x.at[0, :5, 3].set(0.0)  # exact zeros: the masked count must see them
    for dt in (jnp.bfloat16, jnp.float32):
        xd = x.astype(dt)
        got = ops.probe_moments(xd, interpret=interpret)
        check_moments(got, pr.moments_ref(xd),
                      f"{jnp.dtype(dt).name} {shape}")
    lg = jax.random.normal(k2, logits, jnp.float32).astype(jnp.bfloat16)
    got = jax.vmap(lambda t: ops.probe_moments(t, interpret=interpret))(lg)
    want = jax.vmap(pr.moments_ref)(lg)
    for lane in range(logits[0]):
        check_moments(got[lane], want[lane],
                      f"vmap lane {lane} bf16 {logits[1:]}")
    p = jax.nn.softmax(jax.random.normal(k3, probs, jnp.float32), -1)
    got = ops.probe_moments(p, interpret=interpret, with_entropy=True)
    check_moments(got, pr.moments_ref(p, with_entropy=True),
                  f"entropy f32 {probs}")


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_run(cfg, *, seq: int, batch: int, steps: int, k: int, mesh=None):
    """``fit`` with the default monitor spec; returns its summary dict."""
    from repro.data import DataConfig
    from repro.models.registry import Arch
    from repro.optim import OptConfig
    from repro.train.loop import TrainLoopConfig, fit

    return fit(
        Arch(cfg),
        OptConfig(lr=3e-4, warmup_steps=2, total_steps=steps),
        DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch,
                   seed=SEED),
        TrainLoopConfig(steps=steps, steps_per_commit=k, hook_every=k,
                        log_every=0, seed=SEED),
        mesh=mesh,
    )


def train_phase(cfg, *, seq: int, batch: int, steps: int = 8,
                k: int = 4) -> None:
    import jax
    import numpy as np

    from repro.data import DataConfig, SyntheticLM

    # fit reports and closes its runtime: a failed drain raises there
    out = train_run(cfg, seq=seq, batch=batch, steps=steps, k=k)
    losses = np.asarray(out["losses"])
    require(losses.shape == (steps,) and np.isfinite(losses).all(), losses)
    log(f"  losses finite over {steps} steps: {losses.round(4).tolist()}")
    drained = out["runtime"].telemetry.last_state
    require(drained is not None, "no telemetry snapshot was drained")
    calls = int(np.sum(drained.calls))
    samples = int(np.sum(drained.samples))
    require(calls > 0 and samples > 0, (calls, samples))
    last = out["runtime"].telemetry.last_step
    require(last == steps, f"last drained step {last}, want {steps}")
    log(f"  drained report at step {steps}: probe calls={calls} "
        f"samples={samples}")
    # the compiled megastep carries the Pallas probe kernel
    host = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=seq,
                                  global_batch=batch)).batch_at(0)
    batches = {n: jax.ShapeDtypeStruct((k,) + v.shape, v.dtype)
               for n, v in host.items()}
    hlo = out["step"].lower(out["monitor"], batches,
                            out["state"]).compile().as_text()
    n_kernels = hlo.count("tpu_custom_call")
    require(n_kernels > 0, "probe kernel missing from the compiled megastep")
    log(f"  compiled megastep HLO holds tpu_custom_call x{n_kernels}")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def serve_requests(vocab: int, n: int, lo: int, hi: int, greedy_len: int):
    """Seeded prompts: the first is ``greedy_len`` long, the rest drawn
    uniformly from [lo, hi]."""
    import numpy as np

    rng = np.random.default_rng(SEED)
    lens = [greedy_len] + rng.integers(lo, hi + 1, size=n - 1).tolist()
    return [rng.integers(0, vocab, size=(1, s), dtype=np.int32)
            for s in lens]


def serve_run(arch, params, prompts, *, n_lanes: int, cache_len: int,
              max_new: int, lane_shards: int = 1):
    """One ``ContinuousEngine`` run; checks its exact accounting and
    returns ``(engine, rids, results)``."""
    import numpy as np

    from repro.serve.engine import ContinuousEngine, ServeConfig

    eng = ContinuousEngine(arch, params, ServeConfig(
        cache_len=cache_len, max_new_tokens=max_new, n_lanes=n_lanes,
        steps_per_commit=8, lane_shards=lane_shards))
    rids = [eng.submit(p, max_new=max_new) for p in prompts]
    results = eng.run()
    total = sum(len(r.tokens) for r in results.values())
    require(all(len(results[r].tokens) == max_new for r in rids),
            {r: len(results[r].tokens) for r in rids})
    plane = eng.runtime.telemetry
    require(plane.dropped_tokens == 0, plane.dropped_tokens)
    require(eng.stats["tokens_out"] == total == len(prompts) * max_new,
            (eng.stats["tokens_out"], total))
    lane_sum = sum(int(np.sum(results[r].counters.calls)) for r in rids)
    agg = int(np.sum(np.asarray(eng.counters.calls)))
    require(lane_sum == agg, (lane_sum, agg))
    # the final drain: close() re-raises a failed background drain
    eng.runtime.close()
    step = int(eng.lstate.step)
    drained = int(np.sum(plane.last_state.calls))
    require(plane.last_step == step and drained == agg,
            (plane.last_step, step, drained, agg))
    log(f"  lane_shards={lane_shards}: {len(rids)} requests x {max_new} "
        f"tokens on {n_lanes} lanes in {eng.stats['megasteps']} megasteps, "
        f"0 dropped, lane counter calls sum {lane_sum} == aggregate {agg} "
        f"== drained at step {step}")
    return eng, rids, results


def serve_phase(cfg, *, n_lanes: int = 4, cache_len: int = 2048,
                n_requests: int = 6, prompt_range=(128, 1024),
                greedy_len: int = GREEDY_PROMPT, max_new: int = 32) -> None:
    import jax
    import numpy as np

    from repro.models.registry import Arch
    from repro.serve.engine import Engine, ServeConfig

    arch = Arch(cfg)
    params = arch.init(jax.random.PRNGKey(SEED))
    prompts = serve_requests(cfg.vocab, n_requests, *prompt_range,
                             greedy_len=greedy_len)
    log(f"  prompt lengths {[p.shape[1] for p in prompts]}")
    eng, rids, results = serve_run(arch, params, prompts, n_lanes=n_lanes,
                                   cache_len=cache_len, max_new=max_new)
    log(f"  compile stats {eng.compile_stats()}")
    oracle = Engine(arch, params, ServeConfig(cache_len=cache_len,
                                              max_new_tokens=max_new))
    want, _ = oracle.generate({"tokens": prompts[0]})
    want = np.asarray(want)[0]
    got = results[rids[0]].tokens
    agree = int(np.sum(got == want))
    log(f"  greedy request vs serial Engine: {agree}/{max_new} tokens agree "
        f"(first {int(got[0])} vs {int(want[0])})")
    require(got[0] == want[0], (got[:4], want[:4]))


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def sharded_serve_phase(cfg, *, shards: int, n_lanes: int = 8,
                        cache_len: int = 2048, n_requests: int = 12,
                        prompt_range=(128, 1024),
                        greedy_len: int = GREEDY_PROMPT,
                        max_new: int = 32) -> None:
    """Lane-sharded serving against one shard: exact token counts and
    per-request counter calls; how many tokens agree is reported."""
    import jax
    import numpy as np

    from repro.models.registry import Arch

    arch = Arch(cfg)
    params = arch.init(jax.random.PRNGKey(SEED))
    prompts = serve_requests(cfg.vocab, n_requests, *prompt_range,
                             greedy_len=greedy_len)
    kw = dict(n_lanes=n_lanes, cache_len=cache_len, max_new=max_new)
    _, rids1, res1 = serve_run(arch, params, prompts, lane_shards=1, **kw)
    _, ridsn, resn = serve_run(arch, params, prompts, lane_shards=shards,
                               **kw)
    agree = total = 0
    for a, b in zip(rids1, ridsn):
        ra, rb = res1[a], resn[b]
        require(len(ra.tokens) == len(rb.tokens),
                (a, len(ra.tokens), len(rb.tokens)))
        ca, cb = np.asarray(ra.counters.calls), np.asarray(rb.counters.calls)
        require(np.array_equal(ca, cb), (a, ca, cb))
        agree += int(np.sum(ra.tokens == rb.tokens))
        total += len(ra.tokens)
    log(f"  {shards} shards vs 1: token counts and per-request counter "
        f"calls exact; {agree}/{total} tokens agree")


def sharded_train_phase(cfg, *, data: int, seq: int, batch: int,
                        steps: int = 4) -> None:
    """Data-parallel ``fit`` on a (data, 1) host mesh against one device."""
    import numpy as np

    from repro.launch.mesh import make_host_mesh

    one = train_run(cfg, seq=seq, batch=batch, steps=steps, k=steps)
    many = train_run(cfg, seq=seq, batch=batch, steps=steps, k=steps,
                     mesh=make_host_mesh(data=data, model=1))
    l1, ln = np.asarray(one["losses"]), np.asarray(many["losses"])
    s1 = one["runtime"].telemetry.last_state
    sn = many["runtime"].telemetry.last_state
    log(f"  losses 1 device {l1.round(4).tolist()} vs "
        f"({data},1) mesh {ln.round(4).tolist()}")
    require(np.isfinite(ln).all() and np.allclose(l1, ln, rtol=2e-2),
            (l1, ln))
    for lane in ("calls", "samples"):
        a, b = np.asarray(getattr(s1, lane)), np.asarray(getattr(sn, lane))
        require(np.array_equal(a, b), (lane, a, b))
    v1, vn = np.asarray(s1.values), np.asarray(sn.values)
    rel = np.max(np.abs(v1 - vn) / np.maximum(np.abs(v1), 1e-6))
    require(np.isfinite(vn).all() and rel < 5e-2, rel)
    log(f"  losses agree within bf16 tolerance; drained counter calls and "
        f"samples equal ({int(np.sum(s1.calls))}, {int(np.sum(s1.samples))})"
        f", values within {rel:.2e} relative")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded serve and train phases")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r} "
              f"devices", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2

    from repro.configs import model_config
    from repro.launch.compile_cache import enable_compile_cache

    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)} "
        f"(jax {jax.__version__})")
    log(f"compile cache: {enable_compile_cache()}")
    train_cfg = model_config(TRAIN_ARCH)
    serve_cfg = model_config(SERVE_ARCH).replace(n_layers=SERVE_LAYERS)
    log(f"train config: {train_cfg.name} {train_cfg.n_layers}L "
        f"d{train_cfg.d_model} vocab {train_cfg.vocab}, seq {TRAIN_SEQ}, "
        f"batch {TRAIN_BATCH}")
    log(f"serve config: {serve_cfg.name} d{serve_cfg.d_model} "
        f"{serve_cfg.n_heads}H/{serve_cfg.n_kv_heads}KV vocab "
        f"{serve_cfg.vocab}; reduced: n_layers "
        f"{model_config(SERVE_ARCH).n_layers} -> {SERVE_LAYERS}")

    if args.chips == 1:
        with Phase("kernel", dev):
            kernel_phase()
        with Phase("train", dev):
            train_phase(train_cfg, seq=TRAIN_SEQ, batch=TRAIN_BATCH)
        with Phase("serve", dev):
            serve_phase(serve_cfg)
    else:
        with Phase("serve x4", dev):
            sharded_serve_phase(serve_cfg, shards=args.chips)
        with Phase("train x4", dev):
            sharded_train_phase(train_cfg, data=args.chips, seq=TRAIN_SEQ,
                                batch=TRAIN_BATCH)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
