"""Serving engines: jitted prefill + decode with ScALPEL counters.

Two engines share the monitoring machinery:

* ``Engine`` — the static-batch reference: a fixed batch of slots, one
  prefill per batch, token-synchronous decode steps driven by a host loop
  (one dispatch + host sample per token).  Kept as the semantics oracle:
  the continuous engine's greedy tokens and seeded RNG streams are
  bitwise-checked against it.

* ``ContinuousEngine`` — the production path (ROADMAP item 1): a packed
  request SLAB of ``n_lanes`` decode lanes, each an independent request at
  its own position over its own KV/recurrent cache, advanced K tokens per
  dispatch by a device-resident megastep (``serve/driver.py``) with
  on-device sampling.  New requests enter free lanes between megasteps
  (one compiled admission program — no re-trace); finished lanes retire
  in-graph via the active mask.  Sampled tokens leave through the
  telemetry plane's token ring, drained one megastep behind the dispatch,
  so the decode hot loop performs ZERO host syncs per token — the only
  blocking readback is the final drain at request completion.

Monitoring rides the functional ``Monitor`` API in both: the serial engine
threads one ``MonitorState``; the continuous engine threads a
``LaneMonitorState`` whose per-lane counter rows attribute NaN/entropy
anomalies to individual requests while the lane-summed aggregate feeds the
same ring → drain → adaptive-controller stack unchanged.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro import core as scalpel
from repro.models.registry import Arch

from .driver import DecodeDriver
from .scheduler import Scheduler, ServeResult  # noqa: F401  (re-export)


@dataclasses.dataclass
class ServeConfig:
    cache_len: int = 1024
    max_new_tokens: int = 32
    temperature: float = 0.0  # 0 => greedy
    seed: int = 0
    # continuous-batching knobs (ignored by the serial Engine):
    # n_lanes — decode lanes in the packed slab (concurrent requests).
    # steps_per_commit — K tokens per megastep dispatch.  Bounds BOTH the
    #   dispatch amortization and the reaction latency: admission, adaptive
    #   decisions, and knob swaps land at megastep boundaries, up to K
    #   tokens late (the ROADMAP megastep note) — so serving defaults to a
    #   modest K instead of the pure-throughput optimum.
    # token_ring_depth — token egress ring slots; 0 => max(2*K, 8) (the
    #   pipelined drain consumes K slots per megastep).
    # lane_shards — shard the decode slab over this many devices along a
    #   1-D "lanes" mesh axis (dist.partition.lane_mesh).  Every lane-dim
    #   tensor (slab, tok/keys/masks, per-lane counter rows, token-ring
    #   slots) stays per-shard; only the lane-summed counter aggregate
    #   psum-reduces.  1 = single device (byte-identical programs to the
    #   unsharded engine).  Must divide n_lanes.
    # prefill_buckets — prompt-length pad policy: "pow2" pads each prompt
    #   to the next power-of-two bucket (>= prefill_bucket_min, <=
    #   cache_len) so admission+prefill compile once per BUCKET instead of
    #   once per distinct prompt length; None = exact-length (retrace per
    #   length).  Auto-disabled for families without a length-masked
    #   prefill (models.registry.Arch.supports_prefill_length).
    n_lanes: int = 4
    steps_per_commit: int = 8
    token_ring_depth: int = 0
    lane_shards: int = 1
    prefill_buckets: str | None = "pow2"
    prefill_bucket_min: int = 8

    def bucket_widths(self, supports_length: bool) -> tuple[int, ...] | None:
        """Resolve the configured pad-bucket widths (None = bucketing off)."""
        if self.prefill_buckets is None or not supports_length:
            return None
        if self.prefill_buckets != "pow2":
            raise ValueError(
                f"unknown prefill_buckets policy {self.prefill_buckets!r} "
                f"(expected 'pow2' or None)")
        widths, b = [], max(1, int(self.prefill_bucket_min))
        while b <= self.cache_len:
            widths.append(b)
            b *= 2
        return tuple(widths) or None


def _discover_spec(arch: Arch, cfg: ServeConfig):
    """Scope discovery from an abstract prefill + decode (shared by both
    engines so they compile identical probe plans)."""

    def probe_fn(p, toks):
        cache, logits = arch.prefill(p, {"tokens": toks},
                                     cache_len=cfg.cache_len)
        return arch.decode_step(p, cache, toks[:, :1])

    seen = scalpel.discover(
        probe_fn, arch.abstract_params(),
        jax.ShapeDtypeStruct((1, min(32, cfg.cache_len)), jnp.int32),
    )
    return scalpel.spec_from_discovery(seen)


class Engine:
    def __init__(self, arch: Arch, params, cfg: ServeConfig,
                 spec=None, runtime=None):
        self.arch = arch
        self.params = params
        self.cfg = cfg
        if spec is None:
            spec = _discover_spec(arch, cfg)
        self.spec = spec
        self.runtime = runtime or scalpel.ScalpelRuntime(spec)
        # ONE pytree replaces the old (counters, ring, decode_step) triple:
        # the monitor borrows the runtime's telemetry plane for its ring.
        self.mon = scalpel.Monitor(spec, telemetry=self.runtime.telemetry)
        self.mstate = self.mon.init()
        # per-token decode times, keyed by (batch, max_new): medians of one
        # regime never mix with another's (a [1,1]-shape decode is not
        # comparable to a [16,1] one)
        self.step_times: dict[tuple[int, int], list[float]] = {}
        # the RNG carries across generate() calls — reseeding per call would
        # make every generation sample identically (see generate()).
        self._rng = jax.random.PRNGKey(cfg.seed)

        def _prefill(params, batch):
            return self.arch.prefill(params, batch,
                                     cache_len=self.cfg.cache_len)

        def _decode(params, cache, tokens):
            return self.arch.decode_step(params, cache, tokens)

        # wrapped signatures: (mstate, *args) -> (out, mstate).  Monitor.jit
        # draws the jit boundary leaf-wise (runtime knobs never round-trip
        # the graph); the cache is donated, the MonitorState is NOT (its
        # ring buffers are read by the telemetry drain thread while later
        # decode steps run).
        self._jit_prefill = self.mon.jit(_prefill)
        self._jit_decode = self.mon.jit(_decode, donate_argnums=(1,))

    @property
    def counters(self):
        """The engine's cumulative counters (compact dense layout)."""
        return self.mstate.counters

    def reset_stats(self) -> None:
        """Drop accumulated decode timings (all shape buckets)."""
        self.step_times.clear()

    def _sample(self, logits, rng):
        logits = logits[:, -1, :].astype(jnp.float32)
        if self.cfg.temperature <= 0:
            return jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        logits = logits / self.cfg.temperature
        return jax.random.categorical(rng, logits)[:, None].astype(jnp.int32)

    def generate(self, batch: dict[str, Any], max_new: int | None = None,
                 seed: int | None = None):
        """batch: {'tokens': [b, s], ...extras}. Returns [b, n_new] tokens.

        ``max_new=None`` falls back to the config default; an explicit
        ``max_new=0`` is honored and returns an empty ``[b, 0]`` result.

        ``seed``: per-request seed; by default the engine's RNG is split and
        carried across calls so repeated sampled generations differ.

        RNG strategy: a seeded request derives its whole sampling stream
        from ``PRNGKey(seed)`` alone — it never reads or advances the
        engine-level carried RNG, and the per-token keys are split from
        the request key, not from any monitoring state.  Consequences the
        adaptive loop depends on: (a) two requests with the same seed and
        prompt sample identical tokens regardless of how many unseeded
        requests ran in between (engine split order is irrelevant), and
        (b) a monitoring plan swap mid-decode (``runtime.set_params`` /
        cadence change picked up by the per-token ``mon.sync``) cannot
        perturb sampling — MonitorParams are masks over counter lanes,
        data-flow-disjoint from logits and keys.  Tested in
        test_train_serve.py::test_serve_seeded_rng_independent, and
        inherited by the continuous engine's per-lane keys
        (test_serve_batching.py).
        """
        max_new = self.cfg.max_new_tokens if max_new is None else int(max_new)
        if max_new <= 0:
            b = int(np.shape(batch["tokens"])[0])
            return (
                jnp.zeros((b, 0), jnp.int32),
                {"prefill_s": 0.0, "decode_total_s": 0.0,
                 "decode_per_tok_s": 0.0, "decode_p50_s": 0.0},
            )
        if seed is not None:
            rng = jax.random.PRNGKey(seed)
        else:
            self._rng, rng = jax.random.split(self._rng)
        t0 = time.perf_counter()
        # pick up live runtime knobs (mask/period/cadence) — reference
        # swaps into the state pytree, never a re-trace
        self.mstate = self.mon.sync(self.mstate, runtime=self.runtime)
        (cache, logits), self.mstate = self._jit_prefill(
            self.mstate, self.params, batch
        )
        self.runtime.observe(self.mstate.counters)
        jax.block_until_ready(logits)  # output sync: sampling needs logits
        prefill_s = time.perf_counter() - t0
        outs = []
        tok = self._sample(logits, rng)
        t0 = time.perf_counter()
        for i in range(max_new):
            outs.append(tok)
            self.mstate = self.mon.sync(self.mstate, runtime=self.runtime)
            (logits, cache), self.mstate = self._jit_decode(
                self.mstate, self.params, cache, tok
            )
            # async monitoring: swap the ring ref to the drain thread and
            # keep decoding — no block_until_ready inside the token loop.
            self.runtime.on_step(self.mstate.counters,
                                 ring=self.mstate.ring)
            rng, sub = jax.random.split(rng)
            tok = self._sample(logits, sub)
        out = jnp.concatenate(outs, axis=1)
        jax.block_until_ready(out)  # output sync: the sampled tokens
        decode_s = time.perf_counter() - t0
        per_tok = decode_s / max_new
        shape_key = (int(np.shape(batch["tokens"])[0]), max_new)
        bucket = self.step_times.setdefault(shape_key, [])
        bucket.append(per_tok)
        return (
            out,
            {
                "prefill_s": prefill_s,
                "decode_total_s": decode_s,
                "decode_per_tok_s": per_tok,
                # p50 over THIS call's (batch, max_new) bucket only
                "decode_p50_s": float(np.median(bucket)),
            },
        )

    def report(self) -> str:
        self.runtime.observe(self.mstate.counters)
        return self.runtime.report("ScALPEL serving report")


class ContinuousEngine:
    """Continuous-batching engine: submit requests, run megasteps, join.

    Usage::

        eng = ContinuousEngine(arch, params, ServeConfig(n_lanes=8))
        rid = eng.submit(tokens, max_new=64, seed=123)
        results = eng.run()      # {rid: ServeResult(tokens, counters, lane)}

    RNG contract (inherited from ``Engine.generate``): a seeded request's
    stream derives from ``PRNGKey(seed)`` alone — the first token samples
    with the unsplit key on the prefill logits, then each decode step
    splits per token inside its lane.  vmap guarantees per-lane streams
    are bitwise identical to a serial run, so identical seeds produce
    identical tokens regardless of lane placement or concurrent unseeded
    traffic.

    Host-sync discipline: megastep dispatch, admission (prefill + slab
    write), counter-ring publish and token-ring publish are all async; the
    token ring is drained one megastep BEHIND the dispatch (its producer
    already retired, so the copy doesn't wait on in-flight work).  The one
    blocking readback is the final drain when all lanes empty — request
    completion.  ``stats`` counts every dispatch and drain so tests can
    attest the zero-syncs-per-token claim.
    """

    def __init__(self, arch: Arch, params, cfg: ServeConfig,
                 spec=None, runtime=None):
        self.arch = arch
        self.params = params
        self.cfg = cfg
        if spec is None:
            spec = _discover_spec(arch, cfg)
        self.spec = spec
        self.runtime = runtime or scalpel.ScalpelRuntime(spec)
        self.mon = scalpel.Monitor(spec, telemetry=self.runtime.telemetry)
        n = int(cfg.n_lanes)
        shards = int(cfg.lane_shards)
        self.mesh = None
        if shards > 1:
            from repro.dist.partition import lane_mesh

            if n % shards:
                raise ValueError(
                    f"n_lanes={n} must divide evenly over "
                    f"lane_shards={shards}")
            self.mesh = lane_mesh(shards)
        self.driver = DecodeDriver(
            arch, self.mon, cache_len=cfg.cache_len,
            temperature=cfg.temperature,
            steps_per_commit=cfg.steps_per_commit,
            mesh=self.mesh,
        )
        self._buckets = cfg.bucket_widths(arch.supports_prefill_length)
        self.sched = Scheduler(n, buckets=self._buckets)
        self.lstate = self.mon.lane_init(n)
        # per-lane decode state: slab of batch-1 caches + current token +
        # RNG key + active/remaining masks (all donated through megasteps)
        self.slab = arch.init_lane_cache(n, cfg.cache_len, mesh=self.mesh)
        self.tok = jnp.zeros((n, 1, 1), jnp.int32)
        self.keys = jnp.stack([jax.random.PRNGKey(0)] * n)
        self.active = jnp.zeros((n,), jnp.int32)
        self.remaining = jnp.zeros((n,), jnp.int32)
        depth = int(cfg.token_ring_depth) or max(2 * cfg.steps_per_commit, 8)
        self.tok_ring = self.runtime.telemetry.make_token_ring(n, depth)
        if self.mesh is not None:
            self._place_sharded()
        self._rng = jax.random.PRNGKey(cfg.seed)
        self._warned_traces = False
        self.stats = {
            "megasteps": 0, "prefills": 0, "admissions": 0,
            "tokens_out": 0, "token_drains": 0, "wall_s": 0.0,
        }

    def _place_sharded(self) -> None:
        """Lay the initial lane state out on the lane mesh: lane-dim leaves
        split over the ``lanes`` axis, aggregate leaves replicated — the
        shard_map programs then consume everything without a resharding
        copy (and donation recycles the same sharded buffers)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self.mesh
        lane = NamedSharding(mesh, P("lanes"))
        rep = NamedSharding(mesh, P())
        row1 = NamedSharding(mesh, P(None, "lanes"))
        put = jax.device_put
        self.slab = jax.tree.map(lambda x: put(x, lane), self.slab)
        self.tok = put(self.tok, lane)
        self.keys = put(self.keys, lane)
        self.active = put(self.active, lane)
        self.remaining = put(self.remaining, lane)
        ls = self.lstate
        self.lstate = dataclasses.replace(
            ls,
            lane_calls=put(ls.lane_calls, lane),
            lane_values=put(ls.lane_values, lane),
            lane_samples=put(ls.lane_samples, lane),
            lane_sched=put(ls.lane_sched, lane),
            calls=put(ls.calls, rep),
            values=put(ls.values, rep),
            samples=put(ls.samples, rep),
            step=put(ls.step, rep),
            ring=jax.tree.map(lambda x: put(x, rep), ls.ring),
        )
        tr = self.tok_ring
        self.tok_ring = dataclasses.replace(
            tr, steps=put(tr.steps, rep), toks=put(tr.toks, row1),
            live=put(tr.live, row1), head=put(tr.head, rep),
        )

    @property
    def counters(self):
        """Aggregate (lane-summed) cumulative counters — serial-comparable."""
        return self.lstate.counters

    def submit(self, tokens, max_new: int | None = None,
               seed: int | None = None) -> int:
        """Queue a single request (tokens: [1, s]); returns its rid.
        ``max_new=None`` falls back to the config; 0 completes immediately
        with an empty result."""
        max_new = self.cfg.max_new_tokens if max_new is None \
            else int(max_new)
        return self.sched.submit(tokens, max_new, seed)

    def _admit_ready(self, mega: int) -> None:
        for lane in self.sched.free_lanes():
            if not self.sched.queue:
                break
            req = self.sched.queue.popleft()
            s = int(np.shape(req.tokens)[1])
            # one span per admission: route, pad and both dispatches
            with TraceAnnotation("scalpel.serve.admit", step=mega,
                                 rid=req.rid, width=self.sched.width(s)):
                self._admit(lane, req, s)

    def _admit(self, lane: int, req, s: int) -> None:
        if req.seed is not None:
            key = jax.random.PRNGKey(req.seed)
        else:
            self._rng, key = jax.random.split(self._rng)
        # two async dispatches per admission: monitored prefill (+
        # first-token sample with the UNSPLIT request key — the serial
        # contract) and the slab/counter-row write
        width = self.sched.route(s)
        if self._buckets is not None:
            toks = np.asarray(req.tokens)
            if width > s:
                toks = np.pad(toks, ((0, 0), (0, width - s)))
            cache, tok0, pdelta = self.driver.prefill_bucketed(
                self.params, self.lstate.params, toks, s, key)
        else:
            cache, tok0, pdelta = self.driver.prefill(
                self.params, self.lstate.params, req.tokens, key)
        self._check_traces()
        (self.slab, self.tok, self.keys, self.active,
         self.remaining), self.lstate = self.driver.admit(
            self.lstate, self.slab, self.tok, self.keys, self.active,
            self.remaining, lane, cache, tok0, key, req.max_new, pdelta)
        self.sched.admit(lane, req)
        self.stats["prefills"] += 1
        self.stats["admissions"] += 1

    def _check_traces(self) -> None:
        """One-shot compile-churn warning: when prefill has traced more
        than twice per bucket actually in use, admission is re-compiling
        per prompt length — point at the bucket config."""
        if self._warned_traces:
            return
        traces = self.driver.trace_counts()["prefill_traces"]
        n_buckets = (len(self.sched.buckets_used)
                     if self._buckets is not None else 1)
        if traces > 2 * max(1, n_buckets):
            self._warned_traces = True
            import warnings

            hint = ("prefill_buckets is disabled or unsupported for this "
                    "family" if self._buckets is None else
                    f"buckets in use: {sorted(self.sched.buckets_used)}")
            warnings.warn(
                f"serve prefill has compiled {traces} traces for "
                f"{max(1, n_buckets)} prompt bucket(s) — every distinct "
                f"prompt length is re-tracing. Configure "
                f"ServeConfig.prefill_buckets/prefill_bucket_min to bound "
                f"compiles ({hint}).", RuntimeWarning, stacklevel=3)

    def run(self) -> dict[int, ServeResult]:
        """Drive megasteps until every submitted request completes.

        Host spans (``jax.profiler.TraceAnnotation``, on the device trace's
        clock), one per leaf phase of a megastep, none inside another, each
        carrying the megastep index as ``step``; ``drain_tokens`` writes its
        own ``scalpel.tokens.wait`` and ``scalpel.tokens``."""
        plane = self.runtime.telemetry
        k = self.cfg.steps_per_commit
        t0 = time.perf_counter()
        while True:
            mega = self.stats["megasteps"]
            # knob swaps (adaptive/runtime) land here — megastep boundary
            with TraceAnnotation("scalpel.serve.sync", step=mega):
                self.lstate = self.mon.sync(self.lstate,
                                            runtime=self.runtime)
            self._admit_ready(mega)
            if not self.sched.occupied:
                break
            with TraceAnnotation("scalpel.serve.dispatch", step=mega):
                (self.slab, self.tok, self.keys, self.active,
                 self.remaining), self.lstate, self.tok_ring = \
                    self.driver.megastep(
                        self.lstate, self.params, self.slab, self.tok,
                        self.keys, self.active, self.remaining,
                        self.tok_ring)
            self.stats["megasteps"] += 1
            with TraceAnnotation("scalpel.serve.advance", step=mega):
                # arithmetic completion: each occupied lane advanced by
                # min(K, remaining) tokens — no device readback to retire
                for lane, rid in self.sched.advance(k):
                    # harvest per-request counters as eager device slices
                    # (async); materialized at join
                    self.sched.set_counters(
                        rid, self.lstate.lane_counters(lane))
            with TraceAnnotation("scalpel.serve.publish", step=mega):
                # async monitoring egress: aggregate ring to the drain
                # thread
                self.runtime.on_step(self.lstate.counters,
                                     ring=self.lstate.ring)
            # pipelined token drain: consume the PREVIOUS megastep's ring
            # before publishing this one.  On a TPU its head reaches the
            # host only after the megastep just dispatched has left its
            # decode loop, so this is where the loop waits for the device
            # (``scalpel.tokens.wait``).
            drained = plane.drain_tokens(step=mega)
            with TraceAnnotation("scalpel.serve.attribute", step=mega):
                self.stats["tokens_out"] += self.sched.attribute(drained)
                self.stats["token_drains"] += 1
            with TraceAnnotation("scalpel.serve.publish", step=mega):
                plane.publish_tokens(self.tok_ring)
        # the one blocking readback: the final ring drain at completion
        drained = plane.drain_tokens(step=mega)
        with TraceAnnotation("scalpel.serve.attribute", step=mega):
            self.stats["tokens_out"] += self.sched.attribute(drained)
            self.stats["token_drains"] += 1
        self.stats["wall_s"] += time.perf_counter() - t0
        if plane.dropped_tokens:
            raise RuntimeError(
                f"token ring overrun: {plane.dropped_tokens} slots lost — "
                f"token_ring_depth must exceed appends per drain")
        results = self.sched.results()
        for r in results.values():
            if r.counters is not None:
                r.counters = scalpel.Monitor.lane_counters_host(r.counters)
        return results

    def compile_stats(self) -> dict[str, Any]:
        """Jit cache sizes of the three serve programs plus the pad-waste
        fraction — the bucketing win's observable surface."""
        out = self.driver.trace_counts()
        out["pad_waste_frac"] = self.sched.pad_waste_frac
        out["buckets_used"] = sorted(self.sched.buckets_used)
        return out

    def report(self) -> str:
        self.runtime.observe(self.lstate.counters)
        rep = self.runtime.report("ScALPEL serving report (continuous)")
        cs = self.compile_stats()
        rep += (
            f"\ncompile: prefill_traces={cs['prefill_traces']} "
            f"admission_traces={cs['admission_traces']} "
            f"megastep_traces={cs['megastep_traces']} "
            f"pad_waste_frac={cs['pad_waste_frac']:.3f} "
            f"lane_shards={int(self.cfg.lane_shards)}"
        )
        return rep
