"""Device-resident decode driver for the continuous-batching engine.

One jitted **megastep** advances every lane by K tokens without touching
the host: a ``lax.scan`` (the ``Monitor.scan`` megastep shape — K inner
steps per commit/dispatch boundary) whose body

1. appends the lanes' CURRENT tokens to the token egress ring (tokens are
   emitted the step they are consumed, matching the serial engine's
   emit-then-decode order),
2. vmaps the single-request ``decode_step`` + on-device sampling over the
   lane axis, with a per-lane collector opened INSIDE the vmap so counters
   attribute to lanes (each lane's per-token RNG key splits exactly like
   the serial engine's, so seeded streams are bitwise identical to a
   serial run — vmap semantics guarantee stacked-equals-individual),
3. folds the lane-stacked delta through ``Monitor.commit_lanes`` (inactive
   lanes masked out; aggregate counters ring-append at the telemetry
   cadence), and
4. advances the per-lane active/remaining masks — finished lanes retire
   in-graph, no re-trace.

K (``steps_per_commit``) bounds both the per-token dispatch amortization
and the reaction latency: admission and adaptive/knob swaps land at
megastep boundaries, up to K tokens late (the ROADMAP megastep-latency
note) — so serving defaults to a modest K rather than the throughput
optimum.

The jit boundary is leaf-wise (``Monitor.jit_wrapped`` style): the
read-only ``params``/``tparams``/model params are inputs only, and the
slab + per-lane decode state are donated — the steady-state loop allocates
nothing for the cache.  The rings are NEVER donated: the host drains their
buffers while the next megastep runs.

Lane-mesh sharding (``mesh`` + ``lane_axis``): all three programs compile
through ``shard_map`` over a 1-D ``lanes`` mesh (``partition.lane_mesh``)
so the slab spans devices.  The invariants, per program:

* megastep — lane-dim state (slab / tok / keys / masks / per-lane counter
  rows / ``lane_sched`` / token-ring slots) stays PER-SHARD; only the
  lane-SUMMED aggregate psum-reduces over the lane axis
  (``Monitor.commit_lanes`` via ``counter_reduce_axes``), feeding the
  unchanged replicated ring/adaptive stack.  ``lane_sched`` must never see
  the psum (the ROADMAP mux invariant).
* admission — every shard runs the same program on its local block; the
  traced GLOBAL lane index maps to a local one, and only the owning shard
  takes the write (clamped-index + ``owned`` mask; see ``write_lane`` /
  ``Monitor.admit_lane``).
* prefill — replicated (every shard computes the batch-1 prompt; no
  transfers).  Its counter delta is replicated too and is deliberately
  NOT psum-reduced — ``admit_lane`` folds it into the replicated
  aggregate exactly once per shard's copy.

Prompt-length bucketing: ``_prefill_bucketed`` takes right-padded tokens
plus a traced ``length`` (mask-correct per family — see
``models/*.SUPPORTS_PREFILL_LENGTH``), so admission + prefill compile once
per BUCKET instead of once per distinct prompt length.
"""
from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import telemetry as telemetry_lib
from repro.core.monitor import LaneMonitorState, Monitor
from repro.models.registry import Arch, write_lane


class DecodeDriver:
    """Compiles and owns the three jitted serve programs: the K-step
    megastep, the admission slab update, and the monitored prefill
    (exact-length + bucketed variants) — optionally shard_mapped over a
    ``lanes`` mesh axis."""

    def __init__(self, arch: Arch, mon: Monitor, *, cache_len: int,
                 temperature: float, steps_per_commit: int,
                 mesh=None, lane_axis: str = "lanes"):
        if steps_per_commit < 1:
            raise ValueError(
                f"steps_per_commit must be >= 1, got {steps_per_commit}")
        self.arch = arch
        self.cache_len = int(cache_len)
        self.temperature = float(temperature)
        self.steps_per_commit = int(steps_per_commit)
        self.mesh = mesh
        self.lane_axis = lane_axis
        if mesh is not None:
            # the driver's monitor copy psums counter aggregates over the
            # lane axis INSIDE shard_map (explicit axes, like shard_wrap —
            # no ambient sharding_ctx: the model's own logical-axis
            # constraints must not name manual axes)
            mon = copy.copy(mon)
            mon.counter_axes = tuple(mesh.axis_names)
        self.mon = mon

        sample = self.sample
        fingerprint = mon.spec.fingerprint
        k_steps = self.steps_per_commit
        sharded = mesh is not None
        LANE, REP = P(lane_axis), P()
        ring_spec = telemetry_lib.TokenRing(
            steps=REP, toks=P(None, lane_axis), live=P(None, lane_axis),
            head=REP,
        )

        def compile_program(core, in_specs, out_specs, donate=()):
            if not sharded:
                return jax.jit(core, donate_argnums=donate)
            return jax.jit(
                jax.shard_map(core, mesh=mesh, in_specs=in_specs,
                              out_specs=out_specs, check_vma=False),
                donate_argnums=donate,
            )

        def megastep_core(lane_calls, lane_values, lane_samples, lane_sched,
                          calls, values, samples, step, ring,
                          mparams, tparams, params,
                          slab, tok, keys, active, remaining, tok_ring):
            def lane_step(sched, cache, t, key):
                # collector opened INSIDE the vmap: trace-time call counts
                # are identical across lanes (same program), and the delta
                # comes back as an explicit lane-stacked output
                with mon.open(mparams, calls_base=sched) as col:
                    logits, cache2 = arch.decode_step(params, cache, t)
                delta = col.compact_delta()
                # serial contract, per lane: split, then sample with the sub
                key2, sub = jax.random.split(key)
                nxt = sample(logits, sub)
                return cache2, nxt, key2, delta

            def sbody(c, _):
                (slab, tok, keys, active, remaining,
                 lane_calls, lane_values, lane_samples, lane_sched,
                 calls, values, samples, step, ring, tok_ring) = c
                step2 = step + 1
                # egress first: the token each lane consumes THIS step (the
                # serial engine emits tok_i, then decodes it)
                tok_ring2 = telemetry_lib.token_ring_append(
                    tok_ring, tok[:, 0, 0], active, step2)
                slab2, nxt, keys2, delta = jax.vmap(
                    lane_step, in_axes=(0, 0, 0, 0)
                )(lane_sched, slab, tok, keys)
                ls = LaneMonitorState(
                    lane_calls=lane_calls, lane_values=lane_values,
                    lane_samples=lane_samples, lane_sched=lane_sched,
                    calls=calls, values=values, samples=samples,
                    step=step, ring=ring, params=mparams, tparams=tparams,
                    fingerprint=fingerprint,
                )
                ls2 = mon.commit_lanes(ls, delta, active)
                remaining2 = remaining - active
                active2 = ((active > 0) & (remaining2 > 0)).astype(jnp.int32)
                return (slab2, nxt, keys2, active2, remaining2,
                        ls2.lane_calls, ls2.lane_values, ls2.lane_samples,
                        ls2.lane_sched, ls2.calls, ls2.values, ls2.samples,
                        ls2.step, ls2.ring, tok_ring2), None

            init = (slab, tok, keys, active, remaining,
                    lane_calls, lane_values, lane_samples, lane_sched,
                    calls, values, samples, step, ring, tok_ring)
            out, _ = jax.lax.scan(sbody, init, None, length=k_steps)
            return out

        # arg positions: 0-8 monitor leaves, 9-11 read-only knobs/params,
        # 12-16 slab + per-lane decode state (donated — the engine holds
        # only the outputs), 17 token ring (never donated; host-drained)
        self._megastep = compile_program(
            megastep_core,
            in_specs=(LANE, LANE, LANE, LANE, REP, REP, REP, REP, REP,
                      REP, REP, REP, LANE, LANE, LANE, LANE, LANE,
                      ring_spec),
            out_specs=(LANE, LANE, LANE, LANE, LANE,
                       LANE, LANE, LANE, LANE, REP, REP, REP, REP, REP,
                       ring_spec),
            donate=(12, 13, 14, 15, 16),
        )

        def admit_core(slab, tok, keys, active, remaining,
                       lane_calls, lane_values, lane_samples, lane_sched,
                       calls, values, samples, step, ring, tparams,
                       lane, cache, tok0, key0, max_new, pdelta):
            if sharded:
                # global traced lane -> this shard's local block index;
                # non-owners run the same program as a masked no-op
                n_local = active.shape[0]
                li = lane - jax.lax.axis_index(lane_axis) * n_local
                own = (li >= 0) & (li < n_local)
                li = jnp.clip(li, 0, n_local - 1)
            else:
                li, own = lane, None

            def setm(arr, val):
                val = jnp.asarray(val).astype(arr.dtype)
                if own is None:
                    return arr.at[li].set(val)
                return arr.at[li].set(jnp.where(own, val, arr[li]))

            slab2 = write_lane(slab, li, cache, owned=own)
            ls = LaneMonitorState(
                lane_calls=lane_calls, lane_values=lane_values,
                lane_samples=lane_samples, lane_sched=lane_sched,
                calls=calls, values=values, samples=samples,
                step=step, ring=ring, params=None, tparams=tparams,
                fingerprint=fingerprint,
            )
            ls2 = mon.admit_lane(ls, li, pdelta, owned=own)
            return ((slab2,
                     setm(tok, tok0),
                     setm(keys, key0),
                     setm(active, 1),
                     setm(remaining, jnp.asarray(max_new, jnp.int32))),
                    (ls2.lane_calls, ls2.lane_values, ls2.lane_samples,
                     ls2.lane_sched, ls2.calls, ls2.values, ls2.samples,
                     ls2.step, ls2.ring))

        # lane/max_new are traced scalars: ONE compiled admission program
        # serves every lane and request length — no re-trace on admission
        self._admit = compile_program(
            admit_core,
            in_specs=(LANE, LANE, LANE, LANE, LANE,
                      LANE, LANE, LANE, LANE, REP, REP, REP, REP, REP, REP,
                      REP, REP, REP, REP, REP, REP),
            out_specs=((LANE, LANE, LANE, LANE, LANE),
                       (LANE, LANE, LANE, LANE, REP, REP, REP, REP, REP)),
            donate=(0, 1, 2, 3, 4),
        )

        def prefill_core(params, mparams, tokens, key):
            base = jnp.zeros((mon.spec.n_scopes,), jnp.int32)
            with mon.open(mparams, calls_base=base) as col:
                cache, logits = arch.prefill(
                    params, {"tokens": tokens}, cache_len=cache_len)
            # serial first-token contract: sample with the UNSPLIT request
            # key on the prefill logits (the lane splits per token after)
            tok0 = sample(logits, key)
            return cache, tok0, col.compact_delta()

        def prefill_bucketed_core(params, mparams, tokens, length, key):
            base = jnp.zeros((mon.spec.n_scopes,), jnp.int32)
            with mon.open(mparams, calls_base=base) as col:
                cache, logits = arch.prefill(
                    params, {"tokens": tokens}, cache_len=cache_len,
                    length=length)
            tok0 = sample(logits, key)
            return cache, tok0, col.compact_delta()

        # exact-length fallback: retraces per distinct prompt length (the
        # engine prefers the bucketed program whenever the family supports
        # a traced ``length``)
        self._prefill = compile_program(
            prefill_core,
            in_specs=(REP, REP, REP, REP), out_specs=(REP, REP, REP))
        # bucketed: one trace per PAD BUCKET — ``length`` is a traced
        # operand, so every prompt length in a bucket shares the program
        self._prefill_bucketed = compile_program(
            prefill_bucketed_core,
            in_specs=(REP, REP, REP, REP, REP), out_specs=(REP, REP, REP))

    # -- host-visible entry points ----------------------------------------
    def sample(self, logits, rng):
        """Identical semantics to the serial ``Engine._sample``."""
        logits = logits[:, -1, :].astype(jnp.float32)
        if self.temperature <= 0:
            return jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        logits = logits / self.temperature
        return jax.random.categorical(rng, logits)[:, None].astype(jnp.int32)

    def prefill(self, params, mparams, tokens, key):
        """Monitored batch-1 prefill + first-token sample:
        ``(cache, tok0, compact delta)`` — one dispatch, all async."""
        return self._prefill(params, mparams,
                             jnp.asarray(tokens, jnp.int32), key)

    def prefill_bucketed(self, params, mparams, tokens, length, key):
        """Bucketed prefill: ``tokens`` right-padded to its bucket width,
        ``length`` the real prompt length (traced — no re-trace per
        length).  Same returns as ``prefill``."""
        return self._prefill_bucketed(
            params, mparams, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(int(length), jnp.int32), key)

    def trace_counts(self) -> dict[str, int]:
        """Compile-cache sizes of the three programs (jit cache stats) —
        the bucketing win's attestation: ``prefill_traces`` is bounded by
        the bucket count, not by distinct prompt lengths."""

        def n(f):
            return int(f._cache_size())

        return {
            "prefill_traces": n(self._prefill) + n(self._prefill_bucketed),
            "admission_traces": n(self._admit),
            "megastep_traces": n(self._megastep),
        }

    def admit(self, lstate: LaneMonitorState, slab, tok, keys, active,
              remaining, lane, cache, tok0, key0, max_new, pdelta):
        """Write an admitted request into lane ``lane`` and seed its
        counter rows with the prefill delta — one async dispatch (donates
        the previous slab/lane-state buffers; rings are never donated)."""
        (state, leaves) = self._admit(
            slab, tok, keys, active, remaining,
            lstate.lane_calls, lstate.lane_values, lstate.lane_samples,
            lstate.lane_sched, lstate.calls, lstate.values, lstate.samples,
            lstate.step, lstate.ring, lstate.tparams,
            jnp.asarray(int(lane), jnp.int32), cache, tok0, key0,
            jnp.asarray(int(max_new), jnp.int32), pdelta,
        )
        (lane_calls, lane_values, lane_samples, lane_sched,
         calls, values, samples, step, ring) = leaves
        ls2 = LaneMonitorState(
            lane_calls=lane_calls, lane_values=lane_values,
            lane_samples=lane_samples, lane_sched=lane_sched,
            calls=calls, values=values, samples=samples, step=step,
            ring=ring, params=lstate.params, tparams=lstate.tparams,
            fingerprint=lstate.fingerprint,
        )
        return state, ls2

    def megastep(self, lstate: LaneMonitorState, params,
                 slab, tok, keys, active, remaining, tok_ring):
        """Dispatch one K-token megastep; returns the new lane decode state
        tuple, the new LaneMonitorState, and the new token ring."""
        (slab2, tok2, keys2, active2, remaining2,
         lane_calls, lane_values, lane_samples, lane_sched,
         calls, values, samples, step, ring, tok_ring2) = self._megastep(
            lstate.lane_calls, lstate.lane_values, lstate.lane_samples,
            lstate.lane_sched, lstate.calls, lstate.values, lstate.samples,
            lstate.step, lstate.ring, lstate.params, lstate.tparams, params,
            slab, tok, keys, active, remaining, tok_ring,
        )
        ls2 = LaneMonitorState(
            lane_calls=lane_calls, lane_values=lane_values,
            lane_samples=lane_samples, lane_sched=lane_sched,
            calls=calls, values=values, samples=samples, step=step,
            ring=ring, params=lstate.params, tparams=lstate.tparams,
            fingerprint=lstate.fingerprint,
        )
        return (slab2, tok2, keys2, active2, remaining2), ls2, tok_ring2
