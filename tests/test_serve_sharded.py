"""Mesh-sharded continuous batching (serve/driver.py over a ``lanes``
mesh), in a subprocess with 2 forced host devices so the XLA device-count
flag never leaks into the other tests' 1-device environment.

The acceptance contract of the lane-sharding redesign:

* EXACTNESS — a 2-shard engine (``ServeConfig.lane_shards=2``) produces
  greedy tokens BITWISE equal to the single-device engine, and per-request
  lane-counter attribution allclose to fresh serial-engine runs, for
  requests landing on lanes of BOTH shards (including lane reuse);

* PER-SHARD SCHEDULE — ``lane_sched`` stays per-shard under shard_map
  with K=4 megasteps and a multiplexed scope: it tracks ``lane_calls``
  exactly (both seed and advance together; a psum would double one of
  them), and the sharded aggregate counters — including the mux samples
  split — exactly equal the unsharded run's;

* ZERO HOST SYNCS — the sharded decode loop still never calls
  ``jax.block_until_ready``: megasteps, admissions, psum-reduced counter
  publishes and token-ring publishes are all async, with the single
  blocking readback at the final completion drain.
"""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import jax
import jax.numpy as jnp
import numpy as np

from repro import core as scalpel
from repro.configs import model_config
from repro.core.context import EventSpec, MonitorSpec, ScopeContext
from repro.models.registry import Arch
from repro.serve.engine import ContinuousEngine, Engine, ServeConfig

assert len(jax.devices()) == 2

arch = Arch(model_config("xlstm_125m", smoke=True))
params = arch.init(jax.random.PRNGKey(0))
V = arch.cfg.vocab


def prompt(seed, s=8):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                         (1, s), 0, V))


def serial(p, max_new, seed=None):
    eng = Engine(arch, params, ServeConfig(cache_len=64,
                                           max_new_tokens=max_new))
    out, _ = eng.generate({"tokens": p}, seed=seed)
    return np.asarray(out)[0], eng.counters


def run_engine(shards, spec=None, k=4):
    cfg = ServeConfig(cache_len=64, max_new_tokens=6, n_lanes=4,
                      steps_per_commit=k, lane_shards=shards)
    eng = ContinuousEngine(arch, params, cfg, spec=spec)
    rids = [eng.submit(prompt(100 + i), max_new=6) for i in range(6)]
    return eng, rids, eng.run()

# ---- sharded == single-device, bitwise tokens + allclose counters ------
e1, rids1, res1 = run_engine(1)
e2, rids2, res2 = run_engine(2)

tokens_equal = all(
    np.array_equal(res1[a].tokens, res2[b].tokens)
    for a, b in zip(rids1, rids2)
)
# 6 requests over 4 lanes across 2 shards: both shards served requests,
# and at least one lane was reused (re-admission on a sharded slab)
lanes2 = [res2[r].lane for r in rids2]
both_shards_used = any(ln < 2 for ln in lanes2) and \
    any(ln >= 2 for ln in lanes2)
lane_reused = len(lanes2) > len(set(lanes2))

ctr_close = True
for a, b in zip(rids1, rids2):
    for x, y in zip(jax.tree.leaves(res1[a].counters),
                    jax.tree.leaves(res2[b].counters)):
        ctr_close &= bool(np.allclose(np.asarray(x), np.asarray(y),
                                      rtol=1e-5, atol=1e-6))

# ---- per-request attribution vs fresh SERIAL runs, both shards ---------
serial_close = True
for i, rid in enumerate(rids2):
    want_toks, want_ctr = serial(prompt(100 + i), max_new=6)
    serial_close &= bool(np.array_equal(res2[rid].tokens, want_toks))
    got = res2[rid].counters
    serial_close &= bool(np.array_equal(np.asarray(got.calls),
                                        np.asarray(want_ctr.calls)))
    serial_close &= bool(np.array_equal(np.asarray(got.samples),
                                        np.asarray(want_ctr.samples)))
    serial_close &= bool(np.allclose(np.asarray(got.values),
                                     np.asarray(want_ctr.values),
                                     rtol=1e-5, atol=1e-6))

agg_close = all(
    np.allclose(np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6)
    for x, y in zip(jax.tree.leaves(e1.counters),
                    jax.tree.leaves(e2.counters))
)

# ---- multiplexed scope under K=4 sharded megasteps ---------------------
# Rebuild the serve spec with its widest scope MULTIPLEXED into two event
# sets.  The schedule base is per-lane AND per-shard (lane_sched); if the
# megastep fed psum-reduced totals back as the base, the sharded run's
# set rotation — hence its sampled counters — would diverge from the
# unsharded run's.


def probe_fn(p, toks):
    cache, logits = arch.prefill(p, {"tokens": toks}, cache_len=64)
    return arch.decode_step(p, cache, toks[:, :1])


seen = scalpel.discover(probe_fn, arch.abstract_params(),
                        jax.ShapeDtypeStruct((1, 8), jnp.int32))
ctxs = []
for scope, tnames in sorted(seen.items()):
    slots = [EventSpec(event=ev, tensor=t) for t in tnames
             for ev in ("ACT_RMS", "ACT_MEAN_ABS")]
    if scope == max(seen, key=lambda s: len(seen[s])):
        half = max(1, len(slots) // 2)
        ctxs.append(ScopeContext.multiplexed(scope,
                                             [slots[:half], slots[half:]]))
    else:
        ctxs.append(ScopeContext.exhaustive(scope, slots))
mux_spec = MonitorSpec.of(ctxs)

m1, _, _ = run_engine(1, spec=mux_spec, k=4)
m2, _, _ = run_engine(2, spec=mux_spec, k=4)
mux_agg_equal = bool(
    np.array_equal(np.asarray(m1.counters.calls),
                   np.asarray(m2.counters.calls))
    and np.array_equal(np.asarray(m1.counters.samples),
                       np.asarray(m2.counters.samples))
    and np.allclose(np.asarray(m1.counters.values),
                    np.asarray(m2.counters.values), rtol=1e-5, atol=1e-6)
)
# both event sets actually sampled (the mux rotated), on both engines
mux_rotated = bool((np.asarray(m1.counters.samples) > 0).all()
                   and (np.asarray(m2.counters.samples) > 0).all())
# the per-shard schedule invariant: lane_sched tracks lane_calls exactly
# (seeded and advanced together; any stray reduction breaks one of them)
sched_per_shard = bool(
    np.array_equal(np.asarray(m2.lstate.lane_sched),
                   np.asarray(m2.lstate.lane_calls))
)

# ---- zero-host-sync attestation on the sharded engine ------------------
blocks = []
real_block = jax.block_until_ready
jax.block_until_ready = lambda x: (blocks.append(1), real_block(x))[1]
try:
    e3, rids3, res3 = run_engine(2)
finally:
    jax.block_until_ready = real_block
no_syncs = not blocks
sharded_complete = (len(res3) == 6
                    and all(len(res3[r].tokens) == 6 for r in rids3)
                    and e3.runtime.telemetry.dropped_tokens == 0)

print(json.dumps({
    "tokens_equal": tokens_equal,
    "both_shards_used": both_shards_used,
    "lane_reused": lane_reused,
    "ctr_close": ctr_close,
    "serial_close": serial_close,
    "agg_close": agg_close,
    "mux_agg_equal": mux_agg_equal,
    "mux_rotated": mux_rotated,
    "sched_per_shard": sched_per_shard,
    "no_syncs": no_syncs,
    "sharded_complete": sharded_complete,
    "lanes2": lanes2,
    "compile_stats": {k: v for k, v in e2.compile_stats().items()},
}))
"""


@pytest.mark.slow
def test_serve_sharded_2dev_subprocess():
    env = dict(os.environ)
    # two simulated host devices: pinned to the CPU, off any accelerator
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True,
        text=True, timeout=1200,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["tokens_equal"], res
    assert res["both_shards_used"], res
    assert res["lane_reused"], res
    assert res["ctr_close"], res
    assert res["serial_close"], res
    assert res["agg_close"], res
    assert res["mux_agg_equal"], res
    assert res["mux_rotated"], res
    assert res["sched_per_shard"], res
    assert res["no_syncs"], res
    assert res["sharded_complete"], res
    # the sharded engine compiled each program exactly once (one prompt
    # bucket; no per-length or per-lane re-traces)
    cs = res["compile_stats"]
    assert cs["prefill_traces"] == 1, cs
    assert cs["admission_traces"] == 1, cs
    assert cs["megastep_traces"] == 1, cs
