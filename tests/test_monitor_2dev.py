"""Mesh-aware counter reduction under a REAL 2-device (forced host) world,
in a subprocess so the XLA device-count flag never leaks into the other
tests' 1-device environment.

The acceptance contract of the Monitor redesign:
  * a ``shard_wrap``-ped step on a ("data",)-mesh psums its counter delta
    in-graph — the carried MonitorState holds counters EXACTLY equal to the
    sum of two independent per-shard manual runs (cluster-wide sums, the
    paper's MPI support living in the transport);
  * the same wrapped function runs unchanged under plain jit on the same
    mesh (no bound axis -> the reduction melts away, jit-SPMD semantics are
    already global);
  * the wrapped train step from train/step.py behaves the same way.
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
from jax.sharding import PartitionSpec as P

from repro import core as scalpel
from repro.core.context import EventSpec, MonitorSpec, ScopeContext
from repro.dist.partition import sharding_ctx
from repro.launch.mesh import auto_mesh

assert len(jax.devices()) == 2

spec = MonitorSpec.of([
    ScopeContext.exhaustive("hot", [EventSpec("MEAN", "x"),
                                    EventSpec("NUMEL", "x"),
                                    EventSpec("ACT_MAX_ABS", "x")]),
])


def work(x):
    with scalpel.function("hot"):
        x = x * 1.5
        scalpel.probe(x=x)
    return x


x = jnp.arange(16.0)
mesh = auto_mesh((2,), ("data",))

# ---- shard_map: per-shard collection, in-graph psum --------------------
mon = scalpel.Monitor(spec)
with sharding_ctx(mesh):
    step = jax.jit(mon.shard_wrap(work, mesh, in_specs=P("data"),
                                  out_specs=P("data")))
    out, ms = step(mon.init(), x)

# ---- per-shard manual baseline summed on the host ----------------------
ref = scalpel.Monitor(spec, counter_axes=())
w1 = ref.wrap(work)
a = ref.init()
b = ref.init()
_, a = w1(a, x[:8])
_, b = w1(b, x[8:])
sum_calls = np.asarray(a.calls) + np.asarray(b.calls)
sum_values = np.asarray(a.values) + np.asarray(b.values)
sum_samples = np.asarray(a.samples) + np.asarray(b.samples)

psum_equal = bool(
    np.array_equal(np.asarray(ms.calls), sum_calls)
    and np.array_equal(np.asarray(ms.values), sum_values)
    and np.array_equal(np.asarray(ms.samples), sum_samples)
)

# ---- multiplexed scope: the schedule follows PER-SHARD calls -----------
# (feeding the psum-reduced totals back as the schedule base would advance
# the set index by 2 per call here and never sample set 1 again)
mspec = MonitorSpec.of([
    ScopeContext.multiplexed("mux", [
        [EventSpec("MEAN", "x")],
        [EventSpec("NUMEL", "x")],
    ]),
])


def mwork(x):
    with scalpel.function("mux"):
        scalpel.probe(x=x)
    return x


mmon = scalpel.Monitor(mspec)
with sharding_ctx(mesh):
    mstep = jax.jit(mmon.shard_wrap(mwork, mesh, in_specs=P("data"),
                                    out_specs=P("data")))
    mms = mmon.init()
    for _ in range(4):
        _, mms = mstep(mms, x)
# 4 calls alternate sets 0,1,0,1 on EVERY shard: each set sampled twice
# per shard -> psum-reduced samples [4, 4]; sched_calls stays per-shard.
mux_schedule_ok = bool(
    np.asarray(mms.samples).tolist() == [4, 4]
    and np.asarray(mms.calls).tolist() == [8]       # cluster-wide total
    and np.asarray(mms.sched_calls).tolist() == [4]  # per-shard base
)

# ---- megastep under shard_map: per-shard schedule advances K x ---------
# ONE K=4 megastep call must land exactly where the 4 unrolled calls above
# did: counters psum-exact, sched_calls still the PER-SHARD base (feeding
# the reduced totals through the scan carry would advance the set index 2x
# per inner step and skip set 1 on every shard).
mm2 = scalpel.Monitor(mspec, counter_axes=("data",))
mega = mm2.wrap(mwork, steps_per_commit=4)
smega = jax.jit(jax.shard_map(
    mega, mesh=mesh, in_specs=(P(), P("data")), out_specs=(P("data"), P()),
    check_vma=False,
))
_, mega_ms = smega(mm2.init(), x)
mega_mux_ok = bool(
    np.asarray(mega_ms.samples).tolist() == [4, 4]
    and np.asarray(mega_ms.calls).tolist() == [8]
    and np.asarray(mega_ms.sched_calls).tolist() == [4]
    and int(mega_ms.step) == 4
    and np.allclose(np.asarray(mega_ms.values), np.asarray(mms.values),
                    rtol=1e-6, atol=1e-8)
)

# ---- plain jit on the same mesh: reduction melts away ------------------
with sharding_ctx(mesh):
    jstep = jax.jit(mon.wrap(work))
    _, msj = jstep(mon.init(), x)
# jit-SPMD semantics are global: one call, MEAN over the full array
jit_ok = bool(
    int(msj.calls[0]) == 1
    and float(msj.values[1]) == 16.0     # NUMEL of the global tensor
)

# ---- the real train step under shard_map -------------------------------
from repro.configs import model_config
from repro.data import DataConfig, SyntheticLM
from repro.models.registry import Arch
from repro.optim import OptConfig
from repro.train.step import TrainState, build_monitor_spec, make_train_step

arch = Arch(model_config("xlstm_125m", smoke=True))
data = SyntheticLM(DataConfig(vocab=256, seq_len=16, global_batch=4))
batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}
tspec = build_monitor_spec(arch, batch)
opt = OptConfig(lr=1e-3, warmup_steps=0)

tmon = scalpel.Monitor(tspec, counter_axes=("data",))
tstep = make_train_step(arch, opt, tspec, monitor=tmon)
t0 = TrainState.create(arch, opt, jax.random.PRNGKey(0))

# NB: out_specs claims replication for tstate (per-shard grads are NOT
# psum-ed here — this exercise is about the counters, which ARE)
smap = jax.shard_map(
    tstep, mesh=mesh,
    in_specs=(P(), {"tokens": P("data"), "targets": P("data")}, P()),
    out_specs=(P(), P(), P()),
    check_vma=False,
)
# no ambient sharding_ctx here: inside shard_map the model's logical-axis
# constraints would name manual axes (counter_axes is explicit instead)
t1, o1, m1 = jax.jit(smap)(t0, batch, tmon.init())

# per-shard baseline: run each half-batch separately and sum counters
rmon = scalpel.Monitor(tspec, counter_axes=())
rstep = make_train_step(arch, opt, tspec, monitor=rmon)
half = {k: v[:2] for k, v in batch.items()}, {k: v[2:] for k, v in batch.items()}
ca = rstep(t0, half[0], rmon.init())[2]
cb = rstep(t0, half[1], rmon.init())[2]
train_calls_equal = bool(np.array_equal(
    np.asarray(m1.calls), np.asarray(ca.calls) + np.asarray(cb.calls)
))
train_values_close = bool(np.allclose(
    np.asarray(m1.values), np.asarray(ca.values) + np.asarray(cb.values),
    rtol=1e-4, atol=1e-5,
))

print(json.dumps({
    "psum_equal": psum_equal,
    "mux_schedule_ok": mux_schedule_ok,
    "mega_mux_ok": mega_mux_ok,
    "jit_ok": jit_ok,
    "train_calls_equal": train_calls_equal,
    "train_values_close": train_values_close,
    "psum_calls": np.asarray(ms.calls).tolist(),
    "shard_sum_calls": sum_calls.tolist(),
}))
"""


@pytest.mark.slow
def test_monitor_psum_2dev_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True,
        text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["psum_equal"], res
    assert res["mux_schedule_ok"], res
    assert res["mega_mux_ok"], res
    assert res["jit_ok"], res
    assert res["train_calls_equal"], res
    assert res["train_values_close"], res
