"""SPMD semantics under a real (host-device) mesh, run in a subprocess so
the 8-device XLA flag never leaks into the other tests' 1-device world.

Checks:
  * sharded train step runs under a (2,4) ("data","model") mesh,
  * counters are replicated and call counts match the unsharded run,
  * loss matches the single-device run (SPMD correctness),
  * elastic re-mesh: a checkpoint saved under (2,4) restores under (4,2),
  * under the sharded jit the probe layer takes XLA's reduction (a Mosaic
    kernel cannot be partitioned) and its counters equal the unsharded
    run; inside a shard_map the kernel policy is left to choose.
"""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
# eight simulated host devices: pinned to the CPU so this child never
# reaches for an accelerator its parent may hold
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro import core as scalpel
from repro.configs import model_config
from repro.data import DataConfig, SyntheticLM
from repro.dist.partition import sharding_ctx, tree_shardings
from repro.launch.mesh import auto_mesh
from repro.models.registry import Arch
from repro.optim import OptConfig, init_opt_state, opt_state_axes
from repro.train.step import TrainState, build_monitor_spec, make_train_step
from repro.checkpoint.manager import save_tree, restore_tree

assert len(jax.devices()) == 8

cfg = model_config("qwen3_14b", smoke=True).replace(remat="none")
arch = Arch(cfg)
data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8))
host_batch = data.batch_at(0)
batch = {k: jnp.asarray(v) for k, v in host_batch.items()}
spec = build_monitor_spec(arch, batch)
opt_cfg = OptConfig(lr=1e-3, warmup_steps=0, min_lr_frac=1.0)

# ---- single-device reference ----
mon1 = scalpel.Monitor(spec)
t0 = TrainState.create(arch, opt_cfg, jax.random.PRNGKey(0))
step1 = jax.jit(make_train_step(arch, opt_cfg, spec, monitor=mon1))
t1, o1, m1 = step1(t0, batch, mon1.init())
ref_loss = float(o1["loss"])
ref_calls = np.asarray(m1.calls).copy()

# ---- sharded run under (2,4) ----
# jit-SPMD: reductions over sharded tensors are already global, so the
# Monitor's "auto" counter reduction resolves to a no-op (no bound axes)
# and counters stay replicated — asserted equal to the unsharded run.
mesh = auto_mesh((2, 4), ("data", "model"))
with mesh, sharding_ctx(mesh):
    params = arch.init(jax.random.PRNGKey(0))
    params = jax.device_put(
        params, tree_shardings(
            jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                         params),
            arch.param_axes(), mesh))
    tstate = TrainState(
        params=params,
        opt=init_opt_state(opt_cfg, params),
        step=jnp.zeros((), jnp.int32),
    )
    sb = {k: jax.device_put(
        v, NamedSharding(mesh, PartitionSpec("data"))) for k, v in
        batch.items()}
    monN = scalpel.Monitor(spec)
    stepN = jax.jit(make_train_step(arch, opt_cfg, spec, monitor=monN))
    t2, o2, m2 = stepN(tstate, sb, monN.init())
    spmd_loss = float(o2["loss"])
    spmd_calls = np.asarray(m2.calls).copy()

    # ---- elastic re-mesh: save under (2,4), restore under (4,2) ----
    save_tree("/tmp/spmd_ck.npz", t2.params)
mesh2 = auto_mesh((4, 2), ("data", "model"))
with mesh2, sharding_ctx(mesh2):
    like = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t2.params)
    back = restore_tree("/tmp/spmd_ck.npz", like, mesh=mesh2,
                        axes=arch.param_axes())
    ok_elastic = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(t2.params), jax.tree.leaves(back))
    )

# ---- the probe layer's choice under a partitioned trace ----
# a Mosaic kernel cannot be partitioned: under a sharded jit the probe
# layer asks for XLA's reduction (use_pallas=False), inside a shard_map
# binding every mesh axis it leaves the choice to the kernel policy (None)
from jax.sharding import PartitionSpec as P
from repro.core.context import EventSpec, MonitorSpec, ScopeContext
from repro.kernels import ops as kops
real_moments, seen = kops.tensor_moments, []

def spy(x, names, *, use_pallas=None):
    seen.append(use_pallas)
    return real_moments(x, names, use_pallas=use_pallas)

kops.tensor_moments = spy
pspec = MonitorSpec.of([ScopeContext.exhaustive(
    "p", [EventSpec("ACT_RMS", "x"), EventSpec("ACT_MAX_ABS", "x")])])

def body(x):
    with scalpel.function("p"):
        scalpel.probe(x=x)
    return x

xk = jax.random.normal(jax.random.PRNGKey(3), (8, 40, 130))
pmon = scalpel.Monitor(pspec)
_, m_ref = jax.jit(pmon.wrap(body))(pmon.init(), xk)
choice_plain = seen[-1]
with sharding_ctx(mesh):
    xs = jax.device_put(xk, NamedSharding(mesh, P("data")))
    _, m_spmd = jax.jit(pmon.wrap(body))(pmon.init(), xs)
    choice_spmd = seen[-1]
    smon = scalpel.Monitor(pspec)
    jax.jit(smon.shard_wrap(body, mesh, in_specs=P("data"),
                            out_specs=P("data")))(smon.init(), xs)
    choice_shard = seen[-1]
kernel_ok = (choice_plain is None and choice_spmd is False
             and choice_shard is None
             and np.allclose(np.asarray(m_ref.values),
                             np.asarray(m_spmd.values), rtol=1e-5))

print(json.dumps({
    "kernel_sharded_ok": bool(kernel_ok),
    "ref_loss": ref_loss,
    "spmd_loss": spmd_loss,
    "calls_match": bool((ref_calls == spmd_calls).all()),
    "elastic_ok": bool(ok_elastic),
}))
"""


@pytest.mark.slow
def test_spmd_8dev_subprocess(tmp_path):
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
    assert res["calls_match"], res
    assert res["kernel_sharded_ok"], res
    assert res["elastic_ok"], res
    assert abs(res["ref_loss"] - res["spmd_loss"]) < 5e-2, res
