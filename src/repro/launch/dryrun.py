"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST set XLA_FLAGS before any jax import (jax locks the device count on
first init) — hence the first two lines below.

For each cell this:
  1. builds the exact assigned ModelConfig + the cell's execution policy,
  2. constructs abstract inputs (ShapeDtypeStruct — no allocation) and
     NamedShardings from the logical-axes trees,
  3. jit(step).lower(...).compile() under the production mesh,
  4. records memory_analysis / cost_analysis / parsed collective traffic
     into a JSON record (the roofline source; EXPERIMENTS.md §Dry-run).

Usage:
  python -m repro.launch.dryrun --arch all --shape all --mesh both \
      --out experiments/dryrun
"""
import os

# a 512-device pod simulated on the host CPU: pinned there, so the script
# never starts on (or waits for) an attached accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec  # noqa: E402

from repro import configs  # noqa: E402
from repro import core as scalpel  # noqa: E402
from repro.core.backends import hlo_graph, xla_cost  # noqa: E402
from repro.dist.partition import (  # noqa: E402
    sharding_ctx,
    tree_shardings,
)
from repro.launch.mesh import describe, make_production_mesh  # noqa: E402
from repro.models import SHAPES, Arch  # noqa: E402
from repro.optim import OptConfig, init_opt_state, opt_state_axes  # noqa: E402
from repro.train.step import TrainState, build_monitor_spec, make_train_step  # noqa: E402


def _replicated(tree, mesh):
    return jax.tree.map(
        lambda _: NamedSharding(mesh, PartitionSpec()), tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )


def _abstractify(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype), tree
    )


def _opt_cfg(policy: dict) -> OptConfig:
    return OptConfig(
        state=policy.get("opt_state", "f32"),
        momentum=policy.get("opt_momentum", True),
        master=policy.get("opt_master", True),
    )


def build_cell(arch_id: str, shape_name: str, multi_pod: bool,
               monitor: str = "all", policy_overrides: dict | None = None):
    """Returns (fn, abstract_args, in_shardings, donate, meta)."""
    shape = SHAPES[shape_name]
    policy = configs.cell_policy(arch_id, shape_name)
    policy.update(policy_overrides or {})
    overrides = dict(policy.get("model_overrides", {}))
    cfg = configs.model_config(arch_id, **overrides)
    arch = Arch(cfg)

    ok, why = arch.supports(shape)
    if not ok:
        raise SkipCell(why)

    mesh = make_production_mesh(multi_pod=multi_pod)
    batch = arch.input_specs(shape)
    tensor_events = () if monitor == "none" else ("ACT_RMS",)

    with mesh, sharding_ctx(mesh):
        params_abs = arch.abstract_params()
        params_sh = tree_shardings(params_abs, arch.param_axes(), mesh)
        batch_sh = {
            k: tree_shardings(
                {"x": v}, {"x": ("batch",) + (None,) * (v.ndim - 1)}, mesh
            )["x"]
            for k, v in batch.items()
        }

        if shape.kind == "train":
            spec = build_monitor_spec(arch, batch,
                                      tensor_events=tensor_events)
            opt_cfg = _opt_cfg(policy)
            opt_abs = jax.eval_shape(
                lambda p: init_opt_state(opt_cfg, p), params_abs
            )
            opt_sh = tree_shardings(
                opt_abs, opt_state_axes(opt_cfg, arch.param_axes()), mesh
            )
            tstate_abs = TrainState(
                params=params_abs, opt=opt_abs,
                step=jax.ShapeDtypeStruct((), jnp.int32),
            )
            tstate_sh = TrainState(
                params=params_sh, opt=opt_sh,
                step=NamedSharding(mesh, PartitionSpec()),
            )
            mon = scalpel.Monitor(spec)
            mstate_abs = _abstractify(mon.init())
            step_fn = make_train_step(
                arch, opt_cfg, spec,
                microbatches=policy.get("microbatches", 1),
                monitor=mon,
            )
            args = (tstate_abs, batch, mstate_abs)
            shardings = (tstate_sh, batch_sh, _replicated(mstate_abs, mesh))
            donate = (0,)
            fn = step_fn
        elif shape.kind == "prefill":
            def probe_fn(p, b):
                return arch.prefill(p, b, cache_len=shape.seq_len)

            seen = scalpel.discover(probe_fn, params_abs, batch)
            spec = scalpel.spec_from_discovery(seen,
                                               tensor_events=tensor_events)
            mon = scalpel.Monitor(spec)
            mstate_abs = _abstractify(mon.init())
            fn = mon.wrap(
                lambda params, b: arch.prefill(params, b,
                                               cache_len=shape.seq_len)
            )
            args = (mstate_abs, params_abs, batch)
            shardings = (_replicated(mstate_abs, mesh), params_sh, batch_sh)
            donate = ()
        else:  # decode
            cache_abs = arch.init_cache(shape.global_batch, shape.seq_len,
                                        abstract=True)
            cache_sh = tree_shardings(cache_abs, arch.cache_axes(), mesh)
            tokens = batch["tokens"]

            def probe_fn(p, c, t):
                return arch.decode_step(p, c, t)

            seen = scalpel.discover(probe_fn, params_abs, cache_abs, tokens)
            spec = scalpel.spec_from_discovery(seen,
                                               tensor_events=tensor_events)
            mon = scalpel.Monitor(spec)
            mstate_abs = _abstractify(mon.init())
            fn = mon.wrap(lambda params, cache, t:
                          arch.decode_step(params, cache, t))
            args = (mstate_abs, params_abs, cache_abs, tokens)
            shardings = (_replicated(mstate_abs, mesh), params_sh, cache_sh,
                         batch_sh["tokens"])
            donate = (2,)

    meta = {
        "arch": arch_id, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(mesh.devices.size),
        "kind": shape.kind,
        "n_params": arch.n_params(),
        "policy": {k: v for k, v in policy.items() if k != "model_overrides"},
        "model_overrides": overrides,
        "monitor": monitor,
        "scopes": list(spec.scopes),
    }
    return fn, args, shardings, donate, mesh, meta


class SkipCell(Exception):
    pass


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             monitor: str = "all", policy_overrides: dict | None = None,
             keep_hlo: bool = False) -> dict:
    t0 = time.time()
    fn, args, shardings, donate, mesh, meta = build_cell(
        arch_id, shape_name, multi_pod, monitor, policy_overrides
    )
    with mesh, sharding_ctx(mesh):
        jitted = jax.jit(fn, in_shardings=shardings, donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    hlo_text = compiled.as_text()
    report = xla_cost.analyze(
        compiled, default_group=meta["n_devices"],
        scopes=tuple(meta["scopes"]), hlo_text=hlo_text,
    )
    # while-loop-aware graph costing (cost_analysis counts loop bodies once;
    # scan-over-layers would underreport by ~n_layers without this)
    graph = hlo_graph.analyze_text(hlo_text, default_group=meta["n_devices"])
    mem = report.memory_analysis or {}
    record = dict(
        meta,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        flops=report.flops,
        bytes_accessed=report.bytes_accessed,
        transcendentals=report.transcendentals,
        collective_link_bytes=report.collective_link_bytes,
        collective_payload_bytes=report.collective_payload_bytes,
        collectives_by_kind=report.collective_bytes_by_kind(),
        n_collectives=len(report.collectives),
        memory=mem,
        hlo_graph=graph,
    )
    if keep_hlo:
        record["hlo_collective_lines"] = [
            f"{c.kind} g{c.group_size} {c.link_bytes:.3e}B {c.scope}"
            for c in report.collectives[:2000]
        ]
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--monitor", default="all", choices=["all", "none"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--keep-hlo", action="store_true")
    args = ap.parse_args()

    archs = configs.ARCH_IDS if args.arch == "all" else [
        configs.canonical(a) for a in args.arch.split(",")
    ]
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    n_ok = n_skip = n_fail = 0
    for arch_id in archs:
        for shape_name in shapes:
            for multi in meshes:
                tag = f"{arch_id}__{shape_name}__{'multi' if multi else 'single'}"
                if args.monitor != "all":
                    tag += f"__mon-{args.monitor}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"[skip-cached] {tag}")
                    continue
                print(f"[lower] {tag} ...", flush=True)
                try:
                    rec = run_cell(arch_id, shape_name, multi,
                                   monitor=args.monitor,
                                   keep_hlo=args.keep_hlo)
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                    m = rec["memory"]
                    print(
                        f"[ok] {tag}: compile {rec['compile_s']}s "
                        f"flops {rec['flops']:.3e} "
                        f"coll {rec['collective_link_bytes']:.3e}B "
                        f"temp {m.get('temp_size_in_bytes', 0)/2**30:.2f}GiB",
                        flush=True,
                    )
                    n_ok += 1
                except SkipCell as e:
                    with open(path, "w") as f:
                        json.dump({"arch": arch_id, "shape": shape_name,
                                   "mesh": "2x16x16" if multi else "16x16",
                                   "skipped": str(e)}, f, indent=1)
                    print(f"[skip] {tag}: {e}")
                    n_skip += 1
                except Exception:
                    n_fail += 1
                    print(f"[FAIL] {tag}:\n{traceback.format_exc()}",
                          flush=True)
    print(f"dry-run done: {n_ok} ok, {n_skip} skipped-by-design, "
          f"{n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
