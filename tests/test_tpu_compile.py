"""The probe kernel compiles for a TPU v5e, where the train and serve paths
call it: bf16 and f32 activations at real widths, per-lane logits under
``jax.vmap``, the entropy variant, and a monitored probe on a data mesh.
The dense and the hybrid serve megasteps compile for one v5e with their
lane slabs in place.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology.  The topology is described inside a fixture (never at import), so
every test worker collects the same tests and only the one running this
file loads the TPU library.
"""
import math
import re
from typing import NamedTuple

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro import core as scalpel
from repro.core.context import MonitorSpec
from repro.dist.partition import sharding_ctx
from repro.kernels import ops
from repro.launch.mesh import auto_mesh
from repro.models import hybrid
from repro.models.registry import Arch
from repro.models.spec import HybridConfig, ModelConfig, SSMConfig
from repro.serve.driver import DecodeDriver


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("shape,dtype,vmapped,with_entropy", [
    ((8, 2048, 768), jnp.bfloat16, False, False),     # xlstm_125m residual
    ((8, 2048, 768), jnp.float32, False, False),
    ((4, 512, 5120), jnp.bfloat16, False, False),     # qwen3_14b prefill
    ((1, 1024, 8, 128), jnp.bfloat16, False, False),  # per-head k/v slabs
    ((3, 1000, 777), jnp.bfloat16, False, False),     # ragged row blocks
    ((1021, 8, 128), jnp.bfloat16, False, False),     # ragged lead blocks
    ((4, 1, 1, 151936), jnp.bfloat16, True, False),   # per-lane decode logits
    ((8, 4, 2048, 2048), jnp.float32, False, True),   # attention probs
], ids=["bf16-768", "f32-768", "bf16-5120", "bf16-kv-slabs", "bf16-ragged-rows",
        "bf16-ragged-lead", "vmap-logits", "entropy"])
def test_probe_kernel_compiles_for_v5e(one_chip, shape, dtype, vmapped,
                                       with_entropy):
    def fn(x):
        return ops.probe_moments(x, with_entropy=with_entropy)

    if vmapped:
        fn = jax.vmap(fn)
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(fn, x)


def test_probe_kernel_runs_per_shard_under_a_sharded_jit(topo, monkeypatch):
    """A Mosaic kernel cannot be partitioned by the compiler.  A monitored
    probe under a sharded jit on a data mesh compiles to XLA's reduction,
    which the partitioner splits and all-reduces; inside a ``shard_map``
    (the lane-sharded serve path) each shard runs the kernel."""
    from repro import core as scalpel
    from repro.core.context import EventSpec, MonitorSpec, ScopeContext

    # the probe policy as it runs on the chip, where the kernel is the
    # default for a tensor this large
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    mesh = auto_mesh((4, 1), ("data", "model"), devices=topo.devices[:4])
    x = jax.ShapeDtypeStruct(
        (8, 2048, 768), jnp.bfloat16,
        sharding=NamedSharding(mesh, PartitionSpec("data")))
    spec = MonitorSpec.of([ScopeContext.exhaustive(
        "p", [EventSpec("ACT_RMS", "x"), EventSpec("ACT_MAX_ABS", "x")])])

    def body(x):
        with scalpel.function("p"):
            scalpel.probe(x=x)
        return x

    with sharding_ctx(mesh):
        mon = scalpel.Monitor(spec)
        state = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), mon.init())
        spmd = _compiled_text(mon.wrap(body), state, x)
        per_shard = _compiled_text(
            mon.shard_wrap(body, mesh, in_specs=PartitionSpec("data"),
                           out_specs=PartitionSpec("data")), state, x)
    assert "all-reduce" in spmd and "tpu_custom_call" not in spmd
    assert "tpu_custom_call" in per_shard


def _computations(hlo: str) -> dict[str, list[str]]:
    """Each computation's instruction lines, by name."""
    comps, comp = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if m:
            comp = comps.setdefault(m.group(1), [])
        elif comp is not None:
            comp.append(line)
    return comps


def _in_loops(hlo: str) -> set[str]:
    """The computations that run inside a while loop: its bodies and all
    they call."""
    comps = _computations(hlo)
    todo = re.findall(r"body=%([\w.\-]+)", hlo)
    seen = set()
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo += re.findall(r"%([\w.\-]+)", " ".join(
                re.findall(r"(?:calls|body|condition|to_apply|"
                           r"branch_computations)=(\{[^}]*\}|%[\w.\-]+)",
                           "\n".join(comps.get(c, ())))))
    return seen


class Buffer(NamedTuple):
    name: str
    op: str            # the instruction's op, or a fusion's root op
    n: int             # element count
    comp: str          # the computation that holds it
    on_chip: bool      # held in on-chip memory (a memory space ``S(n)``)


def _materialized(hlo: str):
    """Every instruction that owns an array buffer: those outside the
    computations that fusions call."""
    fused = set(re.findall(r"calls=%([\w.\-]+)", hlo))
    comps = _computations(hlo)
    roots = {}
    for comp in fused & comps.keys():
        for line in comps[comp]:
            m = re.match(r"\s*ROOT %[\w.\-]+ = \S+ ([\w\-]+)\(", line)
            if m:
                roots[comp] = m.group(1)
    for comp, lines in comps.items():
        if comp in fused:
            continue
        for line in lines:
            m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]"
                         r"(\S*) ([\w\-]+)\(", line)
            if not m:
                continue
            called = re.search(r"calls=%([\w.\-]+)", line)
            op = roots.get(called.group(1)) if called else None
            yield Buffer(m.group(1), op or m.group(4),
                         math.prod(int(d) for d in m.group(2).split(",") if d),
                         comp, bool(re.search(r"S\(\d+\)", m.group(3))))


def _megastep_hlo(one_chip, cfg: ModelConfig, lanes: int, cache_len: int,
                  k_steps: int) -> str:
    """The serve driver's megastep for ``cfg``, compiled for one v5e with
    its lane slab donated."""
    arch = Arch(cfg)
    spec = MonitorSpec.of([])
    mon = scalpel.Monitor(spec,
                          telemetry=scalpel.ScalpelRuntime(spec).telemetry)
    driver = DecodeDriver(arch, mon, cache_len=cache_len, temperature=0.0,
                          steps_per_commit=k_steps)
    ls = jax.eval_shape(lambda: mon.lane_init(lanes))
    ring = jax.eval_shape(
        lambda: mon.telemetry.make_token_ring(lanes, 2 * k_steps))
    lane_i32 = jax.ShapeDtypeStruct((lanes,), jnp.int32)
    args = (ls.lane_calls, ls.lane_values, ls.lane_samples, ls.lane_sched,
            ls.calls, ls.values, ls.samples, ls.step, ls.ring, ls.params,
            ls.tparams, jax.eval_shape(arch.init, jax.random.PRNGKey(0)),
            arch.init_lane_cache(lanes, cache_len, abstract=True),
            jax.ShapeDtypeStruct((lanes, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((lanes, 2), jnp.uint32), lane_i32, lane_i32,
            ring)
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    return driver._megastep.lower(*args).compile().as_text()


def test_dense_megastep_keeps_the_slab_in_place_for_v5e(one_chip):
    """The serve megastep of a dense GQA model (kv 8, head_dim 128, 2
    layers, 4 lanes, cache 1024, K = 2, slab donated) moves no slab on the
    chip: no layout copy of the slab or of one layer's slab, no second
    slab allocated, no layer sliced out into a buffer of its own.  What
    owns a buffer of a layer's size or more is the donated slab and its
    in-place row writes."""
    cfg = ModelConfig(name="slab", family="dense", n_layers=2, d_model=512,
                      n_heads=16, n_kv_heads=8, d_ff=1024, vocab=1024,
                      head_dim=128, qk_norm=True, param_dtype="bfloat16",
                      compute_dtype="bfloat16")
    lanes, cache_len = 4, 1024
    hlo = _megastep_hlo(one_chip, cfg, lanes, cache_len, k_steps=2)

    layer = lanes * cfg.n_kv_heads * cache_len * cfg.resolved_head_dim
    big = [(b.name, b.op) for b in _materialized(hlo) if b.n >= layer]
    assert any(op == "dynamic-update-slice" for _, op in big)
    moved = [b for b in big if b[1] not in
             ("parameter", "get-tuple-element", "bitcast",
              "dynamic-update-slice")]
    assert not moved, moved
    copies = [line.strip()[:120] for line in hlo.splitlines()
              if re.search(r"= \w+\[[\d,]*\]\S* copy(-start)?\(", line)
              and math.prod(int(d) for d in re.search(
                  r"\[([\d,]*)\]", line).group(1).split(",") if d) >= layer]
    assert not copies, copies


def _relayouts(hlo: str, elems: int):
    """(computation, name, dtype) of every ``copy`` of ``elems`` elements
    or more, and of every ``copy-start`` of that size that changes the
    layout.  One that keeps it moves a buffer between memory spaces (the
    compiler's prefetches and evictions), which a small model's slab fits
    in."""
    for comp, lines in _computations(hlo).items():
        for line in lines:
            m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = \(?(\w+)\[([\d,]*)\]"
                         r".*? (copy|copy-start)\(", line)
            if not m or math.prod(
                    int(d) for d in m.group(3).split(",") if d) < elems:
                continue
            if m.group(4) == "copy-start":
                dst, src = (re.sub(r"S\(\d+\)", "", lay) for lay in
                            re.findall(r"\w+\[[\d,]*\]\{([^}]*)\}", line)[:2])
                if dst == src:
                    continue
            yield comp, m.group(1), m.group(2)


def test_hybrid_megastep_keeps_the_slab_in_place_for_v5e(one_chip):
    """The serve megastep of a Zamba2-shaped hybrid (d 512, 4 heads of 224,
    Mamba2 heads of 64 with state 64 in 2 groups, 6 layers with sites 2
    and 4, 4 lanes, cache 1024, K = 2, slab donated) updates its lane slab
    where it lies: no layout copy of a site's K or V anywhere (their
    buffers are the donated slab and its in-place column writes), and in
    the K-step loop no copy or materialised broadcast of one layer's SSD
    state for all lanes: each run's state stack rides the layer loop's
    carry.  Outside the loop a run's state may be re-laid out once in and
    once out."""
    cfg = ModelConfig(name="hyb", family="hybrid", n_layers=6, d_model=512,
                      n_heads=4, n_kv_heads=4, head_dim=224, d_ff=1024,
                      vocab=1024,
                      ssm=SSMConfig(d_state=64, d_conv=4, expand=2,
                                    head_dim=64, n_groups=2),
                      hybrid=HybridConfig(hybrid_layer_ids=(2, 4),
                                          num_mem_blocks=2),
                      param_dtype="bfloat16", compute_dtype="bfloat16")
    lanes, cache_len = 4, 1024
    hlo = _megastep_hlo(one_chip, cfg, lanes, cache_len, k_steps=2)

    site = lanes * cfg.n_kv_heads * cfg.resolved_head_dim * cache_len
    kv_copies = list(_relayouts(hlo, site))
    assert not kv_copies, kv_copies
    # in device memory (on-chip buffers are the compiler's prefetches)
    owners = {b.op for b in _materialized(hlo) if b.n == site
              and not b.on_chip} - {"get-tuple-element", "bitcast",
                                     "copy-done"}
    assert owners == {"parameter", "dynamic-update-slice"}, owners

    n_heads = cfg.ssm.expand * cfg.d_model // cfg.ssm.head_dim
    state = lanes * n_heads * cfg.ssm.head_dim * cfg.ssm.d_state
    loops = _in_loops(hlo)
    in_loop = [c for c in _relayouts(hlo, state)
               if c[0] in loops and c[2] == "f32"]
    in_loop += [(b.comp, b.name, b.op) for b in _materialized(hlo)
                if b.comp in loops and b.n >= state and b.op == "broadcast"]
    assert not in_loop, in_loop
    outside = [c for c in _relayouts(hlo, state)
               if c[0] not in loops and c[2] == "f32"]
    assert len(outside) <= 2 * len(hybrid.runs(cfg)), outside
