"""Kernel microbench: Pallas kernels vs pure-jnp oracles (interpret mode) +
the analytic cost-model table per schedule/block-shape (the numbers a real
TPU run would validate against).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, probe_reduce, ref

from .common import bench, fmt_table, save_json


def gemm_cost_table():
    rows = []
    for m, n, k in [(2048, 2048, 2048), (4096, 4096, 4096),
                    (8192, 8192, 1024)]:
        for sched, blocks in [
            ("cache_blocked", dict(bm=128, bn=128, bk=128)),
            ("cache_blocked", dict(bm=256, bn=256, bk=256)),
            ("cache_blocked", dict(bm=512, bn=512, bk=512)),
            ("panel_streaming", dict(bm=128, bn=256, bk=0)),
            ("panel_streaming", dict(bm=256, bn=512, bk=0)),
        ]:
            c = ops.matmul_cost(sched, m, n, k, **{k2: v for k2, v in
                                                   blocks.items() if v})
            rows.append({
                "mnk": f"{m}x{n}x{k}",
                "schedule": sched,
                "blocks": "/".join(str(v) for v in blocks.values() if v),
                "GFLOP": round(c["FLOPS"] / 1e9, 1),
                "HBM_MB": round(c["HBM_BYTES"] / 1e6, 1),
                "AI": round(c["arithmetic_intensity"], 1),
                "VMEM_KB": round(c["vmem_working_set_bytes"] / 1e3, 1),
                "stall_kcyc": round(c["EST_STALL_CYCLES"] / 1e3, 1),
            })
    return rows


def correctness_and_speed(fast: bool):
    rows = []
    # gemm
    m = n = k = 256
    a = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
    want = np.asarray(ref.matmul(a, b))
    for sched in ops.SCHEDULES:
        got = ops.matmul(a, b, sched, bm=128, bn=128, bk=128,
                         interpret=True)
        err = float(np.max(np.abs(np.asarray(got) - want)))
        t = bench(lambda: ops.matmul(a, b, sched, bm=128, bn=128, bk=128,
                         interpret=True),
                  iters=3 if fast else 5)
        rows.append({"kernel": f"gemm/{sched}", "shape": f"{m}^3",
                     "max_err": f"{err:.1e}",
                     "ms_interpret": round(t["min_s"] * 1e3, 2)})
    # flash attention
    bq, s, h, d = 1, 512, 4, 64
    q = jax.random.normal(jax.random.PRNGKey(2), (bq, s, h, d), jnp.float32)
    kk = jax.random.normal(jax.random.PRNGKey(3), (bq, s, h, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(4), (bq, s, h, d), jnp.float32)
    want = np.asarray(ref.attention(q, kk, v, causal=True))
    got = ops.flash_attention(q, kk, v, causal=True, block_q=128,
                              block_kv=128, interpret=True)
    err = float(np.max(np.abs(np.asarray(got) - want)))
    t = bench(lambda: ops.flash_attention(q, kk, v, causal=True,
                                          block_q=128, block_kv=128,
                                          interpret=True),
              iters=3 if fast else 5)
    rows.append({"kernel": "flash_attn", "shape": f"s{s} h{h} d{d}",
                 "max_err": f"{err:.1e}",
                 "ms_interpret": round(t["min_s"] * 1e3, 2)})
    # ssm scan
    B, S, D = 2, 1024, 64
    la = -jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (B, S, D))) * 0.3
    bb = jax.random.normal(jax.random.PRNGKey(6), (B, S, D))
    want = np.asarray(ref.ssm_scan(None, la, bb))
    got = ops.ssm_scan(la, bb, chunk=256, bd=64, interpret=True)
    err = float(np.max(np.abs(np.asarray(got) - want)))
    t = bench(lambda: ops.ssm_scan(la, bb, chunk=256, bd=64, interpret=True),
              iters=3 if fast else 5)
    rows.append({"kernel": "ssm_scan", "shape": f"B{B} S{S} D{D}",
                 "max_err": f"{err:.1e}",
                 "ms_interpret": round(t["min_s"] * 1e3, 2)})
    # fused probe-moment reduction (the monitoring hot path)
    x = jax.random.normal(jax.random.PRNGKey(7), (1 << 16,), jnp.float32)
    want = np.asarray(probe_reduce.moments_ref(x))
    got = np.asarray(ops.probe_moments(x, interpret=True))
    err = float(np.max(np.abs(got - want)))
    t = bench(lambda: ops.probe_moments(x, interpret=True),
              iters=3 if fast else 5)
    rows.append({"kernel": "probe_reduce", "shape": f"{x.size} elems",
                 "max_err": f"{err:.1e}",
                 "ms_interpret": round(t["min_s"] * 1e3, 2)})
    return rows


def main(fast: bool = False):
    rows = correctness_and_speed(fast)
    print(fmt_table(rows, ["kernel", "shape", "max_err", "ms_interpret"],
                    title="Pallas kernels vs oracle (interpret mode on CPU)"))
    cost = gemm_cost_table()
    print()
    print(fmt_table(
        cost,
        ["mnk", "schedule", "blocks", "GFLOP", "HBM_MB", "AI", "VMEM_KB",
         "stall_kcyc"],
        title="GEMM schedule cost model (TPU v5e constants)",
    ))
    save_json("kernels.json", {"correctness": rows, "cost": cost},
              sub="bench")
    return rows


if __name__ == "__main__":
    import sys

    main(fast="--fast" in sys.argv)
