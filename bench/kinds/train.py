"""Runs training cells: one call of the program's ``fit``.

``fit`` takes a step count, builds its own compiled megastep of ``K``
steps, and reports each drained telemetry snapshot to ``on_report`` on the
drain thread.  So one call does set-up and window alike:

* the first ``warmup_commits`` megasteps compile (or load from the
  persistent cache) and settle; the window opens when the snapshot of the
  last of them is drained;
* the window is the next ``ceil(seconds / (K * nominal_step_s))``
  megasteps, a fixed amount of work for a given ``--seconds``; it closes
  when the snapshot of its last step is drained, i.e. when that step's
  device work is done;
* with ``--trace 1``, ``trace_commits`` more megasteps follow, of which
  ``trace_megasteps`` times the megastep's time that the window measured
  are profiled (a whole number of megasteps holds every part of one, the
  gap between two included, equally often, whatever the phase at which
  the trace opens).

Then the check: the megastep object ``fit`` returns is driven once more
from the seed's state over the window's first ``K`` batches, which must
give the window's own first ``K`` losses again, so that the gradient norms
and the parameters' change after ``K`` steps can be read and compared with
the plain reference run over the same batches; so is the first drained
snapshot of the ``grads`` scope (losses and gradient norms summed).  The
losses alone are not compared with the reference: the float8 control moves
them by no more than 2 times what sound runs read.
"""
from __future__ import annotations

import gc
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, flops, gen

KIND = "train"


def _leaf_map(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(q, "key", q)) for q in path): leaf
            for path, leaf in flat}


def run(ctx: common.RunContext, fault=None) -> dict:
    """Set up, measure, check.  ``fault`` (tests only) is called with the
    program's modules before ``fit`` to break the timed path."""
    from repro.data import DataConfig
    from repro.data.pipeline import SyntheticLM, shard_batch
    from repro.models.registry import Arch
    from repro.optim import OptConfig
    from repro.train import loop as loop_lib
    from repro.train.step import TrainState

    tr = ctx.traffic
    chips = int(ctx.cell["chips"])
    k = int(tr["steps_per_commit"])
    cfg = common.model_config(ctx.config)
    arch = Arch(cfg)
    opt = OptConfig(**tr["opt"])
    batch = int(tr["batch_per_chip"]) * chips
    seq = int(tr["seq_len"])
    data_kw = dict(tr["data"])
    data_cfg = DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch,
                          seed=ctx.seed, **data_kw)
    warm = k * int(tr["warmup_commits"])
    window = k * max(1, math.ceil(ctx.seconds / (k * tr["nominal_step_s"])))
    extra = k * int(tr["trace_commits"]) if ctx.trace else 0
    steps = warm + window + extra
    if fault is not None:
        fault(loop_lib)

    marks: dict = {}
    first_snapshot: dict = {}
    traced = common.TracedPart(ctx.work_dir) if ctx.trace else None
    grads_scope = "grads"

    def on_report(rt, reports):
        # one call per drained snapshot, in order; the ring appends one
        # snapshot per megastep (hook_every = K), so the n-th holds step nK
        now = time.perf_counter()
        marks["snapshots"] = marks.get("snapshots", 0) + 1
        step = k * marks["snapshots"]
        if not first_snapshot:
            vals = {}
            for r in reports:
                if r.scope == grads_scope:
                    vals = {s.slot_id: float(s.raw) for s in r.slots}
            first_snapshot.update(
                step=step, values=vals,
                samples=sum(s.samples for r in reports for s in r.slots))
        if "start" not in marks and step >= warm:
            marks["start"] = (now, step, ctx.compiles.snapshot())
        if "end" not in marks and step >= warm + window:
            marks["end"] = (now, step, ctx.compiles.snapshot())
            if traced is not None:
                t_start, s_start, _ = marks["start"]
                megastep_s = (now - t_start) * k / (step - s_start)
                traced.start(tr["trace_megasteps"] * megastep_s)

    out = loop_lib.fit(
        arch, opt, data_cfg,
        loop_lib.TrainLoopConfig(
            steps=steps, steps_per_commit=k, hook_every=k, log_every=0,
            ckpt_every=0, seed=ctx.seed,
            monitor_config_path=(str(ctx.monitor_cfg)
                                 if ctx.monitor_cfg else None)),
        on_report=on_report)
    t_fit_end = time.perf_counter()
    mem = common.memory_peak_bytes(ctx.devices)
    t_start, s_start, c_start = marks["start"]
    t_end, s_end, c_end = marks["end"]
    seconds = t_end - t_start
    tokens = (s_end - s_start) * batch * seq
    e2e = {"train_tokens_per_s": tokens / seconds,
           "setup_s": t_start - ctx.t0}

    readings = None
    if traced is not None:
        readings = traced.reduce()
        print("traced part (s after the window's end): " + ", ".join(
            f"{name} {t - t_end:.3f}" for name, t in
            {**traced.times, "fit_end": t_fit_end}.items())
            + f"; traced {readings['window_s']:.3f} s, read in "
            f"{readings['reduce_s']:.1f} s", file=sys.stderr, flush=True)
    window_compiles = c_end["compiles"] - c_start["compiles"]
    losses = [float(x) for x in out["losses"][:k]]
    drained_step = int(out["runtime"].telemetry.last_step)

    # the megastep fit drove, once more from the seed, over the same
    # batches: gradient norms and the parameters' change after K steps
    host = SyntheticLM(data_cfg)
    batches = [host.batch_at(i) for i in range(k)]
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *batches)
    state0 = TrainState.create(arch, opt, jax.random.PRNGKey(ctx.seed))
    master0 = jax.tree.map(jnp.copy, state0.opt.master)
    (state_k, outs), _ = out["step"](out["monitor"], shard_batch(stacked),
                                     state0)
    redrive_losses = [float(x) for x in np.asarray(outs["loss"])]
    gnorms = [float(x) for x in np.asarray(outs["grad_norm"])]
    delta = {name: float(jnp.sqrt(jnp.sum(jnp.square(a - b))))
             for (name, a), b in zip(_leaf_map(state_k.opt.master).items(),
                                     jax.tree.leaves(master0))}
    del out, state0, state_k, master0, outs
    gc.collect()

    ref = ctx.reference.train_steps(
        ctx.config["model"], tr["opt"], ctx.seed,
        [gen.lm_batch(ctx.seed, i, vocab=cfg.vocab, seq_len=seq,
                      batch=batch, **data_kw) for i in range(k)])
    checks = compare(tr, ref, losses=losses, redrive_losses=redrive_losses,
                     gnorms=gnorms, delta=delta, snapshot=first_snapshot,
                     drained_step=drained_step, steps=steps,
                     window_compiles=window_compiles,
                     dormant=ctx.monitor_cfg is not None)
    r = {
        "kind": KIND, "chips": chips, "devices": ctx.devices,
        "window": {"seconds": seconds, "tokens": tokens,
                   "model_flops": tokens * flops.train_flops_per_token(
                       ctx.reference, ctx.config["model"])},
    }
    if readings is not None:
        r.update(readings)
    return {"e2e": e2e, "readings": r, "checks": checks,
            "attempted": s_end - s_start, "failed": 0,
            "memory_peak_bytes": mem}


def compare(tr: dict, ref: dict, *, losses, redrive_losses, gnorms, delta,
            snapshot, drained_step, steps, window_compiles,
            dormant: bool) -> dict:
    """Each compared number beside its limit (``tr["limits"]``)."""
    lim = tr["limits"]
    rl, rg = ref["losses"], ref["gnorms"]
    redrive_gap = max(abs(a - b) for a, b in zip(redrive_losses, losses))
    gnorm_gap = max(abs(a - b) / abs(b) for a, b in zip(gnorms, rg))
    # leaves whose first gradient is nought to rounding in the reference
    # move under Adam by round-off alone: leave them out by that rule
    g1 = ref["grad1_norms"]
    g_med = float(np.median(list(g1.values())))
    moved = [n for n in ref["delta_norms"] if g1[n] >= 1e-3 * g_med]
    d_med = float(np.median([ref["delta_norms"][n] for n in moved]))
    update_gap = max(
        abs(delta[n] - ref["delta_norms"][n])
        / max(ref["delta_norms"][n], d_med) for n in moved)
    checks = {
        "gnorm_gap": common.check(gnorm_gap, lim["gnorm_gap"]),
        "update_gap": common.check(update_gap, lim["update_gap"]),
        "redrive_loss_diff": common.check(redrive_gap, 0.0),
        "drained_steps_missing": common.check(steps - drained_step, 0.0),
        "window_compiles": common.check(window_compiles, 0.0),
    }
    vals = snapshot.get("values", {})
    if dormant:
        # every scope masked off: the drained counters sampled nothing
        checks["dormant_samples"] = common.check(
            snapshot.get("samples", -1), 0.0)
    else:
        n = snapshot.get("step", 0)
        want_loss, want_gn = sum(rl[:n]), sum(rg[:n])
        got_loss = vals.get("MEAN:loss_value", float("nan"))
        got_gn = vals.get("MEAN:gnorm", float("nan"))
        gap = max(abs(got_loss - want_loss) / want_loss,
                  abs(got_gn - want_gn) / want_gn) \
            if n == len(rl) else float("nan")
        checks["counter_gap"] = common.check(gap, lim["counter_gap"])
    return checks
