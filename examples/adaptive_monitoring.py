"""Adaptive monitoring, closed-loop (paper §3.3 + C5): the self-retuning
``AdaptiveController`` localizes an injected NaN to the right scope,
widens that scope's event set, raises the snapshot rate, then decays
everything back down the degradation ladder once the anomaly passes.

What replaced the old SIGUSR1 demo: the reconfiguration POLICY is now in
the library (core/adaptive.py) instead of a hand-rolled signal handler.
The mechanism is unchanged — every controller action is a MonitorParams /
cadence reference swap picked up by ``mon.sync`` between steps, never a
re-trace (``runtime.plan_fingerprint`` is printed before and after to
attest it).  The controller runs as a ``CallbackSink`` over drained
telemetry snapshots and never dispatches device work.

The degradation ladder, per scope:

    wide        scope+slot masks all-on, multiplex period 1 (escalated)
    configured  whatever params the controller was installed with
    sentinel    scope_mask 0 — presence counters only (the probe path's
                lax.cond skips every event sweep; interception still
                counts calls for free)

The fault harness (repro.testing.faults) injects a deterministic NaN into
ONE scope's probed tensor at a known step; the smoke assertion is the
acceptance criterion — the right scope escalates within K=5 drained
snapshots, and nothing else does.

    PYTHONPATH=src python examples/adaptive_monitoring.py
"""
import jax
import jax.numpy as jnp

from repro import core as scalpel
from repro.core.adaptive import AdaptiveConfig
from repro.core.context import EventSpec, MonitorSpec, ScopeContext
from repro.launch.compile_cache import enable_compile_cache
from repro.testing.faults import FaultInjector, TensorFault

EVENTS = ("ACT_RMS", "ACT_ZERO_FRAC", "NAN_COUNT", "INF_COUNT")
SCOPES = ("layer/attn", "layer/mlp", "head")
FAULT_SCOPE = "layer/attn"
# The NaN must land while its scope still monitors (a scope that already
# decayed to the sentinel rung is blind to tensor anomalies by design —
# only the global step-time detector wakes sentinels): with quiet_steps=12
# the scopes hibernate around step 12, so inject at step 10.  Patience is
# denominated in STEPS (snapshot stamp spans), not drained snapshots, so
# the timing here is independent of the ring cadence.
NAN_STEP = 10        # carried step at which the NaN is spliced in
STEPS = 56
CADENCE = 2          # baseline ring-append cadence (steps per snapshot)
K_DRAINS = 5         # acceptance bound: escalate within K drained snapshots


def build_spec() -> MonitorSpec:
    return MonitorSpec.of([
        ScopeContext.exhaustive(s, [EventSpec(e, "x") for e in EVENTS])
        for s in SCOPES
    ])


def main():
    enable_compile_cache()
    spec = build_spec()
    runtime = scalpel.ScalpelRuntime(spec, hook_every=CADENCE,
                                     graceful_shutdown=True)
    ctl = runtime.attach_controller(AdaptiveConfig(
        quiet_steps=12, cooldown_steps=4, warmup_drains=2,
        escalated_cadence=1,
        # this demo drains synchronously inside a trivial workload, so the
        # measured drain overhead IS most of the wall time — park the
        # budget loop (run_adaptive_sweep exercises it on a real workload)
        overhead_budget=1.0,
    ))
    injector = FaultInjector([
        TensorFault(FAULT_SCOPE, "x", step=NAN_STEP, kind="nan"),
    ])
    fp_before = runtime.plan_fingerprint

    mon = scalpel.Monitor(spec, telemetry=runtime.telemetry,
                          counter_axes=())
    key = jax.random.PRNGKey(0)
    w1, w2, w3 = (jax.random.normal(k, (64, 64)) * 0.2
                  for k in jax.random.split(key, 3))

    def workload(x, step):
        h = jnp.tanh(x @ w1)
        with scalpel.function("layer/attn"):
            # the fault corrupts only the PROBED copy: the anomaly shows up
            # in exactly one scope's counters and nowhere downstream
            scalpel.probe(x=injector.corrupt(FAULT_SCOPE, "x", step, h))
        m = jnp.tanh(h @ w2)
        with scalpel.function("layer/mlp"):
            scalpel.probe(x=m)
        y = m @ w3
        with scalpel.function("head"):
            scalpel.probe(x=y)
        return x, step + 1

    step_fn = mon.jit(workload)
    mstate = mon.init()
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
    step = jnp.zeros((), jnp.int32)
    for _ in range(STEPS):
        # pick up the controller's latest mask/period/cadence decisions —
        # reference swaps into the carried pytree, never a re-trace
        mstate = mon.sync(mstate, runtime=runtime)
        (x, step), mstate = step_fn(mstate, x, step)
        runtime.on_step(mstate.counters, ring=mstate.ring)
        # deterministic demo: drain synchronously so controller decisions
        # land at fixed steps (production leaves this to the drain thread)
        runtime.flush()

    print(f"plan fingerprint: {fp_before[:12]} -> "
          f"{runtime.plan_fingerprint[:12]} (unchanged: no re-trace)")
    print(ctl.describe())
    print(mon.report(mstate.counters, title="ScALPEL adaptive demo"))

    # ---- smoke assertions (CI adaptive-smoke job greps for PASS) --------
    assert runtime.plan_fingerprint == fp_before
    wide = [t for t in ctl.transitions if t.to == "wide"]
    assert wide, f"no escalation happened: {ctl.events}"
    assert all(t.scope == FAULT_SCOPE for t in wide), \
        f"escalated the wrong scope(s): {wide}"
    # localized within K drained snapshots of the faulty step's snapshot
    t = wide[0]
    assert t.step - NAN_STEP <= CADENCE * K_DRAINS, (t, NAN_STEP)
    assert "NAN_COUNT" in t.reason
    # the ladder decayed once quiet: the faulty scope stepped back down and
    # quiet scopes reached the sentinel rung (presence counters only)
    assert ctl.stats["deescalations"] > 0, ctl.events
    assert ctl.levels[FAULT_SCOPE] != "wide", ctl.levels
    assert "sentinel" in ctl.levels.values(), ctl.levels
    # escalation raised the snapshot rate, the decay restored it
    assert runtime.telemetry.cadence == CADENCE, runtime.telemetry.cadence
    print("ADAPTIVE-SMOKE: PASS")
    runtime.shutdown()


if __name__ == "__main__":
    main()
