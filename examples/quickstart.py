"""Quickstart: monitor a model with ScALPEL-JAX in ~40 lines.

    PYTHONPATH=src python examples/quickstart.py

1. Build a model (any callable using scalpel.function/probe scopes).
2. Discover the compile-time scope set (the '-finstrument-functions' pass).
3. Inspect the compiled probe plans (what each event set will actually
   sweep — core/plan.py).
4. Wrap the step with the functional Monitor: ONE MonitorState pytree
   threads compact counters + step stamp through jit — no hand-threaded
   ``state = state.add(col.delta)`` anywhere.
5. Pick a runtime subset; run; read the per-scope report.
"""
import jax

from repro import core as scalpel
from repro.configs import model_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import Arch


def main():
    enable_compile_cache()
    # -- 1. the application: a small LM forward+loss ----------------------
    arch = Arch(model_config("qwen3_14b", smoke=True))
    params = arch.init(jax.random.PRNGKey(0))
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0,
                                     arch.cfg.vocab),
        "targets": jax.random.randint(jax.random.PRNGKey(2), (4, 64), 0,
                                      arch.cfg.vocab),
    }

    # -- 2. compile-time set: discover every scope the program touches ----
    seen = scalpel.discover(arch.loss_fn, params, batch)
    spec = scalpel.spec_from_discovery(
        seen, tensor_events=("ACT_RMS", "ACT_MAX_ABS")
    )
    print("compile-time scope set:")
    print(spec.describe())

    # -- 2b. the compiled probe plans: per (scope, event set), exactly the
    # raw channels that set sweeps per probed tensor (identical sweeps
    # share one switch branch body — see 'plans_deduped').  The fingerprint
    # is the attestation that the runtime reconfig below re-selects among
    # these plans instead of re-tracing.
    print("\ncompiled probe plans:")
    print(scalpel.describe_plans(spec))
    print(f"plan fingerprint: {spec.fingerprint[:12]}")

    # -- 3. the functional Monitor: wrap the step once, thread ONE pytree -
    # monitor only attention scopes to start (the runtime subset)
    attn_scopes = [s for s in spec.scopes if s.endswith("attn")]
    mon = scalpel.Monitor(
        spec, scalpel.MonitorParams.selective(spec, attn_scopes)
    )
    step = jax.jit(mon.wrap(lambda b: arch.loss_fn(params, b)))
    mstate = mon.init()

    for _ in range(3):
        loss, mstate = step(mstate, batch)

    # -- 4. report (paper: stdout on termination) — reports read the
    # compact counter lanes directly; no padded block is ever built
    print(f"\nloss={float(loss):.4f}")
    print(mon.report(mstate))

    # flipping the monitored subset is a data swap riding IN the state
    # pytree — NO recompile; the compiled plans (and their fingerprint)
    # are untouched:
    mstate = mon.sync(mstate, params=scalpel.MonitorParams.selective(
        spec, [s for s in spec.scopes if s.endswith("mlp")]
    ))
    loss, mstate = step(mstate, batch)  # same compiled step
    print("\nafter runtime reconfig to mlp scopes (no re-trace, plan "
          f"fingerprint still {spec.fingerprint[:12]}):")
    print(mon.report(mstate))


if __name__ == "__main__":
    main()
