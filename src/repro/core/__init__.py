"""ScALPEL-JAX core: the paper's contribution as a composable JAX module.

Public API:

    spec    = scalpel.MonitorSpec / spec_from_mapping / spec_from_discovery
    mon     = scalpel.Monitor(spec, params, telemetry=...)
    step    = mon.wrap(step_fn)          # or @scalpel.monitored(spec)
    mstate  = mon.init()

    out, mstate = jax.jit(step)(mstate, *args)   # one pytree, compact
    print(mon.report(mstate))                    # counters read directly

    runtime = scalpel.ScalpelRuntime(spec, config_path=..., install_signal=True)

``Monitor.open`` is the raw collection region ``wrap`` is built on.
"""
from .adaptive import (  # noqa: F401
    AdaptiveConfig,
    AdaptiveController,
)
from .config_file import (  # noqa: F401
    ConfigError,
    ScalpelConfig,
    apply_config,
    parse,
    parse_file,
    serialize,
)
from .context import (  # noqa: F401
    EventSpec,
    MonitorSpec,
    ScopeContext,
    spec_from_mapping,
)
from .counters import CounterState, MonitorParams  # noqa: F401
from .events import (  # noqa: F401
    CHANNELS,
    EXTENSIVE,
    INTENSIVE,
    channels_for,
    compute,
    lookup,
    registered,
)
from .instrument import (  # noqa: F401
    capture,
    current_collector,
    discover,
    discovering,
    function,
    instrument,
    probe,
    probe_scope,
    scan_with_counters,
    spec_from_discovery,
)
from .monitor import (  # noqa: F401
    LaneMonitorState,
    Monitor,
    MonitorState,
    monitored,
)
from .plan import (  # noqa: F401
    CompactDelta,
    MomentPlan,
    ScopePlans,
    SentinelLane,
    SentinelSet,
    SlotLayout,
    compile_scope_plans,
    compile_sentinels,
    describe_plans,
    lane_slot_ids,
    spec_fingerprint,
    spec_layout,
)
from .report import (  # noqa: F401
    JsonlWriter,
    build,
    estimates,
    format_text,
    to_json,
    write_jsonl,
)
from .runtime import ScalpelRuntime  # noqa: F401
from .telemetry import (  # noqa: F401
    CallbackSink,
    JsonlSink,
    Sink,
    SnapshotRing,
    TelemetryParams,
    TelemetryPlane,
    TelemetrySnapshot,
    TextSink,
    TokenRing,
    ring_append,
    token_ring_append,
)
