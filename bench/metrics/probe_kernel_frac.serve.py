"""Device time of the probe-moments kernel over the traced seconds."""
from bench import trace

KIND = "serve"


def read(r: dict):
    if r.get("kind") != KIND or r.get("trace") is None:
        return None
    calls = r["trace"].kernel_calls(trace.PROBE_KERNEL)
    if not calls:
        return None
    return 100.0 * sum(c["seconds"] for c in calls) / r["window_s"]
