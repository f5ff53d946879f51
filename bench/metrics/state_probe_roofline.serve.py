"""Share of the chip's HBM peak that the probe-moments kernel reaches over
the recurrent state of the Mamba2 layers: the bytes those calls read
(``flops.moments_kernel_cost`` of their operands) over their device
seconds.  The calls are those whose operand is the float32 SSD state
``[..., 112, 64, 64]`` (heads, head_dim and d_state of Zamba2-7B's Mamba2),
in prefill and decode alike.  None where no such call ran."""
from bench import flops, peaks, trace

KIND = "serve"
STATE_DIMS = (112, 64, 64)


def state_calls(r: dict) -> list[dict]:
    if r.get("kind") != KIND or r.get("trace") is None:
        return []
    return [c for c in r["trace"].kernel_calls(trace.PROBE_KERNEL)
            if any(dt == "f32" and tuple(dims[-3:]) == STATE_DIMS
                   for dt, dims in c["operands"])]


def read(r: dict):
    calls = state_calls(r)
    seconds = sum(c["seconds"] for c in calls)
    if not calls or seconds <= 0:
        return None
    moved = sum(flops.moments_kernel_cost(c["operands"])["bytes"]
                for c in calls)
    peak = peaks.peaks(r["devices"][0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * moved / seconds / peak
