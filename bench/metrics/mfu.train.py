"""Model FLOP utilization of training: ``6 N`` per token times the
window's tokens per second, over the chips' bf16 peak (no recompute)."""
from bench import peaks


def read(r: dict):
    if r.get("kind") != "train":
        return None
    w = r["window"]
    peak = peaks.peaks(r["devices"][0].device_kind)["bf16_flops_per_s"]
    return 100.0 * w["model_flops"] / w["seconds"] / (r["chips"] * peak)
