"""Model FLOP utilization of serving: ``2 N`` per prompt and generated
token, over the window's seconds and the chips' bf16 peak."""
from bench import peaks


def read(r: dict):
    if r.get("kind") != "serve":
        return None
    w = r["window"]
    peak = peaks.peaks(r["devices"][0].device_kind)["bf16_flops_per_s"]
    return 100.0 * w["model_flops"] / w["seconds"] / (r["chips"] * peak)
