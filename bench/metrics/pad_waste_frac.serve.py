"""Pad tokens as a share of all prefill tokens the window routed (the
serve scheduler's counters, read before and after the window)."""


def read(r: dict):
    if r.get("kind") != "serve":
        return None
    return 100.0 * r["window"]["pad_waste_frac"]
