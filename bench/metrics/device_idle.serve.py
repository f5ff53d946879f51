"""Share of the traced part of a serving run in which no operation ran on
the device (1 - union of op intervals / traced seconds)."""


def read(r: dict):
    if r.get("kind") != "serve" or not r.get("window_s"):
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
