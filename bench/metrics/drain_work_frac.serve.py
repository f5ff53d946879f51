"""Share of the traced part of a serving run in which the telemetry drain
worked: the union of the program's ``scalpel.drain`` spans (snapshots built
from transfers already on the host, and sink emits; the drain's wait for
the device has no span), clipped to the traced window, over the traced
seconds.  None where the program writes no such span."""
import numpy as np

from bench import trace

KIND = "serve"
SPAN = "scalpel.drain"


def read(r: dict):
    tr = r.get("trace")
    if r.get("kind") != KIND or tr is None:
        return None
    starts, ends = [], []
    for line in tr.host_lines:
        hit = np.array([n == SPAN for n in line.names], bool)
        starts.append(line.starts[hit])
        ends.append(line.ends[hit])
    starts, ends = np.concatenate(starts or [[]]), np.concatenate(ends or [[]])
    if not len(starts):
        return None
    merged = trace.union(starts, ends, tr.start_ns,
                         tr.start_ns + tr.window_ns)
    return 100.0 * sum(b - a for a, b in merged) / tr.window_ns
