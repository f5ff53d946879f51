"""Share of the traced part of a serving run in which the serving loop's
host thread worked: the union of the program's spans ``scalpel.serve.*``
(less the wait ``scalpel.serve.wait``) and ``scalpel.tokens``, clipped to
the traced window, over the traced seconds.  None where the program writes
no such span."""
import numpy as np

from bench import trace

KIND = "serve"


def is_work(name: str) -> bool:
    return (name.startswith("scalpel.serve.")
            and name != "scalpel.serve.wait") or name == "scalpel.tokens"


def read(r: dict):
    tr = r.get("trace")
    if r.get("kind") != KIND or tr is None:
        return None
    starts, ends = [], []
    for line in tr.host_lines:
        hit = np.array([is_work(n) for n in line.names], bool)
        starts.append(line.starts[hit])
        ends.append(line.ends[hit])
    starts, ends = np.concatenate(starts or [[]]), np.concatenate(ends or [[]])
    if not len(starts):
        return None
    merged = trace.union(starts, ends, tr.start_ns,
                         tr.start_ns + tr.window_ns)
    return 100.0 * sum(b - a for a, b in merged) / tr.window_ns
