"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports.

A trace holds one plane per device (``/device:TPU:<n>``) whose ``XLA Ops``
line has one event per executed HLO operation, nested: a ``while`` or
``conditional`` event spans the events of its body.  Host planes hold the
threads' events, among them the harness's own spans (``bench.*``, written
with ``jax.profiler.TraceAnnotation``) and the runtime's dispatch and
transfer events.  Times in a trace count from the start of the profile.
The device tracer starts some hundreds of milliseconds after the profile
(0.25 s in the recorded test trace), so the harness waits before it opens
its span ``bench.traced_part``, and the traced window is that span: idle
time at either end of it counts, as any other.

* busy: the union of the intervals of the *leaf* operations (events that
  contain no other) on a device, within the traced window, averaged over
  the devices; idle is the rest of the window;
* op time by name: the time of the top-level operations (events inside no
  other), summed by HLO name;
* idle gaps: the holes in the union of leaf intervals, each named by the
  innermost harness span it fell in and the host event that overlapped it
  most;
* kernel calls: the leaf events whose HLO text matches a pattern, with
  the operand shapes parsed from that text.
"""
from __future__ import annotations

import glob
import math
import re
from pathlib import Path

import numpy as np

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
HARNESS_PREFIX = "bench."
# the harness span that is the traced window
WINDOW_SPAN = "bench.traced_part"
# the probe-moments Pallas kernel: its custom call takes the kernel's name
PROBE_KERNEL = r"^%?probe_moments(\.\d+)? = "

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
               "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
               "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8, "u64": 8}
_SHAPE = re.compile(r"\b([a-z]+[0-9]*[a-z0-9]*)\[([0-9,]*)\]")


def find_xplane(trace_dir) -> Path:
    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(files[-1])


def op_short_name(text: str) -> str:
    """``%fusion.12 = bf16[..] fusion(..)`` -> ``fusion.12``."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


_OPCODE = re.compile(r" ([a-z][\w.-]*)\(")


def operand_shapes(text: str) -> list[tuple[str, tuple[int, ...]]]:
    """(dtype, dims) of each operand of the HLO instruction ``text``: the
    typed operands inside the parentheses that follow the opcode (the
    result's layout, ``T(8,128)``, has parentheses of its own)."""
    rhs = text.split(" = ", 1)[-1]
    m = _OPCODE.search(rhs)
    if m is None:
        return []
    start = m.end() - 1
    depth, end = 0, len(rhs)
    for i in range(start, len(rhs)):
        if rhs[i] == "(":
            depth += 1
        elif rhs[i] == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    out = []
    for dt, dims in _SHAPE.findall(rhs[start + 1:end]):
        if dt in DTYPE_BYTES:
            out.append((dt, tuple(int(d) for d in dims.split(",") if d)))
    return out


def shape_bytes(shapes) -> int:
    return sum(DTYPE_BYTES[dt] * math.prod(dims) for dt, dims in shapes)


class Line:
    """One timeline: event starts and ends in ns, and their names."""

    def __init__(self, name, starts, ends, names, texts=None):
        self.name = name
        self.starts = np.asarray(starts, np.float64)
        self.ends = np.asarray(ends, np.float64)
        self.names = names
        # full event text by name, where it is longer (HLO instructions)
        self.texts = texts or {}

    @classmethod
    def of(cls, line, hlo: bool = False):
        """Read one line of a plane.  With ``hlo`` the names are HLO
        instructions: each event keeps the short name (``#k`` added where
        another program's instruction has the same one) and the full text
        is kept once per name."""
        starts, ends, names, texts = [], [], [], {}
        key_of: dict[str, str] = {}
        seen: dict[str, int] = {}
        for e in line.events:
            starts.append(e.start_ns)
            ends.append(e.start_ns + e.duration_ns)
            name = e.name
            if hlo:
                key = key_of.get(name)
                if key is None:
                    short = op_short_name(name)
                    k = seen.get(short, 0) + 1
                    seen[short] = k
                    key = short if k == 1 else f"{short}#{k}"
                    key_of[name] = key
                    texts[key] = name
                name = key
            names.append(name)
        return cls(line.name, starts, ends, names, texts)

    def nesting(self):
        """(leaf, top) masks: events containing no other event, and events
        inside no other event (events of one line nest properly)."""
        n = len(self.starts)
        order = np.lexsort((-(self.ends - self.starts), self.starts))
        leaf = np.ones(n, bool)
        top = np.zeros(n, bool)
        stack: list[int] = []
        for i in order:
            s = self.starts[i]
            while stack and self.ends[stack[-1]] <= s:
                stack.pop()
            if stack:
                leaf[stack[-1]] = False
            else:
                top[i] = True
            stack.append(i)
        return leaf, top


def union(starts, ends, lo, hi) -> list[tuple[float, float]]:
    """Merged intervals of [starts, ends) clipped to [lo, hi]."""
    s = np.clip(np.asarray(starts, np.float64), lo, hi)
    e = np.clip(np.asarray(ends, np.float64), lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if not len(s):
        return []
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    # an interval opens a new merged one where it starts past all before it
    first = np.flatnonzero(np.r_[True, s[1:] > reach[:-1]])
    last = np.r_[first[1:] - 1, len(s) - 1]
    return list(zip(s[first].tolist(), reach[last].tolist()))


def gaps_of(merged, lo, hi) -> list[tuple[float, float]]:
    """The holes in ``merged`` (sorted, disjoint) within [lo, hi]."""
    edges = [lo] + [x for ab in merged for x in ab] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


class Trace:
    """The parts of one profile the benchmark reads."""

    def __init__(self, window_ns: float, devices: list[dict],
                 host_lines: list[Line], start_ns: float = 0.0):
        # the traced window: [start_ns, start_ns + window_ns)
        self.start_ns = start_ns
        self.window_ns = window_ns
        self.devices = devices          # per device: ops Line + masks
        self.host_lines = host_lines

    @classmethod
    def load(cls, path, span: str = WINDOW_SPAN) -> "Trace":
        """Read the profile at ``path``; the traced window is the first host
        event named ``span``."""
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(str(path))
        devices, host = [], []
        for plane in pd.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        ops = Line.of(line, hlo=True)
                        leaf, top = ops.nesting()
                        devices.append({"plane": plane.name, "ops": ops,
                                        "leaf": leaf, "top": top})
            elif plane.name.startswith("/host:"):
                host.extend(Line.of(line) for line in plane.lines)
        for line in host:
            for i, name in enumerate(line.names):
                if name == span:
                    lo, hi = float(line.starts[i]), float(line.ends[i])
                    return cls(hi - lo, devices, host, lo)
        raise ValueError(f"{path}: no host event {span!r}")

    # -- device time ------------------------------------------------------
    def busy_s(self) -> float:
        """Seconds in which a leaf operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        total = 0.0
        for d in self.devices:
            ops, leaf = d["ops"], d["leaf"]
            merged = union(ops.starts[leaf], ops.ends[leaf], self.start_ns,
                           self.start_ns + self.window_ns)
            total += sum(b - a for a, b in merged)
        return total / len(self.devices) / 1e9

    def op_seconds(self) -> dict[str, float]:
        """Seconds by name of the top-level operations, summed over devices
        and divided by their number."""
        out: dict[str, float] = {}
        for d in self.devices:
            ops = d["ops"]
            for i in np.nonzero(d["top"])[0]:
                key = ops.names[i]
                out[key] = out.get(key, 0.0) + (ops.ends[i] - ops.starts[i])
        n = max(1, len(self.devices))
        return {k: v / n / 1e9 for k, v in out.items()}

    def kernel_calls(self, pattern: str) -> list[dict]:
        """Leaf operations whose HLO text matches ``pattern``: seconds and
        operand shapes of each call."""
        rx = re.compile(pattern)
        calls = []
        for d in self.devices:
            ops, leaf = d["ops"], d["leaf"]
            hits = {name: operand_shapes(text)
                    for name, text in ops.texts.items() if rx.search(text)}
            for i in np.nonzero(leaf)[0]:
                name = ops.names[i]
                if name in hits:
                    calls.append({
                        "seconds": (ops.ends[i] - ops.starts[i]) / 1e9,
                        "operands": hits[name], "name": name})
        return calls

    # -- idle gaps --------------------------------------------------------
    def idle_gaps(self, device: int = 0) -> list[tuple[float, float]]:
        d = self.devices[device]
        ops, leaf = d["ops"], d["leaf"]
        lo, hi = self.start_ns, self.start_ns + self.window_ns
        return gaps_of(union(ops.starts[leaf], ops.ends[leaf], lo, hi), lo, hi)

    def name_gap(self, a: float, b: float) -> str:
        """The innermost harness span around the gap, and the host event
        (not a harness span) that overlapped it most."""
        mid = 0.5 * (a + b)
        span, span_len = "outside any span", math.inf
        best, best_overlap = "no host event", 0.0
        for line in self.host_lines:
            if not len(line.starts):
                continue
            lo = np.maximum(line.starts, a)
            hi = np.minimum(line.ends, b)
            overlap = hi - lo
            for i in np.nonzero(overlap > 0)[0]:
                name = line.names[i]
                if name.startswith(HARNESS_PREFIX):
                    length = line.ends[i] - line.starts[i]
                    if line.starts[i] <= mid <= line.ends[i] \
                            and length < span_len:
                        span, span_len = name, length
                elif overlap[i] > best_overlap:
                    best, best_overlap = name, overlap[i]
        return f"{span}: {best}"

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])[:n] \
            if self.devices else []
        return {
            "device_ops": [[name, s] for name, s in ops],
            "idle_gaps": [[self.name_gap(a, b), (b - a) / 1e9]
                          for a, b in gaps],
        }
