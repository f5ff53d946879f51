"""Host-side continuous-batching scheduler: lane table, admission queue,
token attribution.

The device never sees requests — it sees LANES.  The scheduler owns the
mapping: which request occupies which lane, how many tokens it still owes,
and which drained token-ring slot belongs to whom.

Two deliberate design points keep the host out of the hot path:

* Completion is tracked ARITHMETICALLY.  Every lane decodes exactly once
  per megastep inner step and retires via the device-side active mask, so
  a dispatched K-step megastep advances an occupied lane by exactly
  ``min(K, remaining)`` tokens — admission/eviction decisions never read
  device state.

* Token attribution is DEFERRED.  Sampled tokens arrive a megastep late
  through the telemetry token ring; each lane keeps a FIFO of
  ``(request, expected)`` segments that drained slots consume in step
  order, so a lane's tokens attribute correctly even when retirement and
  re-admission happen before its last tokens are drained.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any

import numpy as np


@dataclasses.dataclass
class Request:
    rid: int
    tokens: np.ndarray          # [1, s] prompt
    max_new: int
    seed: int | None = None


@dataclasses.dataclass
class ServeResult:
    """One finished request: its sampled tokens plus the per-lane counter
    attribution harvested at retirement (prefill + decode, compact
    layout)."""

    tokens: np.ndarray                  # [n_new] i32, decode order
    counters: Any = None                # plan.CompactDelta (host numpy)
    lane: int = -1


class Scheduler:
    def __init__(self, n_lanes: int, buckets=None):
        if n_lanes < 1:
            raise ValueError(f"n_lanes must be >= 1, got {n_lanes}")
        self.n_lanes = int(n_lanes)
        # prompt-length buckets (sorted pad widths, or None = exact-length):
        # admission pads each prompt to its routed width so prefill compiles
        # once per BUCKET, not once per distinct length
        self.buckets = (tuple(sorted({int(b) for b in buckets}))
                        if buckets else None)
        self.prompt_tokens = 0
        self.pad_tokens = 0
        self.buckets_used: set[int] = set()
        self.queue: deque[Request] = deque()
        self.lane_rid: list[int | None] = [None] * n_lanes
        self.lane_left: list[int] = [0] * n_lanes
        # per-lane FIFO of [rid, tokens_still_expected] segments, admission
        # order — drained token slots consume them in step order
        self._segments: list[deque[list[int]]] = \
            [deque() for _ in range(n_lanes)]
        self._out: dict[int, list[int]] = {}
        self._expected: dict[int, int] = {}
        self._counters: dict[int, Any] = {}
        self._lane_of: dict[int, int] = {}
        self._next_rid = 0
        self.admitted = 0
        self.completed = 0

    # -- submission --------------------------------------------------------
    def submit(self, tokens, max_new: int, seed: int | None = None) -> int:
        rid = self._next_rid
        self._next_rid += 1
        self._expected[rid] = int(max_new)
        self._out[rid] = []
        if max_new > 0:
            self.queue.append(Request(rid, np.asarray(tokens),
                                      int(max_new), seed))
        return rid

    # -- prompt-length bucketing -------------------------------------------
    def width(self, length: int) -> int:
        """The pad width of a prompt length: the smallest configured bucket
        that fits (prompts past the largest bucket — and every prompt when
        bucketing is off — go exact-length)."""
        length = int(length)
        for b in self.buckets or ():
            if b >= length:
                return b
        return length

    def route(self, length: int) -> int:
        """Route a prompt length to its pad width (``width``).  Records pad
        waste: the fraction of prefill FLOPs spent on pad is the price of
        the bounded trace count."""
        length = int(length)
        width = self.width(length)
        self.prompt_tokens += length
        self.pad_tokens += width - length
        self.buckets_used.add(width)
        return width

    @property
    def pad_waste_frac(self) -> float:
        """Pad tokens as a fraction of all prefill tokens routed so far."""
        tot = self.prompt_tokens + self.pad_tokens
        return self.pad_tokens / tot if tot else 0.0

    # -- lane table --------------------------------------------------------
    def free_lanes(self) -> list[int]:
        return [i for i, r in enumerate(self.lane_rid) if r is None]

    @property
    def occupied(self) -> bool:
        return any(r is not None for r in self.lane_rid)

    def admit(self, lane: int, req: Request) -> None:
        assert self.lane_rid[lane] is None, f"lane {lane} occupied"
        self.lane_rid[lane] = req.rid
        self.lane_left[lane] = req.max_new
        self._segments[lane].append([req.rid, req.max_new])
        self._lane_of[req.rid] = lane
        self.admitted += 1

    def advance(self, k: int) -> list[tuple[int, int]]:
        """Account one dispatched K-step megastep.  Returns the
        ``(lane, rid)`` pairs whose requests finish WITHIN it: their lanes
        are free for the next admission phase (the device's active mask
        retired them in-graph; no re-trace, no readback)."""
        done = []
        for lane, rid in enumerate(self.lane_rid):
            if rid is None:
                continue
            self.lane_left[lane] -= min(int(k), self.lane_left[lane])
            if self.lane_left[lane] == 0:
                done.append((lane, rid))
                self.lane_rid[lane] = None
                self.completed += 1
        return done

    # -- token attribution (drained slots, a megastep behind) --------------
    def attribute(self, drained) -> int:
        """Feed drained token-ring slots ``(seq, step, toks, live)`` in
        append order; returns the number of tokens attributed."""
        n = 0
        for _seq, _step, toks, live in drained:
            for lane in np.nonzero(np.asarray(live) != 0)[0]:
                seg = self._segments[int(lane)]
                assert seg, f"live token on lane {lane} with no segment"
                rid, left = seg[0]
                self._out[rid].append(int(toks[int(lane)]))
                n += 1
                if left <= 1:
                    seg.popleft()
                else:
                    seg[0][1] = left - 1
        return n

    def set_counters(self, rid: int, counters) -> None:
        self._counters[rid] = counters

    # -- completion --------------------------------------------------------
    @property
    def all_attributed(self) -> bool:
        return all(len(self._out[r]) == e
                   for r, e in self._expected.items())

    def results(self) -> dict[int, ServeResult]:
        """Assemble final per-request results; every submitted request must
        be fully attributed (the engine drains the last ring first)."""
        out = {}
        for rid, expected in self._expected.items():
            toks = self._out[rid]
            assert len(toks) == expected, (
                f"request {rid}: {len(toks)}/{expected} tokens attributed"
            )
            out[rid] = ServeResult(
                tokens=np.asarray(toks, np.int32),
                counters=self._counters.get(rid),
                lane=self._lane_of.get(rid, -1),
            )
        return out
