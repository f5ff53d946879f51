"""Host wall-clock backend: named series of wall-clock samples.

The cheapest possible "effect" counter — equivalent to the paper's use of
UNIX ``time`` for the overhead study, but per named series and feeding the
runtime's adaptive hooks (``fit``'s straggler tripwire reads the per-step
series' outliers).
"""
from __future__ import annotations

import dataclasses
import statistics


@dataclasses.dataclass
class TimingStats:
    name: str
    calls: int
    total_s: float
    mean_s: float
    p50_s: float
    p95_s: float
    max_s: float


class HostTimer:
    def __init__(self):
        self.samples: dict[str, list[float]] = {}

    def record(self, name: str, seconds: float) -> None:
        self.samples.setdefault(name, []).append(seconds)

    def stats(self, name: str) -> TimingStats:
        xs = sorted(self.samples.get(name, []))
        if not xs:
            return TimingStats(name, 0, 0.0, 0.0, 0.0, 0.0, 0.0)
        n = len(xs)
        return TimingStats(
            name=name,
            calls=n,
            total_s=sum(xs),
            mean_s=sum(xs) / n,
            p50_s=xs[n // 2],
            p95_s=xs[min(n - 1, int(0.95 * n))],
            max_s=xs[-1],
        )

    def outliers(self, name: str, sigma: float = 3.0) -> list[int]:
        """Indices of samples more than ``sigma`` stdevs above the median —
        the straggler-detection primitive."""
        xs = self.samples.get(name, [])
        if len(xs) < 4:
            return []
        med = statistics.median(xs)
        sd = statistics.pstdev(xs) or 1e-12
        return [i for i, x in enumerate(xs) if (x - med) / sd > sigma]
