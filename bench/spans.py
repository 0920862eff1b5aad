"""Window-only views of the program's span histograms.

The serving stack keeps cumulative fixed-bucket histograms per stage
(`repro.obs.LatencyHistogram`, read through `ServingMetrics.state()`).
The difference of two readings, bucket by bucket, holds exactly the
observations made between them; percentiles interpolate inside the
winning bucket as the program's own histogram does.
"""

from __future__ import annotations

import math


class Hist:
    def __init__(self, bounds: list[float], counts: list[int]):
        if len(counts) != len(bounds) + 1:
            raise ValueError(f"{len(counts)} counts for {len(bounds)} bucket edges")
        self.bounds, self.counts = list(bounds), list(counts)

    @classmethod
    def delta(cls, before: dict | None, after: dict) -> "Hist":
        """Observations made between two ``LatencyHistogram.state()``s."""
        counts = list(after["counts"])
        if before is not None:
            if list(before["bounds"]) != list(after["bounds"]):
                raise ValueError("histogram bucket edges changed between readings")
            counts = [a - b for a, b in zip(counts, before["counts"])]
        if any(c < 0 for c in counts):
            raise ValueError("histogram lost observations between readings")
        return cls(after["bounds"], counts)

    @property
    def count(self) -> int:
        return sum(self.counts)

    def percentile(self, p: float) -> float | None:
        """p-th percentile in seconds; None when the window saw nothing."""
        n = self.count
        if n == 0:
            return None
        target = min(max(math.ceil(p / 100.0 * n), 1), n)
        cum = 0
        for i, c in enumerate(self.counts):
            if c and cum + c >= target:
                lo = self.bounds[i - 1] if i > 0 else 0.0
                hi = self.bounds[i] if i < len(self.bounds) else self.bounds[-1]
                return lo + (target - cum) / c * (hi - lo)
            cum += c
        return self.bounds[-1]
