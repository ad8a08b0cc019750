"""Histograms counted where the work happens, exported at the scrape.

The serving loop observes latencies far more often than Prometheus scrapes
(one gap per stream per decode step), and from a thread that must not take a
registry lock per token.  A ``HistogramAccumulator`` is the loop-side half:
fixed buckets, counts and a sum, one writer, no lock.  The scrape-side half is
``MetricsRegistry._histogram_catch_up``, which raises the Prometheus histogram
to the accumulator's lifetime tallies by difference - the counter catch-up
idiom, bucket by bucket - so no observation is lost however rarely the
endpoint is scraped.
"""

from __future__ import annotations

from bisect import bisect_left
from typing import Dict, Sequence

LATENCY_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
)
HOST_LAG_BUCKETS = (0, 1, 2, 3, 4, 6, 8, 16, 32)
# seconds a request waits for a slot: from "admitted on arrival" to a closed
# loop's callers queueing for a whole prefill ahead of them
QUEUE_WAIT_BUCKETS = LATENCY_BUCKETS + (10.0, 30.0, 60.0)


class HistogramAccumulator:
    """Lifetime bucket counts (non-cumulative, the last one is +Inf) and sum
    of what one writer observed.  Readers take ``snapshot()`` from another
    thread: a torn read is at most one observation behind."""

    __slots__ = ("bounds", "counts", "sum")

    def __init__(self, bounds: Sequence[float]):
        self.bounds = tuple(float(b) for b in bounds)
        self.counts = [0] * (len(self.bounds) + 1)
        self.sum = 0.0

    def observe(self, value: float, weight: int = 1) -> None:
        """``weight`` equal observations of ``value`` (a fused step surfaces
        k steps of the same duration at one drain)."""
        self.counts[bisect_left(self.bounds, value)] += weight
        self.sum += value * weight

    @property
    def count(self) -> int:
        return sum(self.counts)

    def snapshot(self) -> Dict[str, object]:
        """``{"sum", "buckets": {index: count}}``: numbers under keys (not a
        list), so that a fleet's merge adds replicas bucket by bucket."""
        return {"sum": self.sum, "buckets": dict(enumerate(self.counts))}
