"""Arithmetic from samples, counters and histogram deltas to metrics."""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple


def percentile(samples: Sequence[float], q: float) -> float:
    """Nearest-rank percentile ``q`` in (0, 100] of a non-empty sample."""
    ordered = sorted(samples)
    return ordered[max(0, math.ceil(q / 100.0 * len(ordered)) - 1)]


def counter_delta(after: Dict[str, int], before: Dict[str, int]) -> Dict[str, int]:
    return {k: after[k] - before.get(k, 0) for k in after}


def histogram_delta(after, before):
    """Per histogram and series: (bucket counts, count, sum) gained between
    two ``program.Server.histograms()`` snapshots."""
    out = {}
    for name, hist in after.items():
        earlier = before.get(name, {"series": {}})["series"]
        series = {}
        for label, (counts, count, total) in hist["series"].items():
            c0, n0, s0 = earlier.get(label, ([0] * len(counts), 0, 0.0))
            series[label] = ([a - b for a, b in zip(counts, c0)], count - n0, total - s0)
        out[name] = {"bounds": hist["bounds"], "series": series}
    return out


def pooled(hist, label_prefix: str = "") -> Tuple[List[int], int, float]:
    """One histogram's series whose label starts with ``label_prefix``,
    summed: (bucket counts, count, sum)."""
    counts = [0] * (len(hist["bounds"]) + 1)
    count, total = 0, 0.0
    for label, (c, n, s) in hist["series"].items():
        if label.startswith(label_prefix):
            counts = [a + b for a, b in zip(counts, c)]
            count, total = count + n, total + s
    return counts, count, total


def bucket_quantile(bounds: Sequence[float], counts: Sequence[int], q: float) -> Optional[float]:
    """Quantile ``q`` in [0, 100] interpolated inside the crossing bucket
    (``observability/metrics.py`` ``Histogram.percentile``'s arithmetic, on
    a delta); None when empty."""
    total = sum(counts)
    if total == 0:
        return None
    rank, cumulative = q / 100.0 * total, 0
    for i, c in enumerate(counts):
        if c > 0 and cumulative + c >= rank:
            if i >= len(bounds):
                return bounds[-1]
            lo = bounds[i - 1] if i > 0 else 0.0
            return lo + (bounds[i] - lo) * min(max((rank - cumulative) / c, 0.0), 1.0)
        cumulative += c
    return bounds[-1]
