"""Client-seen suggest time beyond the service's own: gRPC, proto
conversion and any operation polling. Mean client latency minus the mean of
``vizier_suggest_latency_seconds{hop=service}`` over the same requests, in
ms (means, because both are exact from sums; the histogram's buckets are
30 % wide)."""

from chipbench.lib import reduce


def read(evidence):
    hist = evidence["histograms_window"].get("vizier_suggest_latency_seconds")
    latencies = evidence["latencies_ms"]
    if hist is None or not latencies:
        return None
    _, count, total = reduce.pooled(hist, "hop=service")
    if count == 0:
        return None
    return sum(latencies) / len(latencies) - total / count * 1e3
