"""How late the load generator sent a request, in ms: p95 of send time minus
due time over the window's requests, bucket-interpolated from the histogram an
open-loop generator keeps in the serving runtime's registry
(``chipbench_send_lag_seconds``; ``generators/open_poisson.py``). The lag is
inside every latency sample (a request is timed from the instant it was due),
so this says how much of the tail is the sender's own."""

from chipbench.lib import reduce


def read(evidence):
    hist = evidence["histograms_window"].get("chipbench_send_lag_seconds")
    if hist is None:
        return None
    counts, _, _ = reduce.pooled(hist)
    p95 = reduce.bucket_quantile(hist["bounds"], counts, 95)
    return None if p95 is None else p95 * 1e3
