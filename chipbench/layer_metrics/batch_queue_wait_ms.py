"""Median time a slot waited in the batch executor's queue before its
flush, in ms (``vizier_batch_queue_wait_seconds``, bucket-interpolated)."""

from chipbench.lib import reduce


def read(evidence):
    hist = evidence["histograms_window"].get("vizier_batch_queue_wait_seconds")
    if hist is None:
        return None
    counts, _, _ = reduce.pooled(hist)
    p50 = reduce.bucket_quantile(hist["bounds"], counts, 50)
    return None if p50 is None else p50 * 1e3
