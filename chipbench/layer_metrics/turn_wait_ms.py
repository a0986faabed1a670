"""Median time a suggest waited for its study's turn, in ms
(``vizier_study_turn_wait_seconds``, bucket-interpolated): arrival at the
service to the moment no earlier request of the same study is being served."""

from chipbench.lib import reduce


def read(evidence):
    hist = evidence["histograms_window"].get("vizier_study_turn_wait_seconds")
    if hist is None:
        return None
    counts, _, _ = reduce.pooled(hist)
    p50 = reduce.bucket_quantile(hist["bounds"], counts, 50)
    return None if p50 is None else p50 * 1e3
