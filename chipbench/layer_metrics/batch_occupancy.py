"""Mean real (unpadded) slots per batch-executor flush in the window
(``vizier_batch_occupancy``: sum / count over every bucket)."""

from chipbench.lib import reduce


def read(evidence):
    hist = evidence["histograms_window"].get("vizier_batch_occupancy")
    if hist is None:
        return None
    _, count, total = reduce.pooled(hist)
    return total / count if count else None
