"""Share of the service-side suggest time that the named stages account
for, %: (Σ every stage + Σ ``vizier_batch_queue_wait_seconds``) ÷ Σ
``vizier_suggest_latency_seconds{hop=service}`` over the window. A per-flush
stage is waited for by every member of its flush, so its seconds count once
per member: × the window's ``batched_suggests`` ÷ its fused flushes. Well
under 100 means a stretch of a suggest still has no name."""

from chipbench.lib import reduce
from chipbench.lib import stages


def read(evidence):
    found = stages.series(evidence)
    latency = evidence["histograms_window"].get("vizier_suggest_latency_seconds")
    if found is None or latency is None or stages.requests(found) == 0:
        return None
    _, served, service_seconds = reduce.pooled(latency, "hop=service")
    if served == 0 or service_seconds <= 0:
        return None
    covered = sum(total for (_, per), (_, total) in found.items() if per != stages.PER_FLUSH)
    flushes = found.get(("flush.stack", stages.PER_FLUSH), (0, 0.0))[0]
    if flushes:
        members = evidence.get("stats_window", {}).get("batched_suggests", 0) / flushes
        covered += members * sum(total for (_, per), (_, total) in found.items() if per == stages.PER_FLUSH)
    queue = evidence["histograms_window"].get("vizier_batch_queue_wait_seconds")
    if queue is not None:
        covered += reduce.pooled(queue)[2]
    return 100.0 * covered / service_seconds
