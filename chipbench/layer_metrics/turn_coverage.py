"""``stage_coverage``'s share with the wait for the study's turn counted
in, %: (Σ every stage + Σ ``vizier_batch_queue_wait_seconds`` + Σ
``vizier_study_turn_wait_seconds``) ÷ Σ
``vizier_suggest_latency_seconds{hop=service}``. The honesty metric where
most of a suggest is that wait, which is no stage: well under 100 means a
stretch of a suggest still has no name. None from a program without turns."""

from chipbench.layer_metrics import stage_coverage
from chipbench.lib import reduce


def read(evidence):
    wait = evidence["histograms_window"].get("vizier_study_turn_wait_seconds")
    staged = stage_coverage.read(evidence)
    if wait is None or staged is None:
        return None
    service_seconds = reduce.pooled(evidence["histograms_window"]["vizier_suggest_latency_seconds"], "hop=service")[2]
    return staged + 100.0 * reduce.pooled(wait)[2] / service_seconds
