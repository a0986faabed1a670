"""Reading what the program says of the device half of a suggest.

Two sources, both the program's own and both in every untraced run. (1) The
stage histogram's ``phase`` label on ``device.wait``
(``vizier_tpu/observability/tracing.py``): ``train`` / ``acquire`` where the
sequential and mesh paths wait for the two device programs apart, ``flush``
for a fused flush's one wait. (2) Five counters of ``serving_stats()``
(``vizier_tpu/serving/stats.py`` ``train_*``): what each timed ARD train
program counted of its own batched L-BFGS loop. A program without the label
or the counters (a parent commit from before them) gives every reader here
None, and so does a window in which nothing trained.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from chipbench.lib import stages

TRAIN_COUNTERS = (
    "train_programs", "train_loop_trips", "train_row_trips",
    "train_row_iterations", "train_evaluations",
)


def wait_ms(evidence: Dict[str, Any], phase: str) -> Optional[float]:
    """Σ seconds of ``device.wait{phase}`` ÷ the window's requests, in ms
    (``stages.requests``: the divisor of ``device_wait_ms``, so the phases
    of a cell add up to it); None where the window has no such sample."""
    found = stages.series(evidence)
    if found is None or stages.requests(found) == 0:
        return None
    count, total = 0, 0.0
    for label, (_, n, seconds) in evidence["histograms_window"][stages.HISTOGRAM]["series"].items():
        labels = dict(part.split("=", 1) for part in label.split(",") if "=" in part)
        if labels.get("stage") == "device.wait" and labels.get("phase") == phase:
            count, total = count + n, total + seconds
    return total / stages.requests(found) * 1e3 if count else None


def train_counters(evidence: Dict[str, Any]) -> Optional[Dict[str, int]]:
    """The window's five ``train_*`` counters; None where the program has
    none of them or no train program was counted."""
    stats = evidence["stats_window"]
    if any(name not in stats for name in TRAIN_COUNTERS) or not stats["train_programs"]:
        return None
    return {name: stats[name] for name in TRAIN_COUNTERS}
