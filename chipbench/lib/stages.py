"""Reading the program's stage spans: ``vizier_suggest_stage_seconds``.

One histogram in the serving runtime's registry, one series per (``stage``,
``path``, ``per``): the host wall time of each named stage of a served
suggest (``vizier_tpu/observability/tracing.py`` ``STAGES``). ``per=request``
series are observed once a request, ``per=flush`` series once a fused flush
for all of its members. A program without the histogram (a parent commit
from before it) gives every reader here None.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional, Tuple

HISTOGRAM = "vizier_suggest_stage_seconds"
PER_FLUSH = "flush"
# One per request, on either path, before anything can fail: the divisor.
REQUEST_STAGE = "service.read"
# ``tracing.STAGES`` in a request's order (checked against the program's in
# ``tests/chipbench/test_harness.py``), then the three other host spans a
# traced run's idle gaps are named after: a waiter parked in the executor,
# the worker completing its trials, and the worker's own pause between two
# requests (the traffic's ``think_ms``). Leaves only: ``client.suggest``
# covers all of them, and a gap goes to the name that covers most of it.
STAGE_NAMES = (
    "service.read", "policy.load_trials", "designer.update", "designer.prepare",
    "flush.stack", "device.wait", "designer.decode", "service.write",
)
THINK = "client.think"  # the span every generator sleeps its think time in
GAP_ANNOTATIONS = (*STAGE_NAMES, "batch_executor.queue_wait", "client.complete", THINK)


def series(evidence: Dict[str, Any]) -> Optional[Dict[Tuple[str, str], Tuple[int, float]]]:
    """(stage, per) → (count, summed seconds) gained over the window, the
    paths pooled; None when the program has no such histogram."""
    hist = evidence["histograms_window"].get(HISTOGRAM)
    if hist is None:
        return None
    out: Dict[Tuple[str, str], Tuple[int, float]] = {}
    for label, (_, count, total) in hist["series"].items():
        labels = dict(part.split("=", 1) for part in label.split(",") if "=" in part)
        key = (labels.get("stage", ""), labels.get("per", "request"))
        n0, s0 = out.get(key, (0, 0.0))
        out[key] = (n0 + count, s0 + total)
    return out


def requests(found: Dict[Tuple[str, str], Tuple[int, float]]) -> int:
    return found.get((REQUEST_STAGE, "request"), (0, 0.0))[0]


def seconds(found, stages: Iterable[str], per: Optional[str] = None) -> Optional[float]:
    """Summed seconds of ``stages`` (of one ``per``, or of both); None when
    one of the stages has no sample at all."""
    total = 0.0
    for stage in stages:
        rows = [v for (s, p), v in found.items() if s == stage and per in (None, p) and v[0] > 0]
        if not rows:
            return None
        total += sum(v[1] for v in rows)
    return total


def mean_ms_per_request(evidence: Dict[str, Any], stages: Iterable[str]) -> Optional[float]:
    """Σ the stages' seconds ÷ requests, in ms: what one request costs in
    these stages (a flush's stages are shared out over the window's requests)."""
    found = series(evidence)
    if found is None or requests(found) == 0:
        return None
    total = seconds(found, stages)
    return None if total is None else total / requests(found) * 1e3
