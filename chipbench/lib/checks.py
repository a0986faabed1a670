"""What a sound run returns, checked here and not read off a log.

``check_batch`` is copied from ``chip_smoke.py`` (PR 21). The counters
that must stay 0 (``chip_smoke.py`` ``ZERO_COUNTERS``, plus the batch
executor's silent reruns and the sparse serve) are the configuration
file's ``zero_counters``."""

from __future__ import annotations

import math
from typing import Any, List, Sequence

# reliability/fallback.py stamps ns "reliability", key "fallback".
FALLBACK_NAMESPACE, FALLBACK_KEY = "reliability", "fallback"


def judge(value: Any, limit: Any) -> bool:
    """A limit is a ceiling, or ``{"min": floor, "max": ceiling}`` (either
    or both) for a number that has a floor."""
    if isinstance(limit, dict):
        return limit.get("min", -math.inf) <= value <= limit.get("max", math.inf)
    return value <= limit


def check_batch(rows: Sequence[Sequence[float]], metadata: Sequence[Any], count: int) -> List[str]:
    """Failures of one returned batch (empty = valid): ``rows`` are the
    suggested parameter rows, ``metadata`` each suggestion's metadata."""
    failures = []
    if len(rows) != count:
        failures.append(f"returned {len(rows)} suggestions, wanted {count}")
    for i, values in enumerate(rows):
        if not all(isinstance(v, float) and math.isfinite(v) for v in values):
            failures.append(f"suggestion {i}: non-finite parameter value")
        elif not all(0.0 <= v <= 1.0 for v in values):
            failures.append(f"suggestion {i}: parameter outside [0, 1]")
    for i, md in enumerate(metadata):
        if md.ns(FALLBACK_NAMESPACE).get(FALLBACK_KEY) is not None:
            failures.append(f"suggestion {i}: carries the reliability fallback stamp")
    if len({tuple(r) for r in rows}) != len(rows):
        failures.append("two suggestions of the batch are identical")
    return failures
