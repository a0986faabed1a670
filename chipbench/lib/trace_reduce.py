"""From a profiler trace to device busy time, idle share, per-operation
totals and attributed idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with JAX's own
``ProfileData``. Device planes are named ``/device:TPU:<n>``; the line
``XLA Ops`` holds one event per operation executed, ``XLA Modules`` one per
program. Host planes hold one line per thread, where the harness's
``TraceAnnotation`` spans (``client.suggest``, ``client.complete``) appear
by name. All planes share one clock (nanoseconds).
"""

from __future__ import annotations

import bisect
import glob
import gzip
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE_PREFIX = "/host:"
TOP = 10
_OPCODE = re.compile(r"(?:^|[ )])([a-z][a-z0-9_-]*)\(")


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as sorted, disjoint intervals."""
    out: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of merged ``busy`` inside [lo, hi]."""
    out, cursor = [], lo
    for a, b in busy:
        if a > cursor:
            out.append((cursor, min(a, hi)))
        cursor = max(cursor, b)
    if cursor < hi:
        out.append((cursor, hi))
    return [(a, b) for a, b in out if b > a]


def overlap(intervals: Sequence[Interval], lo: float, hi: float) -> float:
    return total(clip(intervals, lo, hi))


def attribute(gap: Interval, host: Dict[str, Sequence[Interval]]) -> str:
    """The host annotation whose (merged) spans cover most of ``gap``;
    "none" when none covers a quarter of it."""
    lo, hi = gap
    best, share = "none", 0.25
    for name, spans in host.items():
        covered = overlap(spans, lo, hi) / (hi - lo)
        if covered > share:
            best, share = name, covered
    return best


def reduce_intervals(
    device_ops: Dict[str, List[Tuple[str, float, float]]],
    host: Dict[str, Sequence[Interval]],
    lo: float,
    hi: float,
) -> Dict[str, object]:
    """``device_ops``: device name → (op name, start, end) in seconds;
    ``host``: annotation name → merged spans; the traced span is [lo, hi].
    Busy time is averaged over the devices; gaps are those of device 0."""
    per_device, totals = [], {}
    for ops in device_ops.values():
        busy = merge(clip(((a, b) for _, a, b in ops), lo, hi))
        per_device.append(busy)
        for name, a, b in ops:
            inside = min(b, hi) - max(a, lo)
            if inside > 0:
                totals[name] = totals.get(name, 0.0) + inside
    if not per_device:
        return {}
    busy_s = sum(total(b) for b in per_device) / len(per_device)
    by_label: Dict[str, float] = {}
    longest: List[Tuple[str, float]] = []
    for gap in gaps(per_device[0], lo, hi):
        label = attribute(gap, host)
        by_label[label] = by_label.get(label, 0.0) + (gap[1] - gap[0])
        longest.append((label, gap[1] - gap[0]))
    longest.sort(key=lambda g: -g[1])
    return {
        "busy_s": busy_s,
        "span_s": hi - lo,
        "device_ops": sorted(totals.items(), key=lambda kv: -kv[1])[:TOP],
        "idle_gaps": longest[:TOP],
        "idle_by_host_activity": by_label,
    }


def short_name(hlo: str, limit: int = 96) -> str:
    """An operation's name as XLA printed it, without its operand text:
    ``%fusion.12 = f32[8]{0} fusion(...), kind=kLoop`` → ``%fusion.12 fusion``."""
    head, _, rest = hlo.partition(" = ")
    if not rest:
        return hlo[:limit]
    # Shapes hold only upper-case calls (T(8,128), S(1)); the first
    # lower-case word before a "(" is the opcode.
    opcode = _OPCODE.search(rest)
    return f"{head} {opcode.group(1) if opcode else ''}".strip()[:limit]


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def read_xplane(path: str, annotations: Sequence[str]):
    """(device operations by device, host spans by annotation name, as
    recorded), times in seconds on the trace's clock."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):  # the committed test recording
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Tuple[str, float, float]]] = {}
    host: Dict[str, List[Interval]] = {name: [] for name in annotations}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            modules = sorted(
                (e.start_ns, e.start_ns + e.duration_ns, e.name.split("(")[0])
                for e in (lines[MODULES_LINE].events if MODULES_LINE in lines else ())
            )
            starts = [m[0] for m in modules]
            names: Dict[str, str] = {}
            ops = []
            for e in lines[OPS_LINE].events:
                i = bisect.bisect_right(starts, e.start_ns) - 1
                module = modules[i][2] if i >= 0 and e.start_ns < modules[i][1] else "?"
                if e.name not in names:
                    names[e.name] = short_name(e.name)
                ops.append(
                    (f"{module}:{names[e.name]}", e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9)
                )
            device_ops[plane.name] = ops
        elif plane.name.startswith(HOST_PLANE_PREFIX):
            for line in plane.lines:
                for e in line.events:
                    if e.name in host:
                        host[e.name].append(
                            (e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9)
                        )
    return device_ops, {name: sorted(spans) for name, spans in host.items()}


SPAN_ANNOTATION = "chipbench.traced"  # the harness's own span of the trace


def reduce_trace(path: str, annotations: Sequence[str]) -> Dict[str, object]:
    """The reduction of one recorded trace over the span the harness marked
    with ``SPAN_ANNOTATION``. Empty when the trace lacks the mark or any
    device operation. A host span that began before the trace did is not in
    it, so requests longer than the span name no gap."""
    device_ops, host = read_xplane(path, [SPAN_ANNOTATION, *annotations])
    marks = host.pop(SPAN_ANNOTATION)
    if not marks:
        return {}
    return reduce_intervals(
        device_ops, {name: merge(spans) for name, spans in host.items()}, marks[0][0], marks[-1][1]
    )


def idle_share(trace) -> Optional[float]:
    """Idle % of a reduced trace; None without one."""
    if not trace or not trace.get("span_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["span_s"])


def busy_ms_per_request(trace, seconds: float, requests: int) -> Optional[float]:
    """Device-busy ms per request: the traced span's busy share, taken as
    the window's, over the window's request rate (a span of a second or two
    holds too few completions to divide by its own)."""
    if not trace or not trace.get("span_s") or not requests:
        return None
    return 1e3 * trace["busy_s"] / trace["span_s"] * seconds / requests
