"""Tracing/profiling: timers, runtime decorators, and retrace beacons.

Parity with ``/root/reference/vizier/utils/profiler.py`` (global event
storage ``:68-121``, ``collect_events`` ``:138``, ``timeit`` ``:156``,
``record_runtime`` ``:213`` with ``block_until_ready`` for async accelerator
dispatch, ``record_tracing`` ``:291``). Retraces are the #1 perf bug in the
JAX layer; ``record_tracing`` makes them visible.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import datetime
import functools
import threading
import time
from typing import Any, Callable, Dict, List, Optional


@dataclasses.dataclass(frozen=True)
class ProfileEvent:
    name: str
    kind: str  # 'latency' | 'tracing'
    duration_secs: float
    timestamp: float


class _Storage:
    def __init__(self):
        self._lock = threading.Lock()
        self._events: List[ProfileEvent] = []
        self._enabled = False
        self._scope: List[str] = []

    def add(self, event: ProfileEvent) -> None:
        with self._lock:
            if self._enabled:
                self._events.append(event)

    def scoped_name(self, name: str) -> str:
        with self._lock:
            return "::".join(self._scope + [name])

    @contextlib.contextmanager
    def push_scope(self, name: str):
        with self._lock:
            self._scope.append(name)
        try:
            yield
        finally:
            with self._lock:
                self._scope.pop()

    @contextlib.contextmanager
    def collect(self):
        with self._lock:
            self._enabled = True
            self._events = []
        try:
            yield self._events
        finally:
            with self._lock:
                self._enabled = False


_storage = _Storage()


def collect_events():
    """Context manager enabling collection; yields the event list."""
    return _storage.collect()


@contextlib.contextmanager
def timeit(name: str, also_log: bool = False):
    """Times a block (nested scopes join with ``::``) into the event
    store. The served path's spans are the stage spans of
    ``observability/tracing.py``, opened at the same sites."""
    full = _storage.scoped_name(name)
    start = time.perf_counter()
    with _storage.push_scope(name):
        yield
    duration = time.perf_counter() - start
    _storage.add(
        ProfileEvent(name=full, kind="latency", duration_secs=duration, timestamp=time.time())
    )
    if also_log:
        import logging

        logging.getLogger(__name__).info("%s took %.3fs", full, duration)


def record_runtime(
    fn: Optional[Callable] = None,
    *,
    name_prefix: str = "",
    name: str = "",
    also_log: bool = False,
    block_until_ready: bool = False,
):
    """Decorator recording a function's wall time.

    ``block_until_ready=True`` waits for async accelerator dispatch so the
    recorded time covers device execution, not just tracing/enqueue.
    """

    def decorator(func: Callable) -> Callable:
        label = "::".join(x for x in (name_prefix, name or func.__qualname__) if x)

        @functools.wraps(func)
        def wrapper(*args, **kwargs):
            with timeit(label, also_log=also_log):
                out = func(*args, **kwargs)
                if block_until_ready:
                    import jax

                    out = jax.block_until_ready(out)
            return out

        return wrapper

    if fn is not None:
        return decorator(fn)
    return decorator


def record_tracing(fn: Optional[Callable] = None, *, name: str = ""):
    """Decorator that logs a 'tracing' event each time the body is traced.

    Wrap the *traced* function (the one passed to jit): each execution of
    the python body is a (re)trace — frequent events mean the jit cache is
    missing (shape instability), the top perf bug to hunt.
    """

    def decorator(func: Callable) -> Callable:
        label = name or func.__qualname__

        @functools.wraps(func)
        def wrapper(*args, **kwargs):
            _storage.add(
                ProfileEvent(
                    name=label, kind="tracing", duration_secs=0.0, timestamp=time.time()
                )
            )
            return func(*args, **kwargs)

        return wrapper

    if fn is not None:
        return decorator(fn)
    return decorator


def get_latencies_dict(
    events: List[ProfileEvent],
) -> Dict[str, List[datetime.timedelta]]:
    out: Dict[str, List[datetime.timedelta]] = collections.defaultdict(list)
    for e in events:
        if e.kind == "latency":
            out[e.name].append(datetime.timedelta(seconds=e.duration_secs))
    return dict(out)


def get_tracing_counts(events: List[ProfileEvent]) -> Dict[str, int]:
    out: Dict[str, int] = collections.defaultdict(int)
    for e in events:
        if e.kind == "tracing":
            out[e.name] += 1
    return dict(out)
