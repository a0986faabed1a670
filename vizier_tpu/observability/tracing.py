"""Lightweight tracer: spans, contextvar nesting, cross-process propagation.

One trace follows a SuggestTrials request across all four hops — client RPC
→ Vizier service → Pythia dispatch (worker thread) → designer compute. The
active span lives in a ``contextvars.ContextVar`` so nesting is automatic
within a thread; across threads and processes the ``trace_id``/``span_id``
pair travels as a compact ``"<trace_id>-<span_id>"`` string in request
protos (``trace_context`` fields, see ``tools/regen_protos.py``) and is
re-attached with :meth:`Tracer.use_context`.

Timing is monotonic (``time.perf_counter`` for durations; ``time.time``
only stamps the start for human-readable export). Finished spans land in a
bounded ring buffer (no leak under sustained traffic) and can be dumped as
JSON lines — no third-party deps anywhere.

Every span is also a ``jax.profiler.TraceAnnotation`` of its name, on the
thread that runs it: with a profiler session active the span lands in the
trace's host planes, on the device trace's clock; with none active a TraceMe
costs under a microsecond. A **stage span** is a span whose name is in
:data:`STAGES` — the fixed vocabulary of what a served suggest spends its
host time on. On a clean exit it also observes its duration into ONE
histogram, ``vizier_suggest_stage_seconds{stage,path,per,phase}``, in the
registry the serving runtime bound with :meth:`Tracer.bind_registry` (none
bound: nothing observed). ``path`` is the span's ``path`` attribute: ``fused`` at the
sites inside a batch-executor flush, ``sequential`` everywhere else (the
service and policy stages run the same code whichever way the designer
computes); ``per`` is ``flush`` where a site runs once for all members of a
fused flush, else ``request``; ``phase`` says which device program a
``device.wait`` waited for — the span's ``stage`` attribute (``train`` /
``acquire``: the sequential and mesh paths time the two apart), ``flush`` for
a fused flush's one wait — and is empty on every other stage.
No bookkeeping of a span — annotation, histogram, export — can raise into
the request: a failure there is dropped and counted in
``vizier_tracing_errors_total``.

With observability off, :func:`get_tracer` returns the singleton
:data:`NOOP_TRACER` whose ``span()`` hands back a reusable no-op context
manager: no allocation, no contextvar write, no annotation, ≈ zero overhead.
"""

from __future__ import annotations

import collections
import contextvars
import json
import os
import random
import threading
import time
from typing import Any, Dict, List, Optional, Union

from vizier_tpu.observability import config as config_lib

# The stages of a served suggest (docs/guides/observability.md has the table
# of where each is opened). Once a request, except on the fused path
# ``flush.stack``, ``device.wait`` and the demux half of ``designer.decode``:
# once a flush (``per="flush"``).
STAGES = frozenset(
    {
        "service.read",  # study fetch, open-trial claim, the Pythia request
        "policy.load_trials",  # the delta read (stateless DesignerPolicy: both GetTrials) -> pyvizier
        "designer.update",  # new trials into the designer
        "designer.prepare",  # host encode / padding / RNG before the device
        "flush.stack",  # host re-stack + upload of a fused flush's members
        "device.wait",  # host blocked on the chip
        "designer.decode",  # device results -> suggestions with metadata
        "service.write",  # create_trial x count, metadata deltas, the op
    }
)
STAGE_HISTOGRAM = "vizier_suggest_stage_seconds"
ERRORS_COUNTER = "vizier_tracing_errors"  # rendered with the _total suffix
PATH_SEQUENTIAL = "sequential"
PATH_FUSED = "fused"
# How often a stage site runs: once a request, or once a fused flush for
# all of its members (the readers divide the two differently).
PER_REQUEST = "request"
PER_FLUSH = "flush"
# The attributes of a stage site that runs once a fused flush.
FUSED_FLUSH = {"path": PATH_FUSED, "per": PER_FLUSH}
DEVICE_WAIT = "device.wait"
PHASE_FLUSH = "flush"  # the ``phase`` of a fused flush's one ``device.wait``

# jax.profiler.TraceAnnotation, imported on the first span: None = not yet
# looked for, False = not importable (a stdlib-only process).
_annotation_cls: Any = None


def _trace_annotation_cls():
    global _annotation_cls
    if _annotation_cls is None:
        try:
            from jax.profiler import TraceAnnotation

            _annotation_cls = TraceAnnotation
        except Exception:
            _annotation_cls = False
    return _annotation_cls


# The active span (or a remote SpanContext attached via use_context).
_SPAN_VAR: contextvars.ContextVar = contextvars.ContextVar(
    "vizier_tpu_active_span", default=None
)


class SpanContext:
    """The propagatable identity of a span: (trace_id, span_id)."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str):
        self.trace_id = trace_id
        self.span_id = span_id

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, SpanContext)
            and self.trace_id == other.trace_id
            and self.span_id == other.span_id
        )

    def __repr__(self) -> str:
        return f"SpanContext({self.trace_id!r}, {self.span_id!r})"


def format_context(ctx: Optional[SpanContext]) -> str:
    """Wire form for request metadata; '' when there is nothing to carry."""
    if ctx is None:
        return ""
    return f"{ctx.trace_id}-{ctx.span_id}"


def parse_context(wire: str) -> Optional[SpanContext]:
    """Inverse of :func:`format_context`; malformed input degrades to None
    (a bad header must never fail the request it rides on)."""
    if not wire or "-" not in wire:
        return None
    trace_id, _, span_id = wire.rpartition("-")
    if not trace_id or not span_id:
        return None
    return SpanContext(trace_id, span_id)


# Span/trace ids only need collision-resistance, not UUID semantics; a
# process-seeded Mersenne generator is ~10x cheaper than uuid4 per id, and
# id minting sits on every traced hop of the suggest hot path (measured 6
# ids per served trial). getrandbits is one atomic C call — thread-safe
# under the GIL.
_ID_RNG = random.Random(os.urandom(16))


def _new_trace_id() -> str:
    return f"{_ID_RNG.getrandbits(128):032x}"


def _new_span_id() -> str:
    return f"{_ID_RNG.getrandbits(64):016x}"


class Span:
    """One timed operation; mutable until :meth:`end`."""

    __slots__ = (
        "name",
        "trace_id",
        "span_id",
        "parent_id",
        "attributes",
        "events",
        "links",
        "status",
        "start_time",
        "duration_secs",
        "_t0",
    )

    def __init__(
        self,
        name: str,
        trace_id: str,
        span_id: str,
        parent_id: Optional[str],
        attributes: Optional[Dict[str, Any]] = None,
    ):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.attributes: Dict[str, Any] = dict(attributes or {})
        self.events: List[Dict[str, Any]] = []
        self.links: List[Dict[str, str]] = []
        self.status = "ok"
        self.start_time = time.time()
        self.duration_secs: Optional[float] = None
        self._t0 = time.perf_counter()

    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id)

    def set_attribute(self, key: str, value: Any) -> None:
        self.attributes[key] = value

    def add_event(self, name: str, **attributes: Any) -> None:
        self.events.append(
            {
                "name": name,
                "offset_secs": time.perf_counter() - self._t0,
                **({"attributes": attributes} if attributes else {}),
            }
        )

    def add_link(self, ctx: Optional[SpanContext], name: str = "") -> None:
        """Associates another span (e.g. a coalesced leader's computation)
        without making it a parent."""
        if ctx is None:
            return
        link = {"trace_id": ctx.trace_id, "span_id": ctx.span_id}
        if name:
            link["name"] = name
        self.links.append(link)

    def record_exception(self, error: BaseException) -> None:
        self.status = "error"
        self.attributes.setdefault("error.type", type(error).__name__)
        self.attributes.setdefault("error.message", str(error)[:500])

    def end(self) -> None:
        if self.duration_secs is None:
            self.duration_secs = time.perf_counter() - self._t0

    def to_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "start_time": self.start_time,
            "duration_secs": self.duration_secs,
            "status": self.status,
        }
        if self.attributes:
            out["attributes"] = self.attributes
        if self.events:
            out["events"] = self.events
        if self.links:
            out["links"] = self.links
        return out


class _NoopSpan:
    """Absorbs the whole Span API; one shared instance, zero state."""

    __slots__ = ()

    def context(self):
        return None

    def set_attribute(self, key, value):
        pass

    def add_event(self, name, **attributes):
        pass

    def add_link(self, ctx, name=""):
        pass

    def record_exception(self, error):
        pass

    def end(self):
        pass

    def to_dict(self):
        return {}


NOOP_SPAN = _NoopSpan()


class _NoopSpanCM:
    """Reusable no-op context manager — ``span()`` off the hot path."""

    __slots__ = ()

    def __enter__(self):
        return NOOP_SPAN

    def __exit__(self, *exc):
        return False


_NOOP_CM = _NoopSpanCM()


class _SpanCM:
    """Context manager for one active span (cheaper than a generator CM)."""

    __slots__ = ("_tracer", "_span", "_token", "_annotation")

    def __init__(self, tracer: "Tracer", span: Span):
        self._tracer = tracer
        self._span = span
        self._token = None
        self._annotation = None

    def __enter__(self) -> Span:
        self._token = _SPAN_VAR.set(self._span)
        self._annotation = self._tracer._annotate(self._span.name)
        return self._span

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._token is not None:
            _SPAN_VAR.reset(self._token)
        if exc is not None:
            self._span.record_exception(exc)
        self._span.end()
        self._tracer._finish(self._span, self._annotation)
        return False


class _ContextCM:
    """Attaches a remote SpanContext as the ambient parent for a block."""

    __slots__ = ("_ctx", "_token")

    def __init__(self, ctx: Optional[SpanContext]):
        self._ctx = ctx
        self._token = None

    def __enter__(self):
        if self._ctx is not None:
            self._token = _SPAN_VAR.set(self._ctx)
        return self._ctx

    def __exit__(self, *exc):
        if self._token is not None:
            _SPAN_VAR.reset(self._token)
        return False


Parent = Union[Span, SpanContext, None]


class Tracer:
    """Creates spans, tracks the active one, rings finished ones."""

    enabled = True

    def __init__(
        self,
        max_spans: int = 4096,
        export_path: Optional[str] = None,
    ):
        self._lock = threading.Lock()
        self._finished: "collections.deque[Span]" = collections.deque(
            maxlen=max(1, max_spans)
        )
        self._export_path = export_path or None
        self._export_file = None
        self._stage_seconds = None  # the bound registry's stage histogram
        self._errors = None  # ... and its dropped-bookkeeping counter

    def bind_registry(self, registry: Any) -> None:
        """The registry stage spans observe into and bookkeeping failures
        are counted in: the serving runtime's, handed over when it is built
        (the last runtime built in a process wins)."""
        self._stage_seconds = registry.histogram(
            STAGE_HISTOGRAM,
            help="Host wall time of one stage of a served suggest "
            "(stage spans; path=fused inside a batch-executor flush).",
        )
        self._errors = registry.counter(
            ERRORS_COUNTER,
            help="Span bookkeeping failures dropped instead of raised "
            "into the request.",
        )

    # -- span lifecycle ----------------------------------------------------

    def span(self, name: str, parent: Parent = None, **attributes: Any) -> _SpanCM:
        """Context manager: opens a child of ``parent`` (default: the
        ambient span/context), makes it current, exports it on exit."""
        if parent is None:
            parent = _SPAN_VAR.get()
        if isinstance(parent, Span):
            trace_id, parent_id = parent.trace_id, parent.span_id
        elif isinstance(parent, SpanContext):
            trace_id, parent_id = parent.trace_id, parent.span_id
        else:
            trace_id, parent_id = _new_trace_id(), None
        span = Span(name, trace_id, _new_span_id(), parent_id, attributes)
        return _SpanCM(self, span)

    def use_context(self, ctx: Optional[SpanContext]) -> _ContextCM:
        """Re-attaches a propagated context (thread hop / wire hop)."""
        return _ContextCM(ctx)

    def current_span(self) -> Optional[Span]:
        cur = _SPAN_VAR.get()
        return cur if isinstance(cur, Span) else None

    def current_context(self) -> Optional[SpanContext]:
        cur = _SPAN_VAR.get()
        if isinstance(cur, Span):
            return cur.context()
        if isinstance(cur, SpanContext):
            return cur
        return None

    # -- bookkeeping that must never raise into the request ----------------

    def _dropped(self) -> None:
        try:
            if self._errors is not None:
                self._errors.inc()
        except Exception:
            pass

    def _annotate(self, name: str) -> Any:
        """Enters a profiler annotation of ``name``; None when there is no
        profiler to annotate for (or entering it failed)."""
        try:
            cls = _trace_annotation_cls()
            if not cls:
                return None
            annotation = cls(name)
            annotation.__enter__()
            return annotation
        except Exception:
            self._dropped()
            return None

    def _finish(self, span: Span, annotation: Any) -> None:
        if annotation is not None:
            try:
                annotation.__exit__(None, None, None)
            except Exception:
                self._dropped()
        # A stage whose body raised is not a sample of that stage's time.
        if (
            self._stage_seconds is not None
            and span.name in STAGES
            and span.status == "ok"
        ):
            try:
                per = span.attributes.get("per", PER_REQUEST)
                phase = ""
                if span.name == DEVICE_WAIT:
                    phase = span.attributes.get("stage") or (
                        PHASE_FLUSH if per == PER_FLUSH else ""
                    )
                self._stage_seconds.observe(
                    span.duration_secs,
                    stage=span.name,
                    path=span.attributes.get("path", PATH_SEQUENTIAL),
                    per=per,
                    phase=phase,
                )
            except Exception:
                self._dropped()
        try:
            self._export(span)
        except Exception:
            self._dropped()

    # -- export ------------------------------------------------------------

    def _export(self, span: Span) -> None:
        with self._lock:
            self._finished.append(span)
            if self._export_path is not None:
                try:
                    if self._export_file is None:
                        self._export_file = open(self._export_path, "a")
                    self._export_file.write(json.dumps(span.to_dict()) + "\n")
                    self._export_file.flush()
                except OSError:
                    self._export_path = None  # sink gone; keep serving

    def finished_spans(self) -> List[Span]:
        with self._lock:
            return list(self._finished)

    def drain(self) -> List[Span]:
        """Pops and returns every finished span (oldest first)."""
        with self._lock:
            out = list(self._finished)
            self._finished.clear()
        return out

    def spans_for_trace(self, trace_id: str) -> List[Span]:
        """One trace's finished spans, ordered by start time."""
        return sorted(
            (s for s in self.finished_spans() if s.trace_id == trace_id),
            key=lambda s: s.start_time,
        )

    def dump_jsonl(self, path: str) -> int:
        """Writes the ring buffer to ``path`` as JSON lines; returns count."""
        spans = self.finished_spans()
        with open(path, "w") as f:
            for span in spans:
                f.write(json.dumps(span.to_dict()) + "\n")
        return len(spans)

    def close(self) -> None:
        with self._lock:
            if self._export_file is not None:
                try:
                    self._export_file.close()
                finally:
                    self._export_file = None


class NoopTracer:
    """The off switch: same API, no state, no allocation per span."""

    enabled = False

    def bind_registry(self, registry: Any) -> None:
        pass

    def span(self, name: str, parent: Parent = None, **attributes: Any):
        return _NOOP_CM

    def use_context(self, ctx):
        return _NOOP_CM

    def current_span(self):
        return None

    def current_context(self):
        return None

    def finished_spans(self):
        return []

    def drain(self):
        return []

    def spans_for_trace(self, trace_id: str):
        return []

    def dump_jsonl(self, path: str) -> int:
        return 0

    def close(self) -> None:
        pass


NOOP_TRACER = NoopTracer()

_global_tracer: Optional[Union[Tracer, NoopTracer]] = None
_global_lock = threading.Lock()


def _tracer_from_config(
    config: config_lib.ObservabilityConfig,
) -> Union[Tracer, NoopTracer]:
    if not config.tracing_on:
        return NOOP_TRACER
    return Tracer(
        max_spans=config.span_buffer_size,
        export_path=config.span_log_path or None,
    )


def get_tracer() -> Union[Tracer, NoopTracer]:
    """The process-global tracer, built from the env config on first use."""
    global _global_tracer
    tracer = _global_tracer
    if tracer is None:
        with _global_lock:
            if _global_tracer is None:
                _global_tracer = _tracer_from_config(
                    config_lib.ObservabilityConfig.from_env()
                )
            tracer = _global_tracer
    return tracer


def set_tracer(
    tracer: Optional[Union[Tracer, NoopTracer]],
) -> Optional[Union[Tracer, NoopTracer]]:
    """Swaps the global tracer (tests/tools); None re-derives from env on
    next use. Returns the previous tracer."""
    global _global_tracer
    with _global_lock:
        old, _global_tracer = _global_tracer, tracer
    return old


def add_current_event(name: str, **attributes: Any) -> None:
    """Adds an event to the active span, if any (deep-callee convenience —
    e.g. breaker transitions firing inside a designer computation)."""
    span = get_tracer().current_span()
    if span is not None:
        span.add_event(name, **attributes)
