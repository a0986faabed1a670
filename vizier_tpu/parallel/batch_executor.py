"""Cross-study continuous batching: N same-shape studies, ONE device program.

The LLM-inference-server pattern applied to suggestion serving. Every
study's GP-bandit computation is a small same-shape program — the padding
schedule (``converters.padding``) quantizes trials/features into a small
grid of ``(pad_trials, cont_width, cat_width)`` buckets by construction —
so concurrent designer computations from *different* studies can be
collected into shape-bucket queues and executed as one ``jax.vmap``-ed
dispatch over a leading study axis (the registered programs' flush
bodies, e.g. ``gp_ucb_pe._ucb_pe_flush_program``). That replaces N
dispatches that each leave the MXU idle between kernel launches with one
dispatch of N-fold work.

Scheduling is a bounded micro-batch window: a bucket flushes when it
reaches ``max_batch_size`` slots ("full") or when its oldest slot has
waited ``max_wait_ms`` ("timeout"), so single-study latency is bounded by
the window. Partial batches are padded to ``max_batch_size`` with copies
of slot 0 that are dropped at demux — one compiled program shape per
bucket regardless of occupancy. A batch of one takes the ordinary
sequential designer path (bit-identical to batching off when there is no
concurrency).

Fail isolation: a slot whose host-side ``prepare`` raises is dropped
from the batch before the device program runs; a device-program failure
falls every slot back to its own sequential ``suggest`` (errors stay
per-slot); a slot whose decoded suggestions contain non-finite parameters
gets a typed ``TRANSIENT:`` error. In all three cases the error surfaces
only to that study's waiter, which hands it to the existing reliability
path (retry / circuit breaker / quasi-random fallback) — batchmates are
never poisoned.

Mesh execution plane (``parallel.mesh``, opt-in ``VIZIER_MESH=1``): the
process's devices are carved into placements (1-D submeshes); each bucket
is sticky-assigned to one placement and DIFFERENT buckets execute
concurrently on per-placement worker threads instead of serializing
through the scheduler (which keeps sole ownership of flush *forming* —
windows, lanes, ordering). A flush dispatched to a multi-device placement
is sharded over its study axis (``DevicePlacement.shard``) so one fused
program spans the placement's devices, and every placement pads flushes
at shard granularity (``DevicePlacement.pad_to``: the next power-of-two
multiple of its device count) instead of the single-device executor's
flat pad-to-``max_batch_size`` — a low-occupancy flush no longer computes
``max_batch_size`` padded slots. ``VIZIER_MESH=0`` (default) never builds
placements: single scheduler thread, one device, bit-identical seed path.

Priority lanes (N-lane): every slot rides a named :class:`LaneSpec` lane.
The default table has two — ``live`` (priority 0) and ``speculative``
(priority 1, deferrable): slots submitted with ``speculative=True`` (the
serving tier's background pre-compute, ``vizier_tpu.serving.speculative``)
ride a live flush that is forming anyway, but a bucket holding ONLY
deferrable-lane slots waits for the idle window — it never becomes due
while a lower-priority-number slot is queued in any bucket (bounded by the
lane's ``starvation_cap_ms`` so a live request coalesced onto an in-flight
speculative compute cannot starve), and due batches execute in lane-
priority order. New QoS classes are one more LaneSpec, not a scheduler
rewrite. ``queue_depth()`` / ``live_pending()`` expose per-lane occupancy
so the speculative admission gate can refuse to enqueue under live
saturation.

Weighted fair share (opt-in via the admission controller,
``VIZIER_ADMISSION=1``): inside the live lane, slots carry the tenant the
admission gate admitted (``serving.admission.current_tenant()``), and when
a bucket holds more queued work than one flush, deficit-round-robin
selection across tenants — quantum = the tenant's configured weight —
decides who flushes first instead of FIFO, so a hot tenant cannot
monopolize flush slots: a continuously-hot tenant can delay a light
tenant's first slot by at most one DRR round (the sum of the other
tenants' quanta). Due same-priority batches are likewise ordered by
weighted served-slot counts across buckets. With admission off (the
default) no tenant is attached and selection is exactly the seed FIFO —
bit-identical scheduling.

Batchable designers implement ONE :class:`~vizier_tpu.compute.ir.
DesignerProgram` (bucket_key / prepare / device_program / finalize),
registered in :mod:`vizier_tpu.compute.registry`; the executor resolves a
designer's program there and consumes it generically — the same registry
feeds the prewarm walker, chaos slot-isolation wrappers,
the ``device.wait`` stage span's ``phase``, and the speculative lane.
A wrapper designer hands its program over through a ``compute_program``
hook; a designer with neither a hook nor a registered program runs
sequentially.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import threading
import time
from typing import Any, Callable, Deque, Dict, List, Optional, Sequence, Tuple

from vizier_tpu.compute import ir as compute_ir
from vizier_tpu.compute import registry as compute_registry
from vizier_tpu.observability import flight_recorder as recorder_lib
from vizier_tpu.observability import metrics as metrics_lib
from vizier_tpu.observability import tracing as tracing_lib
from vizier_tpu.reliability import errors as errors_lib

# Canonical home is the compute IR (vizier_tpu.compute.ir.BucketKey);
# re-exported here for the executor's existing import surface.
BucketKey = compute_ir.BucketKey


class BatchSlotError(errors_lib.TransientError):
    """A batched slot produced an invalid result (isolated to its study)."""


@dataclasses.dataclass(frozen=True)
class LaneSpec:
    """One QoS lane in the executor's N-lane scheduler.

    ``priority`` orders execution (lower number first). A ``deferrable``
    lane's buckets wait for the idle window — they only become due while
    no strictly-lower-priority slot is queued anywhere — except after
    ``starvation_cap_ms``, the bounded-starvation escape hatch (0 = the
    normal flush window applies even while deferring, i.e. never extend
    the wait).
    """

    name: str
    priority: int
    deferrable: bool = False
    starvation_cap_ms: float = 0.0


LANE_LIVE = "live"
LANE_SPECULATIVE = "speculative"


def default_lanes(speculative_max_wait_ms: float) -> Tuple[LaneSpec, ...]:
    """The seed two-lane table: live traffic plus the deferrable
    speculative pre-compute lane (its starvation cap bounds how long a
    live request coalesced onto an in-flight speculative compute waits)."""
    return (
        LaneSpec(LANE_LIVE, priority=0),
        LaneSpec(
            LANE_SPECULATIVE,
            priority=1,
            deferrable=True,
            starvation_cap_ms=speculative_max_wait_ms,
        ),
    )


class _Slot:
    """One study's pending computation inside a bucket queue.

    ``action`` is the scheduler's verdict, executed by the WAITING thread
    once ``event`` fires: "batched" (finalize ``output``), "sequential"
    (run the plain per-study suggest — the B=1 path, bit-identical to
    batching off), or "fallback" (the shared device program failed; run the
    plain suggest and account it). Host-side prepare/finalize running on
    the waiter threads keeps the scheduler thread free to dispatch the next
    bucket while this one decodes — the continuous-batching pipeline.
    """

    __slots__ = (
        "designer", "program", "count", "enqueued_at", "event", "error",
        "item", "output", "action", "span", "lane", "tenant",
    )

    def __init__(
        self, designer: Any, program: Any, count: int, now: float, span,
        lane: str = LANE_LIVE, tenant: Optional[str] = None,
    ) -> None:
        self.designer = designer
        self.program = program  # the resolved compute-IR DesignerProgram
        self.count = count
        self.enqueued_at = now
        self.event = threading.Event()
        self.error: Optional[BaseException] = None
        self.item: Optional[dict] = None
        self.output: Any = None
        self.action: str = "sequential"
        self.span = span  # the submitter's active span (may be None)
        # QoS lane (LaneSpec.name): a deferrable-lane slot may ride a
        # higher-priority flush that is forming anyway, but a bucket
        # holding ONLY deferrable slots defers to queued priority traffic.
        self.lane = lane
        # Fair-share identity (admission on only): who this computation
        # bills to inside the live lane's deficit-round-robin.
        self.tenant = tenant

    @property
    def speculative(self) -> bool:
        return self.lane == LANE_SPECULATIVE


def stack_pytrees(trees: Sequence[Any], pad_to: Optional[int] = None) -> Any:
    """Stacks per-study pytrees along a new leading axis, padding with
    copies of tree 0 up to ``pad_to`` (masked out again at demux).

    Host (numpy) leaves stack in numpy — zero device dispatches; the whole
    batch then crosses to the device once, at the jitted program's entry.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    trees = list(trees)
    if pad_to is not None and pad_to > len(trees):
        trees = trees + [trees[0]] * (pad_to - len(trees))

    def stack(*xs):
        if all(not isinstance(x, jax.Array) for x in xs):
            return np.stack([np.asarray(x) for x in xs])
        return jnp.stack(xs)

    return jax.tree_util.tree_map(stack, *trees)


def place_batch(tree: Any, placement: Optional[Any] = None) -> Any:
    """Commits a stacked (leading-study-axis) flush pytree onto a mesh
    placement's submesh; a no-op when ``placement`` is None (the
    single-device path keeps its lazy host->device copy at jit entry).

    The shardable programs' ``device_program`` bodies route every stacked
    input through this, so intra-flush sharding is one call site per
    program instead of per-leaf plumbing.
    """
    if placement is None:
        return tree
    return placement.shard(tree)


def stack_members(
    items: Sequence[dict],
    names: Sequence[str],
    pad_to: Optional[int] = None,
    placement: Optional[Any] = None,
) -> Dict[str, Any]:
    """Every named entry of a flush's members, stacked along the study axis
    and placed: the host re-stack and upload of a fused flush, once per
    flush — the ``flush.stack`` stage, whose ``bytes`` attribute is the
    total size of what was placed."""
    with tracing_lib.get_tracer().span(
        "flush.stack", members=len(items), **tracing_lib.FUSED_FLUSH
    ) as span:
        stacked = {
            name: place_batch(
                stack_pytrees([item[name] for item in items], pad_to), placement
            )
            for name in names
        }
        span.set_attribute("bytes", _tree_nbytes(stacked))
    return stacked


def _tree_nbytes(tree: Any) -> int:
    """Total ``nbytes`` of a pytree's array leaves; -1 if it cannot be told
    (a span attribute must never fail the flush it describes)."""
    try:
        import jax

        return sum(
            int(getattr(leaf, "nbytes", 0))
            for leaf in jax.tree_util.tree_leaves(tree)
        )
    except Exception:
        return -1


def slice_pytree(tree: Any, index: int) -> Any:
    """Slot ``index`` of a leading-study-axis pytree.

    Demux is meant to run on a host (``jax.device_get``-fetched) tree, where
    each slice is a free numpy view; on device arrays every leaf slice would
    be its own dispatch — fetch once, then slice.
    """
    import jax

    return jax.tree_util.tree_map(lambda a: a[index], tree)


def check_finite_suggestions(suggestions: Sequence[Any], study: str = "") -> None:
    """Raises :class:`BatchSlotError` if any numeric parameter is non-finite.

    A NaN escaping one slot of a batched program must degrade only its own
    study; the TRANSIENT marker routes it into the reliability fallback.
    """
    for s in suggestions:
        for name, value in s.parameters.as_dict().items():
            if isinstance(value, float) and not math.isfinite(value):
                raise BatchSlotError(
                    errors_lib.mark_transient(
                        f"BATCH_SLOT_INVALID: non-finite parameter "
                        f"{name!r}={value!r} in batched suggestion"
                        + (f" for study {study!r}" if study else "")
                    )
                )


class BatchExecutor:
    """Continuous-batching engine over shape-bucket queues.

    Thread model: callers (one servicer thread per study, each already
    holding its study's cache-entry lock) block in :meth:`suggest`; a single
    daemon scheduler thread owns flush decisions and runs the batched
    programs, so device dispatch is naturally serialized. The scheduler
    never takes per-study locks — the submitting thread holds them while it
    waits, which is exactly what makes mutating the designer from the
    scheduler safe.
    """

    def __init__(
        self,
        max_batch_size: int = 8,
        max_wait_ms: float = 4.0,
        pad_partial: bool = True,
        stats: Optional[Any] = None,  # serving.stats.ServingStats
        metrics: Optional[metrics_lib.MetricsRegistry] = None,
        time_fn: Callable[[], float] = time.monotonic,
        speculative_max_wait_ms: float = 250.0,
        mesh: Optional[Any] = None,  # parallel.mesh.MeshConfig
        lanes: Optional[Sequence[LaneSpec]] = None,
        admission: Optional[Any] = None,  # serving.admission.AdmissionController
    ):
        if max_batch_size < 1:
            raise ValueError(f"max_batch_size must be >= 1, got {max_batch_size}")
        self.max_batch_size = max_batch_size
        self.max_wait_secs = max(max_wait_ms, 0.0) / 1000.0
        # Starvation cap for the speculative lane: a speculative-only
        # bucket normally flushes only when no live slot is queued anywhere
        # (the idle window), but a live request that COALESCED onto an
        # in-flight speculative compute is waiting on it, so the hold is
        # bounded — after this long the speculative flush runs regardless.
        self.speculative_max_wait_secs = max(speculative_max_wait_ms, 0.0) / 1000.0
        # The N-lane QoS table, keyed by lane name; unknown lane names on
        # a slot fall back to the live lane's rules.
        lane_table = tuple(lanes) if lanes else default_lanes(
            speculative_max_wait_ms
        )
        self._lanes: Dict[str, LaneSpec] = {l.name: l for l in lane_table}
        self._live_lane = min(self._lanes.values(), key=lambda l: l.priority)
        # Weighted fair share across tenants (serving.admission): with a
        # controller attached, live-lane selection is deficit-round-robin
        # by tenant; None (the default) keeps the seed FIFO bit-identical.
        self._admission = admission
        # DRR state, guarded by _cond: per-tenant deficit credits, the
        # stable round-robin ring + cursor, and weighted served-slot
        # totals (the cross-bucket ordering key).
        self._drr_deficit: Dict[str, float] = {}
        self._drr_ring: List[str] = []
        self._drr_cursor = 0
        self._tenant_served: Dict[str, float] = {}
        self.pad_partial = pad_partial
        self._stats = stats
        self._time = time_fn
        self._cond = threading.Condition()
        self._queues: Dict[BucketKey, List[_Slot]] = {}
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        # -- mesh execution plane (parallel.mesh, VIZIER_MESH=1) -----------
        # Placements are built eagerly when the config enables the mesh
        # (this is the only path that enumerates devices); disabled = None
        # and every mesh branch below is dead — the seed executor.
        self._placements: Optional[List[Any]] = None
        self._workers: List[threading.Thread] = []
        self._dispatch_cond = threading.Condition()
        self._dispatch_queues: Dict[int, Deque[Tuple[BucketKey, List[_Slot], str]]] = {}
        self._dispatch_closed = False
        # BucketKey -> placement index, sticky from the first flush (the
        # prewarm walker assigns through the same map, so a prewarmed
        # bucket compiles on the placement that later serves it). Guarded
        # by _dispatch_cond.
        self._bucket_placement: Dict[BucketKey, int] = {}
        # Per-placement flush counts; each entry is written only by its
        # own worker thread (no lock — reads may be momentarily stale).
        self._placement_flushes: Dict[str, int] = {}
        if mesh is not None and getattr(mesh, "enabled", False):
            from vizier_tpu.parallel import mesh as mesh_lib

            self._placements = mesh_lib.build_placements(mesh)
            for placement in self._placements:
                self._dispatch_queues[placement.index] = collections.deque()
                self._placement_flushes[placement.label()] = 0
        self._occupancy = self._flushes = self._queue_wait = None
        if metrics is not None:
            self._occupancy = metrics.histogram(
                "vizier_batch_occupancy",
                help="Real (unpadded) slots per batch flush.",
                buckets=[1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64],
            )
            self._flushes = metrics.counter(
                "vizier_batch_flushes",
                help="Batch flushes by reason (full | timeout | drain).",
            )
            self._queue_wait = metrics.histogram(
                "vizier_batch_queue_wait_seconds",
                help="Time a slot spent queued before its batch flushed.",
            )

    # -- submission ---------------------------------------------------------

    def suggest(
        self,
        designer: Any,
        count: Optional[int] = None,
        *,
        speculative: bool = False,
        lane: Optional[str] = None,
    ) -> List[Any]:
        """Routes one study's suggest through the batching engine.

        Unbatchable paths (no resolvable compute-IR program, seeding
        stage, multi-objective, priors, …) run inline on the caller's
        thread — identical to batching off. ``speculative`` (or an
        explicit ``lane`` name) marks the slot's QoS lane: a deferrable
        lane's bucket never flushes while higher-priority slots are
        queued (see :meth:`_take_due`).
        """
        count = count or 1
        resolved = compute_registry.resolve(designer, count)
        if resolved is None or self._closed:
            return designer.suggest(count)
        program, key = resolved
        tracer = tracing_lib.get_tracer()
        tenant = None
        if self._admission is not None:
            from vizier_tpu.serving import admission as admission_lib

            tenant = admission_lib.current_tenant()
        slot = _Slot(
            designer, program, count, self._time(), tracer.current_span(),
            lane=lane or (LANE_SPECULATIVE if speculative else LANE_LIVE),
            tenant=tenant,
        )
        # Joining a non-empty bucket ⇒ this slot will (very likely) ride a
        # batched flush: run its host-side prepare HERE, on the caller's
        # thread, so it overlaps the in-flight flush's device window instead
        # of serializing on the scheduler. A prepare failure stays inline —
        # naturally isolated to this study. An empty bucket stays
        # unprepared: if nobody joins before the window closes, the
        # scheduler hands it back as a plain sequential suggest
        # (bit-identical to batching off).
        with self._cond:
            will_batch = bool(self._queues.get(key))
        if will_batch:
            try:
                slot.item = self._prepare(slot)
            except BaseException:
                self._increment("batch_slot_errors")
                raise
        with self._cond:
            closed = self._closed
            if not closed:
                self._ensure_scheduler()
                self._queues.setdefault(key, []).append(slot)
                self._cond.notify_all()
        if closed:
            return designer.suggest(count)
        # Parked until the scheduler's verdict: the queue (timed by
        # vizier_batch_queue_wait_seconds) and then the flush itself.
        with tracer.span("batch_executor.queue_wait"):
            slot.event.wait()
        return self._complete(slot)

    @staticmethod
    def _prepare(slot: _Slot) -> dict:
        """A slot's host-side prepare, as a stage of ITS request: the slot
        carries the submitter's span, whichever thread runs this."""
        with tracing_lib.get_tracer().span(
            "designer.prepare", parent=slot.span, path=tracing_lib.PATH_FUSED
        ):
            return slot.program.prepare(slot.designer, slot.count)

    def _complete(self, slot: _Slot) -> List[Any]:
        """Runs the scheduler's verdict on the waiting thread."""
        if slot.error is not None:
            raise slot.error
        if slot.action == "batched":
            try:
                with tracing_lib.get_tracer().span(
                    "designer.decode", path=tracing_lib.PATH_FUSED
                ):
                    suggestions = list(
                        slot.program.finalize(
                            slot.designer, slot.item, slot.output
                        )
                    )
                check_finite_suggestions(suggestions)
            except BaseException:
                self._increment("batch_slot_errors")
                raise
            self._increment("batched_suggests")
            return suggestions
        if slot.action == "fallback":
            # The shared device program died (OOM, compile failure, chaos):
            # nobody got the batched result; everybody retries alone on its
            # own thread. This slot's error — if its sequential run also
            # fails — stays its own.
            self._increment("batch_fallbacks")
            tracing_lib.add_current_event("batch_executor.fallback_sequential")
            return list(slot.designer.suggest(slot.count))
        return list(slot.designer.suggest(slot.count))  # "sequential"

    def close(self) -> None:
        """Drains every queue (reason "drain") and stops the scheduler
        (plus, in mesh mode, the per-placement workers — the scheduler
        routes the drain batches to them before signalling shutdown)."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        thread = self._thread
        if thread is not None:
            thread.join(timeout=30.0)
        if self._placements is not None:
            # Covers the never-started case; the scheduler already set
            # this on exit after routing its drain batches.
            with self._dispatch_cond:
                self._dispatch_closed = True
                self._dispatch_cond.notify_all()
            for worker in self._workers:
                worker.join(timeout=30.0)

    def pending_counts(self) -> Dict[str, int]:
        with self._cond:
            return {k.label(): len(v) for k, v in self._queues.items() if v}

    # -- mesh introspection -------------------------------------------------

    @property
    def mesh_enabled(self) -> bool:
        return self._placements is not None

    def placements(self) -> List[Any]:
        """The device placements (empty when the mesh plane is off)."""
        return list(self._placements or [])

    def placement_flush_counts(self) -> Dict[str, int]:
        """Flushes executed per placement label (mesh mode only)."""
        return dict(self._placement_flushes)

    def bucket_placements(self) -> Dict[str, List[str]]:
        """Sticky bucket -> placement assignment, label -> placement labels.

        Keyed by bucket *label*, which omits the jit statics — buckets that
        differ only in statics share a label, so the value is the list of
        placements assigned across that label's keys.
        """
        if self._placements is None:
            return {}
        by_index = {p.index: p.label() for p in self._placements}
        out: Dict[str, List[str]] = {}
        with self._dispatch_cond:
            for key, idx in self._bucket_placement.items():
                out.setdefault(key.label(), []).append(by_index[idx])
        return {label: sorted(placements) for label, placements in out.items()}

    def _placement_for(self, key: BucketKey):
        """The placement sticky-assigned to ``key`` (least-loaded on first
        sight, stable forever after — one compiled program per (bucket,
        placement)). Caller must NOT hold ``_dispatch_cond``."""
        assert self._placements is not None
        with self._dispatch_cond:
            index = self._bucket_placement.get(key)
            if index is None:
                load: Dict[int, int] = {p.index: 0 for p in self._placements}
                for assigned in self._bucket_placement.values():
                    load[assigned] += 1
                index = min(load, key=lambda i: (load[i], i))
                self._bucket_placement[key] = index
        return self._placements[index]

    def queue_depth(self) -> Dict[str, int]:
        """Queued slots by lane — the speculative admission gate's view of
        whether live traffic is saturating the flush buckets."""
        out = {name: 0 for name in self._lanes}
        with self._cond:
            for slots in self._queues.values():
                for slot in slots:
                    name = slot.lane if slot.lane in out else self._live_lane.name
                    out[name] += 1
        return out

    def live_pending(self) -> int:
        """Queued LIVE (non-speculative) slots across all buckets."""
        return self.queue_depth()["live"]

    # -- scheduling ---------------------------------------------------------

    def _ensure_scheduler(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._scheduler_loop,
                name="vizier-batch-executor",
                daemon=True,
            )
            self._thread.start()
        if self._placements is not None and not self._workers:
            self._workers = [
                threading.Thread(
                    target=self._worker_loop,
                    args=(placement,),
                    name=f"vizier-mesh-worker-{placement.index}",
                    daemon=True,
                )
                for placement in self._placements
            ]
            for worker in self._workers:
                worker.start()

    def _lane_for(self, slot: _Slot) -> LaneSpec:
        return self._lanes.get(slot.lane, self._live_lane)

    def _bucket_lane(self, slots: List[_Slot]) -> LaneSpec:
        """A bucket's effective lane: the lowest-priority-number lane
        among its slots (a deferrable slot rides a priority flush that is
        forming anyway — the seed's spec-slot-on-live-bucket behavior)."""
        return min(
            (self._lane_for(s) for s in slots), key=lambda l: l.priority
        )

    def _fair_order(self, slots: List[_Slot]) -> List[_Slot]:
        """Deficit-round-robin across tenants, FIFO within a tenant.

        Quantum = the tenant's admission weight. Persistent ring/cursor/
        deficit state (caller holds ``_cond``) makes the rotation fair
        across flushes, not just within one. Starvation bound: a light
        tenant's first queued slot is selected within one DRR round, i.e.
        it can be delayed by at most the sum of the OTHER tenants'
        quanta — a continuously-hot tenant cannot push it further back.
        Single-tenant (or tenantless, admission off) input returns FIFO
        unchanged.
        """
        by_tenant: Dict[str, Deque[_Slot]] = collections.OrderedDict()
        for slot in slots:
            by_tenant.setdefault(slot.tenant or "", collections.deque()).append(
                slot
            )
        if len(by_tenant) <= 1:
            return slots
        for tenant in by_tenant:
            if tenant not in self._drr_ring:
                self._drr_ring.append(tenant)
        weight = self._admission.weight
        out: List[_Slot] = []
        remaining = len(slots)
        ring = self._drr_ring
        while remaining:
            self._drr_cursor %= len(ring)
            tenant = ring[self._drr_cursor]
            self._drr_cursor += 1
            queue = by_tenant.get(tenant)
            if not queue:
                # Classic DRR: an idle tenant banks no credit.
                self._drr_deficit.pop(tenant, None)
                continue
            quantum = max(1.0, float(weight(tenant)))
            credit = self._drr_deficit.get(tenant, 0.0) + quantum
            while credit >= 1.0 and queue:
                out.append(queue.popleft())
                remaining -= 1
                credit -= 1.0
            self._drr_deficit[tenant] = credit if queue else 0.0
        return out

    def _order_due(
        self, due: List[Tuple[BucketKey, List[_Slot], str]]
    ) -> List[Tuple[BucketKey, List[_Slot], str]]:
        """Cross-bucket fairness: stable-sort same-priority due batches by
        their tenants' weighted served-slot totals (least-served first),
        then bill the selection — every flush is billed, even a lone one,
        so the credit stays honest across flush cycles. No-op without an
        admission controller."""
        if self._admission is None:
            return due
        weight = self._admission.weight
        if len(due) > 1:

            def served_key(batch):
                _key, slots, _reason = batch
                return min(
                    self._tenant_served.get(s.tenant or "", 0.0)
                    / max(1.0, float(weight(s.tenant)))
                    for s in slots
                )

            due = sorted(due, key=served_key)
        for _key, slots, _reason in due:
            for slot in slots:
                self._tenant_served[slot.tenant or ""] = (
                    self._tenant_served.get(slot.tenant or "", 0.0) + 1.0
                )
        return due

    def _take_due(self) -> List[Tuple[BucketKey, List[_Slot], str]]:
        """Pops every due (key, slots, reason) batch. Caller holds the lock.

        Lane rules: a bucket whose effective lane is non-deferrable
        flushes on the ordinary full/timeout rules. A deferrable-lane
        bucket defers while any strictly-lower-priority slot is queued
        anywhere (priority traffic owns the device; the idle window is
        its admission), flushing only once the queues are clear of
        priority work — or after the lane's ``starvation_cap_ms``, the
        bounded-starvation escape for priority requests that coalesced
        onto an in-flight deferred compute. Due batches come back in
        lane-priority order; same-priority batches are ordered by the
        weighted fair-share credit when admission is on.
        """
        now = self._time()
        due_by_priority: Dict[int, List[Tuple[BucketKey, List[_Slot], str]]] = {}
        deferred: List[Tuple[BucketKey, List[_Slot], LaneSpec]] = []
        min_queued_priority = min(
            (
                self._lane_for(s).priority
                for slots in self._queues.values()
                for s in slots
            ),
            default=0,
        )
        for key, slots in self._queues.items():
            if not slots:
                continue
            if self._closed:
                due_by_priority.setdefault(0, []).append(
                    (key, slots[:], "drain")
                )
                slots.clear()
                continue
            lane = self._bucket_lane(slots)
            if lane.deferrable and min_queued_priority < lane.priority:
                deferred.append((key, slots, lane))
                continue
            bucket_due = due_by_priority.setdefault(lane.priority, [])
            if len(slots) >= self.max_batch_size:
                ordered = (
                    self._fair_order(slots)
                    if self._admission is not None
                    and not lane.deferrable
                    else slots
                )
                while len(ordered) >= self.max_batch_size:
                    bucket_due.append(
                        (key, ordered[: self.max_batch_size], "full")
                    )
                    del ordered[: self.max_batch_size]
                slots[:] = ordered
            # Oldest by enqueue time, not position: a DRR-reordered
            # remainder is no longer FIFO (identical for FIFO queues).
            if slots and now - min(
                s.enqueued_at for s in slots
            ) >= self.max_wait_secs:
                bucket_due.append((key, slots[:], "timeout"))
                slots.clear()
        for key, slots, lane in deferred:
            if not slots:
                continue
            waited = now - slots[0].enqueued_at
            cap = max(lane.starvation_cap_ms, 0.0) / 1000.0
            if waited >= cap:
                reason = "spec_starved"
            else:
                continue
            # A deferred bucket may have grown past the batch size: flush
            # in max-size chunks so the compiled shape stays the bucket's.
            bucket_due = due_by_priority.setdefault(lane.priority, [])
            while len(slots) > self.max_batch_size:
                bucket_due.append((key, slots[: self.max_batch_size], "full"))
                del slots[: self.max_batch_size]
            bucket_due.append((key, slots[:], reason))
            slots.clear()
        out: List[Tuple[BucketKey, List[_Slot], str]] = []
        for priority in sorted(due_by_priority):
            out.extend(self._order_due(due_by_priority[priority]))
        return out

    def _next_deadline(self) -> Optional[float]:
        """Seconds until the next queued bucket becomes due (lock held)."""
        min_queued_priority = min(
            (
                self._lane_for(s).priority
                for slots in self._queues.values()
                for s in slots
            ),
            default=0,
        )
        deadline = None
        for slots in self._queues.values():
            if not slots:
                continue
            lane = self._bucket_lane(slots)
            if lane.deferrable and min_queued_priority < lane.priority:
                window = max(lane.starvation_cap_ms, 0.0) / 1000.0
            else:
                window = self.max_wait_secs
            due_at = min(s.enqueued_at for s in slots) + window
            if deadline is None or due_at < deadline:
                deadline = due_at
        if deadline is None:
            return None
        return max(deadline - self._time(), 0.0)

    def _scheduler_loop(self) -> None:
        while True:
            with self._cond:
                due = self._take_due()
                if not due:
                    if self._closed:
                        self._signal_workers_closed()
                        return
                    self._cond.wait(timeout=self._next_deadline())
                    continue
            if self._placements is None:
                # Seed path: the scheduler thread executes flushes itself
                # (device dispatch naturally serialized).
                for key, slots, reason in due:
                    self._execute(key, slots, reason)
            else:
                # Mesh path: the scheduler only FORMS flushes; execution
                # fans out to the per-placement workers so different
                # buckets dispatch to different devices concurrently.
                for key, slots, reason in due:
                    placement = self._placement_for(key)
                    with self._dispatch_cond:
                        self._dispatch_queues[placement.index].append(
                            (key, slots, reason)
                        )
                        self._dispatch_cond.notify_all()

    def _signal_workers_closed(self) -> None:
        if self._placements is None:
            return
        with self._dispatch_cond:
            self._dispatch_closed = True
            self._dispatch_cond.notify_all()

    def _worker_loop(self, placement: Any) -> None:
        """One placement's dispatch thread: executes its bucket queue.

        Pops under the dispatch lock, executes outside it — a flush's
        device dispatch never runs under any executor lock (the lock-order
        pass's no-compute-under-lock rule covers these threads too).
        """
        queue = self._dispatch_queues[placement.index]
        while True:
            with self._dispatch_cond:
                while not queue and not self._dispatch_closed:
                    self._dispatch_cond.wait()
                if not queue and self._dispatch_closed:
                    return
                key, slots, reason = queue.popleft()
            self._execute(key, slots, reason, placement)
            self._placement_flushes[placement.label()] += 1

    # -- execution ----------------------------------------------------------

    def _observe_flush(
        self,
        key: BucketKey,
        slots: List[_Slot],
        reason: str,
        placement: Optional[Any] = None,
    ) -> None:
        now = self._time()
        label = key.label()
        # The device label only exists in mesh mode so the seed path's
        # metric series stay byte-identical with the mesh off.
        device = {"device": placement.label()} if placement is not None else {}
        if self._flushes is not None:
            self._flushes.inc(reason=reason, **device)
            self._occupancy.observe(len(slots), bucket=label, **device)
            for slot in slots:
                self._queue_wait.observe(
                    now - slot.enqueued_at, bucket=label, **device
                )
        if self._stats is not None:
            self._stats.increment("batch_flushes")
            if placement is not None:
                self._stats.increment("mesh_flushes")
        recorder = recorder_lib.get_recorder()
        if recorder.enabled:
            # Flush membership for the flight recorder: the member suggests'
            # trace ids tie this fleet-scoped event back to each study's
            # own ring (their request spans carry the same ids).
            recorder.record(
                None,
                "batch_flush",
                bucket=label,
                occupancy=len(slots),
                reason=reason,
                device=placement.label() if placement is not None else None,
                members=[
                    s.span.trace_id for s in slots if s.span is not None
                ],
            )

    def _execute(
        self,
        key: BucketKey,
        slots: List[_Slot],
        reason: str,
        placement: Optional[Any] = None,
    ) -> None:
        self._observe_flush(key, slots, reason, placement)
        tracer = tracing_lib.get_tracer()
        device_attr = (
            {"device": placement.label()} if placement is not None else {}
        )
        with tracer.span(
            "batch_executor.flush",
            bucket=key.label(),
            occupancy=len(slots),
            reason=reason,
            **device_attr,
        ) as span:
            # Link the flush span and every member's request span both ways:
            # a member trace shows WHICH batch served it, the flush span
            # shows WHO shared the dispatch.
            for slot in slots:
                if slot.span is not None and span is not None:
                    span.add_link(slot.span.context(), name="batch_member")
                    slot.span.add_link(span.context(), name="batch_flush")
                    slot.span.set_attribute("batch_occupancy", len(slots))
            if len(slots) == 1 and slots[0].item is None:
                # No batchmates and never prepared: hand back the plain
                # sequential path, bit-identical to batching off (and no
                # vmap overhead). The waiter runs it on its own thread.
                self._increment("lone_handbacks")
                slots[0].action = "sequential"
                slots[0].event.set()
                return
            self._execute_batched(slots, placement)

    def _increment(self, field: str, amount: int = 1) -> None:
        if self._stats is not None and amount:
            self._stats.increment(field, amount)

    def _execute_batched(
        self, slots: List[_Slot], placement: Optional[Any] = None
    ) -> None:
        # Prepare any slot that arrived into an empty bucket (typically the
        # flush's first member; the rest prepared on their own threads at
        # submit time). Slot-isolated: a study whose encode/RNG work raises
        # is dropped from the batch before the device program runs.
        live: List[_Slot] = []
        for slot in slots:
            if slot.item is None:
                try:
                    slot.item = self._prepare(slot)
                except BaseException as e:
                    slot.error = e
                    self._increment("batch_slot_errors")
                    slot.event.set()
                    continue
            live.append(slot)
        if not live:
            return
        self._increment("lone_flushes", len(live) == 1)
        # A lone prepare survivor still goes through the batched program:
        # its RNG draws already happened in batch order, and pad_partial
        # keeps the compiled shape identical either way.
        program = live[0].program
        # A shardable program on a mesh placement pads at SHARD granularity
        # (DevicePlacement.pad_to — a multiple of the placement's device
        # count, so every device holds an equal slice of the study axis)
        # and receives the placement so it can commit the stacked batch
        # onto the submesh. Anything else keeps the seed padding contract.
        shardable = placement is not None and getattr(
            program, "shardable_batch_axis", ""
        )
        if shardable:
            pad_to = placement.pad_to(len(live), self.max_batch_size)
        else:
            pad_to = self.max_batch_size if self.pad_partial else None
        try:
            # Slot 0's resolved program runs the bucket's device body (the
            # bucket key guarantees every slot resolves the same kind; a
            # chaos-wrapped slot 0 therefore poisons the shared program,
            # exercising the whole-batch fallback).
            if shardable:
                outputs = program.device_program(
                    [slot.item for slot in live],
                    pad_to=pad_to,
                    placement=placement,
                )
            else:
                outputs = program.device_program(
                    [slot.item for slot in live], pad_to=pad_to
                )
        except BaseException:
            # The shared device program died: every slot retries alone on
            # its own waiting thread (see _complete), errors slot-isolated.
            tracing_lib.add_current_event(
                "batch_executor.fallback_sequential", slots=len(live)
            )
            for slot in live:
                slot.action = "fallback"
                slot.event.set()
            return
        for slot, output in zip(live, outputs):
            slot.output = output
            slot.action = "batched"
            slot.event.set()

    # -- compile prewarm ----------------------------------------------------

    def prewarm(
        self,
        problem: Any,  # pyvizier ProblemStatement
        designer_factory: Callable[..., Any],
        *,
        max_trials: int = 32,
        counts: Sequence[int] = (1,),
        batch_sizes: Optional[Sequence[int]] = None,
        rng_seed: int = 0,
    ) -> List[dict]:
        """Walks the padding-bucket grid and compiles the batched programs.

        For every ``pad_trials`` bucket covering studies up to ``max_trials``
        and every requested suggestion ``count``, synthetic studies are
        trained + swept once at batch sizes {1, max} (1 warms the sequential
        per-study programs, max the vmapped multi-study programs, which —
        with ``pad_partial`` — is the only batched shape that ever runs).
        In mesh mode the batched sizes are instead the placements'
        shard-granularity padding grid (``DevicePlacement.pad_grid``) and
        each bucket compiles on its sticky-assigned placement — exactly
        the (shape, placement) pairs live flushes will use.
        First-request latency then pays no XLA compile. Returns one report
        row per (bucket, count, batch_size) with wall seconds.
        """
        from vizier_tpu.designers import quasi_random
        from vizier_tpu.pyvizier import trial as trial_

        if batch_sizes:
            sizes = tuple(batch_sizes)
        elif self._placements is not None:
            # Mesh mode: the batched shapes a placement can flush are its
            # shard-granularity padding grid (not just {max}); compile all
            # of them plus the sequential singleton. The per-placement
            # grids are identical when shard counts are equal (the normal
            # case), and de-duped otherwise.
            grid = sorted(
                {
                    size
                    for placement in self._placements
                    for size in placement.pad_grid(self.max_batch_size)
                }
            )
            sizes = tuple([1] + [s for s in grid if s != 1])
        else:
            sizes = (1, self.max_batch_size)
        probe = designer_factory(problem)
        schedule = probe._converter.padding
        report: List[dict] = []
        for bucket in schedule.trial_bucket_grid(max_trials):
            for count in counts:
                for size in sizes:
                    t0 = time.perf_counter()
                    designers = []
                    for j in range(size):
                        d = designer_factory(problem)
                        seeder = quasi_random.QuasiRandomDesigner(
                            problem.search_space, seed=rng_seed + j
                        )
                        trials = []
                        for i, s in enumerate(seeder.suggest(bucket)):
                            t = s.to_trial(i + 1)
                            t.complete(
                                trial_.Measurement(
                                    metrics={
                                        m.name: 0.1 * ((i + j) % 7)
                                        for m in problem.metric_information
                                    }
                                )
                            )
                            trials.append(t)
                        from vizier_tpu.algorithms import core as core_lib

                        d.update(core_lib.CompletedTrials(trials))
                        designers.append(d)
                    status = "ok"
                    try:
                        if size == 1:
                            designers[0].suggest(count)
                        else:
                            # Same calling convention as suggest() above:
                            # registry resolution refreshes per-designer
                            # mode state (e.g. the exact↔sparse surrogate
                            # auto-switch) that prepare snapshots into its
                            # item, and hands back the program whose
                            # device body this bucket compiles.
                            resolved = [
                                compute_registry.resolve(d, count)
                                for d in designers
                            ]
                            if any(r is None for r in resolved):
                                designers[0].suggest(count)
                            else:
                                program, key = resolved[0]
                                items = [
                                    program.prepare(d, count)
                                    for d in designers
                                ]
                                # Compile through the same placement
                                # assignment + shard-granularity padding
                                # live flushes of this bucket will use.
                                placement = (
                                    self._placement_for(key)
                                    if self._placements is not None
                                    and getattr(
                                        program, "shardable_batch_axis", ""
                                    )
                                    else None
                                )
                                if placement is not None:
                                    outputs = program.device_program(
                                        items,
                                        pad_to=placement.pad_to(
                                            size, self.max_batch_size
                                        ),
                                        placement=placement,
                                    )
                                else:
                                    outputs = program.device_program(
                                        items,
                                        pad_to=(
                                            self.max_batch_size
                                            if self.pad_partial
                                            else None
                                        ),
                                    )
                                for d, item, out in zip(
                                    designers, items, outputs
                                ):
                                    program.finalize(d, item, out)
                    except Exception as e:  # prewarm must never block serving
                        status = f"error:{type(e).__name__}"
                    report.append(
                        dict(
                            pad_trials=bucket,
                            count=count,
                            batch_size=size,
                            seconds=round(time.perf_counter() - t0, 4),
                            status=status,
                        )
                    )
        return report
