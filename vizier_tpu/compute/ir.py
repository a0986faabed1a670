"""The batched designer-compute IR: one contract, every serving discipline.

Every batchable designer computation in the tree has the same anatomy:

- a **shape/static descriptor** (:class:`BucketKey`) that says which other
  studies' computations it can share a compiled device program with;
- a **host-side encode** run on the submitting thread (trial → padded
  model data + RNG draws, zero device dispatches);
- a **jitted, vmappable device body** (multi-restart ARD train + the
  acquisition sweep) executed once per bucket flush over a leading study
  axis;
- a **host-side decode/demux** that writes the designer's state
  transitions (warm ARD seed, cached posterior, counters) and decodes
  suggestions.

Those four stages are the hooks of :class:`DesignerProgram`; programs
register in :mod:`vizier_tpu.compute.registry`, and every cross-cutting
feature — the batch executor, the compile-prewarm walker, chaos slot
isolation, ``device.wait`` stage-span tracing, the speculative lane —
consumes the registry generically. A designer that implements one program
gets batching, prewarm, fail isolation, tracing, and speculation for free
(docs/guides/performance.md "Batched compute IR" is the author guide).

Layering: this module is import-light (no jax at module import) so the
registry stays cheap to consult from host-side serving code and the
stdlib-only analysis suite can reason about it.
"""

from __future__ import annotations

import abc
import dataclasses
from typing import Any, Hashable, List, Optional, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class BucketKey:
    """Identity of one shape bucket: equal keys ⇒ batchable together.

    ``kind`` is the registered :class:`DesignerProgram` that executes the
    bucket's device body. ``statics`` carries the hashable jit-static
    objects (model, optimizers, acquisition config, restart budget, …) so
    two studies share a bucket exactly when they would share every
    compiled program — shape AND configuration.
    """

    kind: str  # registered program kind, e.g. "gp_bandit" | "gp_ucb_pe"
    pad_trials: int
    cont_width: int
    cat_width: int
    metric_count: int
    count: int  # suggestions per study (a jit-static of the sweep)
    statics: Tuple[Hashable, ...] = ()

    def label(self) -> str:
        """Low-cardinality metrics/tracing label (one per shape bucket)."""
        return (
            f"{self.kind}/t{self.pad_trials}/f{self.cont_width}"
            f"x{self.cat_width}/m{self.metric_count}/q{self.count}"
        )


class DesignerProgram(abc.ABC):
    """One batched designer computation, named by ``kind``.

    Programs are stateless singletons: all per-study state lives on the
    designer instance each hook receives (the ``prepare``/``finalize``
    pair runs the exact state transitions the designer's sequential
    ``suggest`` performs, so slot i of a batch is bit-identical to study i
    run alone). ``device_program`` is a classless device body: it reads
    per-slot jit statics from ``items[0]`` — the bucket key guarantees
    every slot's statics are equal.
    """

    #: Unique registry key; also the BucketKey.kind this program emits.
    kind: str = ""
    #: ``jax_timing.device_phase`` name the device body times itself under
    #: (the ``device.wait`` stage span's ``phase``; read by tools/obs_report.py).
    device_phase: str = ""
    #: Which surrogate family the device body trains ("exact" | "sparse");
    #: tools/obs_report.py builds its phase classification from this.
    surrogate_family: str = "exact"
    #: Name of the batch axis ``device_program`` may shard over a device
    #: placement ("" = unshardable: the executor never passes a
    #: ``placement`` and the flush runs on the default device). Every
    #: in-tree program stacks items along a leading per-study axis and
    #: declares ``"study"``; the mesh executor then commits the stacked
    #: pytree onto the placement's submesh (``DevicePlacement.shard``)
    #: before the fused dispatch. Declared as IR metadata — not inferred —
    #: so the ``compute_ir`` analysis pass can audit that every registered
    #: program made the call explicitly.
    shardable_batch_axis: str = ""
    #: Service algorithm names whose prewarm walks should compile this
    #: program's buckets (PythiaServicer.prewarm consults the registry).
    algorithms: Tuple[str, ...] = ()

    @abc.abstractmethod
    def bucket_key(self, designer: Any, count: int) -> Optional[BucketKey]:
        """This designer's shape bucket for a ``count``-suggestion compute,
        or None when the program does not cover its current state (seeding
        stage, multi-objective, priors, wrong surrogate mode, …)."""

    @abc.abstractmethod
    def prepare(self, designer: Any, count: int) -> dict:
        """Host-side encode on the submitting thread: padded model data +
        RNG draws, consuming the designer's RNG stream in exactly the
        sequential order. Must issue zero device dispatches."""

    @abc.abstractmethod
    def device_program(
        self,
        items: Sequence[dict],
        pad_to: Optional[int] = None,
        placement: Any = None,
    ) -> List[dict]:
        """The jitted, vmapped train+acquire body for a whole bucket:
        stacks the items along a leading study axis, runs ONE fused XLA
        dispatch, fetches once, and returns one host-side output dict per
        item (free numpy views after the single ``device_get``).

        ``placement`` (a ``parallel.mesh.DevicePlacement``) is only passed
        when the program declares a ``shardable_batch_axis``: the program
        must then commit the stacked pytree onto the placement's submesh
        (``placement.shard``) so the fused dispatch spans its devices. The
        executor guarantees ``pad_to`` is a multiple of the placement's
        device count."""

    @abc.abstractmethod
    def finalize(self, designer: Any, item: dict, output: dict) -> List[Any]:
        """Host-side decode/demux on the waiting thread: the designer's
        sequential state writeback (warm seed, cached fit, counters) plus
        suggestion decode. Returns the TrialSuggestion batch."""

    @abc.abstractmethod
    def prewarm_factory(self, problem: Any, **kwargs) -> Any:
        """A designer whose computations route to THIS program, for the
        compile-prewarm walker (``BatchExecutor.prewarm``) to train and
        sweep synthetic studies through every padding bucket."""

    def matches_algorithm(self, algorithm: str) -> bool:
        """Whether a service-level prewarm for ``algorithm`` covers this
        program (case-insensitive exact match on ``algorithms``)."""
        return (algorithm or "").upper() in self.algorithms
