"""The serving policy: cached designer + incremental updates + warm ARD.

Stateless per-request object over shared state: the policy itself is
rebuilt per Pythia request (cheap), while the designer, its trained ARD
params, and the incorporated-trial-id set live in the process-wide
:class:`~vizier_tpu.serving.designer_cache.DesignerStateCache`. Contrast
with ``algorithms.designer_policy.DesignerPolicy`` (fresh designer + full
trial replay per request — the reference shape) and
``InRamDesignerPolicy`` (lives only as long as the policy object the
Pythia servicer happens to cache, no TTL/LRU/invalidation).

The trial read is a **delta read** (``policy.load_trials``): the study's
frontier as ids and states, the set difference against the entry's
incorporated ids (a set, not a high-water mark: a lower id may complete
after a higher one), and a fetch + proto -> pyvizier conversion of only the
missing completed trials and the ACTIVE ones. ``designer.update`` receives
what listing and converting the whole study twice would have handed it;
``serving_stats()`` counts ``trials_fetched`` / ``trials_reused``.
"""

from __future__ import annotations

import logging
from typing import Any, Callable, List, Optional, Sequence

from vizier_tpu.algorithms import core as core_lib
from vizier_tpu.algorithms import designer_policy
from vizier_tpu.observability import flight_recorder as recorder_lib
from vizier_tpu.observability import tracing as tracing_lib
from vizier_tpu.pythia import policy as policy_lib
from vizier_tpu.pythia import policy_supporter as supporter_lib
from vizier_tpu.pyvizier import base_study_config
from vizier_tpu.pyvizier import trial as trial_
from vizier_tpu.serving import designer_cache as cache_lib
from vizier_tpu.serving import runtime as runtime_lib
from vizier_tpu.serving import speculative as speculative_lib
from vizier_tpu.surrogates import config as surrogate_config_lib

_logger = logging.getLogger(__name__)


class CachedDesignerStatePolicy(policy_lib.Policy):
    """Routes suggests through the shared per-study designer cache."""

    def __init__(
        self,
        supporter: supporter_lib.PolicySupporter,
        designer_factory: Callable[[base_study_config.ProblemStatement], Any],
        runtime: runtime_lib.ServingRuntime,
        study_name: str,
        *,
        use_seeding: bool = False,
    ):
        self._supporter = supporter
        self._designer_factory = designer_factory
        self._runtime = runtime
        self._study_name = study_name
        self._use_seeding = use_seeding

    def suggest(self, request: policy_lib.SuggestRequest) -> policy_lib.SuggestDecision:
        if self._use_seeding and request.max_trial_id == 0:
            seed = designer_policy.default_suggestion(
                request.study_config.to_problem()
            )
            rest: Sequence[trial_.TrialSuggestion] = []
            if request.count > 1:
                rest = self._run_designer(request, request.count - 1)
            return policy_lib.SuggestDecision(suggestions=[seed] + list(rest))
        return policy_lib.SuggestDecision(
            suggestions=list(self._run_designer(request, request.count))
        )

    def _run_designer(
        self, request: policy_lib.SuggestRequest, count: int
    ) -> List[trial_.TrialSuggestion]:
        problem = request.study_config.to_problem()
        cache = self._runtime.designer_cache
        entry = cache.get_or_create(
            self._study_name, lambda: self._designer_factory(problem)
        )
        # Surrogate-crossover invalidation hook: a parked speculative batch
        # predates the crossover's warm/posterior reset, so the designer
        # reports the flip straight into the engine the moment it happens
        # (mid-compute), not after the policy's post-hoc stats diff.
        if self._runtime.speculative_engine is not None:
            surrogate_config_lib.install_crossover_listener(
                entry.designer, self._on_surrogate_crossover
            )
        with entry.lock:
            try:
                return self._update_and_suggest(entry, count)
            except Exception:
                # A designer whose live state went bad (e.g. an update that
                # died halfway) must not poison every later suggest for the
                # study: drop the entry so the next request rebuilds from a
                # clean full replay, then surface this request's error.
                cache.invalidate(self._study_name)
                _logger.warning(
                    "Serving designer for %s failed; cache entry invalidated.",
                    self._study_name,
                )
                raise

    def _update_and_suggest(
        self, entry: cache_lib.CachedDesignerEntry, count: int
    ) -> List[trial_.TrialSuggestion]:
        designer = entry.designer
        tracer = tracing_lib.get_tracer()
        # A delta read: only the completed trials this designer does not
        # hold yet are fetched and converted, plus the ACTIVE ones (read
        # fresh every time: pending-point conditioning depends on them).
        # A completed trial is fed to a designer once and never re-read (a
        # later edit or delete of one goes unseen); an entry that starts
        # empty (fresh, evicted, expired, invalidated) replays the study
        # through this same read.
        with tracer.span("policy.load_trials", study=self._study_name) as load:
            new_completed, active, num_completed = self._supporter.GetTrialDelta(
                entry.incorporated_trial_ids
            )
            fetched = len(new_completed) + len(active)
            load.set_attribute("completed", num_completed)
            load.set_attribute("fetched", fetched)
        stats = self._runtime.stats
        stats.increment("trials_fetched", fetched)
        stats.increment("trials_reused", num_completed - len(new_completed))
        stats.increment("pending_trials_conditioned", len(active))
        before = self._train_counts(designer)
        surrogate_before = self._surrogate_counts(designer)
        rows_before = self._row_counts(designer)
        mesh_before = self._mesh_counts(designer)
        with tracer.span(
            "designer.update",
            designer=type(designer).__name__,
            new_completed=len(new_completed),
            incremental=True,
        ):
            designer.update(
                core_lib.CompletedTrials(new_completed),
                core_lib.ActiveTrials(active),
            )
        entry.incorporated_trial_ids.update(t.id for t in new_completed)
        with tracer.span(
            "designer.suggest",
            designer=type(designer).__name__,
            count=count,
        ):
            # Cross-study batching: concurrent same-bucket computations from
            # different studies share one vmapped device program. The
            # executor runs unbatchable paths (and batching off) inline —
            # the exact per-study call below.
            executor = getattr(self._runtime, "batch_executor", None)
            if executor is not None:
                # A speculative job's compute rides the low-priority lane:
                # it shares vmapped flush buckets with live traffic when
                # one is already forming, but never delays a live flush.
                suggestions = list(
                    executor.suggest(
                        designer,
                        count,
                        speculative=speculative_lib.in_speculative_compute(),
                    )
                )
            else:
                suggestions = list(designer.suggest(count))
        self._account_trains(before, self._train_counts(designer))
        self._account_surrogate(
            surrogate_before, self._surrogate_counts(designer)
        )
        self._account_rows(rows_before, self._row_counts(designer))
        self._account_mesh(mesh_before, self._mesh_counts(designer))
        # Mirror the trained unconstrained ARD params into the entry: the
        # stats/inspection surface for "what would seed the next train",
        # and the hand-off if the designer is ever rebuilt around them.
        get_state = getattr(designer, "warm_start_state", None)
        if get_state is not None:
            entry.warm_params = get_state()
        # The active surrogate mode, mirrored. The trained inducing-point
        # state is the designer's, sliced out of its fit when somebody asks
        # (``designer.sparse_inducing_state()``): a copy here cost every
        # sparse suggest 16 one-leaf device programs that nobody read.
        entry.surrogate_mode = getattr(designer, "surrogate_mode", None)
        entry.num_suggests += 1
        return suggestions

    def _on_surrogate_crossover(self, old_mode: str, new_mode: str) -> None:
        """The designer's exact↔sparse flip invalidates the parked batch."""
        self._runtime.speculative_invalidate(
            self._study_name, reason=f"crossover:{old_mode}->{new_mode}"
        )

    @staticmethod
    def _train_counts(designer: Any) -> Optional[dict]:
        counts = getattr(designer, "ard_train_counts", None)
        return dict(counts) if counts is not None else None

    @staticmethod
    def _surrogate_counts(designer: Any) -> Optional[dict]:
        counts = getattr(designer, "surrogate_counts", None)
        return dict(counts) if counts is not None else None

    @staticmethod
    def _row_counts(designer: Any) -> Optional[dict]:
        counts = getattr(designer, "encoded_row_counts", None)
        return dict(counts) if counts is not None else None

    @staticmethod
    def _mesh_counts(designer: Any) -> Optional[dict]:
        counts = getattr(designer, "mesh_counts", None)
        return dict(counts) if counts is not None else None

    def _account_mesh(self, before: Optional[dict], after: Optional[dict]) -> None:
        """Counts the suggests the designer ran on its mesh, and says on how
        many devices on the span that encloses the computation
        (``pythia.suggest_compute``; ``pythia.suggest`` without coalescing)."""
        if before is None or after is None:
            return
        ran = after["suggests"] - before["suggests"]
        if ran <= 0:
            return
        self._runtime.note_mesh_suggests(ran, after["devices"])
        span = tracing_lib.get_tracer().current_span()
        if span is not None:
            span.set_attribute("devices", after["devices"])

    def _account_rows(self, before: Optional[dict], after: Optional[dict]) -> None:
        if before is None or after is None:
            return
        stats = self._runtime.stats
        stats.increment("rows_encoded", after["encoded"] - before["encoded"])
        stats.increment("rows_reused", after["reused"] - before["reused"])

    def _account_surrogate(
        self, before: Optional[dict], after: Optional[dict]
    ) -> None:
        if before is None or after is None:
            return
        stats = self._runtime.stats
        sparse = after.get("sparse_suggests", 0) - before.get("sparse_suggests", 0)
        crossed = after.get("crossovers", 0) - before.get("crossovers", 0)
        if sparse > 0:
            stats.increment("sparse_suggests", sparse)
        joined = after.get("nystrom_augments", 0) - before.get("nystrom_augments", 0)
        if joined > 0:
            stats.increment("nystrom_augments", joined)
        # A deferred fit made a predictive INSIDE a served suggest: nothing
        # on the request path reads one, so this stays 0 (a predict/sample
        # between suggests raises the designer's count, not this).
        read = after.get("fit_reads", 0) - before.get("fit_reads", 0)
        if read > 0:
            stats.increment("fit_reads", read)
        if crossed > 0:
            stats.increment("surrogate_crossovers", crossed)
            recorder_lib.get_recorder().record(
                self._study_name, "surrogate_crossover", count=crossed,
                mode=after.get("mode"),
            )

    def _account_trains(self, before: Optional[dict], after: Optional[dict]) -> None:
        if before is None or after is None:
            return
        stats = self._runtime.stats
        warm = after.get("warm", 0) - before.get("warm", 0)
        cold = after.get("cold", 0) - before.get("cold", 0)
        if warm > 0:
            stats.increment("warm_trains", warm)
        if cold > 0:
            stats.increment("cold_trains", cold)
        cached = after.get("cached", 0) - before.get("cached", 0)
        if cached > 0:
            stats.increment("cached_fit_suggests", cached)
        # What the timed train programs counted of their own work, under
        # the serving counters' own names (``train_programs`` ...; a fused
        # flush's comes through its first member, once a flush), and the
        # sequential trains that enqueued their sweeps while they still ran.
        for field in after:
            if field.startswith("train_") or field in ("sequential_trains", "sweeps_ahead"):
                gained = after[field] - before.get(field, 0)
                if gained > 0:
                    stats.increment(field, gained)
