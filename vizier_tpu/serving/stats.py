"""Serving-level counters: cache, warm/cold ARD trains, coalescing.

Backed by the :mod:`vizier_tpu.observability` metrics registry (one
``Counter`` per field, prefixed ``vizier_serving_``) so the serving
vocabulary shows up in the same Prometheus dump as the latency histograms,
while keeping the original ``FIELDS``/``increment``/``snapshot``/``reset``
API — counters are core serving behavior and stay on even with
``VIZIER_OBSERVABILITY=0``.

Thread safety: the field→counter map is built once in ``__init__`` and
never mutated, so the vocabulary membership check is race-free by
construction (no lock needed to read an immutable dict); each counter
serializes its own increments.
"""

from __future__ import annotations

from typing import Dict, Optional

from vizier_tpu.observability import metrics as metrics_lib


class ServingStats:
    """Thread-safe monotonic counters with a dict snapshot API."""

    # The fixed counter vocabulary: a typo'd increment should fail loudly
    # rather than mint a new counter nobody reads.
    FIELDS = (
        "cache_hits",
        "cache_misses",
        "cache_evictions_ttl",
        "cache_evictions_lru",
        "cache_invalidations",
        # Config-hash turnover drops (shared compute tier: a frontend's
        # delete/recreate detected via the request's StudySpec hash).
        "cache_invalidations_config",
        "coalesced_requests",  # followers served from a shared computation
        "coalesced_computations",  # leader runs that had >= 1 follower
        "warm_trains",
        "cold_trains",
        # A suggest that found its study's fit cached (no completion since
        # the last train) and skipped the train: sequential and unbatchable.
        "cached_fit_suggests",
        # What the ARD train programs counted of their own work, read after
        # each timed train phase (optimizers.lbfgs.work_counts; nothing is
        # read, and these stay, with VIZIER_OBSERVABILITY_JAX=0).
        "train_programs",  # sequential or mesh: one a training suggest; fused: one a flush
        "train_loop_trips",  # trips of the batched L-BFGS loop: its largest row's
        "train_row_trips",  # rows x trips: what lockstep ran, padded slots too
        "train_row_iterations",  # iterations the rows needed
        "train_evaluations",  # loss evaluations of the rows (a Cholesky each)
        # A sequential training suggest enqueues its sweeps under its train:
        # sweeps_ahead / sequential_trains is the share whose train was still
        # running when the last sweep was enqueued (polled, never waited for;
        # counted whatever the knobs; a cached fit or a fused flush counts
        # in neither).
        "sequential_trains",
        "sweeps_ahead",
        # The policy's delta trial read (serving.policy): reused / (reused +
        # fetched) is the share of a study a suggest did not re-read.
        "trials_fetched",  # trial protos converted to pyvizier for an update
        "trials_reused",  # completed trials the cached designer already held
        "pending_trials_conditioned",  # ACTIVE trials handed to designer.update
        # The designer's encoded-row store (converters.EncodedTrials): reused /
        # (reused + encoded) is the share of a study a suggest did not
        # re-encode; 0 when the store is rebuilt on every suggest.
        "rows_encoded",  # trial rows a suggest encoded (new completed + ACTIVE)
        "rows_reused",  # completed rows it took from the store as held
        # A study's suggest turns (vizier_tpu.serving.study_turns).
        "suggest_turns",  # SuggestTrials that got their study's turn
        "suggest_turns_contended",  # ... after waiting behind another request
        # Reliability (vizier_tpu.reliability): retry/fallback/breaker/deadline.
        "retries",  # client-side RPC / suggest retries
        "designer_failures",  # designer computations that raised
        "fallbacks",  # suggestions served by the quasi-random fallback
        "breaker_open_transitions",
        "breaker_half_open_transitions",
        "breaker_close_transitions",
        "breaker_short_circuits",  # suggests skipped because a circuit was open
        "deadline_exceeded",  # ops completed with TRANSIENT: DEADLINE_EXCEEDED
        # Multi-tenant overload protection (vizier_tpu.serving.admission).
        "admission_sheds",  # requests shed with TRANSIENT: RESOURCE_EXHAUSTED
        "admission_deadline_sheds",  # sheds because the deadline was infeasible
        "admission_degraded",  # degraded-mode quasi-random serves
        "admission_transitions",  # overload state-machine transitions
        # Cross-study batching (vizier_tpu.parallel.batch_executor).
        "batch_flushes",  # bucket flushes (full / timeout / drain)
        # Of those, the flushes that held one real slot: handed back to the
        # sequential path unprepared, or run through the fused program with
        # every other slot a padded copy.
        "lone_handbacks",
        "lone_flushes",
        "batched_suggests",  # slots served from a shared vmapped program
        "batch_fallbacks",  # slots rerun sequentially after a batch failure
        "batch_slot_errors",  # slot-isolated prepare/finalize/NaN failures
        "mesh_flushes",  # flushes executed on a mesh placement worker
        # The designers' whole-host mesh (designers/gp_bandit.py: built
        # unasked where more than one device is visible). The width of that
        # mesh is ``mesh_devices`` in the runtime's snapshot.
        "mesh_suggests",  # GP suggests whose device programs ran on it
        # Scalable surrogates (vizier_tpu.surrogates).
        "sparse_suggests",  # suggests served by the sparse-GP posterior
        "nystrom_augments",  # picks of sparse UCB-PE suggests that joined the inducing set
        "surrogate_crossovers",  # exact<->sparse auto-switch transitions
        "fit_reads",  # deferred fits made a predictive inside a served suggest (0: nothing there reads one)
        # Speculative pre-compute (vizier_tpu.serving.speculative).
        "speculative_hits",  # suggests served from a parked pre-computed batch
        "speculative_misses",  # slot empty / frontier moved / count mismatch
        "speculative_stale",  # slots expired by max_speculation_age_s
        "speculative_cancelled",  # jobs superseded / dropped busy / shutdown
        "speculative_precomputes",  # speculative designer computations run
        "speculative_errors",  # speculative failures swallowed off-path
        "speculative_rearms",  # pre-computes re-armed by replica failover
    )

    def __init__(self, registry: Optional[metrics_lib.MetricsRegistry] = None):
        # A private registry by default so each stats object starts from
        # zero; the serving runtime passes its shared registry so the
        # counters land in the same Prometheus dump as the histograms.
        self._registry = registry or metrics_lib.MetricsRegistry()
        self._counters = {
            f: self._registry.counter(
                f"vizier_serving_{f}", help=f"Serving counter: {f}."
            )
            for f in self.FIELDS
        }

    @property
    def registry(self) -> metrics_lib.MetricsRegistry:
        """The backing registry (histogram co-location, Prometheus dump)."""
        return self._registry

    def increment(self, field: str, amount: int = 1) -> None:
        counter = self._counters.get(field)
        if counter is None:
            raise KeyError(f"Unknown serving counter: {field!r}")
        counter.inc(amount)

    def get(self, field: str) -> int:
        return int(self._counters[field].value())

    def snapshot(self) -> Dict[str, int]:
        """A point-in-time copy of every counter."""
        return {f: int(c.value()) for f, c in self._counters.items()}

    def reset(self) -> None:
        for counter in self._counters.values():
            counter.reset()
