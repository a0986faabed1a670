"""ServingRuntime: one object bundling cache + coalescer + stats + config.

The Pythia servicer owns one runtime per process; the policy factory and
the serving policy share it so every counter lands in one place and
``DeleteStudy`` invalidation reaches the real cache. The reliability layer
(per-study circuit breakers + its config) lives here too, so breaker
transitions land in the same stats sink and study invalidation drops the
breaker along with the designer state. The observability layer hangs off
the same object: one metrics registry backs the serving counters AND the
latency histograms (cache lookups, coalescer waits, per-hop suggest
latency), all dumped together by :meth:`prometheus_text`.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional, Sequence

from vizier_tpu.observability import config as obs_config_lib
from vizier_tpu.observability import flight_recorder as recorder_lib
from vizier_tpu.observability import metrics as metrics_lib
from vizier_tpu.observability import slo as slo_lib
from vizier_tpu.observability import tracing as tracing_lib
from vizier_tpu.reliability import breaker as breaker_lib
from vizier_tpu.reliability import config as reliability_config_lib
from vizier_tpu.serving import admission as admission_lib
from vizier_tpu.serving import coalescer as coalescer_lib
from vizier_tpu.serving import compile_cache
from vizier_tpu.serving import config as config_lib
from vizier_tpu.serving import designer_cache as cache_lib
from vizier_tpu.serving import speculative as speculative_lib
from vizier_tpu.serving import stats as stats_lib
from vizier_tpu.surrogates import config as surrogate_config_lib


class ServingRuntime:
    """Shared serving state for one Pythia servicer."""

    def __init__(
        self,
        config: Optional[config_lib.ServingConfig] = None,
        stats: Optional[stats_lib.ServingStats] = None,
        reliability: Optional[reliability_config_lib.ReliabilityConfig] = None,
        observability: Optional[obs_config_lib.ObservabilityConfig] = None,
        surrogates: Optional[surrogate_config_lib.SurrogateConfig] = None,
        speculative: Optional[speculative_lib.SpeculativeConfig] = None,
        mesh: Optional[Any] = None,  # parallel.mesh.MeshConfig
        slo: Optional[slo_lib.SloConfig] = None,
        admission: Optional[admission_lib.AdmissionConfig] = None,
    ):
        self.config = config or config_lib.ServingConfig.from_env()
        self.observability = (
            observability or obs_config_lib.ObservabilityConfig.from_env()
        )
        # Scalable-surrogate auto-switch (vizier_tpu.surrogates): threaded
        # into every GP designer the policy factory builds, so the whole
        # serving tier shares one exact↔sparse policy. VIZIER_SPARSE=0
        # keeps every study on the exact path (the seed behavior).
        self.surrogates = (
            surrogates or surrogate_config_lib.SurrogateConfig.from_env()
        )
        self.stats = stats or stats_lib.ServingStats()
        # One registry for this runtime's whole metric surface. A caller
        # passing pre-existing stats brings its registry along so counters
        # and histograms still land in one dump.
        self.metrics: metrics_lib.MetricsRegistry = self.stats.registry
        # The tracer's stage spans observe into the same registry
        # (vizier_suggest_stage_seconds), like the executor's and the
        # coalescer's histograms below.
        if self.observability.metrics_on:
            tracing_lib.get_tracer().bind_registry(self.metrics)
        self.reliability = (
            reliability or reliability_config_lib.ReliabilityConfig.from_env()
        )
        self.designer_cache = cache_lib.DesignerStateCache(
            max_entries=self.config.cache_max_entries,
            ttl_seconds=self.config.cache_ttl_seconds,
            stats=self.stats,
            observe_latency=self.observability.metrics_on,
        )
        self.coalescer = coalescer_lib.RequestCoalescer(
            stats=self.stats,
            observe_latency=self.observability.metrics_on,
        )
        self.breakers = breaker_lib.CircuitBreakerRegistry(
            failure_threshold=self.reliability.breaker_failure_threshold,
            window_secs=self.reliability.breaker_window_secs,
            cooldown_secs=self.reliability.breaker_cooldown_secs,
            half_open_probes=self.reliability.breaker_half_open_probes,
            stats=self.stats,
        )
        self._suggest_latency = self.metrics.histogram(
            "vizier_suggest_latency_seconds",
            help="SuggestTrials wall time per hop (service, pythia).",
        )
        # A study's suggest turns (serving.study_turns; taken by the Vizier
        # servicer): how long a request stood in line, how long it held.
        self._turn_wait = self.metrics.histogram(
            "vizier_study_turn_wait_seconds",
            help="SuggestTrials arrival until its study's suggest turn.",
        )
        self._turn_held = self.metrics.histogram(
            "vizier_study_turn_seconds",
            help="Time a SuggestTrials held its study's turn (claim to write).",
        )
        # The width of the designers' whole-host mesh, as the last GP
        # suggest served on one reported it (serving.policy); 0 until then.
        self._mesh_devices = self.metrics.gauge(
            "vizier_serving_mesh_devices",
            help="Devices of the designer mesh GP suggests run on (0: none has).",
        )
        # Multi-tenant overload protection (vizier_tpu.serving.admission):
        # bounded in-flight admission + deadline-aware shedding + the
        # healthy→shedding→degraded state machine at the Pythia dispatch
        # boundary, and the weighted fair-share plane inside the batch
        # executor. Off by default (VIZIER_ADMISSION=0): no controller,
        # the bit-identical pre-admission path.
        self.flight_recorder = recorder_lib.get_recorder()
        admission_config = admission or admission_lib.AdmissionConfig.from_env()
        self.admission = None
        if admission_config.enabled:
            self.admission = admission_lib.AdmissionController(
                admission_config,
                stats=self.stats,
                metrics=(self.metrics if self.observability.metrics_on else None),
                recorder=self.flight_recorder,
                compute_p50_fn=lambda: self._suggest_latency.percentile(
                    50, hop="pythia"
                ),
                queue_depth_fn=self._live_queue_depth,
            )
        # JAX persistent compilation cache: survive process restarts so a
        # restarted server pays zero XLA compiles for known buckets.
        # JAX_COMPILATION_CACHE_DIR outranks the repo's own setting; with
        # neither, a bare runtime leaves the cache off (compile_cache).
        self.compilation_cache_dir = compile_cache.configure(
            self.config.compilation_cache_dir
        )
        # Cross-study batch executor: concurrent same-bucket designer
        # computations share ONE vmapped device program. None = batching
        # off (VIZIER_BATCHING=0): the exact per-study path. The mesh
        # execution plane (VIZIER_MESH=1, parallel.mesh.MeshConfig) carves
        # the process's devices into placements the executor schedules
        # buckets over; off (the default) = the single-device seed path.
        self.batch_executor = None
        if self.config.batching:
            from vizier_tpu.parallel import batch_executor as batch_executor_lib
            from vizier_tpu.parallel import mesh as mesh_lib

            self.mesh = mesh or mesh_lib.MeshConfig.from_env()
            self.batch_executor = batch_executor_lib.BatchExecutor(
                max_batch_size=self.config.batch_max_size,
                max_wait_ms=self.config.batch_max_wait_ms,
                pad_partial=self.config.batch_pad_partial,
                stats=self.stats,
                metrics=(
                    self.metrics if self.observability.metrics_on else None
                ),
                mesh=self.mesh,
                admission=self.admission,
            )
        else:
            self.mesh = mesh
        # Speculative pre-compute pipeline (vizier_tpu.serving.speculative):
        # after each completion, the NEXT suggestion batch is computed in
        # the background and served from the designer-cache entry. Requires
        # the cache (the slot lives on its entries); None = off (the
        # default, VIZIER_SPECULATIVE=0): the exact request path.
        self.speculative = (
            speculative or speculative_lib.SpeculativeConfig.from_env()
        )
        self.speculative_engine = None
        if self.speculative.speculative and self.config.designer_cache:
            self.speculative_engine = speculative_lib.SpeculativeEngine(
                config=self.speculative,
                cache=self.designer_cache,
                stats=self.stats,
                metrics=(self.metrics if self.observability.metrics_on else None),
                executor=self.batch_executor,
            )
        # Fleet observability plane: the process-global flight recorder
        # (grabbed above, no-op unless VIZIER_FLIGHT_RECORDER=1) and the
        # SLO engine (VIZIER_SLO=1) evaluating declarative objectives over
        # sliding windows of this runtime's metrics registry, with
        # breach-triggered black-box dumps. Both off by default = today's
        # behavior.
        self.slo = slo or slo_lib.SloConfig.from_env()
        self.slo_engine = None
        if self.slo.enabled:
            self.slo_engine = slo_lib.SloEngine(
                config=self.slo,
                registry=self.metrics,
                recorder=self.flight_recorder,
            )
            self.slo_engine.start()
        self._prewarmed_shapes: set = set()
        self._prewarm_lock = threading.Lock()
        self._prewarm_threads: List[threading.Thread] = []

    @property
    def compilation_cache_active(self) -> bool:
        return self.compilation_cache_dir is not None

    # -- compile prewarm ----------------------------------------------------

    def prewarm_batching(
        self,
        problem: Any,
        designer_factory: Callable[..., Any],
        *,
        max_trials: Optional[int] = None,
        counts: Sequence[int] = (1,),
    ) -> List[dict]:
        """Walks the padding-bucket grid for ``problem`` and AOT-compiles the
        batched suggest programs at batch sizes {1, max} so first-request
        latency pays no XLA compile. Returns the per-bucket compile report."""
        if self.batch_executor is None:
            return []
        return self.batch_executor.prewarm(
            problem,
            designer_factory,
            max_trials=max_trials or self.config.batching_prewarm_max_trials,
            counts=counts,
        )

    def maybe_prewarm_batching_async(
        self, problem: Any, designer_factory: Callable[..., Any]
    ) -> bool:
        """Background prewarm, once per distinct search-space shape; used by
        the policy factory when ``config.batching_prewarm`` is on. Returns
        True when a prewarm thread was started."""
        if self.batch_executor is None or not self.config.batching_prewarm:
            return False
        shape_key = tuple(
            sorted((p.name, str(p.type)) for p in problem.search_space.parameters)
        )
        with self._prewarm_lock:
            if shape_key in self._prewarmed_shapes:
                return False
            self._prewarmed_shapes.add(shape_key)
        thread = threading.Thread(
            target=lambda: self.prewarm_batching(problem, designer_factory),
            name="vizier-batch-prewarm",
            daemon=True,
        )
        with self._prewarm_lock:
            self._prewarm_threads.append(thread)
        thread.start()
        return True

    def shutdown(self) -> None:
        """Joins in-flight prewarm compiles (an XLA compile aborted by
        interpreter teardown SIGABRTs the process), cancels speculative
        jobs and joins their worker pool, and drains the batch executor —
        in that order, so no speculative job can submit into a closing
        executor. Idempotent."""
        if self.slo_engine is not None:
            self.slo_engine.close()
        if self.speculative_engine is not None:
            self.speculative_engine.close()
        with self._prewarm_lock:
            threads, self._prewarm_threads = self._prewarm_threads, []
        for thread in threads:
            thread.join(timeout=120.0)
        if self.batch_executor is not None:
            self.batch_executor.close()

    def _live_queue_depth(self) -> int:
        """Queued live executor slots (0 with batching off) — the
        admission controller's deadline-shed wait estimator input."""
        executor = self.batch_executor
        if executor is None:
            return 0
        return executor.live_pending()

    def observe_suggest_latency(
        self,
        hop: str,
        seconds: float,
        trace_id: Optional[str] = None,
        tenant: Optional[str] = None,
    ) -> None:
        """Records one suggest's wall time at a hop (no-op when metrics are
        off — the off switch must cost nothing). ``trace_id`` makes the
        observation an exemplar candidate: the hop's top-latency samples
        keep their trace ids so an SLO breach links to real traces.
        ``tenant`` (set by the service hop only while admission is armed)
        splits the series per tenant so the SLO engine can hold a
        per-tenant p99 objective; None keeps the seed series unchanged."""
        if self.observability.metrics_on:
            labels = {"hop": hop}
            if tenant is not None:
                labels["tenant"] = tenant
            self._suggest_latency.observe(seconds, trace_id=trace_id, **labels)

    def observe_turn_wait(self, seconds: float, contended: bool) -> None:
        """A study's suggest turn was granted (``study_turns.StudyTurn``):
        arrival -> turn, and whether it stood behind another request. The
        counters stay on with metrics off, like every serving counter."""
        self.stats.increment("suggest_turns")
        if contended:
            self.stats.increment("suggest_turns_contended")
        if self.observability.metrics_on:
            self._turn_wait.observe(seconds)

    def observe_turn_held(self, seconds: float) -> None:
        """A study's suggest turn was given up after ``seconds``."""
        if self.observability.metrics_on:
            self._turn_held.observe(seconds)

    def slo_report(self) -> Dict[str, Any]:
        """Evaluates the armed SLOs now and returns the JSON-ready report
        (``{"armed": False}`` when VIZIER_SLO is off)."""
        if self.slo_engine is None:
            return {"armed": False}
        return self.slo_engine.report()

    def suggest_latency_histogram(self) -> metrics_lib.Histogram:
        return self._suggest_latency

    def invalidate_study(self, study_name: str) -> bool:
        """Drops the study's designer state + breaker + speculative job
        (study deleted)."""
        self.breakers.invalidate(study_name)
        if self.speculative_engine is not None:
            self.speculative_engine.invalidate(study_name, reason="delete_study")
        self.flight_recorder.invalidate(study_name)
        return self.designer_cache.invalidate(study_name)

    def note_study_config(self, study_name: str, config_hash: str) -> bool:
        """Pins per-study serving state to one StudyConfig incarnation.

        Called by the servicer with every request's parsed-config hash.
        On a hash turnover — the shared-compute-tier delete/recreate race,
        where another frontend's ``DeleteStudy`` invalidation cannot reach
        this process — everything TRAINED against the previous incarnation
        (designer entry, breaker, speculative slot) is dropped so it is
        never served again. The flight-recorder ring survives: it is
        forensic history keyed by time, not derived state, and a metadata
        update (a legitimate hash turnover — e.g. the budget-policy knobs
        ride metadata) must not erase the study's earlier events. Returns
        True when a turnover was detected.
        """
        changed = self.designer_cache.note_config_hash(study_name, config_hash)
        if changed:
            # note_config_hash already dropped the designer entry itself.
            self.breakers.invalidate(study_name)
            if self.speculative_engine is not None:
                self.speculative_engine.invalidate(
                    study_name, reason="config_turnover"
                )
        return changed

    def speculative_invalidate(self, study_name: str, reason: str = "") -> None:
        """Drops only the study's speculative slot/job (frontier surgery,
        surrogate crossover); the designer entry itself stays live."""
        if self.speculative_engine is not None:
            self.speculative_engine.invalidate(study_name, reason=reason)

    def note_mesh_suggests(self, suggests: int, devices: int) -> None:
        """``suggests`` GP suggests ran on a designer mesh of ``devices``."""
        self.stats.increment("mesh_suggests", suggests)
        self._mesh_devices.set(devices)

    def snapshot(self) -> Dict[str, int]:
        """All counters plus the current cache/breaker population and the
        width of the designer mesh (0 when no suggest has run on one)."""
        out = self.stats.snapshot()
        out["cached_studies"] = len(self.designer_cache)
        out["open_breakers"] = self.breakers.open_count()
        out["mesh_devices"] = int(self._mesh_devices.value())
        return out

    def admission_snapshot(self) -> Dict[str, Any]:
        """The admission controller's JSON-ready state (per-tenant
        sheds/admits, overload state, transitions); ``{"enabled": False}``
        with the plane off."""
        if self.admission is None:
            return {"enabled": False}
        return self.admission.snapshot()

    def prometheus_text(self) -> str:
        """Every serving counter + latency histogram, Prometheus format."""
        return self.metrics.prometheus_text()
