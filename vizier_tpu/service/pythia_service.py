"""PythiaServicer: hosts suggestion policies.

Parity with ``/root/reference/vizier/_src/service/pythia_service.py:36``:
builds a ``ServicePolicySupporter`` for the study, asks the policy factory
for the algorithm's policy, converts proto⇄pythia types, and captures policy
errors into the response. (No forced float64 — our GP stack is f32/TPU-native
by design, unlike the reference's ``jax_enable_x64`` at ``:50-57``.)
"""

from __future__ import annotations

import dataclasses
import hashlib
import logging
import time
import traceback
from typing import Optional

from vizier_tpu import pyvizier as vz
from vizier_tpu.observability import tracing as tracing_lib
from vizier_tpu.pythia import policy as policy_lib
from vizier_tpu.reliability import deadline as deadline_lib
from vizier_tpu.reliability import errors as errors_lib
from vizier_tpu.reliability import fallback as fallback_lib
from vizier_tpu.service import policy_factory as policy_factory_lib
from vizier_tpu.service import proto_converters as pc
from vizier_tpu.service import service_policy_supporter
from vizier_tpu.service.protos import pythia_service_pb2, study_pb2
from vizier_tpu.service.protos import vizier_service_pb2
from vizier_tpu.serving import admission as admission_lib
from vizier_tpu.serving import speculative as speculative_lib

_logger = logging.getLogger(__name__)


class PythiaServicer:
    def __init__(
        self,
        vizier_service=None,
        policy_factory=None,
        serving_config=None,
        reliability_config=None,
        surrogate_config=None,
        mesh_config=None,
        admission_config=None,
    ):
        from vizier_tpu.serving import runtime as serving_runtime_lib

        self._vizier = vizier_service
        # The stateful serving runtime (designer cache + coalescer + stats +
        # per-study circuit breakers); ``serving_config`` (a
        # vizier_tpu.serving.ServingConfig) and ``reliability_config`` (a
        # vizier_tpu.reliability.ReliabilityConfig) disable parts or all of
        # it; ``surrogate_config`` (a vizier_tpu.surrogates.SurrogateConfig)
        # sets the exact↔sparse auto-switch every GP designer shares;
        # ``mesh_config`` (a vizier_tpu.parallel.mesh.MeshConfig) carves
        # the devices into batch-executor placements (VIZIER_MESH*; off =
        # the single-device seed path); ``admission_config`` (a
        # vizier_tpu.serving.admission.AdmissionConfig) arms the
        # multi-tenant overload-protection plane (VIZIER_ADMISSION*; off =
        # the bit-identical pre-admission path). None -> defaults with
        # env-var overrides.
        self._serving = serving_runtime_lib.ServingRuntime(
            serving_config,
            reliability=reliability_config,
            surrogates=surrogate_config,
            mesh=mesh_config,
            admission=admission_config,
        )
        self._policy_factory = policy_factory or policy_factory_lib.DefaultPolicyFactory(
            serving_runtime=self._serving
        )
        # Cache for policies that declare should_be_cached, keyed by
        # (study_name, algorithm, config_hash).
        self._policy_cache = {}
        # study_name -> (config hash, parsed StudyConfig). The hash (over
        # the serialized StudySpec) catches metadata updates AND the
        # shared-compute-tier delete/recreate turnover — so the hot path
        # skips a full Python proto->pyvizier parse per suggest without
        # ever serving a stale search space (see _parsed_study_config).
        self._config_cache = {}
        # Early-stopping policies cached per study (regression rule holds a
        # trained GBM; see EarlyStop dispatch).
        self._stopping_policies = {}
        self._bind_speculative()

    def connect_to_vizier(self, vizier_service) -> None:
        self._vizier = vizier_service
        self._bind_speculative()

    def _bind_speculative(self) -> None:
        """Connects the runtime's speculative engine to THIS servicer's
        compute path (needs a Vizier service to read frontiers from)."""
        engine = self._serving.speculative_engine
        if engine is None or self._vizier is None:
            return
        engine.bind(
            fingerprint_fn=self._speculative_fingerprint,
            compute_fn=self._speculative_compute,
            accept_fn=self._speculative_accept,
        )

    @property
    def serving_runtime(self):
        return self._serving

    def serving_stats(self) -> dict:
        """Snapshot of the serving counters + current cache population."""
        return self._serving.snapshot()

    def prometheus_text(self) -> str:
        """Serving counters + latency histograms, Prometheus text format."""
        return self._serving.prometheus_text()

    def prewarm(
        self,
        study_config: vz.StudyConfig,
        algorithm: str = "DEFAULT",
        counts=(1,),
        max_trials=None,
    ) -> list:
        """AOT-compiles the (batched) suggest programs for this study shape.

        Walks the padding-bucket grid at batch sizes {1, max}: a server
        prewarmed for its expected study shapes pays no XLA compile on the
        first real request. The designer factory comes from the compute-IR
        program registry (``vizier_tpu.compute.registry``): every
        registered program claiming ``algorithm`` contributes its
        ``prewarm_factory``, so a new DesignerProgram joins the prewarm
        walk by registering — no servicer edit. Returns the per-bucket
        compile report (empty when batching is off or no registered
        program covers the algorithm).
        """
        from vizier_tpu.compute import registry as compute_registry

        problem = study_config.to_problem()
        kwargs_fn = getattr(self._policy_factory, "_gp_designer_kwargs", None)
        kwargs = kwargs_fn() if kwargs_fn is not None else {}
        programs = compute_registry.programs_for_algorithm(algorithm or "DEFAULT")
        report = []
        seen_factories = set()
        for program in programs:
            # Same-designer programs (e.g. exact + sparse families) share
            # one walk: the factory's auto-switch decides which program
            # each synthetic bucket compiles, exactly like live studies.
            factory_key = type(program.prewarm_factory(problem, **kwargs))
            if factory_key in seen_factories:
                continue
            seen_factories.add(factory_key)
            report.extend(
                self._serving.prewarm_batching(
                    problem,
                    lambda p, _program=program: _program.prewarm_factory(
                        p, **kwargs
                    ),
                    counts=counts,
                    max_trials=max_trials,
                )
            )
        return report

    def shutdown(self) -> None:
        """Drains the serving runtime's batch executor (idempotent)."""
        self._serving.shutdown()

    def invalidate_study(self, study_name: str) -> None:
        """Drops every piece of per-study serving state (study deleted)."""
        self._serving.invalidate_study(study_name)
        self._stopping_policies.pop(study_name, None)
        self._config_cache.pop(study_name, None)
        for key in [k for k in self._policy_cache if k[0] == study_name]:
            del self._policy_cache[key]

    def _parsed_study_config(self, request) -> vz.StudyConfig:
        """The request's StudyConfig, cached by (study name, config hash).

        The hash (over the serialized StudySpec) is the cache's identity
        check AND the shared compute tier's staleness detector: against
        one shared Pythia, two frontends racing ``DeleteStudy``/
        ``CreateStudy`` for the same resource name have no invalidation
        RPC to this process, so a hash TURNOVER is the only signal that
        the name now means a different study. On turnover every per-study
        cache pinned to the previous incarnation is dropped — the parsed
        config, the policy cache, the stopping policies, and (through the
        runtime) the designer-state cache.
        """
        spec = request.study_descriptor.config
        spec_bytes = spec.SerializeToString()
        config_hash = hashlib.sha1(spec_bytes).hexdigest()[:16]
        study_name = request.study_name
        cached = self._config_cache.get(study_name)
        if cached is not None and cached[0] == config_hash:
            return cached[1]
        if cached is not None:
            # Same resource name, different config bytes: a delete/
            # recreate (or a metadata update, which can change policy
            # construction — e.g. the acquisition-budget override) from
            # ANY frontend. Drop state keyed to the stale incarnation.
            self._stopping_policies.pop(study_name, None)
            for key in [k for k in self._policy_cache if k[0] == study_name]:
                del self._policy_cache[key]
        config = pc.study_config_from_proto(spec)
        if study_name:
            self._config_cache[study_name] = (config_hash, config)
            self._serving.note_study_config(study_name, config_hash)
        return config

    def _request_config_hash(self, request) -> str:
        """The request's own config hash — NOT a read-back from the parse
        cache: two frontends racing different incarnations of one study
        name interleave freely here, and keying a policy by the OTHER
        request's hash would serve incarnation A under B's key."""
        spec_bytes = request.study_descriptor.config.SerializeToString()
        return hashlib.sha1(spec_bytes).hexdigest()[:16]

    def _get_policy(
        self,
        study_config: vz.StudyConfig,
        algorithm: str,
        study_name: str,
        config_hash: str = "",
    ) -> policy_lib.Policy:
        supporter = service_policy_supporter.ServicePolicySupporter(
            study_name, self._vizier
        )
        # Keyed by (study, algorithm, config hash): a cached policy must
        # die with the config incarnation it was constructed from.
        key = (study_name, algorithm, config_hash)
        cached = self._policy_cache.get(key)
        if cached is not None:
            return cached
        policy = self._policy_factory(
            study_config.to_problem(), algorithm, supporter, study_name
        )
        if policy.should_be_cached:
            self._policy_cache[key] = policy
        return policy

    def Suggest(
        self, request: pythia_service_pb2.PythiaSuggestRequest, context=None
    ) -> pythia_service_pb2.PythiaSuggestResponse:
        # Trace parentage comes from the request's wire context, NOT the
        # ambient contextvar: the deadline-bounded dispatch runs this method
        # on a fresh worker thread (ResponseWaiter), and a remote stub
        # crosses a process boundary — the proto field survives both.
        tracer = tracing_lib.get_tracer()
        parent = tracing_lib.parse_context(request.trace_context)
        t0 = time.perf_counter()
        with tracer.span(
            "pythia.suggest",
            parent=parent,
            study=request.study_name,
            algorithm=request.algorithm,
            count=int(request.count),
            deadline_remaining_secs=float(request.deadline_secs),
        ) as span:
            response = self._suggest_coalesced(request)
            if response.error:
                span.set_attribute("error", response.error.splitlines()[0][:200])
            trace_id = getattr(span, "trace_id", None)
        self._serving.observe_suggest_latency(
            "pythia", time.perf_counter() - t0, trace_id=trace_id
        )
        return response

    def _suggest_coalesced(
        self, request: pythia_service_pb2.PythiaSuggestRequest
    ) -> pythia_service_pb2.PythiaSuggestResponse:
        if not self._serving.config.coalescing:
            return self._suggest_compute(request)
        # Compute-level request coalescing: concurrent suggests against the
        # SAME study state (name, config incarnation, algorithm, trial
        # frontier, count) collapse onto one designer computation;
        # followers receive their own copy of the response (protos are
        # mutable and cross servicer threads). The config hash keeps two
        # frontends racing a delete/recreate of one study name from
        # coalescing onto the OTHER incarnation's computation.
        key = (
            "suggest",
            request.study_name,
            self._request_config_hash(request),
            request.algorithm,
            int(request.study_descriptor.max_trial_id),
            int(request.count),
        )

        def clone(resp):
            out = pythia_service_pb2.PythiaSuggestResponse()
            out.CopyFrom(resp)
            return out

        return self._serving.coalescer.coalesce(
            key,
            lambda: self._suggest_compute(request),
            clone=clone,
            span_name="pythia.suggest_compute",
        )

    # -- speculative pre-compute (vizier_tpu.serving.speculative) -----------

    def notify_trial_event(self, study_name: str) -> None:
        """A completion/measurement moved the study's frontier: drop the
        parked batch and enqueue a pre-compute for the new frontier."""
        engine = self._serving.speculative_engine
        if engine is not None and engine.bound:
            engine.notify_completion(study_name)

    def _trial_frontier(self, study_name: str):
        """``(completed_ids, active_ids, max_trial_id)`` via the connected
        Vizier service (copy-free fast path when in-process)."""
        frontier = getattr(self._vizier, "trial_frontier", None)
        if frontier is not None:
            return frontier(study_name)
        listing = self._vizier.ListTrials(
            vizier_service_pb2.ListTrialsRequest(parent=study_name)
        )
        completed, active, max_id = [], [], 0
        for t in listing.trials:
            max_id = max(max_id, int(t.id))
            if t.state in (study_pb2.Trial.SUCCEEDED, study_pb2.Trial.INFEASIBLE):
                completed.append(int(t.id))
            elif t.state == study_pb2.Trial.ACTIVE:
                active.append(int(t.id))
        return completed, active, max_id

    def _speculative_fingerprint(self, study_name: str):
        """Job-side frontier read: the fingerprint the parked batch will be
        served under, captured BEFORE the compute (conservative: anything
        landing after this point makes the slot a serve-time mismatch)."""
        study = self._vizier.GetStudy(
            vizier_service_pb2.GetStudyRequest(name=study_name)
        )
        completed, active, max_id = self._trial_frontier(study_name)
        fingerprint = speculative_lib.make_fingerprint(
            study.study_spec.SerializeToString(), completed, active
        )
        return fingerprint, max_id

    def _speculative_compute(
        self, study_name: str, count: int, max_trial_id: int
    ) -> Optional[pythia_service_pb2.PythiaSuggestResponse]:
        """Runs one speculative job through the EXACT live suggest path
        (coalescer → policy → designer cache → batch executor), so a hit
        is the live compute run early — same designer state mutations,
        same RNG order, same batching buckets (at low flush priority via
        the speculative-scope thread flag the engine sets)."""
        study = self._vizier.GetStudy(
            vizier_service_pb2.GetStudyRequest(name=study_name)
        )
        if study.state != study_pb2.Study.ACTIVE:
            return None
        preq = pythia_service_pb2.PythiaSuggestRequest(
            count=count,
            algorithm=study.study_spec.algorithm,
            study_name=study_name,
        )
        preq.study_descriptor.config.CopyFrom(study.study_spec)
        preq.study_descriptor.guid = study_name
        preq.study_descriptor.max_trial_id = max_trial_id
        return self._suggest_coalesced(preq)

    def _speculative_accept(
        self, response: pythia_service_pb2.PythiaSuggestResponse
    ) -> Optional[int]:
        """Batch size when the response is servable, else None. A response
        carrying an error, no suggestions, or the reliability fallback
        stamp must never be parked: serving cached quasi-random picks when
        a live compute might succeed would silently degrade the study."""
        if response is None or response.error or not response.suggestions:
            return None
        for suggestion in response.suggestions:
            for kv in suggestion.metadata:
                if (
                    kv.key == fallback_lib.FALLBACK_KEY
                    and kv.string_value == fallback_lib.FALLBACK_VALUE
                ):
                    return None
        return len(response.suggestions)

    def _try_speculative_serve(
        self, engine, request: pythia_service_pb2.PythiaSuggestRequest
    ) -> Optional[pythia_service_pb2.PythiaSuggestResponse]:
        """The microsecond path: pop the parked batch when the request's
        frontier fingerprint (current completed/active sets + config hash)
        matches the one it was computed for. Any failure here decays to
        the live compute — the speculative layer must never break a
        suggest."""
        study_name = request.study_name
        if not study_name:
            return None
        count = max(1, int(request.count))
        try:
            engine.note_live_suggest(study_name, count)
            completed, active, _ = self._trial_frontier(study_name)
            fingerprint = speculative_lib.make_fingerprint(
                request.study_descriptor.config.SerializeToString(),
                completed,
                active,
            )
            response, outcome = engine.try_serve(study_name, count, fingerprint)
        except Exception:
            _logger.warning(
                "Speculative serve check failed for %s; computing live.",
                study_name,
                exc_info=True,
            )
            return None
        if response is None:
            return None
        del outcome  # "hit" — the only outcome with a response
        return self._stamp_speculative(response, count)

    @staticmethod
    def _stamp_speculative(
        response: pythia_service_pb2.PythiaSuggestResponse, count: int
    ) -> pythia_service_pb2.PythiaSuggestResponse:
        """A private copy of the parked response, reconciled to ``count``
        (serving the batch prefix when the client asked for fewer) and
        stamped ``ns "serving": speculative=hit`` per suggestion so served
        speculative picks stay auditable in trial metadata."""
        out = pythia_service_pb2.PythiaSuggestResponse()
        out.CopyFrom(response)
        if count < len(out.suggestions):
            del out.suggestions[count:]
        stamp = vz.Metadata()
        stamp.ns(speculative_lib.SPECULATIVE_NAMESPACE)[
            speculative_lib.SPECULATIVE_KEY
        ] = speculative_lib.SPECULATIVE_HIT_VALUE
        key_values = pc.metadata_to_key_values(stamp)
        for suggestion in out.suggestions:
            suggestion.metadata.extend(key_values)
        return out

    def _suggest_compute(
        self, request: pythia_service_pb2.PythiaSuggestRequest
    ) -> pythia_service_pb2.PythiaSuggestResponse:
        """Speculative serve check wrapped around the live compute.

        With no engine (VIZIER_SPECULATIVE=0, the default) this is a
        direct tail call into the live path — bit-identical to the
        pre-speculation tree. Inside a speculative job's own compute the
        check is skipped too (a job must compute, not self-serve)."""
        engine = self._serving.speculative_engine
        if (
            engine is None
            or not engine.bound
            or speculative_lib.in_speculative_compute()
        ):
            return self._suggest_compute_admitted(request)
        t0 = time.perf_counter()
        served = self._try_speculative_serve(engine, request)
        if served is not None:
            engine.observe_suggest_latency("hit", time.perf_counter() - t0)
            return served
        response = self._suggest_compute_admitted(request)
        engine.observe_suggest_latency("miss", time.perf_counter() - t0)
        if not response.error:
            # "Cache fill" trigger (opt-in): the live compute just
            # refreshed the designer entry; pre-compute the batch a second
            # client at the post-suggest frontier would receive.
            engine.notify_fill(request.study_name)
        return response

    # -- multi-tenant admission (vizier_tpu.serving.admission) ---------------

    def _suggest_compute_admitted(
        self, request: pythia_service_pb2.PythiaSuggestRequest
    ) -> pythia_service_pb2.PythiaSuggestResponse:
        """The admission gate around the live designer computation.

        With no controller (VIZIER_ADMISSION=0, the default) this is a
        direct tail call — bit-identical to the pre-admission tree.
        Speculative jobs bypass it too: the speculative engine has its own
        executor-backed admission gate, and a background pre-compute must
        never consume a live in-flight slot.

        A SHED verdict returns the typed ``TRANSIENT: RESOURCE_EXHAUSTED``
        error (retry-after hint included) WITHOUT touching the study's
        circuit breaker — shed is a capacity condition, not a designer
        failure. A DEGRADE verdict (sustained-overload state machine,
        low-priority tenant) serves the seeded quasi-random fallback,
        stamped in metadata, so the remaining compute budget goes to
        in-SLO tenants.
        """
        admission = self._serving.admission
        if admission is None or speculative_lib.in_speculative_compute():
            return self._suggest_compute_live(request)
        tenant = admission_lib.tenant_of(request.study_name)
        decision = admission.decide(
            tenant,
            deadline_secs=float(request.deadline_secs),
            study=request.study_name,
        )
        if decision.outcome == admission_lib.SHED:
            tracing_lib.add_current_event(
                "admission.shed", tenant=tenant, reason=decision.reason
            )
            response = pythia_service_pb2.PythiaSuggestResponse()
            response.error = errors_lib.format_op_error(decision.error())
            return response
        if decision.outcome == admission_lib.DEGRADE:
            tracing_lib.add_current_event("admission.degraded", tenant=tenant)
            try:
                config = self._parsed_study_config(request)
            except Exception as e:  # permanent, same contract as setup
                response = pythia_service_pb2.PythiaSuggestResponse()
                response.error = errors_lib.format_op_error(e)
                return response
            response = self._fallback_response(
                config, request, "admission_degraded"
            )
            self._stamp_degraded(response)
            return response
        with admission.in_flight(decision):
            return self._suggest_compute_live(request)

    @staticmethod
    def _stamp_degraded(
        response: pythia_service_pb2.PythiaSuggestResponse,
    ) -> None:
        """``ns "admission": degraded=quasi_random`` on every suggestion,
        next to the reliability fallback stamp — degraded-mode serves stay
        auditable in trial metadata."""
        stamp = vz.Metadata()
        stamp.ns(admission_lib.ADMISSION_NAMESPACE)[
            admission_lib.ADMISSION_KEY
        ] = admission_lib.ADMISSION_VALUE
        key_values = pc.metadata_to_key_values(stamp)
        for suggestion in response.suggestions:
            suggestion.metadata.extend(key_values)

    def _suggest_compute_live(
        self, request: pythia_service_pb2.PythiaSuggestRequest
    ) -> pythia_service_pb2.PythiaSuggestResponse:
        response = pythia_service_pb2.PythiaSuggestResponse()
        reliability = self._serving.reliability
        stats = self._serving.stats

        # Config parsing and policy construction fail HARD: an invalid
        # search space or unknown algorithm is permanent — retrying or
        # falling back would serve a misconfigured study forever.
        try:
            config = self._parsed_study_config(request)
            algorithm = request.algorithm or config.algorithm
            if algorithm != config.algorithm:
                # The cached config is shared across requests (and threads):
                # a per-request algorithm override goes on a shallow copy so
                # it never leaks into later requests for the same study.
                config = dataclasses.replace(config, algorithm=algorithm)
            policy = self._get_policy(
                config,
                algorithm,
                request.study_name,
                self._request_config_hash(request),
            )
            descriptor = vz.StudyDescriptor(
                config=config,
                guid=request.study_descriptor.guid,
                max_trial_id=int(request.study_descriptor.max_trial_id),
            )
        except Exception as e:
            _logger.warning("Pythia Suggest setup failed: %s", traceback.format_exc())
            response.error = errors_lib.format_op_error(e)
            return response

        # from_wire, not from_budget: a NEGATIVE wire budget means the
        # caller's deadline already expired at the sender — the dispatch
        # check below then sheds before any designer computation runs,
        # instead of reading "expired" as "no deadline".
        deadline = (
            deadline_lib.Deadline.from_wire(request.deadline_secs)
            if reliability.deadlines_on
            else deadline_lib.Deadline.none()
        )
        breaker = (
            self._serving.breakers.get(request.study_name)
            if reliability.breaker_on
            else None
        )

        # Open circuit: skip the designer computation entirely (it would
        # very likely fail and burn the client's budget) and degrade.
        if breaker is not None and not breaker.allow():
            stats.increment("breaker_short_circuits")
            tracing_lib.add_current_event(
                "breaker.short_circuit", study=request.study_name
            )
            if reliability.fallback_on:
                return self._fallback_response(config, request, "circuit_open")
            response.error = errors_lib.format_op_error(
                errors_lib.CircuitOpenError(
                    errors_lib.mark_transient(
                        f"CIRCUIT_OPEN: breaker for study "
                        f"{request.study_name!r} is open; designer "
                        "computation skipped."
                    )
                )
            )
            return response

        try:
            # Budget already burned upstream (queueing, drain, transport):
            # not a designer failure, so no breaker record.
            deadline.check(f"suggest dispatch for {request.study_name!r}")
        except errors_lib.DeadlineExceededError as e:
            stats.increment("deadline_exceeded")
            tracing_lib.add_current_event("deadline.exceeded", at="dispatch")
            response.error = errors_lib.format_op_error(e)
            return response

        try:
            decision = policy.suggest(
                policy_lib.SuggestRequest(
                    study_descriptor=descriptor, count=int(request.count)
                )
            )
            # The over-budget computation completes the op with a typed
            # error: the client stopped waiting at its deadline, so
            # returning suggestions now would hand out trials nobody runs.
            # A chronically slow designer also counts against the breaker.
            deadline.check(
                f"suggest computation for {request.study_name!r}"
            )
        except errors_lib.DeadlineExceededError as e:
            stats.increment("deadline_exceeded")
            tracing_lib.add_current_event("deadline.exceeded", at="computation")
            if breaker is not None:
                breaker.record_failure()
            response.error = errors_lib.format_op_error(e)
            return response
        except Exception as e:
            _logger.warning("Pythia Suggest failed: %s", traceback.format_exc())
            stats.increment("designer_failures")
            tracing_lib.add_current_event(
                "designer.failure", error_type=type(e).__name__
            )
            if breaker is not None:
                breaker.record_failure()
            if reliability.fallback_on:
                return self._fallback_response(
                    config,
                    request,
                    f"designer_error:{type(e).__name__}",
                    error=e,
                )
            response.error = errors_lib.format_op_error(e)
            return response

        if breaker is not None:
            breaker.record_success()
        for s in decision.suggestions:
            response.suggestions.add().CopyFrom(pc.trial_suggestion_to_proto(s))
        self._append_metadata_deltas(response, decision.metadata)
        return response

    def _fallback_response(
        self,
        config: vz.StudyConfig,
        request: pythia_service_pb2.PythiaSuggestRequest,
        reason: str,
        error: Optional[BaseException] = None,
    ) -> pythia_service_pb2.PythiaSuggestResponse:
        """Graceful degradation: seeded quasi-random, stamped + counted.

        ``error`` (the designer exception being degraded around, if any)
        rides the event, the flight-recorder entry and the log line, so a
        device-side compile error or OOM stays legible behind the counter.
        """
        response = pythia_service_pb2.PythiaSuggestResponse()
        try:
            suggestions = fallback_lib.suggest_fallback(
                config.to_problem(),
                max(1, int(request.count)),
                study_name=request.study_name,
                max_trial_id=int(request.study_descriptor.max_trial_id),
                reason=reason,
            )
        except Exception as e:  # fallback itself failed: surface as transient
            _logger.warning(
                "Quasi-random fallback failed: %s", traceback.format_exc()
            )
            response.error = errors_lib.format_op_error(
                errors_lib.TransientError(
                    errors_lib.mark_transient(
                        f"FALLBACK_FAILED ({reason}): {type(e).__name__}: {e}"
                    )
                )
            )
            return response
        self._serving.stats.increment("fallbacks", len(suggestions))
        cause = (
            {"error_type": type(error).__name__, "error_message": str(error)[:500]}
            if error is not None
            else {}
        )
        tracing_lib.add_current_event(
            "fallback.served", reason=reason, count=len(suggestions), **cause
        )
        self._serving.flight_recorder.record(
            request.study_name, "fallback", reason=reason,
            count=len(suggestions), **cause,
        )
        _logger.warning(
            "Serving %d quasi-random fallback suggestion(s) for %s (%s)%s.",
            len(suggestions),
            request.study_name,
            reason,
            f": {cause['error_message']}" if cause else "",
        )
        for s in suggestions:
            response.suggestions.add().CopyFrom(pc.trial_suggestion_to_proto(s))
        return response

    def EarlyStop(
        self, request: pythia_service_pb2.PythiaEarlyStopRequest, context=None
    ) -> pythia_service_pb2.PythiaEarlyStopResponse:
        response = pythia_service_pb2.PythiaEarlyStopResponse()
        try:
            # Through the parse cache (not a fresh proto->pyvizier parse):
            # EarlyStop polls ride the same (study, config-hash) identity
            # as Suggest, so a delete/recreate turnover also drops the
            # cached stopping policies below.
            config = self._parsed_study_config(request)
            if config.automated_stopping_config is not None:
                # Studies with a stopping spec pick their rule (median curve
                # or curve-regression); otherwise the algorithm's own policy
                # decides.
                from vizier_tpu.algorithms import early_stopping

                stopping = config.automated_stopping_config
                if stopping.rule == "regression":
                    # Cached per study: the policy holds a trained GBM that
                    # repeated polls between completions must reuse.
                    policy = self._stopping_policies.get(request.study_name)
                    if policy is None:
                        policy = early_stopping.RegressionEarlyStopPolicy(
                            supporter=service_policy_supporter.ServicePolicySupporter(
                                request.study_name, self._vizier
                            ),
                            min_num_trials=stopping.min_num_trials,
                        )
                        self._stopping_policies[request.study_name] = policy
                else:
                    policy = early_stopping.MedianEarlyStopPolicy(
                        supporter=service_policy_supporter.ServicePolicySupporter(
                            request.study_name, self._vizier
                        ),
                        use_steps=stopping.use_steps,
                        min_num_trials=stopping.min_num_trials,
                    )
            else:
                policy = self._get_policy(
                    config,
                    request.algorithm or config.algorithm,
                    request.study_name,
                    self._request_config_hash(request),
                )
            descriptor = vz.StudyDescriptor(
                config=config,
                guid=request.study_descriptor.guid,
                max_trial_id=int(request.study_descriptor.max_trial_id),
            )
            decisions = policy.early_stop(
                policy_lib.EarlyStopRequest(
                    study_descriptor=descriptor,
                    trial_ids=frozenset(int(i) for i in request.trial_ids),
                )
            )
            for d in decisions.decisions:
                dp = response.decisions.add()
                dp.id = d.id
                dp.should_stop = d.should_stop
                dp.reason = d.reason
        except Exception as e:
            _logger.warning("Pythia EarlyStop failed: %s", traceback.format_exc())
            response.error = errors_lib.format_op_error(e)
        return response

    def Ping(
        self, request: pythia_service_pb2.PingRequest, context=None
    ) -> pythia_service_pb2.PingResponse:
        return pythia_service_pb2.PingResponse()

    @staticmethod
    def _append_metadata_deltas(
        response: pythia_service_pb2.PythiaSuggestResponse, delta: vz.MetadataDelta
    ) -> None:
        if delta.on_study.namespaces():
            dp = response.metadata_deltas.add()
            dp.trial_id = 0
            dp.key_values.extend(pc.metadata_to_key_values(delta.on_study))
        for trial_id, md in delta.on_trials.items():
            if md.namespaces():
                dp = response.metadata_deltas.add()
                dp.trial_id = trial_id
                dp.key_values.extend(pc.metadata_to_key_values(md))
