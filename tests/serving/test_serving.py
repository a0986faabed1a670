"""Serving runtime: cache TTL/LRU/invalidation, coalescing, serving stats."""

import threading
import time

import pytest

from vizier_tpu import pyvizier as vz
from vizier_tpu.pythia import policy as policy_lib
from vizier_tpu.serving import ServingConfig
from vizier_tpu.serving import ServingStats
from vizier_tpu.serving.coalescer import RequestCoalescer
from vizier_tpu.serving.designer_cache import DesignerStateCache
from vizier_tpu.service import proto_converters as pc
from vizier_tpu.service import pythia_service, vizier_service
from vizier_tpu.service.protos import vizier_service_pb2

STUDY = "owners/o/studies/s"


def _study_config(algorithm="DEFAULT", num_params=2):
    config = vz.StudyConfig(algorithm=algorithm)
    for d in range(num_params):
        config.search_space.root.add_float_param(f"x{d}", 0.0, 1.0)
    config.metric_information.append(
        vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    return config


def _make_service(policy_factory=None, serving_config=None):
    servicer = vizier_service.VizierServicer()
    pythia = pythia_service.PythiaServicer(
        servicer, policy_factory, serving_config=serving_config
    )
    servicer.set_pythia(pythia)
    return servicer, pythia


def _create_study(servicer, config=None, name=STUDY):
    study = pc.study_to_proto(config or _study_config(), name)
    servicer.CreateStudy(
        vizier_service_pb2.CreateStudyRequest(parent="owners/o", study=study)
    )


def _complete_some_trials(servicer, n=3, name=STUDY):
    from vizier_tpu.service.protos import study_pb2

    for i in range(n):
        created = servicer.CreateTrial(
            vizier_service_pb2.CreateTrialRequest(parent=name, trial=study_pb2.Trial())
        )
        req = vizier_service_pb2.CompleteTrialRequest(name=created.name)
        m = req.final_measurement.metrics.add()
        m.name, m.value = "obj", 0.1 * i
        servicer.CompleteTrial(req)


class TestServingStats:
    def test_increment_and_snapshot(self):
        stats = ServingStats()
        stats.increment("cache_hits")
        stats.increment("warm_trains", 3)
        snap = stats.snapshot()
        assert snap["cache_hits"] == 1
        assert snap["warm_trains"] == 3
        assert snap["cold_trains"] == 0

    def test_unknown_counter_rejected(self):
        with pytest.raises(KeyError):
            ServingStats().increment("cache_hit")  # singular: a typo


class TestDesignerStateCache:
    def test_miss_then_hit(self):
        cache = DesignerStateCache()
        built = []

        def factory():
            built.append(1)
            return object()

        e1 = cache.get_or_create("s1", factory)
        e2 = cache.get_or_create("s1", factory)
        assert e1 is e2
        assert len(built) == 1
        assert cache.stats.get("cache_misses") == 1
        assert cache.stats.get("cache_hits") == 1

    def test_ttl_eviction(self):
        clock = [0.0]
        cache = DesignerStateCache(ttl_seconds=10.0, time_fn=lambda: clock[0])
        first = cache.get_or_create("s1", object)
        clock[0] = 5.0
        assert cache.get_or_create("s1", object) is first  # within TTL
        clock[0] = 16.0  # idle > TTL since last use at t=5
        fresh = cache.get_or_create("s1", object)
        assert fresh is not first
        assert cache.stats.get("cache_evictions_ttl") == 1

    def test_lru_eviction(self):
        cache = DesignerStateCache(max_entries=2)
        cache.get_or_create("s1", object)
        cache.get_or_create("s2", object)
        cache.get_or_create("s1", object)  # s1 now most recent
        cache.get_or_create("s3", object)  # evicts s2 (least recent)
        assert cache.study_names() == ["s1", "s3"]
        assert cache.stats.get("cache_evictions_lru") == 1

    def test_invalidate(self):
        cache = DesignerStateCache()
        cache.get_or_create("s1", object)
        assert cache.invalidate("s1")
        assert not cache.invalidate("s1")  # already gone
        assert len(cache) == 0
        assert cache.stats.get("cache_invalidations") == 1

    def test_entry_holds_warm_params_and_ids(self):
        cache = DesignerStateCache()
        entry = cache.get_or_create("s1", object)
        entry.warm_params = {"amplitude": 1.0}
        entry.incorporated_trial_ids.update([1, 2])
        again = cache.get_or_create("s1", object)
        assert again.warm_params == {"amplitude": 1.0}
        assert again.incorporated_trial_ids == {1, 2}


class TestRequestCoalescer:
    def test_concurrent_callers_share_one_computation(self):
        coalescer = RequestCoalescer()
        calls = []
        release = threading.Event()
        results = []

        def compute():
            calls.append(1)
            release.wait(timeout=10)
            return {"v": 42}

        def run():
            results.append(coalescer.coalesce("k", compute, clone=dict))

        threads = [threading.Thread(target=run) for _ in range(5)]
        for t in threads:
            t.start()
        # Wait until the leader is inside compute and followers queued.
        deadline = time.time() + 10
        while len(coalescer.inflight_keys()) < 1 and time.time() < deadline:
            time.sleep(0.01)
        time.sleep(0.1)  # let followers reach the wait
        release.set()
        for t in threads:
            t.join(timeout=10)
        assert len(calls) == 1
        assert len(results) == 5
        assert all(r == {"v": 42} for r in results)
        # Followers got clones, not the shared object.
        assert len({id(r) for r in results}) == 5
        assert coalescer._stats.get("coalesced_requests") == 4

    def test_sequential_calls_do_not_share(self):
        coalescer = RequestCoalescer()
        calls = []
        coalescer.coalesce("k", lambda: calls.append(1))
        coalescer.coalesce("k", lambda: calls.append(1))
        assert len(calls) == 2

    def test_leader_error_propagates_to_followers(self):
        coalescer = RequestCoalescer()
        entered = threading.Event()
        release = threading.Event()
        errors = []

        def compute():
            entered.set()
            release.wait(timeout=10)
            raise RuntimeError("boom")

        def leader():
            try:
                coalescer.coalesce("k", compute)
            except RuntimeError as e:
                errors.append(str(e))

        def follower():
            entered.wait(timeout=10)
            try:
                coalescer.coalesce("k", compute)
            except RuntimeError as e:
                errors.append(str(e))

        t1 = threading.Thread(target=leader)
        t2 = threading.Thread(target=follower)
        t1.start()
        t2.start()
        entered.wait(timeout=10)
        time.sleep(0.1)
        release.set()
        t1.join(timeout=10)
        t2.join(timeout=10)
        assert errors == ["boom", "boom"]


class TestServingConfig:
    def test_env_overrides(self, monkeypatch):
        monkeypatch.setenv("VIZIER_SERVING_CACHE", "0")
        monkeypatch.setenv("VIZIER_SERVING_WARM_START", "0")
        cfg = ServingConfig.from_env()
        assert not cfg.designer_cache
        assert not cfg.warm_start
        assert cfg.coalescing

    def test_disabled(self):
        cfg = ServingConfig.disabled()
        assert not (cfg.designer_cache or cfg.warm_start or cfg.coalescing)


class TestBudgetPolicyValidation:
    def test_factory_rejects_bad_metadata_value_early(self):
        from vizier_tpu.service.policy_factory import DefaultPolicyFactory

        problem = vz.ProblemStatement()
        problem.search_space.root.add_float_param("x", 0.0, 1.0)
        problem.metric_information.append(
            vz.MetricInformation(
                name="o", goal=vz.ObjectiveMetricGoal.MAXIMIZE
            )
        )
        problem.metadata.ns("gp_ucb_pe")["acquisition_budget_policy"] = "per_pik"
        with pytest.raises(ValueError, match="acquisition_budget_policy.*per_pik"):
            DefaultPolicyFactory()(problem, "DEFAULT", None, STUDY)


class _CountingPolicyFactory:
    """A deterministic slow policy: counts designer computations."""

    def __init__(self, delay_s: float = 1.0):
        self.computations = 0
        self.delay_s = delay_s
        self._lock = threading.Lock()

    def __call__(self, problem, algorithm, supporter, study_name):
        outer = self

        class _P(policy_lib.Policy):
            def suggest(self, request):
                with outer._lock:
                    outer.computations += 1
                time.sleep(outer.delay_s)
                suggestions = [
                    vz.TrialSuggestion(parameters={"x0": 0.25, "x1": 0.75})
                    for _ in range(request.count)
                ]
                return policy_lib.SuggestDecision(suggestions=suggestions)

        return _P()


def _suggest_request(client, name=STUDY, **kwargs):
    return vizier_service_pb2.SuggestTrialsRequest(
        parent=name, suggestion_count=1, client_id=client, **kwargs
    )


def _pythia_request(servicer, name=STUDY):
    """The Pythia request the service would send for ``name`` right now."""
    from vizier_tpu.service.protos import pythia_service_pb2

    study = servicer.GetStudy(vizier_service_pb2.GetStudyRequest(name=name))
    preq = pythia_service_pb2.PythiaSuggestRequest(
        count=1, algorithm=study.study_spec.algorithm, study_name=name
    )
    preq.study_descriptor.config.CopyFrom(study.study_spec)
    preq.study_descriptor.guid = name
    preq.study_descriptor.max_trial_id = servicer.datastore.max_trial_id(name)
    return preq


def _run_together(n, call):
    """``call(i)`` on n threads released at once; their results, by i."""
    out = [None] * n
    barrier = threading.Barrier(n)

    def worker(i):
        barrier.wait(timeout=10)
        out[i] = call(i)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    return out


class TestSuggestCoalescing:
    """Coalescing lives at the Pythia servicer: identical computations that
    reach it together (a second frontend, the speculative engine) share
    one. ``SuggestTrials`` of one study no longer arrive there together:
    they take turns (``TestStudyTurns``)."""

    @pytest.mark.parametrize("n", [2, 6])
    def test_n_identical_pythia_suggests_one_computation(self, n):
        factory = _CountingPolicyFactory(delay_s=0.5)
        servicer, pythia = _make_service(policy_factory=factory)
        _create_study(servicer)
        preq = _pythia_request(servicer)
        responses = _run_together(n, lambda i: pythia.Suggest(preq))
        assert factory.computations == 1
        for resp in responses:
            assert resp is not None and not resp.error
            assert len(resp.suggestions) == 1
        assert len({id(r) for r in responses}) == n  # a copy each
        snap = pythia.serving_stats()
        assert snap["coalesced_requests"] == n - 1
        assert snap["coalesced_computations"] == 1

    def test_coalescing_disabled_by_config(self):
        factory = _CountingPolicyFactory(delay_s=0.3)
        servicer, pythia = _make_service(
            policy_factory=factory,
            serving_config=ServingConfig(coalescing=False),
        )
        _create_study(servicer)
        n = 3
        preq = _pythia_request(servicer)
        responses = _run_together(n, lambda i: pythia.Suggest(preq))
        for resp in responses:
            assert resp is not None and not resp.error
        assert factory.computations == n
        assert pythia.serving_stats()["coalesced_requests"] == 0


class _RecordingPolicyFactory:
    """Computation i suggests the point (i / 100, 0.5) and records the
    ACTIVE trials it could read; ``gate`` (an Event) holds every
    computation until set, ``fail_first`` makes the first one raise."""

    def __init__(self, delay_s=0.0, gate=None, fail_first=False):
        self.seen_active = []  # per computation: x0 of each ACTIVE trial
        self.delay_s = delay_s
        self.gate = gate
        self.fail_first = fail_first
        self.started = threading.Event()

    @property
    def computations(self):
        return len(self.seen_active)

    def __call__(self, problem, algorithm, supporter, study_name):
        outer = self

        class _P(policy_lib.Policy):
            def suggest(self, request):
                active = supporter.GetTrials(status_matches=vz.TrialStatus.ACTIVE)
                outer.seen_active.append(
                    sorted(t.parameters["x0"].value for t in active)
                )
                i = len(outer.seen_active)
                outer.started.set()
                if outer.gate is not None:
                    assert outer.gate.wait(timeout=20)
                time.sleep(outer.delay_s)
                if outer.fail_first and i == 1:
                    raise RuntimeError("the designer fell over")
                point = vz.TrialSuggestion(parameters={"x0": i / 100, "x1": 0.5})
                return policy_lib.SuggestDecision(suggestions=[point])

        return _P()


def _wait_until(condition, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not condition():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.005)


def _in_line(turn) -> int:
    """Requests standing behind a turn's holder."""
    return max(0, turn._tickets - turn._serving - 1)


class TestStudyTurns:
    """A study's SuggestTrials run one at a time from the claim to the
    write, in arrival order: N workers of one study get N points."""

    @pytest.mark.parametrize("n", [2, 6])
    def test_n_clients_of_one_study_get_n_points_in_arrival_order(self, n):
        gate = threading.Event()
        factory = _RecordingPolicyFactory(gate=gate)
        servicer, pythia = _make_service(policy_factory=factory)
        _create_study(servicer)
        ops = [None] * n

        def worker(i):
            ops[i] = servicer.SuggestTrials(_suggest_request(f"client-{i}"))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
        turn = servicer._study_turns[STUDY]
        for i, t in enumerate(threads):  # arrivals in order: i stands behind i - 1
            t.start()
            if i == 0:
                assert factory.started.wait(timeout=10)
            else:
                _wait_until(lambda: _in_line(turn) == i)
        gate.set()
        for t in threads:
            t.join(timeout=30)

        assert factory.computations == n
        for i, op in enumerate(ops):
            assert op is not None and op.done and not op.error, op
            (trial,) = op.response.trials
            assert trial.id == i + 1 and trial.assigned_worker == f"client-{i}"
            values = {p.name: p.value.double_value for p in trial.parameters}
            assert values == {"x0": (i + 1) / 100, "x1": 0.5}
            # Computation i read the i earlier picks as ACTIVE.
            assert factory.seen_active[i] == [(k + 1) / 100 for k in range(i)]
        snap = pythia.serving_stats()
        assert snap["suggest_turns"] == n
        assert snap["suggest_turns_contended"] == n - 1
        assert snap["coalesced_requests"] == 0

    def test_same_client_asking_again_gets_its_own_trial_back(self):
        factory = _RecordingPolicyFactory()
        servicer, _ = _make_service(policy_factory=factory)
        _create_study(servicer)
        first = servicer.SuggestTrials(_suggest_request("worker"))
        again = servicer.SuggestTrials(_suggest_request("worker"))
        assert not first.error and not again.error
        assert [t.id for t in again.response.trials] == [t.id for t in first.response.trials] == [1]
        assert factory.computations == 1

    def test_a_completion_is_served_while_a_turn_is_held(self):
        gate = threading.Event()
        factory = _RecordingPolicyFactory(gate=gate)
        servicer, _ = _make_service(policy_factory=factory)
        _create_study(servicer)
        created = servicer.CreateTrial(
            vizier_service_pb2.CreateTrialRequest(
                parent=STUDY, trial=pc.trial_to_proto(vz.Trial(parameters={"x0": 0.9, "x1": 0.9}))
            )
        )
        holder = threading.Thread(
            target=servicer.SuggestTrials, args=(_suggest_request("client-0"),)
        )
        holder.start()
        try:
            assert factory.started.wait(timeout=10)  # the turn is held, mid-computation
            req = vizier_service_pb2.CompleteTrialRequest(name=created.name)
            m = req.final_measurement.metrics.add()
            m.name, m.value = "obj", 1.0
            t0 = time.monotonic()
            done = servicer.CompleteTrial(req)
            assert time.monotonic() - t0 < 5.0
            from vizier_tpu.service.protos import study_pb2

            assert done.state == study_pb2.Trial.SUCCEEDED
            assert holder.is_alive()
        finally:
            gate.set()
            holder.join(timeout=30)

    def test_a_waiter_whose_deadline_expires_gets_the_transient_error_and_the_turn_moves_on(self):
        from vizier_tpu.reliability import errors as errors_lib

        gate = threading.Event()
        factory = _RecordingPolicyFactory(gate=gate)
        servicer, pythia = _make_service(policy_factory=factory)
        _create_study(servicer)
        ops = {}

        def ask(client, **kwargs):
            ops[client] = servicer.SuggestTrials(_suggest_request(client, **kwargs))

        turn = servicer._study_turns[STUDY]
        holder = threading.Thread(target=ask, args=("holder",))
        holder.start()
        assert factory.started.wait(timeout=10)
        hurried = threading.Thread(target=ask, args=("hurried",), kwargs={"deadline_secs": 0.2})
        hurried.start()
        _wait_until(lambda: _in_line(turn) == 1)
        patient = threading.Thread(target=ask, args=("patient",))
        patient.start()
        _wait_until(lambda: _in_line(turn) == 2)
        time.sleep(0.3)  # the hurried one's budget runs out in line
        gate.set()
        for t in (holder, hurried, patient):
            t.join(timeout=30)

        assert not ops["holder"].error and not ops["patient"].error
        assert ops["hurried"].done and not ops["hurried"].response.trials
        assert errors_lib.has_transient_marker(ops["hurried"].error)
        assert "DEADLINE_EXCEEDED" in ops["hurried"].error
        assert factory.computations == 2  # the expired request never reached Pythia
        assert [t.id for t in ops["patient"].response.trials] == [2]
        assert pythia.serving_stats()["deadline_exceeded"] == 1

    def test_an_exception_inside_a_turn_releases_it(self):
        from vizier_tpu.service.protos import study_pb2

        factory = _RecordingPolicyFactory(fail_first=True)
        servicer, _ = _make_service(policy_factory=factory)
        _create_study(servicer)
        # A designer that raises (the reliability fallback answers in its place) ...
        rescued = servicer.SuggestTrials(_suggest_request("client-0"))
        assert rescued.done and factory.computations == 1
        # ... and an exception that leaves SuggestTrials itself, mid-turn.
        servicer.SetStudyState(
            vizier_service_pb2.SetStudyStateRequest(name=STUDY, state=study_pb2.Study.INACTIVE)
        )
        with pytest.raises(ValueError, match="not ACTIVE"):
            servicer.SuggestTrials(_suggest_request("client-1"))
        servicer.SetStudyState(
            vizier_service_pb2.SetStudyStateRequest(name=STUDY, state=study_pb2.Study.ACTIVE)
        )
        ok = servicer.SuggestTrials(_suggest_request("client-1"))
        assert ok.done and not ok.error and len(ok.response.trials) == 1
        assert _in_line(servicer._study_turns[STUDY]) == 0

    def test_first_requests_of_a_fresh_study_share_one_turn(self):
        """Fifty workers behind a barrier hit a study no request has met:
        every one of them must find the SAME turn, or two compute at once
        and two workers get one point."""
        from vizier_tpu.serving import study_turns

        made = []
        real = study_turns.StudyTurn.__init__

        def slow_init(self, *args):  # widens the window between miss and set
            made.append(self)
            time.sleep(0.05)
            real(self, *args)

        n = 12
        factory = _RecordingPolicyFactory()
        servicer, _ = _make_service(policy_factory=factory)
        _create_study(servicer)
        barrier = threading.Barrier(n)
        ops = [None] * n

        def worker(i):
            barrier.wait()
            ops[i] = servicer.SuggestTrials(_suggest_request(f"client-{i}"))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
        study_turns.StudyTurn.__init__ = slow_init
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            study_turns.StudyTurn.__init__ = real
        turn = servicer._study_turns[STUDY]
        assert turn._tickets == turn._serving == n  # all n went through the one turn
        points = sorted(
            tuple(p.value.double_value for p in op.response.trials[0].parameters) for op in ops
        )
        assert len(set(points)) == n, points
        # Computation k read exactly the k earlier picks as ACTIVE.
        assert sorted(len(seen) for seen in factory.seen_active) == list(range(n))

    def test_an_observer_that_raises_does_not_strand_the_turn(self):
        from vizier_tpu.serving import study_turns

        calls = []

        def waited(seconds, contended):
            calls.append(contended)
            if len(calls) == 1:
                raise RuntimeError("observer")

        turn = study_turns.StudyTurns(waited, lambda seconds: None)["s"]
        with pytest.raises(RuntimeError, match="observer"):
            with turn:
                pass
        with turn:  # would wait for ever behind the stranded ticket
            assert _in_line(turn) == 0
        assert calls == [False, False] and turn._tickets == turn._serving == 2

    def test_two_studies_do_not_wait_for_each_other(self):
        gate = threading.Event()
        slow = _RecordingPolicyFactory(gate=gate)
        other = "owners/o/studies/other"

        def factory(problem, algorithm, supporter, study_name):
            if study_name == STUDY:
                return slow(problem, algorithm, supporter, study_name)
            return _RecordingPolicyFactory()(problem, algorithm, supporter, study_name)

        servicer, pythia = _make_service(policy_factory=factory)
        _create_study(servicer)
        _create_study(servicer, name=other)
        holder = threading.Thread(
            target=servicer.SuggestTrials, args=(_suggest_request("client-0"),)
        )
        holder.start()
        try:
            assert slow.started.wait(timeout=10)
            op = servicer.SuggestTrials(_suggest_request("client-0", name=other))
            assert op.done and not op.error and len(op.response.trials) == 1
            assert holder.is_alive()
        finally:
            gate.set()
            holder.join(timeout=30)
        assert pythia.serving_stats()["suggest_turns_contended"] == 0


@pytest.fixture(scope="module")
def fast_gp_kwargs():
    """Keeps the real-GP serving tests' designers cheap on CPU."""
    from vizier_tpu.optimizers import lbfgs as lbfgs_lib

    return dict(
        max_acquisition_evaluations=300,
        ard_restarts=2,
        ard_optimizer=lbfgs_lib.LbfgsOptimizer(maxiter=5),
        # These tests assert warm/cold counter plumbing at single-digit
        # trial counts; disable the convergence-protecting engage floor so
        # warm seeding starts on the second train as the assertions expect.
        warm_start_min_trials=0,
    )


class _FastGPFactory:
    """DEFAULT -> a cheap VizierGPUCBPEBandit, routed through serving."""

    def __init__(self, serving_runtime, designer_kwargs):
        self._serving = serving_runtime
        self._kwargs = designer_kwargs

    def __call__(self, problem, algorithm, supporter, study_name):
        from vizier_tpu.designers import gp_ucb_pe
        from vizier_tpu.serving.policy import CachedDesignerStatePolicy

        kwargs = dict(self._kwargs)
        cfg = self._serving.config
        kwargs["use_warm_start_ard"] = cfg.warm_start
        if cfg.warm_start:
            kwargs["warm_ard_restarts"] = cfg.warm_ard_restarts
        return CachedDesignerStatePolicy(
            supporter,
            lambda p, **kw: gp_ucb_pe.VizierGPUCBPEBandit(p, **kwargs),
            self._serving,
            study_name,
            use_seeding=True,
        )


def _gp_service(fast_gp_kwargs, serving_config=None):
    servicer = vizier_service.VizierServicer()
    pythia = pythia_service.PythiaServicer(servicer, serving_config=serving_config)
    pythia._policy_factory = _FastGPFactory(pythia.serving_runtime, fast_gp_kwargs)
    servicer.set_pythia(pythia)
    return servicer, pythia


class TestServingWithRealDesigner:
    def test_warm_cold_counters_and_cache_reuse(self, fast_gp_kwargs):
        servicer, pythia = _gp_service(fast_gp_kwargs)
        _create_study(servicer)
        _complete_some_trials(servicer, 3)

        for step in range(3):
            op = servicer.SuggestTrials(
                vizier_service_pb2.SuggestTrialsRequest(
                    parent=STUDY, suggestion_count=1, client_id=f"w{step}"
                )
            )
            assert op.done and not op.error, op.error
            req = vizier_service_pb2.CompleteTrialRequest(
                name=op.response.trials[0].name
            )
            m = req.final_measurement.metrics.add()
            m.name, m.value = "obj", 0.5
            servicer.CompleteTrial(req)

        snap = pythia.serving_stats()
        # First suggest builds + cold-trains; later suggests hit the cached
        # designer and warm-train from its previous optimum.
        assert snap["cache_misses"] == 1
        assert snap["cache_hits"] == 2
        assert snap["cold_trains"] == 1
        assert snap["warm_trains"] == 2
        assert snap["cached_studies"] == 1
        # The cache entry mirrors the trained unconstrained ARD params.
        entry = pythia.serving_runtime.designer_cache.get_or_create(
            STUDY, lambda: None
        )
        assert entry.warm_params is not None

    def test_delete_study_invalidates_cache(self, fast_gp_kwargs):
        servicer, pythia = _gp_service(fast_gp_kwargs)
        _create_study(servicer)
        _complete_some_trials(servicer, 3)
        op = servicer.SuggestTrials(
            vizier_service_pb2.SuggestTrialsRequest(
                parent=STUDY, suggestion_count=1, client_id="w0"
            )
        )
        assert op.done and not op.error, op.error
        assert pythia.serving_stats()["cached_studies"] == 1
        servicer.DeleteStudy(vizier_service_pb2.DeleteStudyRequest(name=STUDY))
        snap = pythia.serving_stats()
        assert snap["cached_studies"] == 0
        assert snap["cache_invalidations"] == 1

    def test_warm_start_disabled_stays_cold(self, fast_gp_kwargs):
        servicer, pythia = _gp_service(
            fast_gp_kwargs, serving_config=ServingConfig(warm_start=False)
        )
        _create_study(servicer)
        _complete_some_trials(servicer, 3)
        for step in range(2):
            op = servicer.SuggestTrials(
                vizier_service_pb2.SuggestTrialsRequest(
                    parent=STUDY, suggestion_count=1, client_id=f"w{step}"
                )
            )
            assert op.done and not op.error, op.error
            req = vizier_service_pb2.CompleteTrialRequest(
                name=op.response.trials[0].name
            )
            m = req.final_measurement.metrics.add()
            m.name, m.value = "obj", 0.5
            servicer.CompleteTrial(req)
        snap = pythia.serving_stats()
        assert snap["warm_trains"] == 0
        assert snap["cold_trains"] == 2
