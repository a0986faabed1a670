"""Disaggregated compute tier: RemotePythiaStub degradation mechanics,
shared-servicer config-hash invalidation (the two-frontend delete/recreate
race), and the end-to-end fleet (N frontends + 1 real compute server).
"""

import os
import threading
import time

import pytest

grpc = pytest.importorskip("grpc")

from vizier_tpu import pyvizier as vz
from vizier_tpu.distributed import compute_tier, subprocess_fleet
from vizier_tpu.observability import flight_recorder as flight_recorder_lib
from vizier_tpu.pythia import policy as policy_lib
from vizier_tpu.reliability import ReliabilityConfig
from vizier_tpu.reliability import retry as retry_lib
from vizier_tpu.serving.designer_cache import DesignerStateCache
from vizier_tpu.service import proto_converters as pc
from vizier_tpu.service import pythia_service, vizier_client, vizier_service
from vizier_tpu.service.protos import (
    pythia_service_pb2,
    vizier_service_pb2,
)

STUDY = "owners/tier/studies/s"


def _study_config(param="x", algorithm="RANDOM_SEARCH") -> vz.StudyConfig:
    config = vz.StudyConfig(algorithm=algorithm)
    config.search_space.root.add_float_param(param, 0.0, 1.0)
    config.metric_information.append(
        vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    return config


def _suggest_request(config, name=STUDY, count=1):
    request = pythia_service_pb2.PythiaSuggestRequest(
        count=count, study_name=name
    )
    request.study_descriptor.config.CopyFrom(pc.study_to_proto(config, name).study_spec)
    request.study_descriptor.guid = name
    return request


# -- RemotePythiaStub unit mechanics (injected remotes, fake clock) --------


class _FakeRemote:
    """Scripted remote PythiaService stub."""

    def __init__(self, failures=0, error_factory=ConnectionError):
        self.failures = failures
        self.error_factory = error_factory
        self.calls = 0

    def Suggest(self, request):
        self.calls += 1
        if self.calls <= self.failures:
            raise self.error_factory("tier down")
        response = pythia_service_pb2.PythiaSuggestResponse()
        trial = response.suggestions.add()
        p = trial.parameters.add()
        p.name, p.value.double_value = "remote", 1.0
        return response

    EarlyStop = Suggest
    Ping = Suggest


class _FakeLocal:
    def __init__(self):
        self.calls = 0

    def Suggest(self, request):
        self.calls += 1
        response = pythia_service_pb2.PythiaSuggestResponse()
        trial = response.suggestions.add()
        p = trial.parameters.add()
        p.name, p.value.double_value = "local", 1.0
        return response

    EarlyStop = Suggest
    Ping = Suggest


def _stub(remote, local=None, clock=None, fallback="local", interval=5.0):
    config = compute_tier.ComputeTierConfig(
        enabled=True,
        endpoint="localhost:1",
        fallback=fallback,
        health_interval_s=interval,
    )
    factories = {"count": 0}

    def factory():
        factories["count"] += 1
        return remote

    stub = compute_tier.RemotePythiaStub(
        "localhost:1",
        local=local,
        replica_id="r0",
        config=config,
        # No in-hop retry: each scripted failure is one observed failure.
        retry_policy=retry_lib.RetryPolicy(max_attempts=1),
        stub_factory=factory,
        time_fn=(clock or time.monotonic),
    )
    return stub, factories


class TestRemotePythiaStub:
    def test_remote_path_serves_and_counts(self):
        stub, factories = _stub(_FakeRemote(), local=_FakeLocal())
        response = stub.Suggest(_suggest_request(_study_config()))
        assert response.suggestions[0].parameters[0].name == "remote"
        assert factories["count"] == 1
        stats = stub.stats()
        assert stats["remote_calls"] == 1
        assert stats["fallback_serves"] == 0
        assert not stats["cooling_down"]

    def test_unreachable_tier_falls_back_then_cools_down(self):
        clock = [100.0]
        local = _FakeLocal()
        remote = _FakeRemote(failures=1)
        stub, factories = _stub(
            remote, local=local, clock=lambda: clock[0], interval=5.0
        )

        # First call: remote raises ConnectionError -> local fallback.
        response = stub.Suggest(_suggest_request(_study_config()))
        assert response.suggestions[0].parameters[0].name == "local"
        stats = stub.stats()
        assert stats["remote_failures"] == 1
        assert stats["fallback_serves"] == 1
        assert stats["cooling_down"]

        # Inside the cooldown the remote is never touched again.
        stub.Suggest(_suggest_request(_study_config()))
        assert remote.calls == 1
        assert stub.stats()["fallback_serves"] == 2

        # Past the cooldown the stub re-probes (a fresh stub build) and
        # the recovered tier serves remotely again.
        clock[0] += 5.1
        response = stub.Suggest(_suggest_request(_study_config()))
        assert response.suggestions[0].parameters[0].name == "remote"
        assert factories["count"] == 2  # reconnect after eviction
        assert stub.stats()["remote_calls"] == 1

    def test_fallback_fail_mode_surfaces_the_error(self):
        stub, _ = _stub(_FakeRemote(failures=10), fallback="fail")
        with pytest.raises(ConnectionError):
            stub.Suggest(_suggest_request(_study_config()))

    def test_semantic_errors_propagate_without_fallback(self):
        local = _FakeLocal()
        remote = _FakeRemote(failures=10, error_factory=ValueError)
        stub, _ = _stub(remote, local=local)
        with pytest.raises(ValueError):
            stub.Suggest(_suggest_request(_study_config()))
        assert local.calls == 0
        assert not stub.stats()["cooling_down"]

    def test_closed_channel_race_takes_the_fallback(self):
        # A concurrent request's failure path can evict the shared channel
        # (close_channel in _note_tier_down) while this call is in flight;
        # grpcio raises ValueError("Cannot invoke RPC on closed channel!").
        # That is a tier-down signal, NOT a semantic error: the call must
        # fall back locally instead of surfacing the ValueError.
        local = _FakeLocal()
        remote = _FakeRemote(
            failures=10,
            error_factory=lambda msg: ValueError(
                "Cannot invoke RPC on closed channel!"
            ),
        )
        stub, _ = _stub(remote, local=local)
        response = stub.Suggest(_suggest_request(_study_config()))
        assert response.suggestions[0].parameters[0].name == "local"
        assert local.calls == 1
        assert stub.stats()["cooling_down"]

    def test_trace_context_is_restamped_across_the_hop(self):
        seen = {}

        class _Capture(_FakeRemote):
            def Suggest(self, request):
                seen["trace_context"] = request.trace_context
                return super().Suggest(request)

        stub, _ = _stub(_Capture())
        request = _suggest_request(_study_config())
        stub.Suggest(request)
        assert seen["trace_context"]  # the hop span rides the wire

    def test_maybe_wrap_off_switch_returns_local_unchanged(self, monkeypatch):
        monkeypatch.delenv("VIZIER_COMPUTE_TIER", raising=False)
        monkeypatch.delenv("VIZIER_COMPUTE_TIER_ENDPOINT", raising=False)
        local = _FakeLocal()
        assert compute_tier.maybe_wrap_pythia(local) is local

    def test_maybe_wrap_endpoint_flag_arms_the_tier(self, monkeypatch):
        monkeypatch.delenv("VIZIER_COMPUTE_TIER", raising=False)
        local = _FakeLocal()
        wrapped = compute_tier.maybe_wrap_pythia(
            local, replica_id="r1", endpoint="localhost:2"
        )
        assert isinstance(wrapped, compute_tier.RemotePythiaStub)
        assert wrapped.stats()["endpoint"] == "localhost:2"

    def test_bad_fallback_mode_rejected(self):
        with pytest.raises(ValueError):
            compute_tier.ComputeTierConfig(fallback="retry")


# -- config-hash turnover: the shared-tier delete/recreate race ------------


class TestDesignerCacheConfigHash:
    def test_turnover_drops_the_stale_entry(self):
        cache = DesignerStateCache()
        assert not cache.note_config_hash("s1", "aaaa")
        cache.get_or_create("s1", object)
        assert not cache.note_config_hash("s1", "aaaa")  # same incarnation
        assert "s1" in cache
        assert cache.note_config_hash("s1", "bbbb")  # delete/recreate
        assert "s1" not in cache
        assert cache.stats.get("cache_invalidations_config") == 1

    def test_hash_memory_is_bounded(self):
        cache = DesignerStateCache(max_entries=1)
        for i in range(cache._max_hashes + 10):
            cache.note_config_hash(f"s{i}", "h")
        assert len(cache._config_hashes) == cache._max_hashes


class _BakedPolicy:
    """Bakes the problem it was CONSTRUCTED from into every suggestion —
    the shape of a designer-backed policy (the designer's converters are
    pinned to the construction-time search space), so serving a cached
    instance across a config turnover is observable in the output."""

    should_be_cached = True

    def __init__(self, problem):
        self._names = [p.name for p in problem.search_space.parameters]

    def suggest(self, request):
        del request
        return policy_lib.SuggestDecision(
            suggestions=[
                vz.TrialSuggestion(
                    parameters={name: 0.5 for name in self._names}
                )
            ]
        )


class TestSharedServicerInvalidationRace:
    """One shared PythiaServicer, two frontends racing CreateStudy/
    DeleteStudy for the same resource name. Frontend B's delete/recreate
    never reaches this process (there is no invalidation RPC on the
    Pythia surface) — the request's config hash is the only staleness
    signal, and it must be enough."""

    def _service(self):
        servicer = vizier_service.VizierServicer()
        pythia = pythia_service.PythiaServicer(
            servicer,
            policy_factory=lambda problem, algorithm, supporter, name: (
                _BakedPolicy(problem)
            ),
        )
        servicer.set_pythia(pythia)
        return servicer, pythia

    def test_recreated_study_is_served_fresh_not_stale(self):
        _servicer, pythia = self._service()
        config_a = _study_config(param="a0")
        config_b = _study_config(param="b0")

        # Frontend A's traffic warms every per-study cache for config A.
        response = pythia.Suggest(_suggest_request(config_a))
        assert not response.error
        assert response.suggestions[0].parameters[0].name == "a0"
        assert STUDY in pythia._config_cache

        # Frontend B deleted + recreated the study (same name, different
        # search space) and its traffic arrives with the NEW descriptor.
        response = pythia.Suggest(_suggest_request(config_b))
        assert not response.error
        names = [p.name for p in response.suggestions[0].parameters]
        assert names == ["b0"]  # the stale cached policy would say a0

        # The stale incarnation's state is gone, not shadowed: the parse
        # cache holds B, and no policy-cache key references A's hash.
        hash_b = pythia._config_cache[STUDY][0]
        assert all(
            key[2] == hash_b
            for key in pythia._policy_cache
            if key[0] == STUDY
        )

    def test_same_config_does_not_churn_caches(self):
        _servicer, pythia = self._service()
        config = _study_config(param="a0")
        pythia.Suggest(_suggest_request(config))
        cached = pythia._config_cache[STUDY]
        pythia.Suggest(_suggest_request(config))
        assert pythia._config_cache[STUDY] is cached  # hash hit, no reparse
        stats = pythia.serving_runtime.designer_cache.stats
        assert stats.get("cache_invalidations_config") == 0

    def test_concurrent_turnover_never_serves_a_stale_policy(self):
        """Two frontends suggest concurrently, one with each incarnation:
        every response must match ITS request's config — never the other
        incarnation's — regardless of interleaving. (Policies key by the
        REQUEST's own hash, not a parse-cache read-back a racing thread
        may have overwritten.)"""
        _servicer, pythia = self._service()
        configs = {"a0": _study_config("a0"), "b0": _study_config("b0")}
        errors = []
        barrier = threading.Barrier(2)

        def drive(param):
            barrier.wait()
            for _ in range(16):
                response = pythia.Suggest(_suggest_request(configs[param]))
                if response.error:
                    errors.append(response.error)
                    continue
                names = [
                    p.name for p in response.suggestions[0].parameters
                ]
                if names != [param]:
                    errors.append(f"asked {param}, served {names}")

        threads = [
            threading.Thread(target=drive, args=(param,))
            for param in ("a0", "b0")
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors

    def test_runtime_note_study_config_invalidates_serving_state(self):
        _servicer, pythia = self._service()
        runtime = pythia.serving_runtime
        runtime.designer_cache.get_or_create(STUDY, object)
        runtime.flight_recorder = flight_recorder_lib.FlightRecorder()
        runtime.flight_recorder.record(STUDY, "loadgen_outcome")
        assert not runtime.note_study_config(STUDY, "h1")
        assert STUDY in runtime.designer_cache
        assert runtime.note_study_config(STUDY, "h2")
        assert STUDY not in runtime.designer_cache
        # The recorder ring is forensic history, not derived state: a
        # metadata update (hash turnover) must not erase earlier events.
        assert runtime.flight_recorder.ring(STUDY)


# -- the real thing: frontends + one compute-server process ----------------


_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _reliability() -> ReliabilityConfig:
    return ReliabilityConfig(
        retry_max_attempts=16,
        retry_base_delay_secs=0.1,
        retry_max_delay_secs=0.5,
    )


class TestSharedComputeFleet:
    def test_kill_fallback_autorevive_loses_nothing(self, tmp_path):
        fleet = subprocess_fleet.SubprocessReplicaManager(
            2,
            wal_root=str(tmp_path / "fleet"),
            lease_timeout_s=1.0,
            heartbeat_interval_s=0.1,
            compute_tier=True,
            # The compute server inherits its platform; tests pin the CPU.
            child_env={"JAX_PLATFORMS": "cpu"},
        )
        try:
            assert fleet.has_compute_tier()
            assert fleet.compute_is_alive()
            study = "owners/tier/studies/e2e"
            fleet.stub.CreateStudy(
                vizier_service_pb2.CreateStudyRequest(
                    parent="owners/tier",
                    study=pc.study_to_proto(_study_config(), study),
                )
            )
            client = vizier_client.VizierClient(
                fleet.stub, study, "w", reliability=_reliability()
            )
            for i in range(4):
                (trial,) = client.get_suggestions(1)
                client.complete_trial(
                    trial.id, vz.Measurement(metrics={"obj": 0.01 * i})
                )
            stats = fleet.serving_stats()
            assert stats["compute_tier"]["alive"]

            # Crash the shared tier mid-run: suggests keep completing via
            # each frontend's local fallback — zero lost studies/trials.
            fleet.kill_compute_server()
            for i in range(4, 8):
                (trial,) = client.get_suggestions(1)
                client.complete_trial(
                    trial.id, vz.Measurement(metrics={"obj": 0.01 * i})
                )
            assert len(client.list_trials()) == 8

            # The manager's health loop respawns the server (its lease
            # expired); explicit revive is idempotent on a running one.
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if fleet.compute_is_alive():
                    break
                time.sleep(0.2)
            fleet.revive_compute_server()
            assert fleet.compute_is_alive()
            assert fleet.serving_stats()["compute_tier"]["restarts"] >= 1
            (trial,) = client.get_suggestions(1)
            client.complete_trial(
                trial.id, vz.Measurement(metrics={"obj": 0.99})
            )
            assert len(client.list_trials()) == 9
        finally:
            fleet.shutdown()
