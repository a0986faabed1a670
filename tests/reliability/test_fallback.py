"""Fallback + breaker through the full policy path (client → service → Pythia)."""

import pytest

from tests.reliability import harness
from vizier_tpu.reliability import ReliabilityConfig, is_fallback_suggestion
from vizier_tpu.reliability import fallback as fallback_lib
from vizier_tpu.service import vizier_client as vizier_client_lib
from vizier_tpu.testing import failing


@pytest.fixture(autouse=True)
def _fast_polling(monkeypatch):
    monkeypatch.setattr(
        vizier_client_lib.environment_variables, "polling_delay_secs", 0.005
    )


class TestSuggestFallback:
    def test_stamped_and_deterministic_at_a_frontier(self):
        problem = harness.study_config().to_problem()
        a = fallback_lib.suggest_fallback(
            problem, 3, study_name="owners/o/studies/s", max_trial_id=5, reason="r"
        )
        b = fallback_lib.suggest_fallback(
            problem, 3, study_name="owners/o/studies/s", max_trial_id=5, reason="r"
        )
        assert [s.parameters.as_dict() for s in a] == [
            s.parameters.as_dict() for s in b
        ]
        for s in a:
            assert is_fallback_suggestion(s.metadata)
            assert s.metadata.ns("reliability")["fallback_reason"] == "r"

    def test_advances_with_the_frontier(self):
        problem = harness.study_config().to_problem()
        at_0 = fallback_lib.suggest_fallback(
            problem, 1, study_name="s", max_trial_id=0, reason="r"
        )
        at_7 = fallback_lib.suggest_fallback(
            problem, 1, study_name="s", max_trial_id=7, reason="r"
        )
        assert at_0[0].parameters.as_dict() != at_7[0].parameters.as_dict()

    def test_conditional_space_degrades_to_random(self):
        import vizier_tpu.pyvizier as vz

        config = vz.StudyConfig(algorithm="RANDOM_SEARCH")
        root = config.search_space.root
        sel = root.add_categorical_param("model", ["a", "b"])
        sel.select_values(["a"]).add_float_param("lr", 0.0, 1.0)
        config.metric_information.append(
            vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
        )
        suggestions = fallback_lib.suggest_fallback(
            config.to_problem(), 2, study_name="s", max_trial_id=0, reason="r"
        )
        assert len(suggestions) == 2
        assert all(is_fallback_suggestion(s.metadata) for s in suggestions)


class TestAlternateFailingDesignerPolicyPath:
    """Satellite: AlternateFailingDesigner through the full policy path."""

    def _stack(self, reliability):
        from vizier_tpu.designers import random as random_designer

        factory = harness.DesignerPolicyFactory(
            lambda p: failing.AlternateFailingDesigner(
                random_designer.RandomDesigner(p.search_space, seed=0)
            )
        )
        return harness.make_stack(factory, reliability=reliability)

    def test_reliability_off_fails_every_other_suggest(self):
        servicer, pythia, client = self._stack(ReliabilityConfig.disabled())
        # Odd designer calls fail. One fresh designer per request (stateless
        # DesignerPolicy path), so EVERY suggest hits an odd first call.
        with pytest.raises(RuntimeError, match="AlternateFailingDesigner"):
            client.get_suggestions(1)

    def test_fallback_converts_failures_into_quasi_random(self):
        servicer, pythia, client = self._stack(
            ReliabilityConfig(breaker=False)  # isolate the fallback behavior
        )
        for i in range(1, 5):
            (trial,) = client.get_suggestions(1)
            assert trial.id == i
            # Every suggest fails (fresh designer, odd call) and every
            # failure is converted into a marked quasi-random suggestion.
            assert is_fallback_suggestion(trial.metadata)
            harness.complete(client, trial, value=0.1 * i)
        stats = pythia.serving_stats()
        assert stats["designer_failures"] == 4
        assert stats["fallbacks"] == 4

    def test_fallback_entry_names_the_exception_it_degraded_around(self):
        """A chip-side compile error or OOM must stay legible behind the
        counter: the flight-recorder entry carries its type and message."""
        from vizier_tpu.observability import flight_recorder as recorder_lib

        recorder = recorder_lib.FlightRecorder()
        previous = recorder_lib.set_recorder(recorder)
        try:
            servicer, pythia, client = self._stack(ReliabilityConfig(breaker=False))
            (trial,) = client.get_suggestions(1)
        finally:
            recorder_lib.set_recorder(previous)
        assert is_fallback_suggestion(trial.metadata)
        (event,) = recorder.events(kind="fallback")
        assert event["attributes"]["reason"] == "designer_error:FailedSuggestError"
        assert event["attributes"]["error_type"] == "FailedSuggestError"
        assert "AlternateFailingDesigner" in event["attributes"]["error_message"]

    def test_cached_designer_alternates_through_fallback(self):
        """With a cached (stateful) designer the failures really alternate."""
        from vizier_tpu.designers import random as random_designer

        designers = []

        def designer_factory(problem):
            designers.append(
                failing.AlternateFailingDesigner(
                    random_designer.RandomDesigner(problem.search_space, seed=0)
                )
            )
            return designers[-1]

        class CachingFactory:
            def __call__(self, problem, algorithm, supporter, study_name):
                from vizier_tpu.algorithms import designer_policy

                policy = designer_policy.InRamDesignerPolicy(
                    supporter, designer_factory
                )
                return policy

        servicer, pythia, client = harness.make_stack(
            CachingFactory(), reliability=ReliabilityConfig(breaker=False)
        )
        outcomes = []
        for i in range(1, 7):
            (trial,) = client.get_suggestions(1)
            outcomes.append(is_fallback_suggestion(trial.metadata))
            harness.complete(client, trial)
        # One designer, alternating odd-fail/even-succeed across requests.
        assert len(designers) == 1
        assert outcomes == [True, False, True, False, True, False]


class TestBreakerOnServicePath:
    def test_breaker_opens_short_circuits_and_half_opens(self):
        reliability = ReliabilityConfig(
            breaker_failure_threshold=3,
            breaker_window_secs=60.0,
            breaker_cooldown_secs=0.15,
        )
        factory = harness.DesignerPolicyFactory(
            lambda p: failing.FailingDesigner()
        )
        servicer, pythia, client = harness.make_stack(
            factory, reliability=reliability
        )
        # 3 failures open the breaker (each still served via fallback).
        for _ in range(3):
            (trial,) = client.get_suggestions(1)
            assert is_fallback_suggestion(trial.metadata)
            harness.complete(client, trial)
        stats = pythia.serving_stats()
        assert stats["designer_failures"] == 3
        assert stats["breaker_open_transitions"] == 1
        assert stats["open_breakers"] == 1

        # While open: the designer is not even attempted (short-circuit).
        (trial,) = client.get_suggestions(1)
        assert is_fallback_suggestion(trial.metadata)
        assert trial.metadata.ns("reliability")["fallback_reason"] == "circuit_open"
        harness.complete(client, trial)
        stats = pythia.serving_stats()
        assert stats["breaker_short_circuits"] >= 1
        assert stats["designer_failures"] == 3  # unchanged

        # After the cooldown the breaker half-opens and admits a probe,
        # which fails and re-opens the circuit.
        import time

        time.sleep(0.2)
        (trial,) = client.get_suggestions(1)
        harness.complete(client, trial)
        stats = pythia.serving_stats()
        assert stats["breaker_half_open_transitions"] == 1
        assert stats["designer_failures"] == 4  # the probe ran and failed
        assert stats["breaker_open_transitions"] == 2  # reopened

    def test_breaker_open_without_fallback_errors_transient(self):
        reliability = ReliabilityConfig(
            fallback=False,
            retries=False,
            breaker_failure_threshold=2,
            breaker_cooldown_secs=60.0,
        )
        factory = harness.DesignerPolicyFactory(
            lambda p: failing.FailingDesigner()
        )
        servicer, pythia, client = harness.make_stack(
            factory, reliability=reliability
        )
        for _ in range(2):
            with pytest.raises(RuntimeError):
                client.get_suggestions(1)
        with pytest.raises(RuntimeError, match="CIRCUIT_OPEN"):
            client.get_suggestions(1)
        assert pythia.serving_stats()["breaker_short_circuits"] == 1

    def test_delete_study_resets_breaker(self):
        reliability = ReliabilityConfig(breaker_failure_threshold=1)
        factory = harness.DesignerPolicyFactory(
            lambda p: failing.FailingDesigner()
        )
        servicer, pythia, client = harness.make_stack(
            factory, reliability=reliability
        )
        (trial,) = client.get_suggestions(1)  # opens the breaker
        assert pythia.serving_stats()["open_breakers"] == 1
        client.delete_study()
        assert pythia.serving_stats()["open_breakers"] == 0
