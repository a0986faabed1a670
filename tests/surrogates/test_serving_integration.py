"""The surrogate tier through the serving stack: runtime config threading,
stats counters, cache-entry mirrors, and DeleteStudy invalidation."""

import pytest

from vizier_tpu import pyvizier as vz
from vizier_tpu.service import proto_converters as pc
from vizier_tpu.service import pythia_service, vizier_service
from vizier_tpu.service.policy_factory import DefaultPolicyFactory
from vizier_tpu.service.protos import vizier_service_pb2
from vizier_tpu.serving.runtime import ServingRuntime
from vizier_tpu.surrogates import SurrogateConfig

STUDY = "owners/o/studies/s"


def _study_config(num_params=2):
    config = vz.StudyConfig(algorithm="GAUSSIAN_PROCESS_BANDIT")
    for d in range(num_params):
        config.search_space.root.add_float_param(f"x{d}", 0.0, 1.0)
    config.metric_information.append(
        vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    return config


def _create_study(servicer, name=STUDY):
    study = pc.study_to_proto(_study_config(), name)
    servicer.CreateStudy(
        vizier_service_pb2.CreateStudyRequest(parent="owners/o", study=study)
    )


def _complete_some_trials(servicer, n, name=STUDY, start=0):
    from vizier_tpu.service.protos import study_pb2

    for i in range(n):
        created = servicer.CreateTrial(
            vizier_service_pb2.CreateTrialRequest(
                parent=name, trial=study_pb2.Trial()
            )
        )
        req = vizier_service_pb2.CompleteTrialRequest(name=created.name)
        m = req.final_measurement.metrics.add()
        m.name, m.value = "obj", 0.1 * ((start + i) % 9)
        servicer.CompleteTrial(req)


@pytest.fixture()
def sparse_service():
    """A real service whose GP designers auto-switch at 6 trials."""
    surrogates = SurrogateConfig(
        sparse_threshold_trials=6, hysteresis_trials=2, num_inducing=6
    )
    servicer = vizier_service.VizierServicer()
    pythia = pythia_service.PythiaServicer(servicer, surrogate_config=surrogates)
    runtime = pythia.serving_runtime
    assert runtime.surrogates is surrogates  # the passthrough under test
    pythia._policy_factory = _FastFactory(runtime)
    servicer.set_pythia(pythia)
    return servicer, pythia, runtime


class _FastFactory(DefaultPolicyFactory):
    """DefaultPolicyFactory with cheap GP knobs layered on top — the
    surrogate threading under test is the REAL factory code path."""

    def _gp_designer_kwargs(self):
        kwargs = super()._gp_designer_kwargs()
        from vizier_tpu.optimizers import lbfgs as lbfgs_lib

        kwargs.update(
            max_acquisition_evaluations=200,
            ard_restarts=2,
            ard_optimizer=lbfgs_lib.LbfgsOptimizer(maxiter=5),
            warm_start_min_trials=0,
            num_seed_trials=1,
        )
        return kwargs


def _suggest(servicer, step):
    op = servicer.SuggestTrials(
        vizier_service_pb2.SuggestTrialsRequest(
            parent=STUDY, suggestion_count=1, client_id=f"w{step}"
        )
    )
    assert op.done and not op.error, op.error
    return op


class TestFactoryThreading:
    def test_default_factory_threads_runtime_surrogates(self):
        surrogates = SurrogateConfig(sparse_threshold_trials=123)
        runtime = ServingRuntime(surrogates=surrogates)
        factory = DefaultPolicyFactory(runtime)
        kwargs = factory._gp_designer_kwargs()
        assert kwargs["surrogate"] is surrogates

    def test_no_runtime_no_surrogate_kwarg(self):
        assert "surrogate" not in DefaultPolicyFactory()._gp_designer_kwargs()

    def test_runtime_reads_env(self, monkeypatch):
        monkeypatch.setenv("VIZIER_SPARSE", "0")
        assert not ServingRuntime().surrogates.sparse
        monkeypatch.setenv("VIZIER_SPARSE", "1")
        monkeypatch.setenv("VIZIER_SPARSE_THRESHOLD", "77")
        rt = ServingRuntime()
        assert rt.surrogates.sparse
        assert rt.surrogates.sparse_threshold_trials == 77


class TestServingAutoSwitch:
    def test_crossover_counters_and_entry_mirrors(self, sparse_service):
        servicer, pythia, runtime = sparse_service
        _create_study(servicer)
        _complete_some_trials(servicer, 3)
        _suggest(servicer, 0)  # 3 trials: exact

        snap = pythia.serving_stats()
        assert snap["sparse_suggests"] == 0
        assert snap["surrogate_crossovers"] == 0
        entry = runtime.designer_cache.get_or_create(STUDY, lambda: None)
        assert entry.surrogate_mode == "exact"
        assert entry.designer.sparse_inducing_state() is None

        _complete_some_trials(servicer, 4, start=3)
        _suggest(servicer, 1)  # 7 completed trials: sparse

        snap = pythia.serving_stats()
        assert snap["sparse_suggests"] == 1
        assert snap["surrogate_crossovers"] == 1
        entry = runtime.designer_cache.get_or_create(STUDY, lambda: None)
        assert entry.surrogate_mode == "sparse"
        # The trained inducing state (selected set + factorization) is the
        # designer's, read on demand for inspection/hand-off.
        sparse_state = entry.designer.sparse_inducing_state()
        assert sparse_state is not None
        assert sparse_state.sdata.z_continuous.shape[-2] >= 6

        _suggest(servicer, 2)  # stays sparse, no second crossover
        snap = pythia.serving_stats()
        assert snap["sparse_suggests"] == 2
        assert snap["surrogate_crossovers"] == 1

    def test_delete_study_drops_cached_inducing_state(self, sparse_service):
        # Satellite: DeleteStudy must invalidate the whole entry — warm
        # params AND sparse inducing state — so a recreated study of the
        # same name cold-starts with nothing stale.
        servicer, pythia, runtime = sparse_service
        _create_study(servicer)
        _complete_some_trials(servicer, 7)
        _suggest(servicer, 0)
        entry = runtime.designer_cache.get_or_create(STUDY, lambda: None)
        assert entry.designer.sparse_inducing_state() is not None
        assert pythia.serving_stats()["cached_studies"] == 1

        servicer.DeleteStudy(
            vizier_service_pb2.DeleteStudyRequest(name=STUDY)
        )
        snap = pythia.serving_stats()
        assert snap["cached_studies"] == 0
        assert snap["cache_invalidations"] == 1

        # A recreated same-name study builds a FRESH entry: no mirrored
        # mode, a cold designer with no sparse state.
        _create_study(servicer)
        _complete_some_trials(servicer, 2)
        _suggest(servicer, 1)
        fresh = runtime.designer_cache.get_or_create(STUDY, lambda: None)
        assert fresh is not entry
        assert fresh.surrogate_mode == "exact"
        assert fresh.designer.sparse_inducing_state() is None

    def test_sparse_off_runtime_serves_exact_only(self):
        servicer = vizier_service.VizierServicer()
        pythia = pythia_service.PythiaServicer(
            servicer, surrogate_config=SurrogateConfig.disabled()
        )
        pythia._policy_factory = _FastFactory(pythia.serving_runtime)
        servicer.set_pythia(pythia)
        runtime = pythia.serving_runtime
        _create_study(servicer)
        _complete_some_trials(servicer, 8)
        _suggest(servicer, 0)
        snap = pythia.serving_stats()
        assert snap["sparse_suggests"] == 0
        assert snap["surrogate_crossovers"] == 0
        entry = runtime.designer_cache.get_or_create(STUDY, lambda: None)
        assert entry.surrogate_mode == "exact"
