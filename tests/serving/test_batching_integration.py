"""Serving-path batching: config knobs, executor routing, compile cache."""

import os
import threading

import jax
import pytest

from vizier_tpu import pyvizier as vz
from vizier_tpu.serving import ServingConfig, ServingRuntime
from vizier_tpu.serving import compile_cache
from vizier_tpu.service import proto_converters as pc
from vizier_tpu.service import pythia_service, vizier_service
from vizier_tpu.service.protos import study_pb2, vizier_service_pb2

_FAST_GP_KWARGS = None


def _fast_gp_kwargs():
    global _FAST_GP_KWARGS
    if _FAST_GP_KWARGS is None:
        from vizier_tpu.optimizers import lbfgs as lbfgs_lib

        _FAST_GP_KWARGS = dict(
            max_acquisition_evaluations=200,
            ard_restarts=2,
            ard_optimizer=lbfgs_lib.AdamOptimizer(maxiter=10),
            # Few-trial integration runs: keep warm seeding engaged below
            # the production floor so warm-path wiring is exercised.
            warm_start_min_trials=0,
        )
    return _FAST_GP_KWARGS


class _FastGPFactory:
    """DEFAULT -> a cheap VizierGPUCBPEBandit routed through serving."""

    def __init__(self, serving_runtime):
        self._serving = serving_runtime

    def _gp_designer_kwargs(self):
        """Same shape as DefaultPolicyFactory's hook (PythiaServicer.prewarm
        reads it), but with the cheap test budgets folded in."""
        kwargs = dict(_fast_gp_kwargs())
        cfg = self._serving.config
        kwargs["use_warm_start_ard"] = cfg.warm_start
        if cfg.warm_start:
            kwargs["warm_ard_restarts"] = cfg.warm_ard_restarts
        return kwargs

    def __call__(self, problem, algorithm, supporter, study_name):
        from vizier_tpu.designers import gp_ucb_pe
        from vizier_tpu.serving.policy import CachedDesignerStatePolicy

        kwargs = self._gp_designer_kwargs()
        return CachedDesignerStatePolicy(
            supporter,
            lambda p, **kw: gp_ucb_pe.VizierGPUCBPEBandit(p, **kwargs),
            self._serving,
            study_name,
            use_seeding=True,
        )


def _study_config():
    config = vz.StudyConfig(algorithm="DEFAULT")
    for d in range(2):
        config.search_space.root.add_float_param(f"x{d}", 0.0, 1.0)
    config.metric_information.append(
        vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    return config


def _gp_service(serving_config=None):
    servicer = vizier_service.VizierServicer()
    pythia = pythia_service.PythiaServicer(servicer, serving_config=serving_config)
    pythia._policy_factory = _FastGPFactory(pythia.serving_runtime)
    servicer.set_pythia(pythia)
    return servicer, pythia


def _create_study_with_trials(servicer, name, n=3):
    servicer.CreateStudy(
        vizier_service_pb2.CreateStudyRequest(
            parent="owners/o", study=pc.study_to_proto(_study_config(), name)
        )
    )
    for i in range(n):
        created = servicer.CreateTrial(
            vizier_service_pb2.CreateTrialRequest(parent=name, trial=study_pb2.Trial())
        )
        req = vizier_service_pb2.CompleteTrialRequest(name=created.name)
        m = req.final_measurement.metrics.add()
        m.name, m.value = "obj", 0.07 * (i + 1)
        servicer.CompleteTrial(req)


class TestConfigKnobs:
    def test_defaults_on_and_env_off_switch(self, monkeypatch):
        assert ServingConfig().batching is True
        monkeypatch.setenv("VIZIER_BATCHING", "0")
        assert ServingConfig.from_env().batching is False
        monkeypatch.setenv("VIZIER_BATCHING", "1")
        monkeypatch.setenv("VIZIER_BATCH_MAX_SIZE", "16")
        monkeypatch.setenv("VIZIER_BATCH_MAX_WAIT_MS", "2.5")
        cfg = ServingConfig.from_env()
        assert cfg.batching and cfg.batch_max_size == 16
        assert cfg.batch_max_wait_ms == pytest.approx(2.5)
        assert ServingConfig.disabled().batching is False

    def test_compile_cache_env(self, monkeypatch, tmp_path):
        monkeypatch.setenv("VIZIER_COMPILE_CACHE_DIR", str(tmp_path))
        assert ServingConfig.from_env().compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("VIZIER_COMPILE_CACHE_DIR")
        assert ServingConfig.from_env().compilation_cache_dir is None

    def test_batching_off_means_no_executor(self):
        runtime = ServingRuntime(ServingConfig(batching=False))
        assert runtime.batch_executor is None
        runtime.shutdown()  # no-op, must not raise

    def test_batching_on_builds_executor(self):
        runtime = ServingRuntime(ServingConfig(batch_max_size=4))
        try:
            assert runtime.batch_executor is not None
            assert runtime.batch_executor.max_batch_size == 4
        finally:
            runtime.shutdown()


class TestCompilationCacheWiring:
    @pytest.fixture(autouse=True)
    def _restore_jax_cache_config(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = (
            jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs,
        )
        yield
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", before[1])

    def test_runtime_points_jax_at_the_cache_dir(self, tmp_path):
        runtime = ServingRuntime(
            ServingConfig(batching=False, compilation_cache_dir=str(tmp_path))
        )
        assert runtime.compilation_cache_active
        assert runtime.compilation_cache_dir == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)

    def test_no_dir_leaves_jax_alone(self):
        before = jax.config.jax_compilation_cache_dir
        runtime = ServingRuntime(ServingConfig(batching=False))
        assert not runtime.compilation_cache_active
        assert jax.config.jax_compilation_cache_dir == before

    @pytest.mark.parametrize(
        "env_dir, config_dir, entry_point, want, sets_jax_config",
        [
            # JAX's own variable wins and nothing is set in code.
            ("/env/dir", "/config/dir", True, "/env/dir", False),
            ("/env/dir", None, False, "/env/dir", False),
            # Then the repo's own setting.
            (None, "/config/dir", True, "/config/dir", True),
            (None, "/config/dir", False, "/config/dir", True),
            # Then, for entry points only, the fixed checkout directory.
            (None, None, True, compile_cache.CHECKOUT_CACHE_DIR, True),
            (None, None, False, None, False),
        ],
    )
    def test_precedence(
        self, monkeypatch, env_dir, config_dir, entry_point, want, sets_jax_config
    ):
        if env_dir:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        before = jax.config.jax_compilation_cache_dir
        got = compile_cache.configure(config_dir, entry_point=entry_point)
        assert got == want
        assert jax.config.jax_compilation_cache_dir == (
            want if sets_jax_config else before
        )

    def test_entry_point_directory_is_fixed_in_the_checkout(self):
        repo_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        assert compile_cache.CHECKOUT_CACHE_DIR == os.path.join(
            repo_root, ".jax_cache"
        )


class TestServicePathBatching:
    def test_concurrent_studies_share_one_batched_dispatch(self):
        servicer, pythia = _gp_service(
            ServingConfig(batch_max_size=2, batch_max_wait_ms=5000.0)
        )
        studies = ["owners/o/studies/a", "owners/o/studies/b"]
        for s in studies:
            _create_study_with_trials(servicer, s)

        ops, errors = {}, {}

        def run(study, wid):
            try:
                ops[study] = servicer.SuggestTrials(
                    vizier_service_pb2.SuggestTrialsRequest(
                        parent=study, suggestion_count=1, client_id=wid
                    )
                )
            except BaseException as e:  # noqa: BLE001
                errors[study] = e

        threads = [
            threading.Thread(target=run, args=(s, f"w{i}"))
            for i, s in enumerate(studies)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not errors, errors
        for s in studies:
            assert ops[s].done and not ops[s].error, ops[s].error
            assert len(ops[s].response.trials) == 1
        snap = pythia.serving_stats()
        assert snap["batch_flushes"] >= 1
        assert snap["batched_suggests"] == 2
        pythia.shutdown()

    def test_batching_off_restores_per_study_path(self):
        servicer, pythia = _gp_service(ServingConfig(batching=False))
        study = "owners/o/studies/solo"
        _create_study_with_trials(servicer, study)
        op = servicer.SuggestTrials(
            vizier_service_pb2.SuggestTrialsRequest(
                parent=study, suggestion_count=1, client_id="w0"
            )
        )
        assert op.done and not op.error, op.error
        snap = pythia.serving_stats()
        assert snap["batch_flushes"] == 0
        assert snap["batched_suggests"] == 0

    def test_single_study_flushes_alone_via_timeout(self):
        servicer, pythia = _gp_service(
            ServingConfig(batch_max_size=8, batch_max_wait_ms=5.0)
        )
        study = "owners/o/studies/lonely"
        _create_study_with_trials(servicer, study)
        op = servicer.SuggestTrials(
            vizier_service_pb2.SuggestTrialsRequest(
                parent=study, suggestion_count=1, client_id="w0"
            )
        )
        assert op.done and not op.error, op.error
        snap = pythia.serving_stats()
        # Singleton flush -> the sequential per-study path (bit-identical
        # to batching off), accounted as a flush but not a batched slot.
        assert snap["batch_flushes"] == 1
        assert snap["batched_suggests"] == 0
        pythia.shutdown()


class TestPrewarmAPI:
    def test_servicer_prewarm_compiles_bucket_grid(self):
        servicer, pythia = _gp_service(
            ServingConfig(batch_max_size=2, batching_prewarm_max_trials=8)
        )
        report = pythia.prewarm(_study_config())
        assert report, "expected at least one prewarmed bucket"
        assert {r["batch_size"] for r in report} == {1, 2}
        assert all(r["status"] == "ok" for r in report)
        pythia.shutdown()

    def test_prewarm_noop_when_batching_off(self):
        servicer, pythia = _gp_service(ServingConfig(batching=False))
        assert pythia.prewarm(_study_config()) == []

    def test_auto_prewarm_flag_spawns_once_per_shape(self):
        runtime = ServingRuntime(
            ServingConfig(
                batching_prewarm=True,
                batching_prewarm_max_trials=8,
                # max size 1 keeps the background compile tiny: prewarm's
                # batch-size grid {1, max} degenerates to {1, 1}.
                batch_max_size=1,
            )
        )
        try:
            from vizier_tpu.designers import gp_ucb_pe

            problem = _study_config().to_problem()
            factory = lambda p, **kw: gp_ucb_pe.VizierGPUCBPEBandit(  # noqa: E731
                p, **_fast_gp_kwargs()
            )
            assert runtime.maybe_prewarm_batching_async(problem, factory)
            # Same search-space shape: already queued, no second thread.
            assert not runtime.maybe_prewarm_batching_async(problem, factory)
        finally:
            runtime.shutdown()
