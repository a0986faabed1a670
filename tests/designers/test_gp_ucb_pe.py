"""GP-UCB-PE behavioral tests (reference ``gp_ucb_pe_test.py`` scenarios).

Covers: pending-point batch diversity, the UCB/PE decision logic and its
overwrite probabilities, multimetric penalty modes + HV-scalarized UCB,
the joint set acquisition, the high-noise regime, capacity guarding, and
unwarped prediction.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vizier_tpu import pyvizier as vz
from vizier_tpu.algorithms import core as core_lib
from vizier_tpu.designers.gp_ucb_pe import (
    UCBPEConfig,
    VizierGPUCBPEBandit,
    _append_row,
)
from vizier_tpu.optimizers import lbfgs as lbfgs_lib

_FAST_ARD = lbfgs_lib.AdamOptimizer(maxiter=20)


def _single_metric_problem(categorical: bool = False) -> vz.ProblemStatement:
    p = vz.ProblemStatement()
    p.search_space.root.add_float_param("x", 0.0, 1.0)
    if categorical:
        p.search_space.root.add_categorical_param("c", ["a", "b", "c"])
    p.metric_information.append(
        vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    return p


def _multi_metric_problem() -> vz.ProblemStatement:
    p = vz.ProblemStatement()
    p.search_space.root.add_float_param("x", 0.0, 1.0)
    p.search_space.root.add_categorical_param("c", ["a", "b"])
    p.metric_information.append(
        vz.MetricInformation(name="f1", goal=vz.ObjectiveMetricGoal.MINIMIZE)
    )
    p.metric_information.append(
        vz.MetricInformation(name="f2", goal=vz.ObjectiveMetricGoal.MINIMIZE)
    )
    return p


def _designer(problem, **kwargs):
    kwargs.setdefault("max_acquisition_evaluations", 300)
    kwargs.setdefault("ard_restarts", 2)
    kwargs.setdefault("ard_optimizer", _FAST_ARD)
    return VizierGPUCBPEBandit(problem, **kwargs)


def _complete(problem, xs, fn, start_id=1):
    trials = []
    names = problem.search_space.parameter_names()
    for i, x in enumerate(xs):
        params = {"x": float(x)}
        if "c" in names:
            values = list(problem.search_space.get("c").feasible_values)
            params["c"] = values[i % len(values)]
        t = vz.Trial(id=start_id + i, parameters=params)
        metrics = fn(float(x))
        t.complete(vz.Measurement(metrics=metrics))
        trials.append(t)
    return trials


class TestDecisionLogic:
    def test_first_pick_is_ucb_with_fresh_completions(self):
        """pe_overwrite_probability=0 → fresh data forces UCB on pick 1."""
        p = _single_metric_problem()
        d = _designer(
            p,
            config=UCBPEConfig(
                pe_overwrite_probability=0.0,
                pe_overwrite_probability_in_high_noise=0.0,
                ucb_overwrite_probability=0.0,
            ),
        )
        d.update(
            core_lib.CompletedTrials(
                _complete(p, np.linspace(0, 1, 6), lambda x: {"obj": -((x - 0.6) ** 2)})
            )
        )
        s = d.suggest(3)
        flags = [si.metadata.ns("gp_ucb_pe")["use_ucb"] for si in s]
        assert flags[0] == "True"
        # Later picks see pick 1 as pending → PE (overwrite prob is 0).
        assert flags[1] == "False" and flags[2] == "False"

    def test_all_pe_when_no_new_completions(self):
        """Active trials newer than completions → PE (ucb_overwrite=0)."""
        p = _single_metric_problem()
        d = _designer(p, config=UCBPEConfig(ucb_overwrite_probability=0.0))
        completed = _complete(
            p, np.linspace(0, 1, 5), lambda x: {"obj": -((x - 0.4) ** 2)}
        )
        active = [vz.Trial(id=50, parameters={"x": 0.9})]  # created after
        d.update(core_lib.CompletedTrials(completed), core_lib.ActiveTrials(active))
        s = d.suggest(2)
        flags = [si.metadata.ns("gp_ucb_pe")["use_ucb"] for si in s]
        assert flags == ["False", "False"]

    def test_ucb_overwrite_probability_one_forces_ucb(self):
        p = _single_metric_problem()
        d = _designer(p, config=UCBPEConfig(ucb_overwrite_probability=1.0))
        completed = _complete(
            p, np.linspace(0, 1, 5), lambda x: {"obj": -((x - 0.4) ** 2)}
        )
        active = [vz.Trial(id=50, parameters={"x": 0.9})]
        d.update(core_lib.CompletedTrials(completed), core_lib.ActiveTrials(active))
        s = d.suggest(2)
        flags = [si.metadata.ns("gp_ucb_pe")["use_ucb"] for si in s]
        assert flags == ["True", "True"]


class TestBatchDiversity:
    def test_batch_picks_are_distinct(self):
        """Pending-point conditioning must spread the batch out.

        Sparse data keeps real posterior uncertainty between observations, so
        the PE picks have room to diversify; with a dense noiseless quadratic
        the promising region itself shrinks to a point and crowding is the
        semantically-correct behavior.
        """
        p = _single_metric_problem()
        d = _designer(
            p,
            max_acquisition_evaluations=800,
            config=UCBPEConfig(
                pe_overwrite_probability=0.0,
                ucb_overwrite_probability=0.0,
                cb_violation_penalty_coefficient=1.0,
            ),
        )
        d.update(
            core_lib.CompletedTrials(
                _complete(p, [0.1, 0.9], lambda x: {"obj": -((x - 0.5) ** 2)})
            )
        )
        s = d.suggest(4)
        xs = sorted(float(si.parameters["x"].value) for si in s)
        gaps = np.diff(xs)
        # No two suggestions collapse onto the same point.
        assert (gaps > 1e-3).all(), xs

    def test_pure_categorical_batch_explores_new_cells(self):
        """Regression: the trust region must not fence the batch onto
        observed categorical cells (it once put every unobserved combo at
        L-inf 1.0 > radius, collapsing all picks onto one observed cell)."""
        p = vz.ProblemStatement()
        for i in range(4):
            p.search_space.root.add_categorical_param(
                f"op{i}", ["a", "b", "c", "d"]
            )
        p.metric_information.append(
            vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
        )
        d = _designer(p, max_acquisition_evaluations=800)
        rng = np.random.default_rng(0)
        trials = []
        for i in range(6):
            cell = {f"op{j}": "abcd"[rng.integers(4)] for j in range(4)}
            t = vz.Trial(id=i + 1, parameters=cell)
            t.complete(
                vz.Measurement(
                    metrics={"obj": float(sum(v == "a" for v in cell.values()))}
                )
            )
            trials.append(t)
        observed = {
            tuple(str(t.parameters.get_value(f"op{j}")) for j in range(4))
            for t in trials
        }
        d.update(core_lib.CompletedTrials(trials))
        suggested = {
            tuple(str(s.parameters[f"op{j}"].value) for j in range(4))
            for s in d.suggest(4)
        }
        # The batch is diverse AND reaches outside the observed cells.
        assert len(suggested) > 1, suggested
        assert suggested - observed, (suggested, observed)

    def test_pending_active_trials_are_avoided(self):
        """A pending point deflates stddev around itself → PE goes elsewhere."""
        p = _single_metric_problem()
        d = _designer(
            p,
            max_acquisition_evaluations=800,
            config=UCBPEConfig(ucb_overwrite_probability=0.0),
        )
        completed = _complete(
            p, np.linspace(0, 1, 6), lambda x: {"obj": -((x - 0.5) ** 2)}
        )
        active = [vz.Trial(id=40, parameters={"x": 0.52})]
        d.update(core_lib.CompletedTrials(completed), core_lib.ActiveTrials(active))
        s = d.suggest(1)
        x = float(s[0].parameters["x"].value)
        assert abs(x - 0.52) > 0.02


class TestMultimetric:
    @pytest.mark.parametrize("mode", ["union", "intersection", "average"])
    def test_penalty_modes_run_mixed_space(self, mode):
        p = _multi_metric_problem()
        d = _designer(
            p,
            config=UCBPEConfig(
                num_scalarizations=32,
                multimetric_promising_region_penalty_type=mode,
            ),
        )
        trials = []
        for i, x in enumerate(np.linspace(0, 1, 6)):
            t = vz.Trial(
                id=i + 1, parameters={"x": float(x), "c": ["a", "b"][i % 2]}
            )
            t.complete(
                vz.Measurement(metrics={"f1": x**2, "f2": (x - 1) ** 2})
            )
            trials.append(t)
        d.update(core_lib.CompletedTrials(trials))
        s = d.suggest(3)  # mixed-space multi-objective q-batch: the gap row
        assert len(s) == 3
        assert all("use_ucb" in si.metadata.ns("gp_ucb_pe") for si in s)

    def test_invalid_penalty_mode_rejected(self):
        with pytest.raises(ValueError):
            UCBPEConfig(multimetric_promising_region_penalty_type="bogus")

    def test_multimetric_predict_shapes(self):
        p = _multi_metric_problem()
        d = _designer(p, config=UCBPEConfig(num_scalarizations=16))
        trials = []
        for i, x in enumerate(np.linspace(0, 1, 5)):
            t = vz.Trial(
                id=i + 1, parameters={"x": float(x), "c": ["a", "b"][i % 2]}
            )
            t.complete(vz.Measurement(metrics={"f1": x, "f2": 1 - x}))
            trials.append(t)
        d.update(core_lib.CompletedTrials(trials))
        s = d.suggest(2)
        pred = d.predict(s, num_samples=64)
        assert pred.mean.shape == (2, 2)
        assert np.isfinite(pred.stddev).all()


class TestSetAcquisition:
    def test_joint_set_pe_batch(self):
        p = _single_metric_problem()
        d = _designer(
            p,
            config=UCBPEConfig(optimize_set_acquisition_for_exploration=True),
        )
        d.update(
            core_lib.CompletedTrials(
                _complete(p, np.linspace(0, 1, 6), lambda x: {"obj": -((x - 0.3) ** 2)})
            )
        )
        s = d.suggest(3)
        assert len(s) == 3
        xs = sorted(float(si.parameters["x"].value) for si in s)
        # log-det objective decorrelates the set: members must not coincide.
        assert (np.diff(xs) > 1e-4).all(), xs

    def test_set_acquisition_rejects_multimetric(self):
        p = _multi_metric_problem()
        d = _designer(
            p,
            config=UCBPEConfig(optimize_set_acquisition_for_exploration=True),
        )
        trials = []
        for i, x in enumerate(np.linspace(0, 1, 5)):
            t = vz.Trial(
                id=i + 1, parameters={"x": float(x), "c": ["a", "b"][i % 2]}
            )
            t.complete(vz.Measurement(metrics={"f1": x, "f2": 1 - x}))
            trials.append(t)
        d.update(core_lib.CompletedTrials(trials))
        with pytest.raises(ValueError, match="one objective"):
            d.suggest(2)


class TestHighNoiseRegime:
    def test_snr_flips_pe_probability(self):
        """In high noise, pe_overwrite_in_high_noise=1 forces PE on pick 1."""
        p = _single_metric_problem()
        d = _designer(
            p,
            config=UCBPEConfig(
                signal_to_noise_threshold=1e6,  # everything counts as noisy
                pe_overwrite_probability=0.0,
                pe_overwrite_probability_in_high_noise=1.0,
                ucb_overwrite_probability=0.0,
            ),
        )
        rng = np.random.default_rng(0)
        d.update(
            core_lib.CompletedTrials(
                _complete(
                    p,
                    np.linspace(0, 1, 8),
                    lambda x: {"obj": float(rng.normal())},  # pure noise
                )
            )
        )
        s = d.suggest(1)
        assert s[0].metadata.ns("gp_ucb_pe")["use_ucb"] == "False"


class TestPlumbing:
    def test_capacity_reserved_for_batch(self):
        p = _single_metric_problem()
        d = _designer(p)
        d.update(
            core_lib.CompletedTrials(
                _complete(p, np.linspace(0, 1, 7), lambda x: {"obj": x})
            )
        )
        all_data = d._all_points_data(5)
        spare = all_data.row_mask.shape[0] - int(jnp.sum(all_data.row_mask))
        assert spare >= 5

    def test_append_row_fills_first_free_slot(self):
        p = _single_metric_problem()
        d = _designer(p)
        d.update(
            core_lib.CompletedTrials(
                _complete(p, np.linspace(0, 1, 3), lambda x: {"obj": x})
            )
        )
        all_data = jax.device_put(d._all_points_data(2))  # host NumPy -> device
        n_before = int(jnp.sum(all_data.row_mask))
        from vizier_tpu.models import kernels as kernels_lib

        x = kernels_lib.MixedFeatures(
            jnp.full((1, all_data.continuous.shape[-1]), 0.25),
            jnp.zeros((1, all_data.categorical.shape[-1]), jnp.int32),
        )
        grown = _append_row(all_data, x)
        assert int(jnp.sum(grown.row_mask)) == n_before + 1
        np.testing.assert_allclose(grown.continuous[n_before], 0.25)

    def test_seed_trials_count_includes_active(self):
        p = _single_metric_problem()
        d = _designer(p, num_seed_trials=3)
        active = [vz.Trial(id=i, parameters={"x": 0.5}) for i in range(1, 4)]
        d.update(core_lib.CompletedTrials([]), core_lib.ActiveTrials(active))
        # 3 active >= 3 seeds → GP path (runs ARD on an empty completed set).
        s = d.suggest(1)
        assert len(s) == 1

    def test_sample_with_zero_completed_trials(self):
        """sample()/predict() on a fresh study (active-only) must not crash."""
        p = _single_metric_problem()
        d = _designer(p, num_seed_trials=2)
        active = [vz.Trial(id=i, parameters={"x": 0.3 * i}) for i in (1, 2)]
        d.update(core_lib.CompletedTrials([]), core_lib.ActiveTrials(active))
        s = d.suggest(1)
        samples = d.sample(s, rng=jax.random.PRNGKey(0), num_samples=8)
        assert samples.shape == (8, 1)
        assert np.isfinite(samples).all()

    def test_predict_reuses_cached_fit(self):
        """predict() after suggest() must not retrain the GP."""
        p = _single_metric_problem()
        d = _designer(p)
        d.update(
            core_lib.CompletedTrials(
                _complete(p, np.linspace(0, 1, 6), lambda x: {"obj": x})
            )
        )
        s = d.suggest(1)
        assert d._cached_states is not None
        states_before = d._cached_states[0]
        d.predict(s, num_samples=16)
        assert d._cached_states[0] is states_before  # same fit object
        # New completed data invalidates the cache.
        d.update(
            core_lib.CompletedTrials(
                _complete(p, [0.55], lambda x: {"obj": x}, start_id=50)
            )
        )
        assert d._cached_states is None

    def test_unwarped_sample_scale(self):
        """Samples come back in the ORIGINAL metric scale, not warped."""
        p = _single_metric_problem()
        d = _designer(p)
        # Labels around 1000 — warped space is ~[-0.5, 0.5], so unwarping
        # must restore the magnitude.
        d.update(
            core_lib.CompletedTrials(
                _complete(p, np.linspace(0, 1, 8), lambda x: {"obj": 1000.0 + x})
            )
        )
        s = d.suggest(1)
        samples = d.sample(s, rng=jax.random.PRNGKey(1), num_samples=32)
        assert samples.shape == (32, 1)
        assert 900.0 < np.median(samples) < 1100.0


class TestPriorAcquisition:
    def _problem(self):
        p = vz.ProblemStatement()
        p.search_space.root.add_float_param("x", 0.0, 1.0)
        p.search_space.root.add_float_param("y", 0.0, 1.0)
        p.metric_information.append(
            vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
        )
        return p

    def _run(self, designer, n=6):
        tid = 0
        rng = np.random.default_rng(0)
        for _ in range(n):
            (s,) = designer.suggest(1)
            tid += 1
            t = s.to_trial(tid)
            t.complete(
                vz.Measurement(
                    metrics={"obj": float(rng.normal())}
                )
            )
            designer.update(core_lib.CompletedTrials([t]), core_lib.ActiveTrials())
        return designer

    def test_prior_steers_suggestions(self):
        from vizier_tpu.designers.gp_ucb_pe import UCBPEConfig, VizierGPUCBPEBandit

        def corner_prior(query):
            # Overwhelming preference for the (1, 1) corner in scaled space.
            return -1e4 * jnp.sum((query.continuous - 1.0) ** 2, axis=-1)

        problem = self._problem()
        designer = VizierGPUCBPEBandit(
            problem,
            config=UCBPEConfig(ucb_coefficient=1.8),
            num_seed_trials=1,
            rng_seed=0,
            prior_acquisition=corner_prior,
        )
        self._run(designer, n=5)
        # Post-seed suggestions must hug the preferred corner.
        (s,) = designer.suggest(1)
        assert s.parameters["x"].value > 0.85, s.parameters.as_dict()
        assert s.parameters["y"].value > 0.85, s.parameters.as_dict()

    def test_prior_with_set_acquisition(self):
        from vizier_tpu.designers.gp_ucb_pe import UCBPEConfig, VizierGPUCBPEBandit

        def corner_prior(query):
            return -1e4 * jnp.sum((query.continuous - 1.0) ** 2, axis=-1)

        problem = self._problem()
        designer = VizierGPUCBPEBandit(
            problem,
            config=UCBPEConfig(
                optimize_set_acquisition_for_exploration=True
            ),
            num_seed_trials=1,
            rng_seed=0,
            prior_acquisition=corner_prior,
        )
        self._run(designer, n=3)
        batch = designer.suggest(3)
        assert len(batch) == 3
        for s in batch:
            assert s.parameters["x"].value > 0.8, s.parameters.as_dict()


class TestAcquisitionBudgetPolicy:
    """Batch budget semantics (TPU-first default: one sweep's evaluations
    per suggest() call, split across picks; per_pick = reference behavior,
    75k per pick, ref gp_ucb_pe.py:693-697,1440-1446)."""

    def test_default_is_first_pick_full(self):
        problem = _single_metric_problem()
        d = _designer(problem, max_acquisition_evaluations=75_000)
        assert d.acquisition_budget_policy == "first_pick_full"
        # Remaining 24 picks split one further full budget.
        assert d._pick_vec_opt(25).max_evaluations == 75_000 // 24
        # Single pick keeps the full budget.
        assert d._pick_vec_opt(1).max_evaluations == 75_000

    def test_per_batch_splits_across_all_picks(self):
        problem = _single_metric_problem()
        d = _designer(
            problem,
            max_acquisition_evaluations=75_000,
            acquisition_budget_policy="per_batch",
        )
        assert d._pick_vec_opt(25).max_evaluations == 3_000
        assert d._pick_vec_opt(1).max_evaluations == 75_000

    def test_split_budget_floors_at_minimum(self):
        from vizier_tpu.designers import gp_ucb_pe as mod

        problem = _single_metric_problem()
        d = _designer(
            problem,
            max_acquisition_evaluations=1_000,
            acquisition_budget_policy="per_batch",
        )
        assert d._pick_vec_opt(25).max_evaluations == mod._MIN_PICK_EVALUATIONS

    def test_first_pick_full_runs_two_programs(self):
        """Batch suggest under the default policy: first pick full budget,
        remainder split; the batch still comes back whole and in-box."""
        problem = _single_metric_problem()
        d = _designer(problem, max_acquisition_evaluations=900, num_seed_trials=1)
        trials = _complete(
            problem,
            np.random.default_rng(0).uniform(size=5),
            lambda x: {"obj": -((x - 0.5) ** 2)},
        )
        d.update(core_lib.CompletedTrials(trials))
        batch = d.suggest(3)
        assert len(batch) == 3
        for s in batch:
            assert 0.0 <= float(s.parameters["x"].value) <= 1.0
        # Picks 2-3 saw pick 1 as pending: no duplicate suggestions.
        xs = sorted(float(s.parameters["x"].value) for s in batch)
        assert all(b - a > 1e-4 for a, b in zip(xs, xs[1:])), xs

    def test_per_pick_policy_uses_full_budget(self):
        problem = _single_metric_problem()
        d = _designer(
            problem,
            max_acquisition_evaluations=75_000,
            acquisition_budget_policy="per_pick",
        )
        assert d._pick_vec_opt(25) is d._vec_opt
        assert d._pick_vec_opt(25).max_evaluations == 75_000

    def test_invalid_policy_rejected(self):
        problem = _single_metric_problem()
        with pytest.raises(ValueError, match="acquisition_budget_policy"):
            _designer(problem, acquisition_budget_policy="bogus")

    def test_pick_opt_cache_reuses_instances(self):
        problem = _single_metric_problem()
        d = _designer(problem, max_acquisition_evaluations=75_000)
        assert d._pick_vec_opt(25) is d._pick_vec_opt(25)

    def test_batch_suggest_runs_under_split_budget(self):
        problem = _single_metric_problem()
        d = _designer(problem, max_acquisition_evaluations=600, num_seed_trials=1)
        trials = _complete(
            problem,
            np.random.default_rng(0).uniform(size=5),
            lambda x: {"obj": -((x - 0.5) ** 2)},
        )
        d.update(core_lib.CompletedTrials(trials))
        batch = d.suggest(4)
        assert len(batch) == 4


class TestProfilerSpans:
    """suggest() emits the reference's profiler span names
    (ref gp_ucb_pe.py `profiler.timeit('acquisition_optimizer')` etc.)."""

    def test_suggest_emits_latency_events(self):
        from vizier_tpu.utils import profiler

        problem = _single_metric_problem()
        d = _designer(problem, num_seed_trials=1)
        trials = _complete(
            problem,
            np.random.default_rng(0).uniform(size=4),
            lambda x: {"obj": -((x - 0.5) ** 2)},
        )
        d.update(core_lib.CompletedTrials(trials))
        with profiler.collect_events() as events:
            d.suggest(2)
        names = {e.name for e in events}
        assert {"train_gp", "acquisition_optimizer", "best_candidates_to_trials"} <= names


class TestRetraceDiscipline:
    def test_no_retrace_within_padding_bucket_batch(self):
        """Steady-state batch suggests under first_pick_full reuse both
        compiled programs (the full-budget pick and the split rest-batch)."""
        from vizier_tpu.designers import gp_ucb_pe as mod

        problem = _single_metric_problem()
        d = _designer(problem, num_seed_trials=1, max_acquisition_evaluations=300)
        rng = np.random.default_rng(0)
        tid = 0

        def complete_round():
            nonlocal tid
            done = []
            for s in d.suggest(2):
                tid += 1
                t = s.to_trial(tid)
                t.complete(vz.Measurement(metrics={"obj": float(rng.uniform())}))
                done.append(t)
            d.update(core_lib.CompletedTrials(done))

        complete_round()  # seeding round
        complete_round()  # 2 trials: compile both programs in the 8-bucket
        complete_round()  # 4 trials: still 8-bucket (4+2 spare rows -> 8)
        size = mod._suggest_batch._cache_size()
        complete_round()  # 6 trials: 6+2 -> still the 8-bucket, no retrace
        assert mod._suggest_batch._cache_size() == size


class TestPredictionUserScale:
    def test_minimize_metrics_predict_in_user_scale(self):
        """Multimetric: a MINIMIZE metric's predictions come back positive
        (user scale), not negated into the model's all-MAXIMIZE space."""
        p = vz.ProblemStatement()
        p.search_space.root.add_float_param("x", 0.0, 1.0)
        p.metric_information.append(
            vz.MetricInformation(name="loss", goal=vz.ObjectiveMetricGoal.MINIMIZE)
        )
        p.metric_information.append(
            vz.MetricInformation(name="acc", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
        )
        d = _designer(p, num_seed_trials=1)
        trials = []
        for i, x in enumerate(np.linspace(0.0, 1.0, 8)):
            t = vz.Trial(id=i + 1, parameters={"x": float(x)})
            t.complete(
                vz.Measurement(
                    metrics={
                        "loss": float(5.0 + (x - 0.5) ** 2),  # in [5, 5.25]
                        "acc": float(0.9 - (x - 0.5) ** 2),  # in [0.65, 0.9]
                    }
                )
            )
            trials.append(t)
        d.update(core_lib.CompletedTrials(trials))
        pred = d.predict(
            [vz.TrialSuggestion(parameters={"x": 0.5})], num_samples=500
        )
        loss_mean, acc_mean = float(pred.mean[0, 0]), float(pred.mean[0, 1])
        assert 4.5 < loss_mean < 5.8, pred.mean
        assert 0.5 < acc_mean < 1.1, pred.mean
