"""Designer-level auto-switch: threshold, hysteresis, crossover hygiene,
and the off-switch's bit-identity with the seed exact path."""

import jax
import numpy as np
import pytest

from vizier_tpu import pyvizier as vz
from vizier_tpu.algorithms import core as core_lib
from vizier_tpu.designers.gp_bandit import VizierGPBandit
from vizier_tpu.optimizers import lbfgs as lbfgs_lib
from vizier_tpu.surrogates import SurrogateConfig
from vizier_tpu.surrogates import config as config_lib

_FAST = dict(
    ard_optimizer=lbfgs_lib.AdamOptimizer(maxiter=15),
    ard_restarts=3,
    max_acquisition_evaluations=200,
    warm_start_min_trials=0,
    num_seed_trials=1,
)


def _problem(num_params=2):
    p = vz.ProblemStatement()
    for d in range(num_params):
        p.search_space.root.add_float_param(f"x{d}", 0.0, 1.0)
    p.metric_information.append(
        vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    return p


def _trials(start_id, n, seed, num_params=2):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        params = {f"x{d}": float(rng.uniform()) for d in range(num_params)}
        t = vz.Trial(parameters=params, id=start_id + i)
        t.complete(
            vz.Measurement(metrics={"obj": float(sum(params.values()))})
        )
        out.append(t)
    return out


def _params_lists(suggestions):
    return [s.parameters.as_dict() for s in suggestions]


def _tree_equal(a, b):
    leaves_a = jax.tree_util.tree_leaves(a)
    leaves_b = jax.tree_util.tree_leaves(b)
    return len(leaves_a) == len(leaves_b) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(leaves_a, leaves_b)
    )


class TestAutoSwitch:
    def test_exact_below_threshold_sparse_above(self):
        cfg = SurrogateConfig(
            sparse_threshold_trials=8, hysteresis_trials=2, num_inducing=6
        )
        d = VizierGPBandit(_problem(), rng_seed=0, surrogate=cfg, **_FAST)
        d.update(core_lib.CompletedTrials(_trials(1, 5, seed=0)))
        d.suggest(1)
        assert d.surrogate_mode == config_lib.MODE_EXACT
        assert d.surrogate_counts == {
            "sparse_suggests": 0, "crossovers": 0, "nystrom_augments": 0,
            "fit_reads": 0,
        }

        d.update(core_lib.CompletedTrials(_trials(6, 3, seed=1)))
        out = d.suggest(1)
        assert d.surrogate_mode == config_lib.MODE_SPARSE
        assert d.surrogate_counts["sparse_suggests"] == 1
        assert d.surrogate_counts["crossovers"] == 1
        assert d.sparse_inducing_state() is not None
        assert len(out) == 1
        for v in out[0].parameters.as_dict().values():
            assert np.isfinite(v)

    def test_no_config_means_exact_forever(self):
        d = VizierGPBandit(_problem(), rng_seed=0, **_FAST)
        d.update(core_lib.CompletedTrials(_trials(1, 12, seed=0)))
        d.suggest(1)
        assert d.surrogate_mode == config_lib.MODE_EXACT
        assert d.sparse_inducing_state() is None

    def test_sparse_suggestions_accumulate_without_recrossing(self):
        cfg = SurrogateConfig(
            sparse_threshold_trials=4, hysteresis_trials=0, num_inducing=6
        )
        d = VizierGPBandit(_problem(), rng_seed=1, surrogate=cfg, **_FAST)
        d.update(core_lib.CompletedTrials(_trials(1, 6, seed=2)))
        d.suggest(1)
        d.update(core_lib.CompletedTrials(_trials(7, 1, seed=3)))
        d.suggest(1)
        assert d.surrogate_counts["sparse_suggests"] == 2
        assert d.surrogate_counts["crossovers"] == 1  # one transition only


class TestCrossoverInvalidation:
    """Satellite: no stale exact-GP params may leak into the sparse path."""

    def test_crossover_drops_warm_and_posterior_state(self):
        cfg = SurrogateConfig(
            sparse_threshold_trials=8, hysteresis_trials=2, num_inducing=6
        )
        d = VizierGPBandit(_problem(), rng_seed=3, surrogate=cfg, **_FAST)
        d.update(core_lib.CompletedTrials(_trials(1, 6, seed=4)))
        d.suggest(1)  # exact train
        assert d._warm_is_trained
        exact_warm = jax.tree_util.tree_map(np.asarray, d._warm_params)
        assert d._last_predictive is not None

        # Crossing the threshold re-randomizes the warm seed BEFORE any
        # sparse train: the trained exact optimum must not seed (or be
        # served from) the sparse posterior.
        d.update(core_lib.CompletedTrials(_trials(7, 3, seed=5)))
        mode = d._refresh_surrogate_mode()
        assert mode == config_lib.MODE_SPARSE
        assert not d._warm_is_trained
        assert d._last_predictive is None
        assert d._last_sparse_state is None
        assert not _tree_equal(exact_warm, d._warm_params)

        # The next suggest runs the sparse path from the clean slate.
        d.suggest(1)
        assert d.surrogate_counts["sparse_suggests"] == 1
        assert d._warm_is_trained  # now holds the SPARSE optimum
        assert not _tree_equal(exact_warm, d._warm_params)

    def test_mode_is_sticky_across_suggests(self):
        cfg = SurrogateConfig(
            sparse_threshold_trials=6, hysteresis_trials=3, num_inducing=6
        )
        d = VizierGPBandit(_problem(), rng_seed=4, surrogate=cfg, **_FAST)
        d.update(core_lib.CompletedTrials(_trials(1, 7, seed=6)))
        d.suggest(1)
        assert d.surrogate_mode == config_lib.MODE_SPARSE
        # Repeated suggests at the same count stay sparse with no new
        # crossovers (the hysteresis floor is 3, trials stay at 7).
        d.suggest(1)
        assert d.surrogate_counts["crossovers"] == 1


class TestOffSwitchBitIdentity:
    """VIZIER_SPARSE=0 (or no config) must be the seed exact path exactly."""

    @pytest.mark.parametrize(
        "off_cfg", [None, SurrogateConfig.disabled()], ids=["none", "disabled"]
    )
    def test_disabled_matches_no_config_suggestions(self, off_cfg):
        seeds_trials = _trials(1, 10, seed=7)
        base = VizierGPBandit(_problem(), rng_seed=5, **_FAST)
        base.update(core_lib.CompletedTrials(seeds_trials))
        expected = _params_lists(base.suggest(2))

        d = VizierGPBandit(_problem(), rng_seed=5, surrogate=off_cfg, **_FAST)
        d.update(core_lib.CompletedTrials(seeds_trials))
        got = _params_lists(d.suggest(2))
        assert expected == got  # bit-identical, not approximately equal

    def test_below_threshold_matches_no_config_suggestions(self):
        # An enabled config whose threshold is never reached must also be
        # bit-identical to the seed path (the switch reads state only).
        seeds_trials = _trials(1, 10, seed=8)
        base = VizierGPBandit(_problem(), rng_seed=6, **_FAST)
        base.update(core_lib.CompletedTrials(seeds_trials))
        expected = _params_lists(base.suggest(1))

        cfg = SurrogateConfig(sparse_threshold_trials=10_000)
        d = VizierGPBandit(_problem(), rng_seed=6, surrogate=cfg, **_FAST)
        d.update(core_lib.CompletedTrials(seeds_trials))
        assert _params_lists(d.suggest(1)) == expected
