"""Sparse UCB-PE: pending-pick conditioning through the SGPR posterior.

Covers the `gp_ucb_pe_sparse` compute-IR program: auto-switch engagement,
off-switch bit-identity, Nyström augmentation mechanics, batch-pick
diversity (the conditioning actually deflates stddev at earlier picks),
chaos slot isolation through the executor, and predict/sample over the
sparse fit."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vizier_tpu import pyvizier as vz
from vizier_tpu.algorithms import core as core_lib
from vizier_tpu.designers import gp_ucb_pe as gp_ucb_pe_lib
from vizier_tpu.designers.gp_ucb_pe import VizierGPUCBPEBandit
from vizier_tpu.models import kernels
from vizier_tpu.optimizers import lbfgs as lbfgs_lib
from vizier_tpu.parallel.batch_executor import BatchExecutor
from vizier_tpu.surrogates import SurrogateConfig
from vizier_tpu.surrogates import sparse_bandit
from vizier_tpu.surrogates import sparse_gp
from vizier_tpu.testing import chaos as chaos_lib

from tests import program_driver

_FAST = dict(
    ard_optimizer=lbfgs_lib.AdamOptimizer(maxiter=15),
    ard_restarts=3,
    max_acquisition_evaluations=200,
    warm_start_min_trials=0,
)

_SPARSE = SurrogateConfig(
    sparse_threshold_trials=1, hysteresis_trials=0, num_inducing=6
)


def _problem(num_params=2):
    p = vz.ProblemStatement()
    for d in range(num_params):
        p.search_space.root.add_float_param(f"x{d}", 0.0, 1.0)
    p.metric_information.append(
        vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    return p


def _feed(designer, seed, n=12, num_params=2):
    rng = np.random.default_rng(seed)
    trials = []
    for i in range(n):
        params = {f"x{d}": float(rng.uniform()) for d in range(num_params)}
        t = vz.Trial(parameters=params, id=i + 1)
        t.complete(
            vz.Measurement(
                metrics={"obj": float(-sum((v - 0.3) ** 2 for v in params.values()))}
            )
        )
        trials.append(t)
    designer.update(core_lib.CompletedTrials(trials))
    return designer


def _sparse_designer(seed, **kwargs):
    return _feed(
        VizierGPUCBPEBandit(
            _problem(), rng_seed=seed, surrogate=_SPARSE, **_FAST, **kwargs
        ),
        seed,
    )


def _params(suggestions):
    return [s.parameters.as_dict() for s in suggestions]


class TestAutoSwitch:
    def test_sparse_engages_above_threshold(self):
        d = _sparse_designer(0)
        out = d.suggest(3)
        assert len(out) == 3
        assert d.surrogate_mode == "sparse"
        assert d.surrogate_counts["sparse_suggests"] == 1
        assert d.sparse_inducing_state() is not None

    def test_below_threshold_stays_exact(self):
        cfg = SurrogateConfig(sparse_threshold_trials=500)
        d = _feed(
            VizierGPUCBPEBandit(_problem(), rng_seed=0, surrogate=cfg, **_FAST),
            0,
        )
        d.suggest(2)
        assert d.surrogate_mode == "exact"
        assert d.surrogate_counts["sparse_suggests"] == 0

    def test_crossover_resets_per_metric_warm_state(self):
        d = _sparse_designer(3)
        d.suggest(1)
        assert d.surrogate_counts["crossovers"] == 1
        assert d._cached_states is not None
        # The crossover (exact -> sparse on the first suggest) happened
        # BEFORE training, so the sparse train started from a fresh random
        # placeholder, never from exact-GP params.
        assert d.surrogate_mode == "sparse"

    def test_multiobjective_never_flips(self):
        p = _problem()
        p.metric_information.append(
            vz.MetricInformation(
                name="obj2", goal=vz.ObjectiveMetricGoal.MAXIMIZE
            )
        )
        d = VizierGPUCBPEBandit(p, rng_seed=0, surrogate=_SPARSE, **_FAST)
        rng = np.random.default_rng(0)
        trials = []
        for i in range(8):
            t = vz.Trial(
                parameters={
                    "x0": float(rng.uniform()), "x1": float(rng.uniform())
                },
                id=i + 1,
            )
            t.complete(
                vz.Measurement(
                    metrics={"obj": float(rng.uniform()), "obj2": float(rng.uniform())}
                )
            )
            trials.append(t)
        d.update(core_lib.CompletedTrials(trials))
        d.suggest(2)
        assert d.surrogate_mode == "exact"


class TestOffSwitchBitIdentity:
    def test_sparse_ucb_pe_false_is_exact_seed_path(self):
        """sparse_ucb_pe=False (VIZIER_SPARSE_UCB_PE=0) must reproduce the
        no-config exact path bit-for-bit, even above the threshold."""
        off_cfg = SurrogateConfig(
            sparse_threshold_trials=1, hysteresis_trials=0, num_inducing=6,
            sparse_ucb_pe=False,
        )

        def run(surrogate):
            d = _feed(
                VizierGPUCBPEBandit(
                    _problem(), rng_seed=5, surrogate=surrogate, **_FAST
                ),
                5,
            )
            return _params(d.suggest(3))

        assert run(None) == run(off_cfg)

    def test_master_off_is_exact_seed_path(self):
        off = SurrogateConfig(
            sparse=False, sparse_threshold_trials=1, hysteresis_trials=0
        )

        def run(surrogate):
            d = _feed(
                VizierGPUCBPEBandit(
                    _problem(), rng_seed=6, surrogate=surrogate, **_FAST
                ),
                6,
            )
            return _params(d.suggest(2))

        assert run(None) == run(off)


class TestNystromAugmentation:
    def _trained_member(self, seed=0):
        d = _sparse_designer(seed)
        d.suggest(1)
        states_me, _ = d._cached_states
        return jax.tree_util.tree_map(lambda a: a[0, 0], states_me), d

    def test_far_pick_augments_inducing_set(self):
        member, d = self._trained_member()
        all_data = jax.device_put(d._all_points_data(2))  # host NumPy -> device
        sdata = sparse_gp.with_pending_capacity(member.sdata, all_data, 2)
        before = int(jnp.sum(sdata.inducing_mask))
        # The all-ones corner is far from the (0.3-centered) training data:
        # its Nyström residual under the trained lengthscales is large.
        far = kernels.MixedFeatures(
            jnp.full((1, sdata.z_continuous.shape[-1]), 4.0, jnp.float32),
            jnp.zeros((1, sdata.z_categorical.shape[-1]), jnp.int32),
        )
        grown = gp_ucb_pe_lib._append_row_sparse(sdata, far, member)
        assert int(jnp.sum(grown.inducing_mask)) == before + 1
        # The pick also joined the data rows (pending conditioning).
        assert int(jnp.sum(grown.data.row_mask)) == int(
            jnp.sum(sdata.data.row_mask)
        ) + 1

    def test_near_pick_does_not_augment(self):
        member, d = self._trained_member()
        all_data = jax.device_put(d._all_points_data(2))  # host NumPy -> device
        sdata = sparse_gp.with_pending_capacity(member.sdata, all_data, 2)
        before = int(jnp.sum(sdata.inducing_mask))
        # An existing inducing row has zero Nyström residual by definition.
        near = kernels.MixedFeatures(
            sdata.z_continuous[:1], sdata.z_categorical[:1]
        )
        same = gp_ucb_pe_lib._append_row_sparse(sdata, near, member)
        assert int(jnp.sum(same.inducing_mask)) == before
        assert int(jnp.sum(same.data.row_mask)) == int(
            jnp.sum(sdata.data.row_mask)
        ) + 1

    def test_conditioning_deflates_stddev_at_the_pick(self):
        """Appending a pending pick must reduce the conditioned posterior's
        stddev there — the whole point of UCB-PE's all-points posterior."""
        member, d = self._trained_member()
        all_data = jax.device_put(d._all_points_data(2))  # host NumPy -> device
        sdata = sparse_gp.with_pending_capacity(member.sdata, all_data, 2)
        aug_model = d._sparse_all_model(2)
        x = kernels.MixedFeatures(
            jnp.full((1, sdata.z_continuous.shape[-1]), 0.9, jnp.float32),
            jnp.zeros((1, sdata.z_categorical.shape[-1]), jnp.int32),
        )
        coll = aug_model.param_collection()
        p = member.params
        before_state = aug_model.precompute_constrained(p, sdata)
        _, std_before = before_state.predict(x)
        grown = gp_ucb_pe_lib._append_row_sparse(sdata, x, member)
        after_state = aug_model.precompute_constrained(p, grown)
        _, std_after = after_state.predict(x)
        assert float(std_after[0]) < float(std_before[0])


class TestBatchPickDiversity:
    def test_batch_picks_are_distinct_points(self):
        d = _sparse_designer(8)
        out = d.suggest(4)
        points = [tuple(sorted(p.items())) for p in _params(out)]
        assert len(set(points)) == len(points), (
            "pending-pick conditioning failed: duplicate batch picks"
        )


class TestChaosSlotIsolation:
    # ~27 s chaos soak on a 1-core box; slot isolation for the sparse
    # kind is also exercised by the generic executor chaos tests and the
    # chaos_ab harness, so this rides the slow tier (tier-1 timing,
    # ROADMAP.md).
    @pytest.mark.slow
    def test_faulting_sparse_slot_degrades_only_its_own_study(self):
        monkey = chaos_lib.ChaosMonkey(seed=0, failure_prob=1.0)
        chaotic = chaos_lib.ChaosDesigner(_sparse_designer(51), monkey)
        healthy = [_sparse_designer(52), _sparse_designer(53)]
        sequential = [_params(_sparse_designer(s).suggest(1)) for s in (52, 53)]
        ex = BatchExecutor(max_batch_size=3, max_wait_ms=10_000)
        try:
            designers = [chaotic] + healthy
            results = [None] * 3
            errors = [None] * 3

            def run(i):
                try:
                    results[i] = ex.suggest(designers[i], 1)
                except BaseException as e:  # noqa: BLE001
                    errors[i] = e

            threads = [
                threading.Thread(target=run, args=(i,)) for i in range(3)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=240)
            assert isinstance(errors[0], chaos_lib.failing.FailedSuggestError)
            assert errors[1] is None and errors[2] is None
            for seq, res in zip(sequential, (results[1], results[2])):
                got = _params(res)
                assert seq == got
        finally:
            ex.close()

    def test_chaos_program_wraps_sparse_kind(self):
        monkey = chaos_lib.ChaosMonkey(seed=0, failure_prob=0.0)
        wrapped = chaos_lib.ChaosDesigner(_sparse_designer(60), monkey)
        resolved = wrapped.compute_program(1)
        assert resolved is not None
        program, key = resolved
        assert isinstance(program, chaos_lib.ChaosProgram)
        assert key.kind == "gp_ucb_pe_sparse"
        assert program.surrogate_family == "sparse"


class TestSparseFitSurface:
    def test_predict_and_sample_over_sparse_fit(self):
        d = _sparse_designer(70)
        out = d.suggest(2)
        prediction = d.predict(out, rng=np.random.default_rng(0), num_samples=64)
        assert prediction.mean.shape == (2,)
        assert np.all(np.isfinite(prediction.mean))
        assert np.all(prediction.stddev >= 0)

    def test_sparse_metadata_kind_stamped(self):
        d = _sparse_designer(71)
        out = d.suggest(1)
        ns = out[0].metadata.ns("gp_ucb_pe")
        assert ns.get("acquisition") is not None

    def test_exact_and_sparse_never_share_a_bucket(self):
        sparse_key = program_driver.bucket_key(_sparse_designer(80), 1)
        exact_key = program_driver.bucket_key(
            _feed(VizierGPUCBPEBandit(_problem(), rng_seed=81, **_FAST), 81), 1
        )
        assert sparse_key.kind == "gp_ucb_pe_sparse"
        assert exact_key.kind == "gp_ucb_pe"
        assert sparse_key != exact_key
