"""The sharded data plane IS the production designer path.

Asserts that designers auto-build a mesh, route ARD restarts + acquisition
pools through ``vizier_tpu.parallel``, and that an 8-device mesh suggest()
agrees with the single-device suggest() within tolerance on a peaked
objective.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from vizier_tpu import pyvizier as vz
from vizier_tpu.algorithms import core as core_lib
from vizier_tpu.designers.gp_bandit import VizierGPBandit
from vizier_tpu.designers.gp_ucb_pe import UCBPEConfig, VizierGPUCBPEBandit
from vizier_tpu.optimizers import lbfgs as lbfgs_lib

_FAST_ARD = lbfgs_lib.LbfgsOptimizer(maxiter=25)


def _problem():
    p = vz.ProblemStatement()
    p.search_space.root.add_float_param("x", 0.0, 1.0)
    p.search_space.root.add_float_param("y", 0.0, 1.0)
    p.metric_information.append(
        vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    return p


def _trials(n=12, seed=0):
    rng = np.random.default_rng(seed)
    trials = []
    for i in range(n):
        x, y = rng.uniform(), rng.uniform()
        t = vz.Trial(id=i + 1, parameters={"x": float(x), "y": float(y)})
        t.complete(
            vz.Measurement(
                metrics={"obj": -((x - 0.62) ** 2) - (y - 0.31) ** 2}
            )
        )
        trials.append(t)
    return trials


def _suggest_xy(designer, count=1):
    designer.update(core_lib.CompletedTrials(_trials()))
    s = designer.suggest(count)
    return np.array(
        [[float(si.parameters["x"].value), float(si.parameters["y"].value)] for si in s]
    )


class TestDesignerMeshIsProductionPath:
    def test_gp_bandit_builds_mesh_automatically(self, monkeypatch):
        monkeypatch.delenv("VIZIER_DISABLE_MESH", raising=False)
        d = VizierGPBandit(_problem())
        assert d._mesh is not None
        assert len(d._mesh.devices.flat) == len(jax.devices())

    def test_mesh_suggest_matches_single_device(self):
        kwargs = dict(
            ard_restarts=8,
            ard_optimizer=_FAST_ARD,
            max_acquisition_evaluations=2000,
            num_seed_trials=2,
            rng_seed=3,
        )
        single = VizierGPBandit(_problem(), use_mesh=False, **kwargs)
        meshed = VizierGPBandit(_problem(), use_mesh=True, **kwargs)
        assert meshed._mesh is not None and len(meshed._mesh.devices.flat) == 8
        xy_single = _suggest_xy(single)[0]
        xy_meshed = _suggest_xy(meshed)[0]
        # Both must land near the optimum (0.62, 0.31); the sharded path runs
        # 8 independent pools so exact equality is not expected.
        assert np.linalg.norm(xy_single - np.array([0.62, 0.31])) < 0.25
        assert np.linalg.norm(xy_meshed - np.array([0.62, 0.31])) < 0.25
        assert np.linalg.norm(xy_single - xy_meshed) < 0.3

    def test_ucb_pe_default_runs_on_mesh(self):
        d = VizierGPUCBPEBandit(
            _problem(),
            use_mesh=True,
            ard_restarts=8,
            ard_optimizer=_FAST_ARD,
            max_acquisition_evaluations=600,
            config=UCBPEConfig(num_scalarizations=16),
        )
        assert d._mesh is not None
        xy = _suggest_xy(d, count=3)
        assert xy.shape == (3, 2)
        assert np.isfinite(xy).all()

    def test_mesh_restarts_round_up_to_device_multiple(self):
        d = VizierGPBandit(
            _problem(), use_mesh=True, ard_restarts=5, ard_optimizer=_FAST_ARD
        )
        # 5 restarts on 8 devices → padded to 8 at the _train boundary.
        ndev = d._mesh_size()
        restarts = -(-d.ard_restarts // ndev) * ndev
        assert restarts == 8


def _two_metric_problem():
    p = vz.ProblemStatement()
    p.search_space.root.add_float_param("x", 0.0, 1.0)
    p.search_space.root.add_float_param("y", 0.0, 1.0)
    for name in ("m1", "m2"):
        p.metric_information.append(
            vz.MetricInformation(name=name, goal=vz.ObjectiveMetricGoal.MAXIMIZE)
        )
    return p


class TestMeshSeparableMultitask:
    """mesh x SEPARABLE: the sharded joint-GP train path
    (``gp_ucb_pe._train_states_me`` mesh branch) must be exercised and agree
    with the unsharded trainer."""

    def _mt_designer(self, use_mesh):
        from vizier_tpu.models import multitask_gp as mtgp

        return VizierGPUCBPEBandit(
            _two_metric_problem(),
            use_mesh=use_mesh,
            ard_restarts=8,
            ard_optimizer=_FAST_ARD,
            max_acquisition_evaluations=600,
            rng_seed=5,
            num_seed_trials=2,
            config=UCBPEConfig(
                multitask_type=mtgp.MultiTaskType.SEPARABLE,
                num_scalarizations=16,
            ),
        )

    def _mt_trials(self, n=10, seed=0):
        rng = np.random.default_rng(seed)
        trials = []
        for i in range(n):
            x, y = rng.uniform(), rng.uniform()
            base = -((x - 0.62) ** 2) - (y - 0.31) ** 2
            t = vz.Trial(id=i + 1, parameters={"x": float(x), "y": float(y)})
            t.complete(
                vz.Measurement(
                    metrics={"m1": base, "m2": 0.8 * base + 0.01 * rng.normal()}
                )
            )
            trials.append(t)
        return trials

    def test_separable_suggests_on_mesh(self):
        from vizier_tpu.models import multitask_gp as mtgp

        d = self._mt_designer(use_mesh=True)
        assert d._mesh is not None and len(d._mesh.devices.flat) == 8
        d.update(core_lib.CompletedTrials(self._mt_trials()))
        suggestions = d.suggest(3)
        assert len(suggestions) == 3
        states, _ = d._train_states_me()
        assert isinstance(states, mtgp.MultiTaskGPState)
        for s in suggestions:
            for name in ("x", "y"):
                assert 0.0 <= float(s.parameters[name].value) <= 1.0

    def test_sharded_joint_train_matches_unsharded(self):
        """The mesh branch of ``_train_states_me`` (which routes through
        ``parallel.train_gp_sharded`` on the duck-typed multitask model) must
        produce the same fit as the unsharded trainer given the same rng."""
        from vizier_tpu.models import multitask_gp as mtgp

        meshed = self._mt_designer(use_mesh=True)
        meshed.update(core_lib.CompletedTrials(self._mt_trials()))
        states_sharded, _ = meshed._train_states_me()
        assert isinstance(states_sharded, mtgp.MultiTaskGPState)

        # Rebuild the same joint data and rng stream unsharded.
        unsharded = self._mt_designer(use_mesh=False)
        unsharded.update(core_lib.CompletedTrials(self._mt_trials()))
        states_plain, _ = unsharded._train_states_me()

        # Same seed + same restart count (8 rounds up to 8) -> same selected
        # hyperparameters up to float reduction order.
        for k in states_plain.params:
            np.testing.assert_allclose(
                np.asarray(states_sharded.params[k]),
                np.asarray(states_plain.params[k]),
                rtol=0.1,
                atol=0.05,
                err_msg=f"param {k} diverged between sharded/unsharded",
            )


class TestShardedQEI:
    """The joint-batch qEI sweep on the mesh (round-5): pool-sharded
    search of (q*D)-space must return valid batches, and the top-k merge
    must equal the best over its own per-key pools."""

    def _designer(self, use_mesh):
        return VizierGPBandit(
            _problem(),
            use_mesh=use_mesh,
            rng_seed=5,
            ard_restarts=2,
            ard_optimizer=lbfgs_lib.LbfgsOptimizer(maxiter=10),
            max_acquisition_evaluations=300,
            acquisition="qei",
            num_seed_trials=2,
        )

    def test_mesh_qei_batch_valid_and_distinct(self):
        d = self._designer(use_mesh=True)
        assert d._mesh is not None
        pts = _suggest_xy(d, count=3)
        assert pts.shape == (3, 2)
        assert np.all((0.0 <= pts) & (pts <= 1.0))
        # The joint posterior penalizes duplicated batch members; the three
        # suggestions should not collapse onto one point.
        assert np.unique(np.round(pts, 3), axis=0).shape[0] > 1

    def test_mesh_qei_merge_is_best_over_pools(self):
        """Deterministic merge property of the mechanism qEI rides: with a
        closure score_fn over flattened (q*D)-space (no MC randomness),
        the sharded result equals the argmax over its per-key pools."""
        import jax.numpy as jnp

        from vizier_tpu import parallel
        from vizier_tpu.optimizers import eagle as eagle_lib
        from vizier_tpu.optimizers import vectorized as vectorized_lib

        q, dc = 2, 2
        target = jnp.asarray([0.2, 0.8, 0.7, 0.3])  # one optimum per slot

        def score_fn(target, feats):
            return -jnp.sum((feats.continuous - target) ** 2, axis=-1)

        strategy = eagle_lib.VectorizedEagleStrategy(
            num_continuous=q * dc, category_sizes=()
        )
        vec = vectorized_lib.VectorizedOptimizer(strategy, max_evaluations=300)
        mesh = parallel.create_mesh()
        n_pools = len(mesh.devices.flat)
        key = jax.random.PRNGKey(9)
        sharded = parallel.maximize_score_fn_sharded(
            vec, score_fn, target, key, count=1, num_pools=n_pools, mesh=mesh
        )
        pool_best = [
            float(
                vec(
                    functools.partial(score_fn, target), jnp.asarray(k), count=1
                ).scores[0]
            )
            for k in np.asarray(jax.random.split(key, n_pools))
        ]
        np.testing.assert_allclose(
            float(sharded.scores[0]), max(pool_best), rtol=1e-5
        )
        # And the merged optimum is near the planted target.
        np.testing.assert_allclose(
            np.asarray(sharded.features.continuous[0]), np.asarray(target),
            atol=0.1,
        )


def _bowl(operands, feats):
    """A score with everything it reads in ``operands``: two planted optima,
    the better one sharper."""
    near, far = operands
    d_near = jnp.sum((feats.continuous - near) ** 2, axis=-1)
    d_far = jnp.sum((feats.continuous - far) ** 2, axis=-1)
    return jnp.maximum(-d_near, -0.5 - 4.0 * d_far)


def _vmapped_pools(
    vec_opt, score_fn, operands, rng, count, num_pools, mesh, prior_features=None
):
    """The sharded sweep as it was while the pools were a ``vmap`` axis for
    the partitioner to split (before PR 40), under today's signature."""
    from vizier_tpu import parallel

    keys = jax.lax.with_sharding_constraint(
        jax.random.split(rng, num_pools), parallel.batch_sharded(mesh)
    )
    results = jax.vmap(
        lambda key: vec_opt(
            functools.partial(score_fn, operands),
            key,
            count=count,
            prior_features=prior_features,
        )
    )(keys)
    scores = results.scores.reshape(num_pools * count)
    top_scores, idx = jax.lax.top_k(scores, count)
    return type(results)(
        jax.tree_util.tree_map(
            lambda a: a.reshape((num_pools * count,) + a.shape[2:])[idx],
            results.features,
        ),
        top_scores,
    )


def _eagle_sweep(max_evaluations: int):
    from vizier_tpu.optimizers import eagle as eagle_lib
    from vizier_tpu.optimizers import vectorized as vectorized_lib

    strategy = eagle_lib.VectorizedEagleStrategy(num_continuous=3, category_sizes=())
    return vectorized_lib.VectorizedOptimizer(strategy, max_evaluations=max_evaluations)


class TestShardedSweepIsItsDefinition:
    """The pools are a manual axis of the mesh: each device runs the plain
    ``vec_opt`` on its own key(s). The same work as pool by pool on one
    device: the same keys, every pool its full budget, one top-k."""

    @pytest.mark.parametrize(
        "num_pools,devices,count",
        [(2, 2, 1), (2, 2, 3), (4, 4, 1), (4, 4, 3), (4, 2, 1), (4, 2, 3)],
    )
    def test_sharded_sweep_is_the_top_k_over_its_pools(
        self, num_pools, devices, count
    ):
        from vizier_tpu import parallel
        from vizier_tpu.models import kernels

        vec = _eagle_sweep(400)
        operands = (jnp.asarray([0.2, 0.8, 0.5]), jnp.asarray([0.7, 0.3, 0.1]))
        prior = kernels.MixedFeatures(
            jnp.asarray([[0.6, 0.4, 0.2], [0.1, 0.9, 0.4]]),
            jnp.zeros((2, 0), jnp.int32),
        )
        key = jax.random.PRNGKey(11)
        mesh = parallel.create_mesh(devices)
        sharded = parallel.maximize_score_fn_sharded(
            vec, _bowl, operands, key, count, num_pools, mesh, prior
        )
        pools = [
            vec(
                functools.partial(_bowl, operands),
                k,
                count=count,
                prior_features=prior,
            )
            for k in jax.random.split(key, num_pools)
        ]
        scores = np.concatenate([np.asarray(p.scores) for p in pools])
        cont = np.concatenate([np.asarray(p.features.continuous) for p in pools])
        # ``top_k``'s order: descending, the lowest index among ties.
        best = np.argsort(-scores, kind="stable")[:count]
        assert sharded.scores.shape == (count,)
        # To the bit on the CPU: a device's program is the pool's own.
        np.testing.assert_array_equal(np.asarray(sharded.scores), scores[best])
        np.testing.assert_array_equal(
            np.asarray(sharded.features.continuous), cont[best]
        )

    def test_pools_must_tile_the_mesh(self):
        from vizier_tpu import parallel

        with pytest.raises(ValueError, match="multiple"):
            parallel.maximize_score_fn_sharded(
                _eagle_sweep(100), _bowl, (jnp.zeros(3), jnp.ones(3)), jax.random.PRNGKey(0),
                1, 3, parallel.create_mesh(2),
            )

    def test_mesh_suggest_returns_what_the_vmapped_pools_returned(
        self, monkeypatch
    ):
        """A UCB-PE suggest on the mesh, as the service's policy makes it,
        returns the suggestions it returned while the pools were a ``vmap``
        axis: the same keys, the same sweeps, the same merges."""
        from vizier_tpu import parallel
        from vizier_tpu.designers import gp_ucb_pe

        def suggest():
            d = VizierGPUCBPEBandit(
                _problem(),
                use_mesh=True,
                ard_restarts=8,
                ard_optimizer=_FAST_ARD,
                max_acquisition_evaluations=600,
                rng_seed=7,
                num_seed_trials=2,
            )
            assert d._mesh is not None and len(d._mesh.devices.flat) == 8
            return _suggest_xy(d, count=25)

        manual = suggest()
        monkeypatch.setattr(parallel, "maximize_score_fn_sharded", _vmapped_pools)
        gp_ucb_pe._suggest_batch.clear_cache()  # it was traced with the map
        try:
            vmapped = suggest()
        finally:
            gp_ucb_pe._suggest_batch.clear_cache()
        assert manual.shape == (25, 2)
        np.testing.assert_array_equal(manual, vmapped)
