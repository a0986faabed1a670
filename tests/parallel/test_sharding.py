"""Multi-device sharding tests on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vizier_tpu import parallel
from vizier_tpu import types
from vizier_tpu.designers.gp import acquisitions
from vizier_tpu.models import gp as gp_lib
from vizier_tpu.models import kernels
from vizier_tpu.optimizers import eagle as eagle_lib
from vizier_tpu.optimizers import lbfgs as lbfgs_lib
from vizier_tpu.optimizers import vectorized as vectorized_lib


def _data(n=8, n_pad=8, dc=2, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.uniform(size=(n, dc)).astype(np.float32)
    y = -np.sum((x - 0.5) ** 2, axis=1)
    features = types.ContinuousAndCategorical(
        continuous=types.PaddedArray.from_array(x, (n_pad, dc)),
        categorical=types.PaddedArray.from_array(
            np.zeros((n, 0), np.int32), (n_pad, 0), fill_value=0
        ),
    )
    labels = types.PaddedArray.from_array(
        y[:, None].astype(np.float32), (n_pad, 1), fill_value=np.nan
    )
    return gp_lib.GPData.from_model_data(types.ModelData(features, labels))


class TestMesh:
    def test_eight_virtual_devices(self):
        assert len(jax.devices()) == 8

    def test_create_mesh(self):
        mesh = parallel.create_mesh()
        assert mesh.axis_names == ("devices",)
        assert mesh.devices.size == 8
        half = parallel.create_mesh(4)
        assert half.devices.size == 4

    def test_too_many_devices_rejected(self):
        with pytest.raises(ValueError):
            parallel.create_mesh(1000)


class TestShardedTrain:
    def test_matches_unsharded_quality(self):
        model = gp_lib.VizierGaussianProcess(num_continuous=2, num_categorical=0)
        data = _data()
        mesh = parallel.create_mesh()
        opt = lbfgs_lib.AdamOptimizer(maxiter=30)
        states, _ = parallel.train_gp_sharded(
            model, opt, data, jax.random.PRNGKey(0), 8, 2, mesh
        )
        assert states.alpha.shape[0] == 2  # ensemble of 2
        # The trained GP must beat a random init's likelihood.
        coll = model.param_collection()
        rand = coll.random_init_unconstrained(jax.random.PRNGKey(3))
        rand_loss = float(model.neg_log_likelihood(rand, data))
        trained_unconstrained = coll.unconstrain(
            jax.tree_util.tree_map(lambda a: a[0], states.params)
        )
        trained_loss = float(model.neg_log_likelihood(trained_unconstrained, data))
        assert trained_loss <= rand_loss

    def test_restart_axis_sharded(self):
        model = gp_lib.VizierGaussianProcess(num_continuous=2, num_categorical=0)
        mesh = parallel.create_mesh()
        inits = model.param_collection().batch_random_init_unconstrained(
            jax.random.PRNGKey(0), 8
        )
        sharded = jax.device_put(inits, parallel.batch_sharded(mesh))
        shards = sharded["amplitude"].sharding.device_set
        assert len(shards) == 8


class TestShardedAcquisition:
    def test_pools_across_devices(self):
        mesh = parallel.create_mesh()
        model = gp_lib.VizierGaussianProcess(num_continuous=2, num_categorical=0)
        data = _data()
        params = model.param_collection().random_init_unconstrained(
            jax.random.PRNGKey(0)
        )
        state = model.precompute(params, data)
        states = jax.tree_util.tree_map(lambda a: a[None], state)
        scoring = acquisitions.ScoringFunction(
            predictive=gp_lib.EnsemblePredictive(states),
            acquisition=acquisitions.UCB(1.8),
            best_label=jnp.asarray(0.0),
            trust_region=None,
        )
        strategy = eagle_lib.VectorizedEagleStrategy(num_continuous=2, category_sizes=())
        vec_opt = vectorized_lib.VectorizedOptimizer(strategy, max_evaluations=500)
        result = parallel.maximize_acquisition_sharded(
            vec_opt, scoring, jax.random.PRNGKey(1), 3, 8, mesh
        )
        assert result.scores.shape == (3,)
        assert np.all(np.diff(np.asarray(result.scores)) <= 1e-9)

    def test_full_suggest_step(self):
        mesh = parallel.create_mesh()
        model = gp_lib.VizierGaussianProcess(num_continuous=2, num_categorical=0)
        strategy = eagle_lib.VectorizedEagleStrategy(num_continuous=2, category_sizes=())
        result = parallel.suggest_step_sharded(
            model,
            lbfgs_lib.AdamOptimizer(maxiter=20),
            vectorized_lib.VectorizedOptimizer(strategy, max_evaluations=300),
            _data(),
            jax.random.PRNGKey(0),
            count=2,
            num_restarts=8,
            ensemble_size=2,
            mesh=mesh,
        )
        cont = np.asarray(result.features.continuous)
        assert cont.shape == (2, 2)
        assert np.isfinite(np.asarray(result.scores)).all()


class TestMultihostInit:
    def test_single_host_returns_full_mesh(self):
        mesh = parallel.initialize_multihost()
        assert len(mesh.devices.flat) == len(jax.devices())
        # Sharded train accepts the returned mesh unchanged.
        model = gp_lib.VizierGaussianProcess(num_continuous=2, num_categorical=0)
        states, _ = parallel.train_gp_sharded(
            model, lbfgs_lib.AdamOptimizer(maxiter=5), _data(),
            jax.random.PRNGKey(0), num_restarts=8, ensemble_size=1, mesh=mesh,
        )
        assert np.isfinite(np.asarray(states.chol)).all()

    def test_one_host_never_touches_the_distributed_runtime(self, monkeypatch):
        def boom(*args, **kwargs):
            raise AssertionError("jax.distributed.initialize called on one host")

        monkeypatch.setattr(jax.distributed, "initialize", boom)
        mesh = parallel.initialize_multihost()
        assert len(mesh.devices.flat) == len(jax.devices())

    def test_a_failing_cluster_join_propagates(self, monkeypatch):
        def refuse(**kwargs):
            raise RuntimeError(f"no coordinator at {kwargs['coordinator_address']}")

        monkeypatch.setattr(jax.distributed, "initialize", refuse)
        with pytest.raises(RuntimeError, match="no coordinator at nowhere:1"):
            parallel.initialize_multihost(
                coordinator_address="nowhere:1", num_processes=2, process_id=0
            )
