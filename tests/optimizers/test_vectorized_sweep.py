"""The vectorized optimizer's key schedule and its best-of-one merge.

A sweep draws every key it uses in one split before its loop (``keys =
split(rng, 1 + 2 * iterations)``: ``keys[0]`` seeds the pool, iteration ``i``
suggests with ``keys[1 + 2 i]`` and updates with ``keys[2 + 2 i]``;
``docs/guides/tpu_architecture.md``), and at ``count == 1`` its running best
is a max where ``count > 1`` keeps ``top_k``. The strategy never sees the
merge, so the first row of a ``count == 3`` sweep is the ``count == 1``
sweep's answer: that holds the max to ``top_k`` to the bit.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from vizier_tpu.models import kernels
from vizier_tpu.optimizers import eagle as eagle_lib
from vizier_tpu.optimizers import vectorized as vectorized_lib


def _eagle(dc=4, sizes=(), **cfg):
    return eagle_lib.VectorizedEagleStrategy(
        num_continuous=dc,
        category_sizes=sizes,
        config=eagle_lib.EagleStrategyConfig(**cfg),
    )


def _smooth(f: kernels.MixedFeatures):
    return -jnp.sum((f.continuous - 0.3) ** 2, axis=-1)


def _tied(f: kernels.MixedFeatures):
    """Eight levels: most of a batch ties with its neighbours."""
    return jnp.floor(f.continuous[:, 0] * 8.0) / 8.0


def _nothing_finite(f: kernels.MixedFeatures):
    """NaN and -inf by turns: the sweep reads both as -inf."""
    return jnp.where(f.continuous[:, 0] > 0.5, jnp.nan, -jnp.inf)


def _half_infinite(f: kernels.MixedFeatures):
    return jnp.where(f.continuous[:, 1] > 0.5, -jnp.inf, _tied(f))


SCORES = {
    "smooth": _smooth,
    "tied": _tied,
    "nothing_finite": _nothing_finite,
    "half_infinite": _half_infinite,
}


def _run(strategy, score_fn, seed, count, evaluations=2000):
    opt = vectorized_lib.VectorizedOptimizer(strategy, max_evaluations=evaluations)
    run = jax.jit(lambda rng: opt(score_fn, rng, count=count))
    return jax.tree_util.tree_map(np.asarray, run(jax.random.PRNGKey(seed)))


@pytest.mark.parametrize("name", sorted(SCORES))
@pytest.mark.parametrize("sizes", [(), (3, 4)], ids=["continuous", "mixed"])
def test_best_of_one_is_the_first_of_top_k_to_the_bit(name, sizes):
    strategy = _eagle(sizes=sizes)
    one = _run(strategy, SCORES[name], seed=7, count=1)
    three = _run(strategy, SCORES[name], seed=7, count=3)
    assert one.scores.shape == (1,) and three.scores.shape == (3,)
    np.testing.assert_array_equal(one.scores, three.scores[:1])
    np.testing.assert_array_equal(
        one.features.continuous, three.features.continuous[:1]
    )
    np.testing.assert_array_equal(
        one.features.categorical, three.features.categorical[:1]
    )
    if name == "nothing_finite":
        # Nothing ever beat the empty buffer: it is returned as it began.
        assert np.all(np.isneginf(three.scores))
        assert not one.features.continuous.any()


@pytest.mark.parametrize("count", [1, 3])
def test_merge_is_the_stable_top_k_of_everything_evaluated(count):
    """Random search proposes from its keys alone, so the documented key
    schedule reproduces every batch outside the loop: the sweep returns
    the stable top-k of all of them (earlier batches first among ties)."""
    strategy = vectorized_lib.RandomVectorizedStrategy(
        num_continuous=3, num_categorical=1, category_sizes=(5,),
        suggestion_batch_size=16,
    )
    evaluations = 320
    got = _run(strategy, _tied, seed=3, count=count, evaluations=evaluations)
    iterations = evaluations // 16
    keys = jax.random.split(jax.random.PRNGKey(3), 1 + 2 * iterations)
    batches = [strategy.suggest(None, keys[1 + 2 * i]) for i in range(iterations)]
    cont = np.concatenate([np.asarray(b.continuous) for b in batches])
    cat = np.concatenate([np.asarray(b.categorical) for b in batches])
    scores = np.floor(cont[:, 0] * np.float32(8.0)) / np.float32(8.0)
    order = np.argsort(-scores, kind="stable")[:count]
    np.testing.assert_array_equal(got.scores, scores[order])
    np.testing.assert_array_equal(got.features.continuous, cont[order])
    np.testing.assert_array_equal(got.features.categorical, cat[order])


def test_one_key_one_sweep_and_another_key_another():
    strategy = _eagle()
    first = _run(strategy, _smooth, seed=11, count=1)
    again = _run(strategy, _smooth, seed=11, count=1)
    other = _run(strategy, _smooth, seed=12, count=1)
    np.testing.assert_array_equal(first.features.continuous, again.features.continuous)
    np.testing.assert_array_equal(first.scores, again.scores)
    assert not np.array_equal(first.features.continuous, other.features.continuous)
    # Both sweeps still find the optimum's neighbourhood.
    assert first.scores[0] > -0.01 and other.scores[0] > -0.01


def test_first_iteration_perturbations_are_normal_draws():
    """A pool that has seen no reward feels no pull: its first proposals are
    its features plus ``perturbation`` times the iteration's normal draw.
    Over the suggest keys of the sweep's own schedule the draws have the
    moments of N(0, sigma^2), and no two iterations share them."""
    sigma = 0.02  # 25 sigma from the walls of [0, 1]: nothing is clipped
    strategy = _eagle(dc=4, perturbation=sigma)
    iterations = 200
    keys = jax.random.split(jax.random.PRNGKey(5), 1 + 2 * iterations)
    state = strategy.init_state(keys[0])
    state = state.replace(features=jnp.full_like(state.features, 0.5))
    suggest = jax.jit(strategy.suggest)
    draws = np.stack(
        [
            np.asarray(suggest(state, keys[1 + 2 * i]).continuous) - 0.5
            for i in range(iterations)
        ]
    )  # [iterations, pool, dc]
    n = draws.size  # 40,000
    assert abs(draws.mean()) < 4.0 * sigma / np.sqrt(n)
    # The variance of n normal draws spreads by sigma^2 * sqrt(2 / n).
    assert abs(draws.var() - sigma**2) < 4.0 * sigma**2 * np.sqrt(2.0 / n)
    # Excess kurtosis of a normal is 0 (a uniform's is -1.2).
    kurt = np.mean((draws / draws.std()) ** 4) - 3.0
    assert abs(kurt) < 0.15
    flat = draws.reshape(iterations, -1)
    assert len({row.tobytes() for row in flat}) == iterations


def test_reseeded_flies_are_uniform_draws_of_the_update_key():
    """Without categoricals the update key is the continuous draw's own."""
    strategy = _eagle(dc=3)
    key = jax.random.PRNGKey(9)
    cont, cat = strategy._random_features(key, 50)
    np.testing.assert_array_equal(
        np.asarray(cont), np.asarray(jax.random.uniform(key, (50, 3), jnp.float32))
    )
    assert cat.shape == (50, 0)


def test_a_categorical_space_still_draws_its_categories():
    sizes = (3, 4)
    strategy = _eagle(dc=2, sizes=sizes)
    key = jax.random.PRNGKey(2)
    cont, cat = strategy._random_features(key, 400)
    cat = np.asarray(cat)
    for column, size in enumerate(sizes):
        assert set(np.unique(cat[:, column])) == set(range(size))
    # The continuous and the categorical draw use different keys.
    c_rng, s_rng = jax.random.split(key)
    np.testing.assert_array_equal(
        np.asarray(cont), np.asarray(jax.random.uniform(c_rng, (400, 2), jnp.float32))
    )
    # A proposal mutates categories: at full perturbation every fly draws.
    state = strategy.init_state(key).replace(
        categorical=jnp.zeros((50, 2), jnp.int32),
        perturbations=jnp.ones((50,), jnp.float32),
    )
    proposed = np.asarray(strategy.suggest(state, jax.random.PRNGKey(4)).categorical)
    assert len(np.unique(proposed[:, 0])) == 3 and len(np.unique(proposed[:, 1])) == 4

    # And a sweep over the mixed space finds the category the score rewards.
    def score(f):
        return _smooth(f) + (f.categorical[:, 0] == 2) + (f.categorical[:, 1] == 1)

    best = _run(strategy, score, seed=1, count=1, evaluations=3000)
    assert best.features.categorical.tolist() == [[2, 1]]
