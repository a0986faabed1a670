"""Sparse surrogate must not change regret: rank-sum parity at 5 seeds.

For each GP designer (the plain bandit and the service DEFAULT, UCB-PE) the
sparse arm runs the SGPR collapsed-bound posterior from the first post-seed
suggest (threshold 1), the exact arm the seed O(n³) path, on the same
shifted-sphere instances. Deterministic given the pinned seeds, so the gate
is stable. What the sparse tier costs or saves is the chip's to say: the
``default20d-sparse`` cells of ``BENCHMARK.json``.
"""

import numpy as np
import pytest

from vizier_tpu import pyvizier as vz
from vizier_tpu.algorithms import core as core_lib
from vizier_tpu.benchmarks.experimenters import experimenter_factory
from vizier_tpu.designers.gp_bandit import VizierGPBandit
from vizier_tpu.designers.gp_ucb_pe import VizierGPUCBPEBandit
from vizier_tpu.optimizers import lbfgs as lbfgs_lib
from vizier_tpu.surrogates import SurrogateConfig

SEEDS = (1, 2, 3, 4, 5)
DIM = 4
TRIALS = 12
BATCH = 4


def _rank_sum_p(a, b) -> float:
    """Two-sided Mann-Whitney p (normal approximation), H0: same dist."""
    from scipy import stats

    a, b = np.asarray(a, float), np.asarray(b, float)
    ranks = stats.rankdata(np.concatenate([a, b]))
    n, m = len(a), len(b)
    u = ranks[:n].sum() - n * (n + 1) / 2.0
    mu, sigma = n * m / 2.0, np.sqrt(n * m * (n + m + 1) / 12.0)
    return float(2.0 * (1.0 - stats.norm.cdf(abs(u - mu) / max(sigma, 1e-9))))


def _run_arm(designer_cls, seed: int, sparse: bool) -> float:
    exp = experimenter_factory.shifted_bbob_instance("Sphere", seed, dim=DIM)
    surrogate = (
        SurrogateConfig(
            sparse_threshold_trials=1, hysteresis_trials=0, num_inducing=8
        )
        if sparse
        else None
    )
    designer = designer_cls(
        exp.problem_statement(),
        rng_seed=seed,
        num_seed_trials=4,
        max_acquisition_evaluations=500,
        ard_restarts=2,
        ard_optimizer=lbfgs_lib.LbfgsOptimizer(maxiter=8),
        warm_start_min_trials=0,
        surrogate=surrogate,
    )
    best, tid = np.inf, 0
    while tid < TRIALS:
        batch = [
            s.to_trial(tid + i + 1) for i, s in enumerate(designer.suggest(BATCH))
        ]
        tid += len(batch)
        exp.evaluate(batch)
        designer.update(core_lib.CompletedTrials(batch))
        for t in batch:
            best = min(best, t.final_measurement.metrics["bbob_eval"].value)
    if sparse:
        assert designer.surrogate_counts["sparse_suggests"] > 0
    return best


@pytest.mark.parametrize("designer_cls", [VizierGPBandit, VizierGPUCBPEBandit])
def test_sparse_vs_exact_regret_parity(designer_cls):
    sparse_finals = [_run_arm(designer_cls, s, sparse=True) for s in SEEDS]
    exact_finals = [_run_arm(designer_cls, s, sparse=False) for s in SEEDS]
    p = _rank_sum_p(sparse_finals, exact_finals)
    # Parity: the sparse arm's final regrets must be statistically
    # indistinguishable from the exact arm's (deterministic given SEEDS).
    assert p > 0.05, (
        f"sparse={sparse_finals} exact={exact_finals} rank-sum p={p:.4f}"
    )
