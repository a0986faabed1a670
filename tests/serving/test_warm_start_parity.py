"""Warm-started ARD must not change regret: rank-sum parity at 5 seeds.

The warm arm trains with 1 warm-seeded restart after the first suggest,
the cold arm always runs the full restart budget from random inits, on the
same shifted-sphere instances. Deterministic given the pinned seeds, so the
gate is stable. What a warm train takes on the chip is every benchmark
cell's ``train_wait_ms.*`` (all of them serve warm trains).
"""

import numpy as np

from vizier_tpu import pyvizier as vz
from vizier_tpu.algorithms import core as core_lib
from vizier_tpu.benchmarks.experimenters import experimenter_factory
from vizier_tpu.designers.gp_ucb_pe import VizierGPUCBPEBandit
from vizier_tpu.optimizers import lbfgs as lbfgs_lib

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


def _run_arm(seed: int, warm: bool) -> float:
    exp = experimenter_factory.shifted_bbob_instance("Sphere", seed, dim=DIM)
    designer = VizierGPUCBPEBandit(
        exp.problem_statement(),
        rng_seed=seed,
        num_seed_trials=4,
        max_acquisition_evaluations=500,
        ard_restarts=2,
        ard_optimizer=lbfgs_lib.LbfgsOptimizer(maxiter=8),
        use_warm_start_ard=warm,
        warm_ard_restarts=1 if warm else None,
        # The parity claim is about the warm MECHANISM; at this CI scale
        # (12 trials) the engage floor would leave the warm arm cold and
        # make the comparison vacuous.
        warm_start_min_trials=0,
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
    return best


def test_warm_vs_cold_regret_parity():
    warm_finals = [_run_arm(s, warm=True) for s in SEEDS]
    cold_finals = [_run_arm(s, warm=False) for s in SEEDS]
    p = _rank_sum_p(warm_finals, cold_finals)
    # Parity: the warm-started arm's final regrets must be statistically
    # indistinguishable from the cold arm's (deterministic given SEEDS).
    assert p > 0.05, (
        f"warm={warm_finals} cold={cold_finals} rank-sum p={p:.4f}"
    )
