"""A/B: warm-started vs cold-started ARD for steady-state serving.

Usage: python tools/warm_start_ab.py [--out WARM_START_AB.json]
       [--trials 1000] [--dim 20] [--evals 75000] [--repeats 5]
       [--parity-trials 48] [--parity-seeds 1 2 3 4 5]

Two measurements, one JSON report:

1. **Device-side steady-state suggest latency** at the north-star config
   (1000 trials x 20-D): per repeat, one fresh completed trial replaces a
   row (what a steady-state serving step sees), then the measured step is
   ARD train + one full acquisition sweep.
   - cold arm: ``ard_restarts`` full L-BFGS restarts from random inits —
     the reference's per-request behavior;
   - warm arm: ONE restart seeded with the previous repeat's trained
     unconstrained optimum (the serving runtime's steady state,
     ``ServingConfig.warm_ard_restarts=1``). The L-BFGS ftol early exit is
     what converts the good seed into wall-clock savings.

2. **Regret parity**: full BO loops on shifted 20-D Sphere instances,
   warm (1 warm restart) vs cold (full budget), >= 5 seeds, two-sided
   rank-sum on final regrets. Parity is green when p > 0.05.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

import numpy as np


def _progress(msg: str) -> None:
    print(f"[warm_start_ab] {msg}", file=sys.stderr, flush=True)


def measure_latency(args) -> dict:
    import jax

    from vizier_tpu import types
    from vizier_tpu.designers.gp import acquisitions
    from vizier_tpu.designers.gp_bandit import _maximize_acquisition, _train_gp
    from vizier_tpu.models import gp as gp_lib
    from vizier_tpu.models import kernels
    from vizier_tpu.models import output_warpers
    from vizier_tpu.optimizers import eagle as eagle_lib
    from vizier_tpu.optimizers import lbfgs as lbfgs_lib
    from vizier_tpu.optimizers import vectorized as vectorized_lib

    num_trials, dim = args.trials, args.dim
    n_pad = 1 << (num_trials - 1).bit_length()
    rng = np.random.default_rng(0)
    x = rng.uniform(size=(num_trials, dim)).astype(np.float32)
    y = -np.sum((x - 0.5) ** 2, axis=1) + 0.1 * rng.normal(size=num_trials)
    warper = output_warpers.create_default_warper()

    def make_data(step: int) -> gp_lib.GPData:
        """One fresh observation per steady-state step (row swap keeps the
        padded shapes — and therefore the jit cache — identical)."""
        xs, ys = x.copy(), y.copy()
        if step > 0:
            row = (step * 37) % num_trials
            r = np.random.default_rng(1000 + step)
            xs[row] = r.uniform(size=dim).astype(np.float32)
            ys[row] = -np.sum((xs[row] - 0.5) ** 2) + 0.1 * r.normal()
        warped = output_warpers.create_default_warper()(ys)
        features = types.ContinuousAndCategorical(
            continuous=types.PaddedArray.from_array(xs, (n_pad, dim)),
            categorical=types.PaddedArray.from_array(
                np.zeros((num_trials, 0), np.int32), (n_pad, 0), fill_value=0
            ),
        )
        labels = types.PaddedArray.from_array(
            warped[:, None].astype(np.float32), (n_pad, 1), fill_value=np.nan
        )
        return gp_lib.GPData.from_model_data(types.ModelData(features, labels))

    model = gp_lib.VizierGaussianProcess(num_continuous=dim, num_categorical=0)
    ard = lbfgs_lib.LbfgsOptimizer(maxiter=50)
    strategy = eagle_lib.VectorizedEagleStrategy(
        num_continuous=dim, category_sizes=()
    )
    vec_opt = vectorized_lib.VectorizedOptimizer(
        strategy, max_evaluations=args.evals
    )
    coll = model.param_collection()
    cold_restarts = lbfgs_lib.DEFAULT_RANDOM_RESTARTS

    def sweep(states, data, key):
        predictive = gp_lib.EnsemblePredictive(states)
        best_label = jax.numpy.max(
            jax.numpy.where(data.row_mask, data.labels, -jax.numpy.inf)
        )
        scoring = acquisitions.ScoringFunction(
            predictive=predictive,
            acquisition=acquisitions.UCB(1.8),
            best_label=best_label,
            trust_region=acquisitions.TrustRegion.from_data(data),
        )
        return _maximize_acquisition(
            vec_opt, scoring, key, args.batch,
            kernels.MixedFeatures(data.continuous[:10], data.categorical[:10]),
        )

    datas = [make_data(i) for i in range(args.repeats + 1)]

    def run_arm(warm: bool):
        times = []
        prev_params = None
        for step, data in enumerate(datas):
            key = jax.random.PRNGKey(step)
            k_train, k_acq = jax.random.split(key)
            t0 = time.perf_counter()
            if warm and prev_params is not None:
                states, _ = _train_gp(model, ard, data, k_train, 1, 1, prev_params)
            else:
                states, _ = _train_gp(model, ard, data, k_train, cold_restarts, 1)
            result = sweep(states, data, k_acq)
            jax.block_until_ready(result)
            elapsed = (time.perf_counter() - t0) * 1000.0
            if warm:
                prev_params = coll.unconstrain(
                    jax.tree_util.tree_map(lambda a: a[0], states.params)
                )
                jax.block_until_ready(prev_params)
                if step == 0:
                    # Pre-compile the 1-restart warm program so the first
                    # TIMED step measures compute, not XLA compilation.
                    jax.block_until_ready(
                        _train_gp(model, ard, data, k_train, 1, 1, prev_params)
                    )
            # step 0 is the compile/bootstrap run for BOTH arms (and the
            # warm arm's mandatory first cold train): excluded.
            if step > 0:
                times.append(elapsed)
                _progress(
                    f"{'warm' if warm else 'cold'} step {step}: {elapsed:.0f} ms"
                )
        return times

    _progress(f"latency: cold arm at {num_trials}x{dim}d, {args.evals} evals")
    cold_times = run_arm(warm=False)
    _progress("latency: warm arm")
    warm_times = run_arm(warm=True)
    cold_p50 = float(np.percentile(cold_times, 50))
    warm_p50 = float(np.percentile(warm_times, 50))
    return {
        "config": {
            "num_trials": num_trials,
            "dim": dim,
            "max_evaluations": args.evals,
            "batch": args.batch,
            "cold_restarts": cold_restarts,
            "warm_restarts": 1,
            "repeats": args.repeats,
        },
        "cold_suggest_p50_ms": round(cold_p50, 1),
        "warm_suggest_p50_ms": round(warm_p50, 1),
        "cold_suggest_ms": [round(t, 1) for t in cold_times],
        "warm_suggest_ms": [round(t, 1) for t in warm_times],
        "speedup": round(cold_p50 / warm_p50, 3),
    }


def rank_sum_p(a, b) -> float:
    """Two-sided Mann-Whitney p (normal approximation), H0: same dist."""
    from scipy import stats

    a, b = np.asarray(a, float), np.asarray(b, float)
    ranks = stats.rankdata(np.concatenate([a, b]))
    n, m = len(a), len(b)
    u = ranks[:n].sum() - n * (n + 1) / 2.0
    mu, sigma = n * m / 2.0, np.sqrt(n * m * (n + m + 1) / 12.0)
    return float(2.0 * (1.0 - stats.norm.cdf(abs(u - mu) / max(sigma, 1e-9))))


def measure_parity(args) -> dict:
    from vizier_tpu.algorithms import core as core_lib
    from vizier_tpu.benchmarks.experimenters import experimenter_factory
    from vizier_tpu.designers.gp_ucb_pe import VizierGPUCBPEBandit

    def run_arm(seed: int, warm: bool) -> float:
        exp = experimenter_factory.shifted_bbob_instance(
            "Sphere", seed, dim=args.dim
        )
        designer = VizierGPUCBPEBandit(
            exp.problem_statement(),
            rng_seed=seed,
            num_seed_trials=5,
            max_acquisition_evaluations=args.parity_evals,
            use_warm_start_ard=warm,
            warm_ard_restarts=1 if warm else None,
        )
        best, tid = np.inf, 0
        while tid < args.parity_trials:
            batch = [
                s.to_trial(tid + i + 1)
                for i, s in enumerate(designer.suggest(args.parity_batch))
            ]
            tid += len(batch)
            exp.evaluate(batch)
            designer.update(core_lib.CompletedTrials(batch))
            for t in batch:
                best = min(best, t.final_measurement.metrics["bbob_eval"].value)
        return best

    warm_finals, cold_finals = [], []
    for seed in args.parity_seeds:
        t0 = time.perf_counter()
        warm_finals.append(run_arm(seed, warm=True))
        cold_finals.append(run_arm(seed, warm=False))
        _progress(
            f"parity seed {seed}: warm={warm_finals[-1]:.4f} "
            f"cold={cold_finals[-1]:.4f} ({time.perf_counter() - t0:.0f}s)"
        )
    p = rank_sum_p(warm_finals, cold_finals)
    return {
        "config": {
            "fn": "Sphere(shifted)",
            "dim": args.dim,
            "trials": args.parity_trials,
            "batch": args.parity_batch,
            "max_evaluations": args.parity_evals,
            "seeds": list(args.parity_seeds),
        },
        "warm_final_regrets": [round(v, 4) for v in warm_finals],
        "cold_final_regrets": [round(v, 4) for v in cold_finals],
        "rank_sum_p": round(p, 4),
        "parity_green": p > 0.05,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="WARM_START_AB.json")
    ap.add_argument("--trials", type=int, default=1000)
    ap.add_argument("--dim", type=int, default=20)
    ap.add_argument("--evals", type=int, default=75_000)
    ap.add_argument("--batch", type=int, default=25)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--parity-trials", type=int, default=45)
    ap.add_argument("--parity-batch", type=int, default=5)
    ap.add_argument("--parity-evals", type=int, default=2_000)
    ap.add_argument("--parity-seeds", type=int, nargs="+", default=[1, 2, 3, 4, 5])
    ap.add_argument("--skip-latency", action="store_true")
    ap.add_argument("--skip-parity", action="store_true")
    args = ap.parse_args()

    import jax

    report = {
        "backend": jax.default_backend(),
        "note": (
            "Warm-started steady-state ARD (serving designer cache, "
            "warm_ard_restarts=1) vs the reference's cold per-request "
            "train. Latency is the device-side suggest step (ARD train + "
            "acquisition sweep) at the north-star scale; parity is "
            "two-sided rank-sum on final regrets over full BO loops."
        ),
    }
    if not args.skip_latency:
        report["latency"] = measure_latency(args)
    if not args.skip_parity:
        report["parity"] = measure_parity(args)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))


if __name__ == "__main__":
    main()
