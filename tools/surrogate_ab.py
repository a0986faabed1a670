"""A/B: sparse inducing-point surrogate vs the exact O(n³) GP.

Usage: python tools/surrogate_ab.py [--out SPARSE_AB.json]
       [--designer gp_bandit|ucb_pe]
       [--trials 1000] [--dim 20] [--evals 75000] [--inducing 128]
       [--exact-repeats 2] [--sparse-repeats 5]
       [--parity-trials 45] [--parity-seeds 1 2 3 4 5]

``--designer ucb_pe`` runs the same three measurements for the service
DEFAULT (GP-UCB-PE): the sparse arm conditions the greedy batch on
pending picks through the inducing-point posterior (Nyström-augmented;
``gp_ucb_pe_sparse`` compute-IR program) instead of the exact per-pick
O(n³) re-factorization; the latency arms drive the full designer suggest
(train + greedy batch) at the north-star scale, and the output defaults
to ``SPARSE_UCB_PE_AB.json``.

Three measurements, one JSON report:

1. **Device-side suggest latency** at the north-star scale (1000 trials x
   20-D, 75k acquisition evals, batch 25): per repeat, ARD train + one
   full acquisition sweep, device-synchronized.
   - exact arm: the seed path — multi-restart L-BFGS over the exact GP's
     O(n³) marginal likelihood;
   - sparse arm: the SAME restart budget over the SGPR collapsed bound
     with m inducing points (k-center-selected inside the program) —
     O(n·m²) train, O(m²) posterior queries in the sweep.
   Compile (step 0) is excluded from both arms.

2. **Regret parity**: full BO loops on shifted Sphere instances, the
   sparse auto-switch from the first post-seed suggest vs the exact path,
   >= 5 seeds, two-sided rank-sum on final regrets. Green when p > 0.05.

3. **Off-switch bit-identity**: with ``VIZIER_SPARSE=0`` the config built
   from the environment must reproduce the no-config exact path's
   suggestions exactly (float-equal), proving the switch is a pure
   bypass.
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
    print(f"[surrogate_ab] {msg}", file=sys.stderr, flush=True)


def measure_latency(args) -> dict:
    import jax

    from vizier_tpu import types
    from vizier_tpu.converters import padding
    from vizier_tpu.designers.gp import acquisitions
    from vizier_tpu.designers.gp_bandit import _maximize_acquisition, _train_gp
    from vizier_tpu.models import gp as gp_lib
    from vizier_tpu.models import kernels
    from vizier_tpu.models import output_warpers
    from vizier_tpu.optimizers import eagle as eagle_lib
    from vizier_tpu.optimizers import lbfgs as lbfgs_lib
    from vizier_tpu.optimizers import vectorized as vectorized_lib
    from vizier_tpu.surrogates import sparse_bandit
    from vizier_tpu.surrogates import sparse_gp

    num_trials, dim = args.trials, args.dim
    n_pad = 1 << (num_trials - 1).bit_length()
    m_pad = padding.PaddingSchedule().pad_trials(args.inducing)
    rng = np.random.default_rng(0)
    x = rng.uniform(size=(num_trials, dim)).astype(np.float32)
    y = -np.sum((x - 0.5) ** 2, axis=1) + 0.1 * rng.normal(size=num_trials)

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

    base = gp_lib.VizierGaussianProcess(num_continuous=dim, num_categorical=0)
    sparse_model = sparse_gp.SparseGaussianProcess(base=base, num_inducing=m_pad)
    ard = lbfgs_lib.LbfgsOptimizer(maxiter=50)
    strategy = eagle_lib.VectorizedEagleStrategy(
        num_continuous=dim, category_sizes=()
    )
    vec_opt = vectorized_lib.VectorizedOptimizer(
        strategy, max_evaluations=args.evals
    )
    restarts = lbfgs_lib.DEFAULT_RANDOM_RESTARTS

    def scoring_for(predictive, data):
        best_label = jax.numpy.max(
            jax.numpy.where(data.row_mask, data.labels, -jax.numpy.inf)
        )
        return acquisitions.ScoringFunction(
            predictive=predictive,
            acquisition=acquisitions.UCB(1.8),
            best_label=best_label,
            trust_region=acquisitions.TrustRegion.from_data(data),
        )

    def prior(data):
        return kernels.MixedFeatures(data.continuous[:10], data.categorical[:10])

    def run_arm(sparse: bool, repeats: int):
        times = []
        for step in range(repeats + 1):
            data = make_data(step)
            key = jax.random.PRNGKey(step)
            k_train, k_acq = jax.random.split(key)
            t0 = time.perf_counter()
            if sparse:
                states, _ = sparse_bandit._train_sparse_gp(
                    sparse_model, ard, data, k_train, restarts, 1, None
                )
                scoring = scoring_for(
                    sparse_gp.SparseEnsemblePredictive(states), data
                )
                result = sparse_bandit._maximize_sparse_acquisition(
                    vec_opt, scoring, k_acq, args.batch, prior(data)
                )
            else:
                states, _ = _train_gp(model=base, optimizer=ard, data=data,
                                   rng=k_train, num_restarts=restarts,
                                   ensemble_size=1)
                scoring = scoring_for(gp_lib.EnsemblePredictive(states), data)
                result = _maximize_acquisition(
                    vec_opt, scoring, k_acq, args.batch, prior(data)
                )
            jax.block_until_ready(result)
            elapsed = (time.perf_counter() - t0) * 1000.0
            # step 0 is the compile run for both arms: excluded.
            if step > 0:
                times.append(elapsed)
            _progress(
                f"{'sparse' if sparse else 'exact'} step {step}: "
                f"{elapsed:.0f} ms{' (compile, excluded)' if step == 0 else ''}"
            )
        return times

    _progress(
        f"latency: sparse arm at {num_trials}x{dim}d, m={args.inducing} "
        f"(padded {m_pad}), {args.evals} evals"
    )
    sparse_times = run_arm(sparse=True, repeats=args.sparse_repeats)
    _progress(f"latency: exact arm ({args.exact_repeats} repeats of ~72 s)")
    exact_times = run_arm(sparse=False, repeats=args.exact_repeats)
    sparse_p50 = float(np.percentile(sparse_times, 50))
    exact_p50 = float(np.percentile(exact_times, 50))
    return {
        "config": {
            "num_trials": num_trials,
            "dim": dim,
            "max_evaluations": args.evals,
            "batch": args.batch,
            "restarts": restarts,
            "num_inducing": args.inducing,
            "num_inducing_padded": m_pad,
            "exact_repeats": args.exact_repeats,
            "sparse_repeats": args.sparse_repeats,
        },
        "exact_suggest_p50_ms": round(exact_p50, 1),
        "sparse_suggest_p50_ms": round(sparse_p50, 1),
        "exact_suggest_ms": [round(t, 1) for t in exact_times],
        "sparse_suggest_ms": [round(t, 1) for t in sparse_times],
        "speedup": round(exact_p50 / sparse_p50, 2),
    }


def _ucb_pe_designer(problem, seed, args, sparse: bool):
    from vizier_tpu.designers.gp_ucb_pe import VizierGPUCBPEBandit
    from vizier_tpu.surrogates import SurrogateConfig

    surrogate = None
    if sparse:
        surrogate = SurrogateConfig(
            sparse_threshold_trials=1,
            hysteresis_trials=0,
            num_inducing=args.inducing,
        )
    return VizierGPUCBPEBandit(
        problem,
        rng_seed=seed,
        max_acquisition_evaluations=args.evals,
        surrogate=surrogate,
    )


def measure_latency_ucb_pe(args) -> dict:
    """End-to-end UCB-PE suggest latency (train + greedy batch) at the
    north-star scale: the full designer path, so the exact arm pays the
    O(n³) ARD *and* the per-pick O(n³) pending re-conditioning, the
    sparse arm their O(n·m²) inducing-point twins — same study data, same
    backend, same process."""
    import jax

    from vizier_tpu import pyvizier as vz
    from vizier_tpu.algorithms import core as core_lib

    num_trials, dim = args.trials, args.dim
    problem = vz.ProblemStatement()
    for d in range(dim):
        problem.search_space.root.add_float_param(f"x{d}", 0.0, 1.0)
    problem.metric_information.append(
        vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )

    def make_trials(start_id, n, seed):
        rng = np.random.default_rng(seed)
        out = []
        for i in range(n):
            params = {
                f"x{d}": float(rng.uniform()) for d in range(dim)
            }
            t = vz.Trial(parameters=params, id=start_id + i)
            t.complete(
                vz.Measurement(
                    metrics={
                        "obj": float(
                            -sum((v - 0.5) ** 2 for v in params.values())
                            + 0.1 * rng.normal()
                        )
                    }
                )
            )
            out.append(t)
        return out

    base_trials = make_trials(1, num_trials, seed=0)

    def run_arm(sparse: bool, repeats: int):
        designer = _ucb_pe_designer(problem, 0, args, sparse)
        designer.update(core_lib.CompletedTrials(base_trials))
        times = []
        for step in range(repeats + 1):
            if step > 0:
                # One fresh completion per steady-state step forces a
                # retrain without leaving the 1024-row padding bucket.
                designer.update(
                    core_lib.CompletedTrials(
                        make_trials(num_trials + step, 1, seed=1000 + step)
                    )
                )
            t0 = time.perf_counter()
            out = designer.suggest(args.ucb_batch)
            assert len(out) == args.ucb_batch
            elapsed = (time.perf_counter() - t0) * 1000.0
            if step > 0:
                times.append(elapsed)
            _progress(
                f"ucb_pe {'sparse' if sparse else 'exact'} step {step}: "
                f"{elapsed:.0f} ms"
                f"{' (compile, excluded)' if step == 0 else ''}"
            )
        if sparse:
            assert designer.surrogate_counts["sparse_suggests"] > 0
            assert designer.surrogate_mode == "sparse"
        return times

    _progress(
        f"ucb_pe latency: sparse arm at {num_trials}x{dim}d, "
        f"m={args.inducing}, batch {args.ucb_batch}, {args.evals} evals"
    )
    sparse_times = run_arm(sparse=True, repeats=args.sparse_repeats)
    _progress(f"ucb_pe latency: exact arm ({args.exact_repeats} repeats)")
    exact_times = run_arm(sparse=False, repeats=args.exact_repeats)
    sparse_p50 = float(np.percentile(sparse_times, 50))
    exact_p50 = float(np.percentile(exact_times, 50))
    return {
        "config": {
            "designer": "gp_ucb_pe",
            "num_trials": num_trials,
            "dim": dim,
            "max_evaluations": args.evals,
            "batch": args.ucb_batch,
            "num_inducing": args.inducing,
            "exact_repeats": args.exact_repeats,
            "sparse_repeats": args.sparse_repeats,
        },
        "exact_suggest_p50_ms": round(exact_p50, 1),
        "sparse_suggest_p50_ms": round(sparse_p50, 1),
        "exact_suggest_ms": [round(t, 1) for t in exact_times],
        "sparse_suggest_ms": [round(t, 1) for t in sparse_times],
        "speedup": round(exact_p50 / sparse_p50, 2),
    }


def measure_parity_ucb_pe(args) -> dict:
    """Sparse-vs-exact UCB-PE regret parity: full BO loops on shifted
    Sphere instances, rank-sum on final regrets at >= 5 seeds."""
    from vizier_tpu.algorithms import core as core_lib
    from vizier_tpu.benchmarks.experimenters import experimenter_factory
    from vizier_tpu.designers.gp_ucb_pe import VizierGPUCBPEBandit
    from vizier_tpu.surrogates import SurrogateConfig

    def run_arm(seed: int, sparse: bool) -> float:
        exp = experimenter_factory.shifted_bbob_instance(
            "Sphere", seed, dim=args.parity_dim
        )
        surrogate = (
            SurrogateConfig(
                sparse_threshold_trials=1,
                hysteresis_trials=0,
                num_inducing=args.parity_inducing,
            )
            if sparse
            else None
        )
        designer = VizierGPUCBPEBandit(
            exp.problem_statement(),
            rng_seed=seed,
            max_acquisition_evaluations=args.parity_evals,
            surrogate=surrogate,
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
        if sparse:
            assert designer.surrogate_counts["sparse_suggests"] > 0
        return best

    sparse_finals, exact_finals = [], []
    for seed in args.parity_seeds:
        t0 = time.perf_counter()
        sparse_finals.append(run_arm(seed, sparse=True))
        exact_finals.append(run_arm(seed, sparse=False))
        _progress(
            f"ucb_pe parity seed {seed}: sparse={sparse_finals[-1]:.4f} "
            f"exact={exact_finals[-1]:.4f} ({time.perf_counter() - t0:.0f}s)"
        )
    p = rank_sum_p(sparse_finals, exact_finals)
    return {
        "config": {
            "designer": "gp_ucb_pe",
            "fn": "Sphere(shifted)",
            "dim": args.parity_dim,
            "trials": args.parity_trials,
            "batch": args.parity_batch,
            "max_evaluations": args.parity_evals,
            "num_inducing": args.parity_inducing,
            "sparse_threshold_trials": 1,
            "seeds": list(args.parity_seeds),
        },
        "sparse_final_regrets": [round(v, 4) for v in sparse_finals],
        "exact_final_regrets": [round(v, 4) for v in exact_finals],
        "rank_sum_p": round(p, 4),
        "parity_green": p > 0.05,
    }


def check_off_bit_identity_ucb_pe() -> dict:
    """VIZIER_SPARSE_UCB_PE=0 must reproduce the no-config UCB-PE path
    bit-for-bit (even with the study above the sparse threshold)."""
    from vizier_tpu import pyvizier as vz
    from vizier_tpu.algorithms import core as core_lib
    from vizier_tpu.designers.gp_ucb_pe import VizierGPUCBPEBandit
    from vizier_tpu.surrogates import SurrogateConfig

    problem = vz.ProblemStatement()
    for d in range(4):
        problem.search_space.root.add_float_param(f"x{d}", 0.0, 1.0)
    problem.metric_information.append(
        vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    rng = np.random.default_rng(7)
    trials = []
    for i in range(16):
        params = {f"x{d}": float(rng.uniform()) for d in range(4)}
        t = vz.Trial(parameters=params, id=i + 1)
        t.complete(
            vz.Measurement(metrics={"obj": float(sum(params.values()))})
        )
        trials.append(t)

    prev = os.environ.get("VIZIER_SPARSE_UCB_PE")
    os.environ["VIZIER_SPARSE_UCB_PE"] = "0"
    try:
        off_cfg = SurrogateConfig.from_env()
    finally:
        if prev is None:
            os.environ.pop("VIZIER_SPARSE_UCB_PE", None)
        else:
            os.environ["VIZIER_SPARSE_UCB_PE"] = prev
    assert not off_cfg.sparse_ucb_pe
    # Force the threshold below the study so only the ucb_pe gate stands
    # between this designer and the sparse path.
    off_cfg = SurrogateConfig(
        sparse=off_cfg.sparse,
        sparse_threshold_trials=1,
        hysteresis_trials=0,
        num_inducing=8,
        sparse_ucb_pe=off_cfg.sparse_ucb_pe,
    )

    def run(surrogate):
        d = VizierGPUCBPEBandit(
            problem, rng_seed=11,
            max_acquisition_evaluations=500, surrogate=surrogate,
        )
        d.update(core_lib.CompletedTrials(trials))
        out = []
        for _ in range(2):
            out.append([s.parameters.as_dict() for s in d.suggest(2)])
        return out

    identical = run(None) == run(off_cfg)
    _progress(f"ucb_pe off-switch bit-identity: {identical}")
    return {"off_bit_identical": identical}


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
    from vizier_tpu.designers.gp_bandit import VizierGPBandit
    from vizier_tpu.surrogates import SurrogateConfig

    def run_arm(seed: int, sparse: bool) -> float:
        exp = experimenter_factory.shifted_bbob_instance(
            "Sphere", seed, dim=args.parity_dim
        )
        surrogate = (
            SurrogateConfig(
                sparse_threshold_trials=1,
                hysteresis_trials=0,
                num_inducing=args.parity_inducing,
            )
            if sparse
            else None
        )
        designer = VizierGPBandit(
            exp.problem_statement(),
            rng_seed=seed,
            num_seed_trials=5,
            max_acquisition_evaluations=args.parity_evals,
            surrogate=surrogate,
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
        if sparse:
            assert designer.surrogate_counts["sparse_suggests"] > 0
        return best

    sparse_finals, exact_finals = [], []
    for seed in args.parity_seeds:
        t0 = time.perf_counter()
        sparse_finals.append(run_arm(seed, sparse=True))
        exact_finals.append(run_arm(seed, sparse=False))
        _progress(
            f"parity seed {seed}: sparse={sparse_finals[-1]:.4f} "
            f"exact={exact_finals[-1]:.4f} ({time.perf_counter() - t0:.0f}s)"
        )
    p = rank_sum_p(sparse_finals, exact_finals)
    return {
        "config": {
            "fn": "Sphere(shifted)",
            "dim": args.parity_dim,
            "trials": args.parity_trials,
            "batch": args.parity_batch,
            "max_evaluations": args.parity_evals,
            "num_inducing": args.parity_inducing,
            "sparse_threshold_trials": 1,
            "seeds": list(args.parity_seeds),
        },
        "sparse_final_regrets": [round(v, 4) for v in sparse_finals],
        "exact_final_regrets": [round(v, 4) for v in exact_finals],
        "rank_sum_p": round(p, 4),
        "parity_green": p > 0.05,
    }


def check_off_bit_identity() -> dict:
    """VIZIER_SPARSE=0 must reproduce the no-config path bit-for-bit."""
    from vizier_tpu import pyvizier as vz
    from vizier_tpu.algorithms import core as core_lib
    from vizier_tpu.designers.gp_bandit import VizierGPBandit
    from vizier_tpu.surrogates import SurrogateConfig

    problem = vz.ProblemStatement()
    for d in range(4):
        problem.search_space.root.add_float_param(f"x{d}", 0.0, 1.0)
    problem.metric_information.append(
        vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    rng = np.random.default_rng(7)
    trials = []
    for i in range(16):
        params = {f"x{d}": float(rng.uniform()) for d in range(4)}
        t = vz.Trial(parameters=params, id=i + 1)
        t.complete(
            vz.Measurement(metrics={"obj": float(sum(params.values()))})
        )
        trials.append(t)

    prev = os.environ.get("VIZIER_SPARSE")
    os.environ["VIZIER_SPARSE"] = "0"
    try:
        off_cfg = SurrogateConfig.from_env()
    finally:
        if prev is None:
            os.environ.pop("VIZIER_SPARSE", None)
        else:
            os.environ["VIZIER_SPARSE"] = prev
    assert not off_cfg.sparse

    def run(surrogate):
        d = VizierGPBandit(
            problem, rng_seed=11, num_seed_trials=1,
            max_acquisition_evaluations=500, surrogate=surrogate,
        )
        d.update(core_lib.CompletedTrials(trials))
        out = []
        for _ in range(2):
            out.append([s.parameters.as_dict() for s in d.suggest(2)])
        return out

    identical = run(None) == run(off_cfg)
    _progress(f"off-switch bit-identity: {identical}")
    return {"off_bit_identical": identical}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument(
        "--designer", choices=("gp_bandit", "ucb_pe"), default="gp_bandit"
    )
    ap.add_argument("--ucb-batch", type=int, default=5)
    ap.add_argument("--trials", type=int, default=1000)
    ap.add_argument("--dim", type=int, default=20)
    ap.add_argument("--evals", type=int, default=75_000)
    ap.add_argument("--batch", type=int, default=25)
    ap.add_argument("--inducing", type=int, default=128)
    ap.add_argument("--exact-repeats", type=int, default=2)
    ap.add_argument("--sparse-repeats", type=int, default=5)
    ap.add_argument("--parity-trials", type=int, default=45)
    ap.add_argument("--parity-batch", type=int, default=5)
    ap.add_argument("--parity-dim", type=int, default=20)
    ap.add_argument("--parity-evals", type=int, default=2_000)
    ap.add_argument("--parity-inducing", type=int, default=16)
    ap.add_argument("--parity-seeds", type=int, nargs="+", default=[1, 2, 3, 4, 5])
    ap.add_argument("--skip-latency", action="store_true")
    ap.add_argument("--skip-parity", action="store_true")
    args = ap.parse_args()
    if args.out is None:
        args.out = (
            "SPARSE_UCB_PE_AB.json"
            if args.designer == "ucb_pe"
            else "SPARSE_AB.json"
        )

    import jax

    from vizier_tpu.surrogates import SurrogateConfig

    ucb_pe = args.designer == "ucb_pe"
    report = {
        "backend": jax.default_backend(),
        "designer": args.designer,
        # Which path produced what: both arms are stamped explicitly, and
        # the process-wide env default rides along for provenance.
        "surrogates_env_config": SurrogateConfig.from_env().as_dict(),
        "note": (
            (
                "Sparse UCB-PE (SGPR collapsed-bound train + pending-pick "
                "conditioning through the Nyström-augmented inducing "
                "posterior; compute-IR kind gp_ucb_pe_sparse) vs the exact "
                "UCB-PE path (O(n³) ARD + O(n³) per-pick re-conditioning). "
                "Latency is the full designer suggest (train + greedy "
                "batch) at the north-star scale, same run/backend; parity "
                "is two-sided rank-sum on final regrets over full BO "
                "loops; VIZIER_SPARSE_UCB_PE=0 is checked bit-identical "
                "to the exact path."
            )
            if ucb_pe
            else (
                "Sparse SGPR collapsed-bound surrogate (k-center inducing "
                "selection, same multi-restart L-BFGS ARD program) vs the "
                "exact O(n³) GP. Latency is the device-side suggest step "
                "(train + acquisition sweep) at the north-star scale; "
                "parity is two-sided rank-sum on final regrets over full "
                "BO loops; VIZIER_SPARSE=0 is checked bit-identical to "
                "the seed path."
            )
        ),
    }
    if not args.skip_latency:
        report["latency"] = (
            measure_latency_ucb_pe(args) if ucb_pe else measure_latency(args)
        )
    if not args.skip_parity:
        report["parity"] = (
            measure_parity_ucb_pe(args) if ucb_pe else measure_parity(args)
        )
    report["off_switch"] = (
        check_off_bit_identity_ucb_pe() if ucb_pe else check_off_bit_identity()
    )
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))


if __name__ == "__main__":
    main()
