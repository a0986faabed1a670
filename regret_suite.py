"""Regret suite: the BASELINE.md eval configs, one JSON report.

Runs the flagship designers on the driver-specified configurations (Branin,
mixed space, 20-D BBOB eagle, multi-objective ZDT) and writes
``regret_report.json`` with best-so-far numbers — the measurement instrument
for regret parity (the reference publishes no tables; BASELINE.md directs
measuring behaviorally).

Usage: ``python regret_suite.py [--scale 0.25] [--out regret_report.json]``
(scale shrinks budgets for CPU smoke runs).
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np


def _run(designer_factory, experimenter, num_trials, batch, seed=0):
    from vizier_tpu import benchmarks

    state = benchmarks.BenchmarkState.from_designer_factory(
        experimenter, designer_factory, seed=seed
    )
    benchmarks.BenchmarkRunner(
        [benchmarks.GenerateAndEvaluate(batch)], num_repeats=max(num_trials // batch, 1)
    ).run(state)
    return state


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--scale", type=float, default=1.0)
    parser.add_argument("--out", default="regret_report.json")
    parser.add_argument(
        "--platform",
        default=None,
        choices=["cpu", "tpu"],
        help="Pin the JAX platform (sets JAX_PLATFORMS before jax loads; "
        "use 'cpu' for smoke runs on a machine with an accelerator).",
    )
    args = parser.parse_args()
    s = args.scale
    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform

    from vizier_tpu import benchmarks
    from vizier_tpu import pyvizier as vz
    from vizier_tpu.benchmarks.experimenters.synthetic import bbob, multiobjective
    from vizier_tpu.benchmarks.analyzers import convergence_curve as cc
    from vizier_tpu.designers import RandomDesigner
    from vizier_tpu.designers.eagle_strategy import EagleStrategyDesigner
    from vizier_tpu.designers.evolution import NSGA2Designer
    from vizier_tpu.designers.gp_bandit import VizierGPBandit
    from vizier_tpu.designers.gp_ucb_pe import VizierGPUCBPEBandit
    from vizier_tpu.pyvizier import trial as trial_lib

    report = {}
    t_start = time.time()

    def gp(problem, seed=None, **kw):
        return VizierGPBandit(
            problem,
            rng_seed=seed or 0,
            max_acquisition_evaluations=max(int(10_000 * s), 1000),
            num_seed_trials=5,
        )

    def ucbpe(problem, seed=None, **kw):
        return VizierGPUCBPEBandit(
            problem,
            rng_seed=seed or 0,
            max_acquisition_evaluations=max(int(5_000 * s), 500),
            num_seed_trials=5,
        )

    # -- Config 1: GP-UCB on Branin (2-D classic) --------------------------
    def branin_best(factory, seed):
        exp = benchmarks.NumpyExperimenter(
            bbob.Branin, benchmarks.bbob_problem(2, metric_name="bbob_eval")
        )
        state = _run(factory, exp, num_trials=max(int(32 * s), 12), batch=2, seed=seed)
        trials = state.algorithm.supporter.GetTrials(
            status_matches=vz.TrialStatus.COMPLETED
        )
        return min(t.final_measurement.metrics["bbob_eval"].value for t in trials)

    report["branin_gp_ucb"] = {
        "best": [branin_best(gp, seed) for seed in (1, 2)],
        "optimum": 0.397887,
        "baseline_random": [branin_best(
            lambda p, **kw: RandomDesigner(p.search_space, seed=kw.get("seed", 0)), seed
        ) for seed in (1, 2)],
    }

    # -- Config 2: DEFAULT on the README mixed space -----------------------
    def mixed_best(factory, seed):
        problem = vz.ProblemStatement()
        root = problem.search_space.root
        root.add_float_param("lr", 1e-4, 1e-1, scale_type=vz.ScaleType.LOG)
        root.add_int_param("layers", 1, 8)
        root.add_categorical_param("opt", ["adam", "sgd", "rmsprop"])
        problem.metric_information.append(
            vz.MetricInformation(name="acc", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
        )

        class MixedExp(benchmarks.Experimenter):
            def evaluate(self, suggestions):
                for t in suggestions:
                    lr = t.parameters.get_value("lr")
                    layers = t.parameters.get_value("layers")
                    opt = t.parameters.get_value("opt")
                    acc = (
                        1.0
                        - (np.log10(lr) + 2.0) ** 2 * 0.2
                        - 0.03 * abs(layers - 4)
                        + (0.05 if opt == "adam" else 0.0)
                    )
                    t.complete(trial_lib.Measurement(metrics={"acc": acc}))

            def problem_statement(self):
                return problem

        state = _run(factory, MixedExp(), num_trials=max(int(30 * s), 12), batch=3, seed=seed)
        trials = state.algorithm.supporter.GetTrials(
            status_matches=vz.TrialStatus.COMPLETED
        )
        return max(t.final_measurement.metrics["acc"].value for t in trials)

    report["mixed_default_ucbpe"] = {
        "best": [mixed_best(ucbpe, 1)],
        "optimum": 1.05,
    }

    # -- Config 3: Eagle on 20-D BBOB (Rastrigin, Sphere) ------------------
    eagle_results = {}
    for fn_name in ("Sphere", "Rastrigin"):
        exp = benchmarks.NumpyExperimenter(
            bbob.BBOB_FUNCTIONS[fn_name], benchmarks.bbob_problem(20)
        )
        state = _run(
            lambda p, **kw: EagleStrategyDesigner(p, seed=kw.get("seed", 0)),
            exp,
            num_trials=max(int(200 * s), 50),
            batch=10,
        )
        trials = state.algorithm.supporter.GetTrials(
            status_matches=vz.TrialStatus.COMPLETED
        )
        eagle_results[fn_name] = min(
            t.final_measurement.metrics["bbob_eval"].value for t in trials
        )
    report["eagle_20d_bbob"] = eagle_results

    # -- Config 4: multi-objective on ZDT1 (NSGA2 + GP HV-scalarized) ------
    mo_results = {}
    for name, factory in (
        ("nsga2", lambda p, **kw: NSGA2Designer(p, population_size=20, seed=0)),
        ("gp_hv_ucb", gp),
    ):
        exp = multiobjective.MultiObjectiveExperimenter.zdt("zdt1", dimension=6)
        state = _run(factory, exp, num_trials=max(int(60 * s), 20), batch=5)
        trials = state.algorithm.supporter.GetTrials(
            status_matches=vz.TrialStatus.COMPLETED
        )
        curve = cc.HypervolumeCurveConverter(
            list(exp.problem_statement().metric_information),
            reference_point=np.array([-1.1, -6.0], dtype=np.float32),
        ).convert(trials)
        mo_results[name] = float(curve.ys[0, -1])
    report["zdt1_hypervolume"] = mo_results

    report["elapsed_secs"] = round(time.time() - t_start, 1)
    report["scale"] = s
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))


if __name__ == "__main__":
    main()
