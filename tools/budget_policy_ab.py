"""A/B of the acquisition budget policies on shifted 20-D BBOB.

Usage: python tools/budget_policy_ab.py [--trials 150] [--seeds 1 2 3 4 5]

Compares first_pick_full (the shipped default: full budget on the
exploitation pick, one further budget split across the exploration picks)
against per_pick (reference semantics, a full budget on EVERY pick) and
per_batch (one split budget) on the same pinned shifted instances as
parity_suite.py / the CI gate (experimenter_factory.shifted_bbob_instance).
Prints one JSON line per (function, policy, seed) plus a summary. Measured
round 4: first_pick_full matches-or-beats per_pick regret at ~1/12th the
acquisition compute; per_batch degrades 20-D exploitation measurably.
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


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=150)
    ap.add_argument("--batch", type=int, default=10)
    ap.add_argument("--evals", type=int, default=25_000)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2, 3, 4, 5])
    args = ap.parse_args()

    from vizier_tpu.algorithms import core as core_lib
    from vizier_tpu.benchmarks.experimenters import experimenter_factory
    from vizier_tpu.designers.gp_ucb_pe import VizierGPUCBPEBandit

    results: dict = {}
    # Two 20-D BBOB families plus a low-D classic (Branin in the BBOB
    # frame, bbob.EXTRA_FUNCTIONS) so the DEFAULT-policy evidence does not
    # rest on one dimensionality regime (r4 verdict weak #2).
    configs = (("Sphere", 20), ("Rastrigin", 20), ("Branin", 2))
    # Optimum VALUE of each objective (shift moves the argmin, not the
    # minimum): Sphere/Rastrigin are 0 at the optimum; Branin ≈ 0.397887
    # (synthetic/bbob.py:381). Subtracted so "final_regret" is a true
    # regret, comparable across functions.
    optima = {"Sphere": 0.0, "Rastrigin": 0.0, "Branin": 0.3978873577}
    for fn_name, dim in configs:
        for policy in ("first_pick_full", "per_batch", "per_pick"):
            finals = []
            for seed in args.seeds:
                exp = experimenter_factory.shifted_bbob_instance(
                    fn_name, seed, dim=dim
                )
                problem = exp.problem_statement()
                designer = VizierGPUCBPEBandit(
                    problem,
                    rng_seed=seed,
                    max_acquisition_evaluations=args.evals,
                    num_seed_trials=5,
                    acquisition_budget_policy=policy,
                )
                best, tid = np.inf, 0
                t0 = time.perf_counter()
                while tid < args.trials:
                    batch = [
                        s.to_trial(tid + i + 1)
                        for i, s in enumerate(designer.suggest(args.batch))
                    ]
                    tid += len(batch)
                    exp.evaluate(batch)
                    designer.update(core_lib.CompletedTrials(batch))
                    for t in batch:
                        best = min(
                            best,
                            t.final_measurement.metrics["bbob_eval"].value,
                        )
                elapsed = time.perf_counter() - t0
                best -= optima[fn_name]
                finals.append(best)
                print(
                    json.dumps(
                        {
                            "fn": fn_name,
                            "dim": dim,
                            "policy": policy,
                            "seed": seed,
                            "final_regret": round(best, 4),
                            "wall_s": round(elapsed, 1),
                        }
                    ),
                    flush=True,
                )
            results[(f"{fn_name}{dim}d", policy)] = finals
    print("== summary (median final regret, lower better) ==", flush=True)
    summary = {}
    for (cfg_name, policy), finals in results.items():
        summary[f"{cfg_name}:{policy}"] = float(np.median(finals))
    print(json.dumps(summary, indent=1))
    artifact = {
        "seeds": args.seeds,
        "trials": args.trials,
        "batch": args.batch,
        "evals": args.evals,
        "per_run": {
            f"{cfg}:{pol}": [round(v, 4) for v in finals]
            for (cfg, pol), finals in results.items()
        },
        "median_final_regret": summary,
    }
    out = os.path.join(_REPO_ROOT, "budget_ab_r5.json")
    with open(out, "w") as f:
        json.dump(artifact, f, indent=1)
    print(f"wrote {out}", flush=True)


if __name__ == "__main__":
    main()
