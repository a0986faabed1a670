"""Noise-robustness sweep: the DEFAULT designer under the BBOB-noisy zoo.

Usage: python tools/noise_robustness.py [--trials 60] [--seeds 1 2 3]

The r4 review noted noise-robustness experiments (a stated use of the
wrapper zoo) could not be reproduced with a Gaussian-only wrapper. This
tool runs ``VizierGPUCBPEBandit`` on shifted 4-D Sphere under every noise
model in ``wrappers.NOISE_TYPES`` and reports the final TRUE simple
regret (the ``_before_noise`` metric of the observed-noisy incumbent:
what the tuner actually delivered, judged on clean ground truth). Writes
``noise_robustness_r5.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=60)
    ap.add_argument("--batch", type=int, default=5)
    ap.add_argument("--evals", type=int, default=4000)
    ap.add_argument("--dim", type=int, default=4)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2, 3])
    args = ap.parse_args()

    from vizier_tpu.algorithms import core as core_lib
    from vizier_tpu.benchmarks.experimenters import (
        experimenter_factory,
        wrappers,
    )
    from vizier_tpu.designers.gp_ucb_pe import VizierGPUCBPEBandit

    results: dict = {}
    for noise_type in wrappers.NOISE_TYPES:
        finals = []
        for seed in args.seeds:
            clean = experimenter_factory.shifted_bbob_instance(
                "Sphere", seed, dim=args.dim
            )
            exp = wrappers.NoisyExperimenter.from_type(
                clean, noise_type, seed=seed
            )
            designer = VizierGPUCBPEBandit(
                exp.problem_statement(),
                rng_seed=seed,
                max_acquisition_evaluations=args.evals,
                num_seed_trials=5,
            )
            best_noisy, best_true, tid = np.inf, np.inf, 0
            while tid < args.trials:
                batch = [
                    s.to_trial(tid + i + 1)
                    for i, s in enumerate(designer.suggest(args.batch))
                ]
                tid += len(batch)
                exp.evaluate(batch)
                designer.update(core_lib.CompletedTrials(batch))
                for t in batch:
                    m = t.final_measurement.metrics
                    noisy = m["bbob_eval"].value
                    if noisy < best_noisy:
                        best_noisy = noisy
                        # True regret of the incumbent the tuner believes in.
                        best_true = m["bbob_eval_before_noise"].value
            finals.append(best_true)
            print(
                json.dumps(
                    {
                        "noise": noise_type,
                        "seed": seed,
                        "true_regret": round(best_true, 4),
                    }
                ),
                flush=True,
            )
        results[noise_type] = {
            "per_seed_true_regret": [round(v, 4) for v in finals],
            "median": round(float(np.median(finals)), 4),
        }
    artifact = {
        "config": (
            f"shifted Sphere {args.dim}-D, {args.trials} trials x batch "
            f"{args.batch}, DEFAULT designer, seeds {args.seeds}"
        ),
        "metric": "true simple regret of the noisy-incumbent (before_noise)",
        "results": results,
    }
    out = os.path.join(_REPO_ROOT, "noise_robustness_r5.json")
    with open(out, "w") as f:
        json.dump(artifact, f, indent=1)
    print(f"wrote {out}", flush=True)


if __name__ == "__main__":
    main()
