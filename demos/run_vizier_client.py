"""Client demo: optimize a toy objective against a running server.

Usage:
  python demos/run_vizier_client.py --endpoint localhost:28080 [--trials 20]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def evaluate(lr: float, layers: int) -> float:
    return 1.0 - 100.0 * (lr - 0.01) ** 2 - 0.05 * abs(layers - 3)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--endpoint", default=None)
    parser.add_argument("--trials", type=int, default=20)
    parser.add_argument("--algorithm", default="DEFAULT")
    args = parser.parse_args()

    from vizier_tpu import pyvizier as vz
    from vizier_tpu.service import clients

    config = vz.StudyConfig(algorithm=args.algorithm)
    root = config.search_space.root
    root.add_float_param("learning_rate", 1e-4, 1e-1, scale_type=vz.ScaleType.LOG)
    root.add_int_param("layers", 1, 8)
    config.metric_information.append(
        vz.MetricInformation(name="accuracy", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    study = clients.Study.from_study_config(
        config, owner="demo", study_id="client-demo", endpoint=args.endpoint
    )
    for i in range(args.trials):
        for trial in study.suggest(count=1):
            params = trial.parameters
            acc = evaluate(params["learning_rate"], params["layers"])
            trial.complete(vz.Measurement(metrics={"accuracy": acc}))
            print(f"trial {i + 1}: acc={acc:.4f} params={params}")
    best = list(study.optimal_trials())[0].materialize()
    print(
        "best:", best.final_measurement.metrics["accuracy"].value,
        dict(best.parameters.as_dict()),
    )


if __name__ == "__main__":
    main()
