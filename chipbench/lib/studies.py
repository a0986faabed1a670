"""Studies of a configuration: search space, seeded trials, objective, buckets.

The study shape and trial data follow ``chip_smoke.py`` (``study_config``,
``completed_trials``, PR 21), copied here as one function of the
configuration file (any number of floats, either goal, a centre per
dimension), so a new deployment is a new file.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np


def param_names(config: Dict[str, Any]) -> List[str]:
    return [f"x{d}" for d in range(int(config["num_float_parameters"]))]


def study_config(config: Dict[str, Any]):
    """``vz.StudyConfig`` of the deployment: floats in [0, 1], one metric."""
    from vizier_tpu import pyvizier as vz

    out = vz.StudyConfig()
    out.algorithm = config["algorithm"]
    for name in param_names(config):
        out.search_space.root.add_float_param(name, 0.0, 1.0)
    out.metric_information.append(
        vz.MetricInformation(
            name="obj", goal=getattr(vz.ObjectiveMetricGoal, config["goal"])
        )
    )
    evals = config.get("max_acquisition_evaluations")
    if evals is not None:  # rehearsal only: the files state no override
        out.metadata.ns("gp_ucb_pe")["max_acquisition_evaluations"] = str(int(evals))
    return out


class Objective:
    """Signed quadratic around ``center`` plus seeded Gaussian noise."""

    def __init__(self, config: Dict[str, Any]):
        spec = config["objective"]
        dim = int(config["num_float_parameters"])
        self.center = np.broadcast_to(np.asarray(spec["center"], np.float64), (dim,))
        self.sign = -1.0 if config["goal"] == "MAXIMIZE" else 1.0
        self.noise = float(spec["noise_stddev"])

    def __call__(self, x: np.ndarray, rng: np.random.Generator) -> np.ndarray:
        x = np.atleast_2d(x)
        value = self.sign * np.sum((x - self.center) ** 2, axis=-1)
        return value + self.noise * rng.normal(size=len(x))


def seeded_trials(config: Dict[str, Any], rng: np.random.Generator, n: int):
    """``n`` completed trials, their parameter rows and values, from ``rng``."""
    from vizier_tpu import pyvizier as vz

    names = param_names(config)
    x = rng.uniform(size=(n, len(names)))
    y = Objective(config)(x, rng)
    trials = []
    for i in range(n):
        t = vz.Trial(parameters={name: float(x[i, d]) for d, name in enumerate(names)})
        t.complete(vz.Measurement(metrics={"obj": float(y[i])}))
        trials.append(t)
    return trials, x, y


def pad_power_of_two(n: int) -> int:
    """``converters/padding.py`` POWERS_OF_2: next power of two, at least 8."""
    return max(8, 1 << max(0, n - 1).bit_length())


def bucket(completed: int, count: int) -> Tuple[int, int]:
    """The shapes a ``suggest(count)`` compiles for at ``completed`` trials
    and none active: the trained rows' pad and the all-points pad
    (``designers/gp_ucb_pe.py`` ``UCBPEProgram.bucket_key``)."""
    return pad_power_of_two(completed), pad_power_of_two(completed + count)


def rounds_in_bucket(start: int, count: int, limit: Optional[int] = None) -> int:
    """How many successive ``suggest(count)``→complete-all rounds a study
    that starts at ``start`` completed trials makes inside one bucket."""
    home, rounds = bucket(start, count), 0
    while bucket(start + rounds * count, count) == home:
        rounds += 1
        if limit is not None and rounds >= limit:
            break
    return rounds
