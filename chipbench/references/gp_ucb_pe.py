"""Plain reference of the served algorithm, GP-UCB-PE, and the comparison
of a suggest's own answers with it.

NumPy float64 (``scipy.special.erfinv`` for the rank warp); imports nothing
of the program. From the program it takes the hyperparameters a train
arrived at and nothing else: no labels, no factor, no table. Labels are the
client's own record put through the deployment's warp here; every posterior
is solved here from the client's rows.

What is compared is what the timed path returned: each suggestion of a
study's last suggest carries, from the compiled sweep that picked it, its
posterior mean and stddev (completed trials), its stddev conditioned on the
earlier picks of the batch, and whether it was a UCB or a PE pick
and the acquisition value the sweep itself gave it (``gp_ucb_pe``
metadata). ``compare`` recomputes those at the returned points, scores the
first pick against a seeded candidate set under the reference acquisition,
and weighs the trained hyperparameters by the marginal likelihood. The constants (coefficients, trust region, warp, prior
centres) are the deployment's published settings and live in the
configuration file's ``ucb_pe`` block.

A PE pick is scored against a threshold: the completed-posterior mean at
the argmax-UCB point among the trials and the pending picks. Where two of
those points are near-tied, the program's float32 and this float64 may each
put another one first, and the threshold moves by the gap of their means
with every reading inside its limit. So a PE pick is judged under every
threshold float32 could have chosen (``near_tie_tolerance``: the points
whose UCB here lies within twice what the cell itself allows a reading to
be off), and the threshold under which the sweep's own value reads nearest
is kept. A flip inside the near-tied set is then no error; a wrong penalty
or explore coefficient, or a threshold from a point outside the set, still is.

``posterior_bf16_matmul`` is the control that a test run can hold: the same
posterior with the operands of its matmuls rounded to bfloat16, which is
what a float32 matmul at the TPU's default precision multiplies.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import numpy as np
from scipy import special

JITTER = 1e-5  # the model adds K + (noise² + 1e-5)·I
VARIANCE_FLOOR = 1e-12
PE_NOISE_STDDEV = 1e-5  # the all-points posterior's noise when noise is high


# -- the model ------------------------------------------------------------------


def matern52(a, b, amplitude, length_scales) -> np.ndarray:
    """ARD Matern-5/2 in float64."""
    ls = np.asarray(length_scales, np.float64)
    a, b = np.asarray(a, np.float64) / ls, np.asarray(b, np.float64) / ls
    sq = np.sum(a * a, axis=1)[:, None] + np.sum(b * b, axis=1)[None] - 2.0 * a @ b.T
    sq = np.maximum(sq, 1e-20)
    d = np.sqrt(sq)
    s5 = math.sqrt(5.0)
    return float(amplitude) ** 2 * (1.0 + s5 * d + 5.0 / 3.0 * sq) * np.exp(-s5 * d)


def _bf16(a: np.ndarray) -> np.ndarray:
    """Round-to-nearest-even to bfloat16, returned as float64."""
    bits = np.asarray(a, np.float32).view(np.uint32).astype(np.uint64)
    rounded = ((bits + 0x7FFF + ((bits >> 16) & 1)) >> 16) << 16
    return rounded.astype(np.uint32).view(np.float32).astype(np.float64)


def _posterior(x, y, query, amplitude, noise_stddev, length_scales, cast):
    gram = matern52(x, x, amplitude, length_scales)
    gram += (float(noise_stddev) ** 2 + JITTER) * np.eye(len(gram))
    chol = np.linalg.cholesky(gram)
    k_star = matern52(query, x, amplitude, length_scales)
    alpha = np.linalg.solve(gram, np.asarray(y, np.float64))
    linv = np.linalg.solve(chol, np.eye(len(gram)))
    mean = cast(k_star) @ cast(alpha)
    v = cast(linv) @ cast(k_star.T)
    var = float(amplitude) ** 2 - np.sum(v * v, axis=0)
    return mean, np.sqrt(np.maximum(var, VARIANCE_FLOOR))


def posterior(x, y, query, amplitude, noise_stddev, length_scales):
    """(mean, stddev) of the zero-mean GP at ``query`` in float64."""
    return _posterior(x, y, query, amplitude, noise_stddev, length_scales, lambda a: a)


def posterior_bf16_matmul(x, y, query, amplitude, noise_stddev, length_scales):
    """The control: matmul operands rounded to bfloat16, float64 accumulate."""
    return _posterior(x, y, query, amplitude, noise_stddev, length_scales, _bf16)


def neg_log_likelihood(x, y, amplitude, noise_stddev, length_scales) -> float:
    """-log p(y | x, hyperparameters) of the zero-mean GP."""
    gram = matern52(x, x, amplitude, length_scales)
    gram += (float(noise_stddev) ** 2 + JITTER) * np.eye(len(gram))
    chol = np.linalg.cholesky(gram)
    half = np.linalg.solve(chol, np.asarray(y, np.float64))
    return float(0.5 * half @ half + np.sum(np.log(np.diag(chol))) + 0.5 * len(y) * math.log(2 * math.pi))


def warp_labels(labels, goal: str) -> np.ndarray:
    """The deployment's label warp on finite labels: all-MAXIMIZE sign, the
    below-median half Gaussianised by rank, a log scale anchored at the
    best value, then the shift that centres the column on zero."""
    y = np.asarray(labels, np.float64) * (1.0 if goal == "MAXIMIZE" else -1.0)
    if len(np.unique(y)) == 1:
        return np.zeros_like(y)
    if len(y) >= 2:  # half-rank
        median = np.median(y)
        upper = y[y >= median]
        scale = math.sqrt(np.mean((upper - median) ** 2))
        if scale <= 1e-12:
            scale = np.std(y) + 1e-12
        quantile = (np.argsort(np.argsort(y)) + 0.5) / len(y)
        bad = y < median
        y = y.copy()
        y[bad] = median + scale * math.sqrt(2.0) * special.erfinv(2.0 * quantile[bad] - 1.0)
    span = max(y.max() - y.min(), 1e-12)  # log warp, offset 1.5
    y = 0.5 - np.log1p((y.max() - y) / span * 0.5) / math.log(1.5)
    feasible = (0.5 + len(y)) / (1.0 + len(y))  # no infeasible trial in this traffic
    bad_value = y.min() - (0.5 * (y.max() - y.min()) + 1.0)
    return y - np.mean(y) * feasible - bad_value * (1.0 - feasible)


def near_tie_tolerance(config: Dict[str, Any]) -> float:
    """How far under the best UCB, in label stddevs, a trial's or a pending
    pick's float64 UCB may lie for float32 to have put it first: two
    readings of mean + coefficient x stddev, each off by at most what the
    cell's own limits allow."""
    limits = config["limits"]
    return 2.0 * (limits["pick_mean_err_label_std"]
                  + config["ucb_pe"]["ucb_coefficient"] * limits["pick_stddev_err_label_std"])


# -- the acquisition, over a growing pending set --------------------------------


class _Batch:
    """The posteriors a batch's picks are scored under, at a fixed set of
    points (picks first, then candidates): the completed-trials posterior,
    and the all-points stddev, conditioned pick by pick."""

    def __init__(self, x, y, points, amplitude, noise_stddev, length_scales, ucb_pe):
        self.x, self.points = x, points
        self.amplitude, self.length_scales = float(amplitude), length_scales
        self.ucb_pe = ucb_pe
        noise_var = float(noise_stddev) ** 2
        gram = matern52(x, x, amplitude, length_scales)
        chol = np.linalg.cholesky(gram + (noise_var + JITTER) * np.eye(len(x)))
        k_star = matern52(x, points, amplitude, length_scales)
        self.mean = k_star.T @ np.linalg.solve(chol.T, np.linalg.solve(chol, y))
        v = np.linalg.solve(chol, k_star)
        self.std = np.sqrt(np.maximum(self.amplitude**2 - np.sum(v * v, axis=0), VARIANCE_FLOOR))
        # Completed posterior at the trials themselves: the promising region's threshold.
        v_x = np.linalg.solve(chol, gram)
        self.mean_x = gram @ np.linalg.solve(chol.T, np.linalg.solve(chol, y))
        self.std_x = np.sqrt(np.maximum(self.amplitude**2 - np.sum(v_x * v_x, axis=0), VARIANCE_FLOOR))
        # All-points posterior: the train's noise, or none to speak of when
        # every member reads the noise as high.
        high = (self.amplitude / float(noise_stddev)) ** 2 < ucb_pe["signal_to_noise_threshold"]
        self.pe_noise_var = PE_NOISE_STDDEV**2 if high else noise_var
        if high:
            chol = np.linalg.cholesky(gram + (self.pe_noise_var + JITTER) * np.eye(len(x)))
            v = np.linalg.solve(chol, k_star)
        self.var_all = self.amplitude**2 - np.sum(v * v, axis=0)
        self._rows = v  # L_all^-1 k(all, points), a row added per pending pick
        self.pending: List[int] = []
        self.linf_trials = np.concatenate([  # L-inf distance to the nearest trial, in blocks
            np.min(np.max(np.abs(block[:, None, :] - x[None]), axis=-1), axis=-1)
            for block in np.array_split(points, max(1, len(points) // 256))
        ])

    def std_all(self) -> np.ndarray:
        return np.sqrt(np.maximum(self.var_all, VARIANCE_FLOOR))

    def add_pending(self, index: int) -> None:
        """Conditions the all-points posterior on ``points[index]``."""
        column = self._rows[:, index]
        k_row = matern52(self.points[index : index + 1], self.points, self.amplitude, self.length_scales)[0]
        pivot = math.sqrt(max(self.var_all[index] + self.pe_noise_var + JITTER, VARIANCE_FLOOR))
        row = (k_row - column @ self._rows) / pivot
        self._rows = np.vstack([self._rows, row[None]])
        self.var_all = self.var_all - row * row
        self.pending.append(index)

    def thresholds(self, tolerance: float = 0.0) -> List[float]:
        """Completed-posterior mean at the argmax-UCB point among the
        trials and the pending picks, first; then, distinct, at every other
        such point whose UCB lies within ``tolerance`` of the best."""
        mean = np.concatenate([self.mean_x, self.mean[self.pending]])
        std = np.concatenate([self.std_x, self.std[self.pending]])
        ucb = mean + self.ucb_pe["ucb_coefficient"] * std
        order = np.argsort(-ucb, kind="stable")  # the argmax first
        near = order[ucb[order] >= ucb[order[0]] - tolerance]
        return list(dict.fromkeys(float(m) for m in mean[near]))

    def scores(self, use_ucb: bool, also_observed: List[int], threshold: Optional[float] = None) -> np.ndarray:
        """The acquisition at every point; the trust region is around the
        trials and the points ``also_observed``. A PE score takes the
        promising region's ``threshold`` (the argmax-UCB point's, if none)."""
        c = self.ucb_pe
        if use_ucb:
            value = self.mean + c["ucb_coefficient"] * self.std_all()
        else:
            if threshold is None:
                threshold = self.thresholds()[0]
            explore = self.mean + c["explore_region_ucb_coefficient"] * self.std
            value = self.std_all() + c["cb_violation_penalty_coefficient"] * np.minimum(
                explore - threshold, 0.0
            )
        grow = 0.1 * (len(self.x) + len(also_observed)) / math.sqrt(self.x.shape[1])
        radius = min(c["trust_region_min_radius"] + 0.05 * grow, 1.0)
        linf = self.linf_trials
        for index in also_observed:
            linf = np.minimum(linf, np.max(np.abs(self.points - self.points[index]), axis=-1))
        return value - c["trust_region_penalty_weight"] * np.maximum(linf - radius, 0.0)


def candidates(x, y, picks, rng, spec) -> np.ndarray:
    """A seeded candidate set: uniform points, points around the best
    trials, and points around each pick, clipped to the unit cube."""
    dim = x.shape[1]
    out = [rng.uniform(size=(int(spec["uniform"]), dim))]
    best = x[np.argsort(y)[-int(spec["best_trials"]):]]
    for centres, per_scale in ((best, int(spec["around_best_per_scale"])), (picks, int(spec["around_pick_per_scale"]))):
        for scale in spec["scales"]:
            centre = centres[rng.integers(len(centres), size=per_scale * len(centres))]
            out.append(centre + scale * rng.normal(size=centre.shape))
    return np.clip(np.concatenate(out), 0.0, 1.0)


# -- the comparison -------------------------------------------------------------


def compare(study: Dict[str, Any], trained: Dict[str, Any], config: Dict[str, Any], rng) -> Dict[str, Any]:
    """One sampled study: the numbers to hold against ``config['limits']``
    (``numbers``) and what else was read (``seen``).

    ``study``: the client's record — ``rows`` and ``labels`` of the trials
    completed at its last suggest, that suggest's ``picks`` and their
    ``meta`` (mean, stddev, stddev_from_all, use_ucb, acquisition).
    ``trained``: what the program's last train of the study saw and
    arrived at (``completed``, ``x``, ``y``, hyperparameters, surrogate).
    """
    ucb_pe = config["ucb_pe"]
    rows, picks, meta = study["rows"], study["picks"], study["meta"]
    y = warp_labels(study["labels"], config["goal"])
    scale = float(np.std(y))
    numbers: Dict[str, float] = {}

    # The guarantee: the train saw exactly the client's completed trials.
    same = trained["x"].shape == rows.shape and trained["y"].shape == y.shape
    numbers["trained_trials_missing"] = abs(int(trained["completed"]) - len(rows))
    numbers["trained_rows_max_abs_diff"] = float(np.max(np.abs(trained["x"] - rows))) if same else float("inf")
    numbers["trained_labels_max_abs_diff"] = float(np.max(np.abs(trained["y"] - y))) if same else float("inf")
    numbers["surrogate_mismatch"] = int(trained["surrogate_mode"] != config["surrogate"])

    # The train's result, by the marginal likelihood it reaches from the
    # client's data, per trial, over the priors' centre.
    hyper = (trained["amplitude"], trained["noise_stddev"], trained["length_scales"])
    centre = config["hyperparameter_prior_centre"]
    flat = (centre["amplitude"], centre["noise_stddev"], np.full(rows.shape[1], centre["length_scale"]))
    nll = neg_log_likelihood(rows, y, *hyper)
    numbers["train_nll_gain_per_trial"] = (neg_log_likelihood(rows, y, *flat) - nll) / len(rows)

    # The picks, in the order the batch made them.
    points = np.concatenate([picks, candidates(rows, y, picks, rng, config["check_candidates"])])
    batch = _Batch(rows, y, points, *hyper, ucb_pe)
    count = len(picks)
    mean_err = np.max(np.abs(meta["mean"] - batch.mean[:count])) / scale
    std_err = np.max(np.abs(meta["stddev"] - batch.std[:count])) / scale
    std_all_err, score_err, shortfall, tried = [], [], [], []
    two_phase = ucb_pe["acquisition_budget_policy"] == "first_pick_full" and count > 1
    tolerance = near_tie_tolerance(config) * scale
    for j in range(count):
        # The first pick's trust region is around the trials; where the
        # batch is made in two sweeps, the second's includes the first pick.
        observed = [0] if two_phase and j > 0 else []
        std_all_err.append(abs(meta["stddev_from_all"][j] - batch.std_all()[j]) / scale)
        # A UCB pick has one score; a PE pick one under each threshold that
        # float32 could have chosen, and the nearest to the sweep's own counts.
        use_ucb = bool(meta["use_ucb"][j])
        under = [None] if use_ucb else batch.thresholds(tolerance)
        tried.append(0 if use_ucb else len(under))
        pairs = []
        for threshold in under:
            score = batch.scores(use_ucb, observed, threshold)
            pairs.append((abs(meta["acquisition"][j] - score[j]) / scale, (np.max(score[count:]) - score[j]) / scale))
        err, short = min(pairs, key=lambda pair: pair[0])
        score_err.append(err)
        shortfall.append(short)
        batch.add_pending(j)
    numbers["pick_mean_err_label_std"] = float(mean_err)
    numbers["pick_stddev_err_label_std"] = float(std_err)
    numbers["pick_stddev_all_err_label_std"] = float(max(std_all_err))
    numbers["pick_acquisition_err_label_std"] = float(max(score_err))
    numbers["first_pick_shortfall_label_std"] = float(shortfall[0])
    seen = {
        "trials": len(rows), "label_std": scale, "nll_per_trial": nll / len(rows),
        "amplitude": float(hyper[0]), "noise_stddev": float(hyper[1]),
        "length_scale_min_max": [float(np.min(hyper[2])), float(np.max(hyper[2]))],
        "ucb_picks": int(np.sum(meta["use_ucb"])),
        # The thresholds the first pick was judged under (0: a UCB pick), and the most any pick was.
        "pe_thresholds_tried": tried[0], "pe_thresholds_tried_max": max(tried),
    }
    if count > 1:  # the later picks' shortfall, read and not judged (PERF.md, Open questions)
        later, ucb = np.asarray(shortfall[1:]), np.asarray(meta["use_ucb"][1:], bool)
        seen["later_picks_shortfall_mean"] = float(np.mean(np.maximum(later, 0.0)))
        seen["later_picks_shortfall_max"] = float(np.max(later))
        seen["later_ucb_picks_shortfall_mean"] = float(np.mean(later[ucb])) if ucb.any() else None
        seen["later_pe_picks_shortfall_mean"] = float(np.mean(later[~ucb])) if (~ucb).any() else None
    return {"numbers": numbers, "seen": seen}
