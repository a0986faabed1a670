"""Plain reference of GP-UCB-PE on a study that other workers hold trials
of, and the comparison of a shared study's last answer with it.

NumPy float64 (``scipy.special.erfinv`` for the rank warp); imports nothing
of the program and nothing of ``references/gp_ucb_pe.py``, whose kernel,
label warp, likelihood and candidate set are copied here so that each
deployment's yardstick stands alone. What differs is the conditioning: a
``suggest(1)`` of a shared study is scored with the trials handed out and not
yet completed in the all-points set from the start (upstream
``gp_ucb_pe.py``: the pending rows deflate the stddev that UCB and PE
explore with, enter the promising region's threshold and count as observed
for the trust region), and the study may hold no completed trial at all.

From the program it takes the hyperparameters a train arrived at and the
**ids** of the trials its designer held at its last computation
(``lib/pending.py``): the completed ones it trained on and the ACTIVE ones
it conditioned on. Rows and labels are the clients' own, looked up by id.
The ids are held against the clients' clocks first (the configuration's
guarantees): a completion acknowledged before the request was sent must be
among the completed (G1, ``acked_completions_missing``); a trial handed out
before the request was sent, and not completed before its response, must be
among the pending (G2, ``pending_missing``); an id no client could have
caused by then must be in neither (``rows_from_nowhere``). The pick's
UCB-or-PE decision is a draw (``UCBPEConfig``: PE with probability 0.1 after
a new completion, UCB with 0.25 otherwise), so what is held exactly is the
draw's input — whether a completed trial postdates every pending one's
creation, recomputed here from the server's own times as the clients
received them (upstream ``gp_ucb_pe.py:142``) — and that no pick without a
completed trial is a UCB pick (``ucb_or_pe_mismatch``).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import numpy as np
from scipy import special

JITTER = 1e-5  # the model adds K + (noise² + 1e-5)·I
VARIANCE_FLOOR = 1e-12
PE_NOISE_STDDEV = 1e-5  # the all-points posterior's noise when noise is high


# -- the model (as in references/gp_ucb_pe.py) ------------------------------------


def matern52(a, b, amplitude, length_scales) -> np.ndarray:
    """ARD Matern-5/2 in float64."""
    ls = np.asarray(length_scales, np.float64)
    a, b = np.asarray(a, np.float64) / ls, np.asarray(b, np.float64) / ls
    sq = np.sum(a * a, axis=1)[:, None] + np.sum(b * b, axis=1)[None] - 2.0 * a @ b.T
    sq = np.maximum(sq, 1e-20)
    d = np.sqrt(sq)
    s5 = math.sqrt(5.0)
    return float(amplitude) ** 2 * (1.0 + s5 * d + 5.0 / 3.0 * sq) * np.exp(-s5 * d)


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
    if len(np.unique(y)) <= 1:
        return np.zeros_like(y)
    median = np.median(y)  # half-rank
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


def candidates(x, y, pick, rng, spec) -> np.ndarray:
    """A seeded candidate set: uniform points, points around the best
    trials (around the pick where there is none), and points around the
    pick, clipped to the unit cube."""
    out = [rng.uniform(size=(int(spec["uniform"]), pick.shape[1]))]
    best = x[np.argsort(y)[-int(spec["best_trials"]):]] if len(x) else pick
    for centres, per_scale in ((best, int(spec["around_best_per_scale"])), (pick, int(spec["around_pick_per_scale"]))):
        for scale in spec["scales"]:
            centre = centres[rng.integers(len(centres), size=per_scale * len(centres))]
            out.append(centre + scale * rng.normal(size=centre.shape))
    return np.clip(np.concatenate(out), 0.0, 1.0)


def near_tie_tolerance(config: Dict[str, Any]) -> float:
    """How far under the best UCB, in label stddevs, a trial's or a pending
    row's float64 UCB may lie for float32 to have put it first: two
    readings of mean + coefficient x stddev, each off by at most what the
    cell's own limits allow."""
    limits = config["limits"]
    return 2.0 * (limits["pick_mean_err_label_std"]
                  + config["ucb_pe"]["ucb_coefficient"] * limits["pick_stddev_err_label_std"])


# -- one pick, with other workers' trials pending -------------------------------


class Conditioned:
    """The posteriors one pick is scored under, at ``points``: the
    completed trials' (``x``, ``y``; there may be none), and the all-points
    stddev, conditioned on the completed and the ``pending`` rows alike."""

    def __init__(self, x, y, pending, points, amplitude, noise_stddev, length_scales, ucb_pe, tolerance=0.0):
        self.ucb_pe = ucb_pe
        self.amplitude = float(amplitude)
        noise_var = float(noise_stddev) ** 2
        hyper = (amplitude, length_scales)
        self.observed = np.concatenate([x, pending])  # what the trust region is around
        self.points = points

        def solve(rows, noise, query):
            """L⁻¹ k(rows, query) for K(rows) + (noise + jitter)·I = L·Lᵀ, and L."""
            chol = np.linalg.cholesky(matern52(rows, rows, *hyper) + (noise + JITTER) * np.eye(len(rows)))
            return np.linalg.solve(chol, matern52(rows, query, *hyper)), chol

        def stddev(v):
            return np.sqrt(np.maximum(self.amplitude**2 - np.sum(v * v, axis=0), VARIANCE_FLOOR))

        both = np.concatenate([points, self.observed])
        v, chol = solve(x, noise_var, both)
        mean = v.T @ np.linalg.solve(chol, np.asarray(y, np.float64))
        self.mean, self.std = mean[: len(points)], stddev(v)[: len(points)]
        # The promising region's threshold: the completed-posterior mean at
        # the argmax-UCB point among the trials and the pending rows, first;
        # then, distinct, at every other such point whose UCB lies within
        # ``tolerance`` of the best.
        mean_obs, std_obs = mean[len(points):], stddev(v)[len(points):]
        self.thresholds: List[float] = [0.0]
        if len(mean_obs):
            ucb = mean_obs + ucb_pe["ucb_coefficient"] * std_obs
            order = np.argsort(-ucb, kind="stable")  # the argmax first
            near = order[ucb[order] >= ucb[order[0]] - tolerance]
            self.thresholds = list(dict.fromkeys(float(m) for m in mean_obs[near]))
        # All-points posterior: the train's noise, or none to speak of when
        # the model reads the noise as high.
        high = (self.amplitude / float(noise_stddev)) ** 2 < ucb_pe["signal_to_noise_threshold"]
        self.std_all = stddev(solve(self.observed, PE_NOISE_STDDEV**2 if high else noise_var, points)[0])

    def scores(self, use_ucb: bool, threshold: Optional[float] = None) -> np.ndarray:
        """The acquisition at every point, with the trust region around the
        completed and the pending rows. A PE score takes the promising
        region's ``threshold`` (the argmax-UCB point's, if none)."""
        c = self.ucb_pe
        if use_ucb:
            value = self.mean + c["ucb_coefficient"] * self.std_all
        else:
            if threshold is None:
                threshold = self.thresholds[0]
            explore = self.mean + c["explore_region_ucb_coefficient"] * self.std
            value = self.std_all + c["cb_violation_penalty_coefficient"] * np.minimum(explore - threshold, 0.0)
        if not len(self.observed):
            return value  # no observation at all: everything is trusted
        grow = 0.1 * len(self.observed) / math.sqrt(self.points.shape[1])
        radius = min(c["trust_region_min_radius"] + 0.05 * grow, 1.0)
        linf = np.concatenate([
            np.min(np.max(np.abs(block[:, None, :] - self.observed[None]), axis=-1), axis=-1)
            for block in np.array_split(self.points, max(1, len(self.points) // 256))
        ])
        return value - c["trust_region_penalty_weight"] * np.maximum(linf - radius, 0.0)


def has_new_completed(completed, pending) -> bool:
    """Upstream ``_has_new_completed_trials``: a completed trial postdates
    every pending trial's creation (``completed`` / ``pending``: the server's
    completion / creation times, None where a trial carries none)."""
    if not len(completed):
        return False
    done = [t for t in completed if t is not None]
    made = [t for t in pending if t is not None]
    if not len(pending) or not done or not made:
        return True
    return max(done) > max(made)


# -- the comparison -------------------------------------------------------------


def compare(study: Dict[str, Any], trained: Dict[str, Any], config: Dict[str, Any], rng) -> Dict[str, Any]:
    """One sampled study: the numbers to hold against ``config['limits']``
    (``numbers``) and what else was read (``seen``).

    ``study``: the clients' record (``generators/shared_fills.py``) —
    ``trials`` by id, each with its ``row``, ``value`` and the client's clock
    at request sent / response received / complete sent / complete
    acknowledged (``t_sent``, ``t_received``, ``t_complete_sent``,
    ``t_acked``; None = not yet), the server's ``created`` / ``completed``
    times and the sweep's ``meta``; ``last``, the id the study's last
    computation answered with; ``held``, the ids its designer computed that
    answer from (``completed``, ``pending``, ``incorporated``,
    ``first_has_new``). ``trained``: what the program's last train of the
    study saw and arrived at (``lib/program.py`` ``Server.trained``).
    """
    trials, held = study["trials"], study["held"]
    last = trials[study["last"]]
    t0, t1 = last["t_sent"], last["t_received"]
    completed_ids, pending_ids = list(held["completed"]), list(held["pending"])
    completed, pending = set(completed_ids), set(pending_ids)
    numbers: Dict[str, float] = {}

    def before(t, limit) -> bool:
        return t is not None and t < limit

    # The guarantees, by id against the clients' clocks.
    acked = {i for i, t in trials.items() if before(t["t_acked"], t0)}
    handed_out = {i for i, t in trials.items() if before(t["t_received"], t0)}
    still_out = {i for i in handed_out if not before(trials[i]["t_complete_sent"], t1)}
    numbers["acked_completions_missing"] = len(acked - completed)
    numbers["pending_missing"] = len(
        {i for i in handed_out if i not in pending and (i not in completed or i in still_out)})
    nowhere = {i for i in completed if i not in trials or not before(trials[i]["t_complete_sent"], t1)}
    nowhere |= {i for i in pending if i not in trials or not before(trials[i]["t_sent"], t1)}
    nowhere |= (completed & pending) | ({study["last"]} & (completed | pending))
    nowhere |= completed ^ set(held["incorporated"])
    numbers["rows_from_nowhere"] = len(nowhere)

    # The model, from the clients' rows and values of the ids the program
    # names (an id the clients never saw is counted above and left out).
    dim = len(last["row"])
    known = [i for i in completed_ids if i in trials and trials[i]["value"] is not None]
    rows = np.asarray([trials[i]["row"] for i in known], np.float64).reshape(len(known), dim)
    y = warp_labels([trials[i]["value"] for i in known], config["goal"])
    others = [i for i in pending_ids if i in trials]
    pending_rows = np.asarray([trials[i]["row"] for i in others], np.float64).reshape(len(others), dim)
    scale = float(np.std(y)) if len(y) and np.std(y) > 0 else 1.0  # one label or none: absolute

    same = trained["x"].shape == rows.shape and trained["y"].shape == y.shape
    numbers["trained_trials_missing"] = abs(int(trained["completed"]) - len(rows))
    numbers["trained_rows_max_abs_diff"] = float(np.max(np.abs(trained["x"] - rows), initial=0.0)) if same else float("inf")
    numbers["trained_labels_max_abs_diff"] = float(np.max(np.abs(trained["y"] - y), initial=0.0)) if same else float("inf")
    numbers["surrogate_mismatch"] = int(trained["surrogate_mode"] != config["surrogate"])

    # The draw's input, from the server's times as the clients received them.
    first_has_new = has_new_completed(
        [trials[i]["completed"] for i in known], [trials[i]["created"] for i in others])
    meta = last["meta"]
    use_ucb = bool(meta["use_ucb"])
    numbers["ucb_or_pe_mismatch"] = int(first_has_new != held["first_has_new"]) + int(use_ucb and not len(rows))

    # The train's result, by the marginal likelihood it reaches from the
    # clients' data, per trial, over the priors' centre — once a study holds
    # enough trials for the likelihood to outweigh the priors.
    hyper = (trained["amplitude"], trained["noise_stddev"], trained["length_scales"])
    nll = neg_log_likelihood(rows, y, *hyper) if len(rows) else 0.0
    # The model's nugget: the noise it reports is never under its share of
    # the amplitude, whatever the train arrived at (float32's rounding aside).
    nugget = float(config["nugget_to_amplitude"])
    numbers["noise_under_the_nugget"] = int(float(hyper[1]) < nugget * float(hyper[0]) * (1.0 - 1e-4))
    if len(rows) >= int(config["nll_gain_min_trials"]):
        centre = config["hyperparameter_prior_centre"]
        flat = (centre["amplitude"], math.hypot(centre["noise_stddev"], nugget * centre["amplitude"]),
                np.full(dim, centre["length_scale"]))  # (the centre as the model builds it: with its nugget)
        numbers["train_nll_gain_per_trial"] = (neg_log_likelihood(rows, y, *flat) - nll) / len(rows)

    # The pick: what the sweep stamped on it against the reference at it,
    # and its score against the best of a seeded candidate set.
    pick = np.asarray(last["row"], np.float64)[None]
    points = np.concatenate([pick, candidates(rows, y, pick, rng, config["check_candidates"])])
    posterior = Conditioned(rows, y, pending_rows, points, *hyper, config["ucb_pe"], near_tie_tolerance(config) * scale)
    # A UCB pick has one score; a PE pick one under each threshold that
    # float32 could have chosen, and the nearest to the sweep's own counts.
    under = [None] if use_ucb else posterior.thresholds
    pairs = []
    for threshold in under:
        score = posterior.scores(use_ucb, threshold)
        pairs.append((abs(meta["acquisition"] - score[0]) / scale, (np.max(score[1:]) - score[0]) / scale))
    acquisition_err, shortfall = min(pairs, key=lambda pair: pair[0])
    numbers["pick_mean_err_label_std"] = float(abs(meta["mean"] - posterior.mean[0]) / scale)
    numbers["pick_stddev_err_label_std"] = float(abs(meta["stddev"] - posterior.std[0]) / scale)
    numbers["pick_stddev_all_err_label_std"] = float(abs(meta["stddev_from_all"] - posterior.std_all[0]) / scale)
    numbers["pick_acquisition_err_label_std"] = float(acquisition_err)
    numbers["first_pick_shortfall_label_std"] = float(shortfall)
    seen = {
        "trials": len(rows), "pending": len(pending_rows), "handed_out_before_request": len(handed_out),
        "acked_before_request": len(acked), "label_std": scale, "nll_per_trial": nll / max(len(rows), 1),
        "amplitude": float(hyper[0]), "noise_stddev": float(hyper[1]),
        "length_scale_min_max": [float(np.min(hyper[2])), float(np.max(hyper[2]))],
        "use_ucb": use_ucb, "first_has_new": first_has_new,
        "pe_thresholds_tried": 0 if use_ucb else len(under),  # (0: a UCB pick, judged under none)
    }
    return {"numbers": numbers, "seen": seen}
