"""Plain reference of GP-UCB-PE as the shipped default serves it past 512
completed trials: through a sparse inducing-point posterior (SGPR), and the
comparison of a suggest's own answers with it.

NumPy float64; imports nothing of the program. From the program it takes
the hyperparameters a train arrived at and nothing else: no labels, no
factor, no inducing index. The kernel, the label warp, the candidate set,
the near-tie tolerance and the acquisition (UCB / PE scores, promising
region, trust region: ``_Batch.scores`` / ``.thresholds``) are
``references/gp_ucb_pe.py``'s own, loaded from the file beside this one as
``run.py`` ``load_module`` loads a reference, not copied.

The model is Titsias' collapsed sparse GP (2009) as published, with the
program's two stabilisers: for inducing rows Z, ``L = chol(Kmm + 1e-4·I)``,
``σ² = noise² + 1e-5`` (the noise as the model reports it, nugget
included), ``A = L⁻¹Kmn/σ``, ``B = I + AAᵀ``, ``c = chol(B)⁻¹Ay/σ``; mean
``k*ᵀL⁻ᵀchol(B)⁻ᵀc``, variance ``k** − ‖L⁻¹k*‖² + ‖chol(B)⁻¹L⁻¹k*‖²``.

Departures from ``gp_ucb_pe.py``'s ``compare``, each forced by the tier:

- **The inducing set is this file's own k-center** (start at the best
  warped label, then the row farthest from the chosen set under the
  unit-length-scale metric, 128 rows) from the client's rows. Where two
  rows are tied for farthest within float32's resolution the program may
  take either, so both orders are walked (at most ``MAX_INDUCING_SETS``
  distinct sets) and the set under which the suggest's own readings lie
  nearest is kept: ``inducing_sets_tried`` in ``seen``.
- **Pending picks condition through the inducing posterior**: a pick joins
  the all-points rows, and joins the inducing set when its Nyström residual
  under the trained set, ``k** − ‖L⁻¹k(Z, x)‖²``, is over 0.1 amplitude².
  A residual within ``AUGMENT_TOLERANCE`` amplitude² of that threshold is
  walked both ways (at most ``MAX_AUGMENT_WALKS`` walks), the nearer kept.
- **``train_bound_gain_per_trial`` for ``train_nll_gain_per_trial``**: the
  collapsed bound at the trained hyperparameters, per trial, over the model
  that explains nothing (independent zero-mean labels at their own
  variance: what a fit with the amplitude at its floor and the noise at the
  labels' stddev reaches), not over the priors' centre: at the centre
  (length scale 0.3 in 20-D, noise 0.01) 128 rows explain none of the other
  rows and the bound's trace term alone is thousands of nats a trial, which
  every fit gains alike, a noise-only one too. The gain over the centre is
  in ``seen``.
- **``inducing_set_mismatch`` is not returned**: ``lib/program.py``
  ``Server.trained`` hands out rows, labels and hyperparameters, not the
  posterior's inducing rows. A set the reference cannot reproduce shows in
  the posterior's own readings (mean, stddev) instead.

``sgpr_bf16_matmul`` is the control a test can hold: the same predictive
with the operands of its three matmuls rounded to bfloat16.
"""

from __future__ import annotations

import copy
import importlib.util
import math
import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np


def _sibling(name: str):
    """``references/<name>.py``, loaded from its file as ``run.load_module`` does."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py")
    spec = importlib.util.spec_from_file_location(f"chipbench.references.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_exact = _sibling("gp_ucb_pe")
matern52 = _exact.matern52
warp_labels = _exact.warp_labels
JITTER, VARIANCE_FLOOR, PE_NOISE_STDDEV = _exact.JITTER, _exact.VARIANCE_FLOOR, _exact.PE_NOISE_STDDEV

KMM_JITTER = 1e-4  # the model factorises Kmm + 1e-4·I
NYSTROM_RESIDUAL_FRACTION = 0.1  # of amplitude²: over it a pick joins the inducing set
AUGMENT_TOLERANCE = 1e-3  # of amplitude²: how near that threshold float32 may decide otherwise
F32_RESOLUTION = 8.0 * float(np.finfo(np.float32).eps)  # relative: two float32 sums of 20 squares
MAX_INDUCING_SETS = 4
MAX_AUGMENT_WALKS = 8


def _identity(a: np.ndarray) -> np.ndarray:
    return a


# -- the inducing set -----------------------------------------------------------


def kcenter(x: np.ndarray, y: np.ndarray, m: int, max_sets: int = 1) -> List[np.ndarray]:
    """Greedy k-center over the rows ``x``: index sequences of ``min(m, n)``
    rows, the float64 one first, then up to ``max_sets - 1`` others that take
    another row where two are tied for first within float32's resolution."""
    x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
    m = min(int(m), len(x))

    def tied(score: np.ndarray) -> np.ndarray:
        best = float(np.max(score))
        near = np.flatnonzero(score >= best - F32_RESOLUTION * max(abs(best), 1.0))
        return near[np.argsort(-score[near], kind="stable")]  # the float64 argmax first

    found: List[np.ndarray] = []
    seen_sets = set()
    # (chosen so far, squared distance of every row to the chosen set)
    stack: List[Tuple[List[int], Optional[np.ndarray]]] = [([], None)]
    while stack and len(found) < max_sets:
        chosen, min_d = stack.pop()
        chosen = list(chosen)
        while len(chosen) < m:
            if min_d is None:
                order = tied(y)
                min_d = np.full(len(x), np.inf)
            else:
                order = tied(min_d)
            for other in order[1:][::-1]:  # the alternatives wait their turn
                if len(stack) + len(found) + 1 < max_sets:
                    d_other = np.minimum(min_d, np.sum((x - x[other]) ** 2, axis=1))
                    stack.append((chosen + [int(other)], d_other))
            chosen.append(int(order[0]))
            min_d = np.minimum(min_d, np.sum((x - x[chosen[-1]]) ** 2, axis=1))
        key = tuple(sorted(chosen))
        if key not in seen_sets:
            seen_sets.add(key)
            found.append(np.asarray(chosen, np.int64))
    return found


# -- the model ------------------------------------------------------------------


class Sgpr:
    """The collapsed sparse GP over rows ``x`` (labels ``y``), inducing rows ``z``."""

    def __init__(self, x, y, z, amplitude, noise_var, length_scales):
        self.amplitude, self.length_scales, self.z = float(amplitude), length_scales, np.asarray(z, np.float64)
        m = len(self.z)
        kmm = matern52(self.z, self.z, amplitude, length_scales)
        kmm[np.diag_indices(m)] = self.amplitude**2 + KMM_JITTER
        self.sigma2 = float(noise_var) + JITTER
        self.chol = np.linalg.cholesky(kmm)
        self.a = np.linalg.solve(self.chol, matern52(self.z, x, amplitude, length_scales)) / math.sqrt(self.sigma2)
        self.chol_b = np.linalg.cholesky(np.eye(m) + self.a @ self.a.T)
        self.c = np.linalg.solve(self.chol_b, self.a @ np.asarray(y, np.float64)) / math.sqrt(self.sigma2)
        self.n = len(x)
        self.yy = float(np.dot(y, y))

    def predict(self, query, cast: Callable[[np.ndarray], np.ndarray] = _identity):
        """(mean, variance) at ``query``; ``cast`` rounds the operands of the
        three matmuls the program's predictive makes."""
        k_star = matern52(self.z, query, self.amplitude, self.length_scales)  # [m, Q]
        linv = np.linalg.inv(self.chol)
        lb_linv = np.linalg.solve(self.chol_b, linv)
        weights = lb_linv.T @ self.c
        t1 = cast(linv) @ cast(k_star)
        t2 = cast(lb_linv) @ cast(k_star)
        mean = cast(weights) @ cast(k_star)
        return mean, self.amplitude**2 - np.sum(t1 * t1, axis=0) + np.sum(t2 * t2, axis=0)

    def residual(self, query) -> np.ndarray:
        """The Nyström residual ``k** − ‖L⁻¹k(Z, x)‖²`` at ``query``."""
        t1 = np.linalg.solve(self.chol, matern52(self.z, query, self.amplitude, self.length_scales))
        return self.amplitude**2 - np.sum(t1 * t1, axis=0)

    def neg_bound(self) -> float:
        """Titsias' collapsed bound, negated: ½[n log 2π + log|B| + n log σ²
        + yᵀy/σ² − cᵀc] + ½·tr(Knn − Qnn)/σ²."""
        trace = self.n * self.amplitude**2 / self.sigma2 - float(np.sum(self.a * self.a))
        log_det = self.n * math.log(self.sigma2) + 2.0 * float(np.sum(np.log(np.diag(self.chol_b))))
        quad = self.yy / self.sigma2 - float(self.c @ self.c)
        return 0.5 * (self.n * math.log(2.0 * math.pi) + log_det + quad + trace)


def sgpr_posterior(x, y, z, query, amplitude, noise_stddev, length_scales, cast=_identity):
    """(mean, stddev) of the collapsed sparse GP at ``query`` in float64."""
    mean, var = Sgpr(x, y, z, amplitude, float(noise_stddev) ** 2, length_scales).predict(query, cast)
    return mean, np.sqrt(np.maximum(var, VARIANCE_FLOOR))


def sgpr_bf16_matmul(x, y, z, query, amplitude, noise_stddev, length_scales):
    """The control: matmul operands rounded to bfloat16, float64 accumulate."""
    return sgpr_posterior(x, y, z, query, amplitude, noise_stddev, length_scales, _exact._bf16)


def neg_noise_only_bound(y) -> float:
    """What the bound reaches with nothing explained: independent zero-mean
    labels at their own variance, ½·n·(log 2π + log mean(y²) + 1)."""
    y = np.asarray(y, np.float64)
    return 0.5 * len(y) * (math.log(2.0 * math.pi) + math.log(float(np.mean(y * y))) + 1.0)


# -- the acquisition, over a growing pending set --------------------------------


class SparseBatch(_exact._Batch):
    """``gp_ucb_pe._Batch`` with both posteriors through the inducing rows:
    the scores, the thresholds and the trust region are the parent's, read
    from the fields this class fills. (The parent's constructor runs first
    for the fields that do not depend on the posterior — the points, the
    distances to the trials — and every posterior field is then replaced.)"""

    def __init__(self, x, y, z, points, amplitude, noise_stddev, length_scales, ucb_pe, cast=_identity):
        super().__init__(x, y, points, amplitude, noise_stddev, length_scales, ucb_pe)
        self.cast = cast
        hyper = (amplitude, float(noise_stddev) ** 2, length_scales)
        self.trained = Sgpr(x, y, z, *hyper)
        floor = lambda var: np.sqrt(np.maximum(var, VARIANCE_FLOOR))  # noqa: E731
        self.mean, var = self.trained.predict(points, cast)
        self.std = floor(var)
        self.mean_x, var_x = self.trained.predict(x, cast)
        self.std_x = floor(var_x)
        self.rows_all, self.z_all = np.asarray(x, np.float64), np.asarray(z, np.float64)
        self.augmented: List[int] = []
        self._recondition()

    def _recondition(self) -> None:
        everything = Sgpr(self.rows_all, np.zeros(len(self.rows_all)), self.z_all,
                          self.amplitude, self.pe_noise_var, self.length_scales)
        self.var_all = everything.predict(self.points, self.cast)[1]

    def residual_share(self, index: int) -> float:
        """A point's Nyström residual under the TRAINED set, in amplitude²."""
        return float(self.trained.residual(self.points[index : index + 1])[0]) / self.amplitude**2

    def add_pending(self, index: int, augment: Optional[bool] = None) -> None:
        """Conditions the all-points posterior on ``points[index]``, which
        joins the inducing rows too if ``augment`` (the published rule, if None)."""
        if augment is None:
            augment = self.residual_share(index) > NYSTROM_RESIDUAL_FRACTION
        self.rows_all = np.concatenate([self.rows_all, self.points[index : index + 1]])
        if augment:
            self.z_all = np.concatenate([self.z_all, self.points[index : index + 1]])
            self.augmented = self.augmented + [index]
        self.pending = self.pending + [index]
        self._recondition()


# -- the comparison -------------------------------------------------------------

_JUDGED = ("pick_mean_err_label_std", "pick_stddev_err_label_std", "pick_stddev_all_err_label_std",
           "pick_acquisition_err_label_std")


def _walk(batch: SparseBatch, meta, scale: float, config, forced: Sequence[bool]):
    """The batch's picks in the order made, against ``batch`` (consumed).
    ``forced``: the augment decisions of the picks whose residual lies near
    the threshold, in the order met (the published rule beyond its end).
    Returns (numbers, per-pick readings, the near picks' decisions as taken)."""
    ucb_pe = config["ucb_pe"]
    count = len(meta["mean"])
    two_phase = ucb_pe["acquisition_budget_policy"] == "first_pick_full" and count > 1
    tolerance = _exact.near_tie_tolerance(config) * scale
    numbers = {
        "pick_mean_err_label_std": float(np.max(np.abs(meta["mean"] - batch.mean[:count])) / scale),
        "pick_stddev_err_label_std": float(np.max(np.abs(meta["stddev"] - batch.std[:count])) / scale),
    }
    std_all_err, score_err, shortfall, tried, near = [], [], [], [], []
    for j in range(count):
        observed = [0] if two_phase and j > 0 else []
        std_all_err.append(abs(meta["stddev_from_all"][j] - batch.std_all()[j]) / scale)
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
        share = batch.residual_share(j)
        augment = share > NYSTROM_RESIDUAL_FRACTION
        if abs(share - NYSTROM_RESIDUAL_FRACTION) <= AUGMENT_TOLERANCE:
            if len(near) < len(forced):
                augment = bool(forced[len(near)])
            near.append(augment)
        batch.add_pending(j, augment)
    numbers["pick_stddev_all_err_label_std"] = float(max(std_all_err))
    numbers["pick_acquisition_err_label_std"] = float(max(score_err))
    numbers["first_pick_shortfall_label_std"] = float(shortfall[0])
    readings = {"shortfall": shortfall, "tried": tried, "augments": len(batch.augmented)}
    return numbers, readings, near


def _distance(numbers: Dict[str, float], limits: Dict[str, Any]) -> float:
    """How far a walk's readings lie from the suggest's own: the worst of
    the posterior's four, each in units of its limit."""
    return max(numbers[name] / float(limits[name]) for name in _JUDGED)


def compare(study: Dict[str, Any], trained: Dict[str, Any], config: Dict[str, Any], rng) -> Dict[str, Any]:
    """One sampled study: the numbers to hold against ``config['limits']``
    (``numbers``) and what else was read (``seen``). ``study`` and
    ``trained`` as ``gp_ucb_pe.compare`` takes them."""
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

    hyper = (trained["amplitude"], trained["noise_stddev"], trained["length_scales"])
    sets = kcenter(rows, y, int(config["num_inducing"]), MAX_INDUCING_SETS)

    # The train's result, by the collapsed bound it reaches from the client's
    # rows and this file's inducing rows, per trial, over explaining nothing.
    fitted = Sgpr(rows, y, rows[sets[0]], hyper[0], float(hyper[1]) ** 2, hyper[2])
    bound = fitted.neg_bound()
    numbers["train_bound_gain_per_trial"] = (neg_noise_only_bound(y) - bound) / len(rows)
    centre = config["hyperparameter_prior_centre"]
    at_centre = Sgpr(rows, y, rows[sets[0]], centre["amplitude"], float(centre["noise_stddev"]) ** 2,
                     np.full(rows.shape[1], centre["length_scale"])).neg_bound()

    # The picks, in the order the batch made them: under each inducing set
    # float32 could have chosen and each way a near-threshold pick could have
    # gone, the walk whose readings lie nearest the suggest's own.
    points = np.concatenate([picks, _exact.candidates(rows, y, picks, rng, config["check_candidates"])])
    best, walks = None, 0
    for chosen in sets:
        fresh = SparseBatch(rows, y, rows[chosen], points, *hyper, config["ucb_pe"])
        queue: List[Tuple[bool, ...]] = [()]
        walked = set()
        while queue and len(walked) < MAX_AUGMENT_WALKS:
            forced = queue.pop(0)
            found, readings, near = _walk(copy.copy(fresh), meta, scale, config, forced)
            walked.add(tuple(near))
            walks += 1
            if best is None or _distance(found, config["limits"]) < _distance(best[0], config["limits"]):
                best = (found, readings)
            for i in range(len(forced), len(near)):  # each later near pick, the other way
                other = tuple(near[:i]) + (not near[i],)
                if other not in walked and other not in queue:
                    queue.append(other)
    found, readings = best
    numbers.update(found)
    shortfall, tried = readings["shortfall"], readings["tried"]
    count = len(picks)
    seen = {
        "trials": len(rows), "label_std": scale, "bound_per_trial": bound / len(rows),
        "bound_gain_over_prior_centre_per_trial": (at_centre - bound) / len(rows),
        "amplitude": float(hyper[0]), "noise_stddev": float(hyper[1]),
        "length_scale_min_max": [float(np.min(hyper[2])), float(np.max(hyper[2]))],
        "ucb_picks": int(np.sum(meta["use_ucb"])),
        "pe_thresholds_tried": tried[0], "pe_thresholds_tried_max": max(tried),
        "nystrom_augments": readings["augments"], "inducing_sets_tried": len(sets), "walks": walks,
    }
    if count > 1:  # the later picks' shortfall, read and not judged (PERF.md, Open questions)
        later, ucb = np.asarray(shortfall[1:]), np.asarray(meta["use_ucb"][1:], bool)
        seen["later_picks_shortfall_mean"] = float(np.mean(np.maximum(later, 0.0)))
        seen["later_picks_shortfall_max"] = float(np.max(later))
        seen["later_ucb_picks_shortfall_mean"] = float(np.mean(later[ucb])) if ucb.any() else None
        seen["later_pe_picks_shortfall_mean"] = float(np.mean(later[~ucb])) if (~ucb).any() else None
    return {"numbers": numbers, "seen": seen}
