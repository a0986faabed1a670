"""The controls of ``correct``, at a size a test run can hold.

On the chip the controls are the program itself: ``run.py --control 1`` (the
posterior's matmuls at the TPU's default precision) and ``--control 2`` (the
acquisition sweeps cut short); PERF.md section 2 has their readings. The CPU
has no lower matmul precision to switch on, so here the reference stands in
the program's place. A batch picked and stamped by the float64 reference
keeps every limit of the configuration; the same batch stamped from a
posterior whose matmul operands are rounded to bfloat16, picked at random
instead of by a sweep, handed the answers of another study, trained on
other labels, or trained to hyperparameters no train would keep, breaks the
limit that is there for it.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.lib import checks  # noqa: E402
from chipbench.lib import studies  # noqa: E402
from chipbench.references import gp_ucb_pe as reference  # noqa: E402

with open(os.path.join(ROOT, "chipbench", "configs", "default20d.json")) as _f:
    CONFIG = json.load(_f)
COUNT = 6
# Hyperparameters of the size the chip's trains arrived at (PR 23's runs).
HYPER = {"amplitude": 0.35, "noise_stddev": 0.022, "length_scales": np.full(20, 2.0)}


def _study(seed: int, n: int = 120):
    """The client's record of a study and a sound program's answers to its
    last suggest: picked greedily from the reference's own candidates and
    stamped with the float64 posterior."""
    rng = np.random.default_rng([seed, n])
    _, x, labels = studies.seeded_trials(CONFIG, rng, n)
    y = reference.warp_labels(labels, CONFIG["goal"])
    pool = reference.candidates(x, y, x[:1], rng, CONFIG["check_candidates"])
    batch = reference._Batch(x, y, pool, *HYPER.values(), CONFIG["ucb_pe"])
    picks, meta = [], {k: [] for k in ("mean", "stddev", "stddev_from_all", "use_ucb", "acquisition")}
    for j in range(COUNT):
        use_ucb = j == 0
        score = batch.scores(use_ucb, [picks[0]] if j else [])
        best = int(np.argmax(score))
        for key, value in (("mean", batch.mean[best]), ("stddev", batch.std[best]),
                           ("stddev_from_all", batch.std_all()[best]), ("use_ucb", float(use_ucb)),
                           ("acquisition", score[best])):
            meta[key].append(value)
        picks.append(best)
        batch.add_pending(best)
    record = {"rows": x, "labels": labels, "picks": pool[picks],
              "meta": {k: np.asarray(v) for k, v in meta.items()}}
    trained = {"completed": n, "x": x.astype(np.float32).astype(np.float64),
               "y": y.astype(np.float32).astype(np.float64), "surrogate_mode": "exact", **HYPER}
    return record, trained


# The stand-in picks the best of a pool of its own, not by a 75k sweep: it
# lies 0.1-0.25 label stddevs under the best the comparison's candidates find
# (random picks lie 30 and more under it), so the shortfall's limit is a
# stand-in's here; every other limit is the configuration's.
LIMITS = {**CONFIG["limits"], "first_pick_shortfall_label_std": 1.0}


def _broken(record, trained, limits=LIMITS):
    result = reference.compare(record, trained, CONFIG, np.random.default_rng(3))
    return sorted(name for name, value in result["numbers"].items() if not checks.judge(value, limits[name]))


def test_a_sound_batch_keeps_every_limit():
    assert _broken(*_study(1)) == []


def test_bfloat16_matmuls_break_the_pick_errors():
    record, trained = _study(2)
    x, y = record["rows"], reference.warp_labels(record["labels"], CONFIG["goal"])
    mean, stddev = reference.posterior_bf16_matmul(x, y, record["picks"], *HYPER.values())
    record["meta"]["mean"], record["meta"]["stddev"] = mean, stddev
    broken = _broken(record, trained)
    assert "pick_stddev_err_label_std" in broken or "pick_mean_err_label_std" in broken


def test_picks_made_at_random_break_the_shortfalls():
    record, trained = _study(3)
    record["picks"] = np.random.default_rng(9).uniform(size=record["picks"].shape)
    assert "first_pick_shortfall_label_std" in _broken(record, trained)


def test_another_studys_answers_break_the_pick_errors():
    record, trained = _study(4)
    other, _ = _study(5)
    record["picks"], record["meta"] = other["picks"], other["meta"]
    broken = _broken(record, trained)
    assert "pick_mean_err_label_std" in broken and "pick_acquisition_err_label_std" in broken


def test_an_acquisition_value_from_a_lower_precision_sweep_is_seen():
    # What the chip's control does: the stamped posterior at the one pick is
    # exact, the value the sweep scored it with is not.
    record, trained = _study(8)
    record["meta"]["acquisition"] = record["meta"]["acquisition"] + 0.05
    assert _broken(record, trained) == ["pick_acquisition_err_label_std"]


def test_a_train_on_other_labels_is_seen():
    record, trained = _study(6)
    trained["y"] = trained["y"][::-1].copy()
    assert "trained_labels_max_abs_diff" in _broken(record, trained)
    trained["completed"] -= 25  # and one that missed the last round's trials
    assert "trained_trials_missing" in _broken(record, trained)


def test_hyperparameters_no_train_would_keep_break_the_likelihood_gain():
    record, trained = _study(7)
    trained.update(amplitude=0.01, noise_stddev=1.0, length_scales=np.full(20, 0.005))
    assert "train_nll_gain_per_trial" in _broken(record, trained)


def test_bfloat16_rounding_is_round_to_nearest_even():
    values = np.array([1.0, 1.00390625, 1.01171875, -3.1415927, 0.0], np.float32)
    assert reference._bf16(values).tolist() == [1.0, 1.0, 1.015625, -3.140625, 0.0]


def test_the_reference_matches_a_direct_solve_on_a_small_case():
    rng = np.random.default_rng(7)
    x, q = rng.uniform(size=(12, 3)), rng.uniform(size=(5, 3))
    y = rng.normal(size=12)
    mean, stddev = reference.posterior(x, y, q, 0.7, 0.1, np.ones(3))
    k = reference.matern52(x, x, 0.7, np.ones(3)) + (0.01 + reference.JITTER) * np.eye(12)
    ks = reference.matern52(q, x, 0.7, np.ones(3))
    np.testing.assert_allclose(mean, ks @ np.linalg.inv(k) @ y, rtol=1e-9)
    cov = 0.49 - np.einsum("ij,jk,ik->i", ks, np.linalg.inv(k), ks)
    np.testing.assert_allclose(stddev, np.sqrt(cov), rtol=1e-9)


def test_pending_picks_condition_the_stddev_like_a_direct_solve():
    rng = np.random.default_rng(11)
    x, points = rng.uniform(size=(15, 3)), rng.uniform(size=(6, 3))
    batch = reference._Batch(x, rng.normal(size=15), points, 0.7, 0.1, np.ones(3), CONFIG["ucb_pe"])
    batch.add_pending(0)
    batch.add_pending(1)
    both = np.concatenate([x, points[:2]])
    _, direct = reference.posterior(both, np.zeros(17), points, 0.7, 0.1, np.ones(3))
    np.testing.assert_allclose(batch.std_all()[2:], direct[2:], rtol=1e-8)


@pytest.mark.parametrize("goal", ["MAXIMIZE", "MINIMIZE"])
def test_the_label_warp_is_the_programs(goal):
    from vizier_tpu.models import output_warpers

    labels = np.random.default_rng(5).normal(size=40)
    signed = labels if goal == "MAXIMIZE" else -labels
    want = output_warpers.create_default_warper()(signed[:, None])[:, 0]
    np.testing.assert_allclose(reference.warp_labels(labels, goal), want, atol=1e-12)
