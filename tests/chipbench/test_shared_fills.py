"""The shared-fill cell (``perftest2d.shared50x5``) from the CPU side: its
generator's rules and shape arithmetic, the G3 count, the pending reference
against the program on studies with ACTIVE trials of other workers, faults
planted in the ids and in the program, the turn's readers, and child runs
at ``--rehearse`` size: sound, and with the study turns taken out again.
"""

from __future__ import annotations

import contextlib
import copy
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _path in (ROOT, os.path.dirname(os.path.abspath(__file__))):
    if _path not in sys.path:
        sys.path.insert(0, _path)

import contract_checks as contract  # noqa: E402  (beside this file)
from chipbench import run  # noqa: E402
from chipbench.lib import checks  # noqa: E402
from chipbench.lib import studies as studies_lib  # noqa: E402
from test_harness import SKIP_CHIP, _run, cache_dir  # noqa: E402,F401  (the child-run helpers)

BENCH = contract.load(ROOT, "BENCHMARK.json")
CELL = next(w for w in BENCH["workloads"] if w["name"] == "perftest2d.shared50x5")
CONFIG, TRAFFIC, fills = contract.cell_files(BENCH, ROOT, CELL)
reference = run.load_module("references", CONFIG["reference"])
SMALL_CONFIG, SMALL_TRAFFIC = run.sized(CONFIG, True), run.sized(TRAFFIC, True)
SHAPES = [tuple(shape) for shape in CONFIG["warm_shapes"]]
STAMPED = ("pick_mean_err_label_std", "pick_stddev_err_label_std", "pick_stddev_all_err_label_std",
           "pick_acquisition_err_label_std", "trained_rows_max_abs_diff", "trained_labels_max_abs_diff")
EXACT = ("acked_completions_missing", "pending_missing", "rows_from_nowhere", "ucb_or_pe_mismatch",
         "trained_trials_missing", "surrogate_mismatch", "noise_under_the_nugget")


# -- the generator's rules -------------------------------------------------------


def test_a_fill_of_fifty_by_five_meets_sixteen_shapes():
    met = fills.shapes_met(50, 5, 1)
    assert met == sorted(SHAPES) and len(met) == 16
    assert met[0] == (8, 8) and met[-1] == (256, 256)
    assert fills.study_count(TRAFFIC) == 60


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_set_up_meets_every_shape_inside_what_a_fill_can_hold(shape):
    pad = studies_lib.pad_power_of_two
    completed, steps, _ = fills.warm_plan(SHAPES, 50, 5, 1)[shape[0]]
    assert pad(completed) == pad(completed + 1) == shape[0]  # one more completed trial trains in the same pad
    assert completed >= 20 or shape[0] < 32  # from the 32 pad on, that second train is a warm one
    reached = [r for r in steps if pad(r) == shape[1]]
    assert len(reached) == 1  # each all-points pad of this trained pad once, and in rising order
    assert 0 <= reached[0] - completed - 1 <= 49 and reached[0] < 250  # others ACTIVE: one a worker
    assert steps == sorted(steps) and len(steps) == sum(s[0] == shape[0] for s in SHAPES)


PLANTED = [
    ("config", "warm_shapes", SHAPES[:-1], r"meets the shapes .*\(256, 256\)\]; the configuration's warm_shapes"),
    ("config", "clients", 49, "the configuration states clients 49, the traffic 50"),
    ("traffic", "studies", 9, "set-up alone takes 9"),
    ("both", "suggest_count", 2, "one suggestion at a time, not 2"),
]


@pytest.mark.parametrize("where,key,value,sentence", PLANTED, ids=[p[1] for p in PLANTED])
def test_a_planted_breach_of_a_shared_fills_rule_fails_with_its_sentence(where, key, value, sentence):
    fills.check_data(CONFIG, TRAFFIC)  # sound as committed
    fills.check_data(SMALL_CONFIG, SMALL_TRAFFIC)  # and at the rehearsal's sizes
    files = {"config": copy.deepcopy(CONFIG), "traffic": copy.deepcopy(TRAFFIC)}
    for name in ("config", "traffic") if where == "both" else (where,):
        files[name][key] = value
    with pytest.raises(AssertionError, match=sentence):
        fills.check_data(files["config"], files["traffic"])


def test_more_studies_than_the_designer_cache_holds_fails_for_either_generator():
    # (test_contract_fixture.py makes this case on the benchmark's last cell,
    # which it takes for a closed_rounds one; here on one that is, and on this.)
    closed = [w for w in BENCH["workloads"] if contract.cell_files(BENCH, ROOT, w)[1]["generator"] == "closed_rounds"]
    for cell, planted in ((closed[-1], {"clients": 13, "studies_per_client": 5}), (CELL, {"studies": 65})):
        _, traffic, generator = contract.cell_files(BENCH, ROOT, cell)
        contract.at_most_64_studies(generator, traffic)
        with pytest.raises(AssertionError, match="opens 65 studies; the designer cache keeps 64"):
            contract.at_most_64_studies(generator, {**traffic, **planted})


# -- G3, counted by the generator ------------------------------------------------


def _trial(row, received, acked, record=None):
    return {"row": np.asarray(row, np.float64), "t_received": received, "t_acked": acked,
            "record": record if record is not None else {"failures": []}}


@pytest.mark.parametrize(
    "trials,breaches",
    [
        # The same point, handed out again while the first is still out.
        ({1: _trial([0.5, 0.5], 1.0, 3.0), 2: _trial([0.5, 0.5], 2.0, 4.0)}, {2: 1}),
        # Never acknowledged: out for good.
        ({1: _trial([0.5, 0.5], 1.0, None), 2: _trial([0.5, 0.5], 9.0, None)}, {2: 1}),
        # The same point again after the first was acknowledged: no breach.
        ({1: _trial([0.5, 0.5], 1.0, 2.0), 2: _trial([0.5, 0.5], 3.0, 4.0)}, {}),
        # Overlapping, different points.
        ({1: _trial([0.5, 0.5], 1.0, 3.0), 2: _trial([0.5, 0.25], 2.0, 4.0)}, {}),
        # One point three times at once: each later one is a breach; ids need not follow the clock.
        ({3: _trial([0.1, 0.2], 1.0, 9.0), 1: _trial([0.1, 0.2], 2.0, 9.0), 2: _trial([0.1, 0.2], 3.0, 9.0)},
         {1: 1, 2: 1}),
    ],
    ids=["overlap", "never_acked", "one_after_the_other", "other_points", "three_at_once"],
)
def test_the_same_point_on_two_active_trials_is_a_failure_of_the_later_request(trials, breaches):
    study = fills._Study(None, 0, CONFIG)
    study.trials = trials
    fills.Generator._same_point_while_active(study)
    found = {i: len(t["record"]["failures"]) for i, t in trials.items() if t["record"]["failures"]}
    assert found == breaches
    for t in trials.values():
        assert all(f.startswith("G3: ") for f in t["record"]["failures"])


# -- the reference's own arithmetic ----------------------------------------------


@pytest.mark.parametrize(
    "completed,pending,expected",
    [([], [5.0], False), ([1.0, 2.0], [], True), ([1.0, 4.0], [2.0, 3.0], True),
     ([1.0, 2.0], [2.0, 3.0], False), ([1.0, None], [None], True)],
    ids=["no_completed", "none_pending", "completion_after_every_creation", "a_creation_is_newer", "no_times"],
)
def test_has_new_completed_is_upstreams_rule(completed, pending, expected):
    assert reference.has_new_completed(completed, pending) is expected


@pytest.mark.parametrize("completed", [0, 12], ids=["no_completed_trial", "twelve_completed"])
def test_pending_rows_condition_the_stddev_like_a_direct_solve(completed):
    rng = np.random.default_rng(11)
    x, y = rng.uniform(size=(completed, 2)), rng.normal(size=completed)
    pending, points = rng.uniform(size=(4, 2)), rng.uniform(size=(6, 2))
    hyper = (0.7, 0.1, np.ones(2))
    posterior = reference.Conditioned(x, y, pending, points, *hyper, CONFIG["ucb_pe"])
    both = np.concatenate([x, pending])
    gram = reference.matern52(both, both, 0.7, np.ones(2)) + (0.01 + reference.JITTER) * np.eye(len(both))
    k_star = reference.matern52(points, both, 0.7, np.ones(2))
    direct = np.sqrt(0.49 - np.einsum("ij,jk,ik->i", k_star, np.linalg.inv(gram), k_star))
    np.testing.assert_allclose(posterior.std_all, direct, rtol=1e-8)
    if completed:
        gram = reference.matern52(x, x, 0.7, np.ones(2)) + (0.01 + reference.JITTER) * np.eye(completed)
        np.testing.assert_allclose(
            posterior.mean, reference.matern52(points, x, 0.7, np.ones(2)) @ np.linalg.inv(gram) @ y, rtol=1e-8)
    else:
        np.testing.assert_allclose(posterior.mean, 0.0)
        np.testing.assert_allclose(posterior.std, 0.7)
    assert np.all(posterior.std_all <= posterior.std + 1e-12)  # pending rows only deflate
    assert np.isfinite(posterior.scores(True)).all() and np.isfinite(posterior.scores(False)).all()


@pytest.mark.parametrize("goal", ["MAXIMIZE", "MINIMIZE"])
def test_the_copied_label_warp_is_the_programs(goal):
    from vizier_tpu.models import output_warpers

    labels = np.random.default_rng(5).normal(size=40)
    signed = labels if goal == "MAXIMIZE" else -labels
    want = output_warpers.create_default_warper()(signed[:, None])[:, 0]
    np.testing.assert_allclose(reference.warp_labels(labels, goal), want, atol=1e-12)
    assert reference.warp_labels(labels[:1], goal).tolist() == [0.0] and len(reference.warp_labels([], goal)) == 0


# -- the program against the reference, other workers' trials pending ------------


@pytest.fixture(scope="module")
def served():
    """A ``program.Server`` in this process and a generator at the
    rehearsal's sizes, driven study by study (no window)."""
    from chipbench.lib import program

    server = program.Server()
    generator = fills.Generator(server, SMALL_CONFIG, SMALL_TRAFFIC, 2147483659, lambda name: contextlib.nullcontext())
    try:
        yield server, generator
    finally:
        server.stop()


def _suggest_on(served, completed, active):
    """One suggest on a fresh study that holds ``completed`` trials and
    ``active`` ACTIVE ones of other workers: (clients' record, trained)."""
    server, generator = served
    study = generator._open(studies_lib.study_config(SMALL_CONFIG), "test")
    generator._load(study, completed, active)
    for i, known in study.trials.items():  # loaded trials: the server's times, read back
        loaded = study.handle.get_trial(i).materialize()
        known["created"] = loaded.creation_time.timestamp()
        known["completed"] = loaded.completion_time.timestamp() if loaded.completion_time else None
    generator._one_trial(study, 0, np.random.default_rng(3))
    return study.record_at_last_suggest(), server.trained(study.handle)


def _numbers(record, trained):
    return reference.compare(record, trained, SMALL_CONFIG, np.random.default_rng(3))


@pytest.mark.parametrize("completed,active", [(0, 1), (1, 2), (5, 0), (9, 3)],
                         ids=["none_completed", "one_completed", "none_pending", "nine_and_three"])
def test_every_stamped_number_agrees_with_the_pending_reference(served, completed, active):
    record, trained = _suggest_on(served, completed, active)
    result = _numbers(record, trained)
    assert result["seen"]["trials"] == completed and result["seen"]["pending"] == active
    assert record["held"]["pending"] == list(range(completed + 1, completed + active + 1))
    for name in STAMPED:  # the program's float32 on the CPU against float64: 1e-5 at most
        assert result["numbers"][name] <= 2e-5, (name, result["numbers"][name])
    for name in EXACT:
        assert result["numbers"][name] == 0, (name, result["numbers"])
    assert "train_nll_gain_per_trial" not in result["numbers"]  # too few trials to weigh a train
    broken = [n for n, v in result["numbers"].items() if not checks.judge(v, SMALL_CONFIG["limits"][n])]
    assert broken == []


def test_active_trials_withheld_from_the_designer_are_missed(served, monkeypatch):
    from vizier_tpu.designers import gp_ucb_pe

    update = gp_ucb_pe.VizierGPUCBPEBandit.update
    monkeypatch.setattr(gp_ucb_pe.VizierGPUCBPEBandit, "update",
                        lambda self, completed, all_active=None: update(self, completed))
    record, trained = _suggest_on(served, 9, 3)
    assert _numbers(record, trained)["numbers"]["pending_missing"] == 3


@pytest.fixture(scope="module")
def nine_and_three(served):
    return _suggest_on(served, 9, 3)


def test_ids_without_the_conditioning_break_the_conditioned_stddev(nine_and_three):
    # The ids are right, the rows are not what the sweep conditioned on.
    record, trained = copy.deepcopy(nine_and_three)
    for i in record["held"]["pending"]:
        record["trials"][i]["row"] = 1.0 - record["trials"][i]["row"]
    numbers = _numbers(record, trained)["numbers"]
    # Held to the CELL's limit, as ``run.py check_run`` judges it: the
    # planted fault must not pass the comparison that decides ``correct``.
    name = "pick_stddev_all_err_label_std"
    print("planted", name, numbers[name], "against the cell's limit", CONFIG["limits"][name])
    assert not checks.judge(numbers[name], CONFIG["limits"][name]), numbers[name]
    assert numbers["pending_missing"] == 0


PLANTED_MODEL = [
    # (what the reference is given that the program did not compute with, the number that must leave its limit)
    ("two_values_swapped", "pick_mean_err_label_std"),
    ("amplitude_two_percent_off", "pick_stddev_err_label_std"),
]


@pytest.mark.parametrize("fault,name", PLANTED_MODEL, ids=[p[0] for p in PLANTED_MODEL])
def test_a_planted_fault_in_the_model_leaves_the_cells_limit(nine_and_three, fault, name):
    """The upper readings of the limits no control on the chip moves: a
    solve on other labels, a factor of another scale."""
    record, trained = copy.deepcopy(nine_and_three)
    sound = _numbers(record, trained)["numbers"][name]
    if fault == "two_values_swapped":
        trials = record["trials"]
        trials[1]["value"], trials[2]["value"] = trials[2]["value"], trials[1]["value"]
        trained["y"][[0, 1]] = trained["y"][[1, 0]]  # (so that only the solve differs, not the label check)
    else:
        trained["amplitude"] *= 1.02
    planted = _numbers(record, trained)["numbers"][name]
    print("planted", fault, name, planted, "sound", sound, "the cell's limit", CONFIG["limits"][name])
    assert checks.judge(sound, CONFIG["limits"][name]) and not checks.judge(planted, CONFIG["limits"][name])


PLANTED_IDS = [
    # (what is done to the designer's ids or the clients' clocks, the number that must count it)
    ("completed_dropped", "acked_completions_missing"),
    ("pending_dropped", "pending_missing"),
    ("completed_never_sent", "rows_from_nowhere"),
    ("pending_asked_later", "rows_from_nowhere"),
    ("unknown_id", "rows_from_nowhere"),
    ("other_draw_input", "ucb_or_pe_mismatch"),
    ("noise_without_the_nugget", "noise_under_the_nugget"),
]


@pytest.mark.parametrize("fault,number", PLANTED_IDS, ids=[p[0] for p in PLANTED_IDS])
def test_a_planted_fault_in_the_ids_is_counted(nine_and_three, fault, number):
    record, trained = copy.deepcopy(nine_and_three)
    held, trials, last = record["held"], record["trials"], record["trials"][record["last"]]
    assert _numbers(record, trained)["numbers"][number] == 0
    if fault == "completed_dropped":
        held["incorporated"].remove(held["completed"].pop())
    elif fault == "pending_dropped":
        held["pending"].pop()
    elif fault == "completed_never_sent":
        trials[held["completed"][0]]["t_complete_sent"] = None
    elif fault == "pending_asked_later":
        trials[held["pending"][0]]["t_sent"] = last["t_received"] + 1.0
    elif fault == "unknown_id":
        held["pending"].append(999)
    elif fault == "other_draw_input":
        held["first_has_new"] = not held["first_has_new"]
    elif fault == "noise_without_the_nugget":  # what a noise-free fit reported before the model had one
        trained["noise_stddev"] = 0.001
    assert _numbers(record, trained)["numbers"][number] >= 1


# -- the turn's readers ----------------------------------------------------------

BOUNDS = [0.001, 0.01, 0.1, 1.0, 10.0]


def _hist(samples):
    counts = [0] * (len(BOUNDS) + 1)
    for value in samples:
        counts[next((i for i, b in enumerate(BOUNDS) if value <= b), len(BOUNDS))] += 1
    return {"bounds": BOUNDS, "series": {"": (counts, len(samples), float(sum(samples)))}}


def _turn_evidence():
    stage = {"bounds": BOUNDS, "series": {
        "path=sequential,per=request,stage=service.read": ([0, 4, 0, 0, 0, 0], 4, 0.02),
        "path=sequential,per=request,stage=device.wait": ([0, 0, 4, 0, 0, 0], 4, 0.38)}}
    latency = {"bounds": BOUNDS, "series": {"hop=service": ([0, 0, 0, 2, 2, 0], 4, 8.0)}}
    return {
        "histograms_window": {
            "vizier_study_turn_wait_seconds": _hist([0.0005, 2.0, 2.4, 3.0]),
            "vizier_study_turn_seconds": _hist([0.1, 0.1, 0.1, 0.1]),
            "vizier_suggest_stage_seconds": stage, "vizier_suggest_latency_seconds": latency,
        },
        "stats_window": {"suggest_turns": 4, "pending_trials_conditioned": 6}, "latencies_ms": [],
    }


@pytest.mark.parametrize(
    "metric,expected",
    [("turn_ms", 100.0), ("pending_per_suggest", 1.5),
     ("turn_coverage", 100.0 * (0.02 + 0.38 + 0.0005 + 2.0 + 2.4 + 3.0) / 8.0),
     ("turn_wait_ms", 1e3 * (1.0 + 9.0 * 1.0 / 3.0))],  # rank 2 of 4: the first of three in (1, 10]
)
def test_a_turn_reader_gives_a_value_where_the_program_has_turns(metric, expected):
    assert run.load_reader(metric).read(_turn_evidence()) == pytest.approx(expected, rel=1e-9)


@pytest.mark.parametrize("metric", ["turn_ms", "turn_wait_ms", "pending_per_suggest", "turn_coverage"])
def test_a_turn_reader_gives_nothing_from_a_program_without_turns(metric):
    evidence = _turn_evidence()  # a parent commit: stages and latencies, no turn
    for name in ("vizier_study_turn_wait_seconds", "vizier_study_turn_seconds"):
        del evidence["histograms_window"][name]
    evidence["stats_window"] = {"warm_trains": 3}
    assert run.load_reader(metric).read(evidence) is None


def test_a_whole_window_that_conditioned_on_nothing_reads_zero_not_nothing():
    evidence = {"stats_window": {"suggest_turns": 250, "pending_trials_conditioned": 0}}
    assert run.load_reader("pending_per_suggest").read(evidence) == 0.0


# -- child runs at rehearse size ---------------------------------------------------

REHEARSE = ["--workload", "perftest2d.shared50x5", "--seed", "2147483659", "--seconds", "3", "--rehearse"]
# The parent's behaviour planted back: no turns, so requests of one study at
# one frontier meet in the coalescer and share one answer.
NO_TURNS = SKIP_CHIP + (
    "; from vizier_tpu.serving import study_turns as st"
    "; st.StudyTurn.__enter__ = lambda self: self; st.StudyTurn.__exit__ = lambda self, *exc: False"
)


def test_with_the_chip_check_skipped_a_sound_traced_rehearsal_is_correct(cache_dir):  # noqa: F811
    done, objs = _run(REHEARSE + ["--trace", "1"], cache_dir, SKIP_CHIP)
    result = objs[-1]
    assert done.returncode == 0, done.stderr[-2000:]
    assert result["correct"] is True and result["attempted"] > 0 and result["failed"] == 0
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    assert {"turn_wait_ms", "turn_ms", "pending_per_suggest", "turn_coverage", "compiles_in_window.shared",
            "host_store_ms.shared", "device_wait_ms.shared", "trial_reuse_share.shared"} <= set(result["metrics"])
    for name, metric in result["metrics"].items():
        assert metric["unit"] == per_layer[name]["unit"] and contract.reports(per_layer[name], CELL["name"])
    assert result["metrics"]["compiles_in_window.shared"]["value"] == 0  # every shape met was warmed up
    window = [o for o in objs if o.get("phase") == "window"][0]["stats_window"]
    assert window["suggest_turns"] == result["attempted"] and window.get("coalesced_requests", 0) == 0
    fitted = [o for o in objs if o.get("phase") == "fitted"][0]["studies"]
    assert fitted and all(s["trials"] + s["pending"] >= 1 for s in fitted)


def test_with_the_turns_taken_out_the_run_is_not_correct_on_g3(cache_dir):  # noqa: F811
    done, objs = _run(REHEARSE + ["--trace", "0"], cache_dir, NO_TURNS)
    result = objs[-1]
    assert done.returncode != 0 and result["correct"] is False and result["failed"] > 0
    window = [o for o in objs if o.get("phase") == "window"][0]
    assert any(f.startswith("G3: ") for f in window["failures"]), window["failures"]
    assert window["stats_window"].get("coalesced_requests", 0) > 0
    assert result["compared"]["failed_requests"][0] > 0
