"""The fleet cell (``fleet20d.open-burst``) from the CPU side: the open-loop
generator's schedule, population and rules, latency timed from the due time,
its set-up's shapes, the three readers it brings, the pending reference against
the program at 20-D, and child runs at ``--rehearse`` size.
"""

from __future__ import annotations

import contextlib
import copy
import os
import sys
import threading
import time
import types

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
from test_pace import _StubServer, _StubStudy  # noqa: E402  (the server that answers at once)

BENCH = contract.load(ROOT, "BENCHMARK.json")
CELL = next(w for w in BENCH["workloads"] if w["name"] == "fleet20d.open-burst")
CONFIG, TRAFFIC, fleet = contract.cell_files(BENCH, ROOT, CELL)
SMALL_CONFIG, SMALL_TRAFFIC = run.sized(CONFIG, True), run.sized(TRAFFIC, True)
reference = run.load_module("references", CONFIG["reference"])
PADS = [64, 128, 256, 512]
NO_SPANS = lambda name: contextlib.nullcontext()  # noqa: E731


# -- the schedule is a function of the seed ---------------------------------------


def test_the_same_seed_gives_the_same_due_times_and_another_seed_others():
    a, b = fleet.due_times(TRAFFIC, 2147483659, 50.0), fleet.due_times(TRAFFIC, 2147483659, 50.0)
    other = fleet.due_times(TRAFFIC, 2147483660, 50.0)
    assert np.array_equal(a, b) and np.all(np.diff(a) >= 0) and 0.0 <= a[0] and a[-1] < 50.0
    # A seed moves when the requests are due, not how many there are: the window's expectation.
    assert len(other) == len(a) == round(TRAFFIC["rate_per_s"] * 50.0) and not np.array_equal(other, a)


def test_a_burst_second_holds_twice_the_base_count_within_poisson_error():
    seconds, base = 4000.0, fleet.base_rate(TRAFFIC)
    due = fleet.due_times(TRAFFIC, 5, seconds)
    in_burst = (due % TRAFFIC["burst_period_s"]) < TRAFFIC["burst_seconds"]
    burst_s = seconds * TRAFFIC["burst_seconds"] / TRAFFIC["burst_period_s"]
    expected_burst, expected_rest = 2.0 * base * burst_s, base * (seconds - burst_s)
    assert TRAFFIC["burst_factor"] == 2.0 and TRAFFIC["burst_seconds"] == 1 and TRAFFIC["burst_period_s"] == 10
    assert abs(in_burst.sum() - expected_burst) < 4.0 * np.sqrt(expected_burst)
    assert abs((~in_burst).sum() - expected_rest) < 4.0 * np.sqrt(expected_rest)
    assert len(due) == round(TRAFFIC["rate_per_s"] * seconds)  # the mean is 1.1 x the base
    assert TRAFFIC["rate_per_s"] == pytest.approx(1.1 * base)
    # Between bursts the gaps are a Poisson process's: exponential, their deviation their mean.
    quiet = np.diff(due[(due % 10.0 > 1.0)])
    quiet = quiet[quiet < 1.0]  # (not the 400 gaps that span a burst second)
    assert np.mean(quiet) == pytest.approx(1.0 / base, rel=0.05) and np.std(quiet) == pytest.approx(np.mean(quiet), rel=0.08)
    # And a single burst second is not a fixed count: over 400 of them the counts spread as Poisson counts do.
    per_burst = np.bincount((due[in_burst] // 10.0).astype(int), minlength=400)
    assert np.var(per_burst) == pytest.approx(np.mean(per_burst), rel=0.25)


def test_the_population_is_sixty_studies_of_four_sizes_dealt_over_twelve_tenants():
    assert fleet.study_count(TRAFFIC) == 60 and fleet.studies_per_pad(TRAFFIC) == [29, 14, 10, 7]
    studies = fleet.population(TRAFFIC)
    assert studies == fleet.population(TRAFFIC)  # a function of the files
    assert sorted({s["pad"] for s in studies}) == PADS == CONFIG["trial_padding_buckets"]
    for s in studies:
        assert studies_lib.pad_power_of_two(s["initial"]) == s["pad"]
        assert s["pad"] // 2 < s["initial"] <= s["pad"] // 2 + s["pad"] // 6  # the lower third of its bucket
        assert s["initial"] + fleet.capacity(s, CONFIG, TRAFFIC) <= min(s["pad"] - 1, CONFIG["completed_trials"])
    for tenant in range(12):
        held = [s["pad"] for s in studies if s["tenant"] == tenant]
        assert len(held) == 5 and len(set(held)) >= 2


def _stub_generator(seed, traffic=TRAFFIC, config=CONFIG, server=None, annotate=NO_SPANS):
    return fleet.Generator(server or _StubServer(config), config, traffic, seed, annotate)


def test_every_seed_sends_the_same_requests_to_the_same_studies_and_a_third_to_the_hot_tenant():
    fast = {**TRAFFIC, "think_ms": 0, "rate_per_s": 400.0, "knee_per_s": 500.0, "window_seconds": 1}
    sent = []
    for seed in (2147483659, 7):
        generator = _stub_generator(seed, fast)
        generator.setup(lambda: 0)
        generator.window(0.5)
        assert not generator.exhausted and len(generator.records) > 120
        sent.append([(r["client"], r["study"]) for r in generator.records])
    n = min(len(sent[0]), len(sent[1]))
    assert sent[0][:n] == sent[1][:n]
    draws = fleet._Draws(TRAFFIC)
    tenants = [draws.at(k)[0] for k in range(4000)]
    assert tenants.count(0) / 4000 == pytest.approx(TRAFFIC["hot_tenant_share"], abs=0.03) == pytest.approx(0.30, abs=0.03)
    assert set(tenants) == set(range(12))
    assert max(tenants.count(t) for t in range(1, 12)) < 1.35 * min(tenants.count(t) for t in range(1, 12))


# -- latency is timed from the instant a request was due --------------------------


class _SlowStudy(_StubStudy):
    delay = 0.05

    def suggest(self, count, client_id):
        time.sleep(self.delay)
        return super().suggest(count, client_id)


class _SlowServer(_StubServer):
    def open_study(self, study_config, owner, study_id):
        self.opened += 1
        return _SlowStudy(self, self.names, self.opened)


def test_a_request_sent_late_is_timed_from_when_it_was_due(monkeypatch):
    # Two sender threads for 60 requests a second that each take 50 ms: the
    # pool runs behind, and what it runs behind by is in the latency.
    monkeypatch.setattr(fleet, "pool_size", lambda traffic: 2)
    traffic = {**TRAFFIC, "think_ms": 0, "rate_per_s": 60.0, "knee_per_s": 75.0, "window_seconds": 1}
    generator = _stub_generator(11, traffic, server=_SlowServer(CONFIG))
    monkeypatch.setattr(_SlowStudy, "delay", 0.0)
    generator.setup(lambda: 0)
    monkeypatch.setattr(_SlowStudy, "delay", 0.05)
    window = generator.window(1.0)
    records = generator.records
    assert len(records) > 30 and not any(r["failures"] for r in records)
    lags = [r["sent"] - r["t0"] for r in records]
    assert all(lag >= 0.0 for lag in lags) and max(lags) > 0.2
    for r in records:  # due -> answer: the lag and the 50 ms the server took
        assert r["t1"] - r["t0"] >= (r["sent"] - r["t0"]) + 0.05 - 1e-3
        assert window["t0"] <= r["t0"] < window["t1"]
    due = fleet.due_times(traffic, 11, 1.0)
    assert [r["t0"] - window["t0"] for r in records] == pytest.approx(list(due[: len(records)]), abs=1e-9)
    assert records[-1]["t1"] > window["t1"]  # those in flight at the end are waited for


# -- the rules check_data states ---------------------------------------------------

PLANTED = [
    ("traffic", {"rate_per_s": 24.0, "knee_per_s": 30.0}, r"tenant 0's studies hold \d+ requests after set-up; its share of 1.5 windows"),
    ("traffic", {"tenants": 13}, "opens 65 studies; the designer cache keeps 64"),
    ("traffic", {"pads": [64, 128, 256, 1024]}, "are not the configuration's trial_padding_buckets"),
    ("config", {"completed_trials": 600}, "reaches the sparse switch at 512"),
    ("config", {"warm_shapes": [[64, 64], [128, 128], [256, 256]]}, r"meets the shapes .*\(512, 512\)\]; the configuration's warm_shapes"),
    ("traffic", {"rate_per_s": TRAFFIC["rate_per_s"] * 1.1}, "is not 0.8 of knee_per_s"),
    ("traffic", {"suggest_count": 2}, "one suggestion at a time, not 2"),
    ("traffic", {"tenants": 60, "studies_per_tenant": 1}, "holds studies of one bucket only"),
    ("traffic", {"max_requests_per_study": 6}, r"requests after set-up; its share of 1.5 windows"),
]


@pytest.mark.parametrize("where,planted,sentence", PLANTED, ids=[next(iter(p[1])) + str(i) for i, p in enumerate(PLANTED)])
def test_a_planted_breach_of_an_open_poisson_rule_fails_with_its_sentence(where, planted, sentence):
    fleet.check_data(CONFIG, TRAFFIC)  # sound as committed
    fleet.check_data(SMALL_CONFIG, SMALL_TRAFFIC)  # and at the rehearsal's sizes
    files = {"config": copy.deepcopy(CONFIG), "traffic": copy.deepcopy(TRAFFIC)}
    files[where].update(planted)
    with pytest.raises(AssertionError, match=sentence):
        fleet.check_data(files["config"], files["traffic"])


def test_the_supply_lasts_a_window_and_a_half_and_a_stub_window_under_ninety_seconds():
    wanted = 1.5 * TRAFFIC["rate_per_s"] * TRAFFIC["window_seconds"]
    assert TRAFFIC["window_seconds"] == BENCH["run_seconds"] and TRAFFIC["think_ms"] == 2000
    assert TRAFFIC["rate_per_s"] == pytest.approx(0.8 * TRAFFIC["knee_per_s"], rel=0.005)
    supply = [fleet.requests_after_setup(CONFIG, TRAFFIC, warm) for warm in range(1, fleet.MAX_WARM_ROUNDS + 1)]
    assert supply == sorted(supply, reverse=True) and supply[-1] >= wanted
    # test_pace.py drives a window on a stub until a tenant runs out: it lasts supply / rate.
    assert supply[0] / TRAFFIC["rate_per_s"] < 89.5
    # Set-up spends, a warm round: one request on each of a pad's first eight studies, three more on one.
    spent = fleet.spent_in_setup(fleet.population(TRAFFIC), 2)
    assert sum(spent.values()) == 60 + 2 * (8 + 8 + 8 + 7 + 4 * 3) and max(spent.values()) == 1 + 1 + 1 + 3


# -- set-up meets every shape of the cell, and no other, before the window ---------


def test_set_up_and_a_window_meet_the_four_shapes_of_the_cell_and_no_other(monkeypatch):
    from chipbench.lib import program
    from vizier_tpu import pyvizier as vz
    from vizier_tpu.designers import gp_ucb_pe

    met, lock, rng = [], threading.Lock(), np.random.default_rng(7)
    pad = studies_lib.pad_power_of_two

    def suggest(self, count=None):
        """The served designer with its device work taken out (as
        ``test_pace.recording_designer``): the shapes it would compile for."""
        count = count or 1
        with lock:
            met.append((pad(len(self._trials)), pad(len(self._trials) + len(self._active_trials) + count)))
            values = rng.uniform(size=20)
        suggestion = vz.TrialSuggestion(parameters={f"x{d}": float(v) for d, v in enumerate(values)})
        ns = suggestion.metadata.ns("gp_ucb_pe")
        ns["acquisition"], ns["use_ucb"] = "0.0", "False"
        for key in ("mean", "stddev", "stddev_from_all"):
            ns.ns("prediction_in_warped_y_space")[key] = "[0.0]"
        return [suggestion]

    monkeypatch.setattr(gp_ucb_pe.VizierGPUCBPEBandit, "suggest", suggest)
    monkeypatch.setattr(gp_ucb_pe, "_ucb_pe_unbatchable", lambda designer, count: True)  # no device: no fused flush
    shapes = sorted(tuple(shape) for shape in CONFIG["warm_shapes"])
    assert shapes == [(p, p) for p in PADS]
    server = program.Server()
    try:
        traffic = {**TRAFFIC, "think_ms": 100}  # at full size: 60 studies, the file's rate
        generator = fleet.Generator(server, CONFIG, traffic, 2147483659, NO_SPANS)
        report = generator.setup(lambda: 0)
        assert report["warm_shapes"] == 4 and report["warm_rounds"] == fleet.MAX_WARM_ROUNDS  # (nothing fused met)
        assert sorted(set(met)) == shapes  # all of them, and no other
        # One computation a set-up suggest: none was answered with a trial handed back.
        assert len(met) == sum(fleet.spent_in_setup(generator.fleet, report["warm_rounds"]).values())
        available = generator.requests_available()
        assert available == fleet.requests_after_setup(CONFIG, traffic, report["warm_rounds"])
        del met[:]
        generator.window(3.0)
        assert len(generator.records) >= 15 and not any(r["failures"] for r in generator.records)
        assert set(met) <= set(shapes) and len(met) == len(generator.records)
        assert generator.requests_available() == available - len(generator.records)
        stats = server.stats()
        assert stats["pending_trials_conditioned"] > 0  # others' trials were ACTIVE when a study was asked again
    finally:
        server.stop()


# -- the readers this cell brings --------------------------------------------------


def _lag_evidence(lags_ms):
    bounds = fleet.LAG_BUCKETS
    counts = [0] * (len(bounds) + 1)
    for lag in lags_ms:
        counts[next((i for i, b in enumerate(bounds) if lag / 1e3 <= b), len(bounds))] += 1
    series = {"": (counts, len(lags_ms), sum(lags_ms) / 1e3)} if lags_ms else {}
    return {"histograms_window": {fleet.LAG_HISTOGRAM: {"bounds": bounds, "series": series}}, "stats_window": {}}


def test_send_lag_ms_is_the_p95_of_what_the_generator_observed():
    read = run.load_reader("send_lag_ms").read
    # 100 requests: 90 within 0.1-0.2 ms, 10 between 3 and 4 ms: rank 95 is half way through those.
    assert read(_lag_evidence([0.15] * 90 + [3.5] * 10)) == pytest.approx(3.5, rel=1e-9)
    assert read(_lag_evidence([0.15] * 100)) == pytest.approx(0.1 + 0.1 * 0.95, rel=1e-9)
    assert read(_lag_evidence([])) is None  # the histogram is there, nothing was sent
    assert read({"histograms_window": {}, "stats_window": {}}) is None  # a closed-loop generator keeps none


def test_the_generator_observes_each_requests_lag_where_the_reader_finds_it():
    from vizier_tpu.observability import metrics as metrics_lib

    registry = metrics_lib.MetricsRegistry()
    server = _StubServer(CONFIG)
    server.runtime = types.SimpleNamespace(metrics=registry)
    fast = {**TRAFFIC, "think_ms": 0, "rate_per_s": 200.0, "knee_per_s": 250.0, "window_seconds": 1}
    generator = _stub_generator(3, fast, server=server)
    generator.setup(lambda: 0)
    generator.window(0.3)
    (counts, count, total), = registry.get(fleet.LAG_HISTOGRAM).series_data().values()
    assert count == len(generator.records) > 20 and sum(counts) == count
    assert total == pytest.approx(sum(r["sent"] - r["t0"] for r in generator.records), rel=1e-6)


@pytest.mark.parametrize(
    "stats,attempted,expected",
    [({"cached_fit_suggests": 120}, 480, 25.0), ({"cached_fit_suggests": 0}, 480, 0.0),
     ({}, 480, None), ({"cached_fit_suggests": 3}, 0, None)],
    ids=["a_quarter", "none_cached", "a_parent_without_the_counter", "no_request"],
)
def test_cached_fit_share_is_the_share_of_requests_that_trained_nothing(stats, attempted, expected):
    value = run.load_reader("cached_fit_share").read({"stats_window": stats, "attempted": attempted})
    assert value == (expected if expected is None else pytest.approx(expected))


@pytest.mark.parametrize(
    "stats,expected",
    [({"batch_flushes": 400, "lone_handbacks": 370, "lone_flushes": 10}, 95.0),
     ({"batch_flushes": 40, "lone_handbacks": 0, "lone_flushes": 0}, 0.0),
     ({"batch_flushes": 40}, None), ({"batch_flushes": 0, "lone_handbacks": 0, "lone_flushes": 0}, None)],
    ids=["mostly_lone", "never_lone", "a_parent_without_the_counters", "no_flush"],
)
def test_lone_flush_share_counts_hand_backs_and_lone_fused_flushes(stats, expected):
    value = run.load_reader("lone_flush_share").read({"stats_window": stats})
    assert value == (expected if expected is None else pytest.approx(expected))


def test_the_new_entries_report_in_this_cell_alone_and_move_what_it_reports():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name, source, layer, moves in (
            ("send_lag_ms", "host_clock", "client", "suggest_p50_ms.pool"),
            ("cached_fit_share", "program_counter", "serving runtime", "suggest_p50_ms.pool"),
            ("lone_flush_share", "program_counter", "batch executor", "suggestions_per_s")):
        metric = by_name[name]
        assert (metric["source"], metric["layer"], metric["moves"]) == (source, layer, moves)
        assert metric["workloads"] == [CELL["name"]]
    fleet_entries = [m for m in BENCH["per_layer"] if m["name"].endswith(".fleet")]
    assert len(fleet_entries) == 12 and all(m["workloads"] == [CELL["name"]] for m in fleet_entries)
    assert CELL["name"] in by_name["pending_per_suggest"]["workloads"]
    assert BENCH["workloads"][-1] == CELL and CELL["chips"] == 1
    assert TRAFFIC["batched_share_pct"] == {"min": 0} and TRAFFIC["trace_seconds"] == 0.5


# -- the program against the pending reference at 20-D ----------------------------

STAMPED = ("pick_mean_err_label_std", "pick_stddev_err_label_std", "pick_stddev_all_err_label_std",
           "pick_acquisition_err_label_std", "trained_rows_max_abs_diff", "trained_labels_max_abs_diff")
EXACT = ("acked_completions_missing", "pending_missing", "rows_from_nowhere", "ucb_or_pe_mismatch",
         "trained_trials_missing", "surrogate_mismatch", "noise_under_the_nugget")


@pytest.fixture(scope="module")
def served():
    """A ``program.Server`` in this process and a generator at the
    rehearsal's sizes, driven study by study (no set-up, no window)."""
    from chipbench.lib import program

    server = program.Server()
    try:
        yield server, fleet.Generator(server, SMALL_CONFIG, SMALL_TRAFFIC, 2147483659, NO_SPANS)
    finally:
        server.stop()


def _suggest_on(served, completed, active, label):
    """One suggest on a fresh 20-D study that holds ``completed`` trials and
    ``active`` ACTIVE ones of other workers: (study, trained)."""
    from vizier_tpu import pyvizier as vz

    server, generator = served
    handle = server.open_study(studies_lib.study_config(SMALL_CONFIG), "test", f"test-{label}")
    spec = {"index": 0, "tenant": 0, "pad": studies_lib.pad_power_of_two(completed), "initial": completed}
    study = fleet._Study(handle, spec, 99, SMALL_CONFIG, server.runtime)
    rng = np.random.default_rng([11, completed, active])
    x = rng.uniform(size=(completed + active, 20))
    y = study.objective(x, rng)
    trials = [vz.Trial(parameters={name: float(v) for name, v in zip(generator.names, row)}) for row in x]
    for t, value in zip(trials[:completed], y):
        t.complete(vz.Measurement(metrics={"obj": float(value)}))
    server.load_trials(handle, trials)
    ages = float("-inf")  # there before any clock started, as set-up's loaded trials are
    for i, row in enumerate(x):
        done = i < completed
        loaded = handle.get_trial(i + 1).materialize()  # the server's times, read back
        study.note(i + 1, row=row, value=float(y[i]) if done else None, t_sent=ages, t_received=ages,
                   t_complete_sent=ages if done else None, t_acked=ages if done else None,
                   created=loaded.creation_time.timestamp(),
                   completed=loaded.completion_time.timestamp() if loaded.completion_time else None)
    generator._ask(study, f"asks-{label}", None)
    return study, server.trained(handle)


# The cell's limits, but for the shortfall: a rehearsal's sweep makes 300 evaluations of 75,000.
LIMITS = {**CONFIG["limits"], "first_pick_shortfall_label_std": 1e9}


def _numbers(record, trained, config=SMALL_CONFIG):
    return reference.compare(record, trained, config, np.random.default_rng(3))["numbers"]


@pytest.fixture(scope="module")
def a_pad_256_study(served):
    study, trained = _suggest_on(served, 250, 8, "forty")
    return study.record_at_last_suggest(), trained


def test_every_stamped_number_of_a_20d_suggest_agrees_with_the_pending_reference(a_pad_256_study):
    record, trained = a_pad_256_study
    result = reference.compare(record, trained, SMALL_CONFIG, np.random.default_rng(3))
    assert result["seen"]["trials"] == 250 and result["seen"]["pending"] == 8
    assert record["held"]["pending"] == list(range(251, 259)) and trained["x"].shape == (250, 20)
    for name in STAMPED:  # the program's float32 at `highest` on the CPU against float64
        assert result["numbers"][name] <= (1e-3 if "acquisition" in name else 5e-5), (name, result["numbers"][name])
    for name in EXACT:
        assert result["numbers"][name] == 0, (name, result["numbers"])
    assert "train_nll_gain_per_trial" in _numbers(record, trained, {**SMALL_CONFIG, "nll_gain_min_trials": 32})
    broken = [n for n, v in result["numbers"].items() if not checks.judge(v, SMALL_CONFIG["limits"][n])]
    assert broken == []


def test_a_posterior_at_the_default_precision_leaves_a_limit_of_the_cell(a_pad_256_study):
    # The CPU has no lower matmul precision to switch on (test_control.py):
    # the stamped posterior of the pick is recomputed with its matmul
    # operands rounded to bfloat16, which is what control 1 does on the chip.
    plain = run.load_module("references", "gp_ucb_pe")
    record, trained = copy.deepcopy(a_pad_256_study)
    last = record["trials"][record["last"]]
    ids = record["held"]["completed"]
    rows = np.asarray([record["trials"][i]["row"] for i in ids])
    y = reference.warp_labels([record["trials"][i]["value"] for i in ids], CONFIG["goal"])
    mean, stddev = plain.posterior_bf16_matmul(
        rows, y, last["row"][None], trained["amplitude"], trained["noise_stddev"], trained["length_scales"])
    last["meta"] = {**last["meta"], "mean": float(mean[0]), "stddev": float(stddev[0])}
    numbers = _numbers(record, trained)
    broken = [n for n, v in numbers.items() if not checks.judge(v, LIMITS[n])]
    print("bfloat16 operands:", {n: numbers[n] for n in broken}, "against", {n: LIMITS[n] for n in broken})
    assert set(broken) & {"pick_mean_err_label_std", "pick_stddev_err_label_std"}
    assert all(numbers[n] == 0 for n in EXACT)  # the ids are untouched: a limit fails, not each


def test_pending_rows_the_sweep_did_not_condition_on_leave_the_cells_limit(a_pad_256_study):
    record, trained = copy.deepcopy(a_pad_256_study)
    pick = record["trials"][record["last"]]["row"]
    for i in record["held"]["pending"]:  # the clients say the others' trials were out beside the pick
        record["trials"][i]["row"] = np.clip(pick + 0.02, 0.0, 1.0)
    name = "pick_stddev_all_err_label_std"
    assert not checks.judge(_numbers(record, trained)[name], CONFIG["limits"][name])


def test_a_suggest_with_no_completion_since_the_last_fit_is_counted_as_cached(served):
    server, _ = served
    before = server.stats()
    study, _ = _suggest_on(served, 24, 0, "cached")  # trains: the study's first fit
    after_train = server.stats()
    assert after_train["cached_fit_suggests"] == before["cached_fit_suggests"]
    assert after_train["cold_trains"] == before["cold_trains"] + 1
    served[1]._ask(study, "asks-cached-again", None)  # the first trial is still out: nothing new to fit
    after = server.stats()
    assert after["cached_fit_suggests"] == after_train["cached_fit_suggests"] + 1
    assert after["cold_trains"] + after["warm_trains"] == after_train["cold_trains"] + after_train["warm_trains"]
    assert after["pending_trials_conditioned"] == after_train["pending_trials_conditioned"] + 1
    held = study.record_at_last_suggest()["held"]
    assert len(held["pending"]) == 1 and len(held["completed"]) == 24


# -- child runs at rehearse size ---------------------------------------------------

REHEARSE = ["--workload", CELL["name"], "--seed", "2147483659", "--seconds", "3", "--rehearse"]


def test_off_a_tpu_a_rehearsal_of_the_cell_is_not_correct_by_the_platform_alone(cache_dir):  # noqa: F811
    done, objs = _run(REHEARSE + ["--trace", "0"], cache_dir)
    result = objs[-1]
    assert done.returncode != 0 and result["correct"] is False and result["failed"] == 0
    assert set(result["metrics"]) == {"suggest_p50_ms.pool", "suggestions_per_s", "setup_s"}
    compared = [o for o in objs if o.get("phase") == "correct"][0]["compared"]
    assert [c["name"] for c in compared if not c["ok"]] == ["platform"]


def test_with_the_chip_check_skipped_a_sound_traced_rehearsal_is_correct(cache_dir):  # noqa: F811
    done, objs = _run(REHEARSE + ["--trace", "1"], cache_dir, SKIP_CHIP)
    result = objs[-1]
    assert done.returncode == 0, done.stderr[-2000:]
    assert result["correct"] is True and result["attempted"] > 0 and result["failed"] == 0
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    assert {"send_lag_ms", "cached_fit_share", "lone_flush_share", "batched_share.fleet", "batch_occupancy.fleet",
            "compiles_in_window.fleet", "pending_per_suggest", "device_wait_ms.fleet", "host_store_ms.fleet",
            "host_codec_ms.fleet", "client_overhead_ms.fleet", "cache_warm_share.fleet",
            "trial_reuse_share.fleet"} <= set(result["metrics"])
    for name, metric in result["metrics"].items():
        assert metric["unit"] == per_layer[name]["unit"] and contract.reports(per_layer[name], CELL["name"])
    assert result["metrics"]["compiles_in_window.fleet"]["value"] == 0  # every shape met was warmed up
    assert result["metrics"]["cache_warm_share.fleet"]["value"] == 100.0  # set-up made every cold train
    setup = [o for o in objs if o.get("phase") == "setup"][0]
    assert setup["fused_met"] is True and 1 <= setup["warm_rounds"] <= fleet.MAX_WARM_ROUNDS
    arrivals = [o for o in objs if o.get("phase") == "arrivals"][0]
    assert arrivals["sent"] == result["attempted"] == arrivals["due"] and arrivals["exhausted"] == []
    window = [o for o in objs if o.get("phase") == "window"][0]
    flushed = sum(b["flushes"] for b in arrivals["flushes_by_bucket"].values())
    assert flushed == window["stats_window"]["batch_flushes"]  # by bucket label, they add up to the counter
    lone = window["stats_window"].get("lone_handbacks", 0) + window["stats_window"].get("lone_flushes", 0)
    assert lone == sum(b["lone"] for b in arrivals["flushes_by_bucket"].values())
    cached = window["stats_window"].get("cached_fit_suggests", 0)
    assert cached + flushed >= result["attempted"] - window["stats_window"].get("batched_suggests", 0)
    fitted = [o for o in objs if o.get("phase") == "fitted"][0]["studies"]
    assert {studies_lib.pad_power_of_two(s["trials"]) for s in fitted} == set(SMALL_CONFIG["trial_padding_buckets"])
