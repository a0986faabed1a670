"""No check and no cell depends on how fast the tree is (PR 33).

Three places where the tree's pace used to decide ``correct`` or a metric,
each held here from the CPU side: the supply of requests a cell's studies
hold (``requests_available``, ``requests_after_setup``, the reader
``supply_used_share``) against what the clients are really given before one
runs out; a PE pick judged under every threshold float32 could have chosen
(a planted near-tie in both references: the flipped threshold passes, a
wrong penalty and a threshold from outside the near-tied set do not); and
``shared_fills``' direct warm-up, every suggest under a worker's id of its
own, so that all of the cell's shapes are met before the first fill.
"""

from __future__ import annotations

import contextlib
import copy
import os
import sys
import threading
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
from chipbench.lib import stages  # noqa: E402
from chipbench.lib import studies as studies_lib  # noqa: E402

BENCH = contract.load(ROOT, "BENCHMARK.json")
CELLS = {w["name"]: w for w in BENCH["workloads"]}
SUPPLY_METRICS = [m for m in BENCH["per_layer"] if m["name"].startswith("supply_used_share.")]


def _files(cell_name):
    return contract.cell_files(BENCH, ROOT, CELLS[cell_name])


def _cells_of(generator_name):
    """The benchmark's cells whose traffic names this generator: a case
    that belongs to one generator is parametrised over its cells only."""
    return [name for name in CELLS if _files(name)[1]["generator"] == generator_name]


# -- a server that answers at once: the generators' own bookkeeping ---------------


class _NoMetadata:
    def ns(self, name):
        return self

    def get(self, key, default=None):
        return default


class _StubStudy:
    """A study handle that hands out a fresh random trial a suggestion."""

    def __init__(self, server, names, seed):
        self.server, self.names = server, names
        self.rng = np.random.default_rng(seed)
        self.created = 0
        self.lock = threading.Lock()

    def suggest(self, count, client_id):
        with self.lock:
            self.server.suggests += 1
            out = []
            for _ in range(count):
                self.created += 1
                row = [float(v) for v in self.rng.uniform(size=len(self.names))]
                out.append(types.SimpleNamespace(
                    id=self.created, parameters=dict(zip(self.names, row)), _client=self,
                    _snapshot=types.SimpleNamespace(creation_time=None, metadata=_NoMetadata()),
                    complete=lambda measurement: None))
            return out

    def complete_trial(self, trial_id, measurement):
        return types.SimpleNamespace(completion_time=None)


class _StubServer:
    runtime = None

    def __init__(self, config):
        self.names = studies_lib.param_names(config)
        self.suggests = 0
        self.opened = 0

    def open_study(self, study_config, owner, study_id):
        self.opened += 1
        return _StubStudy(self, self.names, self.opened)

    def load_trials(self, handle, trials):
        handle.created += len(trials)

    @staticmethod
    def suggestion_metadata(trial):
        return trial._snapshot.metadata

    @staticmethod
    def pick_metadata(trial):
        return {"acquisition": 0.0, "use_ucb": 0.0, "mean": 0.0, "stddev": 1.0, "stddev_from_all": 1.0}

    def stats(self):
        return {"batched_suggests": self.suggests}  # (rises with every suggest: set-up's fused rounds end)


def _generator(cell_name, think_ms=0, annotate=None):
    config, traffic, module = _files(cell_name)
    traffic = {**traffic, "think_ms": think_ms}
    annotate = annotate or (lambda name: contextlib.nullcontext())
    return module, config, traffic, module.Generator(_StubServer(config), config, traffic, 2147483659, annotate)


@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_requests_available_is_what_the_clients_are_given_before_one_runs_out(cell_name):
    module, config, traffic, generator = _generator(cell_name)
    report = generator.setup(lambda: 0)  # nothing compiles: one warm round, one warm fill
    available = generator.requests_available()
    warm = report.get("warm_rounds", report.get("warm_fills"))
    assert available == module.requests_after_setup(config, traffic, warm)  # the files alone say the same
    generator.window(600.0)  # every client goes on until it has no study left
    assert generator.exhausted and not [r for r in generator.records if r["failures"]]
    assert len(generator.records) == available
    assert generator.requests_available() == 0


def test_lone25_has_room_for_a_suggest_five_times_faster():
    config, traffic, module = _files("default20d.lone25")  # as committed: with its think time
    assert traffic["think_ms"] == 150 and "150 ms think time" in CELLS["default20d.lone25"]["why"]
    supply = [module.requests_after_setup(config, traffic, warm) for warm in (1, 2)]
    assert supply == [214, 213]
    # At the cycle the cell has on the chip (a 234 ms suggest, 150 ms of
    # think time, ~50 ms of completes) a window sends 115 requests.
    sent = BENCH["run_seconds"] / 0.434
    assert 100.0 * sent / min(supply) <= 60.0
    # And the supply lasts until a cycle is 235 ms: think time, the worker's
    # 25 completes and a suggest of ~35 ms.
    assert 1e3 * BENCH["run_seconds"] / min(supply) - traffic["think_ms"] < 90.0


@pytest.mark.parametrize("metric", SUPPLY_METRICS, ids=lambda m: m["name"])
def test_supply_used_share_reads_a_planted_window_line(metric):
    assert len(SUPPLY_METRICS) == 3 and len(metric["workloads"]) == 1
    assert (metric["unit"], metric["better"], metric["source"], metric["layer"]) == (
        "%", "lower", "program_counter", "client")
    read = run.load_reader(metric["name"]).read
    assert read({"attempted": 115, "requests_available": 214}) == pytest.approx(100.0 * 115 / 214)
    assert read({"attempted": 214, "requests_available": 214}) == 100.0  # a client has run out
    assert read({"attempted": 0, "requests_available": 214}) == 0.0
    assert read({"attempted": 5}) is None and read({"attempted": 5, "requests_available": 0}) is None


@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_a_worker_thinks_inside_the_span_the_idle_gaps_are_named_after(cell_name):
    spans, lock = [], threading.Lock()

    @contextlib.contextmanager
    def annotate(name):
        with lock:
            spans.append(name)
        yield

    _, _, _, generator = _generator(cell_name, think_ms=0.01, annotate=annotate)
    generator.setup(lambda: 0)
    spans.clear()
    generator.window(0.3)
    assert stages.THINK in stages.GAP_ANNOTATIONS
    # One pause a round (a trial) evaluated, and at most one more a worker:
    # after a request the window's end answered, whose trials stay out.
    evaluated, asked = spans.count("client.complete"), spans.count("client.suggest")
    assert 0 < evaluated <= spans.count(stages.THINK) <= asked


@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_a_traffic_file_may_shorten_the_traced_span_and_never_lengthens_it(cell_name):
    # A traced run has to end inside the run's time limit: a cell whose second
    # holds more device events than the profiler collects in time traces less.
    _, traffic, _ = _files(cell_name)
    assert 0.1 <= traffic.get("trace_seconds", run.TRACE_SECONDS) <= run.TRACE_SECONDS == 1.0
    if cell_name == "perftest2d.shared50x5":
        assert traffic["trace_seconds"] == 0.5


# -- shared_fills: every direct suggest under a worker's id of its own --------------

SHARED = _cells_of("shared_fills")


@pytest.mark.parametrize("cell_name", SHARED)
@pytest.mark.parametrize("rehearse", [False, True], ids=["full", "rehearse"])
def test_warm_plans_direct_suggests_ask_as_distinct_workers(cell_name, rehearse):
    config, traffic, fills = _files(cell_name)
    config, traffic = run.sized(config, rehearse), run.sized(traffic, rehearse)
    shapes = [tuple(shape) for shape in config["warm_shapes"]]
    plan = fills.warm_plan(shapes, traffic["clients"], traffic["trials_per_client"], traffic["suggest_count"])
    for trained, (_, steps, workers) in plan.items():
        assert len(workers) == len(steps) + 2 == len(set(workers))  # each step, the evaluation, the suggest after it
        assert all(0 <= w < traffic["clients"] for w in workers)
    fills.check_data(config, traffic)


@pytest.mark.parametrize("cell_name", SHARED)
def test_a_plan_that_asks_twice_as_one_worker_breaks_the_generators_rule(cell_name, monkeypatch):
    config, traffic, fills = _files(cell_name)
    plan = fills.warm_plan

    def as_one_worker(*args):
        return {pad: (completed, steps, [0] * (len(steps) + 2)) for pad, (completed, steps, _) in plan(*args).items()}

    monkeypatch.setattr(fills, "warm_plan", as_one_worker)
    with pytest.raises(AssertionError, match="each needs an id of its own"):
        fills.check_data(config, traffic)


@pytest.fixture()
def recording_designer(monkeypatch):
    """The served designer with its device work taken out: a suggest notes
    the shapes it would compile for and answers with random points that
    carry a sweep's readings. The service around it — which trial a client
    is handed, which trials a computation sees — is the real one."""
    from vizier_tpu import pyvizier as vz
    from vizier_tpu.designers import gp_ucb_pe

    met = []
    rng = np.random.default_rng(7)
    pad = studies_lib.pad_power_of_two

    def suggest(self, count=None):
        count = count or 1
        completed, active = len(self._trials), len(self._active_trials)
        met.append((pad(completed), pad(completed + active + count)))
        out = []
        for _ in range(count):
            names = [p.name for p in self.problem.search_space.parameters]
            suggestion = vz.TrialSuggestion(parameters={name: float(rng.uniform()) for name in names})
            ns = suggestion.metadata.ns("gp_ucb_pe")
            ns["acquisition"], ns["use_ucb"] = "0.0", "False"
            for key in ("mean", "stddev", "stddev_from_all"):
                ns.ns("prediction_in_warped_y_space")[key] = "[0.0]"
            out.append(suggestion)
        return out

    monkeypatch.setattr(gp_ucb_pe.VizierGPUCBPEBandit, "suggest", suggest)
    return met


@pytest.mark.parametrize("cell_name", SHARED)
def test_set_up_meets_every_shape_of_the_cell_before_its_first_fill(cell_name, recording_designer, monkeypatch):
    from chipbench.lib import program

    config, traffic, fills = _files(cell_name)  # at full size: 50 x 5, sixteen shapes
    shapes = sorted(tuple(shape) for shape in config["warm_shapes"])
    server = program.Server()
    try:
        generator = fills.Generator(server, config, traffic, 2147483659, lambda name: contextlib.nullcontext())
        monkeypatch.setattr(generator, "_fills", lambda until, limit: None)  # the direct steps alone
        report = generator.setup(lambda: 0)
        assert report["warm_shapes"] == len(shapes) == 16
        assert sorted(set(recording_designer)) == shapes  # all of them, and no other
        # One computation a direct suggest: none was answered with a trial handed back.
        assert len(recording_designer) == sum(len(steps) + 2 for _, steps, _ in fills.warm_plan(
            shapes, traffic["clients"], traffic["trials_per_client"], traffic["suggest_count"]).values())

        # As it was: every step as client-0, which is handed its own ACTIVE
        # trial back, so five shapes waited for a fill — set-up now stops.
        plan = fills.warm_plan
        monkeypatch.setattr(fills, "warm_plan", lambda *args: {
            pad: (completed, steps, [0] * (len(steps) + 2)) for pad, (completed, steps, _) in plan(*args).items()})
        monkeypatch.setattr(fills, "check_data", lambda config, traffic: None)  # (which refuses such a plan first)
        again = fills.Generator(server, config, traffic, 2147483660, lambda name: contextlib.nullcontext())
        with pytest.raises(RuntimeError, match="was handed back a trial it already held"):
            again.setup(lambda: 0)
    finally:
        server.stop()


# -- a PE pick under its near-tied thresholds ---------------------------------------

DEFAULT20D = contract.load(ROOT, "chipbench", "configs", "default20d.json")
PERFTEST2D = contract.load(ROOT, "chipbench", "configs", "perftest2d.json")
PICK_NUMBERS = ("pick_mean_err_label_std", "pick_stddev_err_label_std", "pick_stddev_all_err_label_std",
                "pick_acquisition_err_label_std")


def _completed_posterior(reference, x, y, points, hyper, ucb_pe):
    """(mean, stddev) of the completed trials' posterior at ``points``, by
    the reference under test."""
    if hasattr(reference, "posterior"):
        return reference.posterior(x, y, points, *hyper)
    at = reference.Conditioned(x, y, np.zeros((0, x.shape[1])), points, *hyper, ucb_pe)
    return at.mean, at.std


class _NearTie:
    """A study whose best trial shares the top of the UCB order with one
    more point — a pending one, placed where its UCB lies a quarter of the
    tolerance under the trial's and its mean well apart — and a PE pick of
    the program's, stamped under the threshold of whichever of the two the
    caller says float32 put first."""

    def __init__(self, config, hyper, n, seed):
        self.config, self.hyper = config, hyper
        self.reference = run.load_module("references", config["reference"])
        ref, ucb_pe = self.reference, config["ucb_pe"]
        rng = np.random.default_rng([seed, n])
        _, self.x, self.labels = studies_lib.seeded_trials(config, rng, n)
        self.y = ref.warp_labels(self.labels, config["goal"])
        self.scale = float(np.std(self.y))
        self.tolerance = ref.near_tie_tolerance(config) * self.scale
        coefficient = ucb_pe["ucb_coefficient"]

        def ucb(points):
            mean, std = _completed_posterior(ref, self.x, self.y, np.atleast_2d(points), hyper, ucb_pe)
            return mean + coefficient * std, mean

        at_trials, mean_trials = ucb(self.x)
        order = np.argsort(at_trials)
        best = int(order[-1])
        self.best_mean, self.outside_mean = float(mean_trials[best]), float(mean_trials[int(order[0])])
        assert at_trials[best] - at_trials[int(order[0])] > 2 * self.tolerance  # the last trial is outside the set
        # From the best trial towards a point whose UCB is lower: the UCB
        # crosses (best - tolerance / 4) on the way; bisect for the crossing.
        target = at_trials[best] - self.tolerance / 4
        far = next(p for p in rng.uniform(size=(500, self.x.shape[1])) if ucb(p)[0][0] < target)
        lo, hi = 0.0, 1.0
        for _ in range(80):
            mid = 0.5 * (lo + hi)
            lo, hi = (mid, hi) if ucb(self.x[best] + mid * (far - self.x[best]))[0][0] > target else (lo, mid)
        self.tied = self.x[best] + lo * (far - self.x[best])
        value, mean = ucb(self.tied)
        self.tied_mean = float(mean[0])
        assert 0 < at_trials[best] - value[0] < self.tolerance  # inside the near-tied set, and not the best
        self.pool = ref.candidates(self.x, self.y, self.tied[None], rng, config["check_candidates"])
        mean, std = _completed_posterior(ref, self.x, self.y, self.pool, hyper, ucb_pe)
        self.explore = mean + ucb_pe["explore_region_ucb_coefficient"] * std

    def pick(self):
        """(index, margin in label stddevs) of the pool point at which every
        fault planted below reads furthest from the score under each
        near-tied threshold: its penalty is active, so the threshold counts.
        Only the penalty term differs between a stamped value and a score."""
        c = self.config["ucb_pe"]["cb_violation_penalty_coefficient"]
        under_best, under_tied = (np.maximum(t - self.explore, 0.0) for t in (self.best_mean, self.tied_mean))
        under_outside = np.maximum(self.outside_mean - self.explore, 0.0)

        def nearest(stamped, scores):  # |stamped - the nearest score|, a point
            return np.min([np.abs(stamped - score) for score in scores], axis=0)

        both = (c * under_best, c * under_tied)
        margin = np.min([
            nearest(c * under_tied, both[:1]),  # the judge as it was: the argmax's threshold alone
            nearest((c - 1.0) * under_best, both), nearest((c - 1.0) * under_tied, both),  # a penalty of 9 for 10
            nearest(c * under_outside, both),  # a threshold from outside the near-tied set
        ], axis=0)
        return int(np.argmax(margin)), float(np.max(margin) / self.scale)


def _batch_case(config, tie, threshold_of, penalty=None):
    """``references/gp_ucb_pe.py``: the tied point is the batch's first
    pick (a UCB pick), the judged PE pick its second."""
    ref, ucb_pe = tie.reference, dict(config["ucb_pe"])
    if penalty is not None:
        ucb_pe["cb_violation_penalty_coefficient"] = penalty
    q, _ = tie.pick()
    picks = np.stack([tie.tied, tie.pool[q]])
    batch = ref._Batch(tie.x, tie.y, picks, *tie.hyper, ucb_pe)
    meta = {"mean": batch.mean.copy(), "stddev": batch.std.copy(), "use_ucb": np.asarray([1.0, 0.0]),
            "stddev_from_all": np.zeros(2), "acquisition": np.zeros(2)}
    meta["stddev_from_all"][0], meta["acquisition"][0] = batch.std_all()[0], batch.scores(True, [])[0]
    batch.add_pending(0)
    meta["stddev_from_all"][1] = batch.std_all()[1]
    meta["acquisition"][1] = batch.scores(False, [0], threshold_of)[1]
    record = {"rows": tie.x, "labels": tie.labels, "picks": picks, "meta": meta}
    trained = {"completed": len(tie.x), "x": tie.x, "y": tie.y, "surrogate_mode": "exact",
               "amplitude": tie.hyper[0], "noise_stddev": tie.hyper[1], "length_scales": tie.hyper[2]}
    return record, trained


def _pending_case(config, tie, threshold_of, penalty=None):
    """``references/gp_ucb_pe_pending.py``: the tied point is another
    worker's pending trial, the judged PE pick the study's last answer."""
    ref, ucb_pe = tie.reference, dict(config["ucb_pe"])
    if penalty is not None:
        ucb_pe["cb_violation_penalty_coefficient"] = penalty
    q, _ = tie.pick()
    at = ref.Conditioned(tie.x, tie.y, tie.tied[None], tie.pool[q][None], *tie.hyper, ucb_pe)
    meta = {"mean": at.mean[0], "stddev": at.std[0], "stddev_from_all": at.std_all[0], "use_ucb": 0.0,
            "acquisition": at.scores(False, threshold_of)[0]}
    n, ages = len(tie.x), float("-inf")
    blank = {"t_complete_sent": None, "t_acked": None, "created": None, "completed": None, "meta": None, "value": None}
    trials = {i + 1: {**blank, "row": tie.x[i], "value": float(tie.labels[i]), "t_sent": ages, "t_received": ages,
                      "t_complete_sent": ages, "t_acked": ages} for i in range(n)}
    trials[n + 1] = {**blank, "row": tie.tied, "t_sent": ages, "t_received": ages}
    trials[n + 2] = {**blank, "row": tie.pool[q], "t_sent": 1.0, "t_received": 2.0, "meta": meta}
    ids = list(range(1, n + 1))
    record = {"trials": trials, "last": n + 2,
              "held": {"completed": ids, "pending": [n + 1], "incorporated": ids, "first_has_new": True}}
    trained = {"completed": n, "x": tie.x, "y": tie.y, "surrogate_mode": "exact",
               "amplitude": tie.hyper[0], "noise_stddev": tie.hyper[1], "length_scales": tie.hyper[2]}
    return record, trained


NEAR_TIES = {
    "gp_ucb_pe": (DEFAULT20D, (0.35, 0.022, np.full(20, 2.0)), 120, _batch_case),
    "gp_ucb_pe_pending": (PERFTEST2D, (0.2, 0.011, np.full(2, 0.12)), 40, _pending_case),
}


@pytest.fixture(scope="module", params=sorted(NEAR_TIES))
def near_tie(request):
    config, hyper, n, case = NEAR_TIES[request.param]
    assert config["reference"] == request.param
    return config, _NearTie(config, hyper, n, seed=11), case


def _judged(config, tie, case, threshold_of, penalty=None, config_for_judge=None):
    record, trained = case(config, tie, threshold_of, penalty)
    result = tie.reference.compare(record, trained, config_for_judge or config, np.random.default_rng(3))
    broken = sorted(name for name in PICK_NUMBERS
                    if not checks.judge(result["numbers"][name], config["limits"][name]))
    return result, broken


def test_the_planted_tie_is_one_float32_could_flip(near_tie):
    config, tie, _ = near_tie
    assert abs(tie.tied_mean - tie.best_mean) >= 0.02 * tie.scale  # the threshold would move by this
    limit = config["limits"]["pick_acquisition_err_label_std"]
    assert tie.pick()[1] > 2 * limit  # every planted fault reads well over the limit at the judged pick


def test_a_pick_stamped_under_the_flipped_threshold_is_no_error(near_tie):
    config, tie, case = near_tie
    for threshold_of in (tie.best_mean, tie.tied_mean):  # float64's argmax, and the other of the two
        result, broken = _judged(config, tie, case, threshold_of)
        assert broken == [] and result["numbers"]["pick_acquisition_err_label_std"] < 1e-9
    seen = result["seen"]  # (the batch reference reports the first pick's count, a UCB pick here, and the most)
    assert seen.get("pe_thresholds_tried_max", seen["pe_thresholds_tried"]) >= 2


def test_without_the_tolerance_the_flipped_threshold_was_an_error(near_tie):
    # The judge as it was (the argmax's threshold alone): what failed 2 of 48 states on the chip.
    config, tie, case = near_tie
    exact = copy.deepcopy(config)
    exact["limits"].update(pick_mean_err_label_std=0.0, pick_stddev_err_label_std=0.0)  # tolerance 0
    _, broken = _judged(config, tie, case, tie.tied_mean, config_for_judge=exact)
    assert "pick_acquisition_err_label_std" in broken


def test_a_penalty_of_nine_for_ten_is_still_an_error(near_tie):
    config, tie, case = near_tie
    assert config["ucb_pe"]["cb_violation_penalty_coefficient"] == 10.0
    for threshold_of in (tie.best_mean, tie.tied_mean):
        _, broken = _judged(config, tie, case, threshold_of, penalty=9.0)
        assert broken == ["pick_acquisition_err_label_std"]


def test_a_threshold_from_a_trial_outside_the_set_is_still_an_error(near_tie):
    config, tie, case = near_tie
    _, broken = _judged(config, tie, case, tie.outside_mean)
    assert broken == ["pick_acquisition_err_label_std"]


def test_the_near_tied_set_is_the_cells_own_limits_twice_over():
    for config in (DEFAULT20D, PERFTEST2D):
        reference = run.load_module("references", config["reference"])
        limits, c = config["limits"], config["ucb_pe"]["ucb_coefficient"]
        assert reference.near_tie_tolerance(config) == pytest.approx(
            2 * (limits["pick_mean_err_label_std"] + c * limits["pick_stddev_err_label_std"]))
    assert run.load_module("references", "gp_ucb_pe").near_tie_tolerance(DEFAULT20D) == pytest.approx(0.0186)


def test_thresholds_come_best_first_distinct_and_from_inside_the_tolerance_only():
    reference = run.load_module("references", "gp_ucb_pe")
    rng = np.random.default_rng(5)
    x = rng.uniform(size=(10, 3))
    batch = reference._Batch(x, rng.normal(size=10), rng.uniform(size=(4, 3)), 0.7, 0.1, np.ones(3), DEFAULT20D["ucb_pe"])
    # Planted: trials 0 and 1 near-tied (UCB 0.004 apart, means 0.05 apart), trial 2 a repeat of 1's mean, 3 outside.
    batch.mean_x, batch.std_x = np.full(10, -1.0), np.full(10, 0.1)
    batch.mean_x[:4] = [0.50, 0.45, 0.45, 0.40]
    batch.std_x[:4] = [0.10, 0.10 + (0.05 - 0.004) / 1.8, 0.10 + (0.05 - 0.006) / 1.8, 0.10 + (0.10 - 0.02) / 1.8]
    assert batch.thresholds() == [0.50] and batch.thresholds(0.003) == [0.50]
    assert batch.thresholds(0.01) == [0.50, 0.45]
    assert batch.thresholds(0.03) == [0.50, 0.45, 0.40]
