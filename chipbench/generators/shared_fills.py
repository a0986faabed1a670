"""Many workers fill ONE shared study: upstream's multi-client shape
(``performance_test.py:44-89``), repeated back to back on fresh studies.

Parameters (the traffic file): ``clients`` (worker threads, each with a
``client_id`` of its own), ``trials_per_client``, ``suggest_count`` (1),
``studies`` (all the studies a run may open, set-up's included),
``think_ms``. Every worker loops ``suggest(1)`` → evaluate → ``complete``
``trials_per_client`` times on the current study; a barrier, then the next
fresh (empty) study. A fill that starts sends every worker's first request;
at the window's end no new request is sent, and those in flight are waited
for and recorded.

A study filled from empty crosses the padding buckets, so this generator
keeps none of ``closed_rounds``'s rules and owes another (``check_data``):
the configuration's ``warm_shapes`` name every (trained pad, all-points pad)
a ``suggest(1)`` of the fill can compile for, and ``setup`` drives each one
directly — a study a trained pad, ``c`` completed trials loaded and ACTIVE
ones added until each all-points pad in turn is met, every direct suggest
under a ``client_id`` of its own (the service hands a client that still
holds an ACTIVE trial that trial back and computes nothing) — then whole
fills until one compiles nothing. The cell reports ``compiles_in_window``,
and every run ``requests_available``: the requests the unfilled studies
hold when the window opens, beside the window's count.

Each trial is recorded on the client's clock: request sent, response
received, complete sent, complete acknowledged. From those the generator
counts the guarantee it can see alone (G3: no two trials ACTIVE at the same
time carry the same point; a breach goes into the later request's
``failures``), and the reference (``references/gp_ucb_pe_pending.py``) holds
what the study's designer computed its last answer from against what the
clients had been told by then (G1, G2).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from chipbench.lib import checks
from chipbench.lib import pending as pending_lib
from chipbench.lib import stages
from chipbench.lib import studies as studies_lib

MAX_WARM_FILLS = 3
# The seeding stage (the configuration's ``num_seed_trials``: an empty
# study's first suggestion is the search space's centre) runs no sweep, so
# the study's first trial, and only that one, may lack the sweep's readings.
SEEDED_TRIAL_IDS = (1,)


def study_count(traffic: Dict[str, Any]) -> int:
    """The studies a cell of this traffic opens (every cell: at most 64)."""
    return traffic["studies"]


def shapes_met(clients: int, trials_per_client: int, count: int) -> List[Tuple[int, int]]:
    """Every (trained pad, all-points pad) a ``suggest(count)`` of a fill can
    meet: ``c`` trials completed, ``a`` others ACTIVE — at most one a worker,
    and never more trials than the fill has (``designers/gp_ucb_pe.py``
    ``UCBPEProgram.bucket_key`` pads completed, and completed + ACTIVE + count)."""
    total = clients * trials_per_client
    pad = studies_lib.pad_power_of_two
    return sorted({
        (pad(c), pad(c + a + count))
        for c in range(total) for a in range(min(clients - 1, total - 1 - c) + 1)
    })


def warm_plan(shapes, clients: int, trials_per_client: int, count: int) -> Dict[int, Tuple[int, List[int], List[int]]]:
    """How set-up meets every shape on one study a trained pad: pad →
    (completed trials to load, the completed + ACTIVE + count to reach for
    each all-points pad in turn, the worker each direct suggest asks as).
    The completed trials are the most for which one more still trains in
    the pad — from the 32 pad on that is past the designer's
    ``warm_start_min_trials`` (20), so the train after the first, cold, one
    is a warm one, as in a fill — and every step stays inside what a fill
    can hold (others ACTIVE: at most one a worker). The workers are one a
    direct suggest — each step's, then the one that evaluates a trial, then
    the one that asks after it: a worker that asked before still holds its
    trial and would be handed it back, with no shape met."""
    total = clients * trials_per_client
    pad = studies_lib.pad_power_of_two
    plan: Dict[int, Tuple[int, List[int], List[int]]] = {}
    for trained in sorted({shape[0] for shape in shapes}):
        completed = max(c for c in range(total - 1) if pad(c) == pad(c + 1) == trained)
        steps, reached = [], completed + count
        for _, points in sorted(shape for shape in shapes if shape[0] == trained):
            reached = max(reached, points // 2 + 1 if points > 8 else 0)
            assert pad(reached) == points and reached - completed - count <= clients - 1 and reached < total, (
                f"no fill of {clients} x {trials_per_client} trials meets the shape {(trained, points)} "
                f"from {completed} completed trials")
            steps.append(reached)
            reached += 1  # the suggest's own trial stays ACTIVE
        plan[trained] = (completed, steps, list(range(len(steps) + 2)))
    return plan


def requests_after_setup(config: Dict[str, Any], traffic: Dict[str, Any], warm: int) -> int:
    """The requests a window can be served before the workers run out of
    studies, from the files alone: the studies the traffic opens, less a
    study a trained pad and ``warm`` warm fills (1 to ``MAX_WARM_FILLS``),
    each filled by every worker's ``trials_per_client`` requests."""
    pads = len({tuple(shape)[0] for shape in config["warm_shapes"]})
    return (traffic["studies"] - pads - warm) * traffic["clients"] * traffic["trials_per_client"]


def check_data(config: Dict[str, Any], traffic: Dict[str, Any]) -> None:
    """This generator's rules for a cell's files; an AssertionError says
    which one they break."""
    for key in ("clients", "trials_per_client", "suggest_count"):
        assert config[key] == traffic[key], (
            f"the configuration states {key} {config[key]}, the traffic {traffic[key]}")
    count = traffic["suggest_count"]
    assert count == 1, f"workers of a shared fill ask for one suggestion at a time, not {count}"
    met = shapes_met(traffic["clients"], traffic["trials_per_client"], count)
    warmed = sorted(tuple(shape) for shape in config["warm_shapes"])
    assert met == warmed, (
        f"a fill of {traffic['clients']} x {traffic['trials_per_client']} trials meets the shapes "
        f"{met}; the configuration's warm_shapes, which set-up warms up, are {warmed}")
    assert met[-1][0] < 512, f"a fill reaches the sparse switch at 512 trials: trained pad {met[-1][0]}"
    plan = warm_plan(met, traffic["clients"], traffic["trials_per_client"], count)
    for trained, (_, steps, workers) in plan.items():
        assert len(set(workers)) == len(workers) == len(steps) + 2 and max(workers) < traffic["clients"], (
            f"the direct suggests of the trained pad {trained} ask as the workers {workers}: each needs an id "
            f"of its own among the fill's {traffic['clients']} (a worker that holds a trial is handed it back)")
    pads = len(plan)
    floor = pads + MAX_WARM_FILLS + 1
    assert traffic["studies"] >= floor, (
        f"the traffic opens {traffic['studies']} studies; set-up alone takes {floor - 1} "
        f"({pads} trained pads and up to {MAX_WARM_FILLS} fills) and the window needs one")


class _Study:
    """One shared study and the clients' own record of each of its trials."""

    def __init__(self, handle, index: int, config: Dict[str, Any], runtime=None):
        self.handle = handle
        self.index = index
        self.runtime = runtime  # the serving runtime, for ``pending_lib.held``
        self.objective = studies_lib.Objective(config)  # upstream's: the same sphere for every study
        self.trials: Dict[int, Dict[str, Any]] = {}  # by trial id
        self.completed_at_last_suggest = 0

    def note(self, trial_id: int, **fields) -> None:
        self.trials[trial_id] = {
            "t_sent": None, "t_received": None, "t_complete_sent": None, "t_acked": None,
            "created": None, "completed": None, "meta": None, "record": None, **fields,
        }

    def last(self) -> Optional[Dict[str, Any]]:
        """The trial of the study's last computation: the highest id (ids
        are given out in the order the suggestions were written)."""
        return self.trials[max(self.trials)] if self.trials else None

    def record_at_last_suggest(self) -> Dict[str, Any]:
        """The clients' record of the study when its last suggest was
        answered, and what the program's designer held for that answer."""
        return {
            "trials": {i: {k: v for k, v in t.items() if k != "record"} for i, t in self.trials.items()},
            "last": max(self.trials), "held": pending_lib.held(self.runtime, self.handle),
        }


class Generator:
    def __init__(self, server, config: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int, annotate: Callable[[str], Any]):
        check_data(config, traffic)  # the sizes as run: a rehearsal's too
        self.server = server
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.annotate = annotate
        self.clients = int(traffic["clients"])
        self.per_client = int(traffic["trials_per_client"])
        self.count = int(traffic["suggest_count"])
        self.names = studies_lib.param_names(config)
        self.studies: List[_Study] = []
        self.records: List[Dict[str, Any]] = []  # one per window suggest
        self.exhausted: List[int] = []  # clients that ran out of studies
        self._unfilled: List[_Study] = []
        self._lock = threading.Lock()

    # -- set-up --------------------------------------------------------------

    def _open(self, study_config, label: str) -> _Study:
        index = len(self.studies)
        handle = self.server.open_study(study_config, "shared", f"seed{self.seed}-{label}{index}")
        study = _Study(handle, index, self.config, self.server.runtime)
        self.studies.append(study)
        return study

    def setup(self, compiles_so_far: Callable[[], int]) -> Dict[str, Any]:
        t0 = time.perf_counter()
        study_config = studies_lib.study_config(self.config)
        shapes = [tuple(shape) for shape in self.config["warm_shapes"]]
        plan = warm_plan(shapes, self.clients, self.per_client, self.count)
        warm = [self._open(study_config, "pad") for _ in plan]
        while len(self.studies) < study_count(self.traffic):
            self._unfilled.append(self._open(study_config, "fill"))
        opened = time.perf_counter()

        # Every shape directly, a study a trained pad: its completed trials
        # loaded, then for each all-points pad in turn ACTIVE trials added
        # up to it and a suggest whose trial stays out (the first one trains,
        # cold; the others find the fit cached and only sweep). Then one
        # trial is evaluated, and a last suggest trains again: warm from 20
        # completed trials on, as in a fill. Each asks as a worker that
        # holds no trial, so each is computed and none handed back.
        rng = np.random.default_rng([self.seed, 3])
        for study, (completed, steps, workers) in zip(warm, plan.values()):
            self._load(study, completed, 0)
            for reached, worker in zip(steps, workers):
                self._load(study, 0, reached - self.count - len(study.trials))
                self._direct(study, worker, rng, evaluate=False)
            self._direct(study, workers[-2], rng)
            self._direct(study, workers[-1], rng, evaluate=False)
        shaped = time.perf_counter()
        # Then whole fills, as the window runs them, until one compiles nothing.
        warm_fills = 0
        while warm_fills < MAX_WARM_FILLS:
            compiles = compiles_so_far()
            self._fills(None, 1)
            warm_fills += 1
            if compiles_so_far() == compiles:
                break
        return {
            "studies": len(self.studies), "open_s": opened - t0, "warm_shapes_s": shaped - opened,
            "warm_fills_s": time.perf_counter() - shaped, "warm_shapes": len(shapes), "warm_fills": warm_fills,
        }

    def _direct(self, study: _Study, worker: int, rng: np.random.Generator, evaluate: bool = True) -> None:
        """One of set-up's direct suggests, which has to be computed: a
        trial the clients' record already has was handed back."""
        known = len(study.trials)
        self._one_trial(study, worker, rng, evaluate=evaluate)
        if len(study.trials) != known + self.count:
            raise RuntimeError(
                f"set-up's suggest as client-{worker} on study {study.index} was handed back a trial "
                f"it already held: no shape was met")

    def requests_available(self) -> int:
        """The requests the unfilled studies hold, as they stand: what the
        workers are given before they are ``exhausted``."""
        return len(self._unfilled) * self.clients * self.per_client

    def _load(self, study: _Study, completed: int, active: int) -> None:
        """``completed`` more seeded trials with their values and ``active``
        more left ACTIVE (held by no worker of the fill), known to the
        clients' record as trials that were there before any clock started."""
        from vizier_tpu import pyvizier as vz

        first = len(study.trials) + 1  # ids follow the order of creation, from 1
        rng = np.random.default_rng([self.seed, 2, study.index, first])
        x = rng.uniform(size=(completed + active, len(self.names)))
        y = study.objective(x, rng)
        trials = []
        for i, row in enumerate(x):
            t = vz.Trial(parameters={name: float(v) for name, v in zip(self.names, row)})
            if i < completed:
                t.complete(vz.Measurement(metrics={"obj": float(y[i])}))
            trials.append(t)
        self.server.load_trials(study.handle, trials)
        ages = float("-inf")
        for i, row in enumerate(x):
            done = i < completed
            study.note(first + i, row=row, value=float(y[i]) if done else None, t_sent=ages, t_received=ages,
                       t_complete_sent=ages if done else None, t_acked=ages if done else None)

    # -- one trial -----------------------------------------------------------

    def _one_trial(self, study: _Study, client: int, rng: np.random.Generator, until: Optional[float] = None,
                   record: Optional[Dict[str, Any]] = None, evaluate: bool = True) -> bool:
        """suggest(1) → check → evaluate → complete. False when the worker
        should stop asking of this study (a failed request, the window's
        end, or ``evaluate`` off: the trial stays out)."""
        t_sent = time.perf_counter()
        if record is not None:
            record.update({"client": client, "study": study.index, "failures": [], "t0": t_sent})
        try:
            with self.annotate("client.suggest"):
                trials = study.handle.suggest(count=self.count, client_id=f"client-{client}")
        except Exception as e:  # a failed request is counted, not fatal
            if record is None:
                raise
            record["t1"] = time.perf_counter()
            record["failures"].append(f"{type(e).__name__}: {e}"[:300])
            return False
        t_received = time.perf_counter()
        rows = [[t.parameters[name] for name in self.names] for t in trials]
        failures = checks.check_batch(rows, [self.server.suggestion_metadata(t) for t in trials], self.count)
        if record is not None:
            record.update({"t1": t_received, "suggestions": len(rows), "failures": failures})
        if failures:
            if record is None:
                raise RuntimeError(f"set-up suggest on study {study.index}: {failures}")
            return False
        trial, row = trials[0], np.asarray(rows[0], np.float64)
        try:
            meta = self.server.pick_metadata(trial)
        except (KeyError, ValueError) as e:
            meta = None
            if trial.id not in SEEDED_TRIAL_IDS:
                failures.append(f"trial {trial.id} lacks the sweep's own readings: {e!r}"[:300])
        with self._lock:
            study.note(trial.id, row=row, client=client, t_sent=t_sent, t_received=t_received,
                       created=pending_lib.created_at(trial), meta=meta, record=record)
        if failures or not evaluate or (until is not None and t_received > until):
            return False  # (the window is over: nothing more is evaluated)
        value = float(study.objective(row, rng)[0])
        mine = study.trials[trial.id]
        mine["value"], mine["t_complete_sent"] = value, time.perf_counter()
        with self.annotate("client.complete"):
            mine["completed"] = pending_lib.complete(trial, value)
        mine["t_acked"] = time.perf_counter()
        return True

    # -- fills ---------------------------------------------------------------

    def _fills(self, until: Optional[float], limit: Optional[int]) -> None:
        """Every worker fills study after study, a barrier before each, until
        ``until`` on the clock or ``limit`` fills. Only a window's requests
        (``until`` given) are recorded."""
        think = float(self.traffic.get("think_ms", 0)) / 1000.0
        errors: List[BaseException] = []
        state: Dict[str, Any] = {"study": None, "fills": 0}

        def next_study() -> None:  # one thread, while the others wait at the barrier
            state["study"] = None
            if state["fills"] == limit or (until is not None and time.perf_counter() >= until):
                return
            if not self._unfilled:
                self.exhausted.extend(range(self.clients))
                return
            state["study"], state["fills"] = self._unfilled.pop(0), state["fills"] + 1

        barrier = threading.Barrier(self.clients, action=next_study)

        def worker(c: int) -> None:
            rng = np.random.default_rng([self.seed, 3, c])  # the evaluation's noise, where there is any
            try:
                while True:
                    barrier.wait()
                    study = state["study"]
                    if study is None:
                        return
                    for k in range(self.per_client):
                        # A fill that starts sends every worker's first request.
                        if k and until is not None and time.perf_counter() >= until:
                            break
                        record = {} if until is not None else None
                        more = self._one_trial(study, c, rng, until, record)
                        if record is not None:
                            with self._lock:
                                self.records.append(record)
                        if not more:
                            break
                        if think:
                            with self.annotate(stages.THINK):
                                time.sleep(think)
            except threading.BrokenBarrierError:
                return
            except BaseException as e:  # re-raised on the caller's thread
                errors.append(e)
                barrier.abort()

        threads = [threading.Thread(target=worker, args=(c,)) for c in range(self.clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]

    # -- the window ----------------------------------------------------------

    def window(self, seconds: float) -> Dict[str, float]:
        """Fills for ``seconds``; a suggest in flight at the end is waited
        for and recorded, so no request is dropped from the tail."""
        t0 = time.perf_counter()
        self._fills(t0 + seconds, None)
        for index in sorted({r["study"] for r in self.records}):
            study = self.studies[index]
            self._same_point_while_active(study)
            last = study.last()
            if last is not None:
                study.completed_at_last_suggest = sum(
                    t["t_acked"] is not None and t["t_acked"] < last["t_sent"] for t in study.trials.values())
        return {"t0": t0, "t1": t0 + seconds}

    @staticmethod
    def _same_point_while_active(study: _Study) -> None:
        """G3 over one study: two trials whose handed-out → acknowledged
        intervals overlap and whose parameters are equal. Each breach goes
        into the ``failures`` of the request that was answered later."""
        by_point: Dict[Tuple[float, ...], List[Tuple[int, Dict[str, Any]]]] = {}
        for trial_id, t in sorted(study.trials.items(), key=lambda item: item[1]["t_received"]):
            earlier = by_point.setdefault(tuple(t["row"]), [])
            for other_id, other in earlier:
                if other["t_acked"] is None or t["t_received"] < other["t_acked"]:
                    if t["record"] is not None:
                        stamped = {k: round(float(v), 6) for k, v in (t.get("meta") or {}).items()}
                        t["record"]["failures"].append(
                            f"G3: trial {trial_id} carries the point of trial {other_id} while both are ACTIVE"
                            f" (at {t['row'].tolist()}, the sweep's readings {stamped})")
                    break
            earlier.append((trial_id, t))
