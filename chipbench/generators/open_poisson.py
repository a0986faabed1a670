"""A fleet of tuning jobs on one server, asking on their own clock: an open
loop over many studies of several sizes.

Parameters (the traffic file). *Population*: ``tenants`` jobs of
``studies_per_tenant`` studies; each study sits in one of ``pads`` (trained-row
padding buckets), their numbers a bounded Zipf of ``zipf_exponent`` over the
pads, smallest first; a study opens with completed trials drawn in the lower
third of its bucket (``population_seed``), and the studies are dealt largest
first, one a tenant in turn, so every tenant holds two buckets or more.
*Requests*: a worker that holds no trial asks ``suggest(1)`` under a
``client_id`` of its own, evaluates for ``think_ms`` (slept inside the span
``client.think``) and completes; until then its trial is ACTIVE, and the next
suggest of the study conditions on it. The request's tenant is tenant 0 with
probability ``hot_tenant_share`` and one of the others uniformly, its study
uniform among that tenant's live ones. *Arrivals*: Poisson, ``rate_per_s`` on
average over a window: a base rate, and ``burst_factor`` times it in the first
``burst_seconds`` of every ``burst_period_s``; the window's count is held at
its expectation (``due_times``); ``knee_per_s`` is the highest
constant rate the program sustained when the cell was sized, and
``rate_per_s`` 0.8 of it. ``window_seconds`` is the window the supply is
sized for, ``max_requests_per_study`` (optional) ends a study early.

**Open loop.** One schedule of due times for the whole window, a function of
``--seed``; one dispatcher thread sleeps until each is due and hands the
request to a thread of a bounded pool (``pool_size``: twice what a burst's
requests and evaluations hold at once), which sends it. A request's latency runs from the
instant it was *due* (``t0``) to its answer: a late send is latency, not
grace. ``sent - due`` of every request goes into the histogram
``chipbench_send_lag_seconds`` of the serving runtime's registry, beside the
program's own (the reader ``send_lag_ms`` finds it in the window's histogram
deltas), and its exact percentiles into this generator's ``"phase":
"arrivals"`` line, with the requests and their median latency by pad and
the executor's flushes, slots and lone flushes by bucket label over the window.

**Every seed sends the same requests to the same studies.** Which tenant and
study the k-th request goes to is drawn from ``population_seed``, not from
``--seed``: the supply can then be told from the files alone
(``requests_after_setup``), and the seed changes the due times, the trials'
values and the evaluation noise.

**Supply.** A study takes requests while its completed and ACTIVE trials and
the one asked for fit its bucket — both pads of every computation are the
study's own (``warm_shapes``: (pad, pad) for each pad) — and its completed
trials stay at or under the configuration's ``completed_trials``. The window
stops sending at the first request whose tenant has no live study, and that
tenant is ``exhausted``: the run is then not correct with nothing wrong in the
program. ``check_data`` proves from the files that this comes no sooner than
1.5 windows at ``rate_per_s``, for every tenant. Set-up spends some of it:
every study's first, cold suggest alone; then warm rounds until one compiles
nothing and every pad has met in a fused flush (``MAX_WARM_ROUNDS`` at most).
A warm round drives, a pad: one study alone (a warm train through the lone
hand-back), the same study again while that trial is out (the cached fit, one
pending row), up to eight studies of the pad at once (the fused flush
program), and the first alone again (a sequential suggest after a fused one
compiles small programs of its own).

The clients' record of each trial, the guarantee the generator counts itself
(G3) and the reference's comparison are ``shared_fills``' and
``references/gp_ucb_pe_pending.py``'s: a study here is a shared study that
starts with completed trials.
"""

from __future__ import annotations

import json
import threading
import time
from concurrent import futures
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from chipbench.generators import shared_fills
from chipbench.lib import checks
from chipbench.lib import pending as pending_lib
from chipbench.lib import reduce
from chipbench.lib import stages
from chipbench.lib import studies as studies_lib

MAX_WARM_ROUNDS = 4
FUSED_MEMBERS = 8  # the executor's batch_max_size: a full flush leaves at once
SUPPLY_WINDOWS = 1.5
SPARSE_SWITCH = 512
LAG_HISTOGRAM = "chipbench_send_lag_seconds"
LAG_BUCKETS = [5e-5, 1e-4, 2e-4, 3e-4, 5e-4, 7.5e-4, 1e-3, 1.5e-3, 2e-3, 3e-3, 4e-3, 5e-3, 7.5e-3,
               0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 1.0, 2.0, 5.0]
OCCUPANCY_HISTOGRAM = "vizier_batch_occupancy"  # one series a bucket label; its first bucket is occupancy 1


# -- the population, from the files alone -------------------------------------------


def study_count(traffic: Dict[str, Any]) -> int:
    """The studies a cell of this traffic opens (every cell: at most 64)."""
    return traffic["tenants"] * traffic["studies_per_tenant"]


def studies_per_pad(traffic: Dict[str, Any]) -> List[int]:
    """Bounded Zipf over the pads, smallest first: the shares 1 / rank ** s of
    the studies, rounded, the rounding's remainder on the smallest pad."""
    weights = np.arange(1, len(traffic["pads"]) + 1, dtype=np.float64) ** -float(traffic["zipf_exponent"])
    counts = np.rint(study_count(traffic) * weights / weights.sum()).astype(int)
    counts[0] += study_count(traffic) - counts.sum()
    return [int(c) for c in counts]


def population(traffic: Dict[str, Any]) -> List[Dict[str, int]]:
    """Every study of the fleet, in the order dealt: its ``index``, ``tenant``,
    ``pad`` and the completed trials it opens with (``initial``: the lower
    third of its bucket, drawn from ``population_seed``)."""
    rng = np.random.default_rng([int(traffic["population_seed"]), 0])
    pads = [pad for pad, n in zip(traffic["pads"], studies_per_pad(traffic)) for _ in range(n)]
    out = []
    for index, pad in enumerate(sorted(pads, reverse=True)):
        initial = pad // 2 + 1 + int(rng.integers(max(1, pad // 6)))
        out.append({"index": index, "tenant": index % traffic["tenants"], "pad": pad, "initial": initial})
    return out


def capacity(study: Dict[str, int], config: Dict[str, Any], traffic: Dict[str, Any]) -> int:
    """The requests a study can be sent, set-up's included: each becomes a
    trial, completed or ACTIVE, and with the one asked for they fit its pad."""
    room = min(study["pad"] - 1, int(config["completed_trials"])) - study["initial"]
    return max(0, min(room, int(traffic.get("max_requests_per_study", room))))


def warm_members(fleet: List[Dict[str, int]]) -> Dict[int, List[int]]:
    """pad → the studies a warm round drives at once (the first eight dealt)."""
    return {pad: [s["index"] for s in fleet if s["pad"] == pad][:FUSED_MEMBERS]
            for pad in sorted({s["pad"] for s in fleet})}


def spent_in_setup(fleet: List[Dict[str, int]], warm: int) -> Dict[int, int]:
    """Requests set-up sends each study: its cold suggest, and in each warm
    round one in its pad's fused step, the round's lone study three more."""
    spent = {s["index"]: 1 for s in fleet}
    for members in warm_members(fleet).values():
        for r in range(warm):
            for index in members:
                spent[index] += 1
            spent[members[r % len(members)]] += 3
    return spent


class _Draws:
    """The k-th request's tenant and its pick among that tenant's live
    studies, from ``population_seed``: the same for every ``--seed``."""

    BLOCK = 4096

    def __init__(self, traffic: Dict[str, Any]):
        self._rng = np.random.default_rng([int(traffic["population_seed"]), 1])
        self._hot, self._others = float(traffic["hot_tenant_share"]), traffic["tenants"] - 1
        self._u = np.zeros((0, 3))

    def at(self, k: int) -> Tuple[int, float]:
        while k >= len(self._u):
            self._u = np.concatenate([self._u, self._rng.uniform(size=(self.BLOCK, 3))])
        hot, other, pick = self._u[k]
        return (0 if hot < self._hot else 1 + int(other * self._others)), float(pick)


def pick_study(of_tenant: List[int], left: Dict[int, int], pick: float) -> Optional[int]:
    """Uniform among the tenant's live studies; None when it has none."""
    live = [index for index in of_tenant if left[index] > 0]
    return live[int(pick * len(live))] if live else None


def requests_until_a_tenant_runs_out(fleet, left: Dict[int, int], draws: _Draws, start: int = 0) -> int:
    """How many requests from the ``start``-th on find a live study."""
    left, k = dict(left), start
    by_tenant = _by_tenant(fleet)
    while True:
        tenant, pick = draws.at(k)
        index = pick_study(by_tenant[tenant], left, pick)
        if index is None:
            return k - start
        left[index] -= 1
        k += 1


def _by_tenant(fleet) -> Dict[int, List[int]]:
    out: Dict[int, List[int]] = {}
    for s in fleet:
        out.setdefault(s["tenant"], []).append(s["index"])
    return out


def requests_after_setup(config: Dict[str, Any], traffic: Dict[str, Any], warm: int) -> int:
    """The requests a window is served before a tenant runs out of studies,
    from the files alone, after a set-up of ``warm`` warm rounds."""
    fleet = population(traffic)
    spent = spent_in_setup(fleet, warm)
    left = {s["index"]: capacity(s, config, traffic) - spent[s["index"]] for s in fleet}
    return requests_until_a_tenant_runs_out(fleet, left, _Draws(traffic))


def base_rate(traffic: Dict[str, Any]) -> float:
    """The rate between bursts, from the window's mean ``rate_per_s``."""
    burst_share = float(traffic["burst_seconds"]) / float(traffic["burst_period_s"])
    return float(traffic["rate_per_s"]) / (1.0 + (float(traffic["burst_factor"]) - 1.0) * burst_share)


def pool_size(traffic: Dict[str, Any]) -> int:
    """Sender threads: twice the requests in flight and the evaluations
    running at a burst's rate, a request taken as a second."""
    busiest = base_rate(traffic) * float(traffic["burst_factor"]) * (float(traffic["think_ms"]) / 1e3 + 1.0)
    return int(2.0 * busiest) + 2


def due_times(traffic: Dict[str, Any], seed: int, seconds: float) -> np.ndarray:
    """Seconds into the window at which each request is due, from ``seed``: a
    Poisson process at the base rate, ``burst_factor`` times it in the first
    ``burst_seconds`` of every ``burst_period_s``, given how many arrive in
    the window — and that is held at its expectation, so a seed moves when
    the requests are due and not how many there are (a count left to the
    seed differs by 5 % of 400 from run to run, and ``suggestions_per_s``,
    the offered rate under the knee, with it). Given their number, a Poisson
    process's arrivals are independent draws from its rate over the window."""
    period, burst = float(traffic["burst_period_s"]), float(traffic["burst_seconds"])
    base, factor = base_rate(traffic), float(traffic["burst_factor"])
    edges, rates = [0.0], []
    while edges[-1] < seconds:  # a period: its burst, then the rest of it
        start = period * (len(edges) // 2)
        for end, rate in ((start + burst, base * factor), (start + period, base)):
            if edges[-1] < min(end, seconds):
                edges.append(min(end, seconds))
                rates.append(rate)
    edges = np.asarray(edges)
    expected = np.concatenate([[0.0], np.cumsum(np.asarray(rates) * np.diff(edges))])  # arrivals expected by each edge
    rng = np.random.default_rng([seed, 6])
    at = np.sort(rng.uniform(0.0, expected[-1], size=int(round(expected[-1]))))
    return np.interp(at, expected, edges)


def check_data(config: Dict[str, Any], traffic: Dict[str, Any]) -> None:
    """This generator's rules for a cell's files; an AssertionError says
    which one they break."""
    count = traffic["suggest_count"]
    assert count == 1, f"a worker of the fleet asks for one suggestion at a time, not {count}"
    studies = study_count(traffic)
    assert studies <= 64, f"the traffic opens {studies} studies; the designer cache keeps 64"
    pads = list(traffic["pads"])
    assert pads == sorted(set(pads)) == list(config["trial_padding_buckets"]), (
        f"the traffic's pads {pads} are not the configuration's trial_padding_buckets "
        f"{config['trial_padding_buckets']}, smallest first")
    fleet = population(traffic)
    for s in fleet:
        assert s["pad"] // 2 < s["initial"] <= s["pad"] // 2 + max(1, s["pad"] // 6), (
            f"study {s['index']} opens with {s['initial']} completed trials: outside the lower third of its bucket {s['pad']}")
    assert max(pads) <= SPARSE_SWITCH and config["completed_trials"] < SPARSE_SWITCH, (
        f"completed_trials {config['completed_trials']} or a pad of {pads} reaches the sparse switch at {SPARSE_SWITCH}")
    warmed, met = sorted(tuple(shape) for shape in config["warm_shapes"]), [(pad, pad) for pad in pads]
    assert warmed == met, (
        f"a study is retired before it leaves its bucket, so the window meets the shapes {met}; "
        f"the configuration's warm_shapes, which set-up warms up, are {warmed}")
    by_tenant = _by_tenant(fleet)
    for tenant, of_tenant in by_tenant.items():
        held = {fleet[index]["pad"] for index in of_tenant}
        assert len(held) >= 2, f"tenant {tenant} holds studies of one bucket only: {sorted(held)}"
    for pad, members in warm_members(fleet).items():
        assert len(members) >= 2, f"the pad {pad} has {len(members)} study: a fused flush needs two to meet"
    rate, knee = float(traffic["rate_per_s"]), float(traffic["knee_per_s"])
    assert abs(rate - 0.8 * knee) <= 0.005 * knee, f"rate_per_s {rate} is not 0.8 of knee_per_s {knee}"
    # The supply, after the longest set-up: every tenant's share of 1.5
    # windows, and no tenant out of studies before 1.5 windows have been sent.
    wanted = SUPPLY_WINDOWS * rate * float(traffic["window_seconds"])
    spent = spent_in_setup(fleet, MAX_WARM_ROUNDS)
    left = {s["index"]: capacity(s, config, traffic) - spent[s["index"]] for s in fleet}
    hot = float(traffic["hot_tenant_share"])
    for tenant, of_tenant in by_tenant.items():
        share = hot if tenant == 0 else (1.0 - hot) / (len(by_tenant) - 1)
        supply = sum(max(0, left[index]) for index in of_tenant)
        assert supply >= share * wanted, (
            f"tenant {tenant}'s studies hold {supply} requests after set-up; its share of {SUPPLY_WINDOWS} windows "
            f"of {traffic['window_seconds']} s at {rate} requests/s is {share * wanted:.0f}")
    served = requests_until_a_tenant_runs_out(fleet, left, _Draws(traffic))
    assert served >= wanted, (
        f"a tenant runs out of studies after {served} requests; {SUPPLY_WINDOWS} windows of "
        f"{traffic['window_seconds']} s at {rate} requests/s send {wanted:.0f}")


# -- a run ------------------------------------------------------------------------


class _Study(shared_fills._Study):
    """A study of the fleet: ``shared_fills``' record of every trial by id,
    and how many requests it can still be sent."""

    def __init__(self, handle, spec: Dict[str, int], left: int, config: Dict[str, Any], runtime=None):
        super().__init__(handle, spec["index"], config, runtime)
        self.pad = spec["pad"]
        self.left = left
        self.completed_at_last_suggest = spec["initial"]


class Generator:
    def __init__(self, server, config: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int, annotate: Callable[[str], Any]):
        check_data(config, traffic)  # the sizes as run: a rehearsal's too
        self.server = server
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.annotate = annotate
        self.names = studies_lib.param_names(config)
        self.fleet = population(traffic)
        self.studies: List[_Study] = []
        self.records: List[Dict[str, Any]] = []  # one per window request
        self.exhausted: List[int] = []  # tenants that ran out of studies
        self._draws = _Draws(traffic)
        self._by_tenant = _by_tenant(self.fleet)
        self._next = 0  # the window's requests drawn so far
        self._asked = 0  # set-up's workers, each an id of its own
        self._lock = threading.Lock()
        self._stop = threading.Event()
        registry = getattr(getattr(server, "runtime", None), "metrics", None)
        self._lag = None if registry is None else registry.histogram(
            LAG_HISTOGRAM, help="chipbench: a request's send time minus its due time.", buckets=LAG_BUCKETS)

    # -- set-up --------------------------------------------------------------

    def setup(self, compiles_so_far: Callable[[], int]) -> Dict[str, Any]:
        t0 = time.perf_counter()
        study_config = studies_lib.study_config(self.config)
        for spec in self.fleet:
            handle = self.server.open_study(
                study_config, f"tenant-{spec['tenant']}", f"seed{self.seed}-study{spec['index']}")
            study = _Study(handle, spec, capacity(spec, self.config, self.traffic), self.config,
                           getattr(self.server, "runtime", None))
            rng = np.random.default_rng([self.seed, 1, spec["index"]])
            trials, x, y = studies_lib.seeded_trials(self.config, rng, spec["initial"])
            self.server.load_trials(handle, trials)
            ages = float("-inf")  # there before any clock started
            for i, (row, value) in enumerate(zip(x, y)):
                study.note(i + 1, row=row, value=float(value), t_sent=ages, t_received=ages,
                           t_complete_sent=ages, t_acked=ages)
            self.studies.append(study)
        loaded = time.perf_counter()
        # Every study's first suggest, one at a time: a cold train each, so
        # that the window's trains are warm ones and no cold studies meet.
        for study in self.studies:
            self._finish(study, self._direct(study))
        cold = time.perf_counter()
        members = {pad: [self.studies[i] for i in indices] for pad, indices in warm_members(self.fleet).items()}
        fused = {pad: False for pad in members}
        warm_rounds, compiled = 0, []
        while warm_rounds < MAX_WARM_ROUNDS:
            compiles = compiles_so_far()
            for pad, of_pad in members.items():
                lone = of_pad[warm_rounds % len(of_pad)]
                first = self._direct(lone)  # a warm train, through the lone hand-back
                second = self._direct(lone)  # the cached fit, conditioned on the first
                self._finish(lone, first)
                self._finish(lone, second)
                before = self.server.stats()["batched_suggests"]
                self._at_once(of_pad)  # the fused flush program
                fused[pad] = fused[pad] or self.server.stats()["batched_suggests"] > before
                self._finish(lone, self._direct(lone))  # sequential again after a fused suggest
            warm_rounds += 1
            compiled.append(compiles_so_far() - compiles)
            if not compiled[-1] and all(fused.values()):
                break
        return {
            "studies": len(self.studies), "load_s": loaded - t0, "cold_s": cold - loaded,
            "warm_up_s": time.perf_counter() - cold, "warm_rounds": warm_rounds, "compiles_by_round": compiled,
            "warm_shapes": len(self.config["warm_shapes"]), "fused_met": all(fused.values()),
        }

    def _direct(self, study: _Study, worker: Optional[int] = None):
        """One of set-up's suggests, as a worker that holds no trial."""
        if worker is None:
            worker = self._asked = self._asked + 1
        study.left -= 1
        return self._ask(study, f"setup-{worker}", None)

    def _at_once(self, of_pad: List[_Study]) -> None:
        """One suggest on each study, all sent together, then evaluated."""
        errors: List[BaseException] = []
        barrier = threading.Barrier(len(of_pad))
        self._asked += len(of_pad)
        first = self._asked - len(of_pad)

        def body(n: int, study: _Study) -> None:
            try:
                barrier.wait()
                self._finish(study, self._direct(study, first + n + 1))
            except BaseException as e:  # re-raised on the caller's thread
                errors.append(e)
                barrier.abort()

        threads = [threading.Thread(target=body, args=(n, s)) for n, s in enumerate(of_pad)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]

    def requests_available(self) -> int:
        """The requests the window is served, as the studies stand, before
        a tenant is ``exhausted``."""
        if self.exhausted:
            return 0
        return requests_until_a_tenant_runs_out(self.fleet, self._left(), self._draws, self._next)

    def _left(self) -> Dict[int, int]:
        return {s.index: s.left for s in self.studies}

    # -- one request ---------------------------------------------------------

    def _ask(self, study: _Study, client_id: str, record: Optional[Dict[str, Any]]):
        """suggest(1) → check → the clients' record. The trial; a window's
        request (``record`` given) that failed is counted there and gives
        None, one of set-up's raises."""
        t_sent = time.perf_counter()
        if record is not None:
            record["sent"] = t_sent
            if self._lag is not None:
                self._lag.observe(max(0.0, t_sent - record["t0"]))
        try:
            with self.annotate("client.suggest"):
                trials = study.handle.suggest(count=1, client_id=client_id)
        except Exception as e:  # a failed request is counted, not fatal
            if record is None:
                raise
            record["t1"] = time.perf_counter()
            record["failures"].append(f"{type(e).__name__}: {e}"[:300])
            return None
        t_received = time.perf_counter()
        rows = [[t.parameters[name] for name in self.names] for t in trials]
        failures = checks.check_batch(rows, [self.server.suggestion_metadata(t) for t in trials], 1)
        meta = None
        if not failures:
            try:
                meta = self.server.pick_metadata(trials[0])
            except (KeyError, ValueError) as e:
                failures.append(f"trial {trials[0].id} lacks the sweep's own readings: {e!r}"[:300])
        if record is not None:
            record.update({"t1": t_received, "suggestions": len(rows), "failures": failures})
        elif failures:
            raise RuntimeError(f"set-up suggest on study {study.index}: {failures}")
        if failures:
            return None
        trial = trials[0]
        with self._lock:
            study.note(trial.id, row=np.asarray(rows[0], np.float64), client=client_id, t_sent=t_sent,
                       t_received=t_received, created=pending_lib.created_at(trial), meta=meta, record=record)
        return trial

    def _finish(self, study: _Study, trial, rng: Optional[np.random.Generator] = None) -> None:
        """The worker's evaluation is over: it completes its trial."""
        mine = study.trials[trial.id]
        rng = rng or np.random.default_rng([self.seed, 2, study.index, trial.id])
        mine["value"] = float(study.objective(mine["row"], rng)[0])
        mine["t_complete_sent"] = time.perf_counter()
        with self.annotate("client.complete"):
            mine["completed"] = pending_lib.complete(trial, mine["value"])
        mine["t_acked"] = time.perf_counter()

    def _serve(self, study: _Study, k: int, record: Dict[str, Any], think: float) -> None:
        """A window's request on a pool thread: ask, evaluate, complete."""
        trial = self._ask(study, f"worker-{k}", record)
        if trial is None:
            return
        with self.annotate(stages.THINK):
            stopped = self._stop.wait(think)
        if not stopped:  # the window is over: nothing more is completed
            self._finish(study, trial)

    # -- the window ----------------------------------------------------------

    def window(self, seconds: float) -> Dict[str, float]:
        """Sends every request of the schedule when it is due; those in
        flight at the end are waited for and recorded, the evaluations still
        running are dropped."""
        think = float(self.traffic["think_ms"]) / 1e3
        due = due_times(self.traffic, self.seed, seconds)
        flushes_before = self._occupancy()
        pool = futures.ThreadPoolExecutor(max_workers=pool_size(self.traffic), thread_name_prefix="fleet-worker")
        served: List[futures.Future] = []
        t0 = time.perf_counter() + 0.05
        for offset in due:
            tenant, pick = self._draws.at(self._next)
            index = pick_study(self._by_tenant[tenant], self._left(), pick)
            if index is None:
                self.exhausted.append(tenant)
                break
            self.studies[index].left -= 1
            record = {"client": tenant, "study": index, "failures": [], "t0": t0 + offset}
            time.sleep(max(0.0, record["t0"] - time.perf_counter()))
            self.records.append(record)
            served.append(pool.submit(self._serve, self.studies[index], self._next, record, think))
            self._next += 1
        if not self.exhausted:
            time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        self._stop.set()
        pool.shutdown(wait=True)
        for request in served:
            request.result()  # a fault of the generator's own is raised, not swallowed
        for index in sorted({r["study"] for r in self.records}):
            study = self.studies[index]
            shared_fills.Generator._same_point_while_active(study)
            last = study.last()
            study.completed_at_last_suggest = sum(
                t["t_acked"] is not None and t["t_acked"] < last["t_sent"] for t in study.trials.values())
        self._report(due, seconds, flushes_before)
        return {"t0": t0, "t1": t0 + seconds}

    def _occupancy(self) -> Dict[str, Any]:
        """The executor's occupancy histogram as it stands, or nothing."""
        histograms = getattr(self.server, "histograms", lambda: {})()
        return {k: v for k, v in histograms.items() if k == OCCUPANCY_HISTOGRAM}

    def _report(self, due: np.ndarray, seconds: float, flushes_before: Dict[str, Any]) -> None:
        """This generator's own line: the schedule as sent, and the
        executor's flushes by bucket label over the window."""
        lags = [(r["sent"] - r["t0"]) * 1e3 for r in self.records if "sent" in r]
        by_pad: Dict[int, List[float]] = {}  # due -> answer of each request, by its study's pad
        for r in self.records:
            by_pad.setdefault(self.studies[r["study"]].pad, []).append((r["t1"] - r["t0"]) * 1e3)
        gained = reduce.histogram_delta(self._occupancy(), flushes_before).get(OCCUPANCY_HISTOGRAM, {"series": {}})
        print(json.dumps({
            "phase": "arrivals", "due": int(len(due)), "sent": len(self.records), "seconds": seconds,
            "rate_per_s": self.traffic["rate_per_s"], "base_rate_per_s": base_rate(self.traffic),
            "send_lag_ms": {q: reduce.percentile(lags, q) for q in (50, 95, 100)} if lags else None,
            "requests_by_pad": {str(pad): len(ms) for pad, ms in sorted(by_pad.items())},
            "p50_ms_by_pad": {str(pad): reduce.percentile(ms, 50) for pad, ms in sorted(by_pad.items())},
            "requests_of_hot_tenant": sum(r["client"] == 0 for r in self.records),
            "flushes_by_bucket": {
                label.partition("=")[2]: {"flushes": count, "slots": total, "lone": counts[0]}
                for label, (counts, count, total) in sorted(gained["series"].items()) if count},
            "exhausted": self.exhausted,
        }, sort_keys=True), flush=True)
