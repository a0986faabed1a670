"""Closed-loop tuning workers: each blocks on ``suggest``, evaluates,
completes every returned trial, and asks again.

Parameters (the traffic file): ``clients``, ``studies_per_client``,
``suggest_count``, ``start_trials``, ``max_rounds_per_study`` (optional cap
below what the bucket allows), ``think_ms`` (the worker's pause after it has
completed a round's trials and before it asks again, slept inside the span
``client.think``; no latency sample holds it). Each client thread walks its
own studies round-robin under one fixed ``client_id``. A study leaves the
rotation before a suggest would compile a new shape (``studies.bucket``);
a client with no study left ends the run as not correct — the traffic file
is then wrong for the window, not the program. How near a window came to
that is a number of every run: ``requests_available`` (the rounds the
studies can still serve when the window opens) beside the window's count.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from chipbench.lib import checks
from chipbench.lib import stages
from chipbench.lib import studies as studies_lib

MAX_WARM_ROUNDS = 6


def study_count(traffic: Dict[str, Any]) -> int:
    """The studies a cell of this traffic opens (every cell: at most 64)."""
    return traffic["clients"] * traffic["studies_per_client"]


def requests_after_setup(config: Dict[str, Any], traffic: Dict[str, Any], warm: int) -> int:
    """The requests a window can be served before a client runs out of
    studies, from the files alone: every study's rounds in its bucket, less
    what ``Generator.setup`` spends — one cold round a study, one more on
    the first study, ``warm`` warm rounds on each client's first study (1 to
    ``MAX_WARM_ROUNDS``: as many as it takes to compile nothing) and, with
    several clients, each first study's turn alone."""
    clients, per_client = traffic["clients"], traffic["studies_per_client"]
    rounds = studies_lib.rounds_in_bucket(
        traffic["start_trials"], traffic["suggest_count"], traffic.get("max_rounds_per_study"))
    alone = clients if clients > 1 else 0
    return clients * per_client * rounds - (clients * per_client + 1 + warm * clients + alone)


def check_data(config: Dict[str, Any], traffic: Dict[str, Any]) -> None:
    """This generator's rules for a cell's files; an AssertionError says
    which one they break. A study is retired before it leaves its bucket,
    so its first and last suggest must share one, on the exact side, the
    one the configuration states, with room for a window's rounds."""
    start, count = traffic["start_trials"], traffic["suggest_count"]
    rounds = studies_lib.rounds_in_bucket(start, count)
    home = studies_lib.bucket(start, count)
    assert home[0] == config["trial_padding_bucket"], (
        f"a study that starts at {start} trials trains in the {home[0]} bucket, "
        f"not the configuration's trial_padding_bucket {config['trial_padding_bucket']}")
    last = start + (rounds - 1) * count  # completed trials at the last suggest
    assert studies_lib.bucket(last, count) == home
    assert studies_lib.bucket(last + count, count) != home
    assert last <= config["completed_trials"], (
        f"a study's last suggest holds {last} completed trials, over the "
        f"configuration's completed_trials {config['completed_trials']}")
    assert config["completed_trials"] < 512, (  # never the sparse side
        f"completed_trials {config['completed_trials']} reaches the sparse switch at 512")
    # Room for the window: 1.5x the rounds a client completed on the chip
    # (PERF.md section 4), after the set-up's rounds on its first study.
    served = studies_lib.rounds_in_bucket(start, count, traffic.get("max_rounds_per_study"))
    assert served >= 9, (
        f"a study makes {served} rounds before it is retired; a window needs 9")


class _Study:
    def __init__(self, handle, client: int, index: int, x: np.ndarray, y: np.ndarray, rounds: int):
        self.handle = handle
        self.client = client
        self.index = index
        self.rows = [x]  # parameter rows of every completed trial, in order
        self.labels = [y]  # and the value each was completed with
        self.completed = len(x)
        self.rounds_left = rounds
        self.completed_at_last_suggest: Optional[int] = None
        self.last_picks: Optional[np.ndarray] = None  # the last suggest's answers
        self.last_meta: Optional[Dict[str, np.ndarray]] = None  # and what each carried

    def record_at_last_suggest(self) -> Dict[str, Any]:
        """The client's own record of the study's last suggest: the trials
        it had completed by then, and what the suggest returned."""
        n = self.completed_at_last_suggest
        return {
            "rows": np.concatenate(self.rows)[:n], "labels": np.concatenate(self.labels)[:n],
            "picks": self.last_picks, "meta": self.last_meta,
        }


class Generator:
    def __init__(self, server, config: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int, annotate: Callable[[str], Any]):
        self.server = server
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.annotate = annotate
        self.count = int(traffic["suggest_count"])
        self.clients = int(traffic["clients"])
        self.names = studies_lib.param_names(config)
        self.objective = studies_lib.Objective(config)
        self.studies: List[_Study] = []
        self.records: List[Dict[str, Any]] = []  # one per window suggest
        self.exhausted: List[int] = []  # clients that ran out of studies
        self._lock = threading.Lock()

    # -- set-up --------------------------------------------------------------

    def setup(self, compiles_so_far: Callable[[], int]) -> Dict[str, Any]:
        t0 = time.perf_counter()
        study_config = studies_lib.study_config(self.config)
        start = int(self.traffic["start_trials"])
        rounds = studies_lib.rounds_in_bucket(
            start, self.count, self.traffic.get("max_rounds_per_study")
        )
        per_client = int(self.traffic["studies_per_client"])
        for c in range(self.clients):
            for k in range(per_client):
                index = c * per_client + k
                rng = np.random.default_rng([self.seed, 1, index])
                trials, x, y = studies_lib.seeded_trials(self.config, rng, start)
                handle = self.server.open_study(
                    study_config, f"tenant-{c}", f"seed{self.seed}-study{index}"
                )
                self.server.load_trials(handle, trials)
                self.studies.append(_Study(handle, c, index, x, y, rounds))
        loaded = time.perf_counter()

        # One study alone, twice: the sequential programs, cold then warm.
        first = self.studies[0]
        for _ in range(2):
            self._round(first, None, np.random.default_rng([self.seed, 2]))
        # Every other study once, all clients at once: cold trains fill the
        # designer cache (and compile the fused cold program where slots meet).
        self._each_client(lambda c, rng: [
            self._round(s, None, rng)
            for s in self._of(c) if s.completed_at_last_suggest is None
        ])
        # Warm rounds, all clients at once, each on its first study, until
        # one compiles nothing and, with several clients, slots have met in
        # a fused flush. After the first such round each first study takes a
        # turn alone: a study's first sequential suggest after a fused one
        # compiles small programs of its own.
        warm_rounds, fused = 0, self.server.stats()["batched_suggests"]
        while warm_rounds < MAX_WARM_ROUNDS:
            compiles = compiles_so_far()
            self._each_client(lambda c, rng: self._round(self._of(c)[0], None, rng))
            warm_rounds += 1
            if self.clients > 1 and warm_rounds == 1:
                rng = np.random.default_rng([self.seed, 2])
                for c in range(self.clients):
                    self._round(self._of(c)[0], None, rng)
            elif compiles_so_far() == compiles and (
                self.clients == 1 or self.server.stats()["batched_suggests"] > fused
            ):
                break
        return {
            "studies": len(self.studies),
            "load_s": loaded - t0,
            "warm_up_s": time.perf_counter() - loaded,
            "warm_rounds": warm_rounds,
        }

    def _of(self, client: int) -> List[_Study]:
        return [s for s in self.studies if s.client == client]

    def requests_available(self) -> int:
        """The rounds the studies can still serve, as they stand: what the
        clients are given before one of them is ``exhausted``."""
        return sum(
            min(s.rounds_left, studies_lib.rounds_in_bucket(s.completed, self.count))
            for s in self.studies if self._eligible(s))

    def _each_client(self, work: Callable[[int, np.random.Generator], Any]) -> None:
        errors: List[BaseException] = []
        barrier = threading.Barrier(self.clients)

        def body(c: int) -> None:
            try:
                barrier.wait()
                work(c, np.random.default_rng([self.seed, 3, c]))
            except BaseException as e:  # re-raised on the caller's thread
                errors.append(e)
                barrier.abort()

        threads = [threading.Thread(target=body, args=(c,)) for c in range(self.clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]

    # -- one round -----------------------------------------------------------

    def _eligible(self, s: _Study) -> bool:
        start = int(self.traffic["start_trials"])
        return s.rounds_left > 0 and studies_lib.bucket(
            s.completed, self.count
        ) == studies_lib.bucket(start, self.count)

    def _round(self, s: _Study, deadline: Optional[float], rng) -> Optional[Dict[str, Any]]:
        """suggest → check → complete all. Returns the suggest's record."""
        s.rounds_left -= 1
        s.completed_at_last_suggest = s.completed
        record: Dict[str, Any] = {"client": s.client, "study": s.index, "failures": []}
        record["t0"] = time.perf_counter()
        try:
            with self.annotate("client.suggest"):
                trials = s.handle.suggest(count=self.count, client_id=f"client-{s.client}")
        except Exception as e:  # a failed request is counted, not fatal
            record["t1"] = time.perf_counter()
            record["failures"].append(f"{type(e).__name__}: {e}"[:300])
            s.rounds_left = 0  # its open operation's state is unknown
            return record
        record["t1"] = time.perf_counter()
        rows = [[t.parameters[name] for name in self.names] for t in trials]
        record["failures"] = checks.check_batch(
            rows, [self.server.suggestion_metadata(t) for t in trials], self.count
        )
        record["suggestions"] = len(rows)
        if not record["failures"]:
            try:
                meta = [self.server.pick_metadata(t) for t in trials]
            except (KeyError, ValueError) as e:
                record["failures"].append(f"a suggestion lacks the sweep's own readings: {e!r}"[:300])
                return record
            s.last_picks = np.asarray(rows, np.float64)
            s.last_meta = {k: np.asarray([m[k] for m in meta]) for k in meta[0]}
        if deadline is not None and record["t1"] > deadline:
            return record  # the window is over: nothing more is evaluated
        x = np.asarray(rows, np.float64)
        y = self.objective(x, rng)
        from vizier_tpu import pyvizier as vz

        with self.annotate("client.complete"):
            for t, value in zip(trials, y):
                t.complete(vz.Measurement(metrics={"obj": float(value)}))
        s.rows.append(x)
        s.labels.append(y)
        s.completed += len(rows)
        return record

    # -- the window ----------------------------------------------------------

    def window(self, seconds: float) -> Dict[str, float]:
        """Runs every client for ``seconds``; a suggest in flight at the end
        is waited for and recorded, so no request is dropped from the tail."""
        think = float(self.traffic.get("think_ms", 0)) / 1000.0
        begin: Dict[str, float] = {}

        def client(c: int, rng) -> None:
            mine, turn = self._of(c), 0
            deadline = begin["t"] + seconds
            time.sleep(max(0.0, begin["t"] - time.perf_counter()))
            while time.perf_counter() < deadline:
                ready = [s for s in mine[turn:] + mine[:turn] if self._eligible(s)]
                if not ready:
                    with self._lock:
                        self.exhausted.append(c)
                    return
                s = ready[0]
                turn = (mine.index(s) + 1) % len(mine)
                record = self._round(s, deadline, rng)
                with self._lock:
                    self.records.append(record)
                if think:
                    with self.annotate(stages.THINK):
                        time.sleep(think)

        # One start for every client, a little ahead so each thread is up.
        begin["t"] = time.perf_counter() + 0.05
        self._each_client(client)
        return {"t0": begin["t"], "t1": begin["t"] + seconds}
