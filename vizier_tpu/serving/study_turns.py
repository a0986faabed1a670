"""A study's suggests take turns: one request at a time from the claim to
the write, in arrival order.

Upstream computes each suggestion under the study lock, with every trial
handed out before it ACTIVE in the datastore; N workers of one study get N
points. Since the Pythia dispatch left the study lock here, nothing kept
the next computation from reading the ACTIVE set before the previous pick
was written into it. :class:`StudyTurn` restores that order for
``SuggestTrials`` alone: ``VizierServicer._study_locks`` stay short and are
what ``CompleteTrial``, ``CreateTrial`` and reads take, so a completion is
never blocked by a turn, and different studies hold different turns.

Held across the designer computation BY DESIGN (``analysis/baseline.toml``).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict

from vizier_tpu.observability import tracing as tracing_lib


class StudyTurn:
    """One study's turn: a mutex granted in arrival order (a plain
    ``threading.Lock`` makes no such promise). A context manager; the wait
    is the span ``service.turn_wait``, which is not a stage of a suggest.

    ``waited(seconds, contended)`` is called when a turn is granted (arrival
    -> turn; ``contended``: it stood behind another request) and
    ``held(seconds)`` when it is given up. Neither can strand a ticket: a
    ``waited`` that raises gives the turn up before the exception leaves.
    """

    def __init__(
        self,
        waited: Callable[[float, bool], None],
        held: Callable[[float], None],
    ):
        self._cond = threading.Condition()
        self._tickets = 0  # handed out so far
        self._serving = 0  # the ticket whose turn it is
        self._waited = waited
        self._held = held
        self._began = 0.0  # the holder's own: written and read inside its turn

    def __enter__(self) -> "StudyTurn":
        arrived = time.perf_counter()
        with tracing_lib.get_tracer().span("service.turn_wait"):
            with self._cond:
                ticket = self._tickets
                self._tickets += 1
                contended = ticket != self._serving
                while ticket != self._serving:
                    self._cond.wait()
        self._began = time.perf_counter()
        try:
            self._waited(self._began - arrived, contended)
        except BaseException:
            self._pass_on()
            raise
        return self

    def __exit__(self, *exc_info) -> bool:
        held = time.perf_counter() - self._began
        self._pass_on()
        self._held(held)
        return False

    def _pass_on(self) -> None:
        with self._cond:
            self._serving += 1
            self._cond.notify_all()


class StudyTurns(Dict[str, StudyTurn]):
    """Study name -> its turn, made on first use: ONE turn a study, however
    many first requests of a fresh study arrive at once (a ``defaultdict``
    with a Python factory can build two, and two holders then compute at
    once)."""

    def __init__(
        self,
        waited: Callable[[float, bool], None],
        held: Callable[[float], None],
    ):
        super().__init__()
        self._guard = threading.Lock()
        self._waited = waited
        self._held = held

    def __missing__(self, study_name: str) -> StudyTurn:
        with self._guard:
            return self.setdefault(study_name, StudyTurn(self._waited, self._held))
