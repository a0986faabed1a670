"""What a shared study's cached designer held at its last computation, and
the server's own clock on the trials a client was handed.

The second file of the benchmark that reaches into the program, beside
``lib/program.py`` (which the accepted cells are measured with and which is
not edited): a refactor of the program breaks these two files and no other.
``generators/shared_fills.py`` calls it with the ``program.Server`` it was
given, whose ``runtime`` is public.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def held(runtime, study) -> Optional[Dict[str, Any]]:
    """The trials the study's designer computed its last answer from, by
    id: ``completed`` (what it trained on, in its own order), ``pending``
    (the ACTIVE trials it conditioned on), ``incorporated`` (the delta
    read's bookkeeping of the completed ones), and ``first_has_new``: what
    it handed its UCB-or-PE draw, a function of those two sets alone. None
    when the designer cache holds nothing for the study."""
    entry = runtime.designer_cache.peek(study.resource_name, touch=False)
    if entry is None:
        return None
    designer = entry.designer
    return {
        "completed": [int(t.id) for t in designer._trials],
        "pending": [int(t.id) for t in designer._active_trials],
        "incorporated": sorted(int(i) for i in entry.incorporated_trial_ids),
        "first_has_new": bool(designer._has_new_completed_trials()),
    }


def created_at(trial) -> Optional[float]:
    """Server time at which a suggested trial was created (the proto the
    suggest returned carries it: no further RPC), as a POSIX timestamp."""
    stamp = trial._snapshot.creation_time
    return None if stamp is None else stamp.timestamp()


def complete(trial, value: float) -> Optional[float]:
    """Completes a suggested trial with its objective value over the same
    RPC as ``clients.Trial.complete``, and returns the server's completion
    time, which that method drops."""
    from vizier_tpu import pyvizier as vz

    done = trial._client.complete_trial(trial.id, vz.Measurement(metrics={"obj": float(value)}))
    return None if done.completion_time is None else done.completion_time.timestamp()
