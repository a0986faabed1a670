"""What the benchmark's own checks refuse, planted by hand.

``chipbench.lib.checks.check_batch`` judges every batch a cell's window
returns, and ``chipbench/run.py check_run`` holds the reliability counters
at zero; a failure of either makes the run not ``correct``. Here each is
given the batches and the snapshot a sound server never produces. The
fallback stamp is written with the reliability layer's own constants, so
the benchmark's copy of them is held to the program's.
"""

from __future__ import annotations

import math
import os
import sys
import types

import pytest

from vizier_tpu import pyvizier as vz
from vizier_tpu.reliability import fallback

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

from chipbench import run  # noqa: E402
from chipbench.lib import checks  # noqa: E402

COUNT, DIM = 25, 20


def _batch(mutate=None):
    rows = [[(i + 1) / 26.0 + d * 1e-3 for d in range(DIM)] for i in range(COUNT)]
    metadata = [vz.Metadata() for _ in rows]
    if mutate is not None:
        mutate(rows, metadata)
    return rows, metadata


def _stamp(rows, metadata):
    metadata[3].ns(fallback.FALLBACK_NAMESPACE)[fallback.FALLBACK_KEY] = fallback.FALLBACK_VALUE


def _nan(rows, metadata):
    rows[5][7] = math.nan


def _out_of_range(rows, metadata):
    rows[5][7] = 1.5


def _duplicate(rows, metadata):
    rows[9] = list(rows[8])


def _short(rows, metadata):
    rows.pop()
    metadata.pop()


@pytest.mark.parametrize(
    "mutate,said",
    [
        pytest.param(_stamp, "suggestion 3: carries the reliability fallback stamp", id="fallback_stamp"),
        pytest.param(_nan, "suggestion 5: non-finite parameter value", id="nan_value"),
        pytest.param(_out_of_range, "suggestion 5: parameter outside [0, 1]", id="out_of_range"),
        pytest.param(_duplicate, "two suggestions of the batch are identical", id="duplicate_pair"),
        pytest.param(_short, "returned 24 suggestions, wanted 25", id="short_batch"),
    ],
)
def test_a_planted_batch_fails_with_its_sentence(mutate, said):
    assert checks.check_batch(*_batch(mutate), COUNT) == [said]


def test_a_clean_batch_passes():
    assert checks.check_batch(*_batch(), COUNT) == []


def test_a_zero_counter_missing_from_the_snapshot_is_a_failing_row(capsys):
    """A counter the server stopped reporting is not a counter at zero."""
    config = {"reference": "gp_ucb_pe", "zero_counters": ["fallbacks", "designer_failures"],
              "check_studies": 1, "limits": {}}
    evidence = {"stats_total": {"fallbacks": 0}, "batched_share_pct": 0.0, "failed": 0, "attempted": 1}
    generator = types.SimpleNamespace(exhausted=[], records=[], studies=[])
    compared = run.check_run(None, generator, config, {"batched_share_pct": {"min": 0}}, evidence, seed=1)
    capsys.readouterr()  # (its "fitted" line)
    rows = {row["name"]: row for row in compared}
    assert rows["stats.fallbacks"]["ok"] is True
    assert rows["stats.designer_failures"] == {
        "name": "stats.designer_failures", "value": None, "limit": 0, "ok": False}
