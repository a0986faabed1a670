"""``default20d-host4.lone25``, the cell on four chips, from the CPU side: a
rehearsal in a child that sees four virtual devices and no
``VIZIER_DISABLE_MESH`` (``test_harness.py``'s children hide the mesh, as the
suite does), and the cell's own reader, ``mesh_suggest_share``."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _path in (ROOT, os.path.dirname(os.path.abspath(__file__))):
    if _path not in sys.path:
        sys.path.insert(0, _path)

import contract_checks as contract  # noqa: E402  (beside this file)
from chipbench import run  # noqa: E402

CELL = "default20d-host4.lone25"
BENCH = contract.load(ROOT, "BENCHMARK.json")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def _rehearse(tmp_path, devices: int):
    """A ``--rehearse`` run of the cell as a child that sees ``devices`` virtual
    CPU devices and the designers' mesh, with only the look for a TPU skipped."""
    args = ["--workload", CELL, "--seed", "2147483777", "--seconds", "3", "--rehearse", "--trace", "0"]
    code = (
        "import os, sys; os.sched_setaffinity(0, sorted(os.sched_getaffinity(0))[:2])\n"
        "sys.path.insert(0, %r); from chipbench import run; %s\n"
        "sys.exit(run.main(%r))" % (ROOT, "run.REQUIRED_PLATFORM = 'cpu'", args)
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    env.pop("VIZIER_DISABLE_MESH", None)
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          env=env, timeout=600, cwd=ROOT)
    return done, [json.loads(l) for l in done.stdout.splitlines() if l.startswith("{")]


def test_a_rehearsal_on_four_devices_serves_every_suggest_on_the_mesh_and_is_correct(tmp_path):
    done, objs = _rehearse(tmp_path, 4)
    result = objs[-1]
    assert done.returncode == 0, done.stderr[-2000:]
    assert set(result) == RESULT_KEYS and list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"suggest_p50_ms", "setup_s"} and result["device"]["count"] == 4
    compared = [o for o in objs if o.get("phase") == "correct"][0]["compared"]
    assert all(c["ok"] for c in compared) and "platform" not in {c["name"] for c in compared}
    assert result["compared"]["window.batched_share_pct"] == [0.0, {"max": 0}]
    layers = [o for o in objs if o.get("phase") == "layers"][0]
    assert layers["mesh_suggest_share"] == 100.0 and layers["compiles_in_window.lone"] == 0
    assert layers["cache_warm_share.lone"] == 100.0 and layers["device_wait_ms.lone"] > 0
    window = [o for o in objs if o.get("phase") == "window"][0]
    assert window["stats_window"]["mesh_suggests"] == window["requests"] == result["attempted"]


def test_on_one_device_the_cell_is_not_correct_and_the_guard_reads_zero(tmp_path):
    # The cell is its four chips: with the look for a TPU skipped, a host
    # that shows one device fails by the platform row alone, and with no
    # mesh built the guard reads 0.
    done, objs = _rehearse(tmp_path, 1)
    failing = [c["name"] for o in objs if o.get("phase") == "correct" for c in o["compared"] if not c["ok"]]
    assert done.returncode != 0 and failing == ["platform"]
    assert objs[-1]["correct"] is False and objs[-1]["device"]["count"] == 1
    layers = [o for o in objs if o.get("phase") == "layers"][0]
    assert layers["mesh_suggest_share"] == 0.0


@pytest.mark.parametrize(
    "evidence,share",
    [
        ({"stats_window": {"mesh_suggests": 115}, "attempted": 115}, 100.0),
        ({"stats_window": {"mesh_suggests": 23}, "attempted": 115}, 20.0),  # the rest ran on one chip
        ({"stats_window": {"mesh_suggests": 0}, "attempted": 115}, 0.0),  # no mesh: 0, not nothing
        ({"stats_window": {"mesh_suggests": 0}, "attempted": 0}, None),  # a window without a request
        ({"stats_window": {"warm_trains": 115}, "attempted": 115}, None),  # a parent: no such counter
        ({}, None),
    ],
    ids=["every_suggest", "a_mix", "none_on_the_mesh", "no_request", "no_counter", "no_evidence"],
)
def test_mesh_suggest_share_reads_planted_counters(evidence, share):
    value = run.load_reader("mesh_suggest_share").read(evidence)
    assert value == (share if share is None else pytest.approx(share))


def test_the_cell_is_lone25_on_four_chips_and_nothing_else_changed():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert BENCH["workloads"][-1] is cells[CELL] and cells[CELL]["chips"] == 4
    assert [w["chips"] for w in BENCH["workloads"]].count(4) == 1
    config, traffic, _ = contract.cell_files(BENCH, ROOT, cells[CELL])
    base, lone, _ = contract.cell_files(BENCH, ROOT, cells["default20d.lone25"])
    # The deployment's guarantees are default20d's: every limit, counter and
    # check of it, by the same reference.
    for key in ("limits", "zero_counters", "check_studies", "check_candidates", "reference", "ucb_pe",
                "control_acquisition_evaluations", "completed_trials", "trial_padding_bucket", "rehearse",
                "objective", "num_float_parameters", "algorithm", "reduced"):
        assert config[key] == base[key], key
    assert config["devices"] == 4 and config["guarantees"][:-1] == base["guarantees"]
    assert {k: v for k, v in traffic.items() if k != "trace_seconds"} == lone
    assert 0 < traffic["trace_seconds"] < run.TRACE_SECONDS
    metric = next(m for m in BENCH["per_layer"] if m["name"] == "mesh_suggest_share")
    assert metric == {"name": "mesh_suggest_share", "unit": "%", "better": "higher", "source": "program_counter",
                      "layer": "device programs", "moves": "suggest_p50_ms", "workloads": [CELL]}
    # The cell reports what lone25 reports (but the supply's share, whose
    # test holds each of its three entries to one cell).
    lone_readers = {m["name"] for m in BENCH["per_layer"] if "default20d.lone25" in m["workloads"]}
    here = {m["name"] for m in BENCH["per_layer"] if CELL in m["workloads"]}
    assert lone_readers - here == {"supply_used_share.lone"} and here - lone_readers == {"mesh_suggest_share"}
