"""The five readers of the device half of a suggest (PR 39) from the CPU side:
each one's arithmetic on planted evidence, nothing where the program lacks the
label or the counters (a parent commit under these benchmark files) or the
divisor is zero, their entries, and child runs at ``--rehearse`` size."""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _path in (ROOT, os.path.dirname(os.path.abspath(__file__))):
    if _path not in sys.path:
        sys.path.insert(0, _path)

import contract_checks as contract  # noqa: E402  (beside this file)
from chipbench import run  # noqa: E402
from chipbench.lib import stages  # noqa: E402
from test_harness import SKIP_CHIP, _run, cache_dir  # noqa: E402,F401  (the child-run helpers)

BENCH = contract.load(ROOT, "BENCHMARK.json")
SUFFIX = {  # suffix → (cells, the end-to-end metric its siblings move)
    "lone": (["default20d.lone25", "default20d-host4.lone25"], "suggest_p50_ms"),
    "pool": (["default20d.tenants16"], "suggestions_per_s"),
    "shared": (["perftest2d.shared50x5"], "suggestions_per_s"),
    "fleet": (["fleet20d.open-burst"], "suggest_p50_ms.pool"),
}
ENTRIES = {  # reader → (unit, suffixes)
    "train_wait_ms": ("ms", ("lone", "shared", "fleet")),
    "acquire_wait_ms": ("ms", ("lone", "shared", "fleet")),
    "train_iterations": ("iterations", ("lone", "pool", "fleet")),
    "train_lockstep_idle_share": ("%", ("lone", "pool", "fleet")),
    "train_evals_per_iteration": ("evals", ("lone", "pool", "fleet")),
}


def _histograms(series):
    """``program.Server.histograms()``'s form: label string → (buckets,
    count, Σ seconds), labels sorted by name as the registry keys them; rows
    with the same labels are one series."""
    out = {}
    for stage, path, per, phase, count, total in series:
        labels = {"stage": stage, "path": path, "per": per, **({} if phase is None else {"phase": phase})}
        key = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
        _, n, t = out.get(key, ([0], 0, 0.0))
        out[key] = ([n + count], n + count, t + total)
    return {stages.HISTOGRAM: {"bounds": [1.0], "series": out}}


# Ten requests: eight sequential (five trained), one fused flush of two.
LABELLED = [
    ("service.read", "sequential", "request", "", 10, 0.010),
    ("device.wait", "sequential", "request", "train", 8, 0.320),
    ("device.wait", "sequential", "request", "acquire", 8, 0.480),
    ("device.wait", "fused", "flush", "flush", 1, 0.200),
    ("designer.decode", "sequential", "request", "", 8, 0.040),
]
UNLABELLED = [(s, path, per, None, n, t) for s, path, per, _, n, t in LABELLED]  # a parent's


@pytest.mark.parametrize(
    "series, train, acquire",
    [(LABELLED, 32.0, 48.0), (UNLABELLED, None, None),
     ([row for row in LABELLED if row[3] != "train"], None, 48.0),
     ([row for row in LABELLED if row[0] != "service.read"], None, None)],
    ids=["by_phase", "a_parent_without_the_label", "no_train_sample", "no_request"],
)
def test_the_wait_readers_share_a_phases_seconds_over_the_windows_requests(series, train, acquire):
    evidence = {"histograms_window": _histograms(series), "stats_window": {}}
    for name, expected in (("train_wait_ms.lone", train), ("acquire_wait_ms.fleet", acquire)):
        value = run.load_reader(name).read(evidence)
        assert value == (expected if expected is None else pytest.approx(expected)), name
    # The label pooled away, ``device_wait_ms`` reads what it read: train +
    # acquire + the flush's wait, over the same requests.
    pooled = run.load_reader("device_wait_ms.lone").read(evidence)
    if series is LABELLED or series is UNLABELLED:
        assert pooled == pytest.approx(100.0)
    if series is LABELLED:
        assert train + acquire == pytest.approx(pooled - 20.0)


def test_no_histogram_gives_the_wait_readers_nothing():
    for name in ("train_wait_ms.shared", "acquire_wait_ms.shared"):
        assert run.load_reader(name).read({"histograms_window": {}, "stats_window": {}}) is None


# Three programs: two sequential trains of 2 rows (40 and 44 trips) and a
# flush of 16 rows (50 trips).
COUNTED = {"train_programs": 3, "train_loop_trips": 134, "train_row_trips": 968,
           "train_row_iterations": 600, "train_evaluations": 1380, "warm_trains": 10}


@pytest.mark.parametrize(
    "stats, iterations, idle, evals",
    [(COUNTED, 134 / 3, 100.0 * (1 - 600 / 968), 2.3),
     ({"warm_trains": 10, "cold_trains": 0}, None, None, None),
     ({**COUNTED, "train_programs": 0}, None, None, None),
     ({k: v for k, v in COUNTED.items() if k != "train_row_trips"}, None, None, None),
     ({**COUNTED, "train_loop_trips": 0, "train_row_trips": 0, "train_row_iterations": 0,
       "train_evaluations": 36}, 0.0, None, None)],
    ids=["three_programs", "a_parent_without_the_counters", "no_train_in_the_window",
         "one_counter_missing", "every_row_converged_at_its_start"],
)
def test_the_count_readers_are_ratios_of_the_train_counters(stats, iterations, idle, evals):
    evidence = {"histograms_window": {}, "stats_window": stats}
    for name, expected in (("train_iterations.lone", iterations),
                           ("train_lockstep_idle_share.pool", idle),
                           ("train_evals_per_iteration.fleet", evals)):
        value = run.load_reader(name).read(evidence)
        assert value == (expected if expected is None else pytest.approx(expected)), name


@pytest.mark.parametrize("reader", sorted(ENTRIES))
def test_the_entries_are_counters_of_the_device_programs_in_their_siblings_cells(reader):
    unit, suffixes = ENTRIES[reader]
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert sorted(n for n in by_name if n.startswith(reader + ".")) == sorted(
        f"{reader}.{s}" for s in suffixes)
    for suffix in suffixes:
        metric = by_name[f"{reader}.{suffix}"]
        cells, moves = SUFFIX[suffix]
        assert metric == {"name": f"{reader}.{suffix}", "unit": unit, "better": "lower",
                          "source": "program_counter", "layer": "device programs",
                          "moves": moves, "workloads": cells}
        # Its siblings of the same suffix list the same cells and move the same.
        sibling = by_name[f"device_wait_ms.{suffix}"]
        assert (sibling["workloads"], sibling["moves"], sibling["layer"]) == (cells, moves, "device programs")
        contract.layer_metric_has_a_reader_and_moves_a_reported_metric(BENCH, ROOT, metric)
    assert os.path.exists(os.path.join(ROOT, "chipbench", "layer_metrics", reader + ".py"))


def test_the_fifteen_entries_are_the_benchmarks_last_and_nothing_else_changed():
    names = [m["name"] for m in BENCH["per_layer"]]
    new = [f"{reader}.{s}" for reader, (_, suffixes) in ENTRIES.items() for s in suffixes]
    assert names[-15:] == new and names[-16] == "lone_flush_share"
    assert len(names) == len(set(names)) == 71


# -- child runs at rehearse size ---------------------------------------------------


@pytest.mark.parametrize(
    "cell, suffix, waits",
    [("default20d.lone25", "lone", True), ("default20d.tenants16", "pool", False)],
    ids=["lone25", "tenants16"],
)
def test_a_rehearsal_prints_the_new_names_among_its_layers(cache_dir, cell, suffix, waits):  # noqa: F811
    done, objs = _run(["--workload", cell, "--seed", "2147483659", "--seconds", "3", "--rehearse",
                       "--trace", "0"], cache_dir, SKIP_CHIP)
    assert done.returncode == 0, done.stderr[-2000:]
    (layers,) = [o for o in objs if o.get("phase") == "layers"]
    window = [o for o in objs if o.get("phase") == "window"][0]["stats_window"]
    counted = {f"train_iterations.{suffix}", f"train_lockstep_idle_share.{suffix}",
               f"train_evals_per_iteration.{suffix}"}
    phases = {f"train_wait_ms.{suffix}", f"acquire_wait_ms.{suffix}"}
    assert counted <= set(layers)
    assert (phases <= set(layers)) if waits else not (phases & set(layers))
    assert layers[f"train_iterations.{suffix}"] == pytest.approx(
        window["train_loop_trips"] / window["train_programs"])
    assert 0.0 <= layers[f"train_lockstep_idle_share.{suffix}"] < 100.0
    assert layers[f"train_evals_per_iteration.{suffix}"] >= 1.0
    if waits:
        # Every training suggest ran one train program, and the two phases
        # are the device wait, to rounding.
        assert window["train_programs"] == window.get("warm_trains", 0) + window.get("cold_trains", 0)
        both = layers[f"train_wait_ms.{suffix}"] + layers[f"acquire_wait_ms.{suffix}"]
        assert both == pytest.approx(layers[f"device_wait_ms.{suffix}"], rel=1e-6)
    else:
        # A flush is one program whatever its members (and a lone hand-back
        # one sequential train): never more programs than flushes or trains,
        # and fewer than trains by the members that shared a flush.
        trains = window.get("warm_trains", 0) + window.get("cold_trains", 0)
        shared = window.get("batched_suggests", 0) - (window["batch_flushes"] - window.get("lone_handbacks", 0))
        assert 0 < window["train_programs"] <= min(window["batch_flushes"], trains)
        assert window["train_programs"] == trains - shared
