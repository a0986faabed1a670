"""A generator owns the rules of its data: the contract's checks on a
benchmark of fixture files (``fixtures/``, which no run executes) whose
studies start empty and cross three padding buckets, and each rule of
``closed_rounds`` planted broken on the repo's own cells."""

from __future__ import annotations

import copy
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import contract_checks as contract  # noqa: E402  (beside this file)

ROOT = contract.ROOT
BENCH = contract.load(ROOT, "BENCHMARK.json")
FIXTURE_ROOT = os.path.join(HERE, "fixtures")
FIXTURE = contract.load(FIXTURE_ROOT, "BENCHMARK.json")
FIXTURE_CELL = FIXTURE["workloads"][0]

# Every check test_harness.py makes of the repo's BENCHMARK.json.
CHECKS = (
    [("top_level", lambda: contract.top_level_keys_and_limits(FIXTURE, FIXTURE_ROOT)),
     ("readers_on_empty_evidence",
      lambda: contract.readers_return_nothing_when_there_is_nothing_to_read(FIXTURE, FIXTURE_ROOT))]
    + [(f"entry:{e['name']}", lambda e=e: contract.entry_names_units_and_lines(FIXTURE, e))
       for e in contract.entries(FIXTURE)]
    + [(f"config:{c['name']}", lambda c=c: contract.config_file_states_the_deployment(FIXTURE, FIXTURE_ROOT, c))
       for c in FIXTURE["configs"]]
    + [(f"cell_files:{w['name']}", lambda w=w: contract.cell_files_resolve_by_name(FIXTURE, FIXTURE_ROOT, w))
       for w in FIXTURE["workloads"]]
    + [(f"cell_data:{w['name']}", lambda w=w: contract.cell_data_keeps_its_generators_rules(FIXTURE, FIXTURE_ROOT, w))
       for w in FIXTURE["workloads"]]
    + [(f"reader:{m['name']}",
        lambda m=m: contract.layer_metric_has_a_reader_and_moves_a_reported_metric(FIXTURE, FIXTURE_ROOT, m))
       for m in FIXTURE["per_layer"]]
)


@pytest.mark.parametrize("check", [c for _, c in CHECKS], ids=[name for name, _ in CHECKS])
def test_a_bucket_crossing_benchmark_passes_every_contract_check(check):
    check()


def test_the_fixture_is_what_the_old_tests_shut_out():
    config, traffic, generator = contract.cell_files(FIXTURE, FIXTURE_ROOT, FIXTURE_CELL)
    assert not {"start_trials", "studies_per_client", "clients"} & set(traffic)
    assert not {"trial_padding_bucket", "completed_trials"} & set(config)
    assert generator.buckets_met(traffic) == [8, 16, 32]
    from chipbench.lib import studies

    assert studies.rounds_in_bucket(0, traffic["suggest_count"]) == 8  # under the old test's 9


def test_the_fixtures_generator_holds_its_cell_to_its_own_rule():
    config, traffic, generator = contract.cell_files(FIXTURE, FIXTURE_ROOT, FIXTURE_CELL)
    with pytest.raises(AssertionError, match=r"meets the buckets \[8, 16, 32, 64\]"):
        generator.check_data(config, {**traffic, "trials_per_study": 40})


def test_a_generator_that_states_no_rules_fails(tmp_path):
    root = str(tmp_path / "fixtures")
    shutil.copytree(FIXTURE_ROOT, root)
    path = os.path.join(root, "chipbench", "generators", "growing_fills.py")
    with open(path) as f:
        source = f.read()
    with open(path, "w") as f:
        f.write(source.replace("def check_data(", "def _check_data("))
    contract.cell_files_resolve_by_name(FIXTURE, root, FIXTURE_CELL)  # its files still resolve
    with pytest.raises(AssertionError, match="states no rules"):
        contract.cell_data_keeps_its_generators_rules(FIXTURE, root, FIXTURE_CELL)


# -- closed_rounds' rules still bite ------------------------------------------

# (where, key, planted value, the sentence of the rule it breaks)
BREACHES = [
    ("traffic", "start_trials", 500, "over the configuration's completed_trials"),
    ("config", "completed_trials", 600, "reaches the sparse switch at 512"),
    ("config", "trial_padding_bucket", 256, "not the configuration's trial_padding_bucket 256"),
    ("traffic", "max_rounds_per_study", 3, "makes 3 rounds before it is retired"),
]


@pytest.mark.parametrize("where,key,value,sentence", BREACHES, ids=[b[1] for b in BREACHES])
@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_a_planted_breach_of_a_closed_rounds_rule_fails_with_its_sentence(cell, where, key, value, sentence):
    config, traffic, generator = contract.cell_files(BENCH, ROOT, cell)
    assert traffic["generator"] == "closed_rounds"
    generator.check_data(config, traffic)  # sound as committed
    files = {"config": copy.deepcopy(config), "traffic": copy.deepcopy(traffic)}
    files[where][key] = value
    with pytest.raises(AssertionError, match=sentence):
        generator.check_data(files["config"], files["traffic"])


@pytest.mark.parametrize(
    "bench,root,cell,planted",
    [(BENCH, ROOT, BENCH["workloads"][-1], {"clients": 13, "studies_per_client": 5}),
     (FIXTURE, FIXTURE_ROOT, FIXTURE_CELL, {"studies": 65})],
    ids=["closed_rounds", "growing_fills"],
)
def test_more_studies_than_the_designer_cache_holds_fails_for_any_generator(bench, root, cell, planted):
    _, traffic, generator = contract.cell_files(bench, root, cell)
    contract.at_most_64_studies(generator, traffic)
    with pytest.raises(AssertionError, match="opens 65 studies; the designer cache keeps 64"):
        contract.at_most_64_studies(generator, {**traffic, **planted})
