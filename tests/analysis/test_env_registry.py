"""Env-registry pass + registry helpers: undeclared reads are findings,
declared switches are documented, runtime helpers validate names."""

import os

import pytest

from vizier_tpu.analysis import env_registry, registry

_FIX = "tests/analysis/fixtures/bad_env_read.py"


def _result(fixtures_project, repo_root):
    return env_registry.run(
        fixtures_project, repo_root, check_registry_coverage=False
    )


class TestSeededFixtures:
    def test_undeclared_reads_flagged(self, fixtures_project, repo_root):
        keys = {f.key for f in _result(fixtures_project, repo_root).findings}
        assert f"undeclared-env-read:VIZIER_NOT_A_REAL_SWITCH@{_FIX}" in keys
        assert f"undeclared-env-read:VIZIER_ALSO_NOT_DECLARED@{_FIX}" in keys

    def test_constant_read_flagged(self, fixtures_project, repo_root):
        keys = {f.key for f in _result(fixtures_project, repo_root).findings}
        assert f"environ-read-of-constant:VIZIER_METHODS@{_FIX}" in keys

    def test_dynamic_read_flagged(self, fixtures_project, repo_root):
        rules = {f.rule for f in _result(fixtures_project, repo_root).findings}
        assert "dynamic-env-read" in rules

    def test_undeclared_literals_flagged(self, fixtures_project, repo_root):
        keys = {f.key for f in _result(fixtures_project, repo_root).findings}
        assert f"undeclared-literal:VIZIER_NOT_A_REAL_SWITCH@{_FIX}" in keys

    def test_declared_read_not_flagged(self, fixtures_project, repo_root):
        findings = _result(fixtures_project, repo_root).findings
        assert not any("VIZIER_BATCHING" in f.key for f in findings)


class TestRealTree:
    def test_no_unbaselined_findings(self, real_suite_result):
        assert real_suite_result.passes["env_registry"].new == []

    def test_every_switch_documented_where_declared(self, repo_root):
        for switch in registry.SWITCHES:
            doc = os.path.join(repo_root, switch.doc)
            assert os.path.isfile(doc), f"{switch.name}: missing {switch.doc}"
            with open(doc, "r", encoding="utf-8") as f:
                assert switch.name in f.read(), (
                    f"{switch.name} not mentioned in {switch.doc}"
                )

    def test_registry_covers_the_trees_switch_count(self):
        # 82 in-tree env switches (incl. the 12 VIZIER_DISTRIBUTED* tier
        # knobs — 6 topology/WAL + 4 replication + 2 lease/heartbeat —
        # the 5 VIZIER_SPARSE* surrogate knobs, the 6 VIZIER_SPECULATIVE*
        # pre-compute knobs, the 6 VIZIER_MESH* execution-plane knobs,
        # the 8 VIZIER_SLO* objectives, the 3 VIZIER_FLIGHT_RECORDER*
        # knobs, VIZIER_OBS_DUMP_DIR, the 5 VIZIER_LOADGEN*
        # traffic-engine knobs, the 11 VIZIER_ADMISSION*
        # overload-protection knobs, the 4 VIZIER_COMPUTE_TIER*
        # disaggregated-compute knobs, and the VIZIER_NETCHAOS fault
        # schedule) + the 2 reserved grpc constants.
        # Growing the tree means growing this registry.
        assert len(registry.SWITCHES) == 84
        assert len(registry.env_switch_names()) == 82

    def test_known_switches_declared(self):
        for name in (
            "VIZIER_DISABLE_MESH",
            "VIZIER_BATCHING",
            "VIZIER_RELIABILITY",
            "VIZIER_OBSERVABILITY",
            "VIZIER_SPARSE",
            "VIZIER_DISTRIBUTED_ROUTE_CACHE_SIZE",
        ):
            assert registry.declared(name)
        assert registry.BY_NAME["VIZIER_METHODS"].kind == "constant"
        assert registry.BY_NAME["VIZIER_SERVICE_NAME"].kind == "constant"


class TestRuntimeHelpers:
    def test_undeclared_name_raises(self):
        with pytest.raises(KeyError, match="Undeclared"):
            registry.env_on("VIZIER_TOTALLY_MADE_UP")

    def test_constant_is_not_an_env_switch(self):
        with pytest.raises(KeyError, match="reserved constant"):
            registry.env_str("VIZIER_METHODS")

    def test_env_on_defaults_and_off_values(self, monkeypatch):
        monkeypatch.delenv("VIZIER_BATCHING", raising=False)
        assert registry.env_on("VIZIER_BATCHING") is True
        for off in ("0", "false", "False", ""):
            monkeypatch.setenv("VIZIER_BATCHING", off)
            assert registry.env_on("VIZIER_BATCHING") is False

    def test_env_set_opt_out_semantics(self, monkeypatch):
        monkeypatch.delenv("VIZIER_DISABLE_MESH", raising=False)
        assert registry.env_set("VIZIER_DISABLE_MESH") is False
        monkeypatch.setenv("VIZIER_DISABLE_MESH", "1")
        assert registry.env_set("VIZIER_DISABLE_MESH") is True
        # "0" means NOT disabled (the old raw-truthiness read got this wrong).
        monkeypatch.setenv("VIZIER_DISABLE_MESH", "0")
        assert registry.env_set("VIZIER_DISABLE_MESH") is False

    def test_numeric_helpers_survive_garbage(self, monkeypatch):
        monkeypatch.setenv("VIZIER_BATCH_MAX_SIZE", "not-a-number")
        assert registry.env_int("VIZIER_BATCH_MAX_SIZE", 8) == 8
        monkeypatch.setenv("VIZIER_BATCH_MAX_WAIT_MS", "2.5")
        assert registry.env_float("VIZIER_BATCH_MAX_WAIT_MS", 4.0) == 2.5

    def test_config_modules_round_trip_through_registry(self, monkeypatch):
        # The three config classes' from_env must honor registry reads.
        monkeypatch.setenv("VIZIER_SERVING_CACHE", "0")
        monkeypatch.setenv("VIZIER_RELIABILITY_BREAKER", "0")
        monkeypatch.setenv("VIZIER_OBSERVABILITY_SPAN_BUFFER", "128")
        from vizier_tpu.observability.config import ObservabilityConfig
        from vizier_tpu.reliability.config import ReliabilityConfig
        from vizier_tpu.serving.config import ServingConfig

        assert ServingConfig.from_env().designer_cache is False
        assert ReliabilityConfig.from_env().breaker is False
        assert ObservabilityConfig.from_env().span_buffer_size == 128
