"""JAX-discipline pass: seeded host syncs / tracer branches / retrace
hazards are each reported; the disciplined fixture and the real tree are
clean modulo the baseline."""

from vizier_tpu.analysis import jax_discipline

_FIX = "tests/analysis/fixtures/bad_jit_sync.py"


def _result(fixtures_project):
    return jax_discipline.run(fixtures_project)


class TestSeededFixtures:
    def test_host_syncs_in_jitted_fn(self, fixtures_project):
        keys = {f.key for f in _result(fixtures_project).findings}
        assert f"host-sync@{_FIX}::bad_host_syncs:block_until_ready" in keys
        assert f"host-sync@{_FIX}::bad_host_syncs:np.asarray" in keys
        assert f"host-sync@{_FIX}::bad_host_syncs:.item()" in keys
        assert f"host-sync@{_FIX}::bad_host_syncs:float()" in keys

    def test_tracer_branch(self, fixtures_project):
        keys = {f.key for f in _result(fixtures_project).findings}
        assert f"tracer-branch@{_FIX}::bad_tracer_branch:total" in keys

    def test_sync_in_helper_reached_from_jit(self, fixtures_project):
        # Reachability, not just direct decoration: the helper itself is
        # not decorated but is traced through the jitted caller.
        result = _result(fixtures_project)
        assert f"{_FIX}::_helper_reached_from_jit" in result.traced
        keys = {f.key for f in result.findings}
        assert f"host-sync@{_FIX}::_helper_reached_from_jit:np.asarray" in keys

    def test_retrace_hazards_at_call_sites(self, fixtures_project):
        keys = {f.key for f in _result(fixtures_project).findings}
        assert (
            f"unhashable-static@{_FIX}::bad_call_sites:"
            "takes_static_sizes.sizes" in keys
        )
        assert (
            f"shape-unstable-static@{_FIX}::bad_call_sites:"
            "takes_static_sizes.sizes" in keys
        )
        assert f"jit-in-loop@{_FIX}::bad_call_sites" in keys

    def test_clean_fixture_and_tuple_static_unflagged(self, fixtures_project):
        findings = _result(fixtures_project).findings
        assert not any("clean_module" in f.path for f in findings)
        assert not any("clean_static_usage" in f.key for f in findings)

    def test_exact_seeded_finding_count(self, fixtures_project):
        # 4 host syncs + 1 tracer branch + 1 helper sync + 3 call-site
        # hazards and nothing else.
        assert len(_result(fixtures_project).findings) == 9


class TestRealTree:
    def test_no_unbaselined_findings(self, real_suite_result):
        assert real_suite_result.passes["jax_discipline"].new == []

    def test_roots_cover_the_designer_hot_path(self, real_suite_result):
        roots = {
            r.fn.qualname for r in real_suite_result.jax_result.roots
        }
        # The GP-bandit train/acquisition programs and the cross-study
        # flush program must all be discovered as jit roots.
        assert any("_train_gp" in q for q in roots)
        assert any("_maximize_acquisition" in q for q in roots)
        assert any("_gp_bandit_flush_program" in q for q in roots)
        assert len(roots) >= 15

    def test_statics_parsed_from_partial_decorators(self, real_suite_result):
        by_name = {
            r.fn.name: r for r in real_suite_result.jax_result.roots
        }
        assert "model" in by_name["_train_gp"].static_names
        assert "num_restarts" in by_name["_train_gp"].static_names
