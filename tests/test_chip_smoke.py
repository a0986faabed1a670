"""``chip_smoke.py``'s contract, from the CPU side.

The script itself only passes on a TPU. Here: (a) a child run at a tiny
size, told ``JAX_PLATFORMS=cpu`` explicitly so it never reaches for the TPU
library, goes through every phase and still exits non-zero with
``"ok": false`` as its last line, because the platform is not ``tpu``; (b)
the script's own check functions fail a batch or a stats snapshot that the
reliability layer rescued, and a posterior that is wrong.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import subprocess
import sys

import pytest

from vizier_tpu import pyvizier as vz
from vizier_tpu.reliability import fallback

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_REPO_ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SCRIPT)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_child_run_on_cpu_runs_every_phase_and_fails(tmp_path):
    cache_dir = tmp_path / "jax_cache"
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR": str(cache_dir),
        "PYTHONPATH": _REPO_ROOT,
    }
    env.pop("XLA_FLAGS", None)  # one device, as the driver's chip run sees
    proc = subprocess.run(
        [sys.executable, _SCRIPT, "--trials", "24,30", "--evals", "500"],
        cwd=str(tmp_path),
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode not in (0, None), proc.stderr[-2000:]
    lines = [json.loads(line) for line in proc.stdout.splitlines()]
    assert lines[-1] == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    by_phase = {}
    for line in lines[:-1]:
        by_phase.setdefault(line["phase"], []).append(line)
    # Every phase ran, and nothing but the platform failed.
    assert by_phase["failures"][0]["failures"] == ["platform is 'cpu', not 'tpu'"]
    assert "exception" not in by_phase
    suggests = by_phase["suggest"]
    assert [(s["study"], s["call"], s["transport"]) for s in suggests] == [
        ("A", 1, "in_process"), ("A", 2, "in_process"), ("A", 3, "in_process"),
        ("B", 1, "in_process"), ("B", 2, "in_process"), ("B", 3, "in_process"),
        ("A", 1, "grpc"),
    ]
    assert all(s["returned"] == 25 and not s["failed"] for s in suggests)
    assert [r["executor_bucket_kinds"] for r in by_phase["study_report"]] == [
        {"gp_ucb_pe": 3},
        {"gp_ucb_pe": 3},
    ]
    assert all(not p["failed"] for p in by_phase["posterior_vs_float64"])
    assert len(by_phase["posterior_vs_float64"]) == 2
    assert [s["where"] for s in by_phase["serving_stats"]] == [
        "in_process",
        "grpc_server",
    ]
    # The cache went where JAX's own variable says, and the script said so.
    device = by_phase["device"][0]
    assert device["compile_cache_dir"] == str(cache_dir)
    assert device["compile_cache_from_env"] is True
    assert any(cache_dir.iterdir())
    assert not (tmp_path / ".jax_cache").exists()


def _batch(mutate=None):
    trials = []
    for i in range(25):
        t = vz.Trial(
            id=i + 1,
            parameters={f"x{d}": (i + 1) / 26.0 + d * 1e-3 for d in range(20)},
        )
        trials.append(t)
    if mutate is not None:
        mutate(trials)
    return trials


def _stamp(trials):
    ns = trials[3].metadata.ns(fallback.FALLBACK_NAMESPACE)
    ns[fallback.FALLBACK_KEY] = fallback.FALLBACK_VALUE


def _nan(trials):
    trials[5].parameters["x7"] = vz.ParameterValue(math.nan)


def _out_of_range(trials):
    trials[5].parameters["x7"] = vz.ParameterValue(1.5)


def _duplicate(trials):
    trials[9].parameters = trials[8].parameters


def _short(trials):
    trials.pop()


_CLEAN_STATS = {
    "fallbacks": 0,
    "designer_failures": 0,
    "breaker_short_circuits": 0,
    "deadline_exceeded": 0,
}


@pytest.mark.parametrize(
    "failures_of",
    [
        pytest.param(lambda s: s.check_batch(_batch(_stamp)), id="fallback_stamp"),
        pytest.param(lambda s: s.check_batch(_batch(_nan)), id="nan_value"),
        pytest.param(lambda s: s.check_batch(_batch(_out_of_range)), id="out_of_range"),
        pytest.param(lambda s: s.check_batch(_batch(_duplicate)), id="duplicate_pair"),
        pytest.param(lambda s: s.check_batch(_batch(_short)), id="short_batch"),
        pytest.param(
            lambda s: s.check_stats({**_CLEAN_STATS, "fallbacks": 25}),
            id="fallbacks_counted",
        ),
        pytest.param(
            lambda s: s.check_stats({**_CLEAN_STATS, "designer_failures": 1}),
            id="designer_failures_counted",
        ),
        pytest.param(
            lambda s: s.check_stats({**_CLEAN_STATS, "deadline_exceeded": 1}),
            id="deadline_exceeded_counted",
        ),
        pytest.param(
            lambda s: s.check_stats({"fallbacks": 0}), id="counter_missing"
        ),
        pytest.param(
            lambda s: s.check_posterior(0.001, 0.65, 0), id="stddev_off"
        ),
        pytest.param(
            lambda s: s.check_posterior(0.001, 0.001, 3), id="negative_variance"
        ),
        pytest.param(
            lambda s: s.check_posterior(math.nan, 0.001, 0), id="nan_posterior"
        ),
    ],
)
def test_checks_fail_the_run(smoke, failures_of):
    assert failures_of(smoke), "the check let a bad run through"


def test_checks_pass_a_clean_run(smoke):
    assert smoke.check_batch(_batch()) == []
    assert smoke.check_stats(dict(_CLEAN_STATS, cache_hits=4)) == []
    assert smoke.check_posterior(1e-3, 1e-3, 0) == []


def test_float64_reference_matches_the_model_on_a_small_input(smoke):
    """The script's NumPy reference is the model's posterior: agreement with
    ``models.gp`` in f32 on the CPU at a size where f32 is exact enough."""
    import jax
    import numpy as np

    if _REPO_ROOT not in sys.path:
        sys.path.insert(0, _REPO_ROOT)
    from __graft_entry__ import _tiny_data
    from vizier_tpu.models import gp as gp_lib
    from vizier_tpu.models import kernels

    model = gp_lib.VizierGaussianProcess(num_continuous=4, num_categorical=0)
    data = _tiny_data(n=8, n_pad=16, dc=4)
    params = model.param_collection().random_init_unconstrained(jax.random.PRNGKey(0))
    state = model.precompute(params, data)
    query = np.random.default_rng(1).uniform(size=(32, 4)).astype(np.float32)
    mean, stddev = state.predict(
        kernels.MixedFeatures(query, np.zeros((32, 0), np.int32))
    )
    mask = np.asarray(data.row_mask)
    mean_ref, stddev_ref = smoke.reference_posterior(
        np.asarray(data.continuous)[mask],
        np.asarray(data.labels)[mask],
        query,
        state.params["amplitude"],
        state.params["noise_stddev"],
        state.params["continuous_length_scales"],
    )
    np.testing.assert_allclose(np.asarray(mean), mean_ref, atol=2e-4)
    np.testing.assert_allclose(np.asarray(stddev), stddev_ref, atol=2e-4)
