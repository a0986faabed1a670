"""The served default on a host with four devices: what the automatic
whole-host mesh computes is what one device computes.

The suite hides the designers' mesh (``tests/conftest.py`` sets
``VIZIER_DISABLE_MESH``), so each case here is a child on four virtual CPU
devices that starts ``DefaultVizierServer`` as ``default20d-host4.lone25``
does, with every default as shipped. One seeded study at the benchmark
files' ``rehearse`` size gets ``suggest(3)`` twice over loopback gRPC, a cold
and a warm train, and the second answer is held against the benchmark's own
float64 reference under the configuration's ``rehearse`` limits. With the
mesh switched off the same study reads the same way on one device.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CHILD = r"""
import contextlib, json, sys
sys.path.insert(0, %(root)r)
import jax
import numpy as np
from chipbench import run
from chipbench.generators import closed_rounds
from chipbench.lib import checks, program, studies
from vizier_tpu.observability import tracing

bench = run.load_json(run.ROOT, "BENCHMARK.json")
cell = next(w for w in bench["workloads"] if w["name"] == "default20d-host4.lone25")
entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
config = run.sized(run.load_json(run.ROOT, entry["file"]), True)
traffic = run.sized(run.load_json(run.HERE, "traffic", cell["traffic"] + ".json"), True)
reference = run.load_module("references", config["reference"])
seed = 2147483777

server = program.Server()
try:
    generator = closed_rounds.Generator(server, config, traffic, seed, lambda name: contextlib.nullcontext())
    trials, x, y = studies.seeded_trials(config, np.random.default_rng([seed, 1]), traffic["start_trials"])
    handle = server.open_study(studies.study_config(config), "tenant-0", "mesh-study")
    server.load_trials(handle, trials)
    study = closed_rounds._Study(handle, 0, 0, x, y, rounds=2)
    records = [generator._round(study, None, np.random.default_rng([seed, 2])) for _ in range(2)]
    trained = server.trained(handle)
    numbers = reference.compare(
        study.record_at_last_suggest(), trained, config, np.random.default_rng([seed, 5]))["numbers"]
    designer = server.runtime.designer_cache.peek(handle.resource_name, touch=False).designer
    leaves = jax.tree_util.tree_leaves(designer._last_predictive.states)
    waits = [s for s in tracing.get_tracer().finished_spans() if s.name == "device.wait"]
    computes = [s for s in tracing.get_tracer().finished_spans() if s.name == "pythia.suggest_compute"]
    stats = server.stats()
    print(json.dumps({
        "devices": len(jax.devices()),
        "failures": [f for r in records for f in r["failures"]],
        "completed": trained["completed"],
        "trained_rows": int(trained["x"].shape[0]),
        "not_ok": {k: v for k, v in numbers.items() if not checks.judge(v, config["limits"][k])},
        "numbers": sorted(numbers),
        "leaf_devices": sorted({len(leaf.sharding.device_set) for leaf in leaves}),
        "wait_devices": sorted({s.attributes.get("devices") for s in waits}),
        "compute_devices": sorted({s.attributes.get("devices", 0) for s in computes}),
        "stats": {k: stats[k] for k in ("mesh_suggests", "mesh_devices", "warm_trains", "cold_trains",
                                        "batched_suggests", "sparse_suggests", "fallbacks")},
    }))
finally:
    server.stop()
"""


def _child(mesh: bool, tmp_path) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    env.pop("VIZIER_DISABLE_MESH", None)
    if not mesh:
        env["VIZIER_DISABLE_MESH"] = "1"
    done = subprocess.run([sys.executable, "-c", CHILD % {"root": ROOT}], capture_output=True,
                          text=True, env=env, timeout=600, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads([l for l in done.stdout.splitlines() if l.startswith("{")][-1])


@pytest.mark.parametrize("mesh,devices", [(True, 4), (False, 1)], ids=["whole_host_mesh", "mesh_off"])
def test_the_served_default_on_four_devices_answers_as_the_reference_does(mesh, devices, tmp_path):
    seen = _child(mesh, tmp_path)
    assert seen["devices"] == 4 and seen["failures"] == []
    # The second suggest trained on every trial the client had completed.
    assert seen["completed"] == seen["trained_rows"] == 36 + 3
    assert "trained_rows_max_abs_diff" in seen["numbers"] and "pick_acquisition_err_label_std" in seen["numbers"]
    assert seen["not_ok"] == {}
    # A cold and a warm train, sequential, exact, no rescue.
    assert seen["stats"]["cold_trains"] == 1 and seen["stats"]["warm_trains"] == 1
    assert [seen["stats"][k] for k in ("batched_suggests", "sparse_suggests", "fallbacks")] == [0, 0, 0]
    # On the mesh the trained state spans the host and the counters and the
    # spans say so; off it nothing does.
    assert seen["leaf_devices"][-1] == devices  # (leaves a program only hands through stay where they were)
    assert seen["wait_devices"] == [devices]
    assert seen["compute_devices"] == ([4] if mesh else [0])
    assert (seen["stats"]["mesh_suggests"], seen["stats"]["mesh_devices"]) == ((2, 4) if mesh else (0, 0))
