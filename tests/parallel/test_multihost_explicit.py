"""End-to-end explicit-coordinator multihost init.

Spawns TWO real OS processes that each call
``parallel.initialize_multihost(coordinator_address=..., num_processes=2,
process_id=i)`` on the CPU backend and assert the returned mesh is GLOBAL
(it spans both processes' devices). This executes the explicit-coordinator
branch of ``parallel/__init__.py`` — ``jax.distributed.initialize`` wiring
over a real localhost socket — which the in-process suite cannot reach
(jax.distributed refuses to initialize twice in one process).

Two tests, both on the CPU backend:

- ``test_two_process_explicit_coordinator_returns_global_mesh`` runs the
  distributed init + global-mesh wiring end-to-end (cluster rendezvous,
  process count, global device view — the seam
  ``parallel.mesh.multihost_mesh`` builds placements from);
- ``test_two_process_global_mesh_spmd_compute`` additionally executes a
  pool-sharded computation OVER the global mesh, whose sharding spans the
  other process's devices (the installed jax's CPU client runs it; an
  earlier release could not, and the test was an xfail until PR 21).
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import textwrap

_WORKER = textwrap.dedent(
    """
    import sys

    import jax  # on the CPU: the parent passes JAX_PLATFORMS=cpu in the env

    coordinator, process_id, mode = sys.argv[1], int(sys.argv[2]), sys.argv[3]

    from vizier_tpu import parallel

    mesh = parallel.initialize_multihost(
        coordinator_address=coordinator, num_processes=2, process_id=process_id
    )
    n_global = len(mesh.devices.flat)
    n_local = len(jax.local_devices())
    n_procs = jax.process_count()
    print(
        f"RESULT process_id={process_id} global={n_global} "
        f"local={n_local} procs={n_procs}",
        flush=True,
    )
    assert n_procs == 2, n_procs
    assert n_global == 2 * n_local, (n_global, n_local)

    # The mesh executor's multi-host seam sees the same global device
    # list the data plane shards over.
    from vizier_tpu.parallel import mesh as mesh_lib

    devices = mesh_lib.multihost_mesh(mesh_lib.MeshConfig())
    assert len(devices) == n_global, (len(devices), n_global)
    placements = mesh_lib.build_placements(
        mesh_lib.MeshConfig(enabled=True, shard_devices=n_local)
    )
    assert len(placements) == 2, placements
    print(f"PLACEMENTS process_id={process_id} count={len(placements)}", flush=True)

    if mode == "init":
        sys.exit(0)

    # Data plane over the GLOBAL mesh: a pool-sharded acquisition sweep
    # whose pools live on BOTH processes' devices, merged by a global
    # top-k (the cross-host collective), result replicated so every
    # process reads the same optimum. THIS dispatch is what the CPU
    # backend refuses ("Multiprocess computations aren't implemented on
    # the CPU backend") — it needs a real multi-process runtime (TPU/GPU).
    import jax.numpy as jnp

    from vizier_tpu.optimizers import eagle as eagle_lib
    from vizier_tpu.optimizers import vectorized as vectorized_lib

    target = jnp.asarray([0.25, 0.75])

    def score_fn(target, feats):
        return -jnp.sum((feats.continuous - target) ** 2, axis=-1)

    strategy = eagle_lib.VectorizedEagleStrategy(
        num_continuous=2, category_sizes=()
    )
    vec = vectorized_lib.VectorizedOptimizer(strategy, max_evaluations=200)

    @jax.jit
    def run(key):
        res = parallel.maximize_score_fn_sharded(
            vec, score_fn, target, key, 1, n_global, mesh
        )
        return jax.lax.with_sharding_constraint(
            res, parallel.replicated(mesh)
        )

    res = run(jax.random.PRNGKey(0))
    best = float(res.scores[0])
    xy = [round(float(v), 3) for v in res.features.continuous[0]]
    print(f"SPMD process_id={process_id} best={best:.5f} xy={xy}", flush=True)
    assert best > -0.01, best  # planted optimum found across both hosts
    """
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_workers(tmp_path, mode: str):
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    coordinator = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # two workers cannot share a chip
    # 2 virtual devices per process -> the global mesh must see 4.
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    # Repo root from this file's location, not cwd, so the test passes
    # regardless of where pytest is invoked from.
    env["PYTHONPATH"] = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), coordinator, str(i), mode],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        for i in range(2)
    ]
    outputs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outputs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return procs, outputs


def test_two_process_explicit_coordinator_returns_global_mesh(tmp_path):
    """The CPU backend CAN do this much: rendezvous, global device view,
    and the mesh-plane placement math over it — a pod slice's control
    plane, end to end over a real localhost socket."""
    procs, outputs = _spawn_workers(tmp_path, "init")
    for i, (p, out) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"process {i} failed:\n{out}"
        assert f"RESULT process_id={i} global=4 local=2 procs=2" in out, out
        assert f"PLACEMENTS process_id={i} count=2" in out, out


def test_two_process_global_mesh_spmd_compute(tmp_path):
    procs, outputs = _spawn_workers(tmp_path, "spmd")
    spmd_lines = []
    for i, (p, out) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"process {i} failed:\n{out}"
        line = [l for l in out.splitlines() if l.startswith(f"SPMD process_id={i}")]
        assert line, f"no SPMD result from process {i}:\n{out}"
        spmd_lines.append(line[0].split(" ", 2)[2])
    # Replicated output: both processes must report the identical optimum.
    assert spmd_lines[0] == spmd_lines[1], spmd_lines
