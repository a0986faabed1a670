"""Service throughput at the reference's stress configs, over real gRPC.

Usage: python tools/service_throughput.py [--out SERVICE_THROUGHPUT.json]
       [--side repo|reference|both]
       [--replicas N [--replica-mode inprocess|subprocess]]

Reference ``performance_test.py:44-89`` runs clients×trials configs
{1×10, 2×10, 10×10, 50×5, 100×5} on RANDOM_SEARCH over a 2-D space and
logs wall time only. This tool runs the same topology — one shared study
per config, one thread per client, each doing its own suggest→complete
loop over a real localhost gRPC channel — against BOTH this repo's
``DefaultVizierServer`` and the reference's (the runnable copy that
``tools/build_reference_copy.sh`` puts at /tmp/refvizier, RAM datastore),
and writes a two-column JSON report with wall time and trials/sec.

The reference side runs in a subprocess so its ``vizier`` package import
and proto registrations stay isolated; per-worker clients are created
BEFORE the timed section on both sides, so the clock covers only the
suggest→complete loops.

``--replicas N`` additionally runs the sharded-tier A/B (a "distributed"
section in the JSON; the single-replica report above is byte-compatible
with the original schema): the SAME multi-study workload measured against
(a) one ``DefaultVizierServer`` over localhost gRPC — today's deployment —
and (b) N replicas behind the study-affinity router
(``vizier_tpu.distributed``). ``--replica-mode inprocess`` (default) uses
``ReplicaManager`` — clients route straight to the owning replica's
servicer with no central frontend hop, replicas share one Pythia fleet;
``subprocess`` starts N ``replica_main`` gRPC server processes and routes
over real channels (the multi-host shape; on a single-core container it
cannot beat one server — the processes timeshare the core).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

CONFIGS = ((1, 10), (2, 10), (10, 10), (50, 5), (100, 5))
# The published stress set above is short enough that channel setup and
# first-RPC costs dominate the small configs; the steady-state config
# measures sustained per-trial service latency.
STEADY_STATE = (1, 200)
REFCOPY = "/tmp/refvizier"


REPEATS = 3  # best-of-N per config: throughput = least-interference run
# The distributed A/B arms run short (~0.1 s for the tier), so scheduler
# noise on a small host dominates single runs; more best-of repeats per
# arm, same least-interference methodology.
DIST_REPEATS = 5


def run_repo() -> list:
    from vizier_tpu.service import clients as clients_lib
    from vizier_tpu.service import vizier_server
    from vizier_tpu.testing import stress

    server = vizier_server.DefaultVizierServer(host="localhost")
    clients_lib.environment_variables.server_endpoint = server.endpoint
    rows = []
    try:
        # Warmup: channel connect + proto/codec first-call costs land on a
        # throwaway study, so the timed configs measure the service.
        warm = clients_lib.Study.from_study_config(
            stress.stress_study_config(), owner="perf", study_id="warmup"
        )
        stress.run_stress_round(warm, 1, 3)
        for num_clients, trials_each in CONFIGS + (STEADY_STATE,):
            total = num_clients * trials_each
            best_wall = float("inf")
            for rep in range(REPEATS):
                study = clients_lib.Study.from_study_config(
                    stress.stress_study_config(),
                    owner="perf",
                    study_id=f"tp-{num_clients}x{trials_each}-r{rep}",
                )
                wall, completed, _ = stress.run_stress_round(
                    study, num_clients, trials_each
                )
                assert completed == total, (completed, total)
                best_wall = min(best_wall, wall)
            row = {
                "side": "repo",
                "clients": num_clients,
                "trials_each": trials_each,
                "total_trials": total,
                "completed": total,
                "wall_s": round(best_wall, 3),
                "trials_per_s": round(total / best_wall, 1),
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        clients_lib.environment_variables.server_endpoint = clients_lib.NO_ENDPOINT
        server.stop(0)
    return rows


def _ensure_refcopy() -> None:
    # The shims this diff relies on are part of the build; an isdir check
    # would accept a stale copy from an older build script.
    marker = os.path.join(
        REFCOPY, "vizier/_src/service/vizier_service_pb2_grpc.py"
    )
    if not os.path.exists(marker):
        subprocess.run(
            ["bash", os.path.join(_REPO_ROOT, "tools/build_reference_copy.sh")],
            check=True,
        )


def run_reference() -> list:
    """Identical topology against the reference's DefaultVizierServer."""
    import concurrent.futures as cf

    # The reference is CPU-only; say so before its jax import.
    os.environ["JAX_PLATFORMS"] = "cpu"
    _ensure_refcopy()
    sys.path.insert(0, REFCOPY)
    from vizier._src.service import vizier_client, vizier_server
    from vizier.service import pyvizier as svz

    server = vizier_server.DefaultVizierServer(database_url=None)
    vizier_client.environment_variables.server_endpoint = server.endpoint

    def study_config():
        sc = svz.StudyConfig()
        sc.search_space.root.add_float_param("x", 0.0, 1.0)
        sc.search_space.root.add_float_param("y", 0.0, 1.0)
        sc.metric_information.append(
            svz.MetricInformation(
                name="obj", goal=svz.ObjectiveMetricGoal.MINIMIZE
            )
        )
        sc.algorithm = svz.Algorithm.RANDOM_SEARCH
        return sc

    # Warmup mirrors the repo side: throwaway study absorbs first-RPC costs.
    warm = vizier_client.create_or_load_study(
        owner_id="perf",
        study_id="warmup",
        study_config=study_config(),
        client_id="w",
    )
    for _ in range(3):
        (t,) = warm.get_suggestions(suggestion_count=1)
        warm.complete_trial(
            t.id, svz.Measurement(metrics={"obj": 0.0})
        )

    rows = []
    for num_clients, trials_each in CONFIGS + (STEADY_STATE,):
        total = num_clients * trials_each
        best_wall = float("inf")
        for rep in range(REPEATS):
            study_id = f"tp-{num_clients}x{trials_each}-r{rep}"
            # Per-worker clients before the clock, mirroring the repo side
            # (where the study client exists before run_stress_round).
            clients = [
                vizier_client.create_or_load_study(
                    owner_id="perf",
                    study_id=study_id,
                    study_config=study_config(),
                    client_id=f"worker_{i}",
                )
                for i in range(num_clients)
            ]

            def worker(client):
                for _ in range(trials_each):
                    (trial,) = client.get_suggestions(suggestion_count=1)
                    x = trial.parameters["x"].value
                    y = trial.parameters["y"].value
                    m = svz.Measurement(
                        metrics={"obj": (x - 0.3) ** 2 + (y - 0.7) ** 2}
                    )
                    client.complete_trial(trial.id, m)

            t0 = time.perf_counter()
            with cf.ThreadPoolExecutor(max_workers=num_clients) as pool:
                list(pool.map(worker, clients))
            wall = time.perf_counter() - t0
            completed = sum(
                1
                for t in clients[0].list_trials()
                if t.status == svz.TrialStatus.COMPLETED
            )
            assert completed == total, (completed, total)
            best_wall = min(best_wall, wall)
        row = {
            "side": "reference",
            "clients": num_clients,
            "trials_each": trials_each,
            "total_trials": total,
            "completed": total,
            "wall_s": round(best_wall, 3),
            "trials_per_s": round(total / best_wall, 1),
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


# -- sharded-tier A/B --------------------------------------------------------

# The distributed workload: study-affinity routing only pays off with many
# studies, so the A/B drives STUDIES concurrent studies with
# CLIENTS_PER_STUDY worker threads each, identical on both arms.
DIST_STUDIES = 8
DIST_CLIENTS_PER_STUDY = 2
DIST_TRIALS_EACH = 25


def _dist_workload(stub, tag: str) -> dict:
    """Runs the multi-study workload against ``stub``; returns the row."""
    import concurrent.futures as cf

    from vizier_tpu import pyvizier as vz
    from vizier_tpu.service import proto_converters as pc
    from vizier_tpu.service import vizier_client
    from vizier_tpu.service.protos import vizier_service_pb2
    from vizier_tpu.testing import stress

    study_names, clients = [], []
    for s in range(DIST_STUDIES):
        name = f"owners/perf/studies/{tag}-s{s}"
        stub.CreateStudy(
            vizier_service_pb2.CreateStudyRequest(
                parent="owners/perf",
                study=pc.study_to_proto(stress.stress_study_config(), name),
            )
        )
        study_names.append(name)
        for w in range(DIST_CLIENTS_PER_STUDY):
            clients.append(vizier_client.VizierClient(stub, name, f"worker_{w}"))

    def worker(client):
        for _ in range(DIST_TRIALS_EACH):
            (trial,) = client.get_suggestions(1)
            x = trial.parameters["x"].value
            y = trial.parameters["y"].value
            client.complete_trial(
                trial.id,
                vz.Measurement(metrics={"obj": (x - 0.3) ** 2 + (y - 0.7) ** 2}),
            )

    t0 = time.perf_counter()
    with cf.ThreadPoolExecutor(max_workers=len(clients)) as pool:
        list(pool.map(worker, clients))
    wall = time.perf_counter() - t0

    from vizier_tpu.service.protos import study_pb2

    total = DIST_STUDIES * DIST_CLIENTS_PER_STUDY * DIST_TRIALS_EACH
    completed = 0
    for name in study_names:
        response = stub.ListTrials(
            vizier_service_pb2.ListTrialsRequest(parent=name)
        )
        completed += sum(
            1 for t in response.trials if t.state == study_pb2.Trial.SUCCEEDED
        )
    assert completed == total, (completed, total)
    return {
        "studies": DIST_STUDIES,
        "clients_per_study": DIST_CLIENTS_PER_STUDY,
        "trials_each": DIST_TRIALS_EACH,
        "total_trials": total,
        "completed": completed,
        "wall_s": round(wall, 3),
        "trials_per_s": round(total / wall, 1),
        "study_names": study_names,
    }


def _best_of(fn, repeats: int) -> dict:
    best = None
    for rep in range(repeats):
        row = fn(rep)
        if best is None or row["trials_per_s"] > best["trials_per_s"]:
            best = row
    return best


def run_distributed(num_replicas: int, mode: str) -> dict:
    """The sharded-tier A/B: single gRPC server vs N routed replicas.

    Each arm runs in its OWN subprocess: neither arm's thread pools, gRPC
    channels, or allocator state can pollute the other's measurement (on a
    1-core host, teardown noise from a prior arm is a real bias in either
    direction).
    """
    from vizier_tpu.distributed import config as dist_config_lib

    report = {
        "config": {
            "replicas": num_replicas,
            "mode": mode,
            "studies": DIST_STUDIES,
            "clients_per_study": DIST_CLIENTS_PER_STUDY,
            "trials_each": DIST_TRIALS_EACH,
            "repeats": DIST_REPEATS,
            "distributed": dist_config_lib.DistributedConfig.from_env().as_dict(),
        },
    }
    for arm in ("multi_replica", "single_server"):
        proc = subprocess.run(
            [
                sys.executable,
                os.path.abspath(__file__),
                "--dist-arm",
                arm,
                "--replicas",
                str(num_replicas),
                "--replica-mode",
                mode,
            ],
            capture_output=True,
            text=True,
            cwd=_REPO_ROOT,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"distributed arm {arm} failed:\n{proc.stderr[-3000:]}"
            )
        payload = json.loads(
            [l for l in proc.stdout.splitlines() if l.startswith("{")][-1]
        )
        report.update(payload)
        print(json.dumps(payload), flush=True)
    report["speedup_vs_single_server"] = round(
        report["multi_replica"]["trials_per_s"]
        / report["single_server"]["trials_per_s"],
        2,
    )
    print(
        json.dumps(
            {"speedup_vs_single_server": report["speedup_vs_single_server"]}
        ),
        flush=True,
    )
    return report


def run_dist_arm(arm: str, num_replicas: int, mode: str) -> None:
    """Child-process entry: one A/B arm, result JSON on stdout (last line)."""
    if arm == "single_server":
        from vizier_tpu.service import grpc_stubs, vizier_server

        server = vizier_server.DefaultVizierServer(host="localhost")
        try:
            stub = grpc_stubs.create_vizier_stub(server.endpoint)
            _dist_workload(stub, "warm-single")  # first-RPC costs off the clock
            single = _best_of(
                lambda rep: _dist_workload(stub, f"single-r{rep}"), DIST_REPEATS
            )
        finally:
            server.stop(0)
        single.pop("study_names")
        print(json.dumps({"single_server": single}), flush=True)
        return

    if mode == "inprocess":
        row, per_replica = _run_inprocess_tier(num_replicas)
    else:
        row, per_replica = _run_subprocess_tier(num_replicas)
    print(
        json.dumps({"multi_replica": row, "per_replica": per_replica}),
        flush=True,
    )


def _per_replica_breakdown(stub_stats: dict, assignments: dict) -> dict:
    """Merges router request counters with the study->replica map."""
    out = {}
    for rid, stats in stub_stats["replicas"].items():
        out[rid] = {
            "state": stats["state"],
            "requests": int(stats["requests"]),
            "failures": int(stats["failures"]),
            "studies": sorted(assignments.get(rid, [])),
        }
    return out


def _run_inprocess_tier(num_replicas: int):
    from vizier_tpu.distributed import ReplicaManager

    manager = ReplicaManager(num_replicas)
    try:
        _dist_workload(manager.stub, "warm-tier")
        best = _best_of(
            lambda rep: _dist_workload(manager.stub, f"tier-r{rep}"), DIST_REPEATS
        )
        assignments = {rid: [] for rid in manager.router.replica_ids}
        for name in best.pop("study_names"):
            assignments[manager.router.replica_for(name)].append(name)
        per_replica = _per_replica_breakdown(manager.stub.stats(), assignments)
    finally:
        manager.shutdown()
    return best, per_replica


def _run_subprocess_tier(num_replicas: int):
    from vizier_tpu.distributed import router_stub as router_stub_lib
    from vizier_tpu.service import grpc_stubs

    procs, endpoints = [], []
    try:
        for i in range(num_replicas):
            proc = subprocess.Popen(
                [
                    sys.executable,
                    "-m",
                    "vizier_tpu.distributed.replica_main",
                    "--replica-id",
                    f"replica-{i}",
                ],
                stdout=subprocess.PIPE,
                text=True,
                cwd=_REPO_ROOT,
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
            )
            procs.append(proc)
        for proc in procs:
            line = proc.stdout.readline().strip()
            assert line.startswith("READY "), line
            endpoints.append(line.split(" ", 1)[1])
        stub = router_stub_lib.RoutedVizierStub(
            {
                f"replica-{i}": (lambda ep=ep: grpc_stubs.create_vizier_stub(ep))
                for i, ep in enumerate(endpoints)
            }
        )
        _dist_workload(stub, "warm-tier")
        best = _best_of(
            lambda rep: _dist_workload(stub, f"tier-r{rep}"), DIST_REPEATS
        )
        assignments = {rid: [] for rid in stub.router.replica_ids}
        for name in best.pop("study_names"):
            assignments[stub.router.replica_for(name)].append(name)
        per_replica = _per_replica_breakdown(stub.stats(), assignments)
    finally:
        for proc in procs:
            proc.terminate()
        for proc in procs:
            proc.wait(timeout=10)
    return best, per_replica


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument(
        "--side", choices=("repo", "reference", "both"), default="both"
    )
    ap.add_argument(
        "--replicas",
        type=int,
        default=0,
        help="also run the sharded-tier A/B with N replicas (0 = skip)",
    )
    ap.add_argument(
        "--replica-mode",
        choices=("inprocess", "subprocess"),
        default="inprocess",
    )
    ap.add_argument(
        "--dist-arm",
        choices=("single_server", "multi_replica"),
        default=None,
        help=argparse.SUPPRESS,  # child-process entry for run_distributed
    )
    args = ap.parse_args()

    if args.dist_arm:
        run_dist_arm(args.dist_arm, max(1, args.replicas), args.replica_mode)
        return

    if args.side == "reference":
        rows = run_reference()
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"reference": rows}, f, indent=1)
            print(f"wrote {args.out}")
        return

    report = {
        "topology": (
            "one DefaultVizierServer per side, real localhost gRPC, "
            "per-worker clients created before the clock"
        ),
        "algorithm": "RANDOM_SEARCH",
        "repo": run_repo(),
    }
    if args.side == "both":
        _ensure_refcopy()
        # Subprocess keeps the reference's `vizier` import + proto
        # registrations out of this process.
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--side", "reference"],
            capture_output=True,
            text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        if proc.returncode != 0:
            raise RuntimeError(f"reference side failed:\n{proc.stderr[-3000:]}")
        report["reference"] = [
            json.loads(line)
            for line in proc.stdout.splitlines()
            if line.startswith("{")
        ]
        report["speedup_vs_reference"] = {
            f"{r['clients']}x{r['trials_each']}": round(
                r["trials_per_s"] / ref["trials_per_s"], 2
            )
            for r, ref in zip(report["repo"], report["reference"])
        }
        print(json.dumps(report["speedup_vs_reference"]))

    if args.replicas:
        report["distributed"] = run_distributed(args.replicas, args.replica_mode)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
