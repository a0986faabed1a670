#!/usr/bin/env python
"""Full-stack continuous soak: production-shaped traffic → SOAK_REPORT.json.

Drives the loadgen engine (``vizier_tpu/loadgen/``) end to end:

1. **engine arm** — the scenario's full traffic (open-loop arrivals, Zipf
   study sizes, tenant + program-kind mixes across every registered
   DesignerProgram, scripted kill/revive + chaos windows) against the
   configured target (N-replica sharded tier by default) with the
   scenario's serving planes armed (speculation + batching + mesh + SLO
   on the acceptance scenario);
2. **reference arm** — the parity cohort re-run sequentially, in-process,
   every plane gated off: the seed-path ground truth;
3. **gated-off arm** — the engine itself with every plane off on the same
   cohort, asserted bit-identical to the reference.

The assertion engine rolls all three into ``SOAK_REPORT.json`` (regret
parity rank-sum, zero lost studies, failover completeness, speculative
hit rate, fallback rate, SLO p99 verdicts, bit-identity) and this CLI
exits nonzero when any assertion fails — the regression net the
defaults-ON campaign runs behind.

Usage:
    python tools/soak.py                     # acceptance-scale soak
    python tools/soak.py --smoke             # seconds-scale CI shape
    python tools/soak.py --studies 200 --replicas 4 --mesh-devices 4
    python tools/soak.py --diff A.json B.json   # compare two reports

``--diff`` compares two SOAK_REPORTs (the defaults-ON before/after
campaign gate): per-kind latency deltas, assertion verdict changes,
speculative hit-rate / fallback-rate deltas — exits nonzero on any
regression (an assertion flipping pass→fail, a hit-rate drop, a
fallback rise).

Scenario seed/scale/studies/target/events can also come from the
``VIZIER_LOADGEN*`` environment switches (docs/guides/loadtest.md).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("VIZIER_DISABLE_MESH", "1")


def _peek_int_flag(name: str, default: int) -> int:
    """Reads an int flag from argv BEFORE jax-importing modules below (the
    mesh plane needs --xla_force_host_platform_device_count set before
    jax's backend initializes)."""
    for i, arg in enumerate(sys.argv):
        if arg == name and i + 1 < len(sys.argv):
            return int(sys.argv[i + 1])
        if arg.startswith(name + "="):
            return int(arg.split("=", 1)[1])
    return default


_MESH_DEVICES = _peek_int_flag("--mesh-devices", 0)
if _MESH_DEVICES:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags
            + f" --xla_force_host_platform_device_count={_MESH_DEVICES}"
        ).strip()

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from vizier_tpu.loadgen import driver as driver_lib  # noqa: E402
from vizier_tpu.loadgen import models  # noqa: E402
from vizier_tpu.loadgen import report as report_lib  # noqa: E402


def _stamps() -> dict:
    """Provenance stamps: which serving planes and surrogates ran."""
    import jax

    from vizier_tpu.compute import registry as compute_registry

    return {
        "backend": jax.default_backend(),
        "visible_devices": jax.device_count(),
        "compute_programs": list(compute_registry.kinds()),
    }


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--smoke",
        action="store_true",
        help="the seconds-scale CI scenario instead of the acceptance soak",
    )
    parser.add_argument("--studies", type=int, default=0,
                        help="override the scenario study count")
    parser.add_argument("--seed", type=int, default=None)
    parser.add_argument("--scale", type=float, default=None)
    parser.add_argument("--target",
                        choices=("inprocess", "replicas", "subprocess",
                                 "shared_compute"),
                        default=None)
    parser.add_argument(
        "--compute-tier",
        action="store_true",
        help="run the subprocess fleet behind ONE shared Pythia compute "
        "server (the disaggregated tier): every replica_main frontend is "
        "spawned with --compute-endpoint, so suggest traffic crosses the "
        "remote hop and fuses in the shared batch executor. Shorthand "
        "for --target shared_compute; the scripted event track gains "
        "kill_compute/revive_compute events.",
    )
    parser.add_argument(
        "--replica-mode",
        choices=("inprocess", "subprocess"),
        default="inprocess",
        help="'subprocess' runs the replica tier as REAL replica_main "
        "processes behind the lease-based SubprocessReplicaManager "
        "(cross-process standby replication over gRPC; kill/revive are "
        "SIGKILL + fenced restart) — the severity track against real "
        "processes. Parity/bit-identity assertions are waived for this "
        "mode (per-study seeding cannot cross the process boundary); "
        "the in-process default keeps them, and stays the tier-1 shape.",
    )
    parser.add_argument("--replicas", type=int, default=0)
    parser.add_argument("--concurrency", type=int, default=0)
    parser.add_argument(
        "--events",
        default=None,
        help="event track: comma-separated kind[:arg]@fraction entries "
        "(default: the scenario's built-in kill/revive + chaos track)",
    )
    parser.add_argument(
        "--mesh-devices",
        type=int,
        default=0,
        metavar="N",
        help="simulate N XLA host devices for the mesh plane (0 = leave "
        "the backend alone)",
    )
    parser.add_argument(
        "--think-time", type=float, default=None,
        help="per-GP-trial evaluation window in seconds",
    )
    parser.add_argument(
        "--skip-reference",
        action="store_true",
        help="engine arm only (parity/bit-identity assertions then FAIL "
        "— for iterating on scenarios, not for evidence)",
    )
    parser.add_argument(
        "--diff",
        nargs=2,
        metavar=("A.json", "B.json"),
        default=None,
        help="compare two SOAK_REPORTs (A = before, B = after) instead "
        "of running a soak; exits nonzero on regression",
    )
    parser.add_argument(
        "--diff-out",
        default="",
        help="optional path for the --diff JSON result",
    )
    parser.add_argument(
        "--out",
        default=str(
            pathlib.Path(__file__).resolve().parent.parent / "SOAK_REPORT.json"
        ),
    )
    args = parser.parse_args()

    if args.diff:
        before = json.loads(pathlib.Path(args.diff[0]).read_text())
        after = json.loads(pathlib.Path(args.diff[1]).read_text())
        diff = report_lib.diff_reports(before, after)
        print(report_lib.render_diff(diff))
        if args.diff_out:
            pathlib.Path(args.diff_out).write_text(
                json.dumps(diff, indent=2) + "\n"
            )
            print(f"[soak] wrote {args.diff_out}")
        if not diff["ok"]:
            sys.exit(1)
        return

    # Fast client polling: the soak measures fleet behavior, not the
    # client's long-poll sleep cadence.
    from vizier_tpu.service import vizier_client

    vizier_client.environment_variables.polling_delay_secs = 0.005

    overrides = {}
    if args.seed is not None:
        overrides["seed"] = args.seed
    if args.scale is not None:
        overrides["scale"] = args.scale
    if args.studies:
        overrides["num_studies"] = args.studies
    if args.target:
        overrides["target"] = args.target
    if args.replica_mode == "subprocess" and overrides.get(
        "target", "replicas"
    ) != "inprocess":
        overrides["target"] = "subprocess"
    if args.compute_tier:
        overrides["target"] = "shared_compute"
    if args.replicas:
        overrides["replicas"] = args.replicas
    if args.concurrency:
        overrides["concurrency"] = args.concurrency
    if args.think_time is not None:
        overrides["think_time_s"] = args.think_time

    base = models.smoke_config if args.smoke else models.soak_config
    config = base(**{**_env_overrides(), **overrides})
    if (
        config.target in ("subprocess", "shared_compute")
        and not args.skip_reference
    ):
        # Parity/bit-identity are waived for subprocess tiers (see
        # --replica-mode help); the sequential arms would only burn the
        # wall clock the real-process severity track needs.
        args.skip_reference = True
        print(f"[soak] {config.target} tier: reference/gated arms skipped "
              "(parity assertions waived)", flush=True)
    if args.mesh_devices:
        config = dataclasses.replace(
            config,
            planes=dataclasses.replace(config.planes, mesh=True),
        )
    from vizier_tpu.analysis import registry as _registry

    env_track = _registry.env_str("VIZIER_LOADGEN_EVENTS")
    track = args.events if args.events is not None else env_track
    if track:
        config = dataclasses.replace(
            config, events=models.parse_event_track(track, config)
        )
    scenario = models.build_scenario(config)

    print(
        f"[soak] scenario {config.name!r}: {len(scenario.studies)} studies / "
        f"{scenario.total_trials} trials, kinds {scenario.kinds_present()}, "
        f"target {config.target} x{config.replicas}, planes "
        f"{config.planes.as_dict()}",
        flush=True,
    )
    t0 = time.time()
    engine = driver_lib.run(scenario, arm="engine")
    print(
        f"[soak] engine arm done in {engine.wall_s}s "
        f"(events fired: {[e['kind'] for e in engine.events_fired]})",
        flush=True,
    )
    reference = gated = None
    if not args.skip_reference:
        reference = driver_lib.run_reference(scenario)
        print(f"[soak] reference arm done in {reference.wall_s}s", flush=True)
        gated = driver_lib.run_gated_off(scenario)
        print(f"[soak] gated-off arm done in {gated.wall_s}s", flush=True)

    report = report_lib.build_report(
        scenario, engine, reference, gated, stamps=_stamps()
    )
    report["wall_seconds_total"] = round(time.time() - t0, 1)
    out_path = pathlib.Path(args.out)
    out_path.write_text(json.dumps(report, indent=2) + "\n")
    print(report_lib.render_verdict(report))
    print(f"[soak] wrote {out_path}")
    if not report["ok"]:
        sys.exit(1)


def _env_overrides() -> dict:
    """VIZIER_LOADGEN* env values as preset overrides (CLI flags win)."""
    from vizier_tpu.analysis import registry as _registry

    out = {
        "seed": _registry.env_int("VIZIER_LOADGEN_SEED", 0),
        "scale": _registry.env_float("VIZIER_LOADGEN_SCALE", 1.0),
    }
    studies = _registry.env_int("VIZIER_LOADGEN_STUDIES", 0)
    if studies:
        out["num_studies"] = studies
    target = _registry.env_str("VIZIER_LOADGEN_TARGET")
    if target:
        out["target"] = target
    return out


if __name__ == "__main__":
    main()
