"""The single source of truth for every ``VIZIER_*`` switch.

Every environment variable the tree reads (and every reserved ``VIZIER_*``
constant that is *not* an environment variable) is declared here with its
owner and documentation link. The ``env_registry`` analysis pass fails any
``os.environ`` read — direct or through the helpers below — of a name that
is missing from this table, and any declared switch whose doc file does
not mention it.

Runtime code reads switches through :func:`env_on` / :func:`env_int` /
:func:`env_float` / :func:`env_str`, which raise on undeclared names — so
a typo'd switch fails loudly at import time instead of silently reading an
always-unset variable.

Stdlib-only on purpose: config modules all over the tree import this, and
the analysis pass must be runnable without jax installed.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, Tuple

# Declaration kinds:
#   "flag"     — boolean-ish on/off switch ("0"/"false"/"" = off);
#   "int"      — integer-valued;
#   "float"    — float-valued;
#   "str"      — free-form string (paths, names);
#   "constant" — a reserved VIZIER_* Python constant that is NOT an
#                environment variable (reading it from os.environ is a
#                violation; declaring it here keeps the literal scan and
#                naive greps honest about what is and is not a switch).
_KINDS = ("flag", "int", "float", "str", "constant")


@dataclasses.dataclass(frozen=True)
class EnvSwitch:
    """One declared ``VIZIER_*`` name."""

    name: str
    kind: str
    owner: str  # owning config class or module
    doc: str  # repo-relative doc path that describes the switch
    description: str
    # Default *as read* ("1" = on unless explicitly disabled). Only
    # meaningful for env kinds; constants have no runtime default.
    default: str = ""

    def __post_init__(self):
        if self.kind not in _KINDS:
            raise ValueError(f"Unknown switch kind {self.kind!r} for {self.name}.")
        if not self.name.startswith("VIZIER_"):
            raise ValueError(f"Switch {self.name!r} must start with VIZIER_.")


def _switch(name, kind, owner, doc, description, default=""):
    return EnvSwitch(name, kind, owner, doc, description, default)


_OBS_DOC = "docs/guides/observability.md"
_REL_DOC = "docs/guides/reliability.md"
_SRV_DOC = "docs/guides/serving.md"
_PERF_DOC = "docs/guides/performance.md"
_SWITCH_DOC = "docs/guides/switching_from_oss_vizier.md"
_RUN_DOC = "docs/guides/running_the_service.md"
_LOAD_DOC = "docs/guides/loadtest.md"

SWITCHES: Tuple[EnvSwitch, ...] = (
    # -- observability (ObservabilityConfig) -------------------------------
    _switch("VIZIER_OBSERVABILITY", "flag", "ObservabilityConfig", _OBS_DOC,
            "Master switch for tracing/metrics/JAX profiling.", "1"),
    _switch("VIZIER_OBSERVABILITY_TRACING", "flag", "ObservabilityConfig",
            _OBS_DOC, "Span tracing on/off (counters stay).", "1"),
    _switch("VIZIER_OBSERVABILITY_METRICS", "flag", "ObservabilityConfig",
            _OBS_DOC, "Latency histograms on/off.", "1"),
    _switch("VIZIER_OBSERVABILITY_JAX", "flag", "ObservabilityConfig",
            _OBS_DOC, "Designer device-phase timers (forces syncs).", "1"),
    _switch("VIZIER_OBSERVABILITY_SPAN_BUFFER", "int", "ObservabilityConfig",
            _OBS_DOC, "Finished-span ring-buffer size.", "4096"),
    _switch("VIZIER_OBSERVABILITY_SPAN_LOG", "str", "ObservabilityConfig",
            _OBS_DOC, "JSON-lines span sink path ('' = ring only)."),
    # -- SLO engine (SloConfig) --------------------------------------------
    _switch("VIZIER_SLO", "flag", "SloConfig", _OBS_DOC,
            "Arm the SLO engine: sliding-window error-budget burn rates "
            "+ breach handling (opt-in; unset/0 = no engine, no sampler).",
            "0"),
    _switch("VIZIER_SLO_WINDOWS", "str", "SloConfig", _OBS_DOC,
            "Comma-separated sliding windows in seconds.", "60,300"),
    _switch("VIZIER_SLO_EVAL_INTERVAL_S", "float", "SloConfig", _OBS_DOC,
            "Background evaluation cadence (0 = manual evaluate() only).",
            "1.0"),
    _switch("VIZIER_SLO_SUGGEST_P99_MS", "float", "SloConfig", _OBS_DOC,
            "Objective: 99% of suggests per hop under this many ms.",
            "5000.0"),
    _switch("VIZIER_SLO_SPECULATIVE_HIT_RATE", "float", "SloConfig",
            _OBS_DOC,
            "Objective: minimum speculative serve hit rate (evaluated "
            "only when the window saw speculative traffic).", "0.8"),
    _switch("VIZIER_SLO_FALLBACK_RATE", "float", "SloConfig", _OBS_DOC,
            "Objective: maximum quasi-random fallback fraction.", "0.05"),
    _switch("VIZIER_SLO_SHED_RATE", "float", "SloConfig", _OBS_DOC,
            "Objective: maximum admission-shed fraction of suggests.",
            "0.05"),
    _switch("VIZIER_SLO_DUMP_DIR", "str", "SloConfig", _OBS_DOC,
            "Black-box dump directory for SLO breaches ('' = no dumps)."),
    # -- flight recorder (FlightRecorderConfig) ----------------------------
    _switch("VIZIER_FLIGHT_RECORDER", "flag", "FlightRecorderConfig",
            _OBS_DOC,
            "Per-study flight recorder of structured lifecycle events "
            "(opt-in; unset/0 = the stateless no-op recorder).", "0"),
    _switch("VIZIER_FLIGHT_RECORDER_RING", "int", "FlightRecorderConfig",
            _OBS_DOC, "Events kept per study ring.", "256"),
    _switch("VIZIER_FLIGHT_RECORDER_STUDIES", "int", "FlightRecorderConfig",
            _OBS_DOC, "Study rings kept (LRU-evicted past this).", "1024"),
    # -- fleet aggregation (observability.fleet) ---------------------------
    _switch("VIZIER_OBS_DUMP_DIR", "str", "replica_main", _OBS_DOC,
            "Per-replica observability dump directory: span/metric/"
            "recorder files written on shutdown for fleet merging."),
    # -- reliability (ReliabilityConfig) -----------------------------------
    _switch("VIZIER_RELIABILITY", "flag", "ReliabilityConfig", _REL_DOC,
            "Master switch for retries/deadlines/breaker/fallback.", "1"),
    _switch("VIZIER_RELIABILITY_RETRIES", "flag", "ReliabilityConfig",
            _REL_DOC, "Retry transient RPC/op failures.", "1"),
    _switch("VIZIER_RELIABILITY_DEADLINE", "flag", "ReliabilityConfig",
            _REL_DOC, "Deadline attachment and propagation.", "1"),
    _switch("VIZIER_RELIABILITY_BREAKER", "flag", "ReliabilityConfig",
            _REL_DOC, "Per-study circuit breaker.", "1"),
    _switch("VIZIER_RELIABILITY_FALLBACK", "flag", "ReliabilityConfig",
            _REL_DOC, "Quasi-random fallback on designer failure.", "1"),
    # -- multi-tenant admission (serving.admission.AdmissionConfig) --------
    _switch("VIZIER_ADMISSION", "flag", "AdmissionConfig", _REL_DOC,
            "Multi-tenant overload protection: fair-share admission, "
            "load shedding, deadline-aware rejection, graceful "
            "degradation (opt-in; unset/0 = the bit-identical "
            "pre-admission path).", "0"),
    _switch("VIZIER_ADMISSION_MAX_INFLIGHT", "int", "AdmissionConfig",
            _REL_DOC,
            "Fleet-wide cap on concurrent designer computations.", "16"),
    _switch("VIZIER_ADMISSION_TENANT_INFLIGHT", "int", "AdmissionConfig",
            _REL_DOC,
            "Per-tenant cap on concurrent designer computations.", "8"),
    _switch("VIZIER_ADMISSION_WEIGHTS", "str", "AdmissionConfig", _REL_DOC,
            "Fair-share weights, 'tenant:w,...' (unlisted tenants = 1.0); "
            "drives the DRR quantum and the degraded-mode priority split."),
    _switch("VIZIER_ADMISSION_RETRY_AFTER_MS", "float", "AdmissionConfig",
            _REL_DOC,
            "Backoff-floor hint stamped into shed errors.", "50"),
    _switch("VIZIER_ADMISSION_DEADLINE", "flag", "AdmissionConfig", _REL_DOC,
            "Deadline-aware rejection: shed when the remaining budget "
            "cannot cover estimated queue wait + compute p50.", "1"),
    _switch("VIZIER_ADMISSION_DEGRADED", "flag", "AdmissionConfig", _REL_DOC,
            "Graceful degradation under sustained saturation (the "
            "healthy/shedding/degraded state machine's last stage).", "1"),
    _switch("VIZIER_ADMISSION_DEGRADED_FLOOR", "float", "AdmissionConfig",
            _REL_DOC,
            "Tenants with weight below this serve quasi-random in "
            "degraded mode; others keep GP compute.", "1.0"),
    _switch("VIZIER_ADMISSION_DEGRADE_RATE", "float", "AdmissionConfig",
            _REL_DOC,
            "Windowed shed rate at which SHEDDING escalates to DEGRADED.",
            "0.5"),
    _switch("VIZIER_ADMISSION_RECOVER_RATE", "float", "AdmissionConfig",
            _REL_DOC,
            "Windowed shed rate below which DEGRADED may recover "
            "(hysteretic: must hold for a full window).", "0.1"),
    _switch("VIZIER_ADMISSION_WINDOW_S", "float", "AdmissionConfig",
            _REL_DOC,
            "Sliding decision window for the overload state machine.",
            "5.0"),
    # -- serving (ServingConfig) -------------------------------------------
    _switch("VIZIER_SERVING_CACHE", "flag", "ServingConfig", _SRV_DOC,
            "Per-study designer-state cache.", "1"),
    _switch("VIZIER_SERVING_WARM_START", "flag", "ServingConfig", _SRV_DOC,
            "Warm-started ARD training.", "1"),
    _switch("VIZIER_SERVING_COALESCING", "flag", "ServingConfig", _SRV_DOC,
            "Compute-level request coalescing.", "1"),
    _switch("VIZIER_BATCHING", "flag", "ServingConfig", _PERF_DOC,
            "Cross-study batch executor.", "1"),
    _switch("VIZIER_BATCH_MAX_SIZE", "int", "ServingConfig", _PERF_DOC,
            "Micro-batch flush size.", "8"),
    _switch("VIZIER_BATCH_MAX_WAIT_MS", "float", "ServingConfig", _PERF_DOC,
            "Micro-batch flush window (ms).", "4.0"),
    _switch("VIZIER_BATCHING_PREWARM", "flag", "ServingConfig", _PERF_DOC,
            "Background AOT compile of batched programs.", "0"),
    _switch("VIZIER_COMPILE_CACHE_DIR", "str", "ServingConfig", _PERF_DOC,
            "JAX persistent compilation cache directory."),
    # -- distributed (DistributedConfig) -----------------------------------
    _switch("VIZIER_DISTRIBUTED", "flag", "DistributedConfig", _RUN_DOC,
            "Study-affinity router (off = first replica serves all).", "1"),
    _switch("VIZIER_DISTRIBUTED_REPLICAS", "int", "DistributedConfig",
            _RUN_DOC, "Replica count for env-built sharded tiers.", "4"),
    _switch("VIZIER_DISTRIBUTED_WAL_DIR", "str", "DistributedConfig",
            _RUN_DOC, "Snapshot+WAL root ('' = RAM only, no restart warmth)."),
    _switch("VIZIER_DISTRIBUTED_SNAPSHOT_INTERVAL", "int", "DistributedConfig",
            _RUN_DOC, "Mutations per shard between WAL compactions.", "256"),
    _switch("VIZIER_DISTRIBUTED_WAL_FSYNC", "flag", "DistributedConfig",
            _RUN_DOC, "fsync the WAL per append (power-loss durability).", "0"),
    _switch("VIZIER_DISTRIBUTED_ROUTE_CACHE_SIZE", "int", "StudyRouter",
            _RUN_DOC, "LRU cap on the router's placement cache.", "65536"),
    _switch("VIZIER_DISTRIBUTED_REPLICATION", "flag", "DistributedConfig",
            _RUN_DOC,
            "Stream WAL appends to each study's rendezvous successors' "
            "standby logs so failover needs no shared filesystem "
            "(0 = local-disk-only failover, the pre-replication path).",
            "1"),
    _switch("VIZIER_DISTRIBUTED_REPLICATION_FACTOR", "int",
            "DistributedConfig", _RUN_DOC,
            "Standby copies per study (K rendezvous successors).", "2"),
    _switch("VIZIER_DISTRIBUTED_REPLICATION_QUEUE", "int",
            "DistributedConfig", _RUN_DOC,
            "Per-origin replication streamer queue bound; overflow drops "
            "and re-baselines rather than blocking the write path.",
            "4096"),
    _switch("VIZIER_DISTRIBUTED_REPLICATION_BATCH", "int",
            "DistributedConfig", _RUN_DOC,
            "Records per streamed replication batch.", "64"),
    _switch("VIZIER_DISTRIBUTED_LEASE_TIMEOUT_S", "float",
            "DistributedConfig", _RUN_DOC,
            "Seconds without a renewed heartbeat before the fleet manager "
            "declares a subprocess replica dead and fails it over.", "3.0"),
    _switch("VIZIER_DISTRIBUTED_HEARTBEAT_INTERVAL_S", "float",
            "DistributedConfig", _RUN_DOC,
            "Cadence of the manager's lease-renewal Heartbeat probes to "
            "subprocess replicas.", "1.0"),
    _switch("VIZIER_NETCHAOS", "str", "replica_main", _RUN_DOC,
            "Seeded network fault-injection schedule for a replica's "
            "outbound replication links (testing.netchaos spec string; "
            "'' = no injection)."),
    # -- disaggregated compute tier (ComputeTierConfig) --------------------
    _switch("VIZIER_COMPUTE_TIER", "flag", "ComputeTierConfig", _RUN_DOC,
            "Disaggregated compute tier: frontends dispatch Pythia "
            "suggest/early-stop to one shared standalone compute server "
            "(opt-in; unset/0 = the bit-identical self-contained path).",
            "0"),
    _switch("VIZIER_COMPUTE_TIER_ENDPOINT", "str", "ComputeTierConfig",
            _RUN_DOC,
            "host:port of the shared Pythia compute server ('' with the "
            "tier enabled behaves as tier-down: every request takes the "
            "fallback path)."),
    _switch("VIZIER_COMPUTE_TIER_FALLBACK", "str", "ComputeTierConfig",
            _RUN_DOC,
            "Degradation mode when the tier is unreachable: 'local' "
            "serves from the frontend's own minimal Pythia; 'fail' "
            "surfaces the transport error to the client.", "local"),
    _switch("VIZIER_COMPUTE_TIER_HEALTH_INTERVAL_S", "float",
            "ComputeTierConfig", _RUN_DOC,
            "Cooldown after a compute-tier failure before a frontend "
            "re-probes the remote endpoint (the fallback serves "
            "meanwhile).", "1.0"),
    # -- speculative pre-compute (SpeculativeConfig) -----------------------
    _switch("VIZIER_SPECULATIVE", "flag", "SpeculativeConfig", _SRV_DOC,
            "Background pre-compute of the next suggestion batch after "
            "each completion (opt-in; unset/0 = the exact request path).",
            "0"),
    _switch("VIZIER_SPECULATIVE_WORKERS", "int", "SpeculativeConfig",
            _SRV_DOC, "Speculative worker-pool size.", "1"),
    _switch("VIZIER_SPECULATIVE_MAX_AGE_S", "float", "SpeculativeConfig",
            _SRV_DOC,
            "Staleness deadline: a parked batch older than this is never "
            "served.", "300.0"),
    _switch("VIZIER_SPECULATIVE_ON_FILL", "flag", "SpeculativeConfig",
            _SRV_DOC,
            "Also pre-compute after each live suggest (for a second "
            "client at the post-suggest frontier).", "0"),
    _switch("VIZIER_SPECULATIVE_COUNT_MEMORY", "int", "SpeculativeConfig",
            _SRV_DOC,
            "Distinct recent request counts remembered per study; jobs "
            "speculate the largest so bigger requests stop missing.", "4"),
    _switch("VIZIER_SPECULATIVE_DEBOUNCE_MS", "float", "SpeculativeConfig",
            _SRV_DOC,
            "Trigger debounce: a completion burst coalesces into one "
            "pre-compute after this quiet window (0 = immediate).", "0"),
    # -- surrogates (SurrogateConfig) --------------------------------------
    _switch("VIZIER_SPARSE", "flag", "SurrogateConfig", _PERF_DOC,
            "Sparse-GP surrogate auto-switch (off = exact GP always).", "1"),
    _switch("VIZIER_SPARSE_THRESHOLD", "int", "SurrogateConfig", _PERF_DOC,
            "Completed trials at which a study turns sparse.", "512"),
    _switch("VIZIER_SPARSE_HYSTERESIS", "int", "SurrogateConfig", _PERF_DOC,
            "Trial hysteresis before a sparse study returns to exact.", "64"),
    _switch("VIZIER_SPARSE_INDUCING", "int", "SurrogateConfig", _PERF_DOC,
            "Inducing-point budget m (padded to the trial bucket grid).",
            "128"),
    _switch("VIZIER_SPARSE_UCB_PE", "flag", "SurrogateConfig", _PERF_DOC,
            "Extend the sparse auto-switch to the UCB-PE DEFAULT "
            "(0 = UCB-PE studies stay exact at every size).", "1"),
    # -- mesh execution plane (parallel.mesh.MeshConfig) -------------------
    _switch("VIZIER_MESH", "flag", "MeshConfig", _PERF_DOC,
            "Mesh-sharded batch execution: carve devices into placements "
            "and dispatch buckets concurrently (opt-in; unset/0 = the "
            "bit-identical single-device executor).", "0"),
    _switch("VIZIER_MESH_DEVICES", "int", "MeshConfig", _PERF_DOC,
            "Devices the mesh plane may use (0 = all).", "0"),
    _switch("VIZIER_MESH_SHARD_DEVICES", "int", "MeshConfig", _PERF_DOC,
            "Devices per placement submesh; >1 shards each flush's study "
            "axis over the placement.", "1"),
    _switch("VIZIER_MESH_COORDINATOR", "str", "MeshConfig", _PERF_DOC,
            "jax.distributed coordinator address for a multi-host mesh "
            "('' = single host)."),
    _switch("VIZIER_MESH_PROCESSES", "int", "MeshConfig", _PERF_DOC,
            "Process count for the multi-host mesh (0 = auto).", "0"),
    _switch("VIZIER_MESH_PROCESS_ID", "int", "MeshConfig", _PERF_DOC,
            "This process's id in the multi-host mesh (-1 = auto).", "-1"),
    # -- loadgen traffic engine (loadgen.models.ScenarioConfig) ------------
    _switch("VIZIER_LOADGEN_SEED", "int", "ScenarioConfig", _LOAD_DOC,
            "Scenario seed: the whole workload expansion (arrivals, "
            "sizes, mixes, events) is a pure function of it.", "0"),
    _switch("VIZIER_LOADGEN_SCALE", "float", "ScenarioConfig", _LOAD_DOC,
            "Study-count multiplier for the configured scenario.", "1.0"),
    _switch("VIZIER_LOADGEN_STUDIES", "int", "ScenarioConfig", _LOAD_DOC,
            "Base study count before scaling.", "64"),
    _switch("VIZIER_LOADGEN_TARGET", "str", "ScenarioConfig", _LOAD_DOC,
            "Serving target the driver runs against: inprocess | replicas "
            "| subprocess (real replica_main processes).",
            "replicas"),
    _switch("VIZIER_LOADGEN_EVENTS", "str", "ScenarioConfig", _LOAD_DOC,
            "Scripted event track, kind[:arg]@fraction entries ('' = the "
            "scenario's built-in kill/revive + chaos track)."),
    # -- designers ---------------------------------------------------------
    _switch("VIZIER_DISABLE_MESH", "flag", "GPBanditDesigner", _SWITCH_DOC,
            "Opt out of the multi-device auto-mesh (set = disabled).", "0"),
    # -- reserved constants (NOT environment variables) --------------------
    _switch("VIZIER_METHODS", "constant", "service.grpc_stubs",
            "docs/guides/running_the_service.md",
            "gRPC method table constant in grpc_stubs; never an env var."),
    _switch("VIZIER_SERVICE_NAME", "constant", "service.grpc_stubs",
            "docs/guides/running_the_service.md",
            "gRPC service name constant in grpc_stubs; never an env var."),
)

BY_NAME: Dict[str, EnvSwitch] = {s.name: s for s in SWITCHES}
if len(BY_NAME) != len(SWITCHES):  # pragma: no cover - declaration bug
    raise RuntimeError("Duplicate VIZIER_* switch declaration.")


def declared(name: str) -> bool:
    return name in BY_NAME


def env_switch_names() -> Tuple[str, ...]:
    """Declared names that are real environment switches (not constants)."""
    return tuple(s.name for s in SWITCHES if s.kind != "constant")


def _require(name: str) -> EnvSwitch:
    switch = BY_NAME.get(name)
    if switch is None:
        raise KeyError(
            f"Undeclared environment switch {name!r}: declare it in "
            "vizier_tpu/analysis/registry.py (and document it) first."
        )
    if switch.kind == "constant":
        raise KeyError(
            f"{name!r} is a reserved constant, not an environment switch."
        )
    return switch


def env_on(name: str, default: Optional[str] = None) -> bool:
    """Boolean switch read: unset -> declared default; "0"/"false"/"" = off."""
    switch = _require(name)
    base = switch.default if default is None else default
    return os.environ.get(name, base) not in ("0", "false", "False", "")


def env_set(name: str) -> bool:
    """True when the switch is set to a truthy value (unset -> False).

    The read shape for opt-*out* flags like ``VIZIER_DISABLE_MESH`` whose
    absence means "feature on".
    """
    return env_on(name, default="0")


def env_int(name: str, default: int) -> int:
    _require(name)
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def env_float(name: str, default: float) -> float:
    _require(name)
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def env_str(name: str, default: str = "") -> str:
    _require(name)
    return os.environ.get(name, default)
