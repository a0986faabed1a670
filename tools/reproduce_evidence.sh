#!/bin/bash
# Reproduces the regret and robustness artifacts at the repo root from a
# clean checkout. Everything here runs on the CPU and states no speed:
# rates and latencies are measured on the chip by the benchmark's cells
# (BENCHMARK.json, chipbench/README.md; the driver's record is
# PERF_LEDGER.jsonl). Approximate runtimes are from a quiet 8-core container.
set -e
cd "$(dirname "$0")/.."

echo "== 0. static analysis: lock order / JAX discipline / env registry (~2 s) =="
#    zero unbaselined violations (docs/guides/static_analysis.md)
python tools/check_analysis.py

echo "== 1. tier-1 tests, the driver's flags (CPU; ~10 min with six xdist workers) =="
JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 \
  --dist loadfile -p no:randomly

echo "== 3b. failover chaos: kill one replica mid-study (~1 min) =="
#    -> CHAOS_AB.json gains the distributed_failover arm (50/50 trials
#    complete via router failover + WAL handoff), the replicated_failover
#    arm (--no-shared-fs: the dead replica's WAL directory is DELETED at
#    the kill; 50/50 still completes via the successors' replication
#    standby logs), the subprocess_partition arm (real replica_main
#    processes with lease-based failure detection: SIGKILL the owner AND
#    a netchaos partition-then-heal window; standby recovery over gRPC,
#    fenced stale-append rejection, replication-off bit-identity), the
#    mesh_executor arm (device-program failure isolated to ONE placement
#    of an 8-device mesh), and the runtime lock-order cross-check — now
#    including the per-placement mesh dispatch workers, the replication
#    streamer threads, AND the subprocess fleet's lease/netchaos locks —
#    vs the static graph
JAX_PLATFORMS=cpu python tools/chaos_ab.py --distributed 4 --mesh-devices 8 \
  --no-shared-fs --replica-mode subprocess --partition --instrument-locks

echo "== 3b3. SLO-armed observability soak (~2 min) =="
#    -> OBSERVABILITY_E2E.json (v2): 2-replica tier with SLOs armed +
#    flight recorder on; an induced p99 breach writes a black-box dump
#    whose exemplar trace_ids resolve to complete traces in the merged
#    per-replica span dumps; the fleet merge (obs_report --fleet) stitches
#    cross-replica traces and the failover timeline from recorder events
JAX_PLATFORMS=cpu python tools/chaos_ab.py --trials 50 --slo-soak \
  --out /tmp/chaos_slo.json

echo "== 3b5. hot-tenant overload A/B (~3 min) =="
#    -> OVERLOAD_AB.json: the loadgen hot_tenant scenario (one Zipf-head
#    tenant flooding GP compute at a saturating OPEN-LOOP rate,
#    time_scale=1 real arrival pacing) with the admission plane ON vs
#    OFF; asserts light-tenant suggest p99 within the SLO budget + zero
#    lost studies + sheds nonzero and confined to the hot tenant + sheds
#    never trip a breaker with the plane ON, the p99 collapse with it
#    OFF, and VIZIER_ADMISSION=0 bit-identity vs the sequential
#    reference (docs/guides/reliability.md "Overload protection")
JAX_PLATFORMS=cpu python tools/overload_ab.py

echo "== 3b4. full-stack loadgen soak (slow arm, ~20 min) =="
#    -> SOAK_REPORT.json: >=1000 Zipf-sized studies across every
#    registered program kind on a 2-replica WAL-backed tier, speculation
#    + batching + mesh + SLO armed, kill/revive + chaos mid-run; asserts
#    regret parity (rank-sum vs the sequential reference arm), zero lost
#    studies, failover completeness, bounded fallback rate, SLO p99
#    verdicts, and bit-identical gated-off trajectories in one verdict
#    (docs/guides/loadtest.md; render with tools/obs_report.py --soak)
JAX_PLATFORMS=cpu python tools/soak.py --mesh-devices 2

echo "== 3b6. disaggregated compute tier A/B (~1 min) =="
#    -> COMPUTE_TIER_AB.json: 8 frontends sharing ONE real
#    pythia_server_main subprocess vs 8 self-contained replicas on the
#    same-bucket GP workload (target: shared batch-flush occupancy >= 4x
#    the self-contained arm, p50/p99 both arms), a mid-run compute-server
#    SIGKILL completing 50/50 via each frontend's local fallback, and the
#    VIZIER_COMPUTE_TIER=0 bit-identity check (wrap identity + matching
#    trajectories); the fleet merge attributes all 8 frontends on the
#    remote-hop spans (docs/guides/running_the_service.md
#    "Disaggregated compute tier")
JAX_PLATFORMS=cpu python tools/compute_tier_ab.py

echo "== 4. budget-policy A/B, 5 seeds x 3 families (~45 min) =="
#    -> budget_ab_r5.json
JAX_PLATFORMS=cpu python tools/budget_policy_ab.py

echo "== 5. full designer-parity suite (~11 min) =="
#    -> regret_report_r5.json
JAX_PLATFORMS=cpu python parity_suite.py --out /tmp/regret.json

echo "== 6. multichip dryrun on an 8-device virtual mesh (~2 min) =="
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
  python -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('ok')"

echo "all evidence reproduced"
