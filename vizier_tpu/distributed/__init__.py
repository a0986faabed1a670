"""Horizontally sharded service tier.

One ``VizierService`` replica serves one shard of the study population;
studies are assigned to replicas by rendezvous hashing of their resource
names (``routing.StudyRouter``), clients reach the owning replica through a
drop-in stub wrapper (``router_stub.RoutedVizierStub`` — ``VizierClient``
code is unchanged), each replica's RAM datastore persists through a
snapshot + write-ahead log (``wal.PersistentDataStore``) so replicas
restart warm, WAL appends stream to each study's rendezvous successors'
standby logs (``replication.py``) so failover needs **no shared
filesystem**, and ``replica_manager.ReplicaManager`` health-checks the
fleet and fails a dead replica's studies over to their rendezvous
successors — the reliability layer's retries absorb the transition.

Deployment topologies (docs/guides/running_the_service.md, "Sharded
deployment"):

- **in-process** — N ``VizierServicer`` replicas behind one
  ``ReplicaManager``, all feeding ONE shared Pythia (designer cache,
  coalescer, cross-study batch executor). No transport hop: the router IS
  the channel. This is the tier ``tools/chaos_ab.py --distributed`` kills
  replicas in.
- **subprocess / multi-host** — N ``DefaultVizierServer`` processes
  (``python -m vizier_tpu.distributed.replica_main``), routed over real
  gRPC channels; each process hosts its own Pythia, persists epoch-fenced
  standby logs for its rendezvous predecessors on its own disk, and
  streams its WAL appends to successors over the ``ReplicationService``
  gRPC surface (``replication_service.py``).
  ``subprocess_fleet.SubprocessReplicaManager`` spawns and manages the
  fleet with lease-based failure detection (heartbeat RPCs; death on
  lease expiry), fence-first failover from standby logs over the wire,
  and partition tolerance (``testing.netchaos``): a partitioned-away
  replica that comes back finds its stale appends rejected by fenced
  standby stores.

``ShardedDataStore`` is the datastore-granularity analogue: one service
process partitioning its studies across per-shard stores through the same
rendezvous hash.
"""

from vizier_tpu.distributed.config import DistributedConfig
from vizier_tpu.distributed.replica_manager import ReplicaManager
from vizier_tpu.distributed.replication import (
    ReplicationStreamer,
    StandbyStore,
)
from vizier_tpu.distributed.replication_service import (
    GrpcReplicationLink,
    ReplicaReplicationHost,
    ReplicationServicer,
)
from vizier_tpu.distributed.router_stub import RoutedVizierStub
from vizier_tpu.distributed.routing import StudyRouter
from vizier_tpu.distributed.sharded_datastore import ShardedDataStore
from vizier_tpu.distributed.subprocess_fleet import SubprocessReplicaManager
from vizier_tpu.distributed.wal import PersistentDataStore, WriteAheadLog

__all__ = [
    "DistributedConfig",
    "GrpcReplicationLink",
    "PersistentDataStore",
    "ReplicaManager",
    "ReplicaReplicationHost",
    "ReplicationServicer",
    "ReplicationStreamer",
    "RoutedVizierStub",
    "ShardedDataStore",
    "StandbyStore",
    "StudyRouter",
    "SubprocessReplicaManager",
    "WriteAheadLog",
]
