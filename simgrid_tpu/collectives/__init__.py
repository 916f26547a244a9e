"""Collective schedule tapes: device-resident comm DAGs.

Compiles static communication schedules (ring / recursive-doubling /
reduce-bcast allreduce, pairwise / bruck alltoall, binomial bcast —
mirroring smpi/coll.py — plus captured NAS-style phase DAGs) into the
(pred, ready, edges, exec) tape the superstep while_loop walks with
no host involvement: ops/lmm_drain.DrainSim(collective=...) solo,
ops/lmm_batch.BatchDrainSim(collective=...) for fleets.

Layering: schedule (per-rank op IR + DAG builder + generators) ->
topology (route/constraint lowering: three synthetic flavors and
the routed one, built from a loaded platform) -> tape (DeviceCollective, the
compiled arrays) -> maestro (the host-driven bit-identity oracle) ->
spec (the campaign/serving sweep dimension).
"""

from .maestro import HostMaestro
from .schedule import (CollectiveSchedule, CommRec, GENERATORS, Prog,
                       build_schedule, generate, seq_allreduce_lr,
                       seq_allreduce_rdb, seq_allreduce_redbcast,
                       seq_alltoall_bruck, seq_alltoall_pairwise,
                       seq_bcast_binomial, seq_reduce_flat)
from .spec import CollectiveSpec
from .tape import DeviceCollective
from .topology import FLAVORS, RoutedTopology, Topology

__all__ = [
    "CollectiveSchedule", "CollectiveSpec", "CommRec",
    "DeviceCollective", "FLAVORS", "GENERATORS", "HostMaestro",
    "Prog", "RoutedTopology", "Topology", "build_schedule", "generate",
    "seq_allreduce_lr", "seq_allreduce_rdb", "seq_allreduce_redbcast",
    "seq_alltoall_bruck", "seq_alltoall_pairwise",
    "seq_bcast_binomial", "seq_reduce_flat",
]
