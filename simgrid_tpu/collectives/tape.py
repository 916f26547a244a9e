"""Schedule -> device tape compilation.

``DeviceCollective`` lowers a :class:`~.schedule.CollectiveSchedule`
onto a :class:`~.topology.Topology`: every comm record becomes one
LMM flow slot (variable = record id), its route the element rows, and
the dependency sets become the (pred-count, successor-edge, exec-cost)
arrays the superstep while_loop walks autonomously — the full tape
row of the ISSUE: (pred, src, dst, route-slots, size, exec-cost).
Beside the edge list (grouped by successor) lies its source-major
index, from which an advance that finished few flows walks their
successor edges alone.

Activation protocol (mirrored exactly by maestro.HostMaestro):

* records with no predecessors and no exec cost start LIVE
  (penalty 1, no activation event);
* records with predecessors start DORMANT (penalty 0, full remains,
  pred count = |preds|, ready = +inf).  When the last predecessor
  completes at clock t, the device schedules ready = t + exec_cost
  and a LATER advance lands on that date, scatters penalty 1.0 and
  logs the tagged ring entry ``id = -(1 + n_c + flow)``;
* root records WITH exec cost start dormant with ready = exec_cost —
  the compute leg of a compute/comm phase runs before the wire.

``exec_cost`` defaults to the topology's own delays: nothing on the
synthetic flavors, the route's latency (under the network model's
latency factor) on a routed one, so a block is on the wire only after
both its ranks finished the step before AND its latency has passed.

Zero-byte payloads (a barrier's b"" token) are clamped to one byte:
a zero-size flow can never cross the relative retirement threshold,
and both the tape and the host maestro apply the same clamp, so
bit-identity is unaffected.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ..ops import opstats
from .schedule import CollectiveSchedule
from .topology import Topology


class DeviceCollective:
    """The compiled tape: platform arrays + DAG walk arrays."""

    __slots__ = ("schedule", "topology", "n_v", "n_c", "e_var",
                 "e_cnst", "e_w", "c_bound", "sizes", "penalty0",
                 "pred0", "ready0", "edge_src", "edge_dst", "exec_cost",
                 "v_ptr", "ve_idx", "_succ")

    def __init__(self, schedule: CollectiveSchedule,
                 topology: Topology,
                 exec_cost: Optional[np.ndarray] = None):
        if topology.ranks != schedule.ranks:
            raise ValueError(
                f"topology is for {topology.ranks} ranks, schedule "
                f"for {schedule.ranks}")
        self.schedule = schedule
        self.topology = topology
        recs = schedule.records
        n_v = len(recs)
        if n_v == 0:
            raise ValueError("schedule has no communications")
        self.n_v = n_v
        with opstats.span("coll.lower", id="tape"):
            src = np.fromiter((r.src for r in recs), np.int64, count=n_v)
            dst = np.fromiter((r.dst for r in recs), np.int64, count=n_v)
            self.sizes = np.maximum(
                np.fromiter((r.size for r in recs), np.float64,
                            count=n_v), 1.0)
            self.pred0 = np.fromiter((len(r.preds) for r in recs),
                                     np.int32, count=n_v)
            if self.pred0.any():
                # successor edges, grouped by successor, predecessors
                # ascending within a group
                es = np.fromiter((p.rid for r in recs for p in r.preds),
                                 np.int64, count=int(self.pred0.sum()))
                ed = np.repeat(np.arange(n_v), self.pred0)
                order = np.lexsort((es, ed))
                es, ed = es[order], ed[order]
            else:
                # keep the edge arrays non-empty: a single dropped-slot
                # row (dst = n_v scatters into the drop lane)
                es, ed = [0], [n_v]
            self.edge_src = np.asarray(es, np.int32)
            self.edge_dst = np.asarray(ed, np.int32)
            self._succ = None
            self.succ_index()

        # records are in rid order (rid = index), so a transfer's index
        # is its flow slot.  A routed topology looks its routes up HERE
        # (its own ``routes`` span, between the tape's two), and knows
        # its constraints only afterwards
        ev, ec, ew = topology.lower(src, dst)
        with opstats.span("coll.lower", id="tape"):
            self.n_c = topology.n_c
            self.c_bound = np.asarray(topology.c_bound, np.float64)
            if exec_cost is None:
                # a routed topology delays every record by its route's
                # latency; the synthetic flavors by nothing
                ex = np.asarray(topology.delays(src, dst), np.float64)
            else:
                ex = np.asarray(exec_cost, np.float64)
                if ex.shape != (n_v,):
                    raise ValueError(f"exec_cost must have one entry "
                                     f"per record ({n_v}), got {ex.shape}")
            self.exec_cost = ex
            self.e_var = np.asarray(ev, np.int32)
            self.e_cnst = np.asarray(ec, np.int32)
            self.e_w = np.asarray(ew, np.float64)
            # which elements belong to which flow: with it a solve
            # reaches the few live flows' elements without a pass over
            # the list (ops.lmm_jax.var_index; one argsort, here and not
            # per sim)
            from ..ops.lmm_jax import var_index
            self.v_ptr, self.ve_idx = var_index(self.e_var, self.e_w, n_v)
            roots = self.pred0 == 0
            timed_root = roots & (ex > 0)
            self.penalty0 = np.where(roots & ~timed_root, 1.0, 0.0)
            self.ready0 = np.where(timed_root, ex, np.inf)

    @property
    def n_edges(self) -> int:
        return int(np.count_nonzero(self.edge_dst < self.n_v))

    def succ_index(self):
        """The edge list's source-major index ``(s_ptr, s_dst)``
        (ops.lmm_drain.succ_index), with which an advance walks the
        successor edges of its own completions.  One argsort of the
        edges, kept with the two arrays it was made from: a sim is made
        every lap, the index again only when ``edge_src`` or
        ``edge_dst`` is another array than it was (a test that cuts
        edges out assigns new ones)."""
        from ..ops.lmm_drain import succ_index
        if (self._succ is None or self._succ[0] is not self.edge_src
                or self._succ[1] is not self.edge_dst):
            self._succ = (self.edge_src, self.edge_dst, succ_index(
                self.edge_src, self.edge_dst, self.n_v))
        return self._succ[2]

    def drain_args(self):
        """The ``collective=`` 5-tuple for DrainSim/BatchDrainSim."""
        return (self.pred0, self.ready0, self.edge_src, self.edge_dst,
                self.exec_cost)

    def make_sim(self, superstep: int = 16, pipeline: int = 0,
                 tape=None, device=None, **kw):
        """A ready-to-run tape-driven DrainSim over this collective, in
        the dtype ``lmm/dtype:auto`` resolves on ``device`` (float64
        where it is IEEE, float32 on the TPU) unless ``dtype=`` says
        otherwise."""
        from ..ops.lmm_drain import DrainSim
        kw.setdefault("dtype", "auto")
        return DrainSim(self.e_var, self.e_cnst, self.e_w,
                        self.c_bound, self.sizes,
                        superstep=superstep, pipeline=pipeline,
                        penalty=self.penalty0, tape=tape,
                        device=device, collective=self.drain_args()
                        + (self.v_ptr, self.ve_idx) + self.succ_index(),
                        **kw)

    def key(self) -> tuple:
        return ("dcoll", self.n_v, self.n_c, self.topology.key(),
                float(self.sizes.sum()), int(self.pred0.sum()))
