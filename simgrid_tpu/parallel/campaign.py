"""Batched scenario campaigns: fleets of what-if simulations drained in
lockstep device programs.

The campaign layer is STAGED (the serving refactor, ISSUE 11):

* :class:`ScenarioSpec` — one replica's scenario record, with stable
  content hashing (:meth:`ScenarioSpec.key`) and JSON round-tripping so
  specs can travel between processes and index caches;
* :class:`ScenarioPlan` — the spec-independent middle stage: ONE
  platform flattening (a pure-drain LMM system, captured from a live
  engine via ``NetworkCm02Model.capture_drain_scenario()`` or built
  from arrays) plus solver configuration.  A plan derives per-spec
  overrides/tapes, owns the content-addressed :meth:`ScenarioPlan.
  plan_key` ``(topology-hash, layout, dtype, B, superstep, pipeline,
  mesh, fault_mode)`` that the serving AOT plan cache
  (``serving/plancache.py``) keys compiled executables by, and builds
  executors (:meth:`ScenarioPlan.executor`) and solo oracles
  (:meth:`ScenarioPlan.solo`);
* :class:`Campaign` — the batch front-end over (plan, specs): the
  historical API is unchanged (``run_batched``/``run_solo``/
  ``run_scoped``), base-scenario attributes delegate to the plan.

Each spec contributes *sweep overrides* (global bandwidth / flow-size
multipliers, sparse per-link and per-flow factors, dead flows) and an
optional *fault dimension* — a seeded
:class:`~simgrid_tpu.faults.FaultCampaign` per replica, so a Monte
Carlo fault sweep is just N seeds.  How the schedule is realized is
the ``faults/tape`` flag (or the ``fault_mode`` constructor argument):
``on`` (default) compiles it into a device-resident EVENT TAPE —
links fail and recover mid-drain at the exact schedule dates, the
superstep loop clamping dt so no advance steps over an event — while
``static`` demotes it to the pre-tape time-averaged capacity
multipliers (``FaultCampaign.mean_availability``) and ``off`` ignores
it.

The fleet is stepped through :class:`~simgrid_tpu.ops.lmm_batch.
BatchDrainSim` in chunks of ``batch`` replicas: one shared platform
upload, compact per-replica payloads, lockstep supersteps with an
alive mask, and per-replica completion rings demultiplexed back into
per-replica event streams.  Every replica's event order and clocks are
bit-identical to the same scenario drained solo
(:meth:`ScenarioPlan.solo` is the oracle the determinism tooling
compares against), so batching is purely a throughput choice.
``mesh=M`` shards each fleet's replica axis across M devices
(``NamedSharding(mesh, PartitionSpec("batch"))`` on every [B, ·]
array, shared flattening replicated — see ops.lmm_batch).

The s4u Engine is a process singleton, so replicas are kernel-level
scenario instances sharing one flattening — the drain phase is where
fleet scale pays (the maestro loop outside it is per-process).
"""

from __future__ import annotations

import hashlib
import json
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from ..collectives.spec import CollectiveSpec
from ..faults import FaultCampaign
from ..ops import opstats
from ..ops.device import solve_dtype
from ..ops.lmm_batch import (BatchDrainSim, ReplicaOverrides,
                             derive_replica_arrays, derive_replica_ew)
from ..ops.lmm_jax import SolveError

#: a fully-failed link would zero its capacity and stall every flow
#: routed over it; campaigns clamp availability-derived factors here
#: (a pure drain has no retry path — a dead link means a dead drain)
MIN_LINK_FACTOR = 0.05


def _canon_pairs(d: Dict[int, float]) -> List[List[float]]:
    """Canonical JSON form of a sparse {slot: factor} map: sorted
    [slot, factor] pairs (dict insertion order must never leak into a
    content hash)."""
    return [[int(k), float(d[k])] for k in sorted(d)]


def _pairs_to_map(pairs) -> Dict[int, float]:
    if isinstance(pairs, dict):
        return {int(k): float(pairs[k]) for k in sorted(pairs, key=int)}
    return {int(k): float(v) for k, v in (pairs or [])}


class ScenarioSpec:
    """One replica's scenario: seed + sweep overrides + fault model.

    ``fault_mtbf``/``fault_mttr`` (simulated seconds) switch the fault
    dimension on: every link gets a seeded failure/repair schedule over
    ``fault_horizon``.  How the schedule is realized is the campaign's
    ``fault_mode``: a device event tape (links flip mid-drain at the
    exact dates, failures clamped to ``MIN_LINK_FACTOR``), or a folded
    time-averaged capacity multiplier (``static``, same clamp), or
    nothing (``off``).  Identical seeds give identical scenarios,
    bit-for-bit.

    Specs are content-addressable: :meth:`key` is a stable sha256 over
    the canonical JSON form (sorted keys, sorted sparse maps, ``label``
    excluded — it is presentation only), so the same scenario hashes
    identically across processes and field orderings.  :meth:`to_json`
    / :meth:`from_json` round-trip the full record including the label.
    """

    __slots__ = ("seed", "bw_scale", "size_scale", "link_scale",
                 "flow_scale", "dead_flows", "elem_w", "fault_mtbf",
                 "fault_mttr", "fault_dist", "fault_shape",
                 "fault_horizon", "collective", "label")

    def __init__(self, seed: int = 0, bw_scale: float = 1.0,
                 size_scale: float = 1.0,
                 link_scale: Optional[Dict[int, float]] = None,
                 flow_scale: Optional[Dict[int, float]] = None,
                 dead_flows: Iterable[int] = (),
                 elem_w: Optional[Dict[int, float]] = None,
                 fault_mtbf: Optional[float] = None,
                 fault_mttr: float = 60.0,
                 fault_dist: str = "exponential",
                 fault_shape: float = 1.0,
                 fault_horizon: float = 1000.0,
                 collective: Optional[CollectiveSpec] = None,
                 label: Optional[str] = None):
        self.seed = int(seed)
        self.bw_scale = float(bw_scale)
        self.size_scale = float(size_scale)
        self.link_scale = dict(link_scale or {})
        self.flow_scale = dict(flow_scale or {})
        self.dead_flows = tuple(dead_flows)
        self.elem_w = dict(elem_w or {})
        self.fault_mtbf = fault_mtbf
        self.fault_mttr = float(fault_mttr)
        self.fault_dist = fault_dist
        self.fault_shape = float(fault_shape)
        self.fault_horizon = float(fault_horizon)
        if isinstance(collective, dict):
            collective = CollectiveSpec.from_dict(collective)
        #: optional CollectiveSpec: the comm-DAG workload this spec is
        #: meant for.  Specs carrying one only run on a plan compiled
        #: for the SAME collective (campaign/serving validate by key)
        self.collective = collective
        self.label = label if label is not None else f"seed{seed}"

    # -- stable serialization / content addressing -------------------------

    def to_dict(self, with_label: bool = True) -> Dict:
        """Canonical dict form: sparse maps as sorted [slot, factor]
        pairs, dead flows sorted — a pure function of the scenario
        CONTENT, independent of construction order."""
        d = {"seed": self.seed,
             "bw_scale": self.bw_scale,
             "size_scale": self.size_scale,
             "link_scale": _canon_pairs(self.link_scale),
             "flow_scale": _canon_pairs(self.flow_scale),
             "dead_flows": sorted(int(s) for s in self.dead_flows),
             "elem_w": _canon_pairs(self.elem_w),
             "fault_mtbf": (None if self.fault_mtbf is None
                            else float(self.fault_mtbf)),
             "fault_mttr": self.fault_mttr,
             "fault_dist": str(self.fault_dist),
             "fault_shape": self.fault_shape,
             "fault_horizon": self.fault_horizon}
        if self.collective is not None:
            # present ONLY when set: legacy (collective-free) specs
            # keep their pinned hashes
            d["collective"] = self.collective.to_dict()
        if with_label:
            d["label"] = self.label
        return d

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), sort_keys=True,
                          separators=(",", ":"))

    @classmethod
    def from_dict(cls, d: Dict) -> "ScenarioSpec":
        return cls(seed=d.get("seed", 0),
                   bw_scale=d.get("bw_scale", 1.0),
                   size_scale=d.get("size_scale", 1.0),
                   link_scale=_pairs_to_map(d.get("link_scale")),
                   flow_scale=_pairs_to_map(d.get("flow_scale")),
                   dead_flows=tuple(int(s)
                                    for s in d.get("dead_flows", ())),
                   elem_w=_pairs_to_map(d.get("elem_w")),
                   fault_mtbf=d.get("fault_mtbf"),
                   fault_mttr=d.get("fault_mttr", 60.0),
                   fault_dist=d.get("fault_dist", "exponential"),
                   fault_shape=d.get("fault_shape", 1.0),
                   fault_horizon=d.get("fault_horizon", 1000.0),
                   collective=d.get("collective"),
                   label=d.get("label"))

    @classmethod
    def from_json(cls, text: str) -> "ScenarioSpec":
        return cls.from_dict(json.loads(text))

    def key(self) -> str:
        """Stable content hash (sha256 hex) of the scenario identity —
        the ``label`` is excluded, so renaming a query never misses a
        cache.  Pinned by a regression test: the hash must not move
        under field reordering or dict-insertion-order changes."""
        canon = json.dumps(self.to_dict(with_label=False),
                           sort_keys=True, separators=(",", ":"))
        return hashlib.sha256(canon.encode("utf-8")).hexdigest()


class ReplicaResult:
    """Per-replica campaign outcome (the demultiplexed 'engine')."""

    __slots__ = ("spec", "events", "t", "advances", "error",
                 "fault_events", "collective_events")

    def __init__(self, spec: ScenarioSpec, events, t: float,
                 advances: int, error: Optional[str],
                 fault_events=None, collective_events=None):
        self.spec = spec
        self.events = events          # [(time, flow slot)] solo order
        self.t = t
        self.advances = advances
        self.error = error
        #: (time, constraint slot) per fired tape event, fire order
        #: (empty unless the campaign runs in faults/tape:on mode)
        self.fault_events = list(fault_events or [])
        #: (time, flow slot) per schedule-tape activation, fire order
        #: (empty unless the plan carries a collective)
        self.collective_events = list(collective_events or [])


def _mesh_size(mesh) -> int:
    """Normalize a mesh argument to its device count for cache keys
    (0 = unsharded)."""
    if mesh is None:
        return 0
    if isinstance(mesh, int):
        return int(mesh)
    return int(np.prod(list(mesh.shape.values())))


class ScenarioPlan:
    """The spec-independent stage of a campaign: one shared pure-drain
    flattening + solver configuration.

    A plan (a) derives per-spec scenarios (``overrides_for`` /
    ``tape_for``), (b) is content-addressed — :meth:`topology_hash`
    covers the flattening arrays and solver config, :meth:`plan_key`
    adds the execution shape ``(layout, dtype, B, superstep, pipeline,
    mesh, fault_mode)`` — so AOT-compiled fleet programs can be cached
    and reloaded across processes (serving/plancache.py), and (c)
    builds executors: :meth:`executor` returns a ready
    :class:`~simgrid_tpu.ops.lmm_batch.BatchDrainSim` fleet,
    :meth:`solo` runs the bit-identity oracle for one spec.
    """

    def __init__(self, e_var, e_cnst, e_w, c_bound, sizes,
                 remains=None, penalty=None, v_bound=None,
                 link_names: Optional[List[Optional[str]]] = None,
                 eps: Optional[float] = None, done_eps: float = 1e-4,
                 dtype=None, done_mode: str = "rel",
                 superstep: int = 8, pipeline: int = 0, mesh=None,
                 fault_mode: Optional[str] = None,
                 collective: Optional[CollectiveSpec] = None,
                 _device_collective=None):
        self.e_var = np.asarray(e_var, np.int32)
        self.e_cnst = np.asarray(e_cnst, np.int32)
        self.e_w = np.asarray(e_w, np.float64)
        self.c_bound = np.asarray(c_bound, np.float64)
        self.sizes = np.asarray(sizes, np.float64)
        self.remains = (np.asarray(remains, np.float64)
                        if remains is not None else None)
        self.penalty = (np.asarray(penalty, np.float64)
                        if penalty is not None else None)
        self.v_bound = (np.asarray(v_bound, np.float64)
                        if v_bound is not None else None)
        self.link_names = link_names
        self.done_eps = float(done_eps)
        # None = the device's own solver dtype (float64 where it is
        # IEEE, float32 on the TPU).  A collective plan needs float64,
        # so on a device without it the request is refused here by name
        self.dtype = (solve_dtype(np.float64, "ScenarioPlan(collective=)")
                      if dtype is None and collective is not None
                      else solve_dtype(dtype, "ScenarioPlan(dtype=)"))
        # ... and the solver epsilon that dtype can resolve: the
        # oracle's 1e-9 in float64, maxmin/precision's 1e-5 in float32
        self.eps = (float(eps) if eps is not None
                    else 1e-9 if self.dtype == np.float64 else 1e-5)
        self.done_mode = done_mode
        self.superstep = int(superstep)
        self.pipeline = int(pipeline)
        self.mesh = mesh
        if fault_mode is None:
            from ..utils.config import config
            fault_mode = str(config["faults/tape"])
        if fault_mode not in ("on", "static", "off"):
            raise ValueError(f"Unknown fault_mode {fault_mode!r} "
                             "(expected on, static or off)")
        #: how specs' fault dimension is realized: "on" = device event
        #: tapes (mid-drain capacity flips), "static" = folded
        #: mean-availability multipliers, "off" = ignored
        self.fault_mode = fault_mode
        if isinstance(collective, dict):
            collective = CollectiveSpec.from_dict(collective)
        #: optional CollectiveSpec: when set, the plan's flattening IS
        #: the compiled comm DAG and every executor walks its schedule
        #: tape on device (see collectives/)
        self.collective = collective
        self._dc = None
        if collective is not None:
            if self.dtype != np.float64:
                raise ValueError(
                    "collective schedule tapes require dtype float64 "
                    "(the superstep clock is carried on device)")
            dc = (_device_collective if _device_collective is not None
                  else collective.build())
            if len(self.sizes) != dc.n_v or len(self.c_bound) != dc.n_c:
                raise ValueError(
                    f"plan arrays ({len(self.sizes)} flows, "
                    f"{len(self.c_bound)} links) do not match the "
                    f"collective's compiled tape ({dc.n_v} flows, "
                    f"{dc.n_c} links); build the plan with "
                    f"ScenarioPlan.for_collective")
            if self.penalty is None:
                self.penalty = np.asarray(dc.penalty0, np.float64)
            elif not np.array_equal(self.penalty, dc.penalty0):
                raise ValueError(
                    "plan penalty does not match the collective's "
                    "root-activation mask (dc.penalty0)")
            self._dc = dc
        #: constraint slots that actually carry elements — fault
        #: schedules are drawn for these only (padding slots have no
        #: flows and scaling them is pure noise in the RNG stream)
        used = np.zeros(len(self.c_bound), bool)
        used[self.e_cnst[self.e_w > 0]] = True
        self._used_links = np.flatnonzero(used)
        self._topology_hash: Optional[str] = None

    # -- content addressing ------------------------------------------------

    def topology_hash(self) -> str:
        """Stable sha256 over the shared flattening + solver config:
        two plans with the same hash trace to byte-identical fleet
        programs (given the same execution shape — see plan_key)."""
        if self._topology_hash is None:
            h = hashlib.sha256()
            for name, arr in (("e_var", self.e_var),
                              ("e_cnst", self.e_cnst),
                              ("e_w", self.e_w),
                              ("c_bound", self.c_bound),
                              ("sizes", self.sizes),
                              ("remains", self.remains),
                              ("penalty", self.penalty),
                              ("v_bound", self.v_bound)):
                h.update(name.encode())
                if arr is None:
                    h.update(b"<none>")
                else:
                    h.update(str(arr.shape).encode())
                    h.update(arr.tobytes())
            names = (list(self.link_names)
                     if self.link_names is not None else None)
            h.update(json.dumps(names).encode())
            h.update(json.dumps([self.eps, self.done_eps,
                                 self.done_mode]).encode())
            if self.collective is not None:
                # folded in only when present: legacy plans keep their
                # cached hashes (and cached AOT executables)
                h.update(b"collective")
                h.update(self.collective.key().encode())
            self._topology_hash = h.hexdigest()
        return self._topology_hash

    def plan_key(self, batch: int, pipeline: Optional[int] = None,
                 mesh=None) -> str:
        """The content-addressed cache key for compiled fleet programs:
        ``(topology-hash, layout, dtype, B, superstep, pipeline, mesh,
        fault_mode)`` hashed to one hex digest.  Anything that changes
        the traced program or the shapes it was specialized for changes
        the key; anything that doesn't (spec values, labels) doesn't."""
        from ..utils.config import config
        depth = self.pipeline if pipeline is None else int(pipeline)
        use_mesh = self.mesh if mesh is None else mesh
        canon = json.dumps([self.topology_hash(),
                            str(config["lmm/layout"]),
                            self.dtype.name, int(batch),
                            self.superstep, depth,
                            _mesh_size(use_mesh), self.fault_mode],
                           separators=(",", ":"))
        return hashlib.sha256(canon.encode("utf-8")).hexdigest()

    @classmethod
    def for_collective(cls, cspec: CollectiveSpec, exec_cost=None,
                       **kw) -> "ScenarioPlan":
        """Build a plan whose flattening IS one collective's compiled
        comm DAG: the tape arrays come from ``cspec.build()`` and the
        plan carries the spec, so ``plan_key`` content-addresses the
        (algorithm × ranks × topology) sweep point for the AOT plan
        cache.  Solver/config kwargs pass through."""
        dc = cspec.build(exec_cost=exec_cost)
        return cls(dc.e_var, dc.e_cnst, dc.e_w, dc.c_bound, dc.sizes,
                   penalty=dc.penalty0, collective=cspec,
                   _device_collective=dc, **kw)

    def _check_collective(self, spec: ScenarioSpec) -> None:
        """A spec carrying a collective only runs on a plan compiled
        for the same one — a silent mismatch would report a different
        workload's clocks under the spec's label."""
        if self.collective is not None and spec.dead_flows:
            raise ValueError(
                f"spec {spec.label!r} kills flows "
                f"{spec.dead_flows} but the plan walks a schedule "
                f"tape — a dead record would deadlock its successors")
        if spec.collective is None:
            return
        if self.collective is None:
            raise ValueError(
                f"spec {spec.label!r} carries collective "
                f"{spec.collective.label()} but the plan has none")
        if spec.collective.key() != self.collective.key():
            raise ValueError(
                f"spec {spec.label!r} carries collective "
                f"{spec.collective.label()} but the plan was compiled "
                f"for {self.collective.label()}")

    # -- per-spec scenario derivation --------------------------------------

    def _link_name(self, slot: int) -> str:
        if self.link_names is not None and slot < len(self.link_names) \
                and self.link_names[slot]:
            return str(self.link_names[slot])
        return f"link{slot}"

    def _fault_campaign(self, spec: ScenarioSpec
                        ) -> Tuple[FaultCampaign, Dict[str, int]]:
        """Seeded per-replica FaultCampaign over the used links, plus
        the name → constraint-slot map.  Registration order is the slot
        order, so the RNG substream layout is a pure function of the
        spec — the tape, the static folding and an engine-side
        ``schedule()`` of the same campaign all see identical draws."""
        fc = FaultCampaign(seed=spec.seed, horizon=spec.fault_horizon)
        names: Dict[str, int] = {}
        for slot in self._used_links:
            name = self._link_name(int(slot))
            names[name] = int(slot)
            fc.add_link(name, mtbf=spec.fault_mtbf,
                        mttr=spec.fault_mttr, dist=spec.fault_dist,
                        shape=spec.fault_shape)
        return fc, names

    def tape_len(self, spec: ScenarioSpec) -> int:
        """Number of event-tape entries this spec's seeded schedule
        would compile to (0 when the fault dimension is off for this
        plan/spec).  Cheap capacity probe for admission sizing — no
        replica arrays are derived."""
        if self.fault_mode != "on" or spec.fault_mtbf is None:
            return 0
        fc, _ = self._fault_campaign(spec)
        return fc.tape_len(floor=MIN_LINK_FACTOR)

    def overrides_for(self, spec: ScenarioSpec) -> ReplicaOverrides:
        """Fold one spec's sweep overrides — and, in ``static`` fault
        mode, its time-averaged fault schedule — into the compact
        per-replica override record.  Pure function of the spec (the
        FaultCampaign draw is seeded), so the solo oracle and the batch
        path derive the identical scenario."""
        link_scale = dict(spec.link_scale)
        if spec.fault_mtbf is not None and self.fault_mode == "static":
            fc, names = self._fault_campaign(spec)
            for (kind, name), avail in sorted(
                    fc.mean_availability().items()):
                if avail >= 1.0:
                    continue
                slot = names[name]
                factor = max(avail, MIN_LINK_FACTOR)
                link_scale[slot] = link_scale.get(slot, 1.0) * factor
        return ReplicaOverrides(bw_scale=spec.bw_scale,
                                size_scale=spec.size_scale,
                                link_scale=link_scale,
                                flow_scale=spec.flow_scale,
                                dead_flows=spec.dead_flows,
                                elem_w=spec.elem_w)

    def tape_for(self, spec: ScenarioSpec
                 ) -> Optional[Tuple[np.ndarray, np.ndarray,
                                     np.ndarray]]:
        """Compile one spec's fault schedule into the device event-tape
        triple ``(dates f64, constraint slots i32, new bounds f64)``
        consumed by DrainSim/BatchDrainSim.  ``None`` when the fault
        mode isn't ``on``, the spec has no fault dimension, or the
        seeded schedule is empty.  Bound values are ABSOLUTE post-event
        capacities derived from the replica's own swept ``c_bound`` —
        a factor-1.0 repair restores the replica bound exactly."""
        if self.fault_mode != "on" or spec.fault_mtbf is None:
            return None
        fc, names = self._fault_campaign(spec)
        entries = fc.compile_tape(floor=MIN_LINK_FACTOR)
        if not entries:
            return None
        base_rem = (self.remains if self.remains is not None
                    else self.sizes)
        base_pen = (self.penalty if self.penalty is not None
                    else np.ones(len(self.sizes)))
        cb, _, _, _ = derive_replica_arrays(
            self.c_bound, self.sizes, base_rem, base_pen,
            self.overrides_for(spec))
        t = np.empty(len(entries), np.float64)
        s = np.empty(len(entries), np.int32)
        v = np.empty(len(entries), np.float64)
        for i, (date, kind, name, factor) in enumerate(entries):
            slot = names[name]
            t[i] = date
            s[i] = slot
            v[i] = cb[slot] * factor
        return t, s, v

    # -- executors ---------------------------------------------------------

    def executor(self, specs: Sequence[ScenarioSpec],
                 width: Optional[int] = None,
                 superstep_rounds: int = 0,
                 pipeline: Optional[int] = None, mesh=None,
                 plan_cache=None, tape_slots: int = 0,
                 batch_w: Optional[bool] = None,
                 watchdog=None) -> BatchDrainSim:
        """Build one ready fleet executor for ``specs``.  ``width``
        sizes the fleet wider than the initial spec list — the extra
        lanes are dead from birth and available for mid-flight
        admission (serving).  ``plan_cache`` (a serving.plancache.
        PlanCache) routes the fleet's jitted programs through
        AOT-compiled executables keyed by :meth:`plan_key`.
        ``watchdog`` (an ops.lmm_batch.DispatchWatchdog) wraps every
        fleet dispatch in wall-clock accounting + bounded seeded-
        backoff retries."""
        specs = list(specs)
        width = len(specs) if width is None else int(width)
        if width < len(specs):
            raise ValueError("executor width smaller than spec count")
        for s in specs:
            self._check_collective(s)
        overrides = [self.overrides_for(s) for s in specs]
        overrides += [ReplicaOverrides()
                      for _ in range(width - len(specs))]
        tapes = [self.tape_for(s) for s in specs]
        tapes += [None] * (width - len(specs))
        if not any(t is not None for t in tapes) and not tape_slots:
            tapes = None
        depth = self.pipeline if pipeline is None else int(pipeline)
        use_mesh = self.mesh if mesh is None else mesh
        compiled = None
        if plan_cache is not None:
            compiled = plan_cache.plan(
                self.plan_key(width, pipeline=depth, mesh=use_mesh))
        return BatchDrainSim(
            self.e_var, self.e_cnst, self.e_w, self.c_bound,
            self.sizes, overrides, eps=self.eps,
            done_eps=self.done_eps, dtype=self.dtype,
            done_mode=self.done_mode, superstep=self.superstep,
            superstep_rounds=superstep_rounds,
            v_bound=self.v_bound, penalty=self.penalty,
            remains=self.remains, pipeline=depth, mesh=use_mesh,
            tapes=tapes, plan=compiled, tape_slots=tape_slots,
            start_dead=tuple(range(len(specs), width)),
            batch_w=batch_w, watchdog=watchdog,
            collective=(self._dc.drain_args()
                        if self._dc is not None else None))

    def solo(self, spec: ScenarioSpec,
             superstep_rounds: int = 0) -> ReplicaResult:
        """Drain ONE spec with the solo executor
        (ops.lmm_drain.DrainSim) over host-derived scenario arrays —
        the bit-identity oracle for the batched AND served paths.
        Repacks are disabled to match the fleet's lockstep
        (fixed-shape) program; event order and clocks are
        repack-invariant anyway, but the oracle keeps the dispatch
        structure aligned too."""
        from ..ops.lmm_drain import DrainSim
        self._check_collective(spec)
        ov = self.overrides_for(spec)
        base_rem = (self.remains if self.remains is not None
                    else self.sizes)
        base_pen = (self.penalty if self.penalty is not None
                    else np.ones(len(self.sizes)))
        cb, sz, rem, pen = derive_replica_arrays(
            self.c_bound, self.sizes, base_rem, base_pen, ov)
        ew = derive_replica_ew(self.e_w, ov, self.dtype)
        sim = DrainSim(self.e_var, self.e_cnst, ew,
                       cb.astype(self.dtype), sz, eps=self.eps,
                       done_eps=self.done_eps, dtype=self.dtype,
                       done_mode=self.done_mode,
                       superstep=self.superstep,
                       superstep_rounds=superstep_rounds,
                       v_bound=(self.v_bound.astype(self.dtype)
                                if self.v_bound is not None else None),
                       penalty=pen, remains=rem, repack_min=1 << 62,
                       tape=self.tape_for(spec),
                       collective=(self._dc.drain_args()
                                   if self._dc is not None else None))
        error = None
        try:
            sim.run()
        except SolveError as exc:
            error = str(exc)
        return ReplicaResult(spec, sim.events, sim.t, sim.advances,
                             error, fault_events=sim.fault_events,
                             collective_events=sim.collective_events)


class Campaign:
    """A scenario fleet over one shared pure-drain flattening: the
    batch front-end over ``(ScenarioPlan, specs)``.  Base-scenario
    attributes and derivations (``e_var`` ... ``fault_mode``,
    ``overrides_for``, ``tape_for``) delegate to :attr:`plan`."""

    def __init__(self, e_var, e_cnst, e_w, c_bound, sizes,
                 specs: Sequence[ScenarioSpec],
                 remains=None, penalty=None, v_bound=None,
                 link_names: Optional[List[Optional[str]]] = None,
                 eps: Optional[float] = None, done_eps: float = 1e-4,
                 dtype=None, done_mode: str = "rel",
                 superstep: int = 8, pipeline: int = 0, mesh=None,
                 fault_mode: Optional[str] = None, plan_cache=None,
                 collective: Optional[CollectiveSpec] = None):
        self.plan = ScenarioPlan(
            e_var, e_cnst, e_w, c_bound, sizes, remains=remains,
            penalty=penalty, v_bound=v_bound, link_names=link_names,
            eps=eps, done_eps=done_eps, dtype=dtype,
            done_mode=done_mode, superstep=superstep,
            pipeline=pipeline, mesh=mesh, fault_mode=fault_mode,
            collective=collective)
        self.specs = list(specs)
        #: optional serving.plancache.PlanCache: when set, fleet
        #: programs run through AOT-compiled executables keyed by the
        #: plan key (warm restarts skip tracing entirely)
        self.plan_cache = plan_cache

    def __getattr__(self, name: str):
        # base-scenario attributes live on the plan stage since the
        # serving split; the pre-refactor Campaign carried them
        # directly, so delegate to keep the historical surface
        plan = self.__dict__.get("plan")
        if plan is None or name.startswith("__"):
            raise AttributeError(name)
        return getattr(plan, name)

    # -- construction from a live engine ----------------------------------

    @classmethod
    def from_engine(cls, model, specs: Sequence[ScenarioSpec], **kw
                    ) -> "Campaign":
        """Capture the CURRENT pure-drain phase of a network model (the
        drain fast path's own preconditions, see
        ``NetworkCm02Model.capture_drain_scenario``) as the fleet's
        shared base scenario.  Raises when the phase is not a pure
        drain — a campaign must start from a well-defined snapshot, not
        silently diverge from the engine."""
        snap = capture_plan_snapshot(model)
        return cls(snap["e_var"], snap["e_cnst"], snap["e_w"],
                   snap["c_bound"], snap["sizes"],
                   remains=snap["remains"], penalty=snap["penalty"],
                   v_bound=snap["v_bound"],
                   link_names=snap["link_names"], specs=specs, **kw)

    @classmethod
    def for_collective(cls, cspec: CollectiveSpec,
                       specs: Sequence[ScenarioSpec], **kw
                       ) -> "Campaign":
        """A campaign over one collective's compiled comm DAG — see
        :meth:`ScenarioPlan.for_collective`."""
        dc = cspec.build()
        return cls(dc.e_var, dc.e_cnst, dc.e_w, dc.c_bound, dc.sizes,
                   specs, penalty=dc.penalty0, collective=cspec, **kw)

    # -- execution ---------------------------------------------------------

    def run_batched(self, batch: int = 64, superstep_rounds: int = 0,
                    pipeline: Optional[int] = None, mesh=None
                    ) -> List[ReplicaResult]:
        """Drain the whole fleet in chunks of ``batch`` replicas, each
        chunk one BatchDrainSim (one shared upload, lockstep
        supersteps).  Results come back in spec order; chunking is
        invisible to results — lanes are independent.  ``pipeline``
        overrides the campaign's speculative-superstep depth and
        ``mesh`` its replica-axis device sharding for this run
        (bit-identical results either way)."""
        results: List[ReplicaResult] = []
        for start in range(0, len(self.specs), max(1, int(batch))):
            chunk_specs = self.specs[start:start + max(1, int(batch))]
            sim = self.plan.executor(
                chunk_specs, superstep_rounds=superstep_rounds,
                pipeline=pipeline, mesh=mesh,
                plan_cache=self.plan_cache)
            sim.run()
            for b, spec in enumerate(chunk_specs):
                rep = sim.replicas[b]
                results.append(ReplicaResult(
                    spec, rep.events, rep.t, rep.advances, rep.error,
                    fault_events=rep.fault_events,
                    collective_events=rep.collective_events))
        return results

    def run_solo(self, index: int,
                 superstep_rounds: int = 0) -> ReplicaResult:
        """The bit-identity oracle for spec ``index`` — see
        :meth:`ScenarioPlan.solo`."""
        return self.plan.solo(self.specs[index],
                              superstep_rounds=superstep_rounds)

    def run_scoped(self, batch: int, stage: str,
                   pipeline: Optional[int] = None, mesh=None
                   ) -> Tuple[List[ReplicaResult], Dict[str, float]]:
        """run_batched under an opstats stage scope: returns (results,
        this run's counter deltas) — the campaign's own dispatches and
        upload bytes, unpolluted by whatever ran before in the
        process."""
        with opstats.scoped(stage) as stats:
            results = self.run_batched(batch=batch, pipeline=pipeline,
                                       mesh=mesh)
        return results, stats


def capture_plan_snapshot(model) -> Dict:
    """Capture the current pure-drain phase of a live network model as
    the array dict ScenarioPlan/Campaign construct from.  Raises when
    the phase is not a pure drain."""
    snap = model.capture_drain_scenario()
    if snap is None:
        raise RuntimeError(
            "capture_drain_scenario: the current phase is not a "
            "pure drain (flows still in latency phase, suspended, "
            "deadlined, or a non-flow variable is live)")
    return snap
