"""The always-on campaign service: a persistent scenario queue over
live fleets with mid-flight admission and surrogate triage.

``submit(spec) -> Ticket`` enqueues one what-if query.  Low-stakes
queries (``exact=False``) are first offered to the surrogate
(:mod:`.surrogate`): a tight-interval prediction answers immediately
with ``source="surrogate"`` + conformal bounds; wide-interval queries
escalate to the device path.  ``exact=True`` always bypasses the
surrogate.

Device-path queries run on a resident :class:`~simgrid_tpu.ops.
lmm_batch.BatchDrainSim` fleet whose programs route through the AOT
plan cache (:mod:`.plancache`) — a warm restart performs zero XLA
traces.  ADMISSION BATCHING packs arriving queries into
partially-filled fleets: the service drives the fleet with
``run(between=...)`` and, between supersteps, (a) emits finished lanes
as streaming per-replica results and (b) revives dead lanes with
queued scenarios via ``admit_lane`` — an O(overrides) device scatter;
the admitted lane starts at its own k=0 with a fresh tape slot.  A
fired admission marks the fleet mutated, so in-flight pipeline
speculation discards and replays — preserving the standing invariant:
an admitted scenario's events, fault streams and Kahan clocks are
bit-identical to ``ScenarioPlan.solo`` on the same spec
(``tools/check_determinism.py --runtime-serve``).

Scenarios the live fleet cannot absorb (fault tape wider than the
fleet's reserved slots, elem_w into a shared-weight fleet) are
DEFERRED, not failed: they stay queued and the next fleet is sized for
them at birth.

The service is single-threaded and deterministic — ordering comes from
the submit order and the fleet's lockstep supersteps, never from
wall-clock races.  Wall-clock enters only as latency METADATA on
tickets (``time.perf_counter``).
"""

from __future__ import annotations

import json
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..checkpoint import CheckpointError, FleetCheckpoint
from ..ops import opstats
from ..ops.lmm_batch import (AdmissionError, DispatchExhausted,
                             LaneFault)
from ..parallel.campaign import ScenarioPlan, ScenarioSpec, _mesh_size
from .plancache import PlanCache
from .surrogate import RuntimeSurrogate


class _DrainHalt(Exception):
    """Internal drive-loop signal: ``drain(stop_after=N)`` reached its
    superstep budget.  Raised from the between-supersteps hook — the
    pipelined fleet driver's ``finally`` discards in-flight speculation
    on the way out, so the fleet is left at a committed collect
    boundary (exactly what a checkpoint needs)."""


class ServiceResult:
    """One answered query.  ``source`` is the audit field: ``"device"``
    results carry the exact event stream / clocks; ``"surrogate"``
    results carry the conformal interval they were stated at."""

    __slots__ = ("source", "t", "lo", "hi", "confidence", "events",
                 "fault_events", "advances", "error")

    def __init__(self, source: str, t: float, lo: float = None,
                 hi: float = None, confidence: float = None,
                 events=None, fault_events=None, advances: int = 0,
                 error: Optional[str] = None):
        self.source = source
        self.t = t
        self.lo = lo
        self.hi = hi
        self.confidence = confidence
        self.events = events
        self.fault_events = fault_events
        self.advances = advances
        self.error = error

    def to_dict(self) -> Dict:
        """JSON-ready journal form.  Scalars and event times are f64
        and CPython json round-trips f64 exactly (shortest-repr), so a
        checkpointed result stays bit-identical through save/load."""
        return {
            "source": self.source, "t": self.t, "lo": self.lo,
            "hi": self.hi, "confidence": self.confidence,
            "advances": self.advances, "error": self.error,
            "events": ([[t, int(i)] for t, i in self.events]
                       if self.events is not None else None),
            "fault_events": ([[t, int(s)]
                              for t, s in self.fault_events]
                             if self.fault_events is not None
                             else None),
        }

    @classmethod
    def from_dict(cls, d: Dict) -> "ServiceResult":
        ev = d.get("events")
        fev = d.get("fault_events")
        return cls(d["source"], d["t"], lo=d.get("lo"),
                   hi=d.get("hi"), confidence=d.get("confidence"),
                   events=([(float(t), int(i)) for t, i in ev]
                           if ev is not None else None),
                   fault_events=([(float(t), int(s))
                                  for t, s in fev]
                                 if fev is not None else None),
                   advances=int(d.get("advances", 0)),
                   error=d.get("error"))


class Ticket:
    """One submitted query's handle: spec, routing, and (once
    answered) the result plus submit→done latency metadata."""

    __slots__ = ("id", "spec", "exact", "status", "result", "lane",
                 "submitted_at", "done_at", "defer_reason", "fault",
                 "storms")

    def __init__(self, tid: int, spec: ScenarioSpec, exact: bool):
        self.id = tid
        self.spec = spec
        self.exact = exact
        self.status = "queued"
        self.result: Optional[ServiceResult] = None
        self.lane: Optional[int] = None
        self.submitted_at = time.perf_counter()
        self.done_at: Optional[float] = None
        self.defer_reason: Optional[str] = None
        #: structured quarantine cause when the lane serving this
        #: query was killed (ops.lmm_batch.LaneFault), else None
        self.fault: Optional[LaneFault] = None
        #: fleet generations that retired while this query sat
        #: deferred — the admission-storm trip counter
        self.storms = 0

    @property
    def latency_ms(self) -> Optional[float]:
        if self.done_at is None:
            return None
        return (self.done_at - self.submitted_at) * 1e3


class CampaignService:
    """A persistent scenario service over one :class:`ScenarioPlan`.

    ``batch`` is the resident fleet width (default: the
    ``serve/batch`` flag).  ``plan_cache`` routes fleet programs
    through AOT executables; ``surrogate`` (pass a
    :class:`RuntimeSurrogate`, or None for device-only) enables
    triage; ``corpus_log`` appends every device-served row as jsonl so
    future processes can seed their surrogate from it."""

    def __init__(self, plan: ScenarioPlan, batch: Optional[int] = None,
                 plan_cache: Optional[PlanCache] = None,
                 surrogate: Optional[RuntimeSurrogate] = None,
                 corpus_log: Optional[str] = None,
                 pipeline: Optional[int] = None, mesh=None,
                 watchdog=None, max_admission_retries: int = 8):
        from ..utils.config import config
        self.plan = plan
        self.batch = int(config["serve/batch"] if batch is None
                         else batch)
        if self.batch <= 0:
            raise ValueError("service batch must be >= 1")
        if plan_cache is None and str(config["serve/plan-cache"]):
            plan_cache = PlanCache(str(config["serve/plan-cache"]))
        self.plan_cache = plan_cache
        self.surrogate = surrogate
        self.corpus_log = corpus_log
        self.pipeline = pipeline
        self.mesh = mesh
        #: ops.lmm_batch.DispatchWatchdog guarding every fleet device
        #: dispatch; on retry exhaustion the service falls back to the
        #: solo host path for the affected queries (None = no guard)
        self.watchdog = watchdog
        #: fleet generations a deferred query may sit out before it is
        #: failed with an ``admission_storm`` LaneFault
        self.max_admission_retries = int(max_admission_retries)
        self.tickets: List[Ticket] = []
        self.completed: List[Ticket] = []
        self._queue: List[Ticket] = []
        self._fleet = None
        self._lane_tickets: List[Optional[Ticket]] = []
        # service-lifetime counters (fleet counters are aggregated in
        # on retire; see counters())
        self.fleets = 0
        self.lanes_admitted = 0
        self.surrogate_answers = 0
        self.surrogate_escalations = 0
        self.deferrals = 0
        self.spec_issued = 0
        self.spec_committed = 0
        self.spec_rolled_back = 0
        self.checkpoints = 0
        self.storm_failures = 0
        self.watchdog_solo_fallbacks = 0
        #: committed supersteps observed by THIS drain call (drives
        #: checkpoint cadence and stop_after)
        self.supersteps = 0
        # the device path exhausted its watchdog retries: every later
        # query routes straight to the solo host path
        self._device_broken = False
        # drain()-scoped checkpoint/halt directives
        self._halt_after = 0
        self._ckpt_every = 0
        self._ckpt_path: Optional[str] = None

    # -- submission --------------------------------------------------------

    def submit(self, spec: ScenarioSpec,
               exact: bool = False) -> Ticket:
        """Enqueue one query.  Surrogate triage happens HERE — a
        tight-interval prediction answers without touching the queue;
        ``exact=True`` always bypasses it."""
        # reject collective mismatches at the door: an admitted lane
        # would otherwise report a different workload's clocks
        self.plan._check_collective(spec)
        t = Ticket(len(self.tickets), spec, bool(exact))
        self.tickets.append(t)
        if not exact and self.surrogate is not None:
            ans = self.surrogate.triage(spec)
            if ans is not None:
                t.result = ServiceResult(
                    "surrogate", ans.t, lo=ans.lo, hi=ans.hi,
                    confidence=ans.confidence)
                t.status = "done"
                t.done_at = time.perf_counter()
                self.surrogate_answers += 1
                opstats.bump("surrogate_answers")
                self.completed.append(t)
                return t
            self.surrogate_escalations += 1
            opstats.bump("surrogate_escalations")
        self._queue.append(t)
        return t

    def submit_many(self, specs: Sequence[ScenarioSpec],
                    exact: bool = False) -> List[Ticket]:
        return [self.submit(s, exact=exact) for s in specs]

    def pending(self) -> int:
        return len(self._queue) + sum(
            1 for t in self._lane_tickets if t is not None)

    # -- the drive loop ----------------------------------------------------

    def _start_fleet(self) -> None:
        """Build a resident fleet from the queue head: up to ``batch``
        initial lanes, the rest of the width dead-at-birth and open
        for admission.  Capacity for LATER admissions is reserved at
        birth — tape slots sized by probing every queued faulted
        spec's schedule length, per-replica weight tables forced when
        any queued spec overrides element weights."""
        take = self._queue[:self.batch]
        del self._queue[:len(take)]
        tape_slots = 0
        need_batch_w = False
        for t in take + self._queue:
            if t.spec.fault_mtbf is not None:
                tape_slots = max(tape_slots,
                                 self.plan.tape_len(t.spec))
            if t.spec.elem_w:
                need_batch_w = True
        try:
            self._fleet = self.plan.executor(
                [t.spec for t in take], width=self.batch,
                plan_cache=self.plan_cache, tape_slots=tape_slots,
                batch_w=True if need_batch_w else None,
                pipeline=self.pipeline, mesh=self.mesh,
                watchdog=self.watchdog)
        except DispatchExhausted:
            # construction itself exhausted the watchdog (the very
            # first materialize dispatch can fail on a dead device):
            # nothing is in flight yet, so put the head back in queue
            # order for the solo fallback to serve
            self._queue[:0] = take
            raise
        self._lane_tickets = (list(take)
                              + [None] * (self.batch - len(take)))
        for b, t in enumerate(take):
            t.lane = b
        self.fleets += 1

    def _emit_completions(self, sim) -> None:
        """Stream finished lanes out as device results: feed the
        surrogate corpus, free the lane for admission."""
        for b in range(sim.B):
            t = self._lane_tickets[b]
            if t is None or sim._alive[b]:
                continue
            rep = sim.replicas[b]
            t.result = ServiceResult(
                "device", rep.t, events=list(rep.events),
                fault_events=list(rep.fault_events),
                advances=rep.advances, error=rep.error)
            t.fault = rep.fault
            t.status = "done"
            t.done_at = time.perf_counter()
            self.completed.append(t)
            self._lane_tickets[b] = None
            if rep.error is None:
                if self.surrogate is not None:
                    self.surrogate.observe(t.spec, rep.t)
                if self.corpus_log:
                    with open(self.corpus_log, "a") as f:
                        f.write(json.dumps(
                            {"spec": t.spec.to_dict(), "t": rep.t,
                             "source": "device"}) + "\n")

    def _admit(self, sim) -> bool:
        """Pack queued queries into the fleet's free (dead, emitted)
        lanes.  Scenarios the fleet cannot absorb are deferred — they
        stay queued for the next fleet, sized for them at birth."""
        admitted = False
        free = [b for b in range(sim.B)
                if self._lane_tickets[b] is None and not sim._alive[b]]
        if not free or not self._queue:
            return False
        remaining: List[Ticket] = []
        for t in self._queue:
            if not free:
                remaining.append(t)
                continue
            b = free[0]
            try:
                sim.admit_lane(b, self.plan.overrides_for(t.spec),
                               tape=self.plan.tape_for(t.spec))
            except AdmissionError as exc:
                t.defer_reason = str(exc)
                self.deferrals += 1
                remaining.append(t)
                continue
            free.pop(0)
            t.lane = b
            self._lane_tickets[b] = t
            self.lanes_admitted += 1
            admitted = True
        self._queue = remaining
        return admitted

    def _on_superstep(self, sim) -> bool:
        self._emit_completions(sim)
        mutated = self._admit(sim)
        # the hook runs once per COMMITTED superstep — the cadence
        # checkpoints and stop_after halts hang off that count.
        # Checkpoint before a potential halt so a stop_after aligned
        # with the cadence still lands its snapshot.
        self.supersteps += 1
        if (self._ckpt_every and self._ckpt_path
                and self.supersteps % self._ckpt_every == 0):
            self.checkpoint(self._ckpt_path)
        if self._halt_after and self.supersteps >= self._halt_after:
            raise _DrainHalt()
        return mutated

    def _retire_fleet(self) -> None:
        sim = self._fleet
        self.spec_issued += sim.spec_issued
        self.spec_committed += sim.spec_committed
        self.spec_rolled_back += sim.spec_rolled_back
        self._fleet = None
        self._lane_tickets = []
        # admission-storm trip: a query the retiring fleet kept
        # deferring normally fits the NEXT fleet (sized for it at
        # birth) — one that keeps missing across generations is failed
        # with a structured cause instead of spinning forever
        still: List[Ticket] = []
        for t in self._queue:
            if t.defer_reason is None:
                still.append(t)
                continue
            t.storms += 1
            if t.storms < self.max_admission_retries:
                still.append(t)
                continue
            detail = (f"admission deferred across {t.storms} fleet "
                      f"generations: {t.defer_reason}")
            t.fault = LaneFault("admission_storm", detail, -1)
            t.result = ServiceResult("device", 0.0, error=detail)
            t.status = "failed"
            t.done_at = time.perf_counter()
            self.completed.append(t)
            self.storm_failures += 1
            opstats.bump("lane_quarantined_admission_storm")
        self._queue = still

    def _serve_solo(self, t: Ticket,
                    fault: Optional[LaneFault] = None) -> None:
        """Answer one query on the solo host path (the bit-identity
        oracle itself, so the result is the one the device fleet would
        have produced).  Used after watchdog exhaustion."""
        res = self.plan.solo(t.spec)
        t.result = ServiceResult(
            "solo", res.t, events=list(res.events),
            fault_events=list(res.fault_events),
            advances=res.advances, error=res.error)
        t.fault = fault
        t.status = "done"
        t.done_at = time.perf_counter()
        self.completed.append(t)
        opstats.bump("serve_solo_results")
        if res.error is None:
            if self.surrogate is not None:
                self.surrogate.observe(t.spec, res.t)
            if self.corpus_log:
                with open(self.corpus_log, "a") as f:
                    f.write(json.dumps(
                        {"spec": t.spec.to_dict(), "t": res.t,
                         "source": "solo"}) + "\n")

    def _watchdog_fallback(self, exc: DispatchExhausted) -> None:
        """The device path exhausted its dispatch retries mid-fleet:
        flush the lanes that already finished as normal device
        results, re-serve the in-flight lanes' queries on the solo
        host path from scratch (bit-identical by the standing
        invariant; the ticket carries a ``watchdog`` LaneFault naming
        the exhaustion), and route every later query solo too."""
        sim = self._fleet
        self._device_broken = True
        self.watchdog_solo_fallbacks += 1
        opstats.bump("watchdog_solo_fallbacks")
        self._emit_completions(sim)
        for b in range(sim.B):
            t = self._lane_tickets[b]
            if t is None:
                continue
            self._serve_solo(t, fault=LaneFault(
                "watchdog",
                f"device dispatch watchdog exhausted: {exc}", b))
            self._lane_tickets[b] = None
        self._retire_fleet()

    def drain(self, stop_after: int = 0, checkpoint_every: int = 0,
              checkpoint_path: Optional[str] = None) -> List[Ticket]:
        """Serve every queued query to completion and return ALL
        completed tickets so far, in completion order.  Fleets are
        recycled: one stays resident while admissions keep it fed;
        deferred (capacity-misfit) scenarios get a fresh fleet sized
        for them once the current one drains dry.

        ``checkpoint_every=K`` with ``checkpoint_path`` writes a
        :class:`~simgrid_tpu.checkpoint.FleetCheckpoint` every K
        committed supersteps (overwriting — the token is replaced
        atomically).  ``stop_after=N`` halts after N committed
        supersteps — writing a final checkpoint when a path is set —
        and returns with the fleet still resident, so a later
        ``drain()`` (or a fresh process's :meth:`resume`) continues
        bit-identically.  A :class:`~simgrid_tpu.ops.lmm_batch.
        DispatchExhausted` from the watchdog retires the fleet onto
        the solo host path instead of raising."""
        self._halt_after = int(stop_after)
        self._ckpt_every = int(checkpoint_every)
        self._ckpt_path = checkpoint_path
        self.supersteps = 0
        try:
            while self._queue or self._fleet is not None:
                if self._fleet is None:
                    if self._device_broken:
                        while self._queue:
                            self._serve_solo(self._queue.pop(0))
                        break
                    try:
                        self._start_fleet()
                    except DispatchExhausted:
                        # dead before the fleet existed: no lanes in
                        # flight, so no per-ticket watchdog fault —
                        # the whole queue just routes solo
                        self._device_broken = True
                        self.watchdog_solo_fallbacks += 1
                        opstats.bump("watchdog_solo_fallbacks")
                        continue
                try:
                    self._fleet.run(between=self._on_superstep)
                except DispatchExhausted as exc:
                    self._watchdog_fallback(exc)
                    continue
                # fleet ran dry: everything alive finished and nothing
                # more could be admitted — final sweep, then retire
                self._emit_completions(self._fleet)
                self._retire_fleet()
        except _DrainHalt:
            if self._ckpt_path:
                self.checkpoint(self._ckpt_path)
        finally:
            self._halt_after = 0
            self._ckpt_every = 0
            self._ckpt_path = None
        return list(self.completed)

    # -- superstep-boundary checkpoint / deterministic resume --------------

    def _ticket_to_dict(self, t: Ticket) -> Dict:
        return {"id": t.id, "spec": t.spec.to_dict(),
                "exact": t.exact, "status": t.status, "lane": t.lane,
                "defer_reason": t.defer_reason, "storms": t.storms,
                "fault": (t.fault.to_dict() if t.fault is not None
                          else None),
                "result": (t.result.to_dict()
                           if t.result is not None else None)}

    @staticmethod
    def _ticket_from_dict(d: Dict) -> Ticket:
        t = Ticket(int(d["id"]), ScenarioSpec.from_dict(d["spec"]),
                   bool(d["exact"]))
        t.status = d["status"]
        t.lane = d["lane"]
        t.defer_reason = d["defer_reason"]
        t.storms = int(d.get("storms", 0))
        t.fault = (LaneFault.from_dict(d["fault"])
                   if d.get("fault") else None)
        t.result = (ServiceResult.from_dict(d["result"])
                    if d.get("result") else None)
        if t.status in ("done", "failed"):
            # latency metadata does not survive a process restart —
            # resumed tickets report 0, never a wall-clock lie
            t.submitted_at = t.done_at = 0.0
        return t

    def checkpoint(self, path: str) -> None:
        """Write one :class:`~simgrid_tpu.checkpoint.FleetCheckpoint`
        of the service: the plan's flattening arrays + solver config
        (the token is self-contained — :meth:`resume` needs no other
        input), the full ticket journal (queue order, completion
        order, per-ticket results with f64-exact streams, LaneFaults),
        and — when a fleet is resident — the BatchDrainSim COMMITTED
        state at the current collect boundary.  In-flight pipeline
        speculation is never persisted; resume replays it from
        committed state like a mispredict.  Call between supersteps
        only (``drain(checkpoint_every=...)`` does)."""
        t0 = time.perf_counter()
        plan = self.plan
        arrays: Dict[str, np.ndarray] = {
            "plan_e_var": plan.e_var, "plan_e_cnst": plan.e_cnst,
            "plan_e_w": plan.e_w, "plan_c_bound": plan.c_bound,
            "plan_sizes": plan.sizes,
        }
        for name in ("remains", "penalty", "v_bound"):
            a = getattr(plan, name)
            if a is not None:
                arrays["plan_" + name] = a
        token: Dict = {
            "plan": {
                "topology": plan.topology_hash(),
                "eps": plan.eps, "done_eps": plan.done_eps,
                "dtype": plan.dtype.name,
                "done_mode": plan.done_mode,
                "superstep": plan.superstep,
                "pipeline": plan.pipeline,
                "mesh": _mesh_size(plan.mesh),
                "fault_mode": plan.fault_mode,
                "link_names": (list(plan.link_names)
                               if plan.link_names is not None
                               else None),
                "collective": (plan.collective.to_dict()
                               if plan.collective is not None
                               else None),
            },
            "service": {
                "batch": self.batch,
                "pipeline": self.pipeline,
                "mesh": _mesh_size(self.mesh),
                "max_admission_retries": self.max_admission_retries,
                "device_broken": self._device_broken,
                "tickets": [self._ticket_to_dict(t)
                            for t in self.tickets],
                "queue": [t.id for t in self._queue],
                "completed": [t.id for t in self.completed],
                "lane_tickets": [t.id if t is not None else None
                                 for t in self._lane_tickets],
                "counters": {
                    "fleets": self.fleets,
                    "lanes_admitted": self.lanes_admitted,
                    "surrogate_answers": self.surrogate_answers,
                    "surrogate_escalations":
                        self.surrogate_escalations,
                    "deferrals": self.deferrals,
                    "spec_issued": self.spec_issued,
                    "spec_committed": self.spec_committed,
                    "spec_rolled_back": self.spec_rolled_back,
                    "checkpoints": self.checkpoints,
                    "storm_failures": self.storm_failures,
                    "watchdog_solo_fallbacks":
                        self.watchdog_solo_fallbacks,
                },
            },
            "fleet": None,
        }
        sim = self._fleet
        if sim is not None:
            st = sim.committed_state()
            for name, a in sorted(st["arrays"].items()):
                arrays["fleet_" + name] = a
            token["fleet"] = {
                "width": sim.B,
                "tape_width": (sim._tape_width if sim.has_tape
                               else 0),
                "batch_w": bool(sim.batch_w),
                "errors": st["errors"],
                "faults": st["faults"],
                "counters": st["counters"],
            }
        FleetCheckpoint(token, arrays).save(path)
        self.checkpoints += 1
        opstats.bump("fleet_checkpoints")
        opstats.bump("checkpoint_ms",
                     (time.perf_counter() - t0) * 1e3)

    @classmethod
    def resume(cls, path: str, plan: Optional[ScenarioPlan] = None,
               plan_cache: Optional[PlanCache] = None,
               surrogate: Optional[RuntimeSurrogate] = None,
               corpus_log: Optional[str] = None,
               watchdog=None) -> "CampaignService":
        """Rebuild a service from a :meth:`checkpoint` token and
        continue deterministically: the plan is reconstructed from the
        persisted flattening arrays (or validated against a passed
        ``plan`` via topology hash), the ticket journal is replayed
        into queue/completed order, and a resident fleet is rebuilt
        through :meth:`ScenarioPlan.executor` — hitting the AOT plan
        cache warm (same plan key, zero XLA traces) — then restored to
        the checkpointed committed state.  The continued drain's
        events, fault streams and Kahan clocks are bit-identical to
        the uninterrupted run
        (``tools/check_determinism.py --runtime-resume``).  Resuming
        never mutates the token: a double resume from the same path
        re-runs bit-identically."""
        ck = FleetCheckpoint.load(path)
        tok = ck.token
        pt = tok.get("plan")
        svc_tok = tok.get("service")
        if not isinstance(pt, dict) or not isinstance(svc_tok, dict):
            raise CheckpointError(
                f"fleet checkpoint {path!r} is missing its plan or "
                f"service section (foreign or truncated token)")
        if plan is None:
            kw = {}
            for name in ("remains", "penalty", "v_bound"):
                if "plan_" + name in ck.arrays:
                    kw[name] = ck.arrays["plan_" + name]
            if pt.get("collective"):
                kw["collective"] = pt["collective"]
            plan = ScenarioPlan(
                ck.arrays["plan_e_var"], ck.arrays["plan_e_cnst"],
                ck.arrays["plan_e_w"], ck.arrays["plan_c_bound"],
                ck.arrays["plan_sizes"],
                link_names=pt.get("link_names"),
                eps=pt["eps"], done_eps=pt["done_eps"],
                dtype=pt["dtype"], done_mode=pt["done_mode"],
                superstep=pt["superstep"], pipeline=pt["pipeline"],
                mesh=pt["mesh"] or None,
                fault_mode=pt["fault_mode"], **kw)
        if plan.topology_hash() != pt.get("topology"):
            raise CheckpointError(
                "fleet checkpoint topology hash does not match the "
                "plan it is being resumed onto — refusing a "
                "cross-plan resume")
        svc = cls(plan, batch=int(svc_tok["batch"]),
                  plan_cache=plan_cache, surrogate=surrogate,
                  corpus_log=corpus_log,
                  pipeline=svc_tok.get("pipeline"),
                  mesh=svc_tok.get("mesh") or None,
                  watchdog=watchdog,
                  max_admission_retries=int(
                      svc_tok.get("max_admission_retries", 8)))
        svc._device_broken = bool(svc_tok.get("device_broken"))
        svc.tickets = [cls._ticket_from_dict(d)
                       for d in svc_tok["tickets"]]
        by_id = {t.id: t for t in svc.tickets}
        svc._queue = [by_id[i] for i in svc_tok["queue"]]
        svc.completed = [by_id[i] for i in svc_tok["completed"]]
        c = svc_tok.get("counters") or {}
        for name in ("fleets", "lanes_admitted", "surrogate_answers",
                     "surrogate_escalations", "deferrals",
                     "spec_issued", "spec_committed",
                     "spec_rolled_back", "checkpoints",
                     "storm_failures", "watchdog_solo_fallbacks"):
            setattr(svc, name, int(c.get(name, 0)))
        ft = tok.get("fleet")
        if ft is not None:
            sim = plan.executor(
                [], width=int(ft["width"]),
                plan_cache=svc.plan_cache,
                tape_slots=int(ft["tape_width"]),
                batch_w=bool(ft["batch_w"]) or None,
                pipeline=svc.pipeline, mesh=svc.mesh,
                watchdog=watchdog)
            fleet_arrays = {name[len("fleet_"):]: a
                            for name, a in sorted(ck.arrays.items())
                            if name.startswith("fleet_")}
            try:
                sim.restore_state({"arrays": fleet_arrays,
                                   "errors": ft["errors"],
                                   "faults": ft["faults"],
                                   "counters": ft["counters"]})
            except ValueError as exc:
                raise CheckpointError(
                    f"fleet checkpoint state does not fit the "
                    f"rebuilt fleet: {exc}")
            svc._fleet = sim
            svc._lane_tickets = [
                by_id[i] if i is not None else None
                for i in svc_tok["lane_tickets"]]
        return svc

    # -- introspection -----------------------------------------------------

    def counters(self) -> Dict[str, float]:
        c = {"fleets": self.fleets,
             "lanes_admitted": self.lanes_admitted,
             "surrogate_answers": self.surrogate_answers,
             "surrogate_escalations": self.surrogate_escalations,
             "deferrals": self.deferrals,
             "spec_issued": self.spec_issued,
             "spec_committed": self.spec_committed,
             "spec_rolled_back": self.spec_rolled_back,
             "checkpoints": self.checkpoints,
             "storm_failures": self.storm_failures,
             "watchdog_solo_fallbacks": self.watchdog_solo_fallbacks,
             "device_broken": int(self._device_broken)}
        if self.plan_cache is not None:
            c.update(self.plan_cache.stats())
        return c
