"""Process-wide performance counters and host spans: the program's one
tracing facility.

Every device-facing module reports into one flat counter table so
tools can attribute cost per simulation phase without plumbing a
context object through the solver entry points:

* ``dispatches``            — device kernel dispatches (solver chunks,
                              drain advances/supersteps, warm solves)
* ``fixpoint_rounds``       — saturation rounds executed on device
* ``fixpoint_bound_rounds`` — the subset of a ``solve_arrays`` solve's
                              COO rounds that took the bound-first
                              rule (the local round's bound block
                              behind its ``lax.cond``, the global
                              round's min-bound branch): rides the
                              chunk fetch beside the round count.  0
                              on a system whose variable bounds never
                              bind — the block was skipped every round;
                              absent after ELL solves, which do not
                              count
* ``fixpoint_live_elem_rounds`` — the live elements (valid, their
                              variable not fixed yet) those same COO
                              rounds ENTERED with, summed over the
                              rounds: what an element-wide gather or
                              scatter of the round had to work on.
                              Over ``fixpoint_rounds`` x the element
                              count it is the mean live share, and its
                              inverse the most a perfect compaction
                              can gain.  Exact: ``fixpoint`` carries it
                              as two int32 halves, the chunk fetch
                              ships both; absent after ELL solves
* ``fixpoint_worked_elem_rounds`` — the elements those rounds INDEXED:
                              the size of the ladder rung each ran on
                              (``lmm_jax.fixpoint``), summed over the
                              rounds.  ``fixpoint_rounds`` x the padded
                              element count for a list under the
                              ladder's floor; over the live count above
                              it says how closely the rungs follow the
                              live set.  The same exact pair, through
                              the chunk fetch and, for the drain,
                              through the superstep's packed stats
* ``fixpoint_partitions``   — live-first partitions of the element list
                              those ``solve_arrays`` solves ran between
                              rungs (a descent over several rungs is
                              one partition and a slice a rung); 0
                              under the floor
* ``fixpoint_var_entries``  — committed advances of a collective
                              tape's superstep whose solve entered from
                              the variable side: the live flows'
                              elements fit the ladder's bottom rung,
                              or the one rung above it it also builds
                              (``lmm_jax._VAR_RUNG_TOP_ELEMS``), so
                              ``fixpoint`` built it from the element
                              list's variable-major index and ran no
                              op as wide as the list; one more scalar
                              at the tail of the packed vector.  Over
                              the advances: the share that paid a
                              live-width entry, not a full-width one
* ``collective_src_walks``  — committed advances of a collective
                              tape's superstep that walked the DAG from
                              the source side: their completions owned
                              at most ``lmm_drain._SRC_WALK_EDGES``
                              successor edges, so the predecessor
                              counts were decremented from those edges
                              alone (the DAG's source-major index) and
                              no op ran as wide as the edge list; a
                              fifth scalar at the tail of the packed
                              vector.  Over the advances: the share
                              that did not pay the edge-wide walk
* ``collective_ring_narrow`` — committed advances of a collective
                              tape's superstep that wrote their ring
                              ids on a rung: their entries (completions,
                              fault, activations) fit the widest of
                              ``lmm_drain._RING_RUNGS`` the tape keeps,
                              so ``_ring_narrow`` found their flows
                              from the running counts and no op indexed
                              as many rows as the flows; a sixth scalar
                              at the tail of the packed vector, only
                              where the tape has the DAG's source-major
                              index.  Over the advances: the share that
                              did not pay the flow-wide scatter
* ``collective_loopback_completions`` — completions, in the rings a
                              collective tape's ``_superstep_collect``
                              fetched, of flows between two ranks of
                              one host (``DeviceCollective.loopback``,
                              counted on the host from the fetched ids;
                              only where some flow is one).  Over the
                              completions: the share the intra-node
                              phases carry
* ``uploaded_bytes_full``   — host->device bytes shipped as whole
                              arrays (fresh ``device_put``)
* ``uploaded_bytes_delta``  — host->device bytes shipped as indexed
                              scatter payloads (ops.lmm_warm) or
                              compact per-replica scenario payloads
                              (ops.lmm_batch)
* ``solves`` / ``warm_solves`` / ``cold_solves`` — device solve entry
                              counts (warm = carried modified-component
                              restart, cold = full re-init)
* ``warm_ell_fallbacks``    — selective solves that requested a warm
                              restart while the ELL layout was
                              selected: the warm carry is COO-only, so
                              the solver falls back to cold and counts
                              the gap here instead of hiding it
* ``shards``                — shard lanes admitted into mesh-sharded
                              fleets/solves (ops.lmm_batch ``mesh=``:
                              one bump of the mesh's device count per
                              sharded program set up)
* ``demux_fetches``         — per-SHARD completion-ring transfers of
                              sharded fleets: each fleet sync fetches
                              one [B/M, ·] block per device and the
                              host reassembles them in replica order
                              before the event demux
* ``replicated_upload_bytes`` — host->device bytes for fleet-SHARED
                              arrays under a mesh, counted once per
                              device copy (a pod really ships M
                              copies of the platform flattening)
* ``sharded_upload_bytes``  — host->device bytes for [B, ·]
                              per-replica payloads under a mesh: each
                              byte lands on exactly one device, so
                              this stays flat per replica as the mesh
                              grows
* ``fetches``               — device->host result transfers routed
                              through :func:`timed_fetch` (drain ring
                              fetches, ``solve_arrays`` chunk fetches,
                              batched fleet fetches; each shard block
                              of a sharded fleet counts once)
* ``fetched_bytes``         — device->host bytes moved by those
                              transfers
* ``blocking_fetches``      — the subset of ``fetches`` whose device
                              computation had NOT finished when the
                              host asked (``Array.is_ready()`` false):
                              the host genuinely stalled on the device
                              round trip instead of overlapping it
* ``host_block_ms``         — monotonic host milliseconds spent inside
                              fetches (``time.perf_counter`` deltas —
                              wall time the host driver was blocked on
                              device results; the overlap fraction of
                              the pipelined drain is
                              1 - host_block_ms/phase wall)
* ``speculations_issued`` / ``speculations_committed`` /
  ``speculations_rolled_back`` — speculative supersteps dispatched
                              in-flight by the pipelined drain
                              executors, how many were committed
                              as-is, and how many were discarded
                              because processing the PRECEDING
                              completion ring mutated the system
* ``fault_tape_slots``      — fault-tape entries compiled into device
                              event tapes at sim construction
                              (ops.lmm_drain ``tape=`` / ops.lmm_batch
                              ``tapes=``: one bump per scheduled
                              failure/repair date across all lanes)
* ``fault_tape_events``     — tape events that FIRED mid-drain: the
                              superstep clamped dt to the event date,
                              scattered the new constraint bound and
                              emitted the tagged ring entry the host
                              demuxed into ``fault_events``
* ``fault_replays``         — speculative in-flight supersteps
                              discarded because the superstep they
                              chained from fired a tape event (the
                              pipelined executors treat a fire as a
                              clean-collect boundary and replay from
                              the post-fault state)
* ``warm_bound_restarts``   — warm solves whose entire dirty delta was
                              constraint-bound flips (the
                              fault-injection signature: capacities
                              changed, topology didn't); subset of
                              ``warm_solves``
* ``plan_cache_hits`` / ``plan_cache_misses`` — serving AOT plan-cache
                              lookups (serving.plancache): a hit
                              reuses a resident or disk-serialized
                              compiled executable (zero traces), a
                              miss pays one ``lower().compile()``
* ``plan_cache_disk_hits``  — the subset of hits deserialized from the
                              on-disk artifact store (warm restarts)
* ``plan_compile_ms``       — monotonic milliseconds spent AOT
                              lowering+compiling on plan-cache misses
                              (0 on a fully warm restart)
* ``plan_cache_fallbacks``  — artifacts read from the plan cache's
                              disk store that this backend refused to
                              run (stale / foreign): evicted and
                              compiled afresh.  A freshly compiled
                              executable that fails is raised, never
                              counted here
* ``lanes_admitted``        — dead fleet lanes revived mid-flight with
                              a NEW scenario by the serving admission
                              path (BatchDrainSim.admit_lane)
* ``surrogate_answers`` / ``surrogate_escalations`` — queries the
                              serving surrogate answered from its
                              conformal-interval prediction vs routed
                              to the device because the interval was
                              too wide (exact=True bypasses both)
* ``solver_fallbacks``      — device solves redone by the exact host
                              solver after a non-convergent/non-finite
                              device fixpoint (the per-stage view of
                              ``lmm_jax.get_fallback_count``'s
                              process-global int)
* ``lane_quarantined_<cause>`` — fleet lanes killed WITH a recorded
                              cause (ops.lmm_batch.LaneFault) instead
                              of poisoning the fleet: ``nan_solve``,
                              ``stall``, ``non_convergence``,
                              ``ring_overflow``, ``admission_storm``,
                              ``watchdog``
* ``fleet_checkpoints``     — superstep-boundary FleetCheckpoints
                              written by the campaign service
* ``checkpoint_ms``         — monotonic milliseconds spent building +
                              writing those checkpoints
* ``watchdog_retries`` / ``watchdog_exhausted`` /
  ``watchdog_slow_dispatches`` — dispatch-watchdog activity: seeded-
                              backoff retries of failed device
                              dispatches, dispatches that kept failing
                              past the retry policy, and dispatches
                              that succeeded but exceeded the
                              wall-clock threshold
* ``watchdog_solo_fallbacks`` — campaign-service fallbacks onto the
                              solo host path after watchdog
                              exhaustion (affected in-flight queries
                              are re-served solo, bit-identically)
* ``serve_solo_results``    — queries the campaign service answered
                              on the solo host path (watchdog
                              fallback)
* ``native_advances``       — engine advances served by the generic
                              host sweep (models.cpu/models.network)
                              instead of a device drain plan
* ``fastpath_advances``     — engine advances fully served by the
                              device drain plan (ops.drain_path
                              serve/apply at the planned dt)
* ``drain_transitions``     — drain-plan transition absorptions: dirty
                              deltas scattered into the live device
                              state instead of invalidating the plan
* ``drain_transition_slots`` — slots touched by those scatters
* ``drain_cause_<cause>``   — drain-plan invalidation/absorption
                              causes (``partial_advance``,
                              ``transition``, ``stall``,
                              ``profile_event``, ...): one bump per
                              event, keyed by cause
* ``phase_<kind>``          — drain-plan builds keyed by the
                              classified phase kind of the system
                              snapshot (ops.drain_path.classify_phase)
* ``collective_tape_slots`` — collective-tape entries compiled into
                              device schedule tapes at sim
                              construction (collectives.tape)
* ``collective_tape_fires`` — collective tape events that FIRED
                              mid-drain: counted by the superstep and
                              read from the tail of its packed vector
                              (a fleet counts the ring entries it
                              demuxes into ``collective_events``)
* ``collective_live_flow_advances`` — flows live as an advance of a
                              collective tape entered, summed over the
                              advances: an exact [high, low] pair in
                              the superstep's state (as
                              ``fixpoint_live_elem_rounds``), read from
                              the same tail; over advances x flow
                              slots it is the share of the tape a
                              full-width solve works for
* ``collective_routes``     — (src, dst) rank pairs a
                              ``collectives.RoutedTopology`` looked up
                              through ``routing/`` (``route_to``), each
                              once, when a schedule first used it or
                              its way back: bumped inside the
                              ``coll.lower`` span of ``id`` ``routes``,
                              so span / count is the price of a route
* ``collective_self_routes`` — the subset of those pairs whose rank
                              sends to itself (an lr allreduce's
                              self-copy): routed as ``communicate``
                              routes a host to itself
* ``collective_loopback_routes`` — the subset of those pairs between
                              two DIFFERENT ranks of one host (an SMP
                              collective's intra-node messages):
                              routed as a host to itself too, its
                              loopback (a host's route to itself is
                              looked up once)
* ``collective_schedule_records`` — comm records a schedule generator
                              emitted (``collectives.generate``, inside
                              the ``coll.lower`` span of ``id``
                              ``schedule``): span / count is the price
                              of a record
* ``collective_replays``    — speculative in-flight supersteps
                              discarded because the superstep they
                              chained from fired a collective tape
                              event (mirror of ``fault_replays``)
* ``flows_posted`` / ``post_ms`` — flows posted through
                              ``NetworkCm02Model.communicate`` and the
                              monotonic milliseconds spent inside it
                              (route lookup, variable, expands): a
                              counter pair, not a span — a workload
                              posts 100,000 of them
* ``xla_compiles`` / ``xla_compile_ms`` — programs JAX handed to the
                              backend compiler (a jit's first call
                              with new shapes or statics, an AOT
                              ``compile()``) and the monotonic
                              milliseconds inside: each is also an
                              ``xla.compile`` span.  A steady-state
                              window must keep both flat
* ``retraces``              — jit trace executions of the kernel
                              program functions (bumped at TRACE time
                              only, from inside the program body): a
                              steady-state superstep loop must keep
                              this flat — a nonzero delta on a repeat
                              run is a cache-busting retrace

Counters only ever increase; consumers snapshot before a phase and
diff after (``snapshot``/``diff``), or wrap the phase in ``scoped``.
Purely observational — nothing in the solve paths reads them back.
(``host_block_ms`` uses the monotonic ``time.perf_counter`` — never
the banned wall-clock ``time.time`` — so the determinism lint stays
clean and the timing is immune to clock steps.)

Per-stage scoping
-----------------

``scoped(name)`` brackets a phase: the yielded dict is filled with the
phase's counter *deltas* on exit and also recorded in ``stage_stats``
under ``name``.  Scopes nest (each diffs against its own entry
snapshot), so a bench process running several stages — or the batch
driver running several fleets — reports per-stage counters instead of
process-cumulative ones, and re-running a stage in the same process
can no longer double-count the previous stage's work::

    with opstats.scoped("sweep/b64") as st:
        campaign.run_batched(batch=64)
    st["dispatches"]          # this stage only

Spans
-----

``span(name, id=None)`` brackets one host step: on exit it appends
``Span(name, start, end, parent, id, seq)`` to a bounded in-memory
buffer (``spans()`` reads it, ``reset()`` clears it; the oldest
records fall off, so a service that runs for days does not grow).
``start``/``end`` are ``time.perf_counter`` seconds, the clock a
benchmark's own spans use, so a reader cuts warm-up from window with
one comparison.  ``seq`` numbers spans in the order they were
entered, ``parent`` is the ``seq`` of the enclosing span of the same
thread (None at the top), and ``id`` ties the spans of one device
dispatch together (``DrainSim.supersteps`` at issue); a span without
one inherits its parent's.  The same interval is entered as a
``jax.profiler.TraceAnnotation("sg:" + name)``, which records only
while a profiler session is running: tracing is "on" when the
profiler is, and there is no flag.  In a trace the ``sg:`` host spans
sit on the device's own timeline, beside the device passes that
``jax.named_scope`` names ``sg.lmm.*`` (ops.lmm_jax.fixpoint) and
``sg.drain.*`` (ops.lmm_drain._superstep_program).

Rule: a span site fires at most once per device dispatch or once per
set-up phase.  Finer work gets a counter pair (``*_ms`` + count, as
``post_ms`` / ``flows_posted``).  Span names are literals declared in
this table, like the counters (the ``opstats-discipline`` lint checks
``span("...")`` against it):

* ``platform.load``   — ``s4u.Engine.load_platform``: the XML parse and
                        the zones, hosts, links and routes it builds
* ``lmm.flatten``     — ``lmm_jax.flatten``: the live host system
                        walked into padded COO arrays
* ``coll.lower``      — a collective lowered for the device, three
                        ``id``s that never nest: ``schedule`` in
                        ``CollectiveSpec.build`` (the generator: the
                        per-rank programs matched into records with
                        their predecessor sets), ``routes`` in
                        ``collectives.RoutedTopology`` (the routes of
                        the pairs a schedule uses looked up and put in
                        slots, when ``lower`` first asks for them) and
                        ``tape`` in ``DeviceCollective`` (the records
                        and the DAG compiled into its arrays: two
                        spans, before and after the routes)
* ``drain.init``      — ``DrainSim.__init__``: the host arrays shaped
                        and handed to the device (``device_put``)
* ``drain.issue``     — ``DrainSim._superstep_issue``: one superstep
                        dispatch enqueued (trace and compile on a
                        first call, then the async enqueue alone)
* ``drain.collect``   — ``DrainSim._superstep_collect``: the ring
                        fetched (child ``fetch``), replayed (child
                        ``drain.demux``) and the repack decision
* ``drain.demux``     — the fetched ring replayed into ``events`` /
                        ``fault_events`` / ``collective_events``
* ``solve.chunk``     — one dispatch + fetch of ``solve_arrays``' loop
* ``fetch``           — every :func:`timed_fetch`: the host inside one
                        device->host transfer (``blocking_fetches``
                        still says whether the device was ready)
* ``engine.advance``   — ``EngineImpl.surf_solve``: one time advance
                        of the engine (the models' next event, which
                        holds the solve; the profile events; every
                        model's ``update_actions_state``); ``id`` is
                        the advance's ordinal in its engine
* ``xla.trace``       — one jitted function traced into a jaxpr, as
                        JAX's monitoring reports it when it ends:
                        ``(now - duration, now)``, ``id`` the
                        function's name.  Inner jits traced on the way
                        give spans nested in the outer one's by time
                        (``parent`` names the enclosing ``span()``, not
                        the outer trace): a reader takes self or union
                        seconds, never the plain sum
* ``xla.lower``       — one jaxpr lowered to an MLIR module, reported
                        the same way; ``id`` is the module's name
                        (``jit(<function>)``)
* ``xla.compile``     — one program through the backend compiler,
                        reported the same way; ``id`` is the module's
                        name, prefixed ``cached:`` when the persistent
                        compilation cache served it
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from collections import deque
from typing import Dict, Iterator, List, NamedTuple, Optional

import numpy as np

import jax

_counters: Dict[str, float] = {}


class Span(NamedTuple):
    """One closed host span (see the module docstring)."""
    name: str
    start: float
    end: float
    parent: Optional[int]
    id: Optional[object]
    seq: int


#: how many closed spans are kept; ~25 fire per 9 s dispatch, so this
#: is hours of a drain and a bounded few MB of a service's life
SPAN_BUFFER = 65536

_spans: "deque[Span]" = deque(maxlen=SPAN_BUFFER)
_open = threading.local()         # .stack: the thread's open spans
_seq = itertools.count(1)         # next() is one bytecode: thread-safe

#: per-stage deltas recorded by ``scoped`` (last run of each stage)
stage_stats: Dict[str, Dict[str, float]] = {}


def bump(name: str, n=1) -> None:
    _counters[name] = _counters.get(name, 0) + n


def timed_fetch(arr) -> "np.ndarray":
    """Fetch one device array to host with blocking accounting: counts
    the transfer in ``fetches``, classifies it as a ``blocking_fetch``
    when the device had not finished computing it at call time
    (``is_ready()`` false — the host is about to stall on the round
    trip), and adds the monotonic milliseconds spent inside the fetch
    to ``host_block_ms``.  The pipelined drain's whole point is turning
    blocking fetches into ready ones; this is where that is measured.
    """
    ready = bool(getattr(arr, "is_ready", lambda: False)())
    with span("fetch") as sp:
        out = np.asarray(arr)
    bump("host_block_ms", (sp.end - sp.start) * 1e3)
    bump("fetches")
    bump("fetched_bytes", out.nbytes)
    if not ready:
        bump("blocking_fetches")
    return out


class span:
    """Context manager recording one host span (module docstring,
    "Spans").  A class, not a generator: a site costs two clock reads,
    one ``TraceAnnotation`` and one append."""

    __slots__ = ("name", "id", "start", "end", "_seq", "_parent", "_note")

    def __init__(self, name: str, id=None):
        self.name = name
        self.id = id
        self.start = self.end = 0.0

    def __enter__(self) -> "span":
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        if stack:
            self._parent = stack[-1]._seq
            if self.id is None:
                self.id = stack[-1].id
        else:
            self._parent = None
        self._seq = next(_seq)
        stack.append(self)
        self._note = jax.profiler.TraceAnnotation("sg:" + self.name)
        self._note.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._note.__exit__(*exc)
        _open.stack.pop()
        _spans.append(Span(self.name, self.start, self.end, self._parent,
                           self.id, self._seq))


def spans() -> List[Span]:
    """The closed spans still in the buffer, in the order they were
    entered (a parent before its children)."""
    return sorted(_spans, key=lambda s: s.seq)


def note_xla(name: str, seconds: float, id) -> None:
    """One step of JAX's way from a jitted function to an executable
    ended after ``seconds`` (the listener ``ops/__init__.py``
    registers calls this with a literal name of the table above): a
    closed span called ``name`` ending now."""
    end = time.perf_counter()
    stack = getattr(_open, "stack", None)
    _spans.append(Span(name, end - seconds, end,
                       stack[-1]._seq if stack else None, id, next(_seq)))


def snapshot() -> Dict[str, float]:
    return dict(_counters)


def diff(before: Dict[str, float]) -> Dict[str, float]:
    """Counter deltas since `before` (keys with zero delta omitted)."""
    out = {}
    for k, v in _counters.items():
        d = v - before.get(k, 0)
        if d:
            out[k] = d
    return out


@contextlib.contextmanager
def scoped(name: str) -> Iterator[Dict[str, float]]:
    """Bracket a phase: yields a dict that receives the phase's counter
    deltas on exit (also kept in ``stage_stats[name]``)."""
    before = snapshot()
    stats: Dict[str, float] = {}
    stage_stats[name] = stats
    try:
        yield stats
    finally:
        stats.update(diff(before))


def get_stage(name: str) -> Dict[str, float]:
    """The recorded deltas of a completed ``scoped`` stage ({} when the
    stage never ran)."""
    return dict(stage_stats.get(name, {}))


def reset() -> None:
    """Clear every counter, the recorded stage deltas AND the span
    buffer (fresh process-equivalent state for tests and multi-phase
    tools).  Spans still open keep their place on their thread's stack
    and are recorded when they close."""
    _counters.clear()
    stage_stats.clear()
    _spans.clear()
