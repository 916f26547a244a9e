"""Process-wide performance counters for the device solve paths.

Every device-facing module reports into one flat counter table so
tools can attribute cost per simulation phase without plumbing a
context object through the solver entry points:

* ``dispatches``            — device kernel dispatches (solver chunks,
                              drain advances/supersteps, warm solves)
* ``batch_dispatches``      — dispatches that ran a whole replica
                              FLEET (ops.lmm_batch); always also
                              counted in ``dispatches``
* ``batch_replicas``        — replicas admitted into batched fleets
* ``fixpoint_rounds``       — saturation rounds executed on device
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
                              fetches, batched fleet fetches; each
                              shard block of a sharded fleet counts
                              once)
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
* ``donated_buffers``       — carried-state device buffers handed to
                              XLA for in-place reuse by donating
                              superstep dispatches (ops.lmm_drain /
                              ops.lmm_batch ``donate=``: one bump per
                              donated argument, so steady-state drains
                              add 2 — pen and rem — per superstep);
                              the donation win proglint's ``donation``
                              rule verifies in the lowered IR
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
* ``serve_device_results``  — queries the campaign service answered
                              with exact device simulation
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
* ``fleet_resumes``         — services rebuilt from a FleetCheckpoint
                              token (CampaignService.resume)
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
                              mid-drain (ring entries the host demuxed
                              into ``collective_events``)
* ``collective_replays``    — speculative in-flight supersteps
                              discarded because the superstep they
                              chained from fired a collective tape
                              event (mirror of ``fault_replays``)
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
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator

import numpy as np

_counters: Dict[str, float] = {}

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
    t0 = time.perf_counter()
    out = np.asarray(arr)
    bump("host_block_ms", (time.perf_counter() - t0) * 1e3)
    bump("fetches")
    bump("fetched_bytes", out.nbytes)
    if not ready:
        bump("blocking_fetches")
    return out


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
    """Clear every counter AND the recorded stage deltas (fresh
    process-equivalent state for tests and multi-phase tools)."""
    _counters.clear()
    stage_stats.clear()
