"""Device-resident flow-drain executor: the LMM_TPU batch mode.

The north-star benchmark (BASELINE config #4) is a pure *drain*: a large
set of concurrent flows, posted up front, that only ever complete —
exactly the structure of an SMPI alltoall's network phase, where every
rank has posted all sends/receives and the maestro's loop degenerates to

    while flows remain:
        solve rates -> next completion time -> advance -> retire flows

(reference: surf_solve + Model::update_actions_state,
src/kernel/resource/Model.cpp:40-101).  The reference executes that loop
one C++ step at a time; this executor keeps ALL solver and flow state
device-resident across advances and runs the loop as **supersteps**:
a ``lax.while_loop`` over (solve -> dt -> retire) executes up to K
advances per dispatch (``superstep=K``), logging completions into a
fixed-size device ring buffer ``(time, flow_id)`` fetched in ONE
transfer — amortized syncs are ~1/K per advance, and K = 1 is one
advance a dispatch.  A per-dispatch round budget bounds one dispatch's
run time (same reasoning as lmm_jax._CHUNK_ROUNDS_ACCEL: a spinning
solve must return to the host and raise).

Completion grouping is RELATIVE by default (``rem2 <= done_eps * size``,
the reference's sg_maxmin_precision/sg_surf_precision semantics,
maxmin.cpp:12-14,470-479): an absolute epsilon under f32 splits the f64
tie groups — flows the f64 backends retire in one advance spread over
many f32 advances, which is the diagnosed round-5 blocker of the TPU
end-to-end drain (round-5 record, removed in PR 21; git history).  A
threshold that scales with flow size keeps accumulated f32 rounding
noise (~size * 1.2e-7 per step) below the retirement cut, so
chip-precision ties coalesce exactly like the f64 oracle's.  ``done_mode="abs"``
restores the absolute rule for f64 engine-fidelity runs.

The simulation clock is accumulated in f64 ON THE HOST (``self.t`` is a
Python float); inside a superstep dispatch the per-advance dt values are
combined with compensated (Kahan) summation in the device dtype, so a
100k-advance f32 drain does not drift event timestamps against the f64
backends: per-superstep error is O(K ulp) instead of compounding across
the whole run.

Python bookkeeping is O(completed flows) per advance (recording events),
not O(system).  When the live flow population halves, the element list
is repacked ON DEVICE — a stable live-first partition (the same
machinery as lmm_jax's compaction chain) dispatched without any host
round-trip, so halving the live set costs one kernel launch instead of
a fetch + re-upload.

The superstep program (`_superstep_program`) doubles as the LANE body
of the batched multi-replica executor (ops.lmm_batch), which vmaps it
over a leading replica axis to drain whole scenario fleets per
dispatch — keep it a pure function of its arguments.

Speculative pipelining (``pipeline=D``): JAX dispatch is ASYNC — only
the completion-ring fetch blocks the host — so the superstep driver
can keep D extra supersteps in flight against double-buffered flow
state: while the host parses ring N (a pure-Python O(events) walk),
superstep N+1 is already executing on the device, and the fetch of
ring N+1 finds its buffer ready instead of waiting out the dispatch.  The dispatch of a superstep is split into an *issue*
(:meth:`DrainSim._superstep_issue` — pure with respect to the sim's
committed flow state; the dispatch inputs/outputs ride a
:class:`SuperstepToken`) and a *collect* (the blocking fetch + host
event commit).  Speculation is validated at collect time: if
processing ring N mutated anything the in-flight dispatch assumed
frozen (a device repack, the stop-for-repack trigger decay, a budget
rescue, a stall, or drain completion), every un-collected token is
DISCARDED — issue never touched the committed state, jax arrays are
immutable, so rollback is O(1) — and the pipeline restarts from the
post-N state, recomputing exactly what the unpipelined driver would
have.  Committed speculative supersteps are bit-identical to the
unpipelined path by construction: the program is a deterministic
function of its inputs and a token commits only when its inputs
turned out to equal the unpipelined path's inputs.
"""

from __future__ import annotations

import functools
from collections import deque
from itertools import repeat
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import opstats
from .device import default_platform, solve_dtype
from .lmm_jax import (_MAX_ROUNDS, SolveError, _bucket, _live_elem_rounds,
                      _owners, _pair_add, _pos_group,
                      _stable_livefirst_perm, fixpoint, var_index)


def _to2d(a: np.ndarray, group: int = 8) -> np.ndarray:
    """Element arrays keep a 2D shape end-to-end (the index-shape
    convention of ops/, see lmm_jax._vc_round_body)."""
    n = len(a)
    if n % group:
        pad = group - n % group
        fill = np.zeros(pad, a.dtype)
        a = np.concatenate([a, fill])
    return a.reshape(-1, group)


# The kernel *programs* below are defined as plain functions and
# jitted by assignment so the batched executor (ops.lmm_batch) can vmap
# the raw superstep program over a leading replica axis: one device
# program then advances a whole scenario fleet, amortizing the
# per-dispatch cost across replicas.  Keep them functional (no global
# state) — the solo jit and the vmapped jit share one program.

def _solve_chunk_program(e_var, e_cnst, e_w, c_bound, v_penalty, v_bound,
                         carry, eps: float, n_c: int, n_v: int, chunk: int,
                         has_bounds: bool = False, *, c_fatpipe=None,
                         has_fatpipe: bool = False):
    """``c_fatpipe`` (per constraint, FATPIPE sharing) is read only
    with ``has_fatpipe``: without it every constraint is SHARED and the
    program is the one it always was."""
    dtype = e_w.dtype
    out = fixpoint(e_var, e_cnst, e_w, c_bound,
                   c_fatpipe if has_fatpipe else jnp.zeros(n_c, bool),
                   v_penalty, v_bound,
                   jnp.asarray(eps, dtype), n_c, n_v,
                   parallel_rounds=True, carry=carry, max_rounds=chunk,
                   return_carry=True, has_bounds=has_bounds,
                   has_fatpipe=has_fatpipe)
    carry2 = out[4]
    stats = jnp.stack([out[3].astype(dtype),
                       jnp.count_nonzero(carry2[4]).astype(dtype)])
    return carry2, stats


_drain_solve_chunk = functools.partial(
    jax.jit, static_argnames=("eps", "n_c", "n_v", "chunk",
                              "has_bounds", "has_fatpipe"))(
                                  _solve_chunk_program)


#: the traced runtime zero handed to every advance kernel (see
#: _rounded_product) — an argument, never a constant, so neither XLA's
#: simplifier nor LLVM can fold the integer detour away
_ZERO_BITS = np.int64(0)


def _rounded_product(a, b, zero_bits):
    """a*b rounded to f64 BEFORE the consumer sees it.  XLA:CPU's LLVM
    backend contracts mul+sub chains into FMAs no matter how the HLO is
    structured (selects and optimization_barriers are speculated/erased
    at instruction selection), but the engine's double_update walk
    rounds the product first — so the chained device remains would
    drift a ulp per advance from the host walk.  Routing the product's
    bits through an integer add of `zero_bits` (a TRACED runtime zero
    the compiler cannot constant-fold) pins the standalone rounding."""
    prod = a * b
    itype = jnp.int64 if prod.dtype == jnp.float64 else jnp.int32
    bits = lax.bitcast_convert_type(prod, itype) + zero_bits.astype(itype)
    return lax.bitcast_convert_type(bits, prod.dtype)


def _advance_math(pen, rem, thresh, values, zero_bits=None):
    """The shared dt/retire step: dt to the next completion, relative-
    or absolute-threshold retirement (thresh is a per-flow array, so
    the caller chooses the semantics).  Mirrors
    Model::update_actions_state (FULL mode).

    ``zero_bits`` (a TRACED int zero) routes the rate*dt product
    through `_rounded_product` so the chained remains walk stays
    bit-identical to the host engine — every drain path passes
    `_ZERO_BITS`.  Callers that don't chain remains against the host
    (the rate-level `parallel.sharded` step) may omit it and keep the
    plain product."""
    live = pen > 0
    rate = jnp.where(live, values, 0.0)
    flowing = live & (rate > 0)
    dt = jnp.min(jnp.where(flowing,
                           rem / jnp.where(flowing, rate, 1.0),
                           jnp.inf))
    prod = (rate * dt if zero_bits is None
            else _rounded_product(rate, dt, zero_bits))
    rem2 = jnp.where(flowing, rem - prod, rem)
    # strict <, matching the reference double_update's `value <
    # precision` zeroing (so the absolute mode is bit-compatible with
    # the engine's generic remains bookkeeping)
    done = flowing & (rem2 < thresh)
    pen2 = jnp.where(done, 0.0, pen)
    rem2 = jnp.where(done, 0.0, rem2)
    return dt, pen2, rem2, done


#: scalars at the head of the superstep's packed vector: rounds,
#: advances, events, clock, live flows, flag, live elements, and the
#: elements its rounds indexed as fixpoint's [high, low] pair
_STATS_HEAD = 9

#: superstep completion flags (stats slot 5)
_FLAG_OK = 0          # exited on k / live-count / natural completion
_FLAG_STALLED = 1     # no flow holds bandwidth (dt not finite)
_FLAG_BUDGET = 2      # solve hit the round budget mid-superstep


#: An advance of a collective tape whose completions own at most this
#: many successor edges (and number at most this many) decrements the
#: predecessor counts from those edges alone (:func:`_succ_walk`); one
#: that owns more walks the whole edge list, as every advance did.  Six
#: indexed ops this wide sit at the floor of what an op costs on the
#: chip (PERF.md §5), and 4,096 holds every advance of the pairwise
#: alltoall (784 at most) and all but a burst's of the allreduce (2,248;
#: a step's burst owns 157,872-209,400).
_SRC_WALK_EDGES = 1 << 12


def succ_index(edge_src, edge_dst, n_v: int):
    """The SOURCE-major index of a collective DAG's edge list, on the
    host: ``(s_ptr[n_v + 1], s_dst[E])``, int32.  Flow ``f``'s
    successors are ``s_dst[s_ptr[f]:s_ptr[f + 1]]``, in the list's own
    order; the rows that count for nothing (the pad row, whose successor
    is the dropped slot ``n_v``) lie behind the last flow's.  One stable
    argsort of the list: build it once per list, not per sim
    (``DeviceCollective.succ_index`` keeps it)."""
    edge_src = np.asarray(edge_src, np.int64)
    edge_dst = np.asarray(edge_dst, np.int64)
    counts = ((edge_src >= 0) & (edge_src < n_v)
              & (edge_dst >= 0) & (edge_dst < n_v))
    # the edges grouped by source as var_index groups elements by
    # variable, an edge that counts being an element of weight 1
    s_ptr, order = var_index(edge_src, counts, n_v)
    return s_ptr, np.where(counts, edge_dst, n_v)[order].astype(np.int32)


def _succ_walk(pred, ring_id, n_ev, n_done, s_ptr, s_dst, width: int):
    """``pred`` less one for every successor edge of the ``n_done``
    flows whose slots the completion ring holds at ``[n_ev, n_ev +
    n_done)``: the tape's DAG walk from the completions' own edges.
    They own at most ``width`` edges together and are at most ``width``
    (the caller's test), and nothing here is wider: the edges are
    expanded from the flows as ``lmm_jax._rung_from_vars`` expands
    elements from variables (out-degrees, then ``lmm_jax._owners``).
    The counts are integers and the adds commute, so the edge-wide walk
    gives the same to the bit."""
    n_v = pred.shape[0]
    group = _pos_group(width)
    # 2D index shapes: the ops/ gather and scatter convention
    rows = lambda a: a.reshape(width // group, group)
    at = lax.iota(jnp.int32, width)
    # a gather, not a dynamic_slice: that clamps its start near the
    # ring's end and would misalign
    flow = jnp.where(at < n_done,
                     jnp.take(ring_id, rows(n_ev + at), mode="clip")
                     .reshape(-1), n_v)
    span = jnp.take(s_ptr, flow[:, None] + lax.iota(jnp.int32, 2),
                    mode="clip")
    start = span[:, 0]
    deg = jnp.where(at < n_done, span[:, 1] - start, 0)
    owner, first, _ = _owners(deg, width)
    # edge j of completion i sits at s_dst[start[i] + j], and here at
    # first[i] + j
    edge = at + jnp.take(start - first, rows(owner)).reshape(-1)
    dst = jnp.where(rows(at < jnp.sum(deg)),
                    jnp.take(s_dst, rows(edge), mode="clip"), n_v)
    return pred.at[dst].add(-1, mode="drop")


#: The widths at which an advance of a collective tape with the DAG's
#: source-major index writes its ring ids (:func:`_ring_narrow`): the
#: narrowest its entries fit, and the flow-wide scatter above the top
#: one.  A rung is kept only where it is at most an eighth of the flow
#: slots, and there it costs a small part of that scatter (PERF.md §5).
_RING_RUNGS = (1 << 10, 1 << 13)


def _ring_narrow(ring_id, dcount, acount, ids, n_ev, fault, n_c: int,
                 width: int):
    """``ring_id`` with one advance's completion and activation ids
    written at ``width``: the completions in slot order from ``n_ev``,
    a slot left for the fault's entry when ``fault`` (0 or 1), then the
    activations, as the flow-wide scatter writes them; they are at most
    ``width`` with the fault's (the caller's test).  Position ``p``'s
    flow is the ``p``-th counted one of ``[done, act]``, found from
    their running counts ``dcount`` / ``acount`` viewed as rows: a dense
    compare against the rows' last counts gives its row, one
    ``width``-row gather and a compare within it its lane.  Then one
    ``width``-wide gather of ids and one scatter, positions past the
    entries (and the ring) dropped; nothing is indexed at the width of
    the flows."""
    n_v = dcount.shape[0]
    ring_n = ring_id.shape[0]
    n_done = dcount[-1]
    total = n_done + acount[-1]
    # rows of about the square root of the counts: the rows compared
    # and the lanes of the one row gathered are about as many
    lanes = 128
    while lanes * lanes < 2 * n_v:
        lanes *= 2
    rows = -(-2 * n_v // lanes)
    cnt = jnp.concatenate([
        dcount, n_done + acount,
        jnp.broadcast_to(total, (rows * lanes - 2 * n_v,))]).reshape(
            rows, lanes)
    at = lax.iota(jnp.int32, width)
    rank = jnp.where(at < n_done, at, at - fault)
    keep = (rank < total) & ((at < n_done) | (at >= n_done + fault))
    row = jnp.sum(cnt[:, -1] <= rank[:, None], axis=1, dtype=jnp.int32)
    lane = jnp.sum(jnp.take(cnt, row, axis=0, mode="clip")
                   <= rank[:, None], axis=1, dtype=jnp.int32)
    slot = row * lanes + lane
    group = _pos_group(width)
    rows_of = lambda a: a.reshape(width // group, group)
    got = jnp.take(ids, rows_of(jnp.where(slot < n_v, slot, slot - n_v)),
                   mode="clip").reshape(-1)
    val = jnp.where(slot < n_v, got, -(1 + n_c + got))
    return ring_id.at[rows_of(jnp.where(keep, n_ev + at, ring_n))].set(
        rows_of(val), mode="drop")


def _ring_ids(ring_id, wide, dcount, acount, ids, n_ev, count, fault,
              n_c: int, rungs):
    """``ring_id`` with the ids of one advance's ``count`` entries
    written from ``n_ev``, and whether that took a rung: on the
    narrowest of ``rungs`` they fit (:func:`_ring_narrow`), else by
    ``wide``, the flow-wide scatter.  A ``lax.switch`` on the advance's
    own count, as the DAG walk is chosen; both sides leave the same
    ring to the bit."""
    rung = sum((count > w).astype(jnp.int32) for w in rungs)
    return lax.switch(rung, [functools.partial(
        _ring_narrow, dcount=dcount, acount=acount, ids=ids, n_ev=n_ev,
        fault=fault, n_c=n_c, width=w)
        for w in rungs] + [wide], ring_id), jnp.asarray(rung) < len(rungs)


def _check_collective_start(pen, pred, ready):
    """Refuse a collective tape whose start breaks what the superstep's
    one ring scatter rests on (see :func:`_superstep_program`): a live
    flow (penalty > 0) with predecessors outstanding or a ready date,
    or a dated one with predecessors outstanding.  Every tape
    ``DeviceCollective`` lowers starts as its DAG does: the roots live
    or dated, every other flow dormant, undated and waiting."""
    live = np.asarray(pen, np.float64) > 0
    dated = np.isfinite(np.asarray(ready, np.float64))
    waits = np.asarray(pred, np.int64) > 0
    for bad, what in ((live & waits, "live with predecessors outstanding"),
                      (live & dated, "live with a ready date"),
                      (dated & waits, "dated with predecessors outstanding")):
        if bad.any():
            raise ValueError(
                f"collective= starts {int(bad.sum())} flow(s) {what} "
                f"(the first: flow {int(np.argmax(bad))}); a flow starts "
                "live, dated or waiting, not two of them")


def _superstep_program(e_var, e_cnst, e_w, c_bound, v_bound, pen, rem,
                       thresh, ids, k, round_budget, stop_live, zero_bits,
                       tape_t, tape_slot, tape_val, tape_pos,
                       coll_pred, coll_ready, coll_clk,
                       edge_src, edge_dst, exec_cost, t0,
                       v_ptr=None, ve_idx=None, s_ptr=None, s_dst=None, *,
                       eps: float, n_c: int, n_v: int, k_max: int,
                       group: int, has_bounds: bool = False,
                       has_tape: bool = False, has_coll: bool = False,
                       c_fatpipe=None, has_fatpipe: bool = False):
    """Up to `k` (<= k_max) full advances in ONE dispatch: an outer
    lax.while_loop of (fixpoint to convergence -> dt -> retire), with
    completions logged into a device ring buffer and the clock carried
    as a compensated (Kahan) pair.  Returns the new flow state, the
    (possibly fault-mutated) constraint bounds and tape cursor, plus
    one packed vector (stats + per-advance dt/event-count tables +
    ring) so the host pays a single transfer per superstep.

    `k`, `round_budget` and `stop_live` are TRACED (dynamic) so replay
    (re-running a prefix of a batch deterministically) and budget
    tuning never trigger a recompile; `k_max` is the static table
    capacity.  The round budget bounds total device rounds per dispatch:
    the budget, not k, is what bounds one dispatch's run time (the
    _CHUNK_ROUNDS_ACCEL reasoning).

    ``has_tape`` arms the FAULT EVENT TAPE: ``(tape_t, tape_slot,
    tape_val)`` is a time-sorted schedule of constraint-capacity
    flips (absolute f64 sim dates / constraint slots / new absolute
    bounds) and ``tape_pos`` the cursor of the first un-fired entry.
    Between the solve and the retire of every advance the loop peeks
    the next tape date against the absolute clock ``t0 + t_sum`` (both
    f64, so the comparison never loses to f32 clock granularity): if
    the planned dt would step over it, dt is CLAMPED to land exactly on
    the event, the new bound is scattered into ``c_bound`` (carried in
    the loop state, so the next iteration's fixpoint sees it — the
    device analogue of a Profile event invalidating the solver), a
    TAGGED entry ``id = -(1 + slot)`` is logged in the ring at the
    event time, and the cursor advances.  A fire consumes an advance
    slot, which bounds fires per dispatch by k_max — the ring is
    therefore oversized to ``n_v + k_max``.  A fire also rescues a
    stalled plan (dt = inf with a pending tape date is a wake-up, not
    a stall), mirroring how a Profile event re-arms an idle engine.
    With ``has_tape=False`` the tape arguments are ignored and the
    loop state is the plain 13-tuple.

    ``has_coll`` arms the COLLECTIVE SCHEDULE TAPE: the flow set is a
    compiled communication DAG (collectives.tape) whose dormant flows
    (penalty 0, full remains) activate when their predecessors
    complete.  ``coll_pred`` carries the per-flow outstanding
    predecessor counts, ``(edge_src, edge_dst)`` the static successor
    edge list (padded rows scatter to the dropped slot ``n_v``),
    ``exec_cost`` the per-flow delay between the last predecessor's
    completion and the flow's activation (the compute leg of a
    compute/comm phase), and ``coll_ready`` the f64 pending-activation
    dates (+inf = not scheduled).  Each advance takes the earliest of
    {planned completion, fault date, activation date}; an activation
    scatters penalty 1.0 into the fired flows, consumes their ready
    slots, and logs tagged ring entries ``id = -(1 + n_c + flow_id)``
    (disjoint from fault fires, whose slots are < n_c) — no host
    involvement until the schedule is exhausted.  Because collective
    runs must be bit-identical at EVERY dispatch grouping (the
    host-maestro oracle replays the same recurrence one advance per
    dispatch), the Kahan clock pair is carried ACROSS dispatches via
    ``coll_clk = (t, comp)`` and ring times are ABSOLUTE dates.  The
    pair, the activation dates and ``exec_cost`` are float64 WHATEVER
    the solve dtype (the f64 spine the fault tape's dates already are:
    IEEE on the CPU, an f32 pair on the TPU, finer than float32 on
    both); only ``dt`` is rounded to the solve dtype, where the
    remains it decrements live.  In float64 that is the recurrence it
    always was, bit for bit; in float32 a date is off by the rounding
    of the dts behind it, never by the clock's magnitude.  The ring's
    own dates are in the solve dtype, so the host replays the pair
    from the per-advance dt table instead (``DrainSim._demux``).  The
    ring grows by another n_v activation slots, the state by the live
    flows entering each advance (an exact [high, low] pair, as
    ``fixpoint``'s element counts) and the activations fired; those
    three ride the END of the packed vector.  ``(v_ptr, ve_idx)`` is
    the element list's variable-major index (``lmm_jax.var_index``;
    the tape's list is never repacked, so it stays true): with most
    flows dormant, ``fixpoint`` then enters from the live flows' own
    elements instead of the whole list, and the advances that did are
    counted into a fourth scalar at the end.  ``(s_ptr, s_dst)`` is
    the DAG's source-major edge index (:func:`succ_index`): an advance
    whose completions own at most ``_SRC_WALK_EDGES`` successor edges
    then decrements ``coll_pred`` from those edges alone
    (:func:`_succ_walk`) and one that owns more walks the whole edge
    list, a ``lax.cond`` an advance on a count of its own completions;
    both give the same counts to the bit, and the advances that took
    the first side are counted into a fifth scalar.  With that index
    the ring's ids are written as wide as the advance's entries, on the
    narrowest of ``_RING_RUNGS`` they fit, by the flow-wide scatter
    above it (:func:`_ring_ids`; the same ring to the bit), an advance
    that does not commit logs past the ring's end instead of selecting
    the old ring back, and the advances that took a rung are counted
    into a sixth.  A sim without
    a collective, and the fleet (whose ``vmap`` would run every side),
    pass neither index.

    ``has_fatpipe`` (static) solves the constraints ``c_fatpipe`` marks
    as FATPIPE: a constraint's usage is the max over its unfixed
    elements, and its remaining is never decremented (``fixpoint``'s
    FATPIPE arm, as maxmin.cpp solves a host's loopback).  Without it
    ``c_fatpipe`` is not read, every constraint is SHARED and the
    program is the one it always was, to its lowered text.
    """
    # trace-time only: a steady-state superstep loop re-enters the jit
    # cache, so this stays flat; a nonzero delta on a repeat run means
    # something is busting the cache (shape/static churn)
    opstats.bump("retraces")
    dtype = e_w.dtype
    fat = c_fatpipe if has_fatpipe else jnp.zeros(n_c, bool)
    eps_c = jnp.asarray(eps, dtype)
    k = jnp.asarray(k, jnp.int32)
    round_budget = jnp.asarray(round_budget, jnp.int32)
    stop_live = jnp.asarray(stop_live, jnp.int32)
    # completions scatter to [0, n_ev); the out-of-range sentinel and
    # the ring capacity grow by k_max when faults may interleave and
    # by n_v when collective activations may
    ring_n = (n_v + (k_max if has_tape else 0)
              + (n_v if has_coll else 0))
    if has_tape:
        T = tape_t.shape[0]
        t0 = jnp.asarray(t0, jnp.float64)
    index = (v_ptr, ve_idx) if has_coll and v_ptr is not None else None
    src_index = has_coll and s_ptr is not None
    if src_index:
        out_deg = s_ptr[1:] - s_ptr[:-1]
        rungs = [w for w in _RING_RUNGS if 8 * w <= n_v]
    # where the loop state holds the pending-activation dates
    ready_at = 13 + 2 * has_tape + 1

    def cond(st):
        pen_c = st[0]
        flag, adv, rounds = st[11], st[9], st[10]
        n_live = jnp.count_nonzero(pen_c > 0).astype(jnp.int32)
        alive = n_live > stop_live
        if has_coll:
            # a dormant flow with a pending activation keeps the loop
            # walking even when nothing currently holds bandwidth
            alive = alive | jnp.any(jnp.isfinite(st[ready_at]))
        return ((flag == _FLAG_OK) & (adv < k) & (rounds < round_budget)
                & alive)

    def body(st):
        idx = 13
        (pen_c, rem_c, t_sum, t_comp, ring_t, ring_id, adv_dt,
         adv_nev, n_ev, adv, rounds, flag, worked) = st[:13]
        if has_tape:
            cb_c, tpos = st[idx], st[idx + 1]
            idx += 2
        else:
            cb_c = c_bound
        if has_coll:
            pred_c, ready_c, live_sum, fires, var_entries = st[idx:idx + 5]
            if src_index:
                src_walks, ring_narrow = st[idx + 5:idx + 7]
        with jax.named_scope("sg.drain.solve"):
            out = fixpoint(e_var, e_cnst, e_w, cb_c, fat, pen_c, v_bound,
                           eps_c, n_c, n_v, parallel_rounds=True,
                           carry=None, max_rounds=round_budget - rounds,
                           return_carry=True, has_bounds=has_bounds,
                           has_fatpipe=has_fatpipe, var_index=index)
            carry2 = out[4]
            r = out[3].astype(jnp.int32)
            converged = jnp.count_nonzero(carry2[4]) == 0
        with jax.named_scope("sg.drain.advance"):
            if has_tape or has_coll:
                # planned dt (the _advance_math front half), then the event
                # peek: fire iff the next fault/activation date lands inside
                # this advance (ties go to the event, and a pending event
                # rescues an infinite dt).  Clock math in f64: the event
                # dates are f64, so placement is exact even on f32 drains.
                live = pen_c > 0
                rate = jnp.where(live, carry2[0], 0.0)
                flowing = live & (rate > 0)
                dt_plan = jnp.min(jnp.where(
                    flowing, rem_c / jnp.where(flowing, rate, 1.0), jnp.inf))
                if has_tape:
                    ti = jnp.minimum(tpos, T - 1)
                    next_ft = jnp.where(tpos < T, tape_t[ti], jnp.inf)
                else:
                    next_ft = jnp.asarray(jnp.inf, jnp.float64)
                if has_coll:
                    # collective clocks are absolute (carried across
                    # dispatches); t0 is already folded into t_sum
                    next_at = jnp.min(ready_c)
                    now = t_sum.astype(jnp.float64)
                else:
                    next_at = jnp.asarray(jnp.inf, jnp.float64)
                    now = t0 + t_sum.astype(jnp.float64)
                next_t = jnp.minimum(next_ft, next_at)
                fire = jnp.isfinite(next_t) & (
                    next_t <= now + dt_plan.astype(jnp.float64))
                dt = jnp.where(
                    fire, jnp.maximum(next_t - now, 0.0).astype(dtype),
                    dt_plan)
                f_fire = fire & (next_ft <= next_at)
                prod = _rounded_product(rate, dt, zero_bits)
                rem2 = jnp.where(flowing, rem_c - prod, rem_c)
                done = flowing & (rem2 < thresh)
                pen2 = jnp.where(done, 0.0, pen_c)
                rem2 = jnp.where(done, 0.0, rem2)
            else:
                dt, pen2, rem2, done = _advance_math(pen_c, rem_c, thresh,
                                                     carry2[0], zero_bits)
            ok = converged & jnp.isfinite(dt)

            # Kahan clock: per-advance dts combine compensated so the f32
            # in-dispatch clock error is O(k ulp), not O(advances) drift
            # (a collective's pair is float64 whatever the solve dtype)
            y = (dt.astype(jnp.float64) if has_coll else dt) - t_comp
            t_new = t_sum + y
            t_comp2 = (t_new - t_sum) - y
            t_ring = t_new.astype(dtype) if has_coll else t_new

        with jax.named_scope("sg.drain.ring"):
            if has_coll:
                with jax.named_scope("sg.drain.coll"):
                    # activations: every pending flow whose ready date is
                    # <= the event date wakes up (penalty scatter below)
                    # and its ready slot is consumed
                    a_any = fire & (next_at <= next_ft)
                    act = a_any & (ready_c <= next_t)
                    acount = jnp.cumsum(act.astype(jnp.int32))
            # An advance logs, at its one date, its completions in stable
            # slot order (cumsum, the within-advance order the host paths
            # emit), then the fault that fired, then the activations: one
            # run of entries [n_ev, n_new).  So the dates are one range
            # select, and every id but the fault's one scatter; the slots
            # it drops scatter out of range.  2D index shape: the ops/
            # scatter convention.
            dcount = jnp.cumsum(done.astype(jnp.int32))
            n_done = dcount[-1]
            pos = jnp.where(done, n_ev + dcount - 1, ring_n)
            val = ids
            n_new = n_ev + n_done
            if has_tape:
                # the fault fires AFTER this advance's completions (they
                # retire AT the event date; the new capacity governs from
                # the event onward): tagged entry id = -(1 + slot), bound
                # scatter, and cursor bump — all dropped when not firing
                slot = tape_slot[ti]
                fpos = jnp.where(f_fire, n_new, ring_n)
                n_new = n_new + f_fire.astype(jnp.int32)
                cb2 = cb_c.at[jnp.where(f_fire, slot, n_c)].set(
                    tape_val[ti], mode="drop")
                tpos2 = tpos + (ok & f_fire).astype(jnp.int32)
            if has_coll:
                # a fired successor's tagged entry id = -(1 + n_c +
                # flow_id) rides the completions' scatter, since no flow
                # both completes (it was live) and activates (it held a
                # ready date) in one advance: a date is set only where
                # pred falls to zero, an activation consumes it, and a
                # flow that is live or dated holds pred <= 0, so it never
                # gets one.  DrainSim and BatchDrainSim refuse a start
                # that breaks this (_check_collective_start).
                pos = jnp.where(done, pos, jnp.where(
                    act, n_new + acount - 1, ring_n))
                val = jnp.where(done, ids, -(1 + n_c + ids))
                n_new = n_new + acount[-1]
            if src_index:
                # an advance that does not commit logs past the ring's
                # end: its writes drop, so no select of the old ring
                # follows them and they update the ring in place
                skip = jnp.where(ok, 0, ring_n)
                pos = pos + skip
                if has_tape:
                    fpos = fpos + skip
            wide = lambda ring_id: ring_id.at[pos.reshape(-1, group)].set(
                val.reshape(-1, group), mode="drop")
            if src_index:
                ring_id2, narrow = _ring_ids(
                    ring_id, wide, dcount, acount, ids, n_ev + skip,
                    n_new - n_ev, f_fire.astype(jnp.int32) if has_tape else 0,
                    n_c, rungs)
            else:
                ring_id2 = wide(ring_id)
            if has_tape:
                ring_id2 = ring_id2.at[fpos].set(-(1 + slot), mode="drop")
            at = lax.iota(jnp.int32, ring_n)
            if src_index:
                ring_t2 = jnp.where((at >= n_ev + skip) & (at < n_new + skip),
                                    t_ring, ring_t)
            else:
                ring_t2 = jnp.where((at >= n_ev) & (at < n_new), t_ring,
                                    ring_t)

            if has_coll:
                with jax.named_scope("sg.drain.coll"):
                    pen2 = jnp.where(act, jnp.asarray(1.0, dtype), pen2)
                    ready2 = jnp.where(act, jnp.inf, ready_c)
                    # DAG walk: completions decrement their successors'
                    # outstanding-predecessor counts; flows reaching zero get
                    # a ready date = completion clock + exec cost (activation
                    # happens on a LATER advance, never the completing one)
                    def edge_walk(pred):
                        return pred.at[edge_dst].add(
                            -jnp.take(done.astype(jnp.int32), edge_src),
                            mode="drop")

                    if src_index:
                        # the same counts from the completions' own
                        # edges, when they are few: the test reads no
                        # index
                        own = jnp.sum(jnp.where(done, out_deg, 0))
                        few = ((own <= _SRC_WALK_EDGES)
                               & (n_done <= _SRC_WALK_EDGES))
                        pred2 = lax.cond(
                            few,
                            lambda pred: _succ_walk(
                                pred, ring_id2, n_ev, n_done, s_ptr, s_dst,
                                _SRC_WALK_EDGES),
                            edge_walk, pred_c)
                    else:
                        pred2 = edge_walk(pred_c)
                    newly = (pred2 <= 0) & (pred_c > 0)
                    ready2 = jnp.where(
                        newly, t_new.astype(jnp.float64) + exec_cost, ready2)
                    # what the tape did, for the packed tail: the flows live
                    # as this advance entered, and the activations it fired
                    live_sum2 = _pair_add(live_sum, jnp.count_nonzero(
                        live).astype(jnp.int32))
                    fires2 = fires + acount[-1]

            adv_dt2 = adv_dt.at[adv].set(dt.astype(dtype))
            adv_nev2 = adv_nev.at[adv].set(n_new)

            flag2 = jnp.where(~converged, _FLAG_BUDGET,
                              jnp.where(jnp.isfinite(dt), _FLAG_OK,
                                        _FLAG_STALLED)).astype(jnp.int32)

            sel = lambda a, b: jnp.where(ok, a, b)
            out_st = (sel(pen2, pen_c), sel(rem2, rem_c),
                      sel(t_new, t_sum), sel(t_comp2, t_comp),
                      ring_t2 if src_index else jnp.where(ok, ring_t2, ring_t),
                      ring_id2 if src_index
                      else jnp.where(ok, ring_id2, ring_id),
                      jnp.where(ok, adv_dt2, adv_dt),
                      jnp.where(ok, adv_nev2, adv_nev),
                      sel(n_new, n_ev),
                      adv + ok.astype(jnp.int32), rounds + r, flag2,
                      _pair_add(worked, out[7][1], out[7][0]))
            if has_tape:
                out_st = out_st + (jnp.where(ok, cb2, cb_c),
                                   jnp.where(ok, tpos2, tpos))
            if has_coll:
                out_st = out_st + (jnp.where(ok, pred2, pred_c),
                                   jnp.where(ok, ready2, ready_c),
                                   jnp.where(ok, live_sum2, live_sum),
                                   sel(fires2, fires),
                                   sel(var_entries + out[9], var_entries))
                if src_index:
                    out_st = out_st + (
                        sel(src_walks + few.astype(jnp.int32), src_walks),
                        sel(ring_narrow + narrow.astype(jnp.int32),
                            ring_narrow))
        return out_st

    zero = jnp.asarray(0, jnp.int32)
    if has_coll:
        # the Kahan clock pair is carried across dispatches so the
        # recurrence — and therefore every event date — is invariant
        # to how advances are grouped into dispatches
        clk0 = (coll_clk[0], coll_clk[1])
    else:
        clk0 = (jnp.asarray(0.0, dtype), jnp.asarray(0.0, dtype))
    st0 = (pen, rem) + clk0 + (
           jnp.zeros(ring_n, dtype), jnp.zeros(ring_n, jnp.int32),
           jnp.zeros(k_max, dtype), jnp.zeros(k_max, jnp.int32),
           zero, zero, zero, zero, jnp.zeros(2, jnp.int32))
    if has_tape:
        st0 = st0 + (c_bound, jnp.asarray(tape_pos, jnp.int32))
    if has_coll:
        st0 = st0 + (coll_pred, coll_ready, jnp.zeros(2, jnp.int32), zero,
                     zero)
        if src_index:
            st0 = st0 + (zero, zero)
    st = lax.while_loop(cond, body, st0)
    (pen_o, rem_o, t_sum, t_comp_o, ring_t, ring_id, adv_dt, adv_nev,
     n_ev, adv, rounds, flag, worked) = st[:13]
    idx = 13
    if has_tape:
        cb_o, tpos_o = st[idx], st[idx + 1]
        idx += 2
    else:
        cb_o = c_bound
        tpos_o = jnp.asarray(tape_pos, jnp.int32)
    if has_coll:
        pred_o, ready_o, live_sum, fires, var_entries = st[idx:idx + 5]
        clk_o = jnp.stack([t_sum, t_comp_o])
        t_sum = t_sum.astype(dtype)
    else:
        pred_o, ready_o, clk_o = coll_pred, coll_ready, coll_clk
    with jax.named_scope("sg.drain.pack"):
        n_live = jnp.count_nonzero(pen_o > 0)
        if index is None:
            live_elems = jnp.count_nonzero(
                (e_w > 0) & jnp.take(pen_o > 0, e_var, fill_value=False))
        else:
            # the same count from the index: no pass over the list
            live_elems = jnp.sum(jnp.where(pen_o > 0,
                                           v_ptr[1:] - v_ptr[:-1], 0))
        stats = jnp.stack([rounds.astype(dtype), adv.astype(dtype),
                           n_ev.astype(dtype), t_sum,
                           n_live.astype(dtype), flag.astype(dtype),
                           live_elems.astype(dtype),
                           *worked.astype(dtype)])
        parts = [stats, adv_dt, adv_nev.astype(dtype),
                 ring_t, ring_id.astype(dtype)]
        if has_coll:
            tail = [*live_sum, fires, var_entries]
            if src_index:
                tail += st[idx + 5:idx + 7]
            parts.append(jnp.stack(tail).astype(dtype))
        packed = jnp.concatenate(parts)
    return pen_o, rem_o, cb_o, tpos_o, pred_o, ready_o, clk_o, packed


_drain_superstep = functools.partial(
    jax.jit, static_argnames=("eps", "n_c", "n_v", "k_max", "group",
                              "has_bounds", "has_tape", "has_coll",
                              "has_fatpipe"))(_superstep_program)


#: transition-payload field order (index = the static target code in
#: the payload layout); the first three scatter into the 2D element
#: arrays, the rest into the per-constraint / per-flow vectors
_TRANSITION_FIELDS = ("e_var", "e_cnst", "e_w", "c_bound",
                      "v_penalty", "remains", "thresh", "v_bound")


@functools.partial(jax.jit, static_argnames=("layout", "group"))
def _apply_transition_payload(payload, ev, ec, ew, cb, pen, rem,
                              thresh, vb, layout, group: int):
    """Scatter one fused transition payload into the plan's device
    arrays (the drain-path analogue of lmm_warm._apply_deltas): the
    payload is a single f64 vector of per-field [indices..., values...]
    runs and `layout` is the static ``(target, offset, n)`` tuple
    describing them.  Flow slots and element slots are < 2^32, so the
    f64 round trip is exact; element targets are 2D (group columns,
    the ops/ scatter convention).  Padded payload entries repeat a
    run's first (index, value) pair — duplicate same-value scatters are
    harmless."""
    targets = [ev, ec, ew, cb, pen, rem, thresh, vb]
    for ti, off, n in layout:
        idx = payload[off:off + n].astype(jnp.int32)
        vals = payload[off + n:off + 2 * n]
        t = targets[ti]
        if t.ndim == 2:
            targets[ti] = t.at[idx // group, idx % group].set(
                vals.astype(t.dtype))
        else:
            targets[ti] = t.at[idx].set(vals.astype(t.dtype))
    return tuple(targets)


@jax.jit
def _drain_forced_advance(pen, rem, thresh, values, delta, zero_bits):
    """Advance the flow state by an EXTERNALLY chosen delta (an engine
    advance decided by another model or a latency expiry, delta <= the
    plan's own dt): decrement remains at the solved rates and retire
    threshold crossings with the same strict-< rule as _advance_math,
    so a partial advance that does push a flow under its threshold
    finishes it exactly where the generic double_update walk would."""
    dtype = rem.dtype
    live = pen > 0
    rate = jnp.where(live, values, 0.0)
    flowing = live & (rate > 0)
    rem2 = jnp.where(flowing,
                     rem - _rounded_product(rate, delta, zero_bits), rem)
    done = flowing & (rem2 < thresh)
    pen2 = jnp.where(done, 0.0, pen)
    rem2 = jnp.where(done, 0.0, rem2)
    n_live = jnp.count_nonzero(pen2 > 0)
    head = n_live.astype(dtype)[None]
    return pen2, rem2, jnp.concatenate([head, done.astype(dtype)])


@functools.partial(jax.jit,
                   static_argnames=("vh", "eh", "gv", "ge"))
def _drain_repack(e_var, e_cnst, e_w, pen, rem, thresh, ids,
                  vh: int, eh: int, gv: int, ge: int):
    """On-device repack to halved static shapes: stable live-first
    partition of the flow rows and the element rows (the compaction-
    chain machinery, lmm_jax._stable_livefirst_perm), then a static
    slice.  Exact for the same reason the chain is: live relative
    order is preserved, so the scatter-reduction order over survivors —
    and therefore event ordering — is unchanged, and dropped rows only
    contributed identity values.  NO host transfer: the caller decides
    from counts it already fetched, and every output stays on device.
    """
    V = pen.shape[0]
    livemask = pen > 0
    perm_v = _stable_livefirst_perm(livemask, gv)
    keep_v = perm_v[:vh]
    pen2 = jnp.take(pen, keep_v)
    rem2 = jnp.take(rem, keep_v)
    thresh2 = jnp.take(thresh, keep_v)
    ids2 = jnp.take(ids, keep_v)
    old2new = jnp.zeros(V, jnp.int32).at[
        perm_v.reshape(-1, gv)].set(
        jnp.arange(V, dtype=jnp.int32).reshape(-1, gv))

    ev = e_var.reshape(-1)
    ec = e_cnst.reshape(-1)
    ew = e_w.reshape(-1)
    elive = (ew > 0) & jnp.take(livemask, ev)
    perm_e = _stable_livefirst_perm(elive, ge)
    sel = perm_e[:eh]
    ev2 = jnp.take(old2new, jnp.take(ev, sel))
    # dead-tail elements (weight forced to 0) may map past vh: clamp so
    # downstream gathers stay in range — their weight masks them out
    ev2 = jnp.minimum(ev2, vh - 1)
    ec2 = jnp.take(ec, sel)
    ew2 = jnp.where(jnp.take(elive, sel), jnp.take(ew, sel), 0.0)
    return (ev2.reshape(-1, 8), ec2.reshape(-1, 8), ew2.reshape(-1, 8),
            pen2, rem2, thresh2, ids2)


@functools.partial(jax.jit, static_argnames=("vh",))
def _repack_vbound(v_bound, pen, vh: int):
    """Bound rows follow the same stable live-first permutation."""
    perm_v = _stable_livefirst_perm(pen > 0, _pos_group(pen.shape[0]))
    return jnp.take(v_bound, perm_v[:vh])


class SuperstepToken:
    """One issued (possibly still in-flight) superstep dispatch.

    The token owns the dispatch's input AND output device arrays: jax
    arrays are immutable, so ``(pen_in, rem_in)`` is a free snapshot of
    the pre-dispatch flow state and ``(pen_out, rem_out)`` is the
    double-buffered post-dispatch state the NEXT speculative dispatch
    chains from.  Nothing is committed to the owning sim until the
    token is collected; discarding an un-collected token costs nothing
    but the device work it already burned."""

    __slots__ = ("pen_in", "rem_in", "pen_out", "rem_out", "packed",
                 "k", "k_max", "want_stop", "speculative",
                 "cb_in", "cb_out", "tpos_out", "t0",
                 "pred_out", "ready_out", "clk_out", "seq")

    def __init__(self, pen_in, rem_in, pen_out, rem_out, packed,
                 k: int, k_max: int, want_stop: int, speculative: bool,
                 cb_in=None, cb_out=None, tpos_out=None, t0=None,
                 pred_out=None, ready_out=None, clk_out=None,
                 seq: Optional[int] = None):
        self.pen_in = pen_in
        self.rem_in = rem_in
        self.pen_out = pen_out
        self.rem_out = rem_out
        self.packed = packed
        self.k = k
        self.k_max = k_max
        self.want_stop = want_stop
        self.speculative = speculative
        # fault-tape double buffers: the dispatch's input/output bounds
        # and the post-dispatch tape cursor + the dispatch's f64 base
        # clock (what chained speculative issues derive their t0 from)
        self.cb_in = cb_in
        self.cb_out = cb_out
        self.tpos_out = tpos_out
        self.t0 = t0
        # collective-tape double buffers: post-dispatch predecessor
        # counts, pending-activation dates, and the carried Kahan
        # clock pair speculative successors chain from
        self.pred_out = pred_out
        self.ready_out = ready_out
        self.clk_out = clk_out
        # which dispatch of its sim this is (``DrainSim.supersteps`` at
        # issue): the ``id`` its issue and collect spans share
        self.seq = seq


class DrainSim:
    """Drain a fixed flow set to completion on the JAX backend.

    Parameters mirror a flattened network-only LMM system: COO elements
    (e_var, e_cnst, e_w), constraint capacities, per-flow penalties
    (1.0 = live) and sizes (bytes).  `solve_chunk` bounds device rounds
    per dispatch (a bound on one dispatch's run time); `repack_at`
    triggers a repack when
    the live fraction drops below it.

    `done_eps` retires a flow when its post-advance remainder falls to
    ``done_eps * size`` (``done_mode="rel"``, the reference's relative
    sg_maxmin_precision semantics — REQUIRED for f32 backends to keep
    the f64 tie groups) or to the absolute ``done_eps``
    (``done_mode="abs"``, bit-matching the engine's generic
    double_update path in f64).

    `superstep=K` (>= 1) batches up to K advances per dispatch (~1/K
    syncs/advance) with on-device repacks.  `v_bound` optionally caps
    per-flow rates (TCP-gamma windows etc.).

    `pipeline=D` keeps up to D speculative
    supersteps in flight beyond the one being collected: the host
    processes ring N while the device executes ring N+1, hiding the
    dispatch round trip.  Results are bit-identical to `pipeline=0` —
    any host-side mutation while processing a ring (repack, budget
    rescue, stall, completion) discards the in-flight work and replays
    it from the committed state (see the module docstring).

    `c_fatpipe` marks the constraints whose sharing is FATPIPE (a
    host's loopback): each flow on one gets all of its capacity, as
    maxmin.cpp solves it.  None (the default) is all SHARED, and so is
    a mask with no constraint marked: the programs are then the ones a
    sim without the argument runs.  `coll_loopback` (per flow, with
    `collective`) marks the flows between two ranks of one host; their
    completions are counted (``collective_loopback_completions``).
    """

    def __init__(self, e_var, e_cnst, e_w, c_bound, sizes,
                 eps: float = 1e-5, done_eps: float = 1e-4,
                 dtype=np.float32, solve_chunk: int = 0,
                 repack_at: float = 0.5, device=None,
                 v_bound=None, done_mode: str = "rel",
                 superstep: int = 16,
                 superstep_rounds: int = 0, repack_min: int = 1024,
                 penalty=None, remains=None, pipeline: int = 0,
                 tape=None, collective=None, c_fatpipe=None,
                 coll_loopback=None):
        self.eps = float(eps)
        self.done_eps = float(done_eps)
        if done_mode not in ("rel", "abs"):
            raise ValueError(f"Unknown done_mode {done_mode!r} "
                             "(expected rel or abs)")
        self.done_mode = done_mode
        self.dtype = solve_dtype(dtype, "DrainSim(dtype=)", device)
        if not solve_chunk:
            # bound one dispatch's run time: a round's device time
            # grows with the element count, so big systems get fewer
            # rounds per dispatch
            solve_chunk = 16 if len(e_var) >= 1 << 20 else 64
        self.solve_chunk = int(solve_chunk)
        self.repack_at = float(repack_at)
        # below this live count a repack costs more than it saves
        # (and halved shapes recompile); tests lower it to exercise
        # the repack kernels at small scale
        self.repack_min = int(repack_min)
        self.device = device
        self.superstep_k = int(superstep)
        if self.superstep_k < 1:
            raise ValueError(f"DrainSim(superstep={superstep}): the "
                             "drain runs as supersteps of K >= 1 "
                             "advances a dispatch")
        if not superstep_rounds:
            # Per-dispatch round budget, the bound on one dispatch's
            # run time: on an accelerator a superstep may burn at most
            # what a few solve chunks would; on CPU the budget just has
            # to cover K advances of O(10-100)-round solves.
            platform = (device.platform if device is not None
                        else default_platform())
            if platform == "cpu":
                superstep_rounds = self.superstep_k * 512
            else:
                superstep_rounds = self.solve_chunk * 4
        self.superstep_rounds = int(superstep_rounds)

        with opstats.span("drain.init"):
            elems = (np.asarray(e_var, np.int32),
                     np.asarray(e_cnst, np.int32),
                     np.asarray(e_w, self.dtype))
            self.n_c = len(c_bound)
            self.n_v = len(sizes)
            self._c_bound = np.asarray(c_bound, self.dtype)
            self._sizes = np.asarray(sizes, np.float64)
            if self.n_v >= 1 << 24 and self.dtype == np.float32:
                raise ValueError(
                    "flow ids beyond 2^24 are not exact in the f32 "
                    "single-transfer fetch; use float64 or shard the drain")
            if done_mode == "rel":
                thresh = self.done_eps * self._sizes
            else:
                thresh = np.full(self.n_v, self.done_eps)
            # engine plans hand in mid-simulation state: per-slot penalties
            # (0 = not a live flow) and already-partially-drained remains
            pen0 = (np.asarray(penalty, self.dtype) if penalty is not None
                    else np.ones(self.n_v, self.dtype))
            rem0 = (np.asarray(remains, self.dtype) if remains is not None
                    else self._sizes.astype(self.dtype))
            self._pen = jax.device_put(pen0, device)
            self._rem = jax.device_put(rem0, device)
            self._thresh = jax.device_put(thresh.astype(self.dtype), device)
            self._ids_dev = jax.device_put(
                np.arange(self.n_v, dtype=np.int32), device)
            self._dev = [jax.device_put(_to2d(a), device) for a in elems]
            self._cb = jax.device_put(self._c_bound, device)
            if v_bound is not None:
                vb = np.asarray(v_bound, self.dtype)
                self.has_bounds = bool(np.any(vb > 0))
            else:
                vb = np.full(self.n_v, -1.0, self.dtype)
                self.has_bounds = False
            self._vb = jax.device_put(vb, device)
            fat = (np.zeros(self.n_c, bool) if c_fatpipe is None
                   else np.asarray(c_fatpipe, bool))
            if fat.shape != (self.n_c,):
                raise ValueError(f"c_fatpipe must have one entry per "
                                 f"constraint ({self.n_c}), got "
                                 f"{fat.shape}")
            #: the statics and the mask every solve program of this sim
            #: is called with: nothing at all without a FATPIPE
            #: constraint, so such a sim runs the SHARED programs
            self.has_fatpipe = bool(fat.any())
            self._fat_kw = (dict(c_fatpipe=jax.device_put(fat, device),
                                 has_fatpipe=True)
                            if self.has_fatpipe else {})

            # fault event tape: `tape` is (dates, slots, values) — f64
            # absolute sim dates (sorted), constraint slots, and the
            # ABSOLUTE new capacity each event installs (mirroring the
            # engine's set_bandwidth semantics, so a recovery restores the
            # exact pre-fault bound).  Device-resident; the superstep loop
            # clamps dt so no advance steps over an entry (see
            # _superstep_program).
            self.has_tape = False
            self.fault_events: list = []     # (time, constraint slot)
            self._tpos_host = 0              # fired-entry count (host view)
            self._last_fired = False
            if tape is not None and len(tape[0]):
                tt = np.asarray(tape[0], np.float64)
                ts = np.asarray(tape[1], np.int32)
                tv = np.asarray(tape[2], np.float64).astype(self.dtype)
                if not (len(tt) == len(ts) == len(tv)):
                    raise ValueError("tape arrays must have equal length")
                if np.any(np.diff(tt) < 0):
                    raise ValueError("tape dates must be time-sorted")
                if np.any((ts < 0) | (ts >= self.n_c)):
                    raise ValueError("tape slot out of range")
                self.has_tape = True
                self._tape = tuple(jax.device_put(a, device)
                                   for a in (tt, ts, tv))
                self._tpos = jax.device_put(np.int32(0), device)
                opstats.bump("fault_tape_slots", len(tt))
                opstats.bump("uploaded_bytes_delta",
                             tt.nbytes + ts.nbytes + tv.nbytes)
            else:
                # dummy triple keeps the jit call sites uniform; with
                # has_tape=False the program never reads it (XLA DCE)
                self._tape = (
                    jax.device_put(np.full(1, np.inf), device),
                    jax.device_put(np.full(1, self.n_c, np.int32), device),
                    jax.device_put(np.zeros(1, self.dtype), device))
                self._tpos = np.int32(0)

            # collective schedule tape: `collective` is (pred, ready,
            # edge_src, edge_dst, exec_cost) — the compiled comm DAG
            # (collectives.tape.DeviceCollective.drain_args()), with
            # the element list's (v_ptr, ve_idx) and the DAG's (s_ptr,
            # s_dst) behind them where the caller has them already
            # (``make_sim``).  Dormant
            # flows (penalty 0) activate on device when their outstanding
            # predecessor count hits zero; the superstep loop walks the
            # whole schedule without host involvement (see
            # _superstep_program's has_coll docs).
            self.has_coll = False
            self.collective_events: list = []   # (time, flow id) activations
            if collective is not None:
                cp, cr, ces, ced, cec, *index = collective
                cp = np.asarray(cp, np.int32)
                cr = np.asarray(cr, np.float64)
                ces = np.asarray(ces, np.int32)
                ced = np.asarray(ced, np.int32)
                cec = np.asarray(cec, np.float64)
                if not (len(cp) == len(cr) == len(cec) == self.n_v):
                    raise ValueError("collective arrays must be per-flow "
                                     f"(n_v={self.n_v})")
                if len(ces) != len(ced):
                    raise ValueError("collective edge arrays must have "
                                     "equal length")
                _check_collective_start(pen0, cp, cr)
                self.has_coll = True
                # a repack would scramble the DAG's static slot indexing
                self.repack_min = 1 << 62
                self._coll = tuple(jax.device_put(a, device)
                                   for a in (cp, cr))
                self._coll_edges = tuple(jax.device_put(a, device)
                                         for a in (ces, ced, cec))
                self._coll_clk = jax.device_put(
                    np.zeros(2, np.float64), device)
                self._coll_total = int(self.n_v)
                # the element list's variable-major index, with which a
                # solve finds the live flows' elements without a pass
                # over the list; DeviceCollective brings its own, built
                # once per lowered collective
                # and the DAG's source-major one, with which an advance
                # finds its completions' successor edges
                index = [np.asarray(a, np.int32) for a in (
                    *(index[:2] or var_index(elems[0], elems[2], self.n_v)),
                    *(index[2:] or succ_index(ces, ced, self.n_v)))]
                self._var_index = tuple(jax.device_put(a, device)
                                        for a in index[:2])
                self._succ_index = tuple(jax.device_put(a, device)
                                         for a in index[2:])
                #: the carried Kahan pair as the host replays it from a
                #: dispatch's dt table (see _demux)
                self._coll_clk_host = (0.0, 0.0)
                #: the flows between two ranks of one host, whose
                #: completions _superstep_collect counts
                self._coll_loopback = (
                    None if coll_loopback is None
                    else np.asarray(coll_loopback, bool))
                if (self._coll_loopback is not None
                        and self._coll_loopback.shape != (self.n_v,)):
                    raise ValueError("coll_loopback must be per-flow "
                                     f"(n_v={self.n_v})")
                opstats.bump("collective_tape_slots", self.n_v)
                opstats.bump("uploaded_bytes_delta",
                             cp.nbytes + cr.nbytes + ces.nbytes
                             + ced.nbytes + cec.nbytes
                             + sum(a.nbytes for a in index))
            else:
                self._coll = (
                    jax.device_put(np.zeros(1, np.int32), device),
                    jax.device_put(np.full(1, np.inf), device))
                self._coll_edges = (
                    jax.device_put(np.zeros(1, np.int32), device),
                    jax.device_put(np.zeros(1, np.int32), device),
                    jax.device_put(np.zeros(1, np.float64), device))
                self._coll_clk = jax.device_put(np.zeros(2, np.float64),
                                                device)
                self._coll_total = 0
                self._var_index = self._succ_index = (None, None)
                self._coll_loopback = None

            opstats.bump("uploaded_bytes_full",
                         pen0.nbytes + rem0.nbytes + thresh.nbytes
                         + self._ids_dev.nbytes + self._cb.nbytes + vb.nbytes
                         + sum(d.nbytes for d in self._dev)
                         + (fat.nbytes if self.has_fatpipe else 0))
        self._live0 = (int(np.count_nonzero(pen0 > 0))
                       if penalty is not None else self.n_v)

        self.pipeline = int(pipeline)

        self.t = 0.0              # f64 master clock (host-accumulated)
        self.events: list = []   # (time, original flow id), completion order
        self.advances = 0
        self.rounds = 0
        self.syncs = 0
        self.repacks = 0
        self.supersteps = 0
        # speculation census (pipelined driver + drain fast path)
        self.spec_issued = 0
        self.spec_committed = 0
        self.spec_rolled_back = 0
        #: optional event consumer, called once per collected superstep
        #: with the batch list [(dt, [flow ids])] — the host-side work
        #: (engine bookkeeping, demux, logging) the pipelined driver
        #: overlaps with the next in-flight dispatch.  Runs INSIDE the
        #: collect, i.e. between the ring fetch and the next blocking
        #: point, for both the pipelined and synchronous drivers.
        self.on_batches = None

    # -- repack ------------------------------------------------------------

    def _repack_device(self, n_live: int, live_elems: int) -> bool:
        """Halve the device arrays in place with the stable live-first
        partition kernel — a dispatch with NO transfer.  Only when both
        the live flow and live element populations fit the halves."""
        E = self._dev[0].size
        vh = self.n_v // 2
        eh = -(-(E // 2) // 8) * 8
        if n_live > vh or live_elems > eh:
            return False
        gv = _pos_group(self.n_v)
        ge = _pos_group(E)
        ev, ec, ew, pen, rem, thresh, ids = _drain_repack(
            *self._dev, self._pen, self._rem, self._thresh,
            self._ids_dev, vh=vh, eh=eh, gv=gv, ge=ge)
        if self.has_bounds:
            self._vb = _repack_vbound(self._vb, self._pen, vh=vh)
        else:
            self._vb = jax.device_put(
                np.full(vh, -1.0, self.dtype), self.device)
        self._dev = [ev, ec, ew]
        self._pen, self._rem, self._thresh = pen, rem, thresh
        self._ids_dev = ids
        self.n_v = vh
        self._live0 = n_live
        self.repacks += 1
        return True

    def _should_repack(self, n_live: int) -> bool:
        return bool(n_live and n_live <= self._live0 * self.repack_at
                    and n_live >= self.repack_min)

    # -- solve-only and forced-advance paths (engine fast path) ------------

    def solve_rates(self) -> np.ndarray:
        """Solve the CURRENT flow state to convergence and fetch the
        rate vector (no time advance) — the engine fast path uses this
        to hand a partial advance back to the generic model loop."""
        carry = None
        while True:
            carry, stats = _drain_solve_chunk(
                *self._dev, self._cb, self._pen, self._vb, carry,
                eps=self.eps, n_c=self.n_c, n_v=self.n_v,
                chunk=self.solve_chunk, has_bounds=self.has_bounds,
                **self._fat_kw)
            st = np.asarray(stats)
            self.syncs += 1
            if int(st[1]) == 0:
                break
            if int(st[0]) >= _MAX_ROUNDS:
                raise SolveError("drain solve did not converge")
        self.rounds += int(st[0])
        rates = np.asarray(carry[0])
        self.syncs += 1
        return rates

    def apply_transitions(self, updates: dict) -> int:
        """Absorb a batch of recognized engine transitions into the
        device plan: `updates` maps _TRANSITION_FIELDS names to
        ``(slot_indices, values)`` pairs, shipped as ONE fused indexed
        payload (pow2-bucketed, so payload shapes — and therefore jit
        signatures — are bounded) and applied as device scatters.  No
        re-flatten, no platform re-upload; cost is O(dirty slots).
        Returns the number of real (unpadded) slots scattered."""
        layout = []
        chunks = []
        off = 0
        slots = 0
        for ti, field in enumerate(_TRANSITION_FIELDS):
            pair = updates.get(field)
            if pair is None or len(pair[0]) == 0:
                continue
            ix = np.asarray(pair[0], np.float64)
            vals = np.asarray(pair[1], np.float64)
            slots += len(ix)
            n = _bucket(len(ix), floor=8)
            if n > len(ix):
                ix = np.concatenate([ix, np.repeat(ix[:1], n - len(ix))])
                vals = np.concatenate([vals,
                                       np.repeat(vals[:1], n - len(vals))])
            layout.append((ti, off, n))
            chunks.append(ix)
            chunks.append(vals)
            off += 2 * n
        if not layout:
            return 0
        vb_pair = updates.get("v_bound")
        if vb_pair is not None and len(vb_pair[0]) \
                and np.any(np.asarray(vb_pair[1]) > 0):
            self.has_bounds = True
        if any(ti < 3 for ti, _, _ in layout):
            # the element list changes under its variable-major index
            self._var_index = (None, None)
        payload = jax.device_put(np.concatenate(chunks), self.device)
        out = _apply_transition_payload(
            payload, *self._dev, self._cb, self._pen, self._rem,
            self._thresh, self._vb, layout=tuple(layout),
            group=self._dev[0].shape[1])
        self._dev = list(out[:3])
        (self._cb, self._pen, self._rem, self._thresh, self._vb) = out[3:]
        opstats.bump("dispatches")
        opstats.bump("uploaded_bytes_delta", payload.nbytes)
        return slots

    def partial_advance(self, delta: float):
        """Solve the CURRENT flow state to convergence, then advance it
        by an EXTERNALLY chosen `delta` (an engine advance won by
        another model or a latency expiry; delta <= this plan's own
        next-completion dt) with the forced-advance kernel.  Returns
        ``(done_slots, n_live)`` — the flow slots that crossed their
        retirement threshold inside the partial advance (emitting them
        in started-set order is the caller's concern).  The clock is
        the engine's on this path, so self.t/self.events are untouched.
        """
        carry = None
        while True:
            carry, stats = _drain_solve_chunk(
                *self._dev, self._cb, self._pen, self._vb, carry,
                eps=self.eps, n_c=self.n_c, n_v=self.n_v,
                chunk=self.solve_chunk, has_bounds=self.has_bounds,
                **self._fat_kw)
            st = np.asarray(stats)
            self.syncs += 1
            if int(st[1]) == 0:
                break
            if int(st[0]) >= _MAX_ROUNDS:
                raise SolveError("drain solve did not converge")
        self.rounds += int(st[0])
        opstats.bump("dispatches")
        opstats.bump("fixpoint_rounds", int(st[0]))
        self._pen, self._rem, out = _drain_forced_advance(
            self._pen, self._rem, self._thresh, carry[0],
            jnp.asarray(delta, self.dtype), _ZERO_BITS)
        out = np.asarray(out)
        self.syncs += 1
        self.advances += 1
        n_live = int(out[0])
        done = np.flatnonzero(out[1:] > 0)
        return done, n_live

    # -- superstep path ----------------------------------------------------

    def _superstep_issue(self, k: Optional[int] = None, pen=None,
                         rem=None, speculative: bool = False,
                         stop_live: int = 0, cb=None, tpos=None,
                         t0=None, round_budget: int = 0,
                         pred=None, ready=None, clk=None
                         ) -> SuperstepToken:
        """Dispatch ONE superstep of up to `k` advances WITHOUT
        touching the committed flow state: the dispatch chains from
        `(pen, rem)` (default: the committed state) and its outputs
        ride the returned token.  Pure host-side except the async
        dispatch itself, so speculative issues are free to discard.

        With a fault tape the dispatch additionally chains the
        constraint bounds and tape cursor (`cb`, `tpos`) and needs the
        f64 base clock `t0` the dispatch starts from (default: the
        committed ``self.t``); speculative issues derive all three
        from their predecessor's token."""
        k_max = self.superstep_k
        k = k_max if k is None else min(int(k), k_max)
        budget = int(round_budget) or self.superstep_rounds
        want_stop = (stop_live if stop_live
                     else (int(self._live0 * self.repack_at)
                           if self._live0 * self.repack_at
                           >= self.repack_min else 0))
        group = _pos_group(self.n_v)
        pen_in = self._pen if pen is None else pen
        rem_in = self._rem if rem is None else rem
        cb_in = self._cb if cb is None else cb
        tpos_in = self._tpos if tpos is None else tpos
        t0_in = np.float64(self.t) if t0 is None else t0
        pred_in = self._coll[0] if pred is None else pred
        ready_in = self._coll[1] if ready is None else ready
        clk_in = self._coll_clk if clk is None else clk
        seq = self.supersteps
        with opstats.span("drain.issue", id=seq):
            (pen_out, rem_out, cb_out, tpos_out, pred_out, ready_out,
             clk_out, packed) = _drain_superstep(
                *self._dev, cb_in, self._vb, pen_in, rem_in,
                self._thresh, self._ids_dev,
                np.int32(k), np.int32(budget), np.int32(want_stop),
                _ZERO_BITS, *self._tape, tpos_in,
                pred_in, ready_in, clk_in, *self._coll_edges, t0_in,
                *self._var_index, *self._succ_index, eps=self.eps,
                n_c=self.n_c, n_v=self.n_v, k_max=k_max, group=group,
                has_bounds=self.has_bounds, has_tape=self.has_tape,
                has_coll=self.has_coll, **self._fat_kw)
        self.supersteps += 1
        opstats.bump("dispatches")
        if speculative:
            self.spec_issued += 1
            opstats.bump("speculations_issued")
        return SuperstepToken(pen_in, rem_in, pen_out, rem_out, packed,
                              k, k_max, want_stop, speculative,
                              cb_in=cb_in, cb_out=cb_out,
                              tpos_out=tpos_out, t0=t0_in,
                              pred_out=pred_out, ready_out=ready_out,
                              clk_out=clk_out, seq=seq)

    def _discard_token(self, tok: SuperstepToken) -> None:
        """Drop an un-collected speculative superstep: processing the
        preceding ring mutated the system, so the dispatch's inputs are
        wrong.  Issue never committed anything, so discarding is O(1) —
        only the device work is wasted (and counted)."""
        self.spec_rolled_back += 1
        opstats.bump("speculations_rolled_back")

    def _superstep_collect(self, tok: SuperstepToken
                           ) -> Tuple[int, List[Tuple[float, List[int]]],
                                      bool]:
        """Commit one issued superstep: make its output arrays the
        committed flow state, fetch its packed ring (the ONLY blocking
        transfer) and replay the events into the host clock/stream.

        Returns ``(n_live, batches, clean)`` — `clean` is the
        speculation-validation verdict: True iff processing this ring
        left the system exactly as an in-flight next superstep assumed
        it (no repack, no stop-trigger decay, flow set still live, the
        dispatch exited _FLAG_OK), so a speculative successor may
        commit; on False the caller must discard in-flight tokens."""
        with opstats.span("drain.collect", id=tok.seq):
            self._pen, self._rem = tok.pen_out, tok.rem_out
            if self.has_tape:
                self._cb = tok.cb_out
                self._tpos = tok.tpos_out
            if self.has_coll:
                self._coll = (tok.pred_out, tok.ready_out)
                self._coll_clk = tok.clk_out
            p = opstats.timed_fetch(tok.packed)
            self.syncs += 1
            rounds, adv = int(p[0]), int(p[1])
            t_sum = float(p[3])
            if np.isnan(t_sum):
                # a poisoned scenario (e.g. NaN link capacity) makes the
                # whole advance NaN — fail with a cause instead of
                # committing a garbage clock/ring (the solo mirror of the
                # fleet's nan_solve lane quarantine)
                raise SolveError(
                    "drain solve produced a non-finite clock advance "
                    "(NaN)")
            n_live, flag = int(p[4]), int(p[5])
            live_elems = int(p[6])

            self.rounds += rounds
            opstats.bump("fixpoint_rounds", rounds)
            opstats.bump("fixpoint_worked_elem_rounds",
                         _live_elem_rounds(p[7:9]))
            self.advances += adv
            if self.has_coll:
                # the tape's own counts ride the tail of the same fetch
                opstats.bump("collective_live_flow_advances",
                             _live_elem_rounds(p[-6:-4]))
                opstats.bump("collective_tape_fires", int(p[-4]))
                opstats.bump("fixpoint_var_entries", int(p[-3]))
                opstats.bump("collective_src_walks", int(p[-2]))
                opstats.bump("collective_ring_narrow", int(p[-1]))
            with opstats.span("drain.demux"):
                batches, fired = self._demux(p, adv, tok.k_max, t_sum)
                if self.has_coll and self._coll_loopback is not None:
                    opstats.bump("collective_loopback_completions",
                                 self._loopback_completions(p, adv,
                                                            tok.k_max))

            if flag == _FLAG_STALLED:
                raise SolveError(
                    f"drain stalled: no flow holds bandwidth "
                    f"({n_live} live)")
            if flag == _FLAG_BUDGET and adv == 0 and rounds >= _MAX_ROUNDS:
                raise SolveError("drain solve did not converge")
            repacked = False
            decayed = False
            if self._should_repack(n_live):
                repacked = self._repack_device(n_live, live_elems)
            if not repacked and tok.want_stop and n_live <= tok.want_stop:
                # the stop-for-repack threshold fired but no repack was
                # possible (small live set / dense elements): decay the
                # trigger so the next superstep doesn't exit immediately
                self._live0 = max(n_live, 1)
                decayed = True
            self._last_flag = flag
            if tok.speculative:
                self.spec_committed += 1
                opstats.bump("speculations_committed")
            # a tape fire is a clean-collect boundary for speculation: the
            # spec issue chained from the fired bounds (values were right),
            # but replaying from the committed state keeps the oracle
            # trivially aligned with the unpipelined driver
            clean = (flag == _FLAG_OK and n_live > 0
                     and not repacked and not decayed and not fired)
            if self.on_batches is not None and batches:
                self.on_batches(batches)
        return n_live, batches, clean

    def _loopback_completions(self, p: np.ndarray, adv: int,
                              k_max: int) -> int:
        """The completions of one fetched ring whose flows ride
        between two ranks of one host (``coll_loopback``)."""
        o = _STATS_HEAD + 2 * k_max
        n_ev = int(p[o - k_max + adv - 1]) if adv else 0
        ring_n = (self.n_v + (k_max if self.has_tape else 0)
                  + self.n_v)
        ids = p[o + ring_n:o + ring_n + n_ev].astype(np.int64)
        return int(np.count_nonzero(self._coll_loopback[ids[ids >= 0]]))

    def _demux(self, p: np.ndarray, adv: int, k_max: int, t_sum: float
               ) -> Tuple[List[Tuple[float, List[int]]], int]:
        """Replay one fetched ring into ``events`` (and the fault and
        collective streams) and the f64 master clock; returns the
        per-advance ``(dt, [flow ids])`` batches and how many fault
        entries fired."""
        o = _STATS_HEAD
        adv_dt = p[o:o + adv].tolist()
        ends = p[o + k_max:o + k_max + adv].astype(np.int64).tolist()
        o += 2 * k_max
        ring_n = (self.n_v + (k_max if self.has_tape else 0)
                  + (self.n_v if self.has_coll else 0))
        n_ev = ends[-1] if adv else 0
        ring_id = p[o + ring_n:o + ring_n + n_ev].astype(np.int64)
        # collective dates are ABSOLUTE (the Kahan clock pair is carried
        # across dispatches, replayed below), so the base folds to zero;
        # the others are the ring's offsets in the solve dtype from the
        # dispatch's base clock, added in f64
        t_base = 0.0 if self.has_coll else self.t
        t_ring = (None if self.has_coll
                  else t_base + p[o:o + n_ev].astype(np.float64))

        def runs(mask, vals):
            """Each advance's ``(dates, ids)`` under ``mask``, in ring
            order; dates None where they are the advance's own."""
            at = np.flatnonzero(mask)
            cut = [0] + np.searchsorted(at, ends).tolist()
            ids = vals[at].tolist()
            dates = None if t_ring is None else t_ring[at].tolist()
            return [(None if dates is None else dates[a:b], ids[a:b])
                    for a, b in zip(cut, cut[1:])]

        # negative ids are tagged entries — fault fires (idx < n_c, into
        # the fault stream) or collective activations (idx >= n_c, flow
        # idx - n_c fired into the activation stream) — neither joins
        # the completion batches
        tagged = ring_id < 0
        tags = -1 - ring_id
        act = tagged & (tags >= self.n_c)
        fault = tagged & ~act
        done = runs(~tagged, ring_id)
        streams = [(self.events, done)]
        if act.any():
            streams.append((self.collective_events,
                            runs(act, tags - self.n_c)))
        fired = int(np.count_nonzero(fault))
        if fired:
            streams.append((self.fault_events, runs(fault, tags)))
        batches: List[Tuple[float, List[int]]] = []
        for i, dt in enumerate(adv_dt):
            if self.has_coll:
                # the ring's dates are in the solve dtype; the advance's
                # own is the device's float64 pair, one step of the same
                # recurrence on its exact dt (the step HostMaestro takes)
                t_c, comp = self._coll_clk_host
                y = dt - comp
                t_adv = t_c + y
                self._coll_clk_host = (t_adv, (t_adv - t_c) - y)
            for out, run in streams:
                dates, ids = run[i]
                out.extend(zip(repeat(t_adv) if dates is None else dates,
                               ids))
            batches.append((dt, done[i][1]))
        self._tpos_host += fired
        self._last_fired = fired > 0
        if fired:
            opstats.bump("fault_tape_events", fired)
        # f64 master clock: one Kahan-compensated dtype total per
        # superstep, accumulated on host in f64 (a collective's is the
        # absolute clock of the pair replayed above)
        self.t = (self._coll_clk_host[0] if self.has_coll
                  else t_base + t_sum)
        return batches, fired

    def superstep_batch(self, k: Optional[int] = None,
                        fetch: bool = True, stop_live: int = 0,
                        round_budget: int = 0):
        """Dispatch ONE superstep of up to `k` advances and (optionally)
        fetch its packed result — a single transfer.

        Returns (n_live, batches) where batches is a list of
        (dt, [original flow ids]) per executed advance; with
        fetch=False nothing is transferred (replay) and (None, None) is
        returned.  Events/clock/counters are committed on fetch."""
        if self.has_coll and not fetch:
            raise ValueError("superstep_batch(fetch=False): a collective "
                             "tape's host clock is replayed from the "
                             "fetched dt table")
        tok = self._superstep_issue(k, stop_live=stop_live,
                                    round_budget=round_budget)
        if not fetch:
            self._pen, self._rem = tok.pen_out, tok.rem_out
            if self.has_tape:
                self._cb = tok.cb_out
                self._tpos = tok.tpos_out
            return None, None
        n_live, batches, _clean = self._superstep_collect(tok)
        return n_live, batches

    def _run_pipelined(self, max_advances: int) -> None:
        """The speculative superstep driver: keep up to
        ``self.pipeline`` supersteps in flight beyond the one being
        collected, each chained from its predecessor's (immutable,
        double-buffered) output arrays.  Collect order is strictly
        FIFO, so event order, timestamps and clocks are the committed
        prefix of exactly the computation the unpipelined driver runs;
        any unclean collect (repack/decay/rescue/stall/done) discards
        the speculative tail and re-issues from the committed state."""
        budget = max_advances
        inflight: deque = deque()
        issued_k = 0            # advances the in-flight tokens may eat
        n = self.n_v
        try:
            while (n or self._coll_open()) and budget > 0:
                # fill the pipeline: the head issue mirrors the
                # unpipelined k=min(K, remaining); speculative issues
                # only when a FULL K is guaranteed to still be within
                # the advance budget whatever the in-flight tokens
                # consume — otherwise their k would depend on counts
                # the host has not fetched yet
                while (not inflight
                       or (len(inflight) <= self.pipeline
                           and budget - issued_k >= self.superstep_k)):
                    spec = bool(inflight)
                    k = (self.superstep_k if spec
                         else min(self.superstep_k, budget))
                    if inflight:
                        prev = inflight[-1]
                        pen, rem = prev.pen_out, prev.rem_out
                        if self.has_tape:
                            # chain bounds/cursor and derive the f64
                            # base clock DEVICE-side: the same IEEE
                            # add the host collect will perform, so a
                            # committed chain is bit-identical to a
                            # fresh issue from the committed clock
                            cb, tpos = prev.cb_out, prev.tpos_out
                            t0 = prev.t0 + prev.packed[3].astype(
                                jnp.float64)
                        else:
                            cb = tpos = t0 = None
                        if self.has_coll:
                            # the DAG carry (pred counts, ready dates,
                            # Kahan clock pair) chains device-side, so
                            # a committed speculative chain replays the
                            # exact unpipelined recurrence
                            pred, ready = prev.pred_out, prev.ready_out
                            clk = prev.clk_out
                        else:
                            pred = ready = clk = None
                    else:
                        pen = rem = cb = tpos = t0 = None
                        pred = ready = clk = None
                    inflight.append(self._superstep_issue(
                        k, pen=pen, rem=rem, speculative=spec,
                        cb=cb, tpos=tpos, t0=t0,
                        pred=pred, ready=ready, clk=clk))
                    issued_k += k
                tok = inflight.popleft()
                issued_k -= tok.k
                before = self.advances
                n, _batches, clean = self._superstep_collect(tok)
                budget -= self.advances - before
                if not clean:
                    # speculation mispredicted: processing this ring
                    # mutated the system (repack/decay), hit a tape
                    # fire (clean-collect boundary) or the batch needs
                    # a host-side continuation (rescue/stall) —
                    # discard the in-flight tail and restart from the
                    # committed state
                    if self.has_tape and self._last_fired and inflight:
                        opstats.bump("fault_replays", len(inflight))
                    if self.has_coll and inflight:
                        # schedule exhaustion / stop boundary while a
                        # collective tape is armed: the discarded tail
                        # is replayed from the committed DAG carry
                        opstats.bump("collective_replays",
                                     len(inflight))
                    while inflight:
                        self._discard_token(inflight.popleft())
                    issued_k = 0
                    if (n or self._coll_open()) \
                            and self.advances == before:
                        # the round budget expired inside the first
                        # solve: finish ONE advance with the full
                        # round budget
                        after = self.advances
                        n = self._rescue_one()
                        budget -= 1
                        if self.advances == after \
                                and self._coll_open():
                            raise SolveError(
                                "collective schedule deadlocked: "
                                f"{len(self.events)}/"
                                f"{self._coll_total} flows completed "
                                "and nothing is pending")
        finally:
            while inflight:
                self._discard_token(inflight.popleft())

    def _rescue_one(self) -> int:
        """Finish ONE advance after the superstep round budget expired
        inside its first solve: re-dispatch k=1 with the FULL round
        budget — its collect raises "did not converge" if even that
        fails."""
        n, _ = self.superstep_batch(k=1, round_budget=_MAX_ROUNDS)
        return n

    def _coll_open(self) -> bool:
        """True while an armed collective schedule still owes
        completions: a superstep may exit with zero LIVE flows while
        dormant successors wait on pending activation dates, so the
        drivers must keep dispatching until every DAG flow completed."""
        return self.has_coll and len(self.events) < self._coll_total

    def advance(self) -> int:
        """One solve + time advance, as one K = 1 dispatch and one
        fetch; returns the remaining live count."""
        before = self.advances
        n, _ = self.superstep_batch(k=1)
        if (n or self._coll_open()) and self.advances == before:
            n = self._rescue_one()
        return n

    def run(self, max_advances: int = 10_000_000) -> None:
        if self.pipeline:
            self._run_pipelined(max_advances)
            return
        n = self.n_v
        while (n or self._coll_open()) and max_advances > 0:
            before = self.advances
            k = min(self.superstep_k, max_advances)
            n, _ = self.superstep_batch(k=k)
            max_advances -= self.advances - before
            if (n or self._coll_open()) and self.advances == before:
                # the round budget expired inside the first solve:
                # finish ONE advance, then resume
                n = self._rescue_one()
                max_advances -= 1
                if self.advances == before and self._coll_open():
                    # no live flow, no pending activation, but the
                    # schedule still owes completions: a cyclic or
                    # truncated DAG would spin here forever
                    raise SolveError(
                        "collective schedule deadlocked: "
                        f"{len(self.events)}/{self._coll_total} "
                        "flows completed and nothing is pending")
