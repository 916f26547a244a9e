"""Vectorized max-min fairness solver on JAX (TPU-native hot path).

This is the north-star component: SimGrid's saturate-bottleneck fixpoint
(reference semantics: /root/reference/src/kernel/lmm/maxmin.cpp:502-693)
re-designed for the TPU/XLA execution model instead of intrusive linked
lists:

* the constraint/variable graph is flattened into COO-style element arrays
  ``(e_var, e_cnst, e_w)`` padded to bucketed static shapes (XLA wants
  static shapes; buckets bound recompiles);
* one *saturation round* = a handful of segment-sum / segment-max scatters
  plus two min-reductions over dense vectors — bandwidth-bound vector work
  XLA maps directly onto the TPU's VPU, with the whole fixpoint inside one
  ``lax.while_loop`` so there is a single device dispatch per solve;
* the epsilon semantics (``double_update`` clamping, saturation tests
  against ``bound*eps``) are applied batched, and ties in the min-reduce
  are detected by exact equality like the reference, so the returned rate
  vector matches the exact list solver bit-for-bit in f64 on identical
  round structures.

The same function runs unchanged on CPU (f64, used for validation and as
the oracle cross-check) and on TPU (f32: the chip's f64 is an emulated
f32 pair and is refused, see ops.device).  For multi-simulation batching it is ``vmap``-able, and the
segment ops shard over a device mesh for very large systems (see
simgrid_tpu.parallel.sharded_solve).
"""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.config import config
from . import opstats
from .device import default_platform, solve_dtype
from .lmm_host import SharingPolicy, System, Constraint, Variable

_MAX_ROUNDS = 100_000


class SolveError(RuntimeError):
    """The solver's (or a drain's) own numerical failure: the round cap,
    a stalled fixpoint, non-finite rates or clocks, a drain in which no
    flow holds bandwidth, a deadlocked schedule.  The only exception the
    graceful-degradation handlers (solve_jax, the engine fast path,
    ScenarioPlan.solo) catch: a device or runtime fault
    (``jax.errors.JaxRuntimeError`` — a compile refusal, an
    out-of-memory, a lost chip) is also a RuntimeError and must
    propagate, never be answered by the host solver."""


class LmmArrays(NamedTuple):
    """Flattened (padded) view of an LMM system."""
    e_var: np.ndarray    # [E] int32 — variable slot per element
    e_cnst: np.ndarray   # [E] int32 — constraint slot per element
    e_w: np.ndarray      # [E] float — consumption weight (0 padding)
    c_bound: np.ndarray  # [C] float — constraint capacity (0 padding)
    c_fatpipe: np.ndarray  # [C] bool — max-sharing (FATPIPE) constraint
    v_penalty: np.ndarray  # [V] float — sharing penalty (0 = disabled/pad)
    v_bound: np.ndarray    # [V] float — variable rate bound (-1 = none)
    n_elem: int
    n_cnst: int
    n_var: int


def _bucket(n: int, floor: int = 16, grow: bool = False) -> int:
    """Round up to a bucketed static size to bound XLA recompiles.
    ELL row widths pass floor=4: every padded slot is gathered in EVERY
    round, so a deg-4 graph packed at width 16 would gather 4x the
    elements on each vc-side gather.

    Default policy is power-of-2; ``lmm/pad:tight`` switches to exact
    row widths and multiple-of-4096 array sizes — per-round device work
    is proportional to padded volume, so one-shot solves of large
    systems should not pay the up-to-2x pow2 padding.  Hot simulation
    paths keep pow2: each fresh shape is a multi-second XLA compile.
    ``grow=True`` callers (the incremental
    ArrayView's reallocation policy) always get pow2: ceil-to-4096
    growth would copy the arrays every 4096 insertions (O(n^2) total)
    and compile a fresh shape each time."""
    pad = config["lmm/pad"]
    if pad not in ("pow2", "tight"):
        raise ValueError(f"Unknown lmm/pad {pad!r} "
                         "(expected pow2 or tight)")
    if pad == "tight" and not grow:
        if floor <= 8:              # ELL row width: exact
            return max(n, 1)
        if n > 4096:
            return -(-n // 4096) * 4096
    if n <= floor:
        return floor
    return 1 << (n - 1).bit_length()


class LmmEllArrays(NamedTuple):
    """ELL (padded-row) layout of an LMM system, the accelerator-native
    form: every constraint owns a fixed-width row of (variable, weight)
    slots and every variable a fixed-width row of constraint slots, so
    each solver round is gathers + dense 2D row-reductions — no scatter
    at all (unsorted scatters are the op class a TPU runs worst).
    Skewed systems (one backbone constraint touching everything) would
    blow the row width up, so conversion falls back to COO beyond a
    width cap."""
    cv_var: np.ndarray    # [C, Wc] int32 — variable slot per element
    cv_w: np.ndarray      # [C, Wc] float — weight (0 padding)
    cv_valid: np.ndarray  # [C, Wc] bool
    vc_cnst: np.ndarray   # [V, Wv] int32 — constraint slot per element
    vc_valid: np.ndarray  # [V, Wv] bool
    c_bound: np.ndarray
    c_fatpipe: np.ndarray
    v_penalty: np.ndarray
    v_bound: np.ndarray
    n_cnst: int
    n_var: int
    #: [V, Wv] float — element weight in VARIABLE-row layout.  The
    #: var-side rows are near-unpadded (width = max var degree, usually
    #: the flow's route length), so the vc-centric round body gathers/
    #: scatters ~2-4x fewer elements than the constraint-side tables.
    vc_w: Optional[np.ndarray] = None


#: Conversion to ELL is refused when a row would exceed this width
#: (memory blow-up on skewed graphs) — COO handles those.
_ELL_MAX_WIDTH = 512
#: ...or when padding would inflate total slots by more than this
#: factor over the element count.
_ELL_MAX_FILL = 8.0


def ell_from_arrays(arrays: LmmArrays) -> Optional[LmmEllArrays]:
    """Host-side repack of the COO arrays into ELL rows (numpy)."""
    E, C, V = arrays.n_elem, len(arrays.c_bound), len(arrays.v_penalty)
    e_var = arrays.e_var[:E]
    e_cnst = arrays.e_cnst[:E]
    e_w = arrays.e_w[:E]

    c_deg = np.bincount(e_cnst, minlength=C)
    v_deg = np.bincount(e_var, minlength=V)
    wc = int(c_deg.max()) if E else 1
    wv = int(v_deg.max()) if E else 1
    if wc > _ELL_MAX_WIDTH or wv > _ELL_MAX_WIDTH:
        return None
    Wc, Wv = _bucket(max(wc, 1), floor=4), _bucket(max(wv, 1), floor=4)
    if E and (C * Wc + V * Wv) > _ELL_MAX_FILL * 2 * E:
        return None

    def row_slots(keys, n_rows):
        """Vectorized within-group slot index per element (stable)."""
        order = np.argsort(keys, kind="stable")
        sorted_keys = keys[order]
        group_start = np.searchsorted(sorted_keys, np.arange(n_rows))
        slots = np.arange(E, dtype=np.int64) - group_start[sorted_keys]
        return order, sorted_keys, slots

    cv_var = np.zeros((C, Wc), np.int32)
    cv_w = np.zeros((C, Wc), arrays.e_w.dtype)
    cv_valid = np.zeros((C, Wc), bool)
    order, rows, slots = row_slots(e_cnst, C)
    cv_var[rows, slots] = e_var[order]
    cv_w[rows, slots] = e_w[order]
    cv_valid[rows, slots] = e_w[order] > 0

    vc_cnst = np.zeros((V, Wv), np.int32)
    vc_valid = np.zeros((V, Wv), bool)
    vc_w = np.zeros((V, Wv), arrays.e_w.dtype)
    order, rows, slots = row_slots(e_var, V)
    vc_cnst[rows, slots] = e_cnst[order]
    vc_valid[rows, slots] = e_w[order] > 0
    vc_w[rows, slots] = e_w[order]

    return LmmEllArrays(cv_var, cv_w, cv_valid, vc_cnst, vc_valid,
                        arrays.c_bound, arrays.c_fatpipe,
                        arrays.v_penalty, arrays.v_bound,
                        arrays.n_cnst, arrays.n_var, vc_w)


def _run_rounds(cond, body, carry, max_rounds: int, unroll: bool):
    """Dispatch the round loop either as lax.while_loop or fully
    unrolled straight-line XLA.  Unrolling exists for backends that
    lower gathers inside while_loop/scan to serialized dynamic-slice
    loops.  Each unrolled iteration is masked to
    a no-op once `cond` goes false, so the result is bit-identical to
    the while_loop truncated at max_rounds."""
    if not unroll:
        return lax.while_loop(cond, body, carry)
    if max_rounds > 4096:
        raise ValueError(
            f"unroll=True requires a bounded max_rounds (got {max_rounds}); "
            "compile time scales with the unroll factor — dispatch in "
            "chunks (see solve_arrays) instead")
    state = carry
    for _ in range(max_rounds):
        alive = cond(state)
        new_state = body(state)
        state = tuple(jnp.where(alive, n, o)
                      for n, o in zip(new_state, state))
    return state


def fixpoint_ell(ell: LmmEllArrays, eps, carry=None,
                 parallel_rounds: bool = False,
                 max_rounds: Optional[int] = None,
                 return_carry: bool = False,
                 unroll: bool = False,
                 has_bounds: bool = True,
                 has_fatpipe: bool = True):
    """The saturate-bottleneck fixpoint on the ELL layout: identical
    round structure and epsilon semantics to `fixpoint` (see there for
    the algorithm), with every segment reduction expressed as a masked
    dense 2D row-reduction."""
    cv_var, cv_w, cv_valid = ell.cv_var, ell.cv_w, ell.cv_valid
    vc_cnst, vc_valid = ell.vc_cnst, ell.vc_valid
    vc_w = ell.vc_w
    c_bound, c_fatpipe = ell.c_bound, ell.c_fatpipe
    v_penalty, v_bound = ell.v_penalty, ell.v_bound
    n_c = c_bound.shape[0]

    dtype = cv_w.dtype
    inf = jnp.array(jnp.inf, dtype)

    v_enabled = v_penalty > 0
    cv_evalid = cv_valid & jnp.take(v_enabled, cv_var)
    safe_pen = jnp.where(v_enabled, v_penalty, 1.0)
    cv_upen = jnp.where(cv_evalid, cv_w / jnp.take(safe_pen, cv_var), 0.0)

    usage_sum = cv_upen.sum(axis=1)
    usage_max = cv_upen.max(axis=1, initial=0.0)
    usage0 = jnp.where(c_fatpipe, usage_max, usage_sum)

    remaining0 = c_bound
    light0 = (remaining0 > c_bound * eps) & (usage0 > 0)

    v_value0 = jnp.where(jnp.isfinite(v_penalty), v_penalty, 0.0) * 0.0
    v_fixed0 = v_penalty < 0

    if carry is None:
        cv_live0 = cv_evalid & ~jnp.take(v_fixed0, cv_var)
        carry = (v_value0, v_fixed0, remaining0, usage0, light0,
                 jnp.array(0, jnp.int32), cv_live0)
    start_it = carry[5]
    if max_rounds is None:
        max_rounds = _MAX_ROUNDS

    # Variable-row element validity: a var row is enabled as a whole.
    vc_evalid = vc_valid & v_enabled[:, None]

    def cond(state):
        light = state[4]
        it = state[5]
        return (jnp.any(light) & (it < _MAX_ROUNDS)
                & (it - start_it < max_rounds))

    def apply_fixes(state, fix_now, new_value):
        v_value, v_fixed, remaining, usage, light, it = state[:6]
        cv_live_in = state[6]
        v_value = jnp.where(fix_now, new_value, v_value)
        v_fixed = v_fixed | fix_now

        # one stacked row-gather instead of three element gathers:
        # both channels [v_value, v_fixed] ride one index per slot.
        # fix_now needs
        # no channel: newly-fixed = (was live at round start) & (fixed
        # now), and the round-start liveness rides the carry.
        stacked = jnp.stack([v_value, v_fixed.astype(dtype)], axis=1)
        g = jnp.take(stacked, cv_var, axis=0)
        g_fixed = g[..., 1] > 0
        cv_fix = cv_live_in & g_fixed
        d_rem = jnp.where(cv_fix, cv_w * g[..., 0], 0.0).sum(axis=1)
        d_use = jnp.where(cv_fix, cv_upen, 0.0).sum(axis=1)

        new_remaining = remaining - d_rem
        new_remaining = jnp.where(new_remaining < c_bound * eps, 0.0,
                                  new_remaining)
        new_usage_sum = usage - d_use
        new_usage_sum = jnp.where(new_usage_sum < eps, 0.0, new_usage_sum)

        cv_live2 = cv_evalid & ~g_fixed
        touched = cv_fix.any(axis=1)
        if has_fatpipe:
            new_usage_max = jnp.where(cv_live2, cv_upen,
                                      0.0).max(axis=1, initial=0.0)
            new_usage = jnp.where(c_fatpipe, new_usage_max, new_usage_sum)
            usage = jnp.where(touched, new_usage, usage)
            remaining = jnp.where(touched & ~c_fatpipe, new_remaining,
                                  remaining)
        else:
            # static specialization: no fatpipe constraint in the
            # system, so the max-usage recompute drops out
            usage = jnp.where(touched, new_usage_sum, usage)
            remaining = jnp.where(touched, new_remaining, remaining)

        drop = touched & (~(usage > eps) | ~(remaining > c_bound * eps))
        light = light & ~drop
        has_live = cv_live2.any(axis=1)
        light = light & has_live
        # the fresh liveness mask rides the carry so the next round
        # does not re-gather v_fixed over the cv table
        return v_value, v_fixed, remaining, usage, light, it + 1, cv_live2

    def body_global(state):
        v_value, v_fixed, remaining, usage, light, it = state[:6]
        rou = jnp.where(light, remaining / jnp.where(light, usage, 1.0),
                        inf)
        min_usage = jnp.min(rou)
        saturated_c = light & (rou == min_usage)

        vc_live = vc_evalid & ~v_fixed[:, None]
        v_sat = (vc_live & jnp.take(saturated_c, vc_cnst)).any(axis=1)

        bp = v_bound * v_penalty
        has_low_bound = v_sat & (v_bound > 0) & (bp < min_usage)
        min_bound = jnp.min(jnp.where(has_low_bound, bp, inf))
        use_bounds = jnp.isfinite(min_bound)

        fix_now = jnp.where(use_bounds,
                            v_sat & (jnp.abs(bp - min_bound) < eps),
                            v_sat)
        new_value = jnp.where(use_bounds, v_bound,
                              min_usage / jnp.where(v_enabled, v_penalty,
                                                    1.0))
        return apply_fixes(state, fix_now, new_value)

    def body_local(state):
        v_value, v_fixed, remaining, usage, light, it = state[:6]
        rou = jnp.where(light, remaining / jnp.where(light, usage, 1.0),
                        inf)
        vc_live = vc_evalid & ~v_fixed[:, None]
        cv_live = state[6]        # maintained by apply_fixes

        # Two-hop neighborhood min of rou: constraint -> vars -> cnst.
        # rou_vc is gathered ONCE and reused for nmin_v and level2_v.
        rou_vc = jnp.take(rou, vc_cnst)
        nmin_v = jnp.where(vc_live, rou_vc,
                           inf).min(axis=1, initial=jnp.inf)
        nmin_c = jnp.where(cv_live, jnp.take(nmin_v, cv_var),
                           inf).min(axis=1, initial=jnp.inf)
        processable = light & (rou <= nmin_c)

        vc_proc = vc_live & jnp.take(processable, vc_cnst)
        v_sat = vc_proc.any(axis=1)

        level_v = nmin_v
        bp = v_bound * v_penalty
        low_v = v_sat & (v_bound > 0) & (bp < level_v)
        cv_bp = jnp.where(cv_live & jnp.take(low_v, cv_var),
                          jnp.take(bp, cv_var), inf)
        mb_c = cv_bp.min(axis=1, initial=jnp.inf)
        mb_c = jnp.where(processable, mb_c, inf)
        mb_v = jnp.where(vc_proc, jnp.take(mb_c, vc_cnst),
                         inf).min(axis=1, initial=jnp.inf)
        cv_proc = cv_live & processable[:, None]
        blocked_c = (cv_proc
                     & jnp.isfinite(jnp.take(mb_v, cv_var))).any(axis=1)

        ok_c = processable & ~blocked_c
        level2_v = jnp.where(vc_live & jnp.take(ok_c, vc_cnst),
                             rou_vc,
                             inf).min(axis=1, initial=jnp.inf)

        fix_bound = low_v & (jnp.abs(bp - mb_v) < eps)
        fix_level = jnp.isfinite(level2_v) & ~v_fixed & ~fix_bound
        fix_now = fix_bound | fix_level
        new_value = jnp.where(fix_bound, v_bound,
                              level2_v / jnp.where(v_enabled, v_penalty,
                                                   1.0))
        return apply_fixes(state, fix_now, new_value)

    def body_local_vc(state):
        """The bound-free local round in the VARIABLE-row layout —
        shared with the compaction chain via _vc_round_body (see its
        docstring for the op-cost rationale); this wrapper threads the
        cv-side carry member the 6-tuple body does not use."""
        out6 = _vc_body6(state[:6])
        return (*out6, state[6])

    _vc_body6 = (_vc_round_body(vc_cnst, vc_w, vc_valid, v_penalty,
                                c_bound, c_fatpipe, eps, has_fatpipe)
                 if vc_w is not None else None)

    if parallel_rounds and not has_bounds and vc_w is not None:
        body = body_local_vc
    elif parallel_rounds:
        body = body_local
    else:
        body = body_global
    out = _run_rounds(cond, body, carry, max_rounds, unroll)
    v_value, v_fixed, remaining, usage, light, rounds = out[:6]
    if return_carry:
        return v_value, remaining, usage, rounds, out
    return v_value, remaining, usage, rounds


def fixpoint(e_var, e_cnst, e_w, c_bound, c_fatpipe, v_penalty, v_bound,
             eps, n_c: int, n_v: int, axis: Optional[str] = None,
             parallel_rounds: bool = False, carry=None,
             max_rounds: Optional[int] = None, return_carry: bool = False,
             unroll: bool = False, has_bounds: bool = True,
             has_fatpipe: bool = True, var_index=None):
    """The saturate-bottleneck fixpoint over padded COO arrays.

    The single implementation behind every solve path: single-device
    (``axis=None`` — the reductions are plain segment ops), vmapped
    batches, and mesh-sharded element lists (``axis`` names the shard_map
    mesh axis; cross-shard combines are one psum/pmax pair per round in
    global mode and 4-10 psum/pmax/pmin collectives per round in local
    mode (one per segment reduction: four without bounds, one more with
    FATPIPE; with bounds one for the bound block's predicate and four
    more in a round whose block runs), which still wins because local
    mode needs far fewer rounds — see simgrid_tpu.parallel.sharded).

    ``parallel_rounds=False`` replays the reference's sequential order
    exactly: one global bottleneck level per round.  ``True`` fixes every
    *local-minimum* constraint per round (a constraint whose rou is <= the
    rou of every constraint it shares a live variable with): since a
    constraint's remaining/usage ratio only increases as other variables
    get fixed, a local minimum's level is already final, so whole
    independent regions of the constraint graph saturate concurrently and
    the device round count drops from O(#distinct levels) to O(level-chain
    depth of the graph).

    ``carry``/``max_rounds``/``return_carry`` support *chunked* execution:
    run at most ``max_rounds`` additional rounds from ``carry`` (or the
    fresh initial state) and hand the full loop state back, so the host
    can bound device-kernel run time per dispatch and check convergence
    between chunks (a non-converging f32 solve must come back to the
    host and raise, not spin inside one dispatch).  With
    ``return_carry`` the result is ``(values, remaining, usage, rounds,
    carry, bound_rounds, live_elem_rounds, worked_elem_rounds,
    partitions, var_entry)``: the 6-tuple carry to hand back in, the
    number of this call's rounds that took the bound-first rule (the
    local round's
    bound block, the global round's min-bound branch), the live
    elements its rounds entered with and the elements they indexed
    (the rung's size), each summed over them (:func:`_live_elem_rounds`
    reads either pair), the partitions the ladder ran, and 1 when the
    call entered from the variable side (below).

    THE LADDER.  An element-wide gather or scatter costs by the index,
    live or dead (PERF.md §5), so the round loop is a ladder of round
    loops over element lists of falling static size
    (:func:`_ladder_sizes`): rung ``s`` runs today's round over its own
    list while more elements are live than rung ``s + 1`` holds, then
    the list is put live-first, STABLY, and its head kept
    (:func:`_livefirst_head`).  Survivors keep their order, so every
    segment reduction sees its live terms in the order of the single
    loop, and a dead element only ever contributed an identity (0.0 to
    the sums and maxes, inf to the mins): the results are the single
    loop's bit for bit.  The n_c- and n_v-wide state is never
    renumbered, so nothing is merged at the end.  A stage also ends on
    convergence or on ``max_rounds``; every later stage's condition is
    then false as well, so NO ROUND EVER RUNS ON A LIST THAT WAS CUT
    WHILE MORE WERE LIVE THAN IT HOLDS (such a list is only a slice
    nobody reads), and the caller's carry is the 6-tuple from which the
    next call rebuilds liveness at full width, for one gather and one
    scatter over the whole list (:func:`_entry`, what a cold call pays
    too), and walks down again.  A
    list of up to ``2 * _LADDER_MIN_ELEMS`` has one rung: the program it
    lowered to before the ladder.  ``unroll=True`` keeps the single
    loop.

    THE VARIABLE SIDE.  A call that enters with few live variables (a
    collective tape's advance: 0.15 % of the flows) would pay entry and
    one descent at full width to find them.  ``var_index`` is
    :func:`var_index`'s ``(v_ptr, ve_idx)`` of this element list; with
    it, and more than one rung, the live variables' element counts are
    added up first (n_v wide), and when they fit the BOTTOM rung one
    ``lax.cond`` builds that rung's lists from the live variables' own
    elements (:func:`_rung_from_vars`) and enters there: the live
    elements in the list's order, which is what the stable partition
    leaves, so every result and counter is the full-width entry's bit
    for bit.  Otherwise the other branch is the entry and the descent
    above, unchanged.  ``None`` is the program without the index, to
    its lowered text.
    """
    if var_index is not None and axis:
        raise ValueError("fixpoint: a variable index is of the whole "
                         "element list, not of a shard's")
    dtype = e_w.dtype
    inf = jnp.array(jnp.inf, dtype)
    big = jnp.array(jnp.finfo(dtype).max, dtype)
    # apply_fixes counts a round's fixed elements per constraint in
    # `dtype`: exact while no constraint can hold more elements than
    # the dtype has consecutive integers (2^24 in f32).
    n_shards = lax.psum(1, axis) if axis else 1
    n_elems = e_var.size * n_shards
    if n_elems >= 2 ** (jnp.finfo(dtype).nmant + 1):
        raise ValueError(
            f"{n_elems} elements: more than {dtype.name} counts exactly "
            "per constraint")

    def allsum(x):
        return lax.psum(x, axis) if axis else x

    def allmax(x):
        return lax.pmax(x, axis) if axis else x

    def allmin(x):
        return lax.pmin(x, axis) if axis else x

    sizes = [e_var.size] if unroll else _ladder_sizes(e_var.shape)
    var_side = var_index is not None and len(sizes) > 1

    def enter(e_var, e_cnst, e_w):
        """Entry over element lists of any width: ``(elems, carry,
        e_live, n_live_c)``, of which ``first_state`` makes the loop
        state (apart, so that a call without an index emits its ops in
        the order it always did)."""
        with jax.named_scope("sg.lmm.init"):
            # Element liveness and the live count per constraint ride
            # the loop state, so no round gathers v_fixed again.  Both
            # are rebuilt HERE (from the carry's v_fixed when a chunked
            # caller hands a mid-solve carry back) and dropped at exit:
            # the public carry stays the 6-tuple.
            _, e_upen, e_live0, n_live_c0, usage0 = _entry(
                e_var, e_cnst, e_w, c_fatpipe, v_penalty,
                None if carry is None else carry[1], n_c, has_fatpipe,
                allsum, allmax)
            if carry is not None:
                return (e_var, e_cnst, e_w, e_upen), carry, e_live0, \
                    n_live_c0
            remaining0 = c_bound
            # Initial light set: usage strictly positive (exact,
            # maxmin.cpp:545) and remaining above the relative epsilon
            # (maxmin.cpp:524).
            light0 = (remaining0 > c_bound * eps) & (usage0 > 0)

            # Derive the initial carry from the inputs (not fresh
            # constants) so its varying-manual-axes match the loop
            # output under shard_map+vmap.  Parked variables carry
            # penalty=inf and inf*0.0 is NaN, so sanitize.
            v_value0 = jnp.where(jnp.isfinite(v_penalty), v_penalty,
                                 0.0) * 0.0
            return ((e_var, e_cnst, e_w, e_upen),
                    (v_value0, v_penalty < 0, remaining0, usage0, light0,
                     jnp.array(0, jnp.int32)), e_live0, n_live_c0)

    if not var_side:
        elems, *entered = enter(e_var, e_cnst, e_w)
    v_enabled = v_penalty > 0
    start_it = jnp.array(0, jnp.int32) if carry is None else carry[5]
    if max_rounds is None:
        max_rounds = _MAX_ROUNDS

    def live_elems(e_live, n_live_c):
        """What the ladder's rung test reads: the live elements of the
        list (of the fullest shard's under ``axis``, so that all shards
        step down together)."""
        if axis:
            return allmax(jnp.count_nonzero(e_live).astype(jnp.int32))
        return jnp.sum(n_live_c, dtype=jnp.int32)

    def cond(state):
        light, it = state[4], state[5]
        return (jnp.any(light) & (it < _MAX_ROUNDS)
                & (it - start_it < max_rounds))

    def apply_fixes(elems, state, fix_now, new_value, took_bounds):
        """Shared round tail: write fixed values, batched double_update of
        every touched constraint, epsilon-based light-set pruning.
        ``fix_now`` only ever holds unfixed variables, so the elements it
        fixes are live ones and next round's liveness is this round's
        minus them."""
        e_var, e_cnst, e_w, e_upen = elems
        (v_value, v_fixed, remaining, usage, light, it, e_live, n_live_c,
         _, bound_rounds, live_rounds, worked_rounds) = state
        # The live elements this round entered with, and the elements
        # its indexed ops run over: the rung's size.
        live_rounds = _pair_add(live_rounds,
                                jnp.sum(n_live_c, dtype=jnp.int32))
        worked_rounds = _pair_add(worked_rounds, e_var.size * n_shards)
        with jax.named_scope("sg.lmm.update"):
            v_value = jnp.where(fix_now, new_value, v_value)
            v_fixed = v_fixed | fix_now

            # Batched double_update on every constraint touched by fixed
            # vars.  ONE gather by e_var carries the flag and the value:
            # rates are >= 0, so -1 marks "not fixed this round" (a NaN
            # rate still counts as fixed and poisons d_rem as it must).
            e_fixval = jnp.take(jnp.where(fix_now, new_value, -1.0), e_var)
            e_fix = e_live & ~(e_fixval < 0)
            # d_rem, d_use and the count of fixed elements ride ONE
            # scatter with a 3-wide window: on the chip a scatter costs
            # by the index, and the 3-wide one 1.7x a 1-wide one, not 3x
            # (PERF.md §5).  The count answers both "touched by a fix"
            # and, against the carried live count, "any live element
            # left"; it is exact below 2^24 elements a constraint in f32
            # (checked at entry against the whole element count).
            sums = allsum(jnp.zeros((n_c, 3), dtype).at[e_cnst].add(
                jnp.stack([jnp.where(e_fix, e_w * e_fixval, 0.0),
                           jnp.where(e_fix, e_upen, 0.0),
                           e_fix.astype(dtype)], axis=-1)))
            d_rem, d_use = sums[:, 0], sums[:, 1]

            new_remaining = remaining - d_rem
            new_remaining = jnp.where(new_remaining < c_bound * eps, 0.0,
                                      new_remaining)
            new_usage_sum = usage - d_use
            new_usage_sum = jnp.where(new_usage_sum < eps, 0.0,
                                      new_usage_sum)

        with jax.named_scope("sg.lmm.prune"):
            n_fix = sums[:, 2].astype(jnp.int32)
            touched = n_fix > 0
            e_live = e_live & ~e_fix
            n_live_c = n_live_c - n_fix
            if has_fatpipe:
                # FATPIPE: usage is re-derived as the max over unset
                # variables.
                new_usage_max = allmax(jnp.zeros(n_c, dtype).at[e_cnst].max(
                    jnp.where(e_live, e_upen, 0.0)))
        with jax.named_scope("sg.lmm.update"):
            if has_fatpipe:
                new_usage = jnp.where(c_fatpipe, new_usage_max,
                                      new_usage_sum)
                usage = jnp.where(touched, new_usage, usage)
                remaining = jnp.where(touched & ~c_fatpipe, new_remaining,
                                      remaining)
            else:
                # static specialization (host-checked): no FATPIPE
                # constraint in the system, so the max-usage recompute
                # drops out
                usage = jnp.where(touched, new_usage_sum, usage)
                remaining = jnp.where(touched, new_remaining, remaining)

        with jax.named_scope("sg.lmm.prune"):
            # A constraint leaves the light set only when *touched* by a
            # fixed variable and failing the epsilon tests
            # (maxmin.cpp:607-609); untouched constraints with
            # tiny-but-positive usage stay in.
            drop = touched & (~(usage > eps)
                              | ~(remaining > c_bound * eps))
            light = light & ~drop
            # Numerical safety net (no effect in exact arithmetic, where
            # usage - d_use reaches 0 exactly and the epsilon drop
            # fires): a constraint with no live variable left can never
            # fix anything again, so it must leave the light set even
            # when f32 rounding of the usage residual keeps it above eps
            # — otherwise the loop spins on an unfixable min-rou
            # constraint until _MAX_ROUNDS.
            light = light & (n_live_c > 0)
        return (v_value, v_fixed, remaining, usage, light, it + 1,
                e_live, n_live_c, live_elems(e_live, n_live_c),
                bound_rounds + took_bounds.astype(jnp.int32), live_rounds,
                worked_rounds)

    def body_global(elems, state):
        """One global bottleneck level per round (reference order,
        maxmin.cpp:560-680)."""
        e_var, e_cnst = elems[:2]
        v_value, v_fixed, remaining, usage, light, it, e_live = state[:7]

        with jax.named_scope("sg.lmm.neighmin"):
            rou = jnp.where(light, remaining / jnp.where(light, usage, 1.0),
                            inf)
        with jax.named_scope("sg.lmm.level"):
            min_usage = jnp.min(rou)
            saturated_c = light & (rou == min_usage)

            # Saturated variables: any live element inside a saturated
            # constraint.
            e_sat = e_live & jnp.take(saturated_c, e_cnst)
            v_sat = allmax(jnp.zeros(n_v, dtype=bool).at[e_var].max(e_sat))

            if not has_bounds:
                # static specialization: no active variable bound, so
                # the bound-first rule drops out of the compiled round
                # body
                fix_now = v_sat
                new_value = min_usage / jnp.where(v_enabled, v_penalty, 1.0)
                use_bounds = jnp.array(False, jnp.bool_)
            else:
                # Bound-first rule (maxmin.cpp:566-596): if any saturated
                # variable's bound*penalty sits below min_usage, fix
                # (only) the variables whose bound*penalty equals the
                # smallest such value this round.
                bp = v_bound * v_penalty
                has_low_bound = v_sat & (v_bound > 0) & (bp < min_usage)
                min_bound = jnp.min(jnp.where(has_low_bound, bp, inf))
                use_bounds = jnp.isfinite(min_bound)

                fix_now = jnp.where(use_bounds,
                                    v_sat & (jnp.abs(bp - min_bound) < eps),
                                    v_sat)
                new_value = jnp.where(
                    use_bounds, v_bound,
                    min_usage / jnp.where(v_enabled, v_penalty, 1.0))
        return apply_fixes(elems, state, fix_now, new_value, use_bounds)

    def body_local(elems, state):
        """Fix every local-minimum constraint per round.  Exact: a
        constraint's rou = remaining/usage only ever increases when other
        variables are fixed (fixing removes a below-average contribution),
        so a constraint whose rou is minimal among every constraint it
        shares a live variable with already sits at its final level, no
        matter in which order the rest of the graph saturates."""
        e_var, e_cnst = elems[:2]
        v_value, v_fixed, remaining, usage, light, it, e_live = state[:7]

        with jax.named_scope("sg.lmm.neighmin"):
            rou = jnp.where(light, remaining / jnp.where(light, usage, 1.0),
                            inf)

            # Two-hop neighborhood min of rou: constraint -> vars ->
            # constraint.
            e_rou = jnp.where(e_live, jnp.take(rou, e_cnst), inf)
            nmin_v = allmin(jnp.full(n_v, inf, dtype).at[e_var].min(e_rou))
            e_nmin = jnp.where(e_live, jnp.take(nmin_v, e_var), inf)
            nmin_c = allmin(
                jnp.full(n_c, inf, dtype).at[e_cnst].min(e_nmin))
            processable = light & (rou <= nmin_c)

        with jax.named_scope("sg.lmm.level"):
            # Saturated vars and their levels (min processable rou
            # containing v).
            e_proc = e_live & jnp.take(processable, e_cnst)

            if not has_bounds:
                # static specialization: with no active variable bound
                # every processable constraint is unblocked, so the level
                # of a saturated variable is just its min processable rou
                level2_v = allmin(jnp.full(n_v, inf, dtype).at[e_var].min(
                    jnp.where(e_proc, e_rou, inf)))
                fix_now = jnp.isfinite(level2_v) & ~v_fixed
                new_value = level2_v / jnp.where(v_enabled, v_penalty, 1.0)
                any_low = jnp.array(False, jnp.bool_)
            else:
                level2_v, fix_bound, any_low = bounded_level(
                    elems, e_live, e_rou, e_proc, processable, nmin_v)
                fix_level = jnp.isfinite(level2_v) & ~v_fixed & ~fix_bound
                fix_now = fix_bound | fix_level
                new_value = jnp.where(
                    fix_bound, v_bound,
                    level2_v / jnp.where(v_enabled, v_penalty, 1.0))
        return apply_fixes(elems, state, fix_now, new_value, any_low)

    def bounded_level(elems, e_live, e_rou, e_proc, processable, level_v):
        """The local round's level under variable bounds: (level2_v,
        fix_bound, whether the bound block ran)."""
        e_var, e_cnst = elems[:2]
        # The bound-free level first: it is the answer whenever no bound
        # binds, and it tells the saturated variables.  A processable
        # element sends its rou capped at the largest finite number, so
        # "any processable element" (v_sat) reads off the same scatter
        # even where a light constraint's rou is inf (a usage so small
        # that remaining/usage overflows): its flows still take their
        # bounds.
        capped_v = allmin(jnp.full(n_v, inf, dtype).at[e_var].min(
            jnp.where(e_proc, jnp.minimum(e_rou, big), inf)))
        v_sat = capped_v < inf
        bp = v_bound * v_penalty
        low_v = v_sat & (v_bound > 0) & (bp < level_v)

        def bound_block(_):
            # Bound-first rule, localized: a processable constraint
            # holding a below-level bounded variable only fixes its
            # minimal such bounds this round (the constraint re-enters
            # with an updated rou), and any constraint sharing a
            # variable with it must wait, exactly as the reference's
            # global-min-bound round defers level fixing.
            e_bp = jnp.where(
                e_live, jnp.take(jnp.where(low_v, bp, inf), e_var), inf)
            mb_c = allmin(jnp.full(n_c, inf, dtype).at[e_cnst].min(e_bp))
            mb_c = jnp.where(processable, mb_c, inf)
            e_mb = jnp.where(e_proc, jnp.take(mb_c, e_cnst), inf)
            mb_v = allmin(jnp.full(n_v, inf, dtype).at[e_var].min(e_mb))
            e_blocked = e_proc & jnp.isfinite(jnp.take(mb_v, e_var))
            blocked_c = allmax(
                jnp.zeros(n_c, dtype=bool).at[e_cnst].max(e_blocked))

            # Level-fixing only through processable, unblocked
            # constraints.
            ok_c = processable & ~blocked_c
            level2_v = allmin(jnp.full(n_v, inf, dtype).at[e_var].min(
                jnp.where(jnp.take(ok_c, e_cnst), e_rou, inf)))
            return level2_v, low_v & (jnp.abs(bp - mb_v) < eps)

        def free_result(_):
            # No bound under its level: the block's quantities reduce
            # line by line to the bound-free round (mb_c = mb_v = inf,
            # nothing blocked, ok_c = processable).  One corner departs
            # from the parent: a processable constraint whose rou is
            # EXACTLY the largest finite number reads as inf here, and
            # its variables are not level-fixed at it this round.
            return (jnp.where(capped_v < big, capped_v, inf),
                    jnp.zeros_like(low_v))

        # The predicate is data, and the same on every shard (allmax
        # says so to the compiler).  Under vmap the cond is a select and
        # both sides run.
        any_low = allmax(jnp.any(low_v))
        level2_v, fix_bound = lax.cond(any_low, bound_block, free_result,
                                       None)
        return level2_v, fix_bound, any_low

    body = body_local if parallel_rounds else body_global

    def first_state(carry, e_live0, n_live_c0):
        return (*carry, e_live0, n_live_c0,
                live_elems(e_live0, n_live_c0), jnp.array(0, jnp.int32),
                jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32))

    def descend(elems, state):
        """The rungs above the bottom one: ``(the bottom rung's lists,
        the state there, the partitions it took)``."""
        partitions = jnp.array(0, jnp.int32)
        for size, below in zip(sizes, sizes[1:]):
            entered = state[5]
            state = lax.while_loop(
                lambda st: cond(st) & (st[8] > below),
                functools.partial(body, elems), state)
            # Rounds are left to run (else the next list is never read):
            # put this one live-first and keep its head.  One that came
            # out of a partition and saw no round since is live-first
            # already, and entry's padding is dead from the start, so
            # rungs skipped in one step (random pairs fall from 100 %
            # live to 4 % in a round) cost one partition and a slice
            # each.
            part = cond(state) & ((state[5] > entered)
                                  | (size == sizes[0]))
            with jax.named_scope("sg.lmm.partition"):
                *elems, e_live = lax.cond(
                    part, functools.partial(_livefirst_head, n_keep=below),
                    lambda *lists: tuple(_head(a, below) for a in lists),
                    *elems, state[6])
            state = (*state[:6], e_live, *state[7:])
            partitions = partitions + part.astype(jnp.int32)
        return tuple(elems), state, partitions

    if not var_side:
        elems, state, partitions = descend(elems, first_state(*entered))
        var_entry = jnp.array(0, jnp.int32)
    else:
        v_ptr, ve_idx = var_index
        # The ladder's own rung test, read from the variables before any
        # element is touched: a live variable's elements are live.
        live_v = v_enabled if carry is None else v_enabled & ~carry[1]
        deg = jnp.where(live_v, v_ptr[1:] - v_ptr[:-1], 0)

        def from_lists(e_var, e_cnst, e_w):
            elems, *st = enter(e_var, e_cnst, e_w)
            return (*descend(elems, first_state(*st)),
                    jnp.array(0, jnp.int32))

        def from_vars(e_var, e_cnst, e_w):
            with jax.named_scope("sg.lmm.init"):
                lists = _rung_from_vars(v_ptr, ve_idx, deg, e_var, e_cnst,
                                        e_w, sizes[-1])
            elems, *st = enter(*lists)
            return (elems, first_state(*st), jnp.array(0, jnp.int32),
                    jnp.array(1, jnp.int32))

        elems, state, partitions, var_entry = lax.cond(
            jnp.sum(deg) <= sizes[-1], from_vars, from_lists,
            e_var, e_cnst, e_w)
    state = _run_rounds(cond, functools.partial(body, tuple(elems)), state,
                        max_rounds, unroll)
    v_value, v_fixed, remaining, usage, light, rounds = state[:6]
    if return_carry:
        return (v_value, remaining, usage, rounds, state[:6], state[9],
                state[10], state[11], partitions, var_entry)
    return v_value, remaining, usage, rounds


def _entry(e_var, e_cnst, e_w, c_fatpipe, v_penalty, v_fixed, n_c: int,
           has_fatpipe: bool, allsum, allmax):
    """What ``fixpoint`` derives from the element list before its first
    round (maxmin.cpp's start): ``(e_valid, e_upen, e_live, n_live_c,
    usage)`` — the elements that count (positive weight on an enabled
    variable), their weight over the variable's penalty, those whose
    variable is not fixed yet, their count per constraint, and the
    initial usage per constraint (sum for SHARED, max for FATPIPE).
    ``v_fixed`` is None on a cold call and a carry's on a carried one,
    whose usage the carry brings (``usage`` is then None).

    An element-wide gather or scatter costs by the index whatever it
    moves (PERF.md §5), so this issues ONE gather by ``e_var`` and ONE
    scatter by ``e_cnst`` (a cold FATPIPE call one more, the max)."""
    dtype = e_w.dtype
    # The gather carries all that entry needs of a variable: its penalty
    # where it is enabled (else 0) and the carry's v_fixed in the sign.
    # A cold call's fixed variables (v_penalty < 0) are not enabled, so
    # there liveness is validity.
    pen = jnp.where(v_penalty > 0, v_penalty, 0.0)
    if v_fixed is not None:
        pen = jnp.where(v_fixed, -pen, pen)
    e_pen = jnp.take(pen, e_var, fill_value=0)
    e_valid = (e_w > 0) & (e_pen != 0)
    e_live = (e_w > 0) & (e_pen > 0)
    e_upen = jnp.where(
        e_valid, e_w / jnp.where(e_valid, jnp.abs(e_pen), 1.0), 0.0)
    if v_fixed is not None:
        n_live_c = allsum(jnp.zeros(n_c, jnp.int32).at[e_cnst].add(
            e_live.astype(jnp.int32)))
        return e_valid, e_upen, e_live, n_live_c, None
    # The usage sum and the live count ride one 2-wide scatter-add, as
    # apply_fixes' three columns do; the count is exact in `dtype` under
    # the element limit fixpoint checks.
    sums = allsum(jnp.zeros((n_c, 2), dtype).at[e_cnst].add(
        jnp.stack([e_upen, e_valid.astype(dtype)], axis=-1)))
    usage = sums[:, 0]
    if has_fatpipe:
        usage_max = allmax(jnp.zeros(n_c, dtype).at[e_cnst].max(e_upen))
        usage = jnp.where(c_fatpipe, usage_max, usage)
    return e_valid, e_upen, e_live, sums[:, 1].astype(jnp.int32), usage


#: Every rung of the ladder holds MORE than this many elements.  Down
#: there a round stops going by its indices (``tools/coo_round_probe.py
#: --only ladder``, PERF.md §5: 4.97 ms on 32,768 elements in a 1-D
#: list against 4.78 on 65,536, its 3-wide scatter 3.42 against 1.54;
#: the drain's [E / 8, 8] list still halves, 5.19 ms on 77,608 to 2.72
#: on 38,808), and every rung is one more copy of the round for XLA to
#: compile, ~8 s each at config #4's width.  A list of up to twice
#: this has one rung: the single loop.
_LADDER_MIN_ELEMS = 1 << 15
#: Each rung holds this share of the one above (rounded up to whole
#: index groups), so a round indexes under ``_LADDER_RATIO`` times its
#: live elements.  A finer ratio would index less (sqrt 2: 44 % of
#: rounds x n_elem in the alltoall where 2 reads 54 %) in twice the
#: rungs.
_LADDER_RATIO = 2
#: No rung but the list itself holds more than this many elements: a
#: rung is one more copy of the round for XLA to compile, and above
#: 2^21 elements a copy costs ~25 s on the chip's host where one at
#: config #4's width costs ~10 (PERF.md §6, PR 35: the nine rungs from
#: 9,234,864 down compiled in ~230 s, the seven without 4,617,432 and
#: 2,308,720 in 161-195 s).  A longer list steps from its own size to the
#: first power of the ratio at or under this, so a solve with more
#: live elements than that runs its rounds on the whole list.  Lists
#: of up to 2^22 elements (config #4's are 2^21 at most) keep the
#: plain ladder.
_LADDER_TOP_ELEMS = 1 << 21


def _ladder_sizes(shape) -> List[int]:
    """The static element counts of ``fixpoint``'s rungs for an element
    list of ``shape`` ([E], or the drain's [E / g, g]), the list's own
    first.  Each is a whole number of scatter index groups, so a rung
    keeps the shape convention of the list it was cut from."""
    size = int(np.prod(shape))
    group = shape[-1] if len(shape) == 2 else _pos_group(size)
    sizes = [size]
    while True:
        step = _LADDER_RATIO
        while (below := -(-sizes[-1] // (step * group)) * group) \
                > _LADDER_TOP_ELEMS:
            step *= _LADDER_RATIO
        if not _LADDER_MIN_ELEMS < below < sizes[-1]:
            return sizes
        sizes.append(below)


def _head(a, n: int):
    """The first ``n`` elements of an element list, in its shape
    convention."""
    return a[:n] if a.ndim == 1 else a[:n // a.shape[1]]


def _livefirst_head(*lists_and_live, n_keep: int):
    """Element lists and their liveness (last), partitioned live-first
    and cut to the first ``n_keep``: stable, so the survivors keep their
    relative order (see :func:`_stable_livefirst_perm`).  One scatter
    over the list builds the permutation and ONE gather over the kept
    head moves every list, side by side as rows of int32 words: a
    gather costs by the index, not by what it moves.  The liveness of
    the head needs none: its first ``n_live`` are the live ones.

    On the chip, 2,097,152 -> 1,048,576 elements: 16.4 ms so, 62.7 ms
    with a gather a list; one stable ``lax.sort`` carrying the lists as
    payload runs in 6.8 ms, but with a sort a rung the program takes
    1.6-1.7x as long to compile, and a solve partitions twice
    (``tools/coo_round_probe.py --only ladder``, PERF.md §5)."""
    *lists, e_live = lists_and_live
    live = e_live.reshape(-1)
    group = e_live.shape[-1] if e_live.ndim == 2 else _pos_group(live.size)
    keep = _head(_stable_livefirst_perm(live, group).reshape(e_live.shape),
                 n_keep)
    words = [lax.bitcast_convert_type(a.reshape(-1), jnp.int32)
             .reshape(live.size, -1) for a in lists]
    rows = jnp.take(jnp.concatenate(words, axis=1), keep, axis=0)
    heads, at = [], 0
    for a, w in zip(lists, words):
        n = w.shape[1]
        mine = rows[..., at:at + n] if n > 1 else rows[..., at]
        heads.append(lax.bitcast_convert_type(mine, a.dtype))
        at += n
    head_live = (lax.iota(jnp.int32, n_keep).reshape(keep.shape)
                 < jnp.count_nonzero(live))
    return (*heads, head_live)


def var_index(e_var, e_w, n_v: int):
    """The variable-major index of an element list, on the host:
    ``(v_ptr[n_v + 1], ve_idx[E])``, int32.  ``ve_idx`` holds the
    positions (in the flattened list) of the elements that count — a
    positive weight on a variable of ``[0, n_v)`` — grouped by variable,
    ascending within one; variable ``v``'s are ``ve_idx[v_ptr[v]:
    v_ptr[v + 1]]``.  Static for a list that is never renumbered or
    reweighted; one stable argsort of the list, so build it once per
    list and not per sim."""
    e_var = np.asarray(e_var, np.int64).reshape(-1)
    counts = (np.asarray(e_w, np.float64).reshape(-1) > 0) \
        & (e_var >= 0) & (e_var < n_v)
    key = np.where(counts, e_var, n_v)
    ve_idx = np.argsort(key, kind="stable").astype(np.int32)
    v_ptr = np.zeros(n_v + 1, np.int32)
    np.cumsum(np.bincount(key, minlength=n_v + 1)[:n_v], out=v_ptr[1:])
    return v_ptr, ve_idx


def _owners(deg, n_keep: int):
    """``n_keep`` positions handed out to owners in order, ``deg[i]`` of
    them to owner ``i`` (at most ``n_keep`` in all): ``(owner[n_keep],
    first, end)``, owner ``i`` holding positions ``[first[i], end[i])``.
    One scatter as wide as ``deg`` marks where each owner's run starts
    and a running max hands every position its owner; the positions
    past the last run get the last owner's (mask them by ``end[-1]``)."""
    n = deg.shape[0]
    end = jnp.cumsum(deg)
    first = end - deg
    group = _pos_group(n)
    marks = jnp.zeros(n_keep, jnp.int32).at[
        jnp.where(deg > 0, first, n_keep).reshape(n // group, group)].set(
        lax.iota(jnp.int32, n).reshape(n // group, group) + 1, mode="drop")
    return jnp.maximum(lax.cummax(marks) - 1, 0), first, end


def _rung_from_vars(v_ptr, ve_idx, deg, e_var, e_cnst, e_w, n_keep: int):
    """The ``n_keep``-element rung of the ladder as the stable live-first
    partition of the whole list would leave it — the live elements in
    the list's order, then dead padding (weight 0, as a list's own) —
    built from the live variables' own elements: ``(e_var, e_cnst,
    e_w)`` in the list's shape convention.  ``deg`` is the element count
    of each live variable (0 for the others) and must add up to at most
    ``n_keep``; ``(v_ptr, ve_idx)`` is :func:`var_index`'s.

    Nothing here is as wide as the list: one n_v-wide scatter marks
    where each live variable's elements start in the rung, a running
    max hands every position its variable, and ``n_keep``-wide gathers
    fetch the element's position (two: the variable's shift, then
    ``ve_idx``), its constraint and its weight (its variable is the one
    that owns it).  Positions come out ascending
    when the list is variable-major (as the collective tape lowers it);
    another list pays one ``n_keep``-wide sort."""
    shape = _head(e_var, n_keep).shape
    owner, first, end = _owners(deg, n_keep)
    owner = owner.reshape(shape)
    at = lax.iota(jnp.int32, n_keep).reshape(shape)
    inside = at < end[-1]
    # element j of variable v sits at ve_idx[v_ptr[v] + j], and in the
    # rung at first[v] + j
    pos = jnp.where(
        inside,
        jnp.take(ve_idx, at + jnp.take(v_ptr[:-1] - first, owner)),
        e_var.size).reshape(-1)
    pos, owner = lax.cond(
        jnp.all(pos[1:] >= pos[:-1]), lambda *both: both,
        lambda *both: tuple(lax.sort(both, num_keys=1)),
        pos, owner.reshape(-1))
    pos = jnp.where(inside, pos.reshape(shape), 0)

    def fetch(a):
        got = a[pos // a.shape[1], pos % a.shape[1]] if a.ndim == 2 \
            else jnp.take(a, pos)
        return jnp.where(inside, got, 0)

    return (jnp.where(inside, owner.reshape(shape), 0).astype(e_var.dtype),
            fetch(e_cnst), fetch(e_w))


#: ``fixpoint`` sums the live and the indexed elements of its rounds in
#: two int32 each: [whole multiples of 2^20, the rest].  Each half is
#: exact as a float32 too (the chunk fetch ships its head in the
#: solve's dtype) while the sum stays under 2^44: 2^24 elements over
#: 2^20 rounds.
_LIVE_LOW_BITS = 20


def _pair_add(pair, low, high=0):
    """``low`` (under 2^30) and ``high`` x 2^20 added to an exact
    [high, low] pair of int32 whose low half stays under 2^20: a chunk's
    sum of elements passes 2^24 at config #4's width and 2^31 on a deep
    solve."""
    low = pair[1] + low
    return jnp.stack([pair[0] + high + (low >> _LIVE_LOW_BITS),
                      low & ((1 << _LIVE_LOW_BITS) - 1)])


def _live_elem_rounds(pair) -> int:
    """The exact count behind ``fixpoint``'s ``live_elem_rounds`` or
    ``worked_elem_rounds`` pair (a host array, of any number dtype)."""
    return (int(pair[0]) << _LIVE_LOW_BITS) + int(pair[1])


def _vc_round_body(vc_cnst, vc_w, vc_valid, v_penalty, c_bound,
                   c_fatpipe, eps, has_fatpipe):
    """THE bound-free vc-centric local round, on a 6-tuple state
    (v_value, v_fixed, remaining, usage, light, it): single source for
    both fixpoint_ell's dense path (which wraps it to thread its unused
    cv-side carry member) and the compaction chain.

    2 element gathers + 2 scatters over the near-unpadded vc tables:
    working on [V, Wv] (~1x element count) instead of the padded
    [C, Wc] tables (~2.6x) moves fewer elements per round.  Every
    scatter keeps the 2D [V, Wv] index shape (whether a flat 1D index
    lowers differently on the TPU is not measured)."""
    n_c = c_bound.shape[0]
    dtype = vc_w.dtype
    inf = jnp.array(jnp.inf, dtype)
    v_enabled = v_penalty > 0
    vc_evalid = vc_valid & v_enabled[:, None]
    vc_upen_v = (jnp.where(vc_evalid, vc_w, 0.0)
                 / jnp.where(v_enabled, v_penalty, 1.0)[:, None])

    def body(state):
        v_value, v_fixed, remaining, usage, light, it = state
        vc_live = vc_evalid & ~v_fixed[:, None]
        rou = jnp.where(light, remaining / jnp.where(light, usage, 1.0),
                        inf)
        rou_vc = jnp.take(rou, vc_cnst)
        nmin_v = jnp.where(vc_live, rou_vc,
                           inf).min(axis=1, initial=jnp.inf)
        el_nmin = jnp.where(vc_live, nmin_v[:, None], inf)
        nmin_c = jnp.full(n_c, jnp.inf, dtype).at[vc_cnst].min(el_nmin)
        processable = light & (rou <= nmin_c)
        vc_proc = vc_live & jnp.take(processable, vc_cnst)
        level2_v = jnp.where(vc_proc, rou_vc,
                             inf).min(axis=1, initial=jnp.inf)
        fix_now = jnp.isfinite(level2_v) & ~v_fixed
        new_value = level2_v / jnp.where(v_enabled, v_penalty, 1.0)
        v_value = jnp.where(fix_now, new_value, v_value)
        v_fixed = v_fixed | fix_now

        el_fix = vc_live & fix_now[:, None]
        live2 = vc_live & ~fix_now[:, None]
        contrib = jnp.stack(
            [jnp.where(el_fix, vc_w * v_value[:, None], 0.0),
             jnp.where(el_fix, vc_upen_v, 0.0),
             live2.astype(dtype)], axis=-1)
        sums = jnp.zeros((n_c, 3), dtype).at[vc_cnst].add(contrib)
        d_rem, d_use = sums[:, 0], sums[:, 1]
        touched = d_use > 0
        has_live = sums[:, 2] > 0

        new_remaining = remaining - d_rem
        new_remaining = jnp.where(new_remaining < c_bound * eps, 0.0,
                                  new_remaining)
        new_usage_sum = usage - d_use
        new_usage_sum = jnp.where(new_usage_sum < eps, 0.0,
                                  new_usage_sum)
        if has_fatpipe:
            el_upen = jnp.where(live2, vc_upen_v, 0.0)
            usage_max = jnp.zeros(n_c, dtype).at[vc_cnst].max(el_upen)
            new_usage = jnp.where(c_fatpipe, usage_max, new_usage_sum)
            usage = jnp.where(touched, new_usage, usage)
            remaining = jnp.where(touched & ~c_fatpipe, new_remaining,
                                  remaining)
        else:
            usage = jnp.where(touched, new_usage_sum, usage)
            remaining = jnp.where(touched, new_remaining, remaining)

        drop = touched & (~(usage > eps) | ~(remaining > c_bound * eps))
        light = light & ~drop & has_live
        return (v_value, v_fixed, remaining, usage, light, it + 1)

    return body


def _pos_group(n: int) -> int:
    """Index-array group width for scatters over [n] vectors: scatter
    indices keep a 2D shape throughout ops/ (see _vc_round_body)."""
    for g in (128, 8):
        if n % g == 0:
            return g
    return 1


def _stable_livefirst_perm(livemask, group: int):
    """STABLE live-first partition permutation: perm[k] = index of the
    k-th row when live rows come first, each side keeping its original
    relative order.  Stability is what makes partition-based compaction
    exact: the reduction order over the survivors is unchanged, so
    dropping rows that contribute identity values keeps results
    bit-identical to the dense run.  Shared by the ELL compaction chain
    (_ell_chain_stage) and the drain executor's on-device repack.
    `group` is the 2D scatter-index width (_pos_group)."""
    lm = livemask.astype(jnp.int32)
    n_live = jnp.count_nonzero(livemask)
    pos = jnp.where(livemask, jnp.cumsum(lm) - 1,
                    n_live + jnp.cumsum(1 - lm) - 1).astype(jnp.int32)
    n = livemask.shape[0]
    return jnp.zeros(n, jnp.int32).at[pos.reshape(-1, group)].set(
        jnp.arange(n, dtype=jnp.int32).reshape(-1, group))


@functools.partial(jax.jit,
                   static_argnames=("eps", "cap", "half", "has_fatpipe"))
def _ell_chain_stage(vc_cnst, vc_w, vc_valid, v_penalty, orig_idx,
                     c_bound, c_fatpipe, v_final, carry,
                     eps: float, cap: int, half: int,
                     has_fatpipe: bool):
    """One compaction-chain stage: run vc rounds until the live variable
    count is <= half (or convergence / round cap), then partition the
    variable rows live-first (STABLE: live rows keep their relative
    order, so the scatter-add reduction order over the survivors is
    unchanged — dropping rows that contribute exact 0.0/inf identities
    keeps the chain bit-identical to the dense run) and slice the first
    `half` rows for the next stage.

    Dead rows' values are recorded into v_final (original numbering)
    before the slice.  Returns (new tables, new carry, v_final,
    overflow) — `overflow` set when the cap expired with > half rows
    live, in which case downstream stages are garbage and the caller
    falls back to the dense path."""
    dtype = vc_w.dtype
    body = _vc_round_body(vc_cnst, vc_w, vc_valid, v_penalty, c_bound,
                          c_fatpipe, jnp.asarray(eps, dtype),
                          has_fatpipe)
    v_enabled = v_penalty > 0
    start_it = carry[5]

    def cond(st):
        live = jnp.count_nonzero(~st[1] & v_enabled)
        return (jnp.any(st[4]) & (st[5] - start_it < cap)
                & (live > half))

    st = lax.while_loop(cond, body, carry)
    v_value, v_fixed = st[0], st[1]
    v_final = v_final.at[orig_idx].set(v_value)

    livemask = ~v_fixed & v_enabled
    n_live = jnp.count_nonzero(livemask)
    overflow = (n_live > half) & jnp.any(st[4])
    V = vc_cnst.shape[0]
    perm = _stable_livefirst_perm(livemask, _pos_group(V))
    keep = perm[:half]

    def rows(a):
        return jnp.take(a, keep, axis=0)

    tables = (rows(vc_cnst), rows(vc_w), rows(vc_valid),
              rows(v_penalty), rows(orig_idx))
    carry2 = (rows(st[0]), rows(st[1]), st[2], st[3], st[4], st[5])
    return tables, carry2, v_final, overflow


@functools.partial(jax.jit,
                   static_argnames=("eps", "chunk", "has_fatpipe"))
def _vc_chunk(vc_cnst, vc_w, vc_valid, v_penalty, c_bound, c_fatpipe,
              carry, eps: float, chunk: int, has_fatpipe: bool):
    """Finisher chunk for the chain: plain bounded vc rounds."""
    body = _vc_round_body(vc_cnst, vc_w, vc_valid, v_penalty, c_bound,
                          c_fatpipe, jnp.asarray(eps, vc_w.dtype),
                          has_fatpipe)
    start_it = carry[5]

    def cond(st):
        return (jnp.any(st[4]) & (st[5] < _MAX_ROUNDS)
                & (st[5] - start_it < chunk))

    return lax.while_loop(cond, body, carry)


@functools.partial(jax.jit, static_argnames=())
def _chain_fetch(v_final, orig_idx, carry, overflow):
    """Assemble the chain's single device->host transfer: stats,
    overflow flag, merged values, remaining, usage."""
    v_value, v_fixed, remaining, usage, light, it = carry
    dtype = v_final.dtype
    v_final = v_final.at[orig_idx].set(v_value)
    stats = jnp.stack([it.astype(dtype),
                       jnp.count_nonzero(light).astype(dtype),
                       jnp.count_nonzero(v_fixed).astype(dtype),
                       overflow.astype(dtype)])
    return jnp.concatenate([stats, v_final, remaining.astype(dtype),
                            usage.astype(dtype)])


#: Memo of chain init arrays per (ell identity, eps): fresh host->device
#: transfers per solve would cost more than the chain saves.
_CHAIN_INIT_CACHE: dict = {}
#: Chain stages stop once the halved shape would fall below this: the
#: per-round device time down there is microseconds and each extra
#: stage is one more XLA compile.
_CHAIN_MIN_V = 8192
#: Per-stage round cap.  The live set at the bench classes halves every
#: ~13 local rounds; 64 is generous while bounding one stage's run time.
_CHAIN_STAGE_CAP = 64


def _solve_ell_chain(ell: LmmEllArrays, eps: float, device,
                     has_fatpipe: bool, chunk: int):
    """Device-resident active-set compaction for the ELL/vc path: chain
    jitted stages at halving static shapes, each dispatched WITHOUT a
    host sync; one fetch at the end returns stats + results.  Falls
    back (returns None) when a stage overflowed its cap or the system
    stalled.

    The CPU _Compactor repacks on the host between chunks — free there,
    a host sync plus a fresh XLA compile per shape on an accelerator.
    This chain moves the same idea on-device: the partition is a stable
    live-first permutation, so dropped rows only remove exact-identity
    contributions (cf. _Compactor's docstring); results match the dense
    run up to XLA per-program reduction-order ulps (pinned by
    tests/test_lmm.py::test_ell_chain_matches_dense)."""
    dtype = ell.vc_w.dtype
    V0 = ell.v_penalty.shape[0]
    eps_f = float(eps)

    args = _device_args(
        "vc_chain",
        [ell.vc_cnst, ell.vc_w, ell.vc_valid, ell.v_penalty,
         ell.c_bound, ell.c_fatpipe], device)
    vc_cnst, vc_w, vc_valid, v_pen, c_bound, c_fat = args

    # Initial carry, matching fixpoint_ell's None-carry init (usage0
    # from cv row-sums; numpy's pairwise row-sum can differ from the
    # device reduce in final ulps — the oracle tests bound that).
    # Memoized per (ell, eps) so repeated solves reuse the same host
    # arrays and _DEVICE_ARGS_CACHE skips the re-upload.
    key = (id(ell.vc_cnst), id(ell.cv_w), eps_f)
    hit = _CHAIN_INIT_CACHE.get(key)
    if hit is not None and hit[0] is ell.vc_cnst and hit[1] is ell.cv_w:
        init_np = hit[2]
        # refresh LRU position so the hot entry survives transients
        # (eviction below pops oldest-first)
        _CHAIN_INIT_CACHE.pop(key)
        _CHAIN_INIT_CACHE[key] = hit
    else:
        np_pen = ell.v_penalty
        safe_pen = np.where(np_pen > 0, np_pen, 1.0)
        cv_evalid = ell.cv_valid & (np_pen[ell.cv_var] > 0)
        cv_upen = np.where(cv_evalid,
                           ell.cv_w / safe_pen[ell.cv_var],
                           0.0).astype(dtype)
        usage0_np = cv_upen.sum(axis=1, dtype=dtype)
        if has_fatpipe:
            usage0_np = np.where(ell.c_fatpipe,
                                 cv_upen.max(axis=1, initial=0.0),
                                 usage0_np)
        light0_np = ((ell.c_bound > ell.c_bound * eps_f)
                     & (usage0_np > 0))
        init_np = [np.zeros(V0, dtype), (np_pen < 0),
                   ell.c_bound.astype(dtype), usage0_np, light0_np,
                   np.arange(V0, dtype=np.int32)]
        if len(_CHAIN_INIT_CACHE) >= 8:
            _CHAIN_INIT_CACHE.pop(next(iter(_CHAIN_INIT_CACHE)))
        _CHAIN_INIT_CACHE[key] = (ell.vc_cnst, ell.cv_w, init_np)
    init = _device_args("vc_chain_init", init_np, device)
    carry = (init[0], init[1], init[2], init[3], init[4],
             jnp.asarray(0, jnp.int32))
    orig_idx = init[5]
    v_final = jnp.zeros(V0, dtype)

    overflow = jnp.asarray(False, jnp.bool_)
    tables = (vc_cnst, vc_w, vc_valid, v_pen, orig_idx)
    Vs = V0
    while Vs // 2 >= _CHAIN_MIN_V:
        tables, carry, v_final, ov = _ell_chain_stage(
            *tables, c_bound, c_fat, v_final, carry,
            eps=eps_f, cap=_CHAIN_STAGE_CAP, half=Vs // 2,
            has_fatpipe=has_fatpipe)
        overflow = overflow | ov
        Vs //= 2

    # Finisher: bounded chunks to convergence, still sync-free between
    # dispatches; each iteration fetches stats+results in ONE transfer.
    prev_progress = None
    while True:
        carry = _vc_chunk(*tables[:4], c_bound, c_fat, carry,
                          eps=eps_f, chunk=chunk,
                          has_fatpipe=has_fatpipe)
        fetched = np.asarray(_chain_fetch(v_final, tables[4], carry,
                                          overflow))
        rounds, n_light, n_fixed, oflow = (int(fetched[0]),
                                           int(fetched[1]),
                                           int(fetched[2]),
                                           bool(fetched[3]))
        if oflow:
            return None     # caller re-solves on the dense path
        if n_light == 0:
            break
        if rounds >= _MAX_ROUNDS:
            raise SolveError(
                f"LMM chain solve did not converge within {_MAX_ROUNDS} "
                f"saturation rounds ({ell.n_cnst} constraints, "
                f"{ell.n_var} variables, {n_light} still active); "
                f"check maxmin/precision vs the system's magnitudes")
        progress = (n_light, n_fixed)
        if progress == prev_progress:
            return None     # stalled: let the dense path diagnose
        prev_progress = progress

    n_cc = ell.c_bound.shape[0]
    values = fetched[4:4 + V0]
    remaining = fetched[4 + V0:4 + V0 + n_cc]
    usage = fetched[4 + V0 + n_cc:4 + V0 + 2 * n_cc]
    return values, remaining, usage, rounds


@functools.partial(jax.jit,
                   static_argnames=("eps", "parallel_rounds", "chunk",
                                    "unroll", "has_bounds",
                                    "has_fatpipe"))
def _solve_ell_chunk(cv_var, cv_w, cv_valid, vc_cnst, vc_valid, c_bound,
                     c_fatpipe, v_penalty, v_bound, vc_w, carry,
                     eps: float, parallel_rounds: bool, chunk: int,
                     unroll: bool = False, has_bounds: bool = True,
                     has_fatpipe: bool = True):
    """eps is static: it is fixed per run (maxmin/precision), and a
    traced scalar would be one more host->device transfer per chunk."""
    ell = LmmEllArrays(cv_var, cv_w, cv_valid, vc_cnst, vc_valid, c_bound,
                       c_fatpipe, v_penalty, v_bound, 0, 0, vc_w)
    return fixpoint_ell(ell, jnp.asarray(eps, cv_w.dtype), carry=carry,
                        parallel_rounds=parallel_rounds, max_rounds=chunk,
                        return_carry=True, unroll=unroll,
                        has_bounds=has_bounds, has_fatpipe=has_fatpipe)


#: Device-resident copies of solver inputs, keyed by (kind, ids,
#: device): repeated solves of the same arrays re-ship nothing (~11
#: arrays per solve otherwise). Values keep the host arrays alive and
#: identity-checked, like _ELL_CACHE.
_DEVICE_ARGS_CACHE: dict = {}


def _device_args(kind: str, host_args, device):
    # CONTRACT: callers must never mutate a host array in place after
    # passing it here — the identity check below cannot see mutation.
    # Safe today because flatten()/to_ell() always build fresh arrays.
    key = (kind, tuple(id(a) for a in host_args),
           None if device is None else str(device))
    hit = _DEVICE_ARGS_CACHE.get(key)
    if hit is not None:
        src, dev_args = hit
        if all(a is b for a, b in zip(src, host_args)):
            # refresh LRU position so a steady hot entry survives
            # transient keys (eviction below pops oldest-first)
            _DEVICE_ARGS_CACHE.pop(key)
            _DEVICE_ARGS_CACHE[key] = hit
            return dev_args
    dev_args = [jax.device_put(a, device) for a in host_args]
    opstats.bump("uploaded_bytes_full",
                 sum(getattr(a, "nbytes", 0) for a in host_args))
    if len(_DEVICE_ARGS_CACHE) >= 8:
        # evict oldest-first (dict preserves insertion order) instead of
        # dropping the whole cache — the hot entry is usually the newest
        _DEVICE_ARGS_CACHE.pop(next(iter(_DEVICE_ARGS_CACHE)))
    _DEVICE_ARGS_CACHE[key] = (list(host_args), dev_args)
    return dev_args


#: Tiny memo for COO->ELL conversions so repeated solves of the same
#: arrays (benchmarks, retries) do not re-pack on the host every call.
#: Values hold the source LmmArrays, which (a) keeps the ids in the key
#: alive so they cannot be recycled onto new arrays, and (b) allows an
#: identity check on every field before a hit is trusted.
_ELL_CACHE: dict = {}


def _ell_cached(arrays: LmmArrays) -> Optional[LmmEllArrays]:
    key = (id(arrays.e_var), id(arrays.e_cnst))
    hit = _ELL_CACHE.get(key)
    if hit is not None:
        src, ell = hit
        if all(a is b for a, b in zip(src, arrays)):
            return ell
    ell = ell_from_arrays(arrays)
    if len(_ELL_CACHE) >= 8:
        _ELL_CACHE.clear()
    _ELL_CACHE[key] = (arrays, ell)
    return ell


@functools.partial(jax.jit,
                   static_argnames=("eps", "n_c", "n_v",
                                    "parallel_rounds", "chunk", "unroll",
                                    "has_bounds", "has_fatpipe"))
def _solve_kernel_chunk(e_var, e_cnst, e_w, c_bound, c_fatpipe, v_penalty,
                        v_bound, carry, eps: float, n_c: int, n_v: int,
                        parallel_rounds: bool, chunk: int,
                        unroll: bool = False, has_bounds: bool = True,
                        has_fatpipe: bool = True):
    """Run at most `chunk` more saturation rounds from `carry` (None =
    fresh start) and return (values, remaining, usage, rounds, carry,
    bound_rounds, live_elem_rounds, worked_elem_rounds, partitions): how
    many of THIS dispatch's rounds took the bound-first rule, the live
    elements its rounds entered with and the elements they indexed
    (fixpoint's pairs), and the partitions of its ladder.  eps is static
    for the same reason as _solve_ell_chunk's."""
    return fixpoint(e_var, e_cnst, e_w, c_bound, c_fatpipe, v_penalty,
                    v_bound, jnp.asarray(eps, e_w.dtype), n_c, n_v,
                    axis=None, parallel_rounds=parallel_rounds,
                    carry=carry, max_rounds=chunk, return_carry=True,
                    unroll=unroll, has_bounds=has_bounds,
                    has_fatpipe=has_fatpipe)[:9]


def _solve_chunk_batched_lane(e_var, e_cnst, ew, cb, fat, pen, vb, carry,
                              eps: float, n_c: int, n_v: int,
                              parallel_rounds: bool, chunk: int,
                              has_bounds: bool, has_fatpipe: bool):
    return fixpoint(e_var, e_cnst, ew, cb, fat, pen, vb,
                    jnp.asarray(eps, ew.dtype), n_c, n_v, axis=None,
                    parallel_rounds=parallel_rounds, carry=carry,
                    max_rounds=chunk, return_carry=True,
                    has_bounds=has_bounds, has_fatpipe=has_fatpipe)[:5]


@functools.partial(jax.jit,
                   static_argnames=("eps", "n_c", "n_v",
                                    "parallel_rounds", "chunk",
                                    "has_bounds", "has_fatpipe",
                                    "batch_w"))
def _solve_kernel_chunk_batched_fresh(e_var, e_cnst, e_w, c_bound,
                                      c_fatpipe, v_penalty, v_bound,
                                      eps: float, n_c: int, n_v: int,
                                      parallel_rounds: bool, chunk: int,
                                      has_bounds: bool = True,
                                      has_fatpipe: bool = True,
                                      batch_w: bool = True):
    """Batched (leading replica axis) counterpart of _solve_kernel_chunk,
    fresh-start flavor: ONE device program runs the first `chunk`
    saturation rounds of B independent systems that share the COO
    structure (e_var/e_cnst uploaded once) but carry per-replica
    weights/bounds/penalties.  `batch_w=False` shares the element
    weights too (pure bound/penalty sweeps).  Consumed by
    ops.lmm_batch.solve_arrays_batch."""
    def lane(ew, cb, pen, vb):
        return _solve_chunk_batched_lane(
            e_var, e_cnst, ew, cb, c_fatpipe, pen, vb, None, eps, n_c,
            n_v, parallel_rounds, chunk, has_bounds, has_fatpipe)
    return jax.vmap(lane, in_axes=(0 if batch_w else None, 0, 0, 0))(
        e_w, c_bound, v_penalty, v_bound)


@functools.partial(jax.jit,
                   static_argnames=("eps", "n_c", "n_v",
                                    "parallel_rounds", "chunk",
                                    "has_bounds", "has_fatpipe",
                                    "batch_w"))
def _solve_kernel_chunk_batched(e_var, e_cnst, e_w, c_bound, c_fatpipe,
                                v_penalty, v_bound, carry, eps: float,
                                n_c: int, n_v: int,
                                parallel_rounds: bool, chunk: int,
                                has_bounds: bool = True,
                                has_fatpipe: bool = True,
                                batch_w: bool = True):
    """Continuation flavor: resume each replica from its carried loop
    state.  Converged lanes are frozen by their own while_loop cond, so
    re-dispatching a mixed fleet never perturbs finished replicas."""
    def lane(ew, cb, pen, vb, carry_l):
        return _solve_chunk_batched_lane(
            e_var, e_cnst, ew, cb, c_fatpipe, pen, vb, carry_l, eps,
            n_c, n_v, parallel_rounds, chunk, has_bounds, has_fatpipe)
    return jax.vmap(lane, in_axes=(0 if batch_w else None, 0, 0, 0, 0))(
        e_w, c_bound, v_penalty, v_bound, carry)


def flatten(cnst_list: List[Constraint], dtype=np.float64
            ) -> Optional[Tuple[LmmArrays, List["Variable"]]]:
    """Flatten the live portion of a host System into padded COO arrays.

    Slot numbering follows the constraint-list iteration order and, within
    each constraint, the enabled-element list order, giving the same
    deterministic structure the reference's intrusive lists provide.
    """
    with opstats.span("lmm.flatten"):
        var_slots = {}
        v_penalty: List[float] = []
        v_bound: List[float] = []
        vars_in_order = []
        e_var: List[int] = []
        e_cnst: List[int] = []
        e_w: List[float] = []
        c_bound: List[float] = []
        c_fat: List[bool] = []

        for ci, cnst in enumerate(cnst_list):
            c_bound.append(cnst.bound)
            c_fat.append(cnst.sharing_policy == SharingPolicy.FATPIPE)
            for elem in cnst.enabled_element_set:
                var = elem.variable
                slot = var_slots.get(id(var))
                if slot is None:
                    slot = len(v_penalty)
                    var_slots[id(var)] = slot
                    v_penalty.append(var.sharing_penalty)
                    v_bound.append(var.bound)
                    vars_in_order.append(var)
                e_var.append(slot)
                e_cnst.append(ci)
                e_w.append(elem.consumption_weight)

        n_e, n_c, n_v = len(e_var), len(c_bound), len(v_penalty)
        if n_c == 0:
            return None
        E, C, V = _bucket(max(n_e, 1)), _bucket(n_c), _bucket(max(n_v, 1))

        arrays = LmmArrays(
            e_var=np.zeros(E, np.int32), e_cnst=np.zeros(E, np.int32),
            e_w=np.zeros(E, dtype), c_bound=np.zeros(C, dtype),
            c_fatpipe=np.zeros(C, bool), v_penalty=np.zeros(V, dtype),
            v_bound=np.full(V, -1.0, dtype), n_elem=n_e, n_cnst=n_c, n_var=n_v)
        arrays.e_var[:n_e] = e_var
        # Padding elements point at constraint slot 0 with weight 0: harmless.
        arrays.e_cnst[:n_e] = e_cnst
        arrays.e_w[:n_e] = e_w
        arrays.c_bound[:n_c] = c_bound
        arrays.c_fatpipe[:n_c] = c_fat
        arrays.v_penalty[:n_v] = v_penalty
        arrays.v_bound[:n_v] = v_bound
        return arrays, vars_in_order


def use_local_rounds() -> bool:
    """Parse + validate the lmm/rounds flag (local|global)."""
    mode = config["lmm/rounds"]
    if mode not in ("local", "global"):
        raise ValueError(f"Unknown lmm/rounds {mode!r} "
                         "(expected local or global)")
    return mode == "local"


# Device rounds per dispatch: bounds one dispatch's run time, so a
# spinning f32 solve comes back to the host and raises instead of
# holding the device, while keeping the per-dispatch overhead
# negligible for the common small-round case.
_CHUNK_ROUNDS = 4096
#: On an accelerator the cap is lower: a host cannot interrupt a
#: dispatch, so its worst case is what a stuck solve costs.
#: Local-rounds solves converge in O(10-100) rounds, so 256 lets every
#: practical solve finish in ONE dispatch; the while_loop cond exits
#: early once converged.
_CHUNK_ROUNDS_ACCEL = 256
#: Rounds per dispatch in unrolled mode: compile time scales linearly
#: with the unroll factor, so keep chunks small — local-rounds solves
#: typically converge in O(10) rounds anyway.
_CHUNK_ROUNDS_UNROLL = 16
#: Below this element count the whole solve costs ~a millisecond and
#: compaction's per-chunk host sync + repack + per-shape recompiles
#: are pure overhead on the simulator's per-step hot path.
_COMPACT_MIN_ELEMS = 4096
#: one-shot flag for the lmm/compact:on-with-ELL warning
_WARNED_COMPACT_ELL = False


def _default_chunk() -> int:
    return _CHUNK_ROUNDS if default_platform() == "cpu" \
        else _CHUNK_ROUNDS_ACCEL


class _Compactor:
    """Host-side active-set compaction for the COO chunk loop (see
    solve_arrays).  Owns the CURRENT (possibly repacked) host arrays,
    the current->original row maps, and the full-size result mirrors
    retired rows are merged into.

    Exact by construction: a retired element only ever contributes
    identity values to the round reductions (0.0 to the scatter-adds
    and the bool/float maxes, inf to the mins; 0.0 + x == x,
    max(0.0, u>=0) == u, min(inf, r) == r), and a retired row's state
    is frozen the moment its last live element dies — a variable
    retires fixed, a constraint that can never again be touched keeps
    its remaining/usage."""

    def __init__(self, arrays: LmmArrays, device):
        self.device = device
        self.e = (arrays.e_var, arrays.e_cnst, arrays.e_w)
        self.vc = (arrays.v_penalty, arrays.v_bound, arrays.c_bound,
                   arrays.c_fatpipe)
        self.v_map = self.c_map = None
        self.final = None
        self.orig_nv = len(arrays.v_penalty)
        self.orig_nc = len(arrays.c_bound)

    def try_compact(self, carry):
        """Repack when at most half the element rows are still live.
        Returns (device_args, carry, n_v, n_c) for the shrunken
        system, or None when density is still high."""
        e_var, e_cnst, e_w = self.e
        v_pen, v_bnd, c_bnd, c_fat = self.vc
        vfix = np.asarray(carry[1])
        live = (e_w > 0) & (v_pen[e_var] > 0) & ~vfix[e_var]
        n_live = int(live.sum())
        if n_live > len(e_var) // 2:
            return None
        dt = e_w.dtype
        # rows referenced by a live element stay; all others retire
        vmask = np.zeros(len(v_pen), bool)
        vmask[e_var[live]] = True
        kept_v = np.flatnonzero(vmask)
        cmask = np.zeros(len(c_bnd), bool)
        cmask[e_cnst[live]] = True
        kept_c = np.flatnonzero(cmask)

        vv, vfx, rem, use, lig = (np.asarray(x) for x in carry[:5])
        if self.final is None:
            self.final = (np.zeros(self.orig_nv, dt),
                          np.zeros(self.orig_nc, dt),
                          np.zeros(self.orig_nc, dt))
        vm = (self.v_map if self.v_map is not None
              else np.arange(len(v_pen)))
        cm = (self.c_map if self.c_map is not None
              else np.arange(len(c_bnd)))
        fv, fr, fu = self.final
        # current arrays are bucket-padded beyond the map length
        fv[vm] = vv[:len(vm)]
        fr[cm] = rem[:len(cm)]
        fu[cm] = use[:len(cm)]
        self.v_map, self.c_map = vm[kept_v], cm[kept_c]

        Eb = _bucket(max(n_live, 1))
        Vb = _bucket(max(len(kept_v), 1))
        Cb = _bucket(max(len(kept_c), 1))
        v_o2n = np.zeros(len(v_pen), np.int32)
        v_o2n[kept_v] = np.arange(len(kept_v), dtype=np.int32)
        c_o2n = np.zeros(len(c_bnd), np.int32)
        c_o2n[kept_c] = np.arange(len(kept_c), dtype=np.int32)

        def repack(src, fill, n, idx):
            out = np.full(n, fill, src.dtype)
            out[:len(idx)] = src[idx]
            return out

        ev = np.zeros(Eb, np.int32)
        ev[:n_live] = v_o2n[e_var[live]]
        ec = np.zeros(Eb, np.int32)
        ec[:n_live] = c_o2n[e_cnst[live]]
        ew = np.zeros(Eb, dt)
        ew[:n_live] = e_w[live]
        self.e = (ev, ec, ew)
        self.vc = (repack(v_pen, 0.0, Vb, kept_v),
                   repack(v_bnd, -1.0, Vb, kept_v),
                   repack(c_bnd, 0.0, Cb, kept_c),
                   repack(c_fat, False, Cb, kept_c))

        # compacted arrays bypass _DEVICE_ARGS_CACHE — they are fresh
        # per solve and would thrash it
        def put(a):
            return jax.device_put(a, self.device)
        args = [put(a) for a in
                (ev, ec, ew, self.vc[2], self.vc[3],
                 self.vc[0], self.vc[1])]
        carry = (put(repack(vv, 0.0, Vb, kept_v)),
                 put(repack(vfx, False, Vb, kept_v)),
                 put(repack(rem, 0.0, Cb, kept_c)),
                 put(repack(use, 0.0, Cb, kept_c)),
                 put(repack(lig, False, Cb, kept_c)),
                 carry[5])
        return args, carry, Vb, Cb

    def merge(self, values, remaining, usage):
        """Final (values, remaining, usage) at ORIGINAL row numbering,
        or None when no compaction ever ran."""
        if self.final is None:
            return None
        fv, fr, fu = self.final
        fv[self.v_map] = np.asarray(values)[:len(self.v_map)]
        fr[self.c_map] = np.asarray(remaining)[:len(self.c_map)]
        fu[self.c_map] = np.asarray(usage)[:len(self.c_map)]
        return fv, fr, fu


def solve_arrays(arrays: LmmArrays, eps: float, device=None,
                 parallel_rounds: Optional[bool] = None,
                 chunk: Optional[int] = None,
                 unroll: Optional[bool] = None):
    """Run the jit'd fixpoint in bounded-round chunks with host-side
    convergence checks between dispatches; returns
    (values, remaining, usage, rounds)."""
    chunk_given = chunk is not None
    if parallel_rounds is None:
        parallel_rounds = use_local_rounds()
    if unroll is None:
        mode = config["lmm/unroll"]
        if mode not in ("auto", "on", "off"):
            raise ValueError(f"Unknown lmm/unroll {mode!r} "
                             "(expected auto, on or off)")
        # 'auto' means OFF everywhere: while_loop gathers lower fine on
        # the TPU (bench_results/tpu_round_profile.jsonl) and unrolling
        # only multiplies compile time.  'on' stays available as the
        # escape hatch.
        unroll = mode == "on"
    if chunk is None:
        chunk = _CHUNK_ROUNDS_UNROLL if unroll else _default_chunk()

    # Layout: ELL (dense padded rows, no scatters) on accelerators when
    # the graph is not too skewed; COO everywhere else. lmm/layout
    # overrides (coo|ell|auto).
    layout = config["lmm/layout"]
    platform = (device.platform if device is not None
                else default_platform())
    ell = None
    if layout == "ell" or (layout == "auto" and platform != "cpu"):
        ell = _ell_cached(arrays)

    # Active-set compaction: between chunks, repack the element list
    # dropping elements of already-fixed variables.  Bit-identical to
    # the dense run — a dead element contributes exact identities to
    # every reduction (0.0 to the scatter-adds and the bool/float
    # maxes, inf to the min-reductions), and float identities commute:
    # 0.0 + x == x, max(0.0, u>=0) == u, min(inf, r) == r.  COO on CPU
    # only by default: the per-chunk host sync and device_put that
    # compaction needs are free there, while on an accelerator each is
    # a device round-trip (and a fresh XLA compile per new
    # element-bucket size).
    cmode = config["lmm/compact"]
    if cmode not in ("auto", "on", "off"):
        raise ValueError(f"Unknown lmm/compact {cmode!r} "
                         "(expected auto, on or off)")
    if cmode == "on" and ell is not None:
        global _WARNED_COMPACT_ELL
        if not _WARNED_COMPACT_ELL:
            _WARNED_COMPACT_ELL = True
            from ..utils import log as _log
            _log.get_category("lmm").warning(
                "lmm/compact:on has no effect on the ELL layout; set "
                "lmm/layout:coo to compact on this device")
    if (ell is None and platform != "cpu" and not chunk_given
            and len(arrays.e_var) >= 1 << 20):
        # Big COO systems on the accelerator: a round's device time
        # grows with the element count, so cap the per-dispatch round
        # count to keep one chunk's worst-case run time bounded.
        chunk = min(chunk, 32)
    compacting = (ell is None
                  and arrays.n_elem >= _COMPACT_MIN_ELEMS
                  and (cmode == "on"
                       or (cmode == "auto" and platform == "cpu")))
    if compacting and not chunk_given:
        # short chunks create the compaction points (the live element
        # count at 100k flows halves roughly every 13 local rounds and
        # far faster on small systems); global mode fixes ~one variable
        # per round, so halvings are ~n_v rounds apart and short chunks
        # would only add per-dispatch sync overhead.  An explicit
        # caller-chosen chunk is honored as-is.
        chunk = min(chunk, 4 if parallel_rounds else 64)

    eps_f = float(eps)
    # static specialization: systems with no active variable bound
    # (the common network/bench case) compile a round body with half
    # the gathers — decided HOST-side so it stays a compile-time flag
    has_bounds = bool(np.any((arrays.v_bound[:arrays.n_var] > 0)
                             & (arrays.v_penalty[:arrays.n_var] > 0)))
    has_fatpipe = bool(np.any(arrays.c_fatpipe[:arrays.n_cnst]))
    chain_mode = config["lmm/chain"]
    if chain_mode not in ("auto", "on", "off"):
        raise ValueError(f"Unknown lmm/chain {chain_mode!r} "
                         "(expected auto, on or off)")
    if (ell is not None and ell.vc_w is not None and parallel_rounds
            and not has_bounds and not unroll
            and len(ell.v_penalty) >= 2 * _CHAIN_MIN_V
            and (chain_mode == "on"
                 or (chain_mode == "auto" and platform != "cpu"))):
        res = _solve_ell_chain(ell, eps_f, device, has_fatpipe,
                               chunk if chunk_given
                               else _CHUNK_ROUNDS_ACCEL)
        if res is not None:
            return res
        # overflow/stall: fall through to the dense path below

    compactor = None
    if ell is not None:
        args = _device_args(
            "ell",
            [ell.cv_var, ell.cv_w, ell.cv_valid, ell.vc_cnst,
             ell.vc_valid, ell.c_bound, ell.c_fatpipe, ell.v_penalty,
             ell.v_bound, ell.vc_w], device)

        def run_chunk(carry):
            # the ELL bodies count neither their bound rounds nor their
            # elements: no counts, so the counters are not bumped and
            # read "not counted", not 0
            return (*_solve_ell_chunk(*args, carry, eps=eps_f,
                                      parallel_rounds=parallel_rounds,
                                      chunk=chunk, unroll=unroll,
                                      has_bounds=has_bounds,
                                      has_fatpipe=has_fatpipe), *[None] * 4)
    else:
        args = _device_args(
            "coo",
            [arrays.e_var, arrays.e_cnst, arrays.e_w, arrays.c_bound,
             arrays.c_fatpipe, arrays.v_penalty, arrays.v_bound], device)
        cur_nc, cur_nv = len(arrays.c_bound), len(arrays.v_penalty)
        if compacting:
            compactor = _Compactor(arrays, device)

        def run_chunk(carry):
            return _solve_kernel_chunk(
                *args, carry, eps=eps_f, n_c=cur_nc, n_v=cur_nv,
                parallel_rounds=parallel_rounds, chunk=chunk,
                unroll=unroll, has_bounds=has_bounds,
                has_fatpipe=has_fatpipe)

    carry = None
    prev_progress = None
    bound_rounds = live_elem_rounds = worked_elem_rounds = partitions = 0
    while True:
        with opstats.span("solve.chunk"):
            (values, remaining, usage, rounds, carry, n_bound, n_live,
             n_worked, n_parts) = run_chunk(carry)
            opstats.bump("dispatches")
            # ONE host sync per chunk: [rounds, light count, fixed
            # count, and on the COO path bound rounds, the live and the
            # indexed elements' pairs and the partitions] AND the
            # result vectors ride a single device->host transfer — a
            # converged solve pays exactly one round-trip.  Counts are
            # exact in f32 (< 2^24; the element sums come as two halves
            # that are).
            rdt = values.dtype
            n_vc, n_cc = values.shape[0], remaining.shape[0]
            head = [rounds, jnp.count_nonzero(carry[4]),
                    jnp.count_nonzero(carry[1])]
            if n_bound is not None:
                head += [n_bound, *n_live, *n_worked, n_parts]
            n_h = len(head)
            fetched = opstats.timed_fetch(jnp.concatenate([
                jnp.stack([h.astype(rdt) for h in head]),
                values, remaining.astype(rdt), usage.astype(rdt)]))
        rounds, n_light, n_fixed = (int(fetched[0]), int(fetched[1]),
                                    int(fetched[2]))
        if n_bound is not None:
            bound_rounds += int(fetched[3])
            live_elem_rounds += _live_elem_rounds(fetched[4:6])
            worked_elem_rounds += _live_elem_rounds(fetched[6:8])
            partitions += int(fetched[8])
        if n_light == 0:
            values = fetched[n_h:n_h + n_vc]
            remaining = fetched[n_h + n_vc:n_h + n_vc + n_cc]
            usage = fetched[n_h + n_vc + n_cc:n_h + n_vc + 2 * n_cc]
            break
        if rounds >= _MAX_ROUNDS:
            raise SolveError(
                f"LMM JAX solve did not converge within {_MAX_ROUNDS} "
                f"saturation rounds ({arrays.n_cnst} constraints, "
                f"{arrays.n_var} variables, {n_light} still active); "
                f"check maxmin/precision vs the system's magnitudes")
        progress = (n_light, n_fixed)
        if progress == prev_progress:
            raise SolveError(
                f"LMM JAX solve stalled after {rounds} rounds: "
                f"{n_light} active constraints and {n_fixed} fixed "
                f"variables unchanged over {chunk} rounds "
                f"({arrays.n_cnst} constraints, {arrays.n_var} variables); "
                f"the system does not converge at eps={eps} in "
                f"{arrays.e_w.dtype} precision")
        prev_progress = progress
        if compactor is not None:
            packed = compactor.try_compact(carry)
            if packed is not None:
                args, carry, cur_nv, cur_nc = packed
                # the repack drops the already-fixed rows, so the
                # fixed-count census restarts near zero — a progress
                # comparison across a compaction would false-positive
                # the stall detector (a stalled solve never compacts:
                # compaction requires the live set to halve)
                prev_progress = None
    opstats.bump("fixpoint_rounds", rounds)
    if n_bound is not None:
        opstats.bump("fixpoint_bound_rounds", bound_rounds)
        opstats.bump("fixpoint_live_elem_rounds", live_elem_rounds)
        opstats.bump("fixpoint_worked_elem_rounds", worked_elem_rounds)
        opstats.bump("fixpoint_partitions", partitions)
    merged = (compactor.merge(values, remaining, usage)
              if compactor is not None else None)
    if merged is not None:
        return merged[0], merged[1], merged[2], rounds
    # values/remaining/usage are host np slices of the converged
    # chunk's single fetch.
    return values, remaining, usage, rounds


def check_convergence(rounds: int, n_cnst, n_var) -> None:
    """Raise if a (non-chunked) fixpoint hit the round cap (used by the
    sharded paths, which run the loop to completion in one dispatch)."""
    if rounds >= _MAX_ROUNDS:
        raise SolveError(
            f"LMM JAX solve did not converge within {_MAX_ROUNDS} saturation "
            f"rounds ({n_cnst} constraints, {n_var} variables); "
            f"check maxmin/precision vs the system's magnitudes")


def solve_flattened(system: System, dtype, solve_flat,
                    allow_device: bool = False) -> None:
    """Shared backend wrapper: flatten host graph, solve, scatter back.

    Mirrors the side effects of System::lmm_solve (maxmin.cpp:487-500):
    values written to variables, modified-action collection for lazy model
    updates, constraint usage left consistent, modified flags cleared.
    ``solve_flat(arrays, eps) -> (values, remaining, usage)`` is the
    actual solver (device fixpoint or native C++).

    Full-update systems run through the incrementally-maintained
    ArrayView (ops.lmm_view): no per-solve graph walk at all — the
    arrays were kept in sync by the mutation hooks, so a solve is
    snapshot + device dispatch + scatter-back.

    Selective-update systems on a device backend (``allow_device``)
    are served by the warm solver (ops.lmm_warm): device-resident
    masters, per-slot delta uploads, and warm-started modified-
    component fixpoint restarts.  ``lmm/warm-start:off`` restores the
    legacy behavior below — re-flatten the modified subset and solve
    it cold each time.
    """
    eps = config["maxmin/precision"]

    if system.selective_update_active and allow_device:
        from . import lmm_warm
        if lmm_warm.solve_selective(system, dtype, eps):
            return

    if not system.selective_update_active:
        view = system.array_view
        if view is None:
            from .lmm_view import ArrayView
            view = ArrayView(system)
        arrays = view.snapshot(dtype)
        if arrays.n_cnst:
            values, remaining, usage = solve_flat(arrays, eps)
            vals = np.asarray(values).tolist()
            for slot, var in enumerate(view.slot_var):
                if var is not None:
                    var.value = vals[slot]
            rem = np.asarray(remaining).tolist()
            use = np.asarray(usage).tolist()
            for slot, cnst in enumerate(view.slot_cnst):
                if cnst is not None:
                    cnst.remaining = rem[slot]
                    cnst.usage = use[slot]
        system.modified = False
        return

    cnst_list = list(system.modified_constraint_set)

    # Reset + collect modified actions exactly like the init pass of the
    # list solver (maxmin.cpp:509-539).
    for cnst in cnst_list:
        for elem in cnst.enabled_element_set:
            elem.variable.value = 0.0
    if system.modified_actions is not None:
        # Unlike the reference (maxmin.cpp:523-525) zero-bound constraints'
        # actions are reported too, so the lazy model drops their stale
        # completion dates (park support, see Model lazy path).
        for cnst in cnst_list:
            for elem in cnst.enabled_element_set:
                if elem.consumption_weight > 0:
                    system.flag_action_modified(elem.variable.id)

    flat = flatten(cnst_list, dtype)
    if flat is not None:
        arrays, vars_in_order = flat
        values, remaining, usage = solve_flat(arrays, eps)
        for slot, var in enumerate(vars_in_order):
            var.value = float(values[slot])
        # Scatter back the kernel's end-state remaining/usage so constraint
        # introspection matches the list solver's post-solve state.
        for ci, cnst in enumerate(cnst_list):
            cnst.remaining = float(remaining[ci])
            cnst.usage = float(usage[ci])

    system.modified = False
    if system.selective_update_active:
        system.remove_all_modified_set()


#: solves completed by the exact host solver after the device kernel
#: failed (non-convergence, stall, or non-finite output); see solve_jax
_fallback_count = 0
_fallback_warned = False


def get_fallback_count() -> int:
    return _fallback_count


def reset_fallback_count() -> None:
    global _fallback_count
    _fallback_count = 0


def _solve_host_exact(system: System) -> None:
    """The graceful-degradation target: exact host solve of the same
    system (native C++ when available, Python list solver otherwise)."""
    from . import lmm_native
    if lmm_native.available():
        lmm_native.solve_native(system)
    else:
        system.solve_exact()


def solve_jax(system: System) -> None:
    """Backend entry: flatten host graph, solve on device, scatter back.

    Graceful degradation: when the device fixpoint fails to converge
    (round cap, stall) or returns non-finite rates — a
    :class:`SolveError` — the solve is redone by the exact host solver
    instead of aborting the whole simulation: a production run survives
    one numerically-degenerate system.  The hard raise is preserved
    behind ``--cfg=lmm/strict:1`` for convergence testing.  Nothing
    else is caught: a compile refusal, an out-of-memory or a device
    fault propagates whatever lmm/strict says."""
    global _fallback_count, _fallback_warned
    dtype = solve_dtype(config["lmm/dtype"], "lmm/dtype")

    def solve_flat(arrays, eps):
        values, remaining, usage, _ = solve_arrays(arrays, eps)
        if not np.all(np.isfinite(np.asarray(values))):
            raise SolveError(
                "LMM JAX solve returned non-finite rates "
                f"({arrays.n_cnst} constraints, {arrays.n_var} variables, "
                f"dtype {np.dtype(dtype).name})")
        return values, remaining, usage

    try:
        solve_flattened(system, dtype, solve_flat, allow_device=True)
    except SolveError as exc:
        if config["lmm/strict"]:
            raise
        # the host-exact fallback solves outside the warm solver, so
        # any carried device fixpoint state is stale from here on
        if system.warm_solver is not None:
            system.warm_solver.invalidate()
        _fallback_count += 1
        system.fallback_count = getattr(system, "fallback_count", 0) + 1
        # per-stage visibility (the global int cannot be attributed):
        # quarantine decisions and bench rows read this scoped counter
        opstats.bump("solver_fallbacks")
        if not _fallback_warned:
            _fallback_warned = True
            from ..utils import log as _log
            _log.get_category("lmm").warning(
                "JAX solve failed (%s); falling back to the exact host "
                "solver for this solve. Further fallbacks are silent "
                "(lmm/strict:1 restores the hard error)." % (exc,))
        _solve_host_exact(system)


def _count_live_vars(system: System) -> int:
    n = 0
    for var in system.variable_set:
        if var.sharing_penalty <= 0:
            break  # enabled vars are kept at the list head
        n += 1
    return n


def dispatching_solve(system: System) -> None:
    """'auto' backend: exact host solver for small live sets (native C++
    when available, Python list solver otherwise), JAX above the
    lmm/jax-threshold crossover (SURVEY.md hard part (e))."""
    if _count_live_vars(system) >= config["lmm/jax-threshold"]:
        solve_jax(system)
    else:
        from . import lmm_native
        if lmm_native.available():
            lmm_native.solve_native(system)
        else:
            system.solve_exact()


def install(system: System, backend: Optional[str] = None) -> System:
    """Attach the configured solver backend to a System."""
    backend = backend or config["lmm/backend"]
    if backend in ("jax", "auto") and config["lmm/dtype"] != "auto":
        # an explicit dtype the device cannot run is refused here, when
        # the backend is attached, not at the first large solve
        solve_dtype(config["lmm/dtype"], "lmm/dtype")
    if backend == "jax":
        system.solve_fn = solve_jax
    elif backend == "auto":
        system.solve_fn = dispatching_solve
    elif backend == "native":
        from . import lmm_native
        system.solve_fn = lmm_native.solve_native
    elif backend == "list":
        system.solve_fn = None
    else:
        raise ValueError(f"Unknown lmm/backend {backend!r} "
                         "(expected list, native, jax or auto)")
    return system
