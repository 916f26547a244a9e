"""Warm-started selective device solves (TPU-native incremental path).

The reference's ``network/maxmin-selective-update`` re-solves only the
constraints reachable from a mutation (src/kernel/lmm/maxmin.cpp) — its
soundness argument is that the max-min solution decomposes by connected
component: fixing a variable only ever changes the remaining/usage of
constraints in its own component, so untouched components keep their
exact previous solution.  This module carries that discipline onto the
device backend end to end:

* **Device-resident masters + delta uploads** — the flattened solver
  arrays (ops.lmm_view masters) stay resident on device; each solve
  ships one indexed scatter payload holding only the slots the System
  mutated since the last solve (``ArrayView.consume``), so upload cost
  scales with the number of touched slots, not field size: a
  mutating solve's ~7 MB-sized uploads become one small indexed one.

* **Warm-started modified-component fixpoint restarts** — the previous
  solve's ``(v_value, v_fixed, remaining, usage)`` ride the device
  between solves.  The next solve re-initializes ONLY the slots of the
  modified component (``modified_constraint_set``, already closed
  under shared enabled variables by ``System.update_modified_set``):
  modified constraints get ``remaining = bound`` and a recomputed
  ``usage0``, their variables are unfixed, and everything else is
  masked fixed/dark.  The fixpoint then iterates only the modified
  component, cutting rounds from O(level depth of the whole system) to
  O(level depth of the delta).  Because every per-round reduction in
  the fixpoint (segment sums/maxes/mins over a constraint's elements
  or a variable's constraints) is component-local, the values computed
  for the modified component are bit-identical to a cold full solve of
  the same arrays.

Carry invalidation is exact by construction (the hard part):

* slot renumbering or reallocation (``ArrayView._compact``, bucket
  growth) bumps ``layout_epoch`` -> full re-upload + cold restart;
* any dirty slot that is NOT invisible and NOT inside the modified
  component (a constraint-closure hole: sharing-policy flips, mixing
  in host-backend solves that consumed the modified set, positive->
  positive penalty writes) -> cold restart;
* a live element crossing the component boundary (modified variable
  with an element in an unmodified constraint) -> cold restart;
* dtype alternation keeps independent per-dtype masters/carries, each
  with its own dirty-index consumer, so f64 engine solves and f32
  accelerator solves can interleave without cross-poisoning;
* drain-fast-path retirements (``expected_frees``) skip the plan
  version bump but still mark dirty indices, so the masters see the
  zeroed weights and the closure check sees the retired slots.

Solves that cannot be warmed fall back to a cold full solve of the
same device-resident arrays — always available, always exact.

Runs that selected the ELL layout (``lmm/layout:ell``, or auto on an
accelerator) are served from device-resident ELL masters maintained
incrementally alongside the COO ones: the view's element slots are
append-only within a layout epoch (``on_expand`` always allocates at
``n_elem``; only ``_compact`` renumbers, and that bumps the epoch), so
a new element's lane is simply ``fill[row]++`` on both the cv and vc
tables — the same lane the stable-sort ``ell_from_arrays`` build would
assign, which keeps the row-reduction order (and therefore every
usage sum's rounding) bit-identical to a fresh conversion.  Dead lanes
(zeroed weights from freed variables) contribute exact identities to
the row reductions until the next epoch rebuild.  A row overflowing
its padded width forces a host rebuild of the tables (rare: widths are
pow2-bucketed).  Only when the COO->ELL conversion itself is refused
(width/fill caps — the same caps the plain solve path applies) does
the solve drop to the COO masters, counted in ``warm_ell_fallbacks``;
the plain path serves COO for those systems too, so the layouts stay
consistent.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..utils.config import config
from . import opstats
from .device import default_platform
from .lmm_jax import (_ELL_MAX_FILL, _ELL_MAX_WIDTH, _MAX_ROUNDS, SolveError,
                      _bucket, _default_chunk, _solve_ell_chunk,
                      _solve_kernel_chunk, use_local_rounds)

_FIELDS = ("e_var", "e_cnst", "e_w", "c_bound", "c_fatpipe",
           "v_penalty", "v_bound")
_CAST_FIELDS = ("e_w", "c_bound", "v_penalty", "v_bound")


def _warm_mode() -> str:
    mode = config["lmm/warm-start"]
    if mode not in ("auto", "on", "cold", "off"):
        raise ValueError(f"Unknown lmm/warm-start {mode!r} "
                         "(expected auto, on, cold or off)")
    return mode


def _delta_enabled() -> bool:
    mode = config["lmm/delta-upload"]
    if mode not in ("auto", "on", "off"):
        raise ValueError(f"Unknown lmm/delta-upload {mode!r} "
                         "(expected auto, on or off)")
    return mode != "off"


def _ell_selected() -> bool:
    """True when the run's layout choice resolves to ELL (explicit, or
    auto on an accelerator) — the layout the warm carry cannot serve."""
    layout = config["lmm/layout"]
    return layout == "ell" or (layout == "auto"
                               and default_platform() != "cpu")


@functools.partial(jax.jit, static_argnames=("layout",))
def _apply_deltas(payload, e_var, e_cnst, e_w, c_bound, c_fatpipe,
                  v_penalty, v_bound, layout: Tuple):
    """Apply one fused delta payload to the device masters.

    ``payload`` is a single f64 vector holding, per dirty field,
    ``n`` slot indices followed by ``n`` new values (int32 slots and
    bools are exact in f64); ``layout`` is the static
    ``(field_index, offset, n)`` table.  ONE host->device transfer
    per solve, then pure on-device scatters — ``arr.at[idx].set``
    with the padding slots repeating the first (index, value) pair,
    so duplicate writes all carry the same value and the scatter is
    deterministic."""
    masters = [e_var, e_cnst, e_w, c_bound, c_fatpipe, v_penalty, v_bound]
    for fi, off, n in layout:
        idx = payload[off:off + n].astype(jnp.int32)
        vals = payload[off + n:off + 2 * n].astype(masters[fi].dtype)
        masters[fi] = masters[fi].at[idx].set(vals)
    return tuple(masters)


@jax.jit
def _apply_deltas_ell(cv_var, cv_w, cv_valid, vc_cnst, vc_valid, vc_w,
                      cv_idx, cv_v, cv_wv, vc_idx, vc_c, vc_wv):
    """Scatter one element-delta batch into the ELL masters.  Indices
    are flattened (row * width + lane); the padding entries repeat the
    first (index, value) pair so duplicate writes agree and the scatter
    stays deterministic (the _apply_deltas discipline)."""
    shp_c, shp_v = cv_var.shape, vc_cnst.shape
    cv_var = cv_var.reshape(-1).at[cv_idx].set(cv_v).reshape(shp_c)
    cv_w = cv_w.reshape(-1).at[cv_idx].set(cv_wv).reshape(shp_c)
    cv_valid = cv_valid.reshape(-1).at[cv_idx].set(cv_wv > 0).reshape(shp_c)
    vc_cnst = vc_cnst.reshape(-1).at[vc_idx].set(vc_c).reshape(shp_v)
    vc_w = vc_w.reshape(-1).at[vc_idx].set(vc_wv).reshape(shp_v)
    vc_valid = vc_valid.reshape(-1).at[vc_idx].set(vc_wv > 0).reshape(shp_v)
    return cv_var, cv_w, cv_valid, vc_cnst, vc_valid, vc_w


@functools.partial(jax.jit, static_argnames=("eps",))
def _warm_init_ell(cv_var, cv_w, cv_valid, c_bound, c_fatpipe, v_penalty,
                   prev_value, prev_remaining, prev_usage, prev_cv_live,
                   mc_idx, eps: float):
    """ELL analog of `_warm_init`: cold-start expressions (mirroring
    `fixpoint_ell`'s None-carry init, row reductions included) for the
    modified component, previous solution masked fixed/dark elsewhere.
    The extra carry leg is `cv_live`: modified rows are re-derived from
    the warm v_fixed (identical to the cold expression there — every
    live element of a modified row belongs to a modified variable by
    the component-closure checks), untouched rows keep the previous
    converged mask."""
    dtype = cv_w.dtype
    n_c = c_bound.shape[0]
    n_v = v_penalty.shape[0]
    eps_t = jnp.asarray(eps, dtype)

    c_mod = jnp.zeros(n_c, bool).at[mc_idx].set(True)
    live = cv_valid & (cv_w > 0)
    v_mod = jnp.zeros(n_v, bool).at[cv_var].max(live & c_mod[:, None])
    has_live_elem = jnp.zeros(n_v, bool).at[cv_var].max(live)

    v_enabled = v_penalty > 0
    cv_evalid = cv_valid & jnp.take(v_enabled, cv_var)
    safe_pen = jnp.where(v_enabled, v_penalty, 1.0)
    cv_upen = jnp.where(cv_evalid, cv_w / jnp.take(safe_pen, cv_var), 0.0)
    usage_sum = cv_upen.sum(axis=1)
    usage_max = cv_upen.max(axis=1, initial=0.0)
    usage0 = jnp.where(c_fatpipe, usage_max, usage_sum)

    v_value0 = jnp.where(jnp.isfinite(v_penalty), v_penalty, 0.0) * 0.0
    keep_prev = ~v_mod & v_enabled & has_live_elem
    v_value = jnp.where(keep_prev, prev_value, v_value0)
    v_fixed = jnp.where(v_mod, v_penalty < 0, True)
    remaining = jnp.where(c_mod, c_bound, prev_remaining)
    usage = jnp.where(c_mod, usage0, prev_usage)
    light = c_mod & (c_bound > c_bound * eps_t) & (usage0 > 0)
    cv_live = jnp.where(c_mod[:, None],
                        cv_evalid & ~jnp.take(v_fixed, cv_var),
                        prev_cv_live)
    return (v_value, v_fixed, remaining, usage, light,
            jnp.array(0, jnp.int32), cv_live)


@functools.partial(jax.jit, static_argnames=("eps",))
def _warm_init(e_var, e_cnst, e_w, c_bound, c_fatpipe, v_penalty,
               prev_value, prev_remaining, prev_usage, mc_idx,
               eps: float):
    """Build the fixpoint carry for a modified-component restart.

    Modified slots get exactly the cold-start initialization (same
    expressions as ``fixpoint``'s None-carry init, so the component's
    round arithmetic is bit-identical to a cold full solve); untouched
    slots keep the previous solution, masked fixed/dark so the loop
    never revisits them."""
    dtype = e_w.dtype
    n_c = c_bound.shape[0]
    n_v = v_penalty.shape[0]
    eps_t = jnp.asarray(eps, dtype)

    c_mod = jnp.zeros(n_c, bool).at[mc_idx].set(True)
    e_live = e_w > 0
    v_mod = jnp.zeros(n_v, bool).at[e_var].max(
        e_live & jnp.take(c_mod, e_cnst))
    has_live_elem = jnp.zeros(n_v, bool).at[e_var].max(e_live)

    v_enabled = v_penalty > 0
    e_valid = e_live & jnp.take(v_enabled, e_var)
    safe_pen = jnp.where(v_enabled, v_penalty, 1.0)
    e_upen = jnp.where(e_valid, e_w / jnp.take(safe_pen, e_var), 0.0)
    usage_sum = jnp.zeros(n_c, dtype).at[e_cnst].add(e_upen)
    usage_max = jnp.zeros(n_c, dtype).at[e_cnst].max(e_upen)
    usage0 = jnp.where(c_fatpipe, usage_max, usage_sum)

    v_value0 = jnp.where(jnp.isfinite(v_penalty), v_penalty, 0.0) * 0.0
    # untouched slots keep the previous value only where one exists to
    # keep (enabled with a live element); recycled/ghost slots get the
    # cold init so the returned vector matches a cold full solve
    keep_prev = ~v_mod & v_enabled & has_live_elem
    v_value = jnp.where(keep_prev, prev_value, v_value0)
    v_fixed = jnp.where(v_mod, v_penalty < 0, True)
    remaining = jnp.where(c_mod, c_bound, prev_remaining)
    usage = jnp.where(c_mod, usage0, prev_usage)
    light = c_mod & (c_bound > c_bound * eps_t) & (usage0 > 0)
    return (v_value, v_fixed, remaining, usage, light,
            jnp.array(0, jnp.int32))


class _DtypeState:
    """Per-solve-dtype device residency: masters, carry, validity tags."""

    __slots__ = ("masters", "shapes", "epoch", "carry", "meta",
                 "ell", "ell_shape", "ell_n", "cv_lane", "vc_lane",
                 "cv_fill", "vc_fill")

    def __init__(self):
        self.masters = None        # tuple of device arrays, _FIELDS order
        self.shapes = None         # (E, C, V) padded lengths
        self.epoch = -1            # view.layout_epoch the masters track
        self.carry = None          # converged fixpoint state, or None
        self.meta = None           # (eps, parallel, layout, shape) of it
        # ELL residency (lmm/layout:ell runs): the six 2D tables plus
        # the host lane maps that let element deltas land as scatters
        self.ell = None            # (cv_var, cv_w, cv_valid,
        #                             vc_cnst, vc_valid, vc_w) on device
        self.ell_shape = None      # (C, Wc, V, Wv)
        self.ell_n = 0             # element slots placed so far
        self.cv_lane = None        # per-element lane in its cv row
        self.vc_lane = None        # per-element lane in its vc row
        self.cv_fill = None        # per-constraint occupied lane count
        self.vc_fill = None        # per-variable occupied lane count


class WarmSolver:
    """Device-resident incremental solver attached to one System."""

    def __init__(self, system):
        self.system = system
        system.warm_solver = self
        self._states: Dict[np.dtype, _DtypeState] = {}
        # observability (read by tests, tools and bench)
        self.solves = 0
        self.warm_solves = 0
        self.cold_solves = 0
        self.warm_ell_fallbacks = 0
        self.carry_invalidations = 0
        self.last_rounds = 0
        self.last_mode = ""
        self.last_layout = ""
        self.last_upload_bytes = 0
        self.last_dirty_slots = 0

    # -- carry management --------------------------------------------------

    def invalidate(self) -> None:
        """Drop every carried fixpoint state (masters stay resident).
        Called when a solve happened outside this solver (host-exact
        fallback) so stale values can never seed a warm restart."""
        for dt in sorted(self._states, key=str):
            st = self._states[dt]
            if st.carry is not None:
                self.carry_invalidations += 1
            st.carry = None

    # -- upload ------------------------------------------------------------

    def _cast(self, view, field: str, key):
        src = getattr(view, field)
        return src.astype(key) if field in _CAST_FIELDS else src

    def _upload_full(self, st: _DtypeState, view, key) -> None:
        arrays = [self._cast(view, f, key) for f in _FIELDS]
        nbytes = sum(a.nbytes for a in arrays)
        st.masters = tuple(jax.device_put(a) for a in arrays)
        st.shapes = (len(view.e_var), len(view.c_bound),
                     len(view.v_penalty))
        st.epoch = view.layout_epoch
        st.ell = None              # element slots may have renumbered
        opstats.bump("uploaded_bytes_full", nbytes)
        self.last_upload_bytes += nbytes

    def _upload_delta(self, st: _DtypeState, view, key, dirty) -> int:
        """Apply per-index mutations to the device masters; returns the
        number of dirty slots shipped.  Fields whose index identity was
        lost (dirty is True) are re-shipped whole and poison the carry
        (handled by the caller via the returned sentinel -1)."""
        true_fields = [f for f in _FIELDS if dirty[f] is True]
        if true_fields:
            masters = list(st.masters)
            for f in true_fields:
                arr = self._cast(view, f, key)
                masters[_FIELDS.index(f)] = jax.device_put(arr)
                opstats.bump("uploaded_bytes_full", arr.nbytes)
                self.last_upload_bytes += arr.nbytes
            st.masters = tuple(masters)

        idx_fields = [(f, sorted(dirty[f])) for f in _FIELDS
                      if dirty[f] is not True and dirty[f]]
        n_slots = sum(len(ix) for _, ix in idx_fields)
        if idx_fields:
            if _delta_enabled():
                layout = []
                chunks = []
                off = 0
                for f, ix in idx_fields:
                    n = _bucket(len(ix), floor=8)
                    idx = np.empty(n, np.float64)
                    vals = np.empty(n, np.float64)
                    idx[:len(ix)] = ix
                    idx[len(ix):] = ix[0]
                    src = getattr(view, f)
                    vals[:len(ix)] = src[ix]
                    vals[len(ix):] = src[ix[0]]
                    layout.append((_FIELDS.index(f), off, n))
                    chunks.append(idx)
                    chunks.append(vals)
                    off += 2 * n
                payload = np.concatenate(chunks)
                st.masters = _apply_deltas(jax.device_put(payload),
                                           *st.masters,
                                           layout=tuple(layout))
                opstats.bump("uploaded_bytes_delta", payload.nbytes)
                self.last_upload_bytes += payload.nbytes
            else:
                # whole-field refresh of only the fields that changed
                # (the copy-on-write snapshot discipline, kept as the
                # escape hatch and as the bench's full-upload baseline)
                masters = list(st.masters)
                for f, _ in idx_fields:
                    arr = self._cast(view, f, key)
                    masters[_FIELDS.index(f)] = jax.device_put(arr)
                    opstats.bump("uploaded_bytes_full", arr.nbytes)
                    self.last_upload_bytes += arr.nbytes
                st.masters = tuple(masters)
        if true_fields:
            return -1
        return n_slots

    # -- ELL residency -----------------------------------------------------

    def _build_ell(self, st: _DtypeState, view, key) -> bool:
        """Host rebuild of the ELL masters + lane maps from the view
        (same widths, caps and stable element-index lane order as
        `ell_from_arrays`, so the row-reduction rounding matches the
        plain solve path's conversion).  Returns False when the caps
        refuse the conversion — COO serves those systems everywhere."""
        E = view.n_elem
        e_var = view.e_var[:E].astype(np.int64)
        e_cnst = view.e_cnst[:E].astype(np.int64)
        e_w = view.e_w[:E]
        C, V = len(view.c_bound), len(view.v_penalty)
        c_deg = np.bincount(e_cnst, minlength=C)
        v_deg = np.bincount(e_var, minlength=V)
        wc = int(c_deg.max()) if E else 1
        wv = int(v_deg.max()) if E else 1
        if wc > _ELL_MAX_WIDTH or wv > _ELL_MAX_WIDTH:
            st.ell = None
            return False
        Wc = _bucket(max(wc, 1), floor=4)
        Wv = _bucket(max(wv, 1), floor=4)
        if E and (C * Wc + V * Wv) > _ELL_MAX_FILL * 2 * E:
            st.ell = None
            return False

        slots_total = len(view.e_var)
        cv_lane = np.full(slots_total, -1, np.int32)
        vc_lane = np.full(slots_total, -1, np.int32)
        cv_var = np.zeros((C, Wc), np.int32)
        cv_w = np.zeros((C, Wc), key)
        cv_valid = np.zeros((C, Wc), bool)
        vc_cnst = np.zeros((V, Wv), np.int32)
        vc_valid = np.zeros((V, Wv), bool)
        vc_w = np.zeros((V, Wv), key)
        ew = e_w.astype(key)

        def row_slots(keys, n_rows):
            order = np.argsort(keys, kind="stable")
            sorted_keys = keys[order]
            group_start = np.searchsorted(sorted_keys, np.arange(n_rows))
            lanes = np.arange(E, dtype=np.int64) - group_start[sorted_keys]
            return order, sorted_keys, lanes

        if E:
            order, rows, lanes = row_slots(e_cnst, C)
            cv_lane[order] = lanes
            cv_var[rows, lanes] = e_var[order]
            cv_w[rows, lanes] = ew[order]
            cv_valid[rows, lanes] = ew[order] > 0
            order, rows, lanes = row_slots(e_var, V)
            vc_lane[order] = lanes
            vc_cnst[rows, lanes] = e_cnst[order]
            vc_w[rows, lanes] = ew[order]
            vc_valid[rows, lanes] = ew[order] > 0

        arrays = (cv_var, cv_w, cv_valid, vc_cnst, vc_valid, vc_w)
        nbytes = sum(a.nbytes for a in arrays)
        st.ell = tuple(jax.device_put(a) for a in arrays)
        st.ell_shape = (C, Wc, V, Wv)
        st.ell_n = E
        st.cv_lane, st.vc_lane = cv_lane, vc_lane
        st.cv_fill = c_deg.astype(np.int32)
        st.vc_fill = v_deg.astype(np.int32)
        opstats.bump("uploaded_bytes_full", nbytes)
        self.last_upload_bytes += nbytes
        return True

    def _sync_ell(self, st: _DtypeState, view, key, dirty) -> bool:
        """Scatter the element dirt into the resident ELL tables.  New
        elements (append-only within an epoch) take lane ``fill[row]++``
        on each side — the lane a fresh stable-order build would assign.
        Returns False when a row overflows its padded width (rebuild)."""
        e_dirty = sorted(dirty["e_var"] | dirty["e_cnst"] | dirty["e_w"])
        if not e_dirty:
            return True
        C, Wc, V, Wv = st.ell_shape
        if (len(view.c_bound) != C or len(view.v_penalty) != V
                or len(view.e_var) != len(st.cv_lane)):
            return False           # row/slot table growth: rebuild
        cv_idx: list = []
        cv_v: list = []
        cv_wv: list = []
        vc_idx: list = []
        vc_c: list = []
        vc_wv: list = []
        for i in e_dirty:
            v = int(view.e_var[i])
            c = int(view.e_cnst[i])
            w = float(view.e_w[i])
            if i >= st.ell_n:
                lane_c = int(st.cv_fill[c])
                lane_v = int(st.vc_fill[v])
                if lane_c >= Wc or lane_v >= Wv:
                    return False
                st.cv_fill[c] = lane_c + 1
                st.vc_fill[v] = lane_v + 1
                st.cv_lane[i] = lane_c
                st.vc_lane[i] = lane_v
            else:
                lane_c = int(st.cv_lane[i])
                lane_v = int(st.vc_lane[i])
                if lane_c < 0 or lane_v < 0:
                    return False
            cv_idx.append(c * Wc + lane_c)
            cv_v.append(v)
            cv_wv.append(w)
            vc_idx.append(v * Wv + lane_v)
            vc_c.append(c)
            vc_wv.append(w)
        st.ell_n = max(st.ell_n, e_dirty[-1] + 1)

        n = _bucket(len(cv_idx), floor=8)
        pads = []
        for src, dt in ((cv_idx, np.int32), (cv_v, np.int32),
                        (cv_wv, key), (vc_idx, np.int32),
                        (vc_c, np.int32), (vc_wv, key)):
            a = np.empty(n, dt)
            a[:len(src)] = src
            a[len(src):] = src[0]
            pads.append(a)
        st.ell = _apply_deltas_ell(*st.ell, *pads)
        nbytes = sum(a.nbytes for a in pads)
        opstats.bump("uploaded_bytes_delta", nbytes)
        self.last_upload_bytes += nbytes
        return True

    def _ensure_ell(self, st: _DtypeState, view, key, dirty) -> bool:
        """Bring the ELL masters up to date with the view; returns True
        when the solve can be served in the ELL layout."""
        if st.ell is not None and dirty is not None \
                and not any(dirty[f] is True
                            for f in ("e_var", "e_cnst", "e_w")):
            if self._sync_ell(st, view, key, dirty):
                return True
        # missing, stale or overflowed: rebuild from the view (the
        # carry's cv_live leg is lane-addressed, so a rebuild means a
        # cold restart — enforced via the meta shape tag)
        return self._build_ell(st, view, key)

    # -- carry validity ----------------------------------------------------

    def _delta_in_component(self, view, dirty, c_mod, v_mod,
                            has_live_elem, has_live_c) -> bool:
        """Every slot mutated since the carry must be either inside the
        modified component or invisible to the solve (zero weight, no
        live element) — otherwise the carried values of some untouched
        slot are stale and only a cold restart is exact."""
        e_dirty = dirty["e_var"] | dirty["e_cnst"] | dirty["e_w"]
        if e_dirty:
            ei = np.fromiter(e_dirty, np.int64, len(e_dirty))
            if not np.all(c_mod[view.e_cnst[ei]] | (view.e_w[ei] == 0.0)):
                return False
        v_dirty = dirty["v_penalty"] | dirty["v_bound"]
        if v_dirty:
            vi = np.fromiter(v_dirty, np.int64, len(v_dirty))
            visible = (view.v_penalty[vi] > 0) & has_live_elem[vi]
            if not np.all(v_mod[vi] | ~visible):
                return False
        c_dirty = dirty["c_bound"] | dirty["c_fatpipe"]
        if c_dirty:
            ci = np.fromiter(c_dirty, np.int64, len(c_dirty))
            if not np.all(c_mod[ci] | ~has_live_c[ci]):
                return False
        return True

    # -- solve -------------------------------------------------------------

    def solve(self, view, cnst_list, dtype, eps: float, warm: bool):
        """Solve the System with the given modified constraints;
        returns host (values, remaining, usage) at view slot numbering.
        Raises RuntimeError on non-convergence/stall/non-finite rates
        (the caller degrades to the exact host solver)."""
        key = np.dtype(dtype)
        view.maybe_compact()
        st = self._states.get(key)
        if st is None:
            st = self._states[key] = _DtypeState()
        dirty = view.consume(f"warm:{key}")
        shapes = (len(view.e_var), len(view.c_bound), len(view.v_penalty))

        self.last_upload_bytes = 0
        self.last_dirty_slots = 0
        full = (dirty is None or st.masters is None
                or st.epoch != view.layout_epoch or st.shapes != shapes)
        if full:
            self._upload_full(st, view, key)
            st.carry = None
        else:
            n_slots = self._upload_delta(st, view, key, dirty)
            if n_slots < 0:
                st.carry = None
            else:
                self.last_dirty_slots = n_slots

        # ELL runs are served from the resident ELL masters (lane maps
        # keep them delta-maintained alongside the COO ones).  Only a
        # conversion the width/fill caps refuse drops to COO — the
        # plain solve path serves COO for those systems too, so the
        # layout stays what the run would get anywhere; the residual
        # gap is counted (opstats `warm_ell_fallbacks`).
        use_ell = False
        if _ell_selected():
            use_ell = self._ensure_ell(st, view, key,
                                       None if full else dirty)
            if not use_ell:
                self.warm_ell_fallbacks += 1
                opstats.bump("warm_ell_fallbacks")
        self.last_layout = "ell" if use_ell else "coo"

        eps_f = float(eps)
        parallel = use_local_rounds()
        # the carry is layout-addressed (the ELL leg's cv_live lives at
        # (row, lane)), so a layout or table-shape flip cold-restarts
        meta = (eps_f, parallel, "ell" if use_ell else "coo",
                st.ell_shape if use_ell else None)
        mc = np.fromiter((c._view_slot for c in cnst_list), np.int64,
                         len(cnst_list))

        carry0 = None
        if warm and st.carry is not None and st.meta == meta:
            c_mod = np.zeros(shapes[1], bool)
            c_mod[mc] = True
            live = view.e_w > 0
            en = view.v_penalty > 0
            v_mod = np.zeros(shapes[2], bool)
            v_mod[view.e_var[live & c_mod[view.e_cnst]]] = True
            has_live_elem = np.zeros(shapes[2], bool)
            has_live_elem[view.e_var[live]] = True
            has_live_c = np.zeros(shapes[1], bool)
            has_live_c[view.e_cnst[live & en[view.e_var]]] = True
            # component-closure boundary: a live enabled variable of
            # the modified component must not touch any unmodified
            # constraint, or a cold solve could fix it at that
            # constraint's level while the warm solve cannot
            boundary_ok = not np.any(live & en[view.e_var]
                                     & v_mod[view.e_var]
                                     & ~c_mod[view.e_cnst])
            if boundary_ok and self._delta_in_component(
                    view, dirty, c_mod, v_mod, has_live_elem, has_live_c):
                n_mc = _bucket(len(mc), floor=8)
                mc_pad = np.empty(n_mc, np.int32)
                mc_pad[:len(mc)] = mc
                mc_pad[len(mc):] = mc[0]
                mc_dev = jax.device_put(mc_pad)
                opstats.bump("uploaded_bytes_delta", mc_pad.nbytes)
                self.last_upload_bytes += mc_pad.nbytes
                prev = st.carry
                if use_ell:
                    carry0 = _warm_init_ell(
                        st.ell[0], st.ell[1], st.ell[2],
                        st.masters[3], st.masters[4], st.masters[5],
                        prev[0], prev[2], prev[3], prev[6],
                        mc_dev, eps=eps_f)
                else:
                    carry0 = _warm_init(*st.masters[:6], prev[0],
                                        prev[2], prev[3], mc_dev,
                                        eps=eps_f)

        st.carry = None   # poisoned until this solve converges
        values, remaining, usage, rounds, out = self._run_chunks(
            st, carry0, eps_f, parallel, shapes, view, use_ell)
        st.carry = out
        st.meta = meta

        self.solves += 1
        self.last_rounds = rounds
        self.last_mode = "warm" if carry0 is not None else "cold"
        if carry0 is not None:
            self.warm_solves += 1
            opstats.bump("warm_solves")
            # a warm restart whose entire delta is constraint-bound
            # flips is the fault-injection signature (link capacities
            # changed, topology didn't) — counted separately so fault
            # sweeps can see their re-solves ride the warm path
            if dirty is not None and all(
                    f == "c_bound" or not dirty[f]
                    for f in sorted(dirty)) \
                    and dirty.get("c_bound"):
                opstats.bump("warm_bound_restarts")
        else:
            self.cold_solves += 1
            opstats.bump("cold_solves")
        opstats.bump("solves")
        opstats.bump("fixpoint_rounds", rounds)
        return values, remaining, usage

    def _run_chunks(self, st: _DtypeState, carry, eps_f: float,
                    parallel: bool, shapes, view, use_ell: bool = False):
        """Bounded-round dispatch loop with host convergence checks
        between chunks; one device->host transfer per chunk (the
        solve_arrays discipline, minus host-side compaction, which
        would detach the carry from the resident masters)."""
        E, n_c, n_v = shapes
        chunk = _default_chunk()
        if default_platform() != "cpu" and E >= 1 << 20:
            chunk = min(chunk, 32)
        has_bounds = bool(np.any((view.v_bound > 0)
                                 & (view.v_penalty > 0)))
        has_fatpipe = bool(view.c_fatpipe.any())

        prev_progress = None
        while True:
            if use_ell:
                values, remaining, usage, rounds, carry = _solve_ell_chunk(
                    st.ell[0], st.ell[1], st.ell[2], st.ell[3],
                    st.ell[4], st.masters[3], st.masters[4],
                    st.masters[5], st.masters[6], st.ell[5], carry,
                    eps=eps_f, parallel_rounds=parallel, chunk=chunk,
                    unroll=False, has_bounds=has_bounds,
                    has_fatpipe=has_fatpipe)
            else:
                values, remaining, usage, rounds, carry = \
                    _solve_kernel_chunk(
                        *st.masters, carry, eps=eps_f, n_c=n_c, n_v=n_v,
                        parallel_rounds=parallel, chunk=chunk,
                        unroll=False, has_bounds=has_bounds,
                        has_fatpipe=has_fatpipe)[:5]
            opstats.bump("dispatches")
            rdt = values.dtype
            fetched = np.asarray(jnp.concatenate([
                jnp.stack([rounds.astype(rdt),
                           jnp.count_nonzero(carry[4]).astype(rdt),
                           jnp.count_nonzero(carry[1]).astype(rdt)]),
                values, remaining.astype(rdt), usage.astype(rdt)]))
            rounds, n_light, n_fixed = (int(fetched[0]), int(fetched[1]),
                                        int(fetched[2]))
            if n_light == 0:
                values = fetched[3:3 + n_v]
                remaining = fetched[3 + n_v:3 + n_v + n_c]
                usage = fetched[3 + n_v + n_c:3 + n_v + 2 * n_c]
                break
            if rounds >= _MAX_ROUNDS:
                raise SolveError(
                    f"LMM warm solve did not converge within "
                    f"{_MAX_ROUNDS} saturation rounds ({n_c} constraint "
                    f"slots, {n_v} variable slots, {n_light} still "
                    f"active); check maxmin/precision vs the system's "
                    f"magnitudes")
            progress = (n_light, n_fixed)
            if progress == prev_progress:
                raise SolveError(
                    f"LMM warm solve stalled after {rounds} rounds: "
                    f"{n_light} active constraints and {n_fixed} fixed "
                    f"variables unchanged over {chunk} rounds; the "
                    f"system does not converge at eps={eps_f} in "
                    f"{np.dtype(fetched.dtype).name} precision")
            prev_progress = progress
        if not np.all(np.isfinite(values)):
            raise SolveError(
                "LMM warm solve returned non-finite rates "
                f"({n_c} constraint slots, {n_v} variable slots)")
        return values, remaining, usage, rounds, carry


def solve_selective(system, dtype, eps: float) -> bool:
    """Device entry for selective-update systems: serve the solve from
    the warm solver (device-resident masters + modified-component
    restart).  Returns False when ``lmm/warm-start:off`` asks for the
    legacy re-flatten path instead.

    Host side-effects mirror the list solver's selective init pass
    (maxmin.cpp:509-539) exactly like the legacy path: values of the
    modified constraints' enabled variables are reset, their actions
    flagged modified for lazy model updates, and only the modified
    constraints' variables/remaining/usage are written back — the
    reference's selective-update contract."""
    mode = _warm_mode()
    if mode == "off":
        return False
    view = system.array_view
    if view is None:
        from .lmm_view import ArrayView
        view = ArrayView(system)
    solver = system.warm_solver
    if solver is None:
        solver = WarmSolver(system)

    cnst_list = list(system.modified_constraint_set)
    for cnst in cnst_list:
        for elem in cnst.enabled_element_set:
            elem.variable.value = 0.0
    if system.modified_actions is not None:
        # zero-bound constraints' actions are reported too, matching
        # the legacy paths (park support, see Model lazy path)
        for cnst in cnst_list:
            for elem in cnst.enabled_element_set:
                if elem.consumption_weight > 0:
                    system.flag_action_modified(elem.variable.id)

    if cnst_list:
        values, remaining, usage = solver.solve(
            view, cnst_list, dtype, eps, warm=mode in ("auto", "on"))
        for cnst in cnst_list:
            ci = cnst._view_slot
            cnst.remaining = float(remaining[ci])
            cnst.usage = float(usage[ci])
            for elem in cnst.enabled_element_set:
                elem.variable.value = \
                    float(values[elem.variable._view_slot])

    system.modified = False
    system.remove_all_modified_set()
    return True
