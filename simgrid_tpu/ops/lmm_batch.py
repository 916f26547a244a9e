"""Batched multi-replica scenario executor: vmapped solve+drain across
a fleet of independent simulations in ONE device program.

The paper's hot spot — the max-min fixpoint — is already fast for one
simulation (superstepped drains, warm-started selective solves),
but the north star is serving *fleets* of scenarios: Monte Carlo fault
campaigns, parameter sweeps, per-user what-ifs.  Run solo, each replica
pays its own dispatches and uploads, a per-transfer cost that does not
shrink with the payload — exactly the shape batched inference serving
amortizes (cf. ASTRA-sim
3.0 and the TPU fluid-flow framework in PAPERS.md, both of which get
their throughput from batching many independent problem instances into
one accelerator program).

This module ``vmap``s the existing kernel *programs* (the raw functions
behind ops.lmm_drain's solo jits and ops.lmm_jax's chunk kernels) over
a leading replica axis:

* **one shared platform flattening** — the COO structure (e_var,
  e_cnst) and, by default, the element weights are uploaded ONCE for
  the whole fleet; only per-replica state (bounds, remaining,
  penalties, thresholds) carries the batch axis;
* **compact scenario payloads** — per-replica scenarios are shipped as
  small override records (bandwidth/size scale factors plus sparse
  per-link and per-flow deltas) and *materialized on device*, so the
  per-replica upload cost is O(overrides), not O(system);
* **lockstep supersteps with an alive mask** — every dispatch runs up
  to K advances for every live replica; finished (or diverged)
  replicas go dark (their lane's while_loop cond is forced false, so
  the batching rule freezes their state) instead of forcing ragged
  shapes;
* **per-replica completion rings, one fetch** — each superstep's
  [B, ring] event log comes back in a single device->host transfer and
  is demultiplexed into per-replica event streams.

Determinism contract: each replica's event order AND clocks are
bit-identical to the same scenario drained solo by ops.lmm_drain's
DrainSim — the vmapped lane executes the exact same program, per-lane
reductions keep the solo element order, and per-replica clocks are
accumulated on the host in f64 exactly like the solo path
(``tools/check_determinism.py --runtime-batch`` asserts this against a
batch of 64 mixed fault/sweep scenarios).

Pod-scale sharding: ``mesh=M`` shards the REPLICA axis of the same
vmapped programs across a device mesh with ``NamedSharding(mesh,
PartitionSpec("batch"))`` — per-replica state ([B, ·] bounds, flow
state, thresholds, alive mask, payloads, completion rings) is split
into per-device blocks while the shared platform flattening (COO
structure, base arrays) is replicated.  Compact scenario payloads are
device_put under the batch sharding, so every payload byte lands on
exactly ONE device and host->device traffic stays flat as B grows with
the mesh; each superstep's completion rings come back as one fetch PER
SHARD (``demux_fetches``) and are reassembled in replica order before
the host demux, so the committed event stream is independent of the
mesh shape.  The per-lane program is untouched by partitioning (no
cross-lane math), so a sharded fleet is bit-identical to the
single-device vmapped fleet AND to solo runs
(``tools/check_determinism.py --runtime-shard``).  On CPU, validate
with ``XLA_FLAGS=--xla_force_host_platform_device_count=M``.

When B is not divisible by the mesh size the fleet is padded with
DEAD lanes (neutral overrides, alive=False from birth): the vmap
batching rule freezes them at k=0, they are excluded from the demux,
and a runtime guard asserts they produce zero completion events.
"""

from __future__ import annotations

import functools
import time
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import opstats
from .device import default_platform, solve_dtype
from .lmm_jax import (_MAX_ROUNDS, SolveError, _solve_kernel_chunk_batched,
                      _solve_kernel_chunk_batched_fresh)
from .lmm_drain import (_FLAG_BUDGET, _FLAG_OK, _FLAG_STALLED, _STATS_HEAD,
                        _ZERO_BITS, _check_collective_start, _pos_group,
                        _superstep_program, _to2d)


#: the mesh axis name the replica dimension shards over
BATCH_AXIS = "batch"


class AdmissionError(RuntimeError):
    """A lane admission the fleet cannot honor within the capacity
    fixed at fleet birth: the lane is alive or out of range, the
    overrides carry ``elem_w`` entries but the fleet shares one weight
    table, or the fault tape is wider than the fleet's reserved tape
    slots.  The serving layer catches this and either defers the query
    or retires the fleet."""


class LaneFault:
    """Why one lane was QUARANTINED — killed with a recorded cause
    while the rest of the fleet kept draining.  Attached to the lane's
    :class:`ReplicaState` (and, through the serving layer, to the
    query's Ticket) so a poisoned scenario is diagnosable instead of
    silently missing.  Causes:

    * ``nan_solve``        — the superstep returned a NaN clock
                             advance (degenerate capacities/overrides);
                             the lane's ring events for that dispatch
                             are garbage and are dropped
    * ``stall``            — no flow holds bandwidth (dt not finite)
    * ``non_convergence``  — the budget rescue still could not finish
                             one advance
    * ``ring_overflow``    — the completion ring reported more events
                             than it has slots (defensive; would
                             corrupt the demux)
    * ``admission_storm``  — the serving layer gave up admitting the
                             scenario after repeated fleet generations
    * ``watchdog``         — device dispatches exhausted the retry
                             policy; the query fell back to the solo
                             host path

    Each quarantine bumps the matching ``lane_quarantined_<cause>``
    opstats counter."""

    __slots__ = ("cause", "detail", "lane", "superstep", "t")

    def __init__(self, cause: str, detail: str, lane: int,
                 superstep: int = 0, t: float = 0.0):
        self.cause = str(cause)
        self.detail = str(detail)
        self.lane = int(lane)
        self.superstep = int(superstep)
        self.t = float(t)

    def to_dict(self) -> Dict:
        return {"cause": self.cause, "detail": self.detail,
                "lane": self.lane, "superstep": self.superstep,
                "t": self.t}

    @classmethod
    def from_dict(cls, d: Dict) -> "LaneFault":
        return cls(d["cause"], d["detail"], d["lane"],
                   superstep=d.get("superstep", 0), t=d.get("t", 0.0))

    def __repr__(self) -> str:
        return (f"LaneFault(cause={self.cause!r}, lane={self.lane}, "
                f"t={self.t!r}, detail={self.detail!r})")


class DispatchExhausted(RuntimeError):
    """A device dispatch kept failing after every watchdog retry; the
    caller (serving layer) should fall back to the solo host path for
    the affected lanes instead of poisoning the whole campaign."""


class DispatchWatchdog:
    """Wall-clock guard around fleet device dispatches: bounded
    retries with seeded exponential backoff (riding the existing
    :class:`~simgrid_tpu.s4u.activity.RetryPolicy` shape) around every
    dispatch/fetch, plus a post-hoc slow-dispatch threshold.

    Retrying a fleet dispatch is SAFE: issues and fetches are pure
    functions of the committed device state (nothing commits until the
    host collect), so a re-run after a transient runtime failure is
    bit-identical.  Only ``RuntimeError`` is retried (what the JAX
    runtime raises), and only around EXECUTION: the caller compiles
    outside the guard (``BatchDrainSim._call_plan``), so a program the
    device refuses is raised as it is, never retried into
    :class:`DispatchExhausted` and answered by the solo path.  A
    dispatch that still fails after
    ``policy.max_attempts`` raises :class:`DispatchExhausted`.  A
    dispatch that *succeeds* but took longer than ``timeout_s`` cannot
    be aborted mid-flight (jax calls are synchronous) — it is counted
    in ``watchdog_slow_dispatches`` so operators see the device
    degrading before it dies.

    Backoff delays use the monotonic-safe ``time.sleep`` only; the
    jitter is the RetryPolicy's SEEDED stream, so retry timing never
    introduces wall-clock entropy into the audited packages."""

    def __init__(self, policy=None, timeout_s: float = float("inf")):
        if policy is None:
            from ..s4u.activity import RetryPolicy
            policy = RetryPolicy(max_attempts=3, base_delay=0.05,
                                 multiplier=4.0, max_delay=2.0)
        self.policy = policy
        self.timeout_s = float(timeout_s)
        self.retries = 0
        self.slow_dispatches = 0
        self.exhausted = 0

    def guard(self, fn, what: str = "dispatch"):
        attempt = 1
        while True:
            t0 = time.perf_counter()
            try:
                out = fn()
            except RuntimeError as exc:
                if attempt >= int(self.policy.max_attempts):
                    self.exhausted += 1
                    opstats.bump("watchdog_exhausted")
                    raise DispatchExhausted(
                        f"fleet {what} failed {attempt} time(s), "
                        f"retry policy exhausted: {exc}") from exc
                self.retries += 1
                opstats.bump("watchdog_retries")
                time.sleep(float(self.policy.backoff(attempt)))
                attempt += 1
                continue
            if time.perf_counter() - t0 > self.timeout_s:
                self.slow_dispatches += 1
                opstats.bump("watchdog_slow_dispatches")
            return out

    def timed(self, fn, what: str = "fetch"):
        """Wall-clock accounting WITHOUT retries — for the ring fetch,
        whose source buffer is consumed on failure (the superstep must
        be replayed from committed state, not the fetch re-run)."""
        t0 = time.perf_counter()
        out = fn()
        if time.perf_counter() - t0 > self.timeout_s:
            self.slow_dispatches += 1
            opstats.bump("watchdog_slow_dispatches")
        return out


def _pow2_bucket(n: int) -> int:
    """Smallest power of two >= n (>= 1): payload/tape widths are
    bucketed so admissions and warm restarts hit a handful of stable
    compiled shapes instead of one per width."""
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


def _as_mesh(mesh) -> Optional[Mesh]:
    """Normalize the ``mesh`` argument: None stays None (single-device
    vmap), an int M builds a 1-D ("batch",) mesh over the first M
    devices, a jax Mesh is used as-is (it must carry a "batch" axis)."""
    if mesh is None:
        return None
    if isinstance(mesh, Mesh):
        if BATCH_AXIS not in mesh.axis_names:
            raise ValueError(
                f"replica-sharded fleets need a {BATCH_AXIS!r} mesh "
                f"axis (got {mesh.axis_names})")
        return mesh
    n = int(mesh)
    if n <= 0:
        raise ValueError("mesh must be a positive device count")
    devices = jax.devices()
    if n > len(devices):
        raise ValueError(
            f"mesh={n} but only {len(devices)} device(s) visible "
            f"(on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n})")
    return Mesh(np.asarray(devices[:n]), axis_names=(BATCH_AXIS,))


# ---------------------------------------------------------------------------
# Scenario overrides: compact per-replica deltas, materialized on device
# ---------------------------------------------------------------------------

class ReplicaOverrides:
    """One replica's deviation from the shared base scenario.

    Everything here is SMALL by design — a campaign's whole point is
    that per-replica upload cost must not scale with system size:

    * ``bw_scale``     — global link-capacity multiplier (sweeps);
    * ``size_scale``   — global flow-size multiplier (sweeps);
    * ``link_scale``   — sparse {constraint slot: capacity factor}
                         (fault-campaign degradations, hot-spot what-ifs);
    * ``flow_scale``   — sparse {variable slot: size factor};
    * ``dead_flows``   — variable slots absent from this replica
                         (penalty forced to 0: the flow never runs);
    * ``elem_w``       — sparse {element slot: sharing weight}: this
                         replica's element-weight deviations from the
                         shared ``e_w`` table (route-weight what-ifs,
                         per-replica QoS shares).  The fleet's [B, E]
                         weight table is materialized ON DEVICE from
                         these indexed payloads — upload bytes scale
                         with the overridden slots, never with B×E.
    """

    __slots__ = ("bw_scale", "size_scale", "link_scale", "flow_scale",
                 "dead_flows", "elem_w")

    def __init__(self, bw_scale: float = 1.0, size_scale: float = 1.0,
                 link_scale: Optional[Dict[int, float]] = None,
                 flow_scale: Optional[Dict[int, float]] = None,
                 dead_flows: Iterable[int] = (),
                 elem_w: Optional[Dict[int, float]] = None):
        if bw_scale <= 0 or size_scale <= 0:
            raise ValueError("bw_scale and size_scale must be > 0")
        self.bw_scale = float(bw_scale)
        self.size_scale = float(size_scale)
        self.link_scale = dict(link_scale or {})
        self.flow_scale = dict(flow_scale or {})
        self.dead_flows = tuple(sorted(set(int(s) for s in dead_flows)))
        self.elem_w = dict(elem_w or {})


def derive_replica_arrays(c_bound, sizes, remains, penalty,
                          ov: ReplicaOverrides):
    """HOST materialization of one replica's f64 per-replica arrays —
    the exact op-for-op mirror of the device `_materialize` kernel, so
    a solo run (ops.lmm_drain.DrainSim over these arrays) is
    bit-identical to the replica's lane in the batched program.  Keep
    the two in sync: base*global-scale first, then the sparse factors
    in sorted slot order."""
    cb = np.asarray(c_bound, np.float64) * ov.bw_scale
    for slot in sorted(ov.link_scale):
        cb[slot] *= ov.link_scale[slot]
    sz = np.asarray(sizes, np.float64) * ov.size_scale
    rem = np.asarray(remains, np.float64) * ov.size_scale
    for slot in sorted(ov.flow_scale):
        sz[slot] *= ov.flow_scale[slot]
        rem[slot] *= ov.flow_scale[slot]
    pen = np.asarray(penalty, np.float64).copy()
    for slot in ov.dead_flows:
        pen[slot] = 0.0
    return cb, sz, rem, pen


def derive_replica_ew(e_w, ov: ReplicaOverrides, dtype) -> np.ndarray:
    """HOST materialization of one replica's element weights — the
    op-for-op mirror of the device `_materialize_ew` kernel: indexed
    SET (not multiply) of the overridden slots in sorted order, then
    the dtype cast.  Exact: scatter-set carries the payload value
    bit-for-bit, so solo and batched lanes see identical weights."""
    ew = np.asarray(e_w, np.float64).copy()
    for slot in sorted(ov.elem_w):
        ew[slot] = ov.elem_w[slot]
    return ew.astype(dtype)


def _pack_overrides(specs: List[ReplicaOverrides], n_c: int, n_v: int):
    """Stack the fleet's overrides into padded payload arrays (pad
    index = out-of-range slot, dropped by the device scatters; pad
    factor = 1.0, a no-op)."""
    B = len(specs)
    sl = max(1, max(len(s.link_scale) for s in specs))
    sf = max(1, max(len(s.flow_scale) for s in specs))
    sd = max(1, max(len(s.dead_flows) for s in specs))
    bw = np.array([s.bw_scale for s in specs], np.float64)
    fs = np.array([s.size_scale for s in specs], np.float64)
    li = np.full((B, sl), n_c, np.int32)
    lf = np.ones((B, sl), np.float64)
    fi = np.full((B, sf), n_v, np.int32)
    ff = np.ones((B, sf), np.float64)
    di = np.full((B, sd), n_v, np.int32)
    for b, s in enumerate(specs):
        for j, slot in enumerate(sorted(s.link_scale)):
            li[b, j] = slot
            lf[b, j] = s.link_scale[slot]
        for j, slot in enumerate(sorted(s.flow_scale)):
            fi[b, j] = slot
            ff[b, j] = s.flow_scale[slot]
        for j, slot in enumerate(s.dead_flows):
            di[b, j] = slot
    return bw, fs, li, lf, fi, ff, di


def _pack_elem_w(specs: List[ReplicaOverrides], pad_idx: int, dtype):
    """Stack the fleet's sparse element-weight overrides into one
    padded indexed payload (pad index = out-of-range slot, dropped by
    the device scatter).  Bytes scale with the widest replica's
    override count — NEVER with B×E."""
    B = len(specs)
    se = max(1, max(len(s.elem_w) for s in specs))
    ei = np.full((B, se), pad_idx, np.int32)
    ew = np.zeros((B, se), dtype)
    for b, s in enumerate(specs):
        for j, slot in enumerate(sorted(s.elem_w)):
            ei[b, j] = slot
            ew[b, j] = s.elem_w[slot]
    return ei, ew


@jax.jit
def _materialize_ew(base_ew2, ei, ew):
    """DEVICE materialization of the fleet's [B, ·, group] element
    weights from the shared 2D table + indexed payloads: per-lane
    scatter-SET into the flattened table (pad slots drop).  Must stay
    the op-for-op mirror of derive_replica_ew."""
    flat = base_ew2.reshape(-1)

    def lane(ei_l, ew_l):
        return flat.at[ei_l].set(ew_l, mode="drop").reshape(
            base_ew2.shape)

    return jax.vmap(lane)(ei, ew)


@jax.jit
def _materialize(base_cb, base_sizes, base_rem, base_pen,
                 bw, fs, li, lf, fi, ff, di):
    """DEVICE materialization of the fleet's per-replica f64 state from
    the shared base + compact payloads: base*global-scale elementwise,
    then sparse scatter-multiplies (pad slots scatter out of range and
    drop).  Must stay the op-for-op mirror of derive_replica_arrays."""
    def lane(bw_l, fs_l, li_l, lf_l, fi_l, ff_l, di_l):
        cb = base_cb * bw_l
        cb = cb.at[li_l].multiply(lf_l, mode="drop")
        sz = base_sizes * fs_l
        rem = base_rem * fs_l
        sz = sz.at[fi_l].multiply(ff_l, mode="drop")
        rem = rem.at[fi_l].multiply(ff_l, mode="drop")
        pen = base_pen.at[di_l].set(0.0, mode="drop")
        return cb, sz, rem, pen
    return jax.vmap(lane)(bw, fs, li, lf, fi, ff, di)


@functools.partial(jax.jit, static_argnames=("done_rel",))
def _admit_lane_state(base_cb, base_sizes, base_rem, base_pen,
                      bw, fs, li, lf, fi, ff, di,
                      cb, pen, rem, thresh, b, done_eps,
                      done_rel: bool):
    """DEVICE admission of ONE lane into a live fleet: the per-lane
    `_materialize` math (f64 base*global-scale + sparse scatters), the
    threshold derivation and the f64→dtype casts, scattered into row
    ``b`` of the committed fleet state.  Must stay op-for-op identical
    to the constructor materialization so an admitted lane is
    bit-identical to the same scenario in a fresh fleet (and therefore
    to its solo run).  Upload cost is O(overrides) — the payload is
    the same compact record a fleet-birth lane ships."""
    cb64 = base_cb * bw
    cb64 = cb64.at[li].multiply(lf, mode="drop")
    sz64 = base_sizes * fs
    rem64 = base_rem * fs
    sz64 = sz64.at[fi].multiply(ff, mode="drop")
    rem64 = rem64.at[fi].multiply(ff, mode="drop")
    pen64 = base_pen.at[di].set(0.0, mode="drop")
    if done_rel:
        th64 = done_eps * sz64
    else:
        th64 = jnp.full_like(sz64, done_eps)
    dt = cb.dtype
    return (cb.at[b].set(cb64.astype(dt)),
            pen.at[b].set(pen64.astype(dt)),
            rem.at[b].set(rem64.astype(dt)),
            thresh.at[b].set(th64.astype(dt)))


@jax.jit
def _admit_lane_tape(tape_t, tape_slot, tape_val, tpos,
                     row_t, row_s, row_v, b):
    """Scatter one admitted lane's fault tape row (inf-padded to the
    fleet's tape width) and reset its cursor to 0 — the admitted lane
    starts at its own k=0 with a fresh tape slot."""
    return (tape_t.at[b].set(row_t),
            tape_slot.at[b].set(row_s),
            tape_val.at[b].set(row_v),
            tpos.at[b].set(jnp.int32(0)))


@jax.jit
def _admit_lane_coll(pred, ready, clk, row_p, row_r, b):
    """Reset one admitted lane's collective-DAG walk state to the
    schedule's birth state (fresh predecessor counts and activation
    dates, Kahan clock pair back to zero) — the lane replays the whole
    shared schedule from its own t=0."""
    return (pred.at[b].set(row_p),
            ready.at[b].set(row_r),
            clk.at[b].set(jnp.zeros(2, jnp.float64)))


@jax.jit
def _admit_lane_ew(base_ew2, ew_fleet, ei, ewv, b):
    """Re-materialize one lane's element-weight row from the shared
    base table + the admitted spec's indexed payload (scatter-SET, pad
    slots drop) — clears whatever the lane's previous occupant had."""
    lane = base_ew2.reshape(-1).at[ei].set(
        ewv, mode="drop").reshape(base_ew2.shape)
    return ew_fleet.at[b].set(lane)


# ---------------------------------------------------------------------------
# Batched kernel programs (vmapped solo programs + alive-mask gating)
# ---------------------------------------------------------------------------

def _batch_superstep_program(e_var, e_cnst, e_w, c_bound, v_bound,
                             pen, rem, thresh, ids, alive, k,
                             round_budget, zero_bits,
                             tape_t, tape_slot, tape_val, tape_pos,
                             coll_pred, coll_ready, coll_clk,
                             edge_src, edge_dst, exec_cost, t0,
                             eps: float, n_c: int, n_v: int,
                             k_max: int, group: int,
                             has_bounds: bool = False,
                             batch_w: bool = False,
                             has_tape: bool = False,
                             has_coll: bool = False):
    """One fleet superstep: the solo superstep program vmapped over the
    replica axis.  A dead lane (alive=False) gets k=0, so its outer
    while_loop cond is false on entry and the vmap batching rule
    freezes its state — finished/diverged replicas cost nothing but
    masked lanes, and their state is returned unchanged bit-for-bit.

    With ``has_tape`` each lane additionally carries its own fault
    event tape ([B, T] dates/slots/values, inf-padded), tape cursor and
    f64 base clock — sharded shard-local like every other [B, ·]
    payload, so a lane's fires never cross device boundaries.

    With ``has_coll`` each lane carries its own collective-DAG state
    (predecessor counts [B, n_v], pending-activation dates [B, n_v],
    the Kahan clock pair [B, 2]) while the schedule STRUCTURE
    (edge_src / edge_dst / exec_cost) is shared across the fleet like
    the platform — rank-count/algorithm sweeps batch scenarios that
    differ only in per-lane overrides."""
    k = jnp.asarray(k, jnp.int32)

    def lane(cb, pen_l, rem_l, th_l, alive_l, tt_l, ts_l, tv_l, tp_l,
             cp_l, cr_l, ck_l, t0_l, ew_l):
        k_l = jnp.where(alive_l, k, jnp.int32(0))
        return _superstep_program(
            e_var, e_cnst, ew_l, cb, v_bound, pen_l, rem_l, th_l, ids,
            k_l, jnp.asarray(round_budget, jnp.int32), jnp.int32(0),
            zero_bits, tt_l, ts_l, tv_l, tp_l,
            cp_l, cr_l, ck_l, edge_src, edge_dst, exec_cost, t0_l,
            eps=eps, n_c=n_c, n_v=n_v, k_max=k_max,
            group=group, has_bounds=has_bounds, has_tape=has_tape,
            has_coll=has_coll)

    return jax.vmap(lane,
                    in_axes=(0,) * 13 + (0 if batch_w else None,))(
        c_bound, pen, rem, thresh, alive, tape_t, tape_slot, tape_val,
        tape_pos, coll_pred, coll_ready, coll_clk, t0, e_w)


_batch_superstep = functools.partial(
    jax.jit, static_argnames=("eps", "n_c", "n_v", "k_max", "group",
                              "has_bounds", "batch_w", "has_tape",
                              "has_coll"))(_batch_superstep_program)


# ---------------------------------------------------------------------------
# Batched flattened solve (no drain): B rate queries, one program
# ---------------------------------------------------------------------------

def solve_arrays_batch(e_var, e_cnst, e_w, c_bound, c_fatpipe,
                       v_penalty, v_bound, eps: float,
                       parallel_rounds: bool = True,
                       chunk: int = 4096, device=None, mesh=None):
    """Solve B independent max-min systems sharing one COO structure in
    lockstep chunks; returns (values [B,V], remaining [B,C],
    usage [B,C], rounds [B]).

    ``e_w`` may be [E] (shared weights) or [B,E]; ``c_bound``,
    ``v_penalty``, ``v_bound`` are [B,·].  Convergence is checked once
    per chunk for the WHOLE fleet in a single [B, 3+V+2C] fetch;
    converged lanes are frozen by their own loop cond, so stragglers
    never recompute finished replicas.

    ``mesh`` (int device count or a ("batch",) jax Mesh) shards the
    replica axis across devices: shared structure replicated,
    per-replica arrays split into per-device blocks.  A ragged B is
    padded with penalty-0 lanes (they converge in zero rounds) and the
    padding is trimmed from every output, so results are bit-identical
    to the unsharded call."""
    mesh = _as_mesh(mesh)
    e_w = np.asarray(e_w)
    batch_w = e_w.ndim == 2
    dtype = e_w.dtype
    c_bound = np.asarray(c_bound, dtype)
    v_penalty = np.asarray(v_penalty, dtype)
    v_bound = np.asarray(v_bound, dtype)
    B = c_bound.shape[0]
    n_shards = int(np.prod(list(mesh.shape.values()))) if mesh else 1
    pad = (-B) % n_shards
    if pad:
        # dead padding lanes: penalty 0 everywhere, so usage0 is 0,
        # the light set starts empty and the lane converges instantly
        c_bound = np.concatenate([c_bound, c_bound[-1:].repeat(pad, 0)])
        v_penalty = np.concatenate(
            [v_penalty, np.zeros((pad,) + v_penalty.shape[1:], dtype)])
        v_bound = np.concatenate(
            [v_bound, np.full((pad,) + v_bound.shape[1:], -1.0, dtype)])
        if batch_w:
            e_w = np.concatenate([e_w, e_w[-1:].repeat(pad, 0)])
    n_c, n_v = c_bound.shape[1], v_penalty.shape[1]
    c_fatpipe = np.asarray(c_fatpipe, bool)
    has_bounds = bool(np.any((v_bound > 0) & (v_penalty > 0)))
    has_fatpipe = bool(c_fatpipe.any())
    eps_f = float(eps)

    if mesh is not None:
        bspec = NamedSharding(mesh, P(BATCH_AXIS))
        rspec = NamedSharding(mesh, P())
        put_shared = lambda a: jax.device_put(np.asarray(a), rspec)  # noqa: E731
        put_batched = lambda a: jax.device_put(np.asarray(a), bspec)  # noqa: E731
        opstats.bump("shards", n_shards)
    else:
        put_shared = put_batched = \
            lambda a: jax.device_put(np.asarray(a), device)  # noqa: E731
    shared = [put_shared(a) for a in (e_var, e_cnst)]
    fat = put_shared(c_fatpipe)
    batched = [put_batched(e_w) if batch_w else put_shared(e_w)]
    batched += [put_batched(a) for a in (c_bound, v_penalty, v_bound)]
    shared_bytes = (sum(np.asarray(a).nbytes for a in (e_var, e_cnst))
                    + c_fatpipe.nbytes
                    + (0 if batch_w else e_w.nbytes))
    batched_bytes = (sum(a.nbytes for a in (c_bound, v_penalty, v_bound))
                     + (e_w.nbytes if batch_w else 0))
    opstats.bump("uploaded_bytes_full", shared_bytes + batched_bytes)
    if mesh is not None:
        opstats.bump("replicated_upload_bytes", shared_bytes * n_shards)
        opstats.bump("sharded_upload_bytes", batched_bytes)

    carry = None
    prev_progress = None
    while True:
        if carry is None:
            out = _solve_kernel_chunk_batched_fresh(
                shared[0], shared[1], batched[0], batched[1], fat,
                batched[2], batched[3], eps=eps_f, n_c=n_c, n_v=n_v,
                parallel_rounds=parallel_rounds, chunk=chunk,
                has_bounds=has_bounds, has_fatpipe=has_fatpipe,
                batch_w=batch_w)
        else:
            out = _solve_kernel_chunk_batched(
                shared[0], shared[1], batched[0], batched[1], fat,
                batched[2], batched[3], carry, eps=eps_f, n_c=n_c,
                n_v=n_v, parallel_rounds=parallel_rounds, chunk=chunk,
                has_bounds=has_bounds, has_fatpipe=has_fatpipe,
                batch_w=batch_w)
        values, remaining, usage, rounds, carry = out
        opstats.bump("dispatches")
        rdt = values.dtype
        fetched = np.asarray(jnp.concatenate([
            jnp.stack([rounds.astype(rdt),
                       jnp.count_nonzero(carry[4], axis=1).astype(rdt),
                       jnp.count_nonzero(carry[1], axis=1).astype(rdt)],
                      axis=1),
            values, remaining.astype(rdt), usage.astype(rdt)], axis=1))
        rounds_h = fetched[:, 0].astype(np.int64)
        n_light = fetched[:, 1].astype(np.int64)
        n_fixed = fetched[:, 2].astype(np.int64)
        if not n_light.any():
            values = fetched[:, 3:3 + n_v]
            remaining = fetched[:, 3 + n_v:3 + n_v + n_c]
            usage = fetched[:, 3 + n_v + n_c:3 + n_v + 2 * n_c]
            break
        if (rounds_h >= _MAX_ROUNDS).any():
            bad = int(np.argmax(rounds_h >= _MAX_ROUNDS))
            raise SolveError(
                f"LMM batch solve: replica {bad} did not converge "
                f"within {_MAX_ROUNDS} saturation rounds "
                f"({n_c} constraints, {n_v} variables, batch {B})")
        progress = (n_light.tobytes(), n_fixed.tobytes())
        if progress == prev_progress:
            bad = int(np.argmax(n_light > 0))
            raise SolveError(
                f"LMM batch solve stalled: replica {bad} made no "
                f"progress over {chunk} rounds ({int(n_light[bad])} "
                f"active constraints); the system does not converge "
                f"at eps={eps} in {np.dtype(dtype).name} precision")
        prev_progress = progress
    opstats.bump("fixpoint_rounds", int(rounds_h.sum()))
    if pad:
        values, remaining, usage, rounds_h = (
            values[:B], remaining[:B], usage[:B], rounds_h[:B])
    return values, remaining, usage, rounds_h


# ---------------------------------------------------------------------------
# The batched drain executor
# ---------------------------------------------------------------------------

class FleetToken:
    """One issued (possibly in-flight) fleet superstep: the batched
    mirror of ops.lmm_drain.SuperstepToken, carrying the [B, ·] flow
    state in/out plus the alive mask the dispatch ran under.  jax
    arrays are immutable, so the token is a free double-buffered
    snapshot; discarding an un-collected token is O(1)."""

    __slots__ = ("pen_in", "rem_in", "pen_out", "rem_out", "packed",
                 "k", "alive", "speculative",
                 "cb_in", "cb_out", "tpos_out", "t0_in", "t0_out",
                 "pred_out", "ready_out", "clk_out")

    def __init__(self, pen_in, rem_in, pen_out, rem_out, packed,
                 k: int, alive, speculative: bool,
                 cb_in=None, cb_out=None, tpos_out=None,
                 t0_in=None, t0_out=None,
                 pred_out=None, ready_out=None, clk_out=None):
        self.pen_in = pen_in
        self.rem_in = rem_in
        self.pen_out = pen_out
        self.rem_out = rem_out
        self.packed = packed
        self.k = k
        self.alive = alive
        self.speculative = speculative
        # fault-tape double buffers (see SuperstepToken): per-lane
        # bounds in/out, post-dispatch tape cursors, and the [B] f64
        # base clocks this dispatch started from / left behind
        self.cb_in = cb_in
        self.cb_out = cb_out
        self.tpos_out = tpos_out
        self.t0_in = t0_in
        self.t0_out = t0_out
        # collective-tape double buffers (see SuperstepToken)
        self.pred_out = pred_out
        self.ready_out = ready_out
        self.clk_out = clk_out


class ReplicaState:
    """Host-side record of one replica in a fleet."""

    __slots__ = ("index", "events", "fault_events",
                 "collective_events", "t", "advances",
                 "alive", "error", "fault")

    def __init__(self, index: int):
        self.index = index
        self.events: List[Tuple[float, int]] = []
        #: (time, constraint slot) per fired tape entry, fire order
        self.fault_events: List[Tuple[float, int]] = []
        #: (time, flow id) per fired collective activation, fire order
        self.collective_events: List[Tuple[float, int]] = []
        self.t = 0.0              # f64 master clock (host-accumulated)
        self.advances = 0
        self.alive = True
        self.error: Optional[str] = None
        #: why the lane was quarantined (None for clean completion)
        self.fault: Optional[LaneFault] = None


class BatchDrainSim:
    """Drain B scenario replicas of ONE shared platform flattening to
    completion in lockstep batched device programs.

    Constructor arguments mirror ops.lmm_drain.DrainSim — COO elements,
    constraint capacities, flow sizes — plus ``overrides``: one
    :class:`ReplicaOverrides` per replica, materialized on device from
    compact payloads (upload cost O(total overrides), not O(B*system)).

    Per-replica state is (c_bound, penalties, remaining, thresholds)
    with the batch axis leading; the structure tables and (by default)
    the element weights are shared and uploaded once.  Replicas whose
    overrides carry ``elem_w`` entries get per-replica weight tables
    materialized ON DEVICE from the indexed payload (upload bytes ~
    overridden slots, not B×E).  Finished or
    diverged replicas go dark via the alive mask instead of forcing
    ragged shapes; the fleet repacks NEVER (lockstep shapes), so each
    lane's reduction order — and therefore its event order and clock —
    is bit-identical to a solo no-repack DrainSim of the same scenario.

    ``pipeline=D`` keeps up to D speculative fleet supersteps in
    flight beyond the one being collected (see ops.lmm_drain): the
    host demultiplexes ring N's [B, ·] fetch — a serial Python walk
    over every lane — while the device already executes fleet
    superstep N+1.  Any alive-mask change or budget rescue while
    processing ring N discards the in-flight tokens; results are
    bit-identical to ``pipeline=0``.

    ``mesh=M`` (int device count or a ("batch",) jax Mesh) shards the
    replica axis across M devices: every [B, ·] array — payloads,
    materialized state, alive mask, completion rings — is placed under
    ``NamedSharding(mesh, P("batch"))`` while the shared flattening is
    replicated.  One fleet superstep is still ONE logical dispatch and
    one FleetToken; the ring comes back as one fetch per shard,
    reassembled in replica order before the demux, so events and
    clocks are bit-identical to ``mesh=None``.  When B is ragged the
    fleet is padded with dead lanes (see module docstring); padded
    lanes are asserted to produce zero events.
    """

    def __init__(self, e_var, e_cnst, e_w, c_bound, sizes,
                 overrides: List[ReplicaOverrides],
                 eps: float = 1e-5, done_eps: float = 1e-4,
                 dtype=None, done_mode: str = "rel",
                 superstep: int = 8, superstep_rounds: int = 0,
                 device=None, v_bound=None, penalty=None, remains=None,
                 pipeline: int = 0, mesh=None, tapes=None,
                 plan=None, tape_slots: int = 0, start_dead=(),
                 batch_w: Optional[bool] = None, watchdog=None,
                 collective=None):
        if not overrides:
            raise ValueError("BatchDrainSim needs at least one replica")
        if done_mode not in ("rel", "abs"):
            raise ValueError(f"Unknown done_mode {done_mode!r} "
                             "(expected rel or abs)")
        #: serving.plancache.CompiledPlan routing the fleet's jitted
        #: programs through AOT-compiled executables (None = plain jit)
        self._plan = plan
        #: DispatchWatchdog wrapping every device dispatch/fetch in
        #: wall-clock accounting + seeded-backoff retries (None = raw)
        self._watchdog = watchdog
        #: AOT executables of the watchdog-guarded jit path, by program
        #: signature (see _call_plan)
        self._compiled: Dict = {}
        self.eps = float(eps)
        self.done_eps = float(done_eps)
        self.done_mode = done_mode
        # None = the device's own solver dtype (f64 where it is IEEE)
        self.dtype = solve_dtype(dtype, "BatchDrainSim(dtype=)", device)
        self.device = device
        self._mesh = _as_mesh(mesh)
        self.n_shards = (int(np.prod(list(self._mesh.shape.values())))
                         if self._mesh is not None else 1)
        if self._mesh is not None:
            self._bspec = NamedSharding(self._mesh, P(BATCH_AXIS))
            self._rspec = NamedSharding(self._mesh, P())
            opstats.bump("shards", self.n_shards)
        self.B = len(overrides)
        self.overrides = list(overrides)
        # ragged-fleet guard: pad to a multiple of the shard count with
        # lanes that are dead from birth (neutral overrides, alive
        # False) — the vmap batching rule freezes them at k=0 and the
        # collect asserts they never log an event
        self.B_padded = self.B + (-self.B) % self.n_shards
        overrides = (list(overrides)
                     + [ReplicaOverrides()
                        for _ in range(self.B_padded - self.B)])
        self.n_c = len(c_bound)
        self.n_v = len(sizes)
        self.superstep_k = int(superstep)
        if self.superstep_k <= 0:
            raise ValueError("BatchDrainSim is superstep-only "
                             "(superstep >= 1)")
        if not superstep_rounds:
            platform = (device.platform if device is not None
                        else default_platform())
            # same per-dispatch round-budget reasoning as the solo
            # DrainSim: the bound is per dispatch, and a vmapped lane
            # runs the same per-advance round count as solo
            superstep_rounds = (self.superstep_k * 512
                                if platform == "cpu" else 64 * 4)
        self.superstep_rounds = int(superstep_rounds)

        # shared base (f64 masters for materialization + dtype tables)
        self._base_cb = np.asarray(c_bound, np.float64)
        self._base_sizes = np.asarray(sizes, np.float64)
        self._base_rem = (np.asarray(remains, np.float64)
                          if remains is not None else self._base_sizes)
        self._base_pen = (np.asarray(penalty, np.float64)
                          if penalty is not None
                          else np.ones(self.n_v, np.float64))
        ev2 = _to2d(np.asarray(e_var, np.int32))
        ec2 = _to2d(np.asarray(e_cnst, np.int32))
        ew2 = _to2d(np.asarray(e_w, self.dtype))
        # per-replica element weights ride an INDEXED payload and are
        # materialized on device below — the shared 2D table is still
        # uploaded exactly once whatever B is.  ``batch_w=True`` forces
        # the per-replica tables even when no INITIAL lane overrides
        # weights, so mid-flight admissions may bring elem_w specs.
        self.batch_w = (any(ov.elem_w for ov in overrides)
                        if batch_w is None else bool(batch_w))
        ew_payload = (_pack_elem_w(overrides, ew2.size, self.dtype)
                      if self.batch_w else None)
        if v_bound is not None:
            vb = np.asarray(v_bound, self.dtype)
            self.has_bounds = bool(np.any(vb > 0))
        else:
            vb = np.full(self.n_v, -1.0, self.dtype)
            self.has_bounds = False

        ew_dev = self._put_shared(ew2)
        # base (pre-materialize) weight table + pad index, kept for
        # per-lane re-materialization on admission
        self._base_ew_dev = ew_dev
        self._ew_pad_idx = int(ew2.size)
        if self.batch_w:
            ei_dev, ewv_dev = [self._put_batched(a)
                               for a in ew_payload]
            opstats.bump("uploaded_bytes_delta",
                         sum(a.nbytes for a in ew_payload))
            ew_dev = self._call_plan(
                "materialize_ew", _materialize_ew,
                (self._base_ew_dev, ei_dev, ewv_dev), {})
            opstats.bump("dispatches")
            ew_dev = self._pin(ew_dev)
        self._dev = [self._put_shared(ev2),
                     self._put_shared(ec2), ew_dev]
        self._vb = self._put_shared(vb)
        ids = np.arange(self.n_v, dtype=np.int32)
        self._ids_dev = self._put_shared(ids)
        base_dev = [self._put_shared(a) for a in
                    (self._base_cb, self._base_sizes, self._base_rem,
                     self._base_pen)]
        payload = _pack_overrides(overrides, self.n_c, self.n_v)
        payload_dev = [self._put_batched(a) for a in payload]
        opstats.bump("uploaded_bytes_full",
                     ev2.nbytes + ec2.nbytes + ew2.nbytes + vb.nbytes
                     + ids.nbytes
                     + sum(a.nbytes for a in (self._base_cb,
                                              self._base_sizes,
                                              self._base_rem,
                                              self._base_pen)))
        opstats.bump("uploaded_bytes_delta",
                     sum(a.nbytes for a in payload))

        # one materialization dispatch derives the whole fleet's f64
        # state on device; the dtype cast below mirrors DrainSim's
        # host-side casts exactly (f64 math first, cast second)
        self._base_dev = base_dev
        cb64, sz64, rem64, pen64 = self._call_plan(
            "materialize", _materialize,
            (*base_dev, *payload_dev), {})
        opstats.bump("dispatches")
        if done_mode == "rel":
            thresh64 = self.done_eps * sz64
        else:
            thresh64 = jnp.full_like(sz64, self.done_eps)
        self._cb = self._pin(cb64.astype(self.dtype))
        self._pen = self._pin(pen64.astype(self.dtype))
        self._rem = self._pin(rem64.astype(self.dtype))
        self._thresh = self._pin(thresh64.astype(self.dtype))

        # per-replica fault event tapes: `tapes` is one (dates, slots,
        # values) triple — or None — per replica (see DrainSim's tape=;
        # identical semantics per lane).  Packed to [B_padded, T] with
        # inf date padding (a padded entry can never fire) and sharded
        # shard-local like every other per-replica payload.
        self.has_tape = False
        self._last_fired = False
        self._tape_width = 0
        if tapes is not None and any(
                t is not None and len(t[0]) for t in tapes):
            need = max(len(t[0]) for t in tapes if t is not None)
        else:
            need = 0
            tapes = None
        # `tape_slots` reserves ring capacity for tapes that arrive
        # later via admit_lane; only then is the width bucketed to a
        # power of two, so admissions and warm restarts hit stable
        # compiled shapes (inf-padded entries never fire —
        # bit-identity is unaffected).  A fleet whose tapes are all
        # known at build keeps the exact width: no padding overhead on
        # the plain batched path.
        reserving = int(tape_slots) > 0
        need = max(need, int(tape_slots))
        if need:
            if tapes is None:
                tapes = [None] * self.B
            if len(tapes) != self.B:
                raise ValueError(f"tapes must have one entry per "
                                 f"replica ({len(tapes)} != {self.B})")
            tapes = list(tapes) + [None] * (self.B_padded - self.B)
            T = _pow2_bucket(need) if reserving else need
            self._tape_width = T
            tt = np.full((self.B_padded, T), np.inf, np.float64)
            ts = np.full((self.B_padded, T), self.n_c, np.int32)
            tv = np.zeros((self.B_padded, T), np.float64)
            n_slots = 0
            for b, t in enumerate(tapes):
                if t is None or not len(t[0]):
                    continue
                dates = np.asarray(t[0], np.float64)
                slots = np.asarray(t[1], np.int32)
                vals = np.asarray(t[2], np.float64)
                if not (len(dates) == len(slots) == len(vals)):
                    raise ValueError(
                        f"replica {b}: tape arrays must have equal "
                        f"length")
                if np.any(np.diff(dates) < 0):
                    raise ValueError(
                        f"replica {b}: tape dates must be time-sorted")
                if np.any((slots < 0) | (slots >= self.n_c)):
                    raise ValueError(f"replica {b}: tape slot out of "
                                     f"range")
                n = len(dates)
                tt[b, :n] = dates
                ts[b, :n] = slots
                tv[b, :n] = vals
                n_slots += n
            # same f64 -> dtype cast order as the solo DrainSim tape
            tvd = tv.astype(self.dtype)
            self.has_tape = True
            self._tape = (self._put_batched(tt), self._put_batched(ts),
                          self._put_batched(tvd))
            opstats.bump("fault_tape_slots", n_slots)
            opstats.bump("uploaded_bytes_delta",
                         tt.nbytes + ts.nbytes + tvd.nbytes)
        else:
            # dummy [B, 1] triple keeps the jit call sites uniform;
            # DCE'd when has_tape=False
            self._tape = (
                self._put_batched(np.full((self.B_padded, 1), np.inf)),
                self._put_batched(np.full((self.B_padded, 1), self.n_c,
                                          np.int32)),
                self._put_batched(np.zeros((self.B_padded, 1),
                                           self.dtype)))
        self._tpos = self._put_batched(
            np.zeros(self.B_padded, np.int32))

        # collective schedule tape: ONE compiled comm DAG (pred, ready,
        # edge_src, edge_dst, exec_cost — see DrainSim's collective=)
        # shared across the fleet.  The schedule STRUCTURE (edges,
        # exec costs) is platform-like and replicated; the walk STATE
        # (predecessor counts, pending-activation dates, the carried
        # Kahan clock pair) is per-lane, so lanes differing only in
        # overrides sweep the same collective independently.
        self.has_coll = False
        if collective is not None:
            cp, cr, ces, ced, cec = collective
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
            if self.dtype != np.float64:
                raise ValueError("collective= needs dtype=float64 "
                                 "(see DrainSim)")
            if any(ov.dead_flows for ov in self.overrides):
                raise ValueError("collective fleets cannot kill DAG "
                                 "flows via dead_flows overrides")
            # every lane starts from the base penalties (dead_flows
            # refused above), admitted lanes too
            _check_collective_start(self._base_pen, cp, cr)
            self.has_coll = True
            self._coll_base = (cp, cr)
            self._coll_edges = tuple(self._put_shared(a)
                                     for a in (ces, ced, cec))
            self._coll_pred = self._put_batched(
                np.broadcast_to(cp, (self.B_padded, self.n_v)).copy())
            self._coll_ready = self._put_batched(
                np.broadcast_to(cr, (self.B_padded, self.n_v)).copy())
            self._coll_clk = self._put_batched(
                np.zeros((self.B_padded, 2), np.float64))
            opstats.bump("collective_tape_slots", self.n_v * self.B)
            opstats.bump("uploaded_bytes_delta",
                         cp.nbytes * self.B_padded
                         + cr.nbytes * self.B_padded
                         + ces.nbytes + ced.nbytes + cec.nbytes
                         + 16 * self.B_padded)
        else:
            self._coll_edges = (
                self._put_shared(np.zeros(1, np.int32)),
                self._put_shared(np.zeros(1, np.int32)),
                self._put_shared(np.zeros(1, np.float64)))
            self._coll_pred = self._put_batched(
                np.zeros((self.B_padded, 1), np.int32))
            self._coll_ready = self._put_batched(
                np.full((self.B_padded, 1), np.inf))
            self._coll_clk = self._put_batched(
                np.zeros((self.B_padded, 2), np.float64))

        self.replicas = [ReplicaState(b) for b in range(self.B)]
        self._alive = np.zeros(self.B_padded, bool)
        self._alive[:self.B] = True
        # serving fleets are built wider than their initial spec list:
        # `start_dead` lanes are dead at birth (k=0, state frozen) and
        # wait for admit_lane to revive them mid-flight
        for b in start_dead:
            self._alive[int(b)] = False
            self.replicas[int(b)].alive = False
        self.admitted = 0
        self.pad_events = 0
        self.rescues = 0
        self.supersteps = 0
        self.syncs = 0
        self.rounds = 0
        self.pipeline = int(pipeline)
        # speculation census (pipelined fleet driver)
        self.spec_issued = 0
        self.spec_committed = 0
        self.spec_rolled_back = 0

    # -- device placement (single-device or replica-sharded) ---------------

    def _put_shared(self, a):
        """Upload one fleet-shared array: replicated onto every mesh
        device (counted per device copy — a pod really ships M copies)
        or plain device_put when unsharded."""
        if self._mesh is not None:
            opstats.bump("replicated_upload_bytes",
                         a.nbytes * self.n_shards)
            return jax.device_put(a, self._rspec)
        return jax.device_put(a, self.device)

    def _put_batched(self, a):
        """Upload one [B, ·] per-replica array split over the batch
        axis: every byte lands on exactly one device."""
        if self._mesh is not None:
            opstats.bump("sharded_upload_bytes", a.nbytes)
            return jax.device_put(a, self._bspec)
        return jax.device_put(a, self.device)

    def _pin(self, arr):
        """Re-commit a device-resident [B, ·] result to the batch
        sharding (device-side reshard, no host bytes; GSPMD usually
        already chose this layout and the put is a no-op)."""
        if self._mesh is not None:
            return jax.device_put(arr, self._bspec)
        return arr

    def _put_mask(self, m: np.ndarray):
        if self._mesh is not None:
            return jax.device_put(m, self._bspec)
        return jnp.asarray(m)

    def _call_plan(self, kind: str, fn, args, statics):
        """Dispatch one fleet program: through the AOT plan cache when
        the fleet carries a CompiledPlan (warm restarts reuse
        serialized executables, zero traces), else the plain jit.
        With a watchdog every dispatch runs under its wall-clock guard
        (seeded backoff + bounded retries); dispatches are pure
        functions of committed device state, so a retry is safe.
        Compiling is a step of its own OUTSIDE the guard: a compile
        refusal is raised as it is, not retried."""
        if self._watchdog is None:
            if self._plan is not None:
                return self._plan.call(kind, fn, args, statics)
            return fn(*args, **statics)
        if self._plan is not None:
            self._plan.compile(kind, fn, args, statics)
            issue = lambda: self._plan.call(kind, fn, args, statics)
        else:
            sig = (kind, tuple((getattr(a, "shape", None),
                                str(getattr(a, "dtype", type(a))))
                               for a in args))
            ex = self._compiled.get(sig)
            if ex is None:
                ex = self._compiled[sig] = fn.lower(
                    *args, **statics).compile()
            issue = lambda: ex(*args)
        return self._watchdog.guard(issue, what=f"dispatch:{kind}")

    # -- fleet stepping ----------------------------------------------------

    def _fetch(self, packed) -> np.ndarray:
        self.syncs += 1
        if self._watchdog is not None:
            # the ring fetch is the sync point where a wedged device
            # program actually surfaces — time it, but do NOT retry on
            # failure (the buffer is gone; the superstep must replay)
            return self._watchdog.timed(
                lambda: self._fetch_raw(packed), what="fetch")
        return self._fetch_raw(packed)

    def _fetch_raw(self, packed) -> np.ndarray:
        if self._mesh is None:
            return opstats.timed_fetch(packed)
        # per-shard ring demux: each device's [B/M, ·] block comes back
        # as its own transfer (counted in demux_fetches) and the blocks
        # are reassembled in replica order, so the host walk below
        # commits events in the same deterministic order as mesh=None.
        # Dedupe by block start: a compiler-replicated output shows the
        # same rows on every device.
        parts = {}
        for sh in packed.addressable_shards:
            start = sh.index[0].start or 0
            if start not in parts:
                parts[start] = sh.data
        fetched = [opstats.timed_fetch(parts[s]) for s in sorted(parts)]
        opstats.bump("demux_fetches", len(fetched))
        return np.concatenate(fetched, axis=0)

    def _superstep_issue_all(self, k: Optional[int] = None, pen=None,
                             rem=None, speculative: bool = False,
                             alive=None, cb=None, tpos=None, t0=None,
                             round_budget: int = 0,
                             pred=None, ready=None,
                             clk=None) -> "FleetToken":
        """Dispatch ONE fleet superstep without touching the committed
        state: chains from `(pen, rem)` (default: committed) under the
        CURRENT alive mask (or an explicit `alive` restriction — the
        budget rescue); inputs/outputs ride the returned token
        (see ops.lmm_drain — same issue/collect speculation protocol,
        one [B, ·] ring per token).  With a fault tape the dispatch
        chains per-lane bounds/cursors (`cb`, `tpos`) and [B] f64 base
        clocks `t0` (default: the committed replica clocks)."""
        k_max = self.superstep_k
        k = k_max if k is None else min(int(k), k_max)
        budget = int(round_budget) or self.superstep_rounds
        group = _pos_group(self.n_v)
        alive = (self._alive.copy() if alive is None
                 else np.asarray(alive, bool).copy())
        pen_in = self._pen if pen is None else pen
        rem_in = self._rem if rem is None else rem
        cb_in = self._cb if cb is None else cb
        tpos_in = self._tpos if tpos is None else tpos
        if t0 is None:
            # the committed host clocks ARE the lanes' f64 base clocks
            # (padded lanes never advance, 0.0 is fine)
            t0_in = np.zeros(self.B_padded, np.float64)
            for b, rep in enumerate(self.replicas):
                t0_in[b] = rep.t
            t0_in = self._put_batched(t0_in)
        else:
            t0_in = t0
        pred_in = self._coll_pred if pred is None else pred
        ready_in = self._coll_ready if ready is None else ready
        clk_in = self._coll_clk if clk is None else clk
        (pen_out, rem_out, cb_out, tpos_out, pred_out, ready_out,
         clk_out, packed) = self._call_plan(
            "superstep", _batch_superstep,
            (*self._dev, cb_in, self._vb, pen_in, rem_in,
             self._thresh, self._ids_dev,
             self._put_mask(alive), np.int32(k),
             np.int32(budget), _ZERO_BITS,
             *self._tape, tpos_in,
             pred_in, ready_in, clk_in, *self._coll_edges, t0_in),
            dict(eps=self.eps, n_c=self.n_c, n_v=self.n_v, k_max=k_max,
                 group=group, has_bounds=self.has_bounds,
                 batch_w=self.batch_w, has_tape=self.has_tape,
                 has_coll=self.has_coll))
        t0_out = None
        if self.has_tape:
            # derive the post-dispatch base clocks DEVICE-side with the
            # exact f64 add the host collect performs (rep.t = t0 +
            # t_sum), so a chained speculative issue is bit-identical
            # to a fresh issue from the committed clocks
            t0_out = t0_in + packed[:, 3].astype(jnp.float64)
        self.supersteps += 1
        opstats.bump("dispatches")
        if speculative:
            self.spec_issued += 1
            opstats.bump("speculations_issued")
        return FleetToken(pen_in, rem_in, pen_out, rem_out, packed,
                          k, alive, speculative,
                          cb_in=cb_in, cb_out=cb_out, tpos_out=tpos_out,
                          t0_in=t0_in, t0_out=t0_out,
                          pred_out=pred_out, ready_out=ready_out,
                          clk_out=clk_out)

    def _discard_token(self, tok: "FleetToken") -> None:
        """Drop an un-collected speculative fleet superstep (the alive
        mask changed or a rescue ran while processing the preceding
        ring): issue never committed anything, so rollback is O(1)."""
        self.spec_rolled_back += 1
        opstats.bump("speculations_rolled_back")

    def _stall_cause(self, b: int, n_live: int) -> Tuple[str, str]:
        """Attribute a fatal stall honestly: the superstep kernel's
        masked arithmetic surfaces a NaN-poisoned scenario (NaN
        capacity/size/penalty) as "no flow holds bandwidth" rather
        than a NaN clock, so on this already-fatal path we pay one
        extra fetch of the lane's committed arrays and classify NaN
        state as ``nan_solve`` instead of ``stall``."""
        for name, arr in (("remaining work", self._rem),
                          ("penalties", self._pen),
                          ("capacities", self._cb)):
            if np.isnan(np.asarray(arr[b])).any():
                return ("nan_solve",
                        f"drain solve consumed non-finite lane state "
                        f"(NaN in {name})")
        return ("stall",
                f"drain stalled: no flow holds bandwidth "
                f"({n_live} live)")

    def _quarantine(self, b: int, cause: str, detail: str) -> None:
        """Kill exactly lane ``b`` with a structured cause: the lane
        goes dark via the alive mask (like any death — every other
        lane's vmapped math is untouched, so their streams stay
        bit-identical to solo) and the replica record carries a
        :class:`LaneFault` for the serving layer to surface on the
        ticket."""
        rep = self.replicas[b]
        rep.error = detail
        rep.fault = LaneFault(cause, detail, b,
                              superstep=self.supersteps, t=rep.t)
        rep.alive = False
        self._alive[b] = False
        opstats.bump("lane_quarantined_" + cause)

    def _superstep_collect_all(self, tok: "FleetToken",
                               rescue: bool = False
                               ) -> Tuple[int, bool]:
        """Commit one issued fleet superstep: adopt its output arrays,
        fetch its [B, ·] packed rings (ONE transfer) and demultiplex
        per-replica events/clocks on the host.  Returns
        ``(n_alive, clean)`` — clean False when processing this ring
        mutated the fleet (a lane died, a tape event fired, or a
        rescue ran), so in-flight speculative successors must be
        discarded.  With ``rescue=True`` (the budget rescue's own
        collect — the dispatch already ran with the FULL round budget)
        still-stuck lanes are converted to non-convergence deaths
        instead of re-rescued."""
        self._pen, self._rem = tok.pen_out, tok.rem_out
        if self.has_tape:
            self._cb = tok.cb_out
            self._tpos = tok.tpos_out
        if self.has_coll:
            self._coll_pred = tok.pred_out
            self._coll_ready = tok.ready_out
            self._coll_clk = tok.clk_out
        k_max = self.superstep_k
        p = self._fetch(tok.packed)
        n_v = self.n_v
        ring_n = (n_v + (k_max if self.has_tape else 0)
                  + (n_v if self.has_coll else 0))
        o = _STATS_HEAD
        stuck: List[int] = []
        deaths = 0
        fired = 0
        coll_fired = 0
        for b in range(self.B):
            if not tok.alive[b]:
                continue
            rep = self.replicas[b]
            row = p[b]
            rounds, adv, n_ev = int(row[0]), int(row[1]), int(row[2])
            t_sum = float(row[3])
            n_live, flag = int(row[4]), int(row[5])
            ring_t = row[o + 2 * k_max:o + 2 * k_max + ring_n]
            ring_id = row[o + 2 * k_max + ring_n:
                          o + 2 * k_max + 2 * ring_n].astype(np.int64)
            self.rounds += rounds
            opstats.bump("fixpoint_rounds", rounds)
            if np.isnan(t_sum):
                # a poisoned scenario (e.g. NaN link capacity) turns
                # the lane's whole advance into NaN — quarantine it
                # BEFORE the ring demux so its garbage events never
                # reach the committed stream; the vmapped lane math is
                # per-lane, so no other lane saw the NaN
                self._quarantine(
                    b, "nan_solve",
                    "drain solve produced a non-finite clock advance "
                    "(NaN)")
                deaths += 1
                continue
            if n_ev > ring_n:
                # defensive: a ring claiming more events than it has
                # slots would walk the demux off the row and corrupt
                # neighbouring lanes' streams
                self._quarantine(
                    b, "ring_overflow",
                    f"completion ring overflow: {n_ev} events for "
                    f"{ring_n} slots")
                deaths += 1
                continue
            rep.advances += adv
            # collective lanes carry ABSOLUTE ring dates/clocks (the
            # Kahan pair chains on device across dispatches)
            t_base = 0.0 if self.has_coll else rep.t
            if self.has_tape or self.has_coll:
                # demux: negative ids are tagged — fault fires
                # (idx < n_c, fault stream) or collective activations
                # (idx >= n_c, activation stream) — see DrainSim
                for j in range(n_ev):
                    fid = int(ring_id[j])
                    tj = t_base + float(ring_t[j])
                    if fid < 0:
                        idx = -fid - 1
                        if idx >= self.n_c:
                            rep.collective_events.append(
                                (tj, idx - self.n_c))
                            coll_fired += 1
                        else:
                            rep.fault_events.append((tj, idx))
                            fired += 1
                    else:
                        rep.events.append((tj, fid))
            else:
                for j in range(n_ev):
                    rep.events.append((t_base + float(ring_t[j]),
                                       int(ring_id[j])))
            rep.t = t_base + t_sum
            coll_pending = (self.has_coll
                            and len(rep.events) < self.n_v)
            if flag == _FLAG_STALLED:
                self._quarantine(b, *self._stall_cause(b, n_live))
                deaths += 1
            elif n_live == 0 and not coll_pending:
                rep.alive = False
                self._alive[b] = False
                deaths += 1
            elif n_live == 0 and coll_pending and adv == 0:
                # no live flow, no progress, schedule still owes
                # completions: a cyclic/truncated DAG would spin the
                # fleet forever — kill exactly this lane
                self._quarantine(
                    b, "collective_deadlock",
                    f"collective schedule deadlocked: "
                    f"{len(rep.events)}/{self.n_v} flows completed "
                    f"and nothing is pending")
                deaths += 1
            elif flag == _FLAG_BUDGET and adv == 0:
                if rescue:
                    self._quarantine(b, "non_convergence",
                                     "drain solve did not converge")
                    deaths += 1
                else:
                    stuck.append(b)
        self._last_fired = fired > 0
        if fired:
            opstats.bump("fault_tape_events", fired)
        if coll_fired:
            opstats.bump("collective_tape_fires", coll_fired)
        if self.B_padded != self.B:
            # ragged-fleet guard: padded lanes are dead from birth
            # (k=0, state frozen), so any event they log would be a
            # sharding/vmap bug silently corrupting the fleet
            pad_ev = int(p[self.B:, 2].sum())
            self.pad_events += pad_ev
            if pad_ev:
                raise RuntimeError(
                    f"ragged-fleet guard: {self.B_padded - self.B} "
                    f"padded dead lane(s) logged {pad_ev} completion "
                    f"event(s) — the frozen-lane invariant is broken")
        if stuck:
            # the round budget expired inside a replica's FIRST solve:
            # finish exactly one advance for those lanes, the batched
            # mirror of the solo run() rescue
            self._rescue_superstep(stuck)
        if tok.speculative:
            self.spec_committed += 1
            opstats.bump("speculations_committed")
        clean = not deaths and not stuck and not fired
        return int(self._alive.sum()), clean

    def superstep_all(self, k: Optional[int] = None) -> int:
        """ONE batched superstep dispatch for every live replica and
        ONE [B, ·] fetch; commits per-replica events and clocks.
        Returns the number of still-live replicas."""
        n_alive, _clean = self._superstep_collect_all(
            self._superstep_issue_all(k))
        return n_alive

    # -- mid-flight lane admission (serving) -------------------------------

    def admit_lane(self, b: int, overrides: ReplicaOverrides,
                   tape=None) -> None:
        """Revive dead lane ``b`` with a NEW scenario, between
        supersteps: the lane's state row is re-materialized ON DEVICE
        from the admitted spec's compact payload (O(overrides) upload,
        the same lane math as fleet birth, so the admitted lane is
        bit-identical to a solo run of the same spec), its tape slot is
        replaced and its cursor reset, and its host replica record
        starts fresh at k=0, t=0.  Raises :class:`AdmissionError` when
        the fleet's birth-time capacity cannot absorb the scenario
        (lane alive/out of range, elem_w into a shared-weight fleet,
        tape wider than the reserved slots).

        The caller must treat a fired admission as a fleet MUTATION:
        in-flight speculative supersteps assumed the old alive mask and
        state, so they must be discarded (``run(between=...)`` does
        this automatically when the hook returns truthy)."""
        b = int(b)
        if not 0 <= b < self.B:
            raise AdmissionError(
                f"lane {b} out of range (fleet width {self.B})")
        if self._alive[b]:
            raise AdmissionError(f"lane {b} is still alive")
        ov = overrides
        if ov.elem_w and not self.batch_w:
            raise AdmissionError(
                "fleet shares one element-weight table (batch_w "
                "False); a spec with elem_w overrides needs a fleet "
                "built with batch_w=True")
        if tape is not None and not len(tape[0]):
            tape = None
        if tape is not None:
            if not self.has_tape:
                raise AdmissionError(
                    "fleet has no tape capacity (built without tapes "
                    "or tape_slots); a faulted spec cannot be "
                    "admitted")
            if len(tape[0]) > self._tape_width:
                raise AdmissionError(
                    f"tape with {len(tape[0])} entries exceeds the "
                    f"fleet's reserved tape width {self._tape_width}")
        # compact single-lane payload, widths bucketed to powers of two
        # so repeat admissions reuse a handful of compiled shapes
        sl = _pow2_bucket(len(ov.link_scale))
        sf = _pow2_bucket(len(ov.flow_scale))
        sd = _pow2_bucket(len(ov.dead_flows))
        li = np.full(sl, self.n_c, np.int32)
        lf = np.ones(sl, np.float64)
        fi = np.full(sf, self.n_v, np.int32)
        ff = np.ones(sf, np.float64)
        di = np.full(sd, self.n_v, np.int32)
        for j, slot in enumerate(sorted(ov.link_scale)):
            li[j] = slot
            lf[j] = ov.link_scale[slot]
        for j, slot in enumerate(sorted(ov.flow_scale)):
            fi[j] = slot
            ff[j] = ov.flow_scale[slot]
        for j, slot in enumerate(ov.dead_flows):
            di[j] = slot
        opstats.bump("uploaded_bytes_delta",
                     li.nbytes + lf.nbytes + fi.nbytes + ff.nbytes
                     + di.nbytes)
        cb, pen, rem, thresh = self._call_plan(
            "admit_state", _admit_lane_state,
            (*self._base_dev, np.float64(ov.bw_scale),
             np.float64(ov.size_scale), li, lf, fi, ff, di,
             self._cb, self._pen, self._rem, self._thresh,
             np.int32(b), np.float64(self.done_eps)),
            dict(done_rel=self.done_mode == "rel"))
        self._cb = self._pin(cb)
        self._pen = self._pin(pen)
        self._rem = self._pin(rem)
        self._thresh = self._pin(thresh)
        opstats.bump("dispatches")
        if self.has_tape:
            # always rewrite the lane's tape row — the previous
            # occupant may have left unfired entries behind
            T = self._tape_width
            row_t = np.full(T, np.inf, np.float64)
            row_s = np.full(T, self.n_c, np.int32)
            row_v = np.zeros(T, np.float64)
            if tape is not None:
                dates = np.asarray(tape[0], np.float64)
                slots = np.asarray(tape[1], np.int32)
                vals = np.asarray(tape[2], np.float64)
                if not (len(dates) == len(slots) == len(vals)):
                    raise AdmissionError(
                        "tape arrays must have equal length")
                if np.any(np.diff(dates) < 0):
                    raise AdmissionError(
                        "tape dates must be time-sorted")
                if np.any((slots < 0) | (slots >= self.n_c)):
                    raise AdmissionError("tape slot out of range")
                n = len(dates)
                row_t[:n] = dates
                row_s[:n] = slots
                row_v[:n] = vals
                opstats.bump("fault_tape_slots", n)
            # same f64 -> dtype cast order as fleet birth
            row_vd = row_v.astype(self.dtype)
            tt, ts, tv, tpos = self._call_plan(
                "admit_tape", _admit_lane_tape,
                (*self._tape, self._tpos, row_t, row_s, row_vd,
                 np.int32(b)), {})
            self._tape = (self._pin(tt), self._pin(ts), self._pin(tv))
            self._tpos = self._pin(tpos)
            opstats.bump("uploaded_bytes_delta",
                         row_t.nbytes + row_s.nbytes + row_vd.nbytes)
            opstats.bump("dispatches")
        if self.has_coll:
            # the admitted lane replays the fleet's shared schedule
            # from its own t=0: fresh DAG walk state, zeroed clock
            if ov.dead_flows:
                raise AdmissionError(
                    "collective fleets cannot kill DAG flows via "
                    "dead_flows overrides")
            cp, cr = self._coll_base
            pred, ready, clk = self._call_plan(
                "admit_coll", _admit_lane_coll,
                (self._coll_pred, self._coll_ready, self._coll_clk,
                 cp, cr, np.int32(b)), {})
            self._coll_pred = self._pin(pred)
            self._coll_ready = self._pin(ready)
            self._coll_clk = self._pin(clk)
            opstats.bump("collective_tape_slots", self.n_v)
            opstats.bump("uploaded_bytes_delta",
                         cp.nbytes + cr.nbytes + 16)
            opstats.bump("dispatches")
        if self.batch_w:
            # re-materialize the lane's weight row from the shared base
            # + this spec's indexed payload (clears the previous lane)
            se = _pow2_bucket(len(ov.elem_w))
            ei = np.full(se, self._ew_pad_idx, np.int32)
            ewv = np.zeros(se, self.dtype)
            for j, slot in enumerate(sorted(ov.elem_w)):
                ei[j] = slot
                ewv[j] = ov.elem_w[slot]
            new_ew = self._call_plan(
                "admit_ew", _admit_lane_ew,
                (self._base_ew_dev, self._dev[2], ei, ewv,
                 np.int32(b)), {})
            self._dev[2] = self._pin(new_ew)
            opstats.bump("uploaded_bytes_delta",
                         ei.nbytes + ewv.nbytes)
            opstats.bump("dispatches")
        self.overrides[b] = ov
        self.replicas[b] = ReplicaState(b)
        self._alive[b] = True
        self.admitted += 1
        opstats.bump("lanes_admitted")

    def _rescue_superstep(self, stuck: List[int]) -> None:
        """The budget rescue: re-dispatch the stuck lanes only
        (restricted alive mask — every other lane runs k=0 and is
        frozen bit-for-bit) for ONE advance with the FULL round budget.
        Collecting with rescue=True converts lanes that still cannot
        converge into non-convergence deaths, the fleet mirror of the
        solo rescue raising "did not converge"."""
        self.rescues += 1
        restricted = np.zeros(self.B_padded, bool)
        restricted[stuck] = True
        tok = self._superstep_issue_all(k=1, alive=restricted,
                                        round_budget=_MAX_ROUNDS)
        self._superstep_collect_all(tok, rescue=True)

    def _run_pipelined(self, max_supersteps: int,
                       between=None) -> None:
        """The speculative fleet driver: up to ``self.pipeline``
        supersteps in flight beyond the one being collected, FIFO
        collects, discard-on-mutation — the fleet mirror of
        ops.lmm_drain.DrainSim._run_pipelined.  The host's serial
        per-lane ring demux overlaps the device's next vmapped
        superstep; a lane death or budget rescue discards the
        speculative tail (their dispatches assumed a stale alive
        mask)."""
        from collections import deque
        inflight: deque = deque()
        left = max_supersteps
        try:
            while self._alive.any() and left > 0:
                while (not inflight
                       or (len(inflight) <= self.pipeline
                           and len(inflight) < left)):
                    spec = bool(inflight)
                    if inflight:
                        prev = inflight[-1]
                        pen, rem = prev.pen_out, prev.rem_out
                        cb, tpos, t0 = (
                            (prev.cb_out, prev.tpos_out, prev.t0_out)
                            if self.has_tape else (None, None, None))
                        pred, ready, clk = (
                            (prev.pred_out, prev.ready_out,
                             prev.clk_out)
                            if self.has_coll else (None, None, None))
                    else:
                        pen = rem = cb = tpos = t0 = None
                        pred = ready = clk = None
                    inflight.append(self._superstep_issue_all(
                        pen=pen, rem=rem, speculative=spec,
                        cb=cb, tpos=tpos, t0=t0,
                        pred=pred, ready=ready, clk=clk))
                tok = inflight.popleft()
                _n_alive, clean = self._superstep_collect_all(tok)
                left -= 1
                # the between-supersteps hook (serving admission): a
                # truthy return means the hook MUTATED the fleet
                # (admitted a lane), which forces clean=False — the
                # in-flight speculation assumed the old alive mask and
                # state, so it is discarded and replayed
                mutated = bool(between(self)) if between else False
                if not clean or mutated:
                    # a lane death/rescue invalidated the in-flight
                    # alive masks, or a tape fire ended the clean
                    # window — discard and replay from committed state
                    if self.has_tape and self._last_fired and inflight:
                        opstats.bump("fault_replays", len(inflight))
                    if self.has_coll and inflight:
                        opstats.bump("collective_replays",
                                     len(inflight))
                    while inflight:
                        self._discard_token(inflight.popleft())
        finally:
            while inflight:
                self._discard_token(inflight.popleft())

    def run(self, max_supersteps: int = 10_000_000,
            between=None) -> None:
        """Drain every replica to completion (or error).  ``between``
        is called after every committed superstep with the sim as its
        argument (the serving layer's admission window: emit completed
        lanes, admit queued scenarios via :meth:`admit_lane`); a truthy
        return marks the fleet mutated, discarding any in-flight
        speculative supersteps.  The drain continues while the hook
        revives lanes and returns once every lane is dead and the hook
        admits nothing more."""
        if self.pipeline:
            self._run_pipelined(max_supersteps, between=between)
            return
        while self._alive.any() and max_supersteps > 0:
            self.superstep_all()
            if between is not None:
                between(self)
            max_supersteps -= 1

    # -- superstep-boundary checkpoint/resume ------------------------------

    def committed_state(self) -> Dict:
        """Snapshot the fleet's COMMITTED state at a collect boundary:
        the materialized per-lane device arrays (bounds, penalties,
        remaining, thresholds, tape rows + cursors, per-replica weight
        tables), the alive mask, the f64 host clocks and advance
        counts, the committed event/fault-event prefixes (ragged-
        flattened, f64/i64 exact) and the per-lane error/LaneFault
        records.  In-flight pipeline speculation is NEVER part of the
        snapshot — speculative tokens carry their state on their own
        buffers and commit nothing until collected — so a checkpoint
        between supersteps is exactly the state resume replays from
        (the same replay semantics as a mispredict discard)."""
        reps = self.replicas
        arrays = {
            "cb": np.asarray(self._cb),
            "pen": np.asarray(self._pen),
            "rem": np.asarray(self._rem),
            "thresh": np.asarray(self._thresh),
            "alive": self._alive.copy(),
            "tpos": np.asarray(self._tpos),
            "clocks": np.array([r.t for r in reps], np.float64),
            "advances": np.array([r.advances for r in reps],
                                 np.int64),
            "ev_counts": np.array([len(r.events) for r in reps],
                                  np.int64),
            "ev_t": np.array([t for r in reps
                              for t, _ in r.events], np.float64),
            "ev_id": np.array([i for r in reps
                               for _, i in r.events], np.int64),
            "fev_counts": np.array(
                [len(r.fault_events) for r in reps], np.int64),
            "fev_t": np.array([t for r in reps
                               for t, _ in r.fault_events],
                              np.float64),
            "fev_slot": np.array([s for r in reps
                                  for _, s in r.fault_events],
                                 np.int64),
        }
        if self.has_tape:
            tt, ts, tv = self._tape
            arrays["tape_t"] = np.asarray(tt)
            arrays["tape_s"] = np.asarray(ts)
            arrays["tape_v"] = np.asarray(tv)
        if self.has_coll:
            arrays["coll_pred"] = np.asarray(self._coll_pred)
            arrays["coll_ready"] = np.asarray(self._coll_ready)
            arrays["coll_clk"] = np.asarray(self._coll_clk)
            arrays["cev_counts"] = np.array(
                [len(r.collective_events) for r in reps], np.int64)
            arrays["cev_t"] = np.array(
                [t for r in reps for t, _ in r.collective_events],
                np.float64)
            arrays["cev_id"] = np.array(
                [i for r in reps for _, i in r.collective_events],
                np.int64)
        if self.batch_w:
            arrays["ew"] = np.asarray(self._dev[2])
        return {
            "arrays": arrays,
            "errors": [r.error for r in reps],
            "faults": [r.fault.to_dict() if r.fault is not None
                       else None for r in reps],
            "counters": {
                "admitted": self.admitted,
                "supersteps": self.supersteps,
                "syncs": self.syncs,
                "rounds": self.rounds,
                "rescues": self.rescues,
                "pad_events": self.pad_events,
                "spec_issued": self.spec_issued,
                "spec_committed": self.spec_committed,
                "spec_rolled_back": self.spec_rolled_back,
            },
        }

    def restore_state(self, st: Dict) -> None:
        """Adopt a :meth:`committed_state` snapshot into THIS fleet
        (built from the same plan/geometry): uploads the saved device
        arrays, rebuilds every host replica record — committed events,
        fault streams, clocks, errors, LaneFaults — and restores the
        alive mask and counters.  Raises ``ValueError`` on any
        geometry mismatch (a snapshot from a different plan)."""
        arrays = st["arrays"]
        B, Bp = self.B, self.B_padded

        def _chk(name, dtype, shape):
            if name not in arrays:
                raise ValueError(
                    f"fleet snapshot is missing array {name!r}")
            a = np.asarray(arrays[name])
            if tuple(a.shape) != tuple(shape):
                raise ValueError(
                    f"fleet snapshot array {name!r} has shape "
                    f"{a.shape}, this fleet expects {tuple(shape)} — "
                    f"the snapshot is from a different plan")
            return np.ascontiguousarray(a, dtype)

        cb = _chk("cb", self.dtype, (Bp, self.n_c))
        pen = _chk("pen", self.dtype, (Bp, self.n_v))
        rem = _chk("rem", self.dtype, (Bp, self.n_v))
        thresh = _chk("thresh", self.dtype, (Bp, self.n_v))
        alive = _chk("alive", bool, (Bp,))
        tpos = _chk("tpos", np.int32, (Bp,))
        clocks = _chk("clocks", np.float64, (B,))
        advances = _chk("advances", np.int64, (B,))
        ev_counts = _chk("ev_counts", np.int64, (B,))
        fev_counts = _chk("fev_counts", np.int64, (B,))
        ev_t = _chk("ev_t", np.float64, (int(ev_counts.sum()),))
        ev_id = _chk("ev_id", np.int64, (int(ev_counts.sum()),))
        fev_t = _chk("fev_t", np.float64, (int(fev_counts.sum()),))
        fev_slot = _chk("fev_slot", np.int64,
                        (int(fev_counts.sum()),))
        if "tape_t" in arrays:
            if not self.has_tape:
                raise ValueError(
                    "fleet snapshot carries fault tapes but this "
                    "fleet was built without tape capacity (pass "
                    "tape_slots at build)")
            T = self._tape_width
            tt = _chk("tape_t", np.float64, (Bp, T))
            ts = _chk("tape_s", np.int32, (Bp, T))
            tv = _chk("tape_v", self.dtype, (Bp, T))
            self._tape = (self._put_batched(tt),
                          self._put_batched(ts),
                          self._put_batched(tv))
        cev_counts = None
        if "coll_pred" in arrays:
            if not self.has_coll:
                raise ValueError(
                    "fleet snapshot carries a collective schedule "
                    "but this fleet was built without collective=")
            cp = _chk("coll_pred", np.int32, (Bp, self.n_v))
            crd = _chk("coll_ready", np.float64, (Bp, self.n_v))
            ck = _chk("coll_clk", np.float64, (Bp, 2))
            cev_counts = _chk("cev_counts", np.int64, (B,))
            cev_t = _chk("cev_t", np.float64,
                         (int(cev_counts.sum()),))
            cev_id = _chk("cev_id", np.int64,
                          (int(cev_counts.sum()),))
            self._coll_pred = self._put_batched(cp)
            self._coll_ready = self._put_batched(crd)
            self._coll_clk = self._put_batched(ck)
        elif self.has_coll:
            raise ValueError(
                "this fleet carries a collective schedule but the "
                "snapshot has no collective arrays — it is from a "
                "different plan")
        if "ew" in arrays:
            if not self.batch_w:
                raise ValueError(
                    "fleet snapshot carries per-replica weight "
                    "tables but this fleet was built with a shared "
                    "table (pass batch_w=True at build)")
            ew = _chk("ew", self.dtype, tuple(self._dev[2].shape))
            self._dev[2] = self._put_batched(ew)
        self._cb = self._put_batched(cb)
        self._pen = self._put_batched(pen)
        self._rem = self._put_batched(rem)
        self._thresh = self._put_batched(thresh)
        self._tpos = self._put_batched(tpos)
        errors = st.get("errors") or [None] * B
        faults = st.get("faults") or [None] * B
        eo = fo = co = 0
        for b in range(B):
            rep = ReplicaState(b)
            n_e, n_f = int(ev_counts[b]), int(fev_counts[b])
            rep.events = [(float(ev_t[eo + j]), int(ev_id[eo + j]))
                          for j in range(n_e)]
            rep.fault_events = [(float(fev_t[fo + j]),
                                 int(fev_slot[fo + j]))
                                for j in range(n_f)]
            eo += n_e
            fo += n_f
            if cev_counts is not None:
                n_cv = int(cev_counts[b])
                rep.collective_events = [
                    (float(cev_t[co + j]), int(cev_id[co + j]))
                    for j in range(n_cv)]
                co += n_cv
            rep.t = float(clocks[b])
            rep.advances = int(advances[b])
            rep.alive = bool(alive[b])
            rep.error = errors[b]
            rep.fault = (LaneFault.from_dict(faults[b])
                         if faults[b] else None)
            self.replicas[b] = rep
        self._alive = alive.copy()
        c = st.get("counters") or {}
        self.admitted = int(c.get("admitted", 0))
        self.supersteps = int(c.get("supersteps", 0))
        self.syncs = int(c.get("syncs", 0))
        self.rounds = int(c.get("rounds", 0))
        self.rescues = int(c.get("rescues", 0))
        self.pad_events = int(c.get("pad_events", 0))
        self.spec_issued = int(c.get("spec_issued", 0))
        self.spec_committed = int(c.get("spec_committed", 0))
        self.spec_rolled_back = int(c.get("spec_rolled_back", 0))

    # -- results -----------------------------------------------------------

    def events_of(self, b: int) -> List[Tuple[float, int]]:
        return self.replicas[b].events

    def clock_of(self, b: int) -> float:
        return self.replicas[b].t
