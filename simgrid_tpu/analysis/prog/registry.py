"""The program registry: every jitted kernel program, with a small-N
example-args factory.

Each :class:`ProgramSpec` names one jitted program, its
:class:`~.contract.ProgramContract`, and a ``make(scale)`` factory
returning the exact ``(args, statics)`` a production driver would
dispatch it with at a tiny example geometry (``make(scale, dtype)``
stages the same dispatch in another solve dtype).  For the drain/fleet
programs the factory does not re-derive the argument assembly — it
builds a real (tiny) sim and *captures* the driver's own dispatch by
swapping the module-level jit wrapper for a raiser, so the registry
can never drift out of sync with the issue paths.  The warm-solver
programs take flat array arguments with no driver state, so their
factories construct arguments directly.

``scale`` selects one of two example geometries (the retrace-surface
rule lowers both and diffs the closed-over constants); everything is
deterministic arithmetic — no RNG, no wallclock.
"""

from __future__ import annotations

import inspect
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Tuple

import numpy as np

from .contract import ProgramContract

#: dtypes every drain program may touch beyond its solve dtype:
#: indices/slots (i32), flow-id math and the bitcast detour (i64),
#: masks (bool), and the f64 spine — base clocks, Kahan pair, tape
#: dates, collective activation dates (the event-ordering oracle).
_F64_WHY = ("Kahan clock pair, f64 base clock, fault-tape dates and "
            "the collective event-ordering oracle")
_COMMON = ("int32", "int64", "bool", "uint32")


def _drain_contract(solve_dtype: str) -> ProgramContract:
    allowed = (solve_dtype, "float64") + _COMMON
    why = {"float64": _F64_WHY} if solve_dtype != "float64" else {}
    return ProgramContract(
        solve_dtype=solve_dtype,
        allowed_dtypes=tuple(dict.fromkeys(allowed)),
        dtype_why=why,
        expected_outputs=8,
        fma_pinned=True)


@dataclass(frozen=True)
class ProgramSpec:
    """One registered program: the jitted callable (whose
    ``.trace()`` / ``.lower()`` staging proglint reuses — the same
    AOT path the serving plan cache compiles through), the raw
    program function (argument-name -> position lookups for the
    donation rule), the contract, and the example-args factory."""

    name: str
    jitted: Any
    program: Callable
    contract: ProgramContract
    #: ``make(scale, dtype=<the contract's solve dtype>)``; another
    #: dtype stages the same program for a device whose solver dtype
    #: differs (chip_smoke.py's compile sweep on the TPU)
    make: Callable[..., Tuple[tuple, Dict[str, Any]]]


class _Captured(Exception):
    def __init__(self, args, statics):
        super().__init__("captured")
        self.args = args
        self.statics = statics


def _capture(module, attr: str, drive: Callable[[], Any]):
    """Swap ``module.attr`` (a jit wrapper) for a raiser, run the
    driver, and return the exact (args, statics) it dispatched —
    without executing (or even tracing) the program."""
    real = getattr(module, attr)

    def raiser(*args, **statics):
        raise _Captured(args, statics)

    setattr(module, attr, raiser)
    try:
        try:
            drive()
        except _Captured as cap:
            return cap.args, cap.statics
    finally:
        setattr(module, attr, real)
    raise RuntimeError(
        f"example driver never dispatched {module.__name__}.{attr}")


# ---------------------------------------------------------------------------
# Example geometries (deterministic, tiny)
# ---------------------------------------------------------------------------

def _geometry(scale: int):
    """Two distinct example geometries; both trace in milliseconds."""
    n_c = 4 + 2 * (scale - 1)
    n_v = 8 + 8 * (scale - 1)
    return n_c, n_v


def _arrays(scale: int, dtype):
    n_c, n_v = _geometry(scale)
    deg = 2
    e_var = np.repeat(np.arange(n_v, dtype=np.int32), deg)
    e_cnst = (np.arange(n_v * deg, dtype=np.int32) * 3 + 1) % n_c
    e_w = (0.5 + (np.arange(n_v * deg) % 4) * 0.25).astype(dtype)
    c_bound = (2.0 + np.arange(n_c)).astype(dtype)
    sizes = 1.0 + (np.arange(n_v) % 5).astype(np.float64)
    return e_var, e_cnst, e_w, c_bound, sizes


def _tape(n_c: int):
    return (np.array([0.25, 0.75]), np.array([0, min(1, n_c - 1)]),
            np.array([1.5, 2.5]))


def _collective(n_v: int):
    """A tiny chain DAG: flow i+1 waits on flow i, and the root starts
    live (the factories' penalties), so undated."""
    pred = np.zeros(n_v, np.int32)
    pred[1:] = 1
    ready = np.full(n_v, np.inf)
    edge_src = np.arange(n_v - 1, dtype=np.int32)
    edge_dst = np.arange(1, n_v, dtype=np.int32)
    exec_cost = np.full(n_v, 0.125)
    return pred, ready, edge_src, edge_dst, exec_cost


# ---------------------------------------------------------------------------
# Factories: solo drain programs (captured from DrainSim drivers)
# ---------------------------------------------------------------------------

def _solo_superstep(scale: int, dtype, tape=False, coll=False):
    from simgrid_tpu.ops import lmm_drain as ld

    e_var, e_cnst, e_w, c_bound, sizes = _arrays(scale, dtype)
    n_c, n_v = _geometry(scale)
    kw: Dict[str, Any] = dict(eps=1e-9, dtype=dtype, superstep=2,
                              repack_min=1 << 62)
    if tape:
        kw["tape"] = _tape(n_c)
    if coll:
        # the sim adds the four inputs only a collective dispatch has:
        # (v_ptr, ve_idx), its element list's variable-major index, and
        # (s_ptr, s_dst), its DAG's source-major one (every other sim
        # passes None for them: no argument at all)
        kw["collective"] = _collective(n_v)
        # dormant successors: only the DAG root starts live
        pen = np.zeros(n_v)
        pen[0] = 1.0
        kw["penalty"] = pen
    sim = ld.DrainSim(e_var, e_cnst, e_w, c_bound, sizes, **kw)
    return _capture(ld, "_drain_superstep",
                    lambda: sim.superstep_batch(k=1))


def _solo_chunk(scale: int, dtype):
    from simgrid_tpu.ops import lmm_drain as ld

    e_var, e_cnst, e_w, c_bound, sizes = _arrays(scale, dtype)
    sim = ld.DrainSim(e_var, e_cnst, e_w, c_bound, sizes, eps=1e-9,
                      dtype=dtype, repack_min=1 << 62)
    return _capture(ld, "_drain_solve_chunk", sim.solve_rates)


# ---------------------------------------------------------------------------
# Factories: fleet programs (captured from BatchDrainSim drivers)
# ---------------------------------------------------------------------------

#: the fleet program's arguments a driver builds in the solve dtype
_FLEET_SOLVE_ARGS = ("e_w", "c_bound", "v_bound", "pen", "rem", "thresh",
                     "tape_val")


def _fleet_superstep(scale: int, dtype, tape=False, coll=False,
                     device=None):
    from simgrid_tpu.ops import lmm_batch as lb

    if coll and np.dtype(dtype) != np.float64:
        # BatchDrainSim still refuses a float32 collective by name (its
        # collect reads the ring's dates in the solve dtype: ROADMAP
        # reach A.1); the PROGRAM has a float32 form since the solo
        # tape got one.  Stage the float64 dispatch (on the host's CPU
        # backend, where float64 is allowed whatever the default device
        # is) with the arguments a driver would have built in the solve
        # dtype narrowed.
        import jax
        args, statics = _fleet_superstep(scale, np.float64, tape, coll,
                                         device=jax.devices("cpu")[0])
        names = inspect.signature(lb._batch_superstep_program).parameters
        return tuple(np.asarray(a, dtype if name in _FLEET_SOLVE_ARGS
                                else None)
                     for name, a in zip(names, args)), statics
    e_var, e_cnst, e_w, c_bound, sizes = _arrays(scale, dtype)
    n_c, n_v = _geometry(scale)
    overrides = [lb.ReplicaOverrides(),
                 lb.ReplicaOverrides(bw_scale=1.25)]
    kw: Dict[str, Any] = dict(eps=1e-9, dtype=dtype, superstep=2,
                              device=device)
    if tape:
        tt, ts, tv = _tape(n_c)
        kw["tapes"] = [(tt, ts, tv), (tt, ts, tv * 0.5)]
    if coll:
        kw["collective"] = _collective(n_v)
        pen = np.zeros(n_v)
        pen[0] = 1.0
        kw["penalty"] = pen
    sim = lb.BatchDrainSim(e_var, e_cnst, e_w, c_bound, sizes,
                           overrides, **kw)
    return _capture(lb, "_batch_superstep",
                    lambda: sim.superstep_all())


# ---------------------------------------------------------------------------
# Factories: warm-start solver programs (flat arguments)
# ---------------------------------------------------------------------------

def _warm_init_args(scale: int, dtype):
    e_var, e_cnst, e_w, c_bound, _sizes = _arrays(scale, dtype)
    n_c, n_v = _geometry(scale)
    args = (e_var, e_cnst, e_w, c_bound,
            np.zeros(n_c, bool),                     # c_fatpipe
            np.ones(n_v, dtype),                     # v_penalty
            np.full(n_v, 0.25, dtype),               # prev_value
            (0.5 * c_bound).astype(dtype),           # prev_remaining
            (0.5 * c_bound).astype(dtype),           # prev_usage
            np.array([1], np.int32))                 # mc_idx
    return args, dict(eps=1e-9)


def _apply_deltas_args(scale: int, dtype):
    e_var, e_cnst, e_w, c_bound, _sizes = _arrays(scale, dtype)
    n_c, n_v = _geometry(scale)
    # one dirty c_bound slot: [index, value] runs, field 3 = c_bound
    payload = np.array([1.0, 3.5], np.float64)
    args = (payload, e_var, e_cnst, e_w, c_bound,
            np.zeros(n_c, bool),
            np.ones(n_v, dtype),
            np.full(n_v, -1.0, dtype))
    return args, dict(layout=((3, 0, 1),))


# ---------------------------------------------------------------------------
# The registry
# ---------------------------------------------------------------------------

def iter_programs() -> List[ProgramSpec]:
    """Every registered program, contracts attached.  Imports the ops
    modules lazily so the analysis package stays importable without
    jax (the AST half never needs it)."""
    from simgrid_tpu.ops import lmm_batch as lb
    from simgrid_tpu.ops import lmm_drain as ld
    from simgrid_tpu.ops import lmm_warm as lw

    f64, f32 = np.float64, np.float32
    # the solve surface: (carry..., stats) — measured from the
    # program's return tuple, pinned so growth is a finding
    chunk_out = 7      # fixpoint carry legs + stats
    specs = [
        ProgramSpec(
            "drain/superstep", ld._drain_superstep,
            ld._superstep_program, _drain_contract("float64"),
            lambda s, dt=f64: _solo_superstep(s, dt)),
        ProgramSpec(
            "drain/superstep_f32", ld._drain_superstep,
            ld._superstep_program, _drain_contract("float32"),
            lambda s, dt=f32: _solo_superstep(s, dt)),
        ProgramSpec(
            "drain/superstep_tape", ld._drain_superstep,
            ld._superstep_program, _drain_contract("float64"),
            lambda s, dt=f64: _solo_superstep(s, dt, tape=True)),
        ProgramSpec(
            "drain/superstep_coll", ld._drain_superstep,
            ld._superstep_program, _drain_contract("float64"),
            lambda s, dt=f64: _solo_superstep(s, dt, coll=True)),
        ProgramSpec(
            "drain/superstep_coll_f32", ld._drain_superstep,
            ld._superstep_program, _drain_contract("float32"),
            lambda s, dt=f32: _solo_superstep(s, dt, coll=True)),
        ProgramSpec(
            "drain/solve_chunk", ld._drain_solve_chunk,
            ld._solve_chunk_program,
            ProgramContract(
                solve_dtype="float64",
                allowed_dtypes=("float64",) + _COMMON,
                expected_outputs=chunk_out,
                donated=(), fma_pinned=False),
            lambda s, dt=f64: _solo_chunk(s, dt)),
        ProgramSpec(
            "fleet/superstep", lb._batch_superstep,
            lb._batch_superstep_program, _drain_contract("float64"),
            lambda s, dt=f64: _fleet_superstep(s, dt)),
        ProgramSpec(
            "fleet/superstep_f32", lb._batch_superstep,
            lb._batch_superstep_program, _drain_contract("float32"),
            lambda s, dt=f32: _fleet_superstep(s, dt)),
        ProgramSpec(
            "fleet/superstep_tape", lb._batch_superstep,
            lb._batch_superstep_program, _drain_contract("float64"),
            lambda s, dt=f64: _fleet_superstep(s, dt, tape=True)),
        ProgramSpec(
            "fleet/superstep_coll", lb._batch_superstep,
            lb._batch_superstep_program, _drain_contract("float64"),
            lambda s, dt=f64: _fleet_superstep(s, dt, coll=True)),
        ProgramSpec(
            "warm/warm_init", lw._warm_init,
            lw._warm_init.__wrapped__,
            ProgramContract(
                solve_dtype="float64",
                allowed_dtypes=("float64",) + _COMMON,
                expected_outputs=6, donated=(), fma_pinned=False),
            lambda s, dt=f64: _warm_init_args(s, dt)),
        ProgramSpec(
            "warm/apply_deltas", lw._apply_deltas,
            lw._apply_deltas.__wrapped__,
            ProgramContract(
                solve_dtype="float64",
                allowed_dtypes=("float64",) + _COMMON,
                expected_outputs=7, donated=(), fma_pinned=False),
            lambda s, dt=f64: _apply_deltas_args(s, dt)),
    ]
    return specs
