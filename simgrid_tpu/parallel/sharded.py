"""Mesh-sharded and batched LMM solves (multi-chip path).

Design (not a translation — the reference is single-core C++ with
intrusive lists, maxmin.cpp:502-693):

* ``sharded_solve``: ONE huge system, its element (COO) arrays split
  over the mesh axis ``"elem"``.  Each saturation round is: local
  segment-sum/segment-max scatters into full-size constraint/variable
  vectors, then one ``psum``/``pmax`` over ICI to combine shards.  The
  whole fixpoint stays inside a single ``lax.while_loop`` under
  ``shard_map`` — the loop condition depends only on replicated values,
  so all chips iterate in lockstep and there is exactly one collective
  pair per round.
* ``batched_solve``: MANY independent systems (each with its OWN COO
  structure) vmapped on a leading batch axis, the batch sharded over
  the mesh axis ``"sim"`` — for heterogeneous sweeps and model-checker
  branch exploration.
* ``sharded_step``: one full step (solve → completion-time min-reduce
  → advance), batched + element-sharded on a 2-D ``("sim", "elem")``
  mesh.

This module owns the ELEMENT-sharding axis only.  The production
replica-sharded path — fleets of scenarios over ONE shared platform
flattening, drained to completion with per-shard completion rings,
alive masks and speculative pipelining — lives in ``ops.lmm_batch``
(``BatchDrainSim(mesh=...)`` / ``solve_arrays_batch(mesh=...)``) and
is driven by ``parallel.campaign``; this prototype's earlier
duplicated fixpoint/step wrappers were rebased onto the shared kernel
programs (``ops.lmm_jax._solve_chunk_batched_lane``,
``ops.lmm_drain._advance_math``), so the fixpoint and advance logic
exist exactly once.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.lmm_drain import _advance_math
from ..ops.lmm_jax import (_MAX_ROUNDS, LmmArrays, _solve_chunk_batched_lane,
                           check_convergence, fixpoint, use_local_rounds)

def make_mesh(n_devices: Optional[int] = None, sim: int = 1,
              devices=None) -> Mesh:
    """Build a ("sim", "elem") mesh over the first n_devices devices."""
    if devices is None:
        devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    assert n_devices % sim == 0, \
        f"sim={sim} must divide n_devices={n_devices} for a (sim, elem) mesh"
    devices = np.asarray(devices[:n_devices]).reshape(sim, n_devices // sim)
    return Mesh(devices, axis_names=("sim", "elem"))


def _pad_to(x: np.ndarray, n: int, fill=0):
    if len(x) == n:
        return x
    out = np.full(n, fill, x.dtype)
    out[:len(x)] = x
    return out


@functools.lru_cache(maxsize=64)
def _sharded_run(mesh: Mesh, axis: str, n_c: int, n_v: int,
                 parallel_rounds: bool = False):
    """Memoized jitted element-sharded fixpoint (jax.jit caches per
    function identity, so the wrapper must be reused across calls)."""
    espec = NamedSharding(mesh, P(axis))
    rspec = NamedSharding(mesh, P())

    @functools.partial(
        jax.jit,
        in_shardings=(espec, espec, espec, rspec, rspec, rspec, rspec, rspec),
        out_shardings=rspec)
    def run(e_var, e_cnst, e_w, c_bound, c_fatpipe, v_penalty, v_bound, eps):
        fn = jax.shard_map(
            functools.partial(fixpoint, n_c=n_c, n_v=n_v, axis=axis,
                              parallel_rounds=parallel_rounds),
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(), P(), P(), P(), P()),
            out_specs=P(), check_vma=False)
        return fn(e_var, e_cnst, e_w, c_bound, c_fatpipe, v_penalty,
                  v_bound, eps)

    return run


@functools.lru_cache(maxsize=64)
def _batched_run(n_c: int, n_v: int, parallel_rounds: bool = False):
    """Memoized jitted vmapped solve for batches of independent
    systems, rebased onto the SHARED chunk lane
    (ops.lmm_jax._solve_chunk_batched_lane — the same raw function
    behind ops.lmm_batch's fleet kernels), so the fixpoint wrapper
    logic exists once.  Here each lane carries its own COO structure,
    hence the extra vmapped axes."""
    def lane(e_var, e_cnst, e_w, c_bound, c_fatpipe, v_penalty, v_bound,
             eps):
        out = _solve_chunk_batched_lane(
            e_var, e_cnst, e_w, c_bound, c_fatpipe, v_penalty, v_bound,
            None, eps, n_c, n_v, parallel_rounds, _MAX_ROUNDS,
            True, True)
        return out[:4]
    return jax.jit(jax.vmap(lane, in_axes=(0, 0, 0, 0, 0, 0, 0, None)))


def sharded_solve(arrays: LmmArrays, eps: float, mesh: Mesh,
                  axis: str = "elem"):
    """Solve one big system with its element list sharded over ``axis``.

    Returns (values, remaining, usage, rounds) as numpy, identical to the
    single-device kernel (the combine order changes only the summation
    order of non-negative float contributions; ties in the min-reduce are
    still detected by exact equality on replicated vectors).
    """
    n_shards = mesh.shape[axis]
    E = len(arrays.e_var)
    Ep = -(-E // n_shards) * n_shards
    e_var = _pad_to(arrays.e_var, Ep)
    e_cnst = _pad_to(arrays.e_cnst, Ep)
    e_w = _pad_to(arrays.e_w, Ep)
    n_c, n_v = len(arrays.c_bound), len(arrays.v_penalty)

    run = _sharded_run(mesh, axis, n_c, n_v, use_local_rounds())
    values, remaining, usage, rounds = run(
        e_var, e_cnst, e_w, arrays.c_bound, arrays.c_fatpipe,
        arrays.v_penalty, arrays.v_bound, np.asarray(eps, e_w.dtype))
    rounds = int(rounds)
    check_convergence(rounds, arrays.n_cnst, arrays.n_var)
    return (np.asarray(values), np.asarray(remaining), np.asarray(usage),
            rounds)


def batched_solve(batch: LmmArrays, eps: float, mesh: Optional[Mesh] = None,
                  axis: str = "sim"):
    """Solve a batch of independent systems (leading axis on every array),
    vmapped, with the batch axis sharded over ``axis`` when a mesh is
    given.  All systems share the padded shapes; disabled slots are
    weight-0 padding, so ragged batches just pad."""
    n_c = batch.c_bound.shape[-1]
    n_v = batch.v_penalty.shape[-1]

    vsolve = _batched_run(n_c, n_v, use_local_rounds())
    eps_arr = np.asarray(eps, batch.e_w.dtype)

    args = (batch.e_var, batch.e_cnst, batch.e_w, batch.c_bound,
            batch.c_fatpipe, batch.v_penalty, batch.v_bound)
    if mesh is not None:
        bspec = NamedSharding(mesh, P(axis))
        args = tuple(jax.device_put(a, bspec) for a in args)
    values, remaining, usage, rounds = vsolve(*args, eps_arr)
    rounds = np.asarray(rounds)
    check_convergence(int(rounds.max()), n_c, n_v)
    return (np.asarray(values), np.asarray(remaining), np.asarray(usage),
            rounds)


def sharded_step(mesh: Mesh, parallel_rounds=None):
    """Build the flagship jitted full step on a ("sim", "elem") mesh.

    One step of a batch of simulations: solve every system's rate vector
    (element-sharded within each sim, batch sharded over "sim"), derive
    each action's completion time from its remaining work, min-reduce to
    the next event date, and advance all remaining-work vectors by the
    elapsed interval — the device side of surf_solve
    (surf_c_bindings.cpp:45-151) for a fleet of simulations.

    Returns ``step(e_var, e_cnst, e_w, c_bound, c_fatpipe, v_penalty,
    v_bound, v_remains, eps) -> (v_values, v_remains', dt)`` with a
    leading batch axis on every operand.
    """
    n_elem_shards = mesh.shape["elem"]
    # Captured at factory time (the returned step is a fixed compiled
    # artifact); pass parallel_rounds explicitly to override the flag.
    if parallel_rounds is None:
        parallel_rounds = use_local_rounds()

    def one_sim(e_var, e_cnst, e_w, c_bound, c_fatpipe, v_penalty, v_bound,
                v_remains, eps):
        n_c, n_v = c_bound.shape[0], v_penalty.shape[0]
        values, remaining, usage, rounds = fixpoint(
            e_var, e_cnst, e_w, c_bound, c_fatpipe, v_penalty, v_bound,
            eps, n_c=n_c, n_v=n_v, axis="elem",
            parallel_rounds=parallel_rounds)
        # dt/advance rides the shared drain-step math
        # (ops.lmm_drain._advance_math): flows with exhausted remains
        # are masked out of the min via penalty 0, threshold 0 keeps
        # the retire semantics out of this rate-level step — the exact
        # lane at the min date lands on remains == 0.0
        pen_live = jnp.where(v_remains > 0, v_penalty, 0.0)
        dt, _pen2, rem2, _done = _advance_math(
            pen_live, v_remains, jnp.zeros_like(v_remains), values)
        dt = jnp.where(jnp.isfinite(dt), dt, 0.0)
        return values, rem2, dt

    espec = P("sim", "elem")  # [sim, E] element arrays

    def step(e_var, e_cnst, e_w, c_bound, c_fatpipe, v_penalty, v_bound,
             v_remains, eps):
        fn = jax.shard_map(
            jax.vmap(one_sim,
                     in_axes=(0, 0, 0, 0, 0, 0, 0, 0, None)),
            mesh=mesh,
            in_specs=(espec, espec, espec,
                      P("sim"), P("sim"), P("sim"), P("sim"), P("sim"),
                      P()),
            out_specs=(P("sim"), P("sim"), P("sim")), check_vma=False)
        return fn(e_var, e_cnst, e_w, c_bound, c_fatpipe, v_penalty,
                  v_bound, v_remains, eps)

    in_shardings = tuple(
        NamedSharding(mesh, s) for s in
        (espec, espec, espec, P("sim"), P("sim"), P("sim"), P("sim"),
         P("sim"), P()))
    out_shardings = tuple(NamedSharding(mesh, P("sim")) for _ in range(3))
    jitted = jax.jit(step, in_shardings=in_shardings,
                     out_shardings=out_shardings)
    jitted.n_elem_shards = n_elem_shards
    return jitted


def assert_sharded_matches_at_scale(n_devices: int,
                                    n_c: int = 16384, n_v: int = 100_000,
                                    deg: int = 4, devices=None) -> str:
    """BASELINE-scale consistency check: the
    (elem-)sharded solve over `n_devices` devices must equal the
    single-device solve to 1e-12.  Runs on ``devices`` (default
    ``jax.devices()``) in their own solver dtype (f64 on a CPU mesh,
    the oracle precision; f32 with a matching tolerance on TPUs).
    Shared by
    tests/test_parallel.py and __graft_entry__.dryrun_multichip so the
    check cannot drift between the two."""
    import numpy as _np

    from bench import build_arrays
    from ..ops import lmm_jax
    from ..ops.device import solve_dtype

    if devices is None:
        devices = jax.devices()
    dtype = solve_dtype(None, "assert_sharded_matches_at_scale",
                        devices[0])
    eps, tol = (1e-9, 1e-12) if dtype == _np.float64 else (1e-5, 1e-4)
    # simlint: ignore[wallclock-rng] -- fixed-seed scenario generator for the self-check harness; never feeds simulation state
    big = build_arrays(_np.random.default_rng(42), n_c, n_v, deg, dtype)
    v1, r1, u1, rounds1 = lmm_jax.solve_arrays(big, eps,
                                               device=devices[0],
                                               parallel_rounds=True)
    mesh = make_mesh(n_devices, sim=1, devices=devices)
    v8, r8, u8, rounds8 = sharded_solve(big, eps, mesh)
    _np.testing.assert_allclose(v8, v1, rtol=tol, atol=tol)
    _np.testing.assert_allclose(r8, r1, rtol=tol, atol=tol)
    _np.testing.assert_allclose(u8, u1, rtol=tol, atol=tol)
    return (f"sharded {n_v}-flow solve over {n_devices} devices matches "
            f"single-device ({rounds8} rounds vs {rounds1})")
