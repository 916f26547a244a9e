"""Entry ``lmm_jax.solve_arrays``: back-to-back whole-system max-min
solves of the flattened flow set, each ending in a fetched result."""

from __future__ import annotations

import time

import numpy as np

from lib import traffic
from lib.compare import Compared, digest, rate_gap

from . import _inputs


def setup(run):
    from simgrid_tpu.ops import lmm_jax

    tr = run.cell.traffic
    pairs = traffic.flow_pairs(tr["flows"], _inputs.n_hosts(run), run.seed)
    src, slot_flow = _inputs.flattened(run, pairs)
    dtype, eps = _inputs.solve_precision(run)
    arrays = src._replace(
        e_w=src.e_w.astype(dtype), c_bound=src.c_bound.astype(dtype),
        v_penalty=src.v_penalty.astype(dtype),
        v_bound=src.v_bound.astype(dtype))
    state = dict(pairs=pairs, slot_flow=slot_flow, arrays=arrays, eps=eps,
                 n_var=src.n_var)
    with run.spans.span("warmup"):
        solve(state, lmm_jax)
    return state


def solve(state, lmm_jax):
    """The timed call: one solve, its rates fetched to the host."""
    values, _rem, _use, rounds = lmm_jax.solve_arrays(state["arrays"],
                                                      state["eps"])
    return np.asarray(values)[:state["n_var"]], int(rounds)


def window(run, state):
    from simgrid_tpu.ops import lmm_jax

    digests, rounds, last = [], 0, None
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < run.seconds:
        with run.spans.span("solve"):
            last, r = solve(state, lmm_jax)
        rounds += r
        digests.append(digest(last))
    wall = time.perf_counter() - t0
    return dict(wall_s=wall, solves=len(digests), rounds=rounds,
                digests=digests, rates=last, attempted=len(digests),
                failed=0)


def release(run, state):
    state.pop("arrays", None)


def by_flow(state, rates: np.ndarray) -> np.ndarray:
    out = np.zeros(len(state["pairs"]))
    out[state["slot_flow"]] = rates
    return out


def check(run, state, rec, precision: str = "f64") -> Compared:
    """Every solve of the window gave the same rates, and they are the
    reference's.  ``precision="bf16"`` is the control: the reference in
    bfloat16 put in the program's place."""
    limits = run.cell.traffic["limits"]
    ref_sys = _inputs.reference_system(run, state["pairs"], False)
    ref, _ = run.cell.reference.maxmin_solve(ref_sys, eps=1e-9)
    if precision == "f64":
        got = by_flow(state, rec["rates"])
    else:
        got, _ = run.cell.reference.maxmin_solve(
            ref_sys, eps=state["eps"], precision=precision)
    floor = 2.0 * state["eps"] * float(np.max(ref_sys.c_bound))
    out = Compared()
    out.add("rate_gap", rate_gap(got, ref, floor), limits["rate_gap"])
    out.add("solves_differing",
            sum(d != rec["digests"][0] for d in rec["digests"]),
            limits["solves_differing"])
    return out


def end_to_end(run, rec):
    return {"solve_ms": 1e3 * rec["wall_s"] / rec["solves"]}
