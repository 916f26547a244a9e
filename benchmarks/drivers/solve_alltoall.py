"""Entry ``lmm_jax.solve_arrays`` under an alltoall: every ordered pair
of the traffic file's ranks posted at once (SimGrid's
``alltoall-basic-linear``), then back-to-back whole-system max-min
solves of the flattened flow set, each ending in a fetched result.
The pair list is this driver's; the front end, the timed call and the
comparison are ``_inputs``' and ``solve``'s."""

from __future__ import annotations

import time

import numpy as np

from lib.compare import digest

from . import _inputs
from . import solve as _solve

check = _solve.check
release = _solve.release


def alltoall_pairs(alltoall, n_hosts: int, seed: int) -> np.ndarray:
    """The R(R-1) ordered (src, dst) host pairs of an alltoall among
    ``ranks`` ranks, rank r on host r x ``stride``.  The ranks post in
    rank order, as SMPI schedules its actors; each posts its R-1 sends
    in an order of its own, drawn from the run's seed (seeds run past
    2**31: SeedSequence takes any non-negative integer).  Every seed is
    the same work under another numbering: the float32 solve is the
    same to the bit, which an order drawn over ALL pairs is not (it
    moves the round count by a tenth, PERF.md)."""
    ranks, stride = int(alltoall["ranks"]), int(alltoall["stride"])
    if ranks < 2 or stride < 1 or (ranks - 1) * stride >= n_hosts:
        raise ValueError(f"{ranks} ranks at stride {stride} do not fit "
                         f"{n_hosts} hosts")
    rng = np.random.default_rng([int(seed), 1])
    src = np.repeat(np.arange(ranks), ranks - 1)
    peer = rng.permuted(np.tile(np.arange(ranks - 1), (ranks, 1)),
                        axis=1).ravel()
    dst = peer + (peer >= src)            # a rank sends nothing to itself
    return np.stack([src, dst], axis=1) * stride


def setup(run):
    from simgrid_tpu.ops import lmm_jax

    tr = run.cell.traffic
    pairs = alltoall_pairs(tr["alltoall"], _inputs.n_hosts(run), run.seed)
    src, slot_flow = _inputs.flattened(run, pairs)
    dtype, eps = _inputs.solve_precision(run)
    arrays = src._replace(
        e_w=src.e_w.astype(dtype), c_bound=src.c_bound.astype(dtype),
        v_penalty=src.v_penalty.astype(dtype),
        v_bound=src.v_bound.astype(dtype))
    state = dict(pairs=pairs, slot_flow=slot_flow, arrays=arrays, eps=eps,
                 n_var=src.n_var)
    with run.spans.span("warmup"):
        _solve.solve(state, lmm_jax)
    return state


def window(run, state):
    """Solves until ``--seconds`` have passed AND ``min_solves`` are
    done: one solve of the deep system outlasts a short window, and
    ``solves_differing`` needs two to compare."""
    from simgrid_tpu.ops import lmm_jax

    least = int(run.cell.traffic["min_solves"])
    digests, rounds, last = [], 0, None
    t0 = time.perf_counter()
    while len(digests) < least or time.perf_counter() - t0 < run.seconds:
        with run.spans.span("solve"):
            last, r = _solve.solve(state, lmm_jax)
        rounds += r
        digests.append(digest(last))
    wall = time.perf_counter() - t0
    return dict(wall_s=wall, solves=len(digests), rounds=rounds,
                digests=digests, rates=last, attempted=len(digests),
                failed=0)


def end_to_end(run, rec):
    return {"solve_ms": 1e3 * rec["wall_s"] / rec["solves"]}
