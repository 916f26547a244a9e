#!/usr/bin/env python3
"""What one element-wide gather or scatter of the COO round costs on the
chip, at config #4's sizes, and what a whole round costs:

    chiprun -- python tools/coo_round_probe.py [--only ops|rounds|ladder]

Builds the 65,536-host dragonfly's max-min system of 100,000 random
flows with the benchmark's numpy reference (no engine: seconds), in
``flatten``'s element order (constraint-major), and times on the TPU

* each indexed op kind alone, K times inside one ``lax.fori_loop`` of
  one dispatch (gather by ``e_var`` / by ``e_cnst``, scatter to the
  variables / to the constraints, 1-wide, 2-wide as ``fixpoint``'s
  entry issues it (ISSUE 32) and 3-wide as its round does), at the
  drain's padding ([E/8, 8], 1,241,664) and at ``solve_arrays``' pow2
  padding (1-D, 2,097,152), there also into the 16,384 constraint rows
  of the alltoall's padded system (``to_c16k``: the same list, its
  constraint numbers scaled down, still non-decreasing); and whether
  a sum scattered as column 0 of a 2-wide window keeps the 1-wide
  scatter's bits (``column0``);
* ``lmm_jax.fixpoint`` to convergence, wall over rounds, the device
  being busy all of it: as the solve cell runs it (LV08 penalties and
  window bounds, which never bind; pow2 padding); the same system with
  every second flow's bound at half its fair rate, so the bound block
  behind the round's ``lax.cond`` RUNS (rates held to the reference's,
  the taken round priced against the skipped one); those two as the
  lanes of one ``vmap`` (the cond a select, both sides run); and as
  the drain's superstep runs it (unit penalty, no bounds, [E/8, 8]);
* the ladder of ``fixpoint`` (ISSUE 30), rung by rung in both layouts:
  the live-first partition down to the next rung as the program does
  it (``lmm_jax._livefirst_head``: cumsum, one scatter, ONE gather of
  the lists as rows: ``packed_ms``), with a gather a list
  (``partition_ms``), and as one stable ``lax.sort`` carrying the lists
  as payload (``sort_ms``; the three must agree), one bound-free
  round of the single loop over a list of that size (5 rounds less
  1, so entry drops out), and one
  3-wide scatter-add of that many indices, all of it once more at half
  the last rung's size, which the ladder's floor refuses: does a round
  go by its indices down to 2^15, and where does its fixed part start
  to show.

One JSON line per reading on stdout, all of them appended to
``chiprun_out/coo_round_probe.jsonl``.  Its readings are device times:
without a TPU it exits 2, prints nothing on stdout and writes no file
(``tests/test_coo_round_probe.py`` checks the plumbing on a CPU, at
128 hosts, and writes nothing).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmarks")]
OUT = os.path.join(ROOT, "chiprun_out", "coo_round_probe.jsonl")

K = 20          # ops per dispatch in the op-price loops
ALLTOALL_ROWS = 16_384      # dfly65k-alltoall's 8,724 constraints, padded


def system(config: str, unit_penalty: bool):
    """The system of ``benchmarks/configs/<config>.json`` from the
    benchmark's reference, elements in flatten's order (by constraint,
    stable)."""
    from configs import dragonfly_lv08 as ref
    from lib import traffic

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    p = cfg["platform"]
    pairs = traffic.draw_pairs(p["hosts"], cfg["flows"], 42)
    s = ref.dragonfly_system(p["topo"], float(p["bw_bytes_per_s"]),
                             float(p["lat_s"]), pairs,
                             unit_penalty=unit_penalty)
    order = np.argsort(s.e_cnst, kind="stable")
    return s._replace(e_var=s.e_var[order], e_cnst=s.e_cnst[order],
                      e_w=s.e_w[order])


def binding(s):
    """``s`` with every second flow's bound at half the rate max-min
    gives it, so bounds bind from the first round on; and the
    reference's rates of that system."""
    from configs import dragonfly_lv08 as ref

    rates, _ = ref.maxmin_solve(s)
    v_bound = s.v_bound.copy()
    v_bound[::2] = 0.5 * rates[::2]
    s = s._replace(v_bound=v_bound)
    return s, ref.maxmin_solve(s)[0]


def padded(a, n, fill=0):
    out = np.full(n, fill, a.dtype)
    out[:len(a)] = a
    return out


def op_loops(jnp, lax, dtype):
    """name -> (size of the scattered-into table or None) -> jittable
    ``run(x, ix)``: K of one op kind in one ``fori_loop``.  Every
    iteration's input hangs on the one before (a float product with 0
    is not folded; for a bool it is, and the scatter is then hoisted
    out of the loop and reads 0.7 ms), so nothing leaves the loop."""
    def loop(step, first, acc0):
        return lax.fori_loop(0, K, lambda i, st: step(*st), (first, acc0))

    def gather(as_bool):
        def run(tab, ix):
            def step(t, acc):
                e = jnp.take(t, ix)
                if as_bool:
                    return t ^ e.reshape(-1)[0], acc ^ e
                return t + e.reshape(-1)[0] * 0, acc + e
            return loop(step, tab > 0.5 if as_bool else tab,
                        jnp.zeros(ix.shape, bool if as_bool else dtype))
        return lambda n: run

    def scatter(kind):
        wide = {"add2": 2, "add3": 3}.get(kind)

        def of(n):
            def run(x, ix):
                def step(e, acc):
                    if kind == "min":
                        out = jnp.full(n, jnp.inf, dtype).at[ix].min(e)
                        return e + out[0] * 0, jnp.minimum(acc, out)
                    if kind == "max_bool":
                        out = jnp.zeros(n, bool).at[ix].max(e > 0.5)
                        return jnp.where(out[0], e, 1.0 - e), acc | out
                    if wide:
                        out = jnp.zeros((n, wide), dtype).at[ix].add(
                            jnp.stack([e, e * 2, e * 3][:wide], axis=-1))
                        return e + out[0, 0] * 0, acc + out
                    out = jnp.zeros(n, kind).at[ix].add(e.astype(kind))
                    return e + out[0].astype(dtype) * 0.0, acc + out
                if wide:
                    acc0 = jnp.zeros((n, wide), dtype)
                else:
                    acc0 = {"min": jnp.full(n, jnp.inf, dtype),
                            "max_bool": jnp.zeros(n, bool)}.get(kind)
                return loop(step, x,
                            jnp.zeros(n, kind) if acc0 is None else acc0)
            return run
        return of

    return {"gather_f32": gather(False), "gather_bool": gather(True),
            "scatter_min_f32": scatter("min"),
            "scatter_add_f32": scatter(dtype),
            "scatter_add_i32": scatter(jnp.int32),
            "scatter_max_bool": scatter("max_bool"),
            "scatter_add2_f32": scatter("add2"),
            "scatter_add3_f32": scatter("add3")}


def readings(emit, only=None, config="dfly65k-random", reps=3):
    """Every reading of the probe on JAX's default device, each handed
    to ``emit(**record)``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from lib.compare import rate_gap
    from simgrid_tpu.ops import lmm_jax

    dtype = np.float32
    eps = 1e-5
    solve_sys = system(config, False)
    n_c, n_v, n_e = solve_sys.shape

    def layout(pow2):
        """(name, E, C, V, element array -> device) of the solve cell's
        pow2 1-D padding or the drain's [E/8, 8]."""
        if pow2:
            E, C, V = (lmm_jax._bucket(n) for n in (n_e, n_c, n_v))
            shape = (E,)
        else:
            E, C, V = -(-n_e // 8) * 8, n_c, n_v
            shape = (E // 8, 8)

        def el(a, dt):
            return jnp.asarray(padded(a.astype(dt), E).reshape(shape))
        return "solve_pow2" if pow2 else "drain_2d", E, C, V, el

    def timed(fn, *args):
        """(median seconds of ``reps`` calls after a first, the result)"""
        out = jax.block_until_ready(fn(*args))
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn(*args))
            times.append(time.perf_counter() - t0)
        return float(np.median(times)), out

    emit(what="sizes", n_cnst=n_c, n_var=n_v, n_elem=n_e)

    def op_prices(pow2):
        name, E, C, V, el = layout(pow2)
        e_var = el(solve_sys.e_var, np.int32)
        e_cnst = el(solve_sys.e_cnst, np.int32)
        rng = np.random.default_rng(1)
        e_x = jnp.asarray(rng.random(e_var.shape, dtype))
        v_x = jnp.asarray(rng.random(V, dtype))
        c_x = jnp.asarray(rng.random(C, dtype))
        loops = op_loops(jnp, lax, dtype)
        into_16k = []
        if pow2:
            e_c16k = el(solve_sys.e_cnst.astype(np.int64) * ALLTOALL_ROWS
                        // n_c, np.int32)
            into_16k = [(kind, "to_c16k", e_x, e_c16k, ALLTOALL_ROWS)
                        for kind in ("scatter_add_f32", "scatter_add_i32",
                                     "scatter_add2_f32", "scatter_add3_f32")]
        for kind, side, x, ix, n in [
                ("gather_f32", "by_e_var", v_x, e_var, None),
                ("gather_f32", "by_e_cnst", c_x, e_cnst, None),
                ("gather_bool", "by_e_var", v_x, e_var, None),
                ("gather_bool", "by_e_cnst", c_x, e_cnst, None),
                ("scatter_min_f32", "to_v", e_x, e_var, V),
                ("scatter_min_f32", "to_c", e_x, e_cnst, C),
                ("scatter_add_f32", "to_c", e_x, e_cnst, C),
                ("scatter_add_i32", "to_c", e_x, e_cnst, C),
                ("scatter_max_bool", "to_c", e_x, e_cnst, C),
                ("scatter_add2_f32", "to_c", e_x, e_cnst, C),
                ("scatter_add3_f32", "to_c", e_x, e_cnst, C)] + into_16k:
            s, _ = timed(jax.jit(loops[kind](n)), x, ix)
            emit(what="op", layout=name, op=f"{kind}_{side}", elems=E,
                 ms_per_op=1e3 * s / K, ns_per_index=1e9 * s / K / E)

        @jax.jit
        def column0(e, ix):
            """Does a sum keep its bits as column 0 of a 2-wide window?
            Entry's usage rides one (ISSUE 32)."""
            narrow = jnp.zeros(C, dtype).at[ix].add(e)
            wide = jnp.zeros((C, 2), dtype).at[ix].add(
                jnp.stack([e, (e > 0.5).astype(dtype)], axis=-1))
            return jnp.array_equal(narrow, wide[:, 0])

        emit(what="column0", layout=name, elems=E, rows=C,
             equal=bool(column0(e_x, e_cnst)))

    def round_ms(label, lanes, pow2, has_bounds, wants=None, skipped=None):
        """``fixpoint`` to convergence on ``lanes`` (systems alike but
        for their bounds: one solo, more under ``vmap`` over v_bound).
        ``wants``: the reference's rates, lane by lane.  ``skipped``:
        ms of a round that skips the bound block, to price the rounds
        that take it."""
        s = lanes[0]
        _, E, C, V, el = layout(pow2)
        shared = (el(s.e_var, np.int32), el(s.e_cnst, np.int32),
                  el(s.e_w, dtype),
                  jnp.asarray(padded(s.c_bound.astype(dtype), C)),
                  jnp.zeros(C, bool),
                  jnp.asarray(padded(s.v_penalty.astype(dtype), V)))
        bounds = [jnp.asarray(padded(x.v_bound.astype(dtype), V, -1))
                  for x in lanes]

        def lane(v_bound, *a):
            out = lmm_jax.fixpoint(
                *a, v_bound, jnp.asarray(eps, dtype), C, V,
                parallel_rounds=True, return_carry=True,
                has_bounds=has_bounds, has_fatpipe=False)
            return out[0], out[3], out[5], jnp.count_nonzero(out[4][4])

        if len(lanes) == 1:
            solve, v_bound = jax.jit(lane), bounds[0]
        else:
            solve = jax.jit(jax.vmap(lane, in_axes=(0,) + (None,) * 6))
            v_bound = jnp.stack(bounds)
        sec, out = timed(solve, v_bound, *shared)
        values, rounds, n_bound, n_light = (
            np.atleast_1d(np.asarray(x)) for x in out)
        values = values.reshape(len(lanes), -1)
        # under vmap the loop runs until its slowest lane is done
        n_rounds = int(rounds.max())
        rec = dict(cell=label, elems=E, lanes=len(lanes),
                   rounds=[int(r) for r in rounds],
                   bound_rounds=[int(b) for b in n_bound],
                   light_left=int(n_light.sum()), solve_ms=1e3 * sec,
                   round_ms=1e3 * sec / max(n_rounds, 1),
                   rates_sum=[float(v.sum()) for v in values])
        if wants is not None:
            floor = 2.0 * eps * float(np.max(s.c_bound))
            rec["rate_gap"] = [rate_gap(v[:n_v], w, floor)
                               for v, w in zip(values, wants)]
        taken = int(n_bound.max())
        if skipped is not None and taken:
            rec["taken_round_ms"] = (
                1e3 * sec - (n_rounds - taken) * skipped) / taken
        emit(what="round", **rec)
        return rec["round_ms"]

    def head_live(e_live, n_keep):
        shape = lmm_jax._head(e_live, n_keep).shape
        return (lax.iota(jnp.int32, n_keep).reshape(shape)
                < jnp.count_nonzero(e_live))

    def scatter_head(*lists_and_live, n_keep):
        """`lmm_jax._livefirst_head` with a gather of the kept head a
        list in place of its one gather of rows: is a gather priced by
        the index here too?"""
        *lists, e_live = lists_and_live
        live = e_live.reshape(-1)
        group = (e_live.shape[-1] if e_live.ndim == 2
                 else lmm_jax._pos_group(live.size))
        keep = lmm_jax._head(lmm_jax._stable_livefirst_perm(
            live, group).reshape(e_live.shape), n_keep)
        return (*(jnp.take(a.reshape(-1), keep) for a in lists),
                head_live(e_live, n_keep))

    def sort_head(*lists_and_live, n_keep):
        """`lmm_jax._livefirst_head` as ONE stable sort on "dead", the
        lists its payload: no indexed op at all."""
        *lists, e_live = lists_and_live
        dead = (~e_live).reshape(-1).astype(jnp.int32)
        _, *out = lax.sort([dead] + [a.reshape(-1) for a in lists],
                           num_keys=1, is_stable=True)
        shape = lmm_jax._head(e_live, n_keep).shape
        return (*(o[:n_keep].reshape(shape) for o in out),
                head_live(e_live, n_keep))

    def ladder(pow2):
        name, E, C, V, el = layout(pow2)
        lists = (el(solve_sys.e_var, np.int32), el(solve_sys.e_cnst, np.int32),
                 el(solve_sys.e_w, dtype))
        c_bound = jnp.asarray(padded(solve_sys.c_bound.astype(dtype), C))
        v_pen = jnp.asarray(padded(solve_sys.v_penalty.astype(dtype), V))
        sizes = lmm_jax._ladder_sizes(lists[0].shape)
        rungs = len(sizes)
        if rungs > 1:
            # and the size the floor refuses, half the last rung: does
            # a round still go by its indices down there?
            group = (lists[0].shape[-1] if lists[0].ndim == 2
                     else lmm_jax._pos_group(E))
            sizes = sizes + [-(-sizes[-1] // (2 * group)) * group]
        scatter3 = op_loops(jnp, lax, dtype)["scatter_add3_f32"](C)
        # the single loop over each cut list: the floor out of reach
        floor, lmm_jax._LADDER_MIN_ELEMS = lmm_jax._LADDER_MIN_ELEMS, E

        def rounds(n, e_var, e_cnst, e_w):
            out = lmm_jax.fixpoint(
                e_var, e_cnst, e_w, c_bound, jnp.zeros(C, bool), v_pen,
                jnp.full(V, -1, dtype), jnp.asarray(eps, dtype), C, V,
                parallel_rounds=True, max_rounds=n, return_carry=True,
                has_bounds=False, has_fatpipe=False)
            return out[0], out[3]

        try:
            for size, below in zip(sizes, sizes[1:] + [None]):
                cut = tuple(lmm_jax._head(a, size) for a in lists)
                rec = dict(layout=name, elems=size, rungs=rungs,
                           rung=size in sizes[:rungs])
                run = jax.jit(rounds)
                one, (_, r1) = timed(run, jnp.int32(1), *cut)
                five, (_, r5) = timed(run, jnp.int32(5), *cut)
                if int(r5) > int(r1):
                    rec["round_ms"] = 1e3 * (five - one) / (int(r5) - int(r1))
                rec["rounds_run"] = [int(r1), int(r5)]
                sec, _ = timed(jax.jit(scatter3),
                               jnp.abs(cut[2]) + 1.0, cut[1])
                rec["scatter_add3_ms"] = 1e3 * sec / K
                if below is not None:
                    rng = np.random.default_rng(size)
                    live = jnp.asarray(rng.random(cut[0].shape) < 0.4)
                    heads = []
                    for how, fn in (("packed_ms", lmm_jax._livefirst_head),
                                    ("partition_ms", scatter_head),
                                    ("sort_ms", sort_head)):
                        fn = jax.jit(lambda *a, fn=fn: fn(*a, n_keep=below))
                        sec, out = timed(fn, *cut, cut[2] * 0.5, live)
                        rec[how] = 1e3 * sec
                        heads.append([np.asarray(x) for x in out])
                    rec["kept"] = below
                    rec["agree"] = all(
                        np.array_equal(a, b) for other in heads[1:]
                        for a, b in zip(heads[0], other))
                emit(what="ladder", **rec)
        finally:
            lmm_jax._LADDER_MIN_ELEMS = floor

    if only in (None, "ops"):
        op_prices(False)
        op_prices(True)
    if only in (None, "ladder"):
        ladder(True)
        ladder(False)
    if only in (None, "rounds"):
        from configs import dragonfly_lv08 as ref

        free_want = ref.maxmin_solve(solve_sys)[0]
        bind_sys, bind_want = binding(solve_sys)
        skipped = round_ms("solve-like", [solve_sys], True, True,
                           [free_want])
        round_ms("solve-bind", [bind_sys], True, True, [bind_want],
                 skipped)
        round_ms("solve-vmap2", [bind_sys, solve_sys], True, True,
                 [bind_want, free_want], skipped)
        round_ms("drain-like", [system(config, True)], False, False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--only", choices=("ops", "rounds", "ladder"),
                    default=None)
    only = ap.parse_args(argv).only

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"coo_round_probe: no TPU - JAX's default device is {dev}; "
              f"nothing is measured on {dev.platform}", file=sys.stderr)
        return 2

    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as sink:
        def emit(**rec):
            line = json.dumps(dict(rec, platform=dev.platform,
                                   kind=dev.device_kind))
            print(line, flush=True)
            sink.write(line + "\n")
            sink.flush()

        readings(emit, only)
    return 0


if __name__ == "__main__":
    sys.exit(main())
