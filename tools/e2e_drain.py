#!/usr/bin/env python3
"""End-to-end north-star bench (BASELINE config #4): drain a 100k-flow
set on a 65,536-host dragonfly to completion, native C++ maxmin vs the
JAX backend (CPU or TPU), comparing WALL-CLOCK and EVENT ORDER.

The simulation phase measured is the whole network drain: every
solve, every time advance, every completion event, until no flow
remains.  Platform parse + route expansion are reported separately
(identical work for every backend).

Workloads:
  random   N random host pairs (the literal config-#4 stress shape)
  alltoall R ranks spread evenly, all ordered pairs (the north-star
           text's SMPI alltoall shape; contention depth ~R)

Usage:
  python tools/e2e_drain.py --backend native|jax [--platform cpu|tpu]
         [--workload random|alltoall] [--flows 100000] [--ranks 320]
         [--superstep K] [--pipeline D]
         [--out FILE.jsonl] [--events-out FILE.npz]

`--superstep K` batches K advances per dispatch with the device
completion ring (~1/K syncs/advance; K = 1 is one advance a dispatch)
and on-device repacks; `--pipeline D` additionally keeps D speculative
supersteps in flight (double-buffered rings: the host processes ring
N while the device runs ring N+1 — bit-identical results, and the
row carries the blocking-fetch split + speculation commit counters).  `--phase-stats` prints, per phase (build/route,
latency advance, drain), the device dispatch count, uploaded bytes
split full vs delta (ops.opstats counters fed by _device_args, the
warm solver and the drain executor), fixpoint rounds, and the runtime
fast-path coverage split (`fastpath_advances` vs `native_advances`
with the invalidation-cause histogram, for engine-driven runs), and
appends the counters to the labeled bench row.  Rows are labeled with mode/superstep_k/syncs so
bench.py reports each shape separately.  Completion grouping is
RELATIVE (done_eps * size) on every backend, the reference's
sg_maxmin_precision semantics — the fix for the round-5 f32
tie-splitting abort.
"""
import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def build_system(workload: str, flows: int, ranks: int, size: float):
    """Parse the 65k dragonfly, post the flow set, advance past the
    latency phase, and flatten to COO arrays + flow action order."""
    import numpy as np
    from simgrid_tpu import s4u
    from simgrid_tpu.ops import lmm_jax
    from tools.scale_proof import build_platform

    t0 = time.perf_counter()
    platform = build_platform(
        os.path.join(tempfile.gettempdir(), "dragonfly65k.xml"), 65536)
    e = s4u.Engine(["e2e", "--cfg=lmm/backend:list",
                    "--cfg=network/maxmin-selective-update:no",
                    "--cfg=network/optim:Full"])
    e.load_platform(platform)
    hosts = e.get_all_hosts()
    n_hosts = len(hosts)
    build_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    model = e.pimpl.network_model
    actions = []
    if workload == "alltoall":
        stride = n_hosts // ranks
        rh = [hosts[i * stride] for i in range(ranks)]
        for i in range(ranks):
            for j in range(ranks):
                if i != j:
                    actions.append(model.communicate(rh[i], rh[j],
                                                     size, -1.0))
    else:
        rng = np.random.default_rng(42)
        pairs = rng.integers(0, n_hosts, size=(flows, 2))
        for k in range(flows):
            src, dst = int(pairs[k, 0]), int(pairs[k, 1])
            if src == dst:
                dst = (dst + 1) % n_hosts
            actions.append(model.communicate(hosts[src], hosts[dst],
                                             size, -1.0))
    for _ in range(400):
        n_live = sum(1 for a in actions
                     if a.variable is not None
                     and a.variable.sharing_penalty > 0)
        if n_live == len(actions):
            break
        e.pimpl.surf_solve(-1.0)
    route_s = time.perf_counter() - t0

    flat = lmm_jax.flatten(list(model.system.active_constraint_set))
    arrays, vars_in_order = flat
    # flow id per variable slot = index into `actions`
    var_slot = {id(a.variable): k for k, a in enumerate(actions)}
    slot_flow = np.array([var_slot[id(v)] for v in vars_in_order],
                         np.int64)
    return arrays, slot_flow, dict(build_s=round(build_s, 1),
                                   route_s=round(route_s, 1),
                                   n_hosts=n_hosts,
                                   flows=len(actions))


def drain_native(arrays, slot_flow, size, done_eps=1e-4,
                 min_events=None):
    """Reference-architecture baseline: the exact C++ maxmin list
    solver (native/lmm.cc) drives the same drain loop.  Per advance the
    live system is repacked with vectorized numpy (cheap next to the
    solve) so the C++ solver only ever sees live flows — the same
    favor the JAX path gets from its repacks.  Completion grouping is
    relative (done_eps * size), matching DrainSim's default rule.
    ``min_events`` stops the drain after the advance that reaches that
    many completions (a window of the drain, for comparison with a
    windowed device run); None drains to completion."""
    import numpy as np
    from simgrid_tpu.ops import lmm_native

    E = arrays.n_elem
    e_var = arrays.e_var[:E].copy()
    e_cnst = arrays.e_cnst[:E].copy()
    e_w = arrays.e_w[:E].astype(np.float64)
    c_bound = arrays.c_bound.astype(np.float64)
    n_c = len(c_bound)
    n_v = arrays.n_var
    rem = np.full(n_v, float(size))
    live = np.ones(n_v, bool)
    ids = np.arange(n_v)
    t = 0.0
    events = []
    advances = 0
    t0 = time.perf_counter()
    while live.any() and (min_events is None
                          or len(events) < min_events):
        keep = np.flatnonzero(live)
        old2new = np.full(n_v, -1, np.int32)
        old2new[keep] = np.arange(len(keep), dtype=np.int32)
        emask = live[e_var]
        ev, ec, ew = old2new[e_var[emask]], e_cnst[emask], e_w[emask]
        pen = np.ones(len(keep))
        vb = np.full(len(keep), -1.0)
        vals, _, _ = lmm_native.solve_coo(
            ev, ec, ew, c_bound, np.zeros(n_c, np.uint8), pen, vb,
            1e-5, len(ev), n_c, len(keep))
        rate = np.asarray(vals)
        flowing = rate > 0
        rl = rem[keep]
        dts = np.where(flowing, rl / np.where(flowing, rate, 1.0),
                       np.inf)
        dt = dts.min()
        if not np.isfinite(dt):
            raise RuntimeError("native drain stalled")
        rl2 = np.where(flowing, rl - rate * dt, rl)
        done = flowing & (rl2 < done_eps * size)
        t += dt
        advances += 1
        for fid in ids[keep[np.flatnonzero(done)]]:
            events.append((t, int(slot_flow[fid])))
        rem[keep] = np.where(done, 0.0, rl2)
        live[keep[done]] = False
    wall = time.perf_counter() - t0
    return events, dict(advances=advances, wall_s=round(wall, 1),
                        t_sim=t)


def compare_events(ref, got, window=2e-4):
    """Hold a drain's completion events to a reference drain's, by the
    rule of tests/test_event_order_parity.py, on whatever part of the
    drain both cover: same completion order, flows finishing in the
    same advance (equal dates) being one unordered group; an inversion
    is admitted only between flows whose reference dates lie within
    ``window`` relative (2x the relative completion threshold: f32
    error plus the grouping rule) and inversions stay under 1 % of the
    events; every date agrees within ``window``; and a flow only one
    side has finished must sit at the end of the compared window.
    Raises AssertionError on a breach, else returns the census."""
    t_ref = {fid: t for t, fid in ref}
    t_got = {fid: t for t, fid in got}
    assert len(t_ref) == len(ref) and len(t_got) == len(got), \
        "a flow completed twice"
    common = [fid for _, fid in got if fid in t_ref]
    assert common, "no completion event in common"
    horizon = min(ref[-1][0], got[-1][0])
    edge = [fid for fid in set(t_ref) ^ set(t_got)
            if (t_ref[fid] if fid in t_ref else t_got[fid])
            < horizon * (1 - window)]
    assert not edge, (f"{len(edge)} flow(s) finished well inside the "
                      f"window on one side only, e.g. {sorted(edge)[:5]}")
    worst = max(abs(t_got[f] - t_ref[f]) / t_ref[f] for f in common)
    assert worst < window, \
        f"completion dates differ by {worst:.3e} relative (>= {window})"
    # walk got in order, same-date groups sorted by reference date
    inversions = 0
    high = 0.0
    order = sorted(range(len(common)),
                   key=lambda i: (t_got[common[i]], t_ref[common[i]]))
    for i in order:
        t = t_ref[common[i]]
        if t < high:
            inversions += 1
            assert t > high * (1 - window), (
                f"flow {common[i]} finished out of order: reference "
                f"date {t!r} after a flow dated {high!r}")
        high = max(high, t)
    assert inversions < max(1, len(common) // 100), \
        f"{inversions} order inversions in {len(common)} events"
    return dict(events_compared=len(common),
                events_ref=len(ref), events_got=len(got),
                same_sequence=[f for _, f in ref if f in t_got] == common,
                inversions=inversions, max_rel_date_err=worst)


def drain_jax(arrays, slot_flow, size, platform=None, done_eps=1e-4,
              superstep=16, pipeline=0):
    import numpy as np
    if platform:
        import jax
        jax.config.update("jax_platforms", platform)
    import jax
    from simgrid_tpu.ops import opstats
    from simgrid_tpu.ops.device import solve_dtype
    from simgrid_tpu.ops.lmm_drain import DrainSim

    dev = jax.devices()[0]
    dtype = solve_dtype(None, "e2e_drain")
    E = arrays.n_elem
    sim = DrainSim(arrays.e_var[:E], arrays.e_cnst[:E],
                   arrays.e_w[:E].astype(dtype),
                   arrays.c_bound[:arrays.n_cnst].astype(dtype),
                   np.full(arrays.n_var, float(size)),
                   eps=1e-5, done_eps=done_eps, dtype=dtype,
                   superstep=superstep, pipeline=pipeline)
    # warm the jits on the first advance before timing?  No: honest
    # end-to-end wall-clock includes compiles once per shape; report
    # both (first advance separately).
    fetch_mark = opstats.snapshot()
    t0 = time.perf_counter()
    n = sim.n_v
    if pipeline:
        # the pipelined driver owns the loop (speculative in-flight
        # supersteps; progress reported per collected ring)
        last = [time.perf_counter()]

        def report(batches):
            if time.perf_counter() - last[0] >= 10.0:
                last[0] = time.perf_counter()
                print(f"[drain] superstep {sim.supersteps}: "
                      f"advances {sim.advances}, t_sim {sim.t:.4f}, "
                      f"spec {sim.spec_committed}/{sim.spec_issued}, "
                      f"wall {time.perf_counter()-t0:.0f}s",
                      flush=True)
        sim.on_batches = report
        sim.run()
        n = 0
    else:
        while n:
            before = sim.advances
            n, _ = sim.superstep_batch()
            if n and sim.advances == before:
                n = sim._rescue_one()
            print(f"[drain] superstep {sim.supersteps}: "
                  f"advances {sim.advances}, live {n}, "
                  f"t_sim {sim.t:.4f}, syncs {sim.syncs}, "
                  f"wall {time.perf_counter()-t0:.0f}s", flush=True)
    wall = time.perf_counter() - t0
    fetch_stats = opstats.diff(fetch_mark)
    events = [(t, int(slot_flow[fid])) for t, fid in sim.events]
    mode = "pipeline" if pipeline else "superstep"
    rec = dict(advances=sim.advances, wall_s=round(wall, 1),
               t_sim=sim.t, rounds=sim.rounds, syncs=sim.syncs,
               repacks=sim.repacks, jax_platform=dev.platform,
               mode=mode, superstep_k=superstep,
               supersteps=sim.supersteps,
               syncs_per_advance=round(
                   sim.syncs / max(sim.advances, 1), 4))
    if pipeline:
        rec.update(pipeline_depth=pipeline,
                   spec_issued=sim.spec_issued,
                   spec_committed=sim.spec_committed,
                   spec_rolled_back=sim.spec_rolled_back,
                   fetches=int(fetch_stats.get("fetches", 0)),
                   blocking_fetches=int(
                       fetch_stats.get("blocking_fetches", 0)),
                   host_block_ms=round(
                       fetch_stats.get("host_block_ms", 0), 1))
    return events, rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", choices=["native", "jax"],
                    required=True)
    ap.add_argument("--platform", default=None)
    ap.add_argument("--workload", default="random",
                    choices=["random", "alltoall"])
    ap.add_argument("--flows", type=int, default=100_000)
    ap.add_argument("--ranks", type=int, default=320)
    ap.add_argument("--size", type=float, default=1e6)
    ap.add_argument("--superstep", type=int, default=16, metavar="K",
                    help="jax: K advances per dispatch (~1/K "
                         "syncs/advance, on-device repacks)")
    ap.add_argument("--pipeline", type=int, default=0, metavar="D",
                    help="jax: keep D speculative supersteps in "
                         "flight (bit-identical results, "
                         "blocking-fetch split on the row)")
    ap.add_argument("--phase-stats", action="store_true",
                    help="report per-phase dispatch count, uploaded "
                         "bytes (full vs delta) and fixpoint rounds; "
                         "counters ride the bench row")
    ap.add_argument("--out", default=None)
    ap.add_argument("--events-out", default=None)
    args = ap.parse_args()

    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)

    import numpy as np
    from simgrid_tpu.ops import opstats

    phase_marks = [opstats.snapshot()]
    arrays, slot_flow, info = build_system(args.workload, args.flows,
                                           args.ranks, args.size)
    phase_marks.append(opstats.snapshot())
    rec = {"backend": args.backend, "platform": args.platform,
           "workload": args.workload, **info,
           "n_cnst": arrays.n_cnst, "n_var": arrays.n_var,
           "n_elem": arrays.n_elem}
    print(json.dumps(rec), flush=True)

    if args.backend == "native":
        events, stats = drain_native(arrays, slot_flow, args.size)
    else:
        events, stats = drain_jax(arrays, slot_flow, args.size,
                                  args.platform,
                                  superstep=args.superstep,
                                  pipeline=args.pipeline)
    rec.update(stats)
    rec["n_events"] = len(events)
    if args.phase_stats:
        drain_mark = opstats.snapshot()
        keys = ("dispatches", "uploaded_bytes_full",
                "uploaded_bytes_delta", "fixpoint_rounds",
                "warm_solves", "cold_solves",
                # fast-path coverage: advances served from the device
                # plan vs the generic native loop, plus the
                # invalidation-cause histogram (ops.drain_path)
                "fastpath_advances", "native_advances",
                "drain_transitions", "drain_transition_slots",
                "drain_cause_transition", "drain_cause_partial_advance",
                "drain_cause_profile_event", "drain_cause_stall",
                "drain_cause_unrecognized",
                # fault-tape activity (ops.lmm_drain tape=): compiled
                # entries, mid-drain fires, speculative replays
                "fault_tape_slots", "fault_tape_events",
                "fault_replays", "warm_bound_restarts",
                # collective-tape activity (ops.lmm_drain
                # collective=): compiled DAG slots, fired
                # activations, speculative replays
                "collective_tape_slots", "collective_tape_fires",
                "collective_replays")
        phases = {}
        for name, before, after in (
                ("build+latency", phase_marks[0], phase_marks[1]),
                ("drain", phase_marks[1], drain_mark)):
            delta = {k: after.get(k, 0) - before.get(k, 0) for k in keys}
            phases[name] = {k: v for k, v in delta.items() if v}
            print(json.dumps({"phase": name, **phases[name]}),
                  flush=True)
        rec["phase_stats"] = phases
        fp = phases["drain"].get("fastpath_advances", 0)
        nat = phases["drain"].get("native_advances", 0)
        if fp or nat:
            rec["fastpath_coverage"] = round(fp / max(nat, 1), 3)
    print(json.dumps(rec), flush=True)

    if args.events_out:
        np.savez_compressed(args.events_out,
                            t=np.array([e[0] for e in events]),
                            flow=np.array([e[1] for e in events]))
    if args.out:
        with open(args.out, "a") as fh:
            fh.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
