"""Entry ``CollectiveSpec(...).build()`` -> ``DeviceCollective
.make_sim(superstep=K).run(max_advances=L)``: a collective's schedule
walked on the device by the collective tape, over the platform's own
routes, in laps.  Set-up loads the platform, places the ranks and
lowers the collective (routes, schedule, DAG) once; a lap builds a
fresh sim from the lowered collective (the upload is inside the window)
and drains the head of the schedule, ``lap_advances`` advances, where
every step-1 block starts and the first finish; laps repeat as
``drivers/drain.py``'s.  Every lap must give the same events.

``--seed`` rotates the ranks over their hosts: rank r sits on host
((r + s) mod R) x stride.  A pairwise step's set of host pairs is the
same for every s, so every seed is the same work under another
numbering."""

from __future__ import annotations

import time

import numpy as np

from lib.compare import Compared, digest, events_gap

from . import _inputs


def rank_hosts(coll, n_hosts: int, seed: int) -> np.ndarray:
    """Host index (hosts in the order of their names) of each rank."""
    ranks, stride = int(coll["ranks"]), int(coll["stride"])
    if ranks < 2 or stride < 1 or (ranks - 1) * stride >= n_hosts:
        raise ValueError(f"{ranks} ranks at stride {stride} do not fit "
                         f"{n_hosts} hosts")
    s = int(np.random.default_rng([int(seed), 1]).integers(ranks))
    return (np.arange(ranks) + s) % ranks * stride


def lowered(run, hosts_of_ranks: np.ndarray):
    """The collective as the program lowers it for this platform."""
    from simgrid_tpu.collectives import CollectiveSpec, RoutedTopology

    coll = run.cell.traffic["collective"]
    with run.spans.span("flatten"):
        e, _model, _none = _inputs.start_engine(
            run, "bench", np.zeros((0, 2), np.int64))
        hosts = e.get_all_hosts()
        topo = RoutedTopology(e, [hosts[h] for h in hosts_of_ranks])
        dc = CollectiveSpec(op=coll["op"], algo=coll["algo"],
                            ranks=int(coll["ranks"]), topo=topo,
                            payload=float(coll["block_bytes"])).build()
    run.shape = (dc.n_c, dc.n_v, len(dc.e_var))
    return dc


def setup(run):
    tr = run.cell.traffic
    placed = rank_hosts(tr["collective"], _inputs.n_hosts(run), run.seed)
    dc = lowered(run, placed)
    # the tape resolves lmm/dtype:auto itself: held to what the
    # configuration states
    _dtype, eps = _inputs.solve_precision(run)
    R = int(tr["collective"]["ranks"])
    state = dict(
        rank_hosts=placed, dc=dc, eps=eps,
        # a flow by its ranks, as the reference lists it
        flow_key=np.array([r.src * R + r.dst
                           for r in dc.schedule.records], np.int64),
        lap_advances=int(tr["lap_advances"]),
        superstep=int(tr["superstep"]),
        done_eps=float(run.cell.config["precision"]["done_eps"]))
    with run.spans.span("warmup"):
        lap(run, state)
    return state


def lap(run, state):
    """The timed call: a fresh sim, ``lap_advances`` advances, the
    completions and the activations listed by flow."""
    key = state["flow_key"]
    with run.spans.span("lap.upload"):
        sim = state["dc"].make_sim(superstep=state["superstep"],
                                   eps=state["eps"],
                                   done_eps=state["done_eps"])
    with run.spans.span("lap.run"):
        sim.run(max_advances=state["lap_advances"])
    with run.spans.span("lap.events"):
        done = [(float(t), int(key[fid])) for t, fid in sim.events]
        started = [(float(t), int(key[fid]))
                   for t, fid in sim.collective_events]
    return done, started, dict(advances=sim.advances,
                               dispatches=sim.supersteps,
                               rounds=sim.rounds)


def window(run, state):
    laps, first, events_n, starts_n = [], None, 0, 0
    totals = dict(advances=0, dispatches=0, rounds=0)
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < run.seconds:
        done, started, info = lap(run, state)
        if first is None:
            first = (done, started)
        laps.append(digest((done, started)))
        events_n += len(done)
        starts_n += len(started)
        for k in totals:
            totals[k] += info[k]
    wall = time.perf_counter() - t0
    short = len(laps) * state["lap_advances"] - totals["advances"]
    return dict(wall_s=wall, laps=len(laps), events=events_n,
                activations=starts_n, digests=laps, first_lap=first,
                attempted=len(laps), failed=0, advances_short=short,
                **totals)


def release(run, state):
    pass                      # every lap's sim died with its lap


def reference(run, state, precision: str = "f64"):
    """(completions, activations) of the reference over as many
    advances, each flow named as ``flow_key`` names it."""
    ref = run.cell.reference
    p = run.cell.config["platform"]
    coll = run.cell.traffic["collective"]
    R = int(coll["ranks"])
    dag = ref.pairwise_dag(R)
    system, delay = ref.dag_system(
        p["topo"], float(p["bw_bytes_per_s"]), float(p["lat_s"]),
        state["rank_hosts"], dag)
    sizes = np.full(len(dag.src), float(coll["block_bytes"]))
    done, started, _ = ref.drain(
        system, dag, delay, sizes, state["lap_advances"],
        eps=1e-9 if precision == "f64" else state["eps"],
        done_eps=state["done_eps"], precision=precision)
    key = dag.src * R + dag.dst
    return ([(t, int(key[f])) for t, f in done],
            [(t, int(key[f])) for t, f in started])


def check(run, state, rec, precision: str = "f64") -> Compared:
    """The first lap's completions and activations against the
    reference's over as many advances, and every other lap against the
    first.  ``precision="bf16"`` is the control."""
    limits = run.cell.traffic["limits"]
    ref_done, ref_started = reference(run, state)
    got_done, got_started = (rec["first_lap"] if precision == "f64"
                             else reference(run, state, precision))
    done = events_gap(ref_done, got_done)
    started = events_gap(ref_started, got_started)
    out = Compared()
    out.add("date_gap", max(done["date_gap"], started["date_gap"]),
            limits["date_gap"])
    out.add("order_gap", max(done["order_gap"], started["order_gap"]),
            limits["order_gap"])
    out.add("events_unmatched", done["unmatched"],
            limits["events_unmatched"])
    out.add("activations_unmatched", started["unmatched"],
            limits["activations_unmatched"])
    out.add("laps_differing",
            sum(d != rec["digests"][0] for d in rec["digests"]),
            limits["laps_differing"])
    out.add("advances_short", rec["advances_short"],
            limits["advances_short"])
    return out


def end_to_end(run, rec):
    return {"events_per_s": rec["events"] / rec["wall_s"]}
