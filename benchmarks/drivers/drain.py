"""Entry ``DrainSim(..., superstep=K).run(max_advances=L)``: the head of
the whole-system drain, in laps.  A lap builds a fresh ``DrainSim`` from
the host arrays (the upload is inside the window) and drains the first
``lap_advances`` advances, where the population is full and an advance
costs most; laps repeat until the window's seconds have passed and the
lap in flight ends.  Every lap must give the same events."""

from __future__ import annotations

import time

import numpy as np

from lib import traffic
from lib.compare import Compared, digest, events_gap

from . import _inputs


def setup(run):
    tr = run.cell.traffic
    pairs = traffic.flow_pairs(tr["flows"], _inputs.n_hosts(run), run.seed)
    a, slot_flow = _inputs.flattened(run, pairs)
    dtype, eps = _inputs.solve_precision(run)
    E = a.n_elem
    state = dict(
        pairs=pairs, slot_flow=slot_flow, eps=eps, dtype=dtype,
        host=dict(e_var=a.e_var[:E], e_cnst=a.e_cnst[:E],
                  e_w=a.e_w[:E].astype(dtype),
                  c_bound=a.c_bound[:a.n_cnst].astype(dtype),
                  sizes=np.full(a.n_var, float(run.cell.config[
                      "flow_bytes"]))),
        lap_advances=int(tr["lap_advances"]),
        superstep=int(tr["superstep"]),
        done_eps=float(run.cell.config["precision"]["done_eps"]))
    with run.spans.span("warmup"):
        lap(run, state)
    return state


def lap(run, state):
    """The timed call: a fresh sim, ``lap_advances`` advances, the
    events listed by flow."""
    from simgrid_tpu.ops.lmm_drain import DrainSim

    h = state["host"]
    with run.spans.span("lap.upload"):
        sim = DrainSim(h["e_var"], h["e_cnst"], h["e_w"], h["c_bound"],
                       h["sizes"], eps=state["eps"],
                       done_eps=state["done_eps"], dtype=state["dtype"],
                       superstep=state["superstep"])
    with run.spans.span("lap.run"):
        sim.run(max_advances=state["lap_advances"])
    with run.spans.span("lap.events"):
        events = [(float(t), int(state["slot_flow"][fid]))
                  for t, fid in sim.events]
    return events, dict(advances=sim.advances, dispatches=sim.supersteps,
                        rounds=sim.rounds)


def window(run, state):
    laps, first, events_n = [], None, 0
    totals = dict(advances=0, dispatches=0, rounds=0)
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < run.seconds:
        events, info = lap(run, state)
        if first is None:
            first = events
        laps.append(digest(events))
        events_n += len(events)
        for k in totals:
            totals[k] += info[k]
    wall = time.perf_counter() - t0
    short = len(laps) * state["lap_advances"] - totals["advances"]
    return dict(wall_s=wall, laps=len(laps), events=events_n,
                digests=laps, first_lap=first, attempted=len(laps),
                failed=0, advances_short=short, **totals)


def release(run, state):
    pass                      # every lap's sim died with its lap


def check(run, state, rec, precision: str = "f64") -> Compared:
    """The first lap's events against the reference drain of the same
    flows over as many advances, and every other lap against the first.
    ``precision="bf16"`` is the control."""
    limits = run.cell.traffic["limits"]
    ref_sys = _inputs.reference_system(run, state["pairs"], True)
    sizes = state["host"]["sizes"]
    ref, _ = run.cell.reference.drain(
        ref_sys, sizes, state["lap_advances"], eps=1e-9,
        done_eps=state["done_eps"])
    if precision == "f64":
        got = rec["first_lap"]
    else:
        got, _ = run.cell.reference.drain(
            ref_sys, sizes, state["lap_advances"], eps=state["eps"],
            done_eps=state["done_eps"], precision=precision)
    gap = events_gap(ref, got)
    out = Compared()
    out.add("date_gap", gap["date_gap"], limits["date_gap"])
    out.add("order_gap", gap["order_gap"], limits["order_gap"])
    out.add("events_unmatched", gap["unmatched"],
            limits["events_unmatched"])
    out.add("laps_differing",
            sum(d != rec["digests"][0] for d in rec["digests"]),
            limits["laps_differing"])
    out.add("advances_short", rec["advances_short"],
            limits["advances_short"])
    return out


def end_to_end(run, rec):
    return {"events_per_s": rec["events"] / rec["wall_s"]}
