"""Entry ``CollectiveSpec("allreduce", "lr", R, RoutedTopology(...),
count, steps=S).build()`` -> ``DeviceCollective.make_sim(superstep=K)
.run(max_advances=L)``: a full-machine logical-ring allreduce, one rank
a host, walked on the device by the collective tape over the
platform's own routes, in laps.  The ring has R x (2R - 1) messages
(8.6 x 10^9 at 65,536 ranks), so the program lowers its HEAD: every
rank's self-copy and first ``steps_held`` ring steps.  Set-up loads the
platform, places the ranks and lowers that head once; a lap, the window
and what a lap lists are ``drivers/coll_drain.py``'s.  Every lap must
give the same events.

``--seed`` rotates the ranks over the hosts: rank r sits on host
(r + s) mod R (hosts in the order of their names).  Every seed then
has the same set of host pairs {(h, h), (h, h + 1)}: the same work
under another numbering.

A flow is named by (sender, receiver, step), because the pair
(r, r + 1) recurs every step.  ``check`` holds the first lap to the
reference drained over ``steps_held + 4`` steps: a head too short for
the lap misses messages the reference finishes.

A program whose ``CollectiveSpec`` lowers no head (``steps=``) would
be handed the whole ring: refused before anything is loaded."""

from __future__ import annotations

import inspect

import numpy as np

from lib.compare import Compared, events_gap

from . import _inputs, coll_drain

lap = coll_drain.lap
window = coll_drain.window
release = coll_drain.release

#: steps the reference's graph holds beyond the program's head
REFERENCE_EXTRA_STEPS = 4


def rank_hosts(coll, n_hosts: int, seed: int) -> np.ndarray:
    """Host index (hosts in the order of their names) of each rank."""
    ranks = int(coll["ranks"])
    if ranks < 3 or ranks > n_hosts:
        raise ValueError(f"a ring of {ranks} ranks, one a host, does not "
                         f"fit {n_hosts} hosts (3 ranks or more)")
    s = int(np.random.default_rng([int(seed), 1]).integers(ranks))
    return (np.arange(ranks) + s) % ranks


def heads_only(coll) -> None:
    """Raise unless the program lowers a head of a schedule."""
    from simgrid_tpu.collectives import CollectiveSpec

    if "steps" not in inspect.signature(CollectiveSpec).parameters:
        R = int(coll["ranks"])
        raise RuntimeError(
            "this program's CollectiveSpec lowers no head of a schedule "
            f"(steps=); the whole ring among {R} ranks is "
            f"{R * (2 * R - 1):.3g} records: not run")


def ring_steps(src: np.ndarray) -> np.ndarray:
    """Each record's step: its place among its sender's sends (the
    records are rank-major, in send program order; 0: the self-copy)."""
    first = np.searchsorted(src, src)
    return np.arange(len(src)) - first


def flow_key(src, dst, step, ranks: int) -> np.ndarray:
    """One integer for (sender, receiver, step)."""
    return (np.asarray(src, np.int64) * ranks
            + np.asarray(dst, np.int64)) * (2 * ranks) \
        + np.asarray(step, np.int64)


def lowered(run, hosts_of_ranks: np.ndarray):
    """The head of the collective as the program lowers it for this
    platform."""
    from simgrid_tpu.collectives import CollectiveSpec, RoutedTopology

    coll = run.cell.traffic["collective"]
    with run.spans.span("flatten"):
        e, _model, _none = _inputs.start_engine(
            run, "bench", np.zeros((0, 2), np.int64))
        hosts = e.get_all_hosts()
        topo = RoutedTopology(e, [hosts[h] for h in hosts_of_ranks])
        dc = CollectiveSpec(op=coll["op"], algo=coll["algo"],
                            ranks=int(coll["ranks"]), topo=topo,
                            payload=float(coll["count"]),
                            steps=int(coll["steps_held"])).build()
    run.shape = (dc.n_c, dc.n_v, len(dc.e_var))
    return dc


def setup(run):
    tr = run.cell.traffic
    heads_only(tr["collective"])
    placed = rank_hosts(tr["collective"], _inputs.n_hosts(run), run.seed)
    dc = lowered(run, placed)
    # the tape resolves lmm/dtype:auto itself: held to what the
    # configuration states
    _dtype, eps = _inputs.solve_precision(run)
    R = int(tr["collective"]["ranks"])
    recs = dc.schedule.records
    src = np.fromiter((r.src for r in recs), np.int64, len(recs))
    dst = np.fromiter((r.dst for r in recs), np.int64, len(recs))
    state = dict(
        rank_hosts=placed, dc=dc, eps=eps,
        flow_key=flow_key(src, dst, ring_steps(src), R),
        lap_advances=int(tr["lap_advances"]),
        superstep=int(tr["superstep"]),
        done_eps=float(run.cell.config["precision"]["done_eps"]))
    with run.spans.span("warmup"):
        lap(run, state)
    return state


def reference(run, state, precision: str = "f64"):
    """(completions, activations) of the reference over as many
    advances, on a ring ``REFERENCE_EXTRA_STEPS`` steps longer than the
    program's head, each flow named as ``flow_key`` names it."""
    ref = run.cell.reference
    p = run.cell.config["platform"]
    coll = run.cell.traffic["collective"]
    R = int(coll["ranks"])
    steps = min(int(coll["steps_held"]) + REFERENCE_EXTRA_STEPS,
                2 * (R - 1))
    dag = ref.ring_dag(R, steps)
    system, delay = ref.dag_system(
        p["topo"], float(p["bw_bytes_per_s"]), float(p["lat_s"]),
        state["rank_hosts"], dag)
    sizes = np.full(len(dag.src), float(coll["count"]) // R
                    * float(coll["elem_bytes"]))
    done, started, _ = ref.drain(
        system, dag, delay, sizes, state["lap_advances"],
        eps=1e-9 if precision == "f64" else state["eps"],
        done_eps=state["done_eps"], precision=precision)
    key = flow_key(dag.src, dag.dst, ref.ring_step(R, steps), R)
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
