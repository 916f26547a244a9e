"""Entry ``CollectiveSpec("allreduce", "rdb", R, RoutedTopology(...),
bytes).build()`` -> ``DeviceCollective.make_sim(superstep=K)
.run(max_advances=L)``: a full-machine recursive-doubling allreduce,
one rank a host, walked on the device by the collective tape over the
platform's own routes, in laps.  Set-up loads the platform, places the
ranks and lowers the collective once (the schedule, the routes of the
R log2 R pairs it uses, the DAG); a lap, the window and what a lap
lists are ``drivers/coll_drain.py``'s: a fresh sim from the lowered
collective (the upload is inside the window), ``lap_advances``
advances of the head of the schedule, where step 0's R messages start
by route length and the first steps' bursts finish.  Every lap must
give the same events.

``--seed`` renumbers the ranks over the hosts: rank r sits on host
r xor s (hosts in the order of their names).  Step k's set of host
pairs {h, h xor 2^k} is the same for every s, so every seed is the
same work under another numbering.

A program whose ``RoutedTopology`` routes every ordered pair of ranks
when it is built (4.3 x 10^9 at 65,536) is refused before it starts
to."""

from __future__ import annotations

import numpy as np

from lib.compare import Compared, events_gap

from . import _inputs, coll_drain

lap = coll_drain.lap
window = coll_drain.window
release = coll_drain.release


def rank_hosts(coll, n_hosts: int, seed: int) -> np.ndarray:
    """Host index (hosts in the order of their names) of each rank."""
    ranks = int(coll["ranks"])
    if ranks < 2 or ranks & (ranks - 1) or ranks > n_hosts:
        raise ValueError(f"{ranks} ranks, one a host by xor, do not fit "
                         f"{n_hosts} hosts (a power of two, at most the "
                         f"hosts)")
    s = int(np.random.default_rng([int(seed), 1]).integers(ranks))
    return np.arange(ranks) ^ s


def routes_on_demand(engine, hosts) -> None:
    """Raise unless the program's routed flavor looks a route up when a
    schedule asks for it: built over two hosts it has routed nothing
    yet."""
    from simgrid_tpu.collectives import RoutedTopology

    if RoutedTopology(engine, hosts[:2]).n_c:
        raise RuntimeError(
            "this program's RoutedTopology routes every ordered pair of "
            "ranks in its constructor; the cell places "
            f"{len(hosts)} ranks ({len(hosts) ** 2:.3g} pairs): not run")


def lowered(run, hosts_of_ranks: np.ndarray):
    """The collective as the program lowers it for this platform."""
    from simgrid_tpu.collectives import CollectiveSpec, RoutedTopology

    coll = run.cell.traffic["collective"]
    with run.spans.span("flatten"):
        e, _model, _none = _inputs.start_engine(
            run, "bench", np.zeros((0, 2), np.int64))
        hosts = e.get_all_hosts()
        routes_on_demand(e, hosts)
        topo = RoutedTopology(e, [hosts[h] for h in hosts_of_ranks])
        dc = CollectiveSpec(op=coll["op"], algo=coll["algo"],
                            ranks=int(coll["ranks"]), topo=topo,
                            payload=float(coll["payload_bytes"])).build()
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
    recs = dc.schedule.records
    state = dict(
        rank_hosts=placed, dc=dc, eps=eps,
        # a flow by its ranks, as the reference lists it
        flow_key=(np.fromiter((r.src for r in recs), np.int64, len(recs))
                  * R
                  + np.fromiter((r.dst for r in recs), np.int64, len(recs))),
        lap_advances=int(tr["lap_advances"]),
        superstep=int(tr["superstep"]),
        done_eps=float(run.cell.config["precision"]["done_eps"]))
    with run.spans.span("warmup"):
        lap(run, state)
    return state


def reference(run, state, precision: str = "f64"):
    """(completions, activations) of the reference over as many
    advances, each flow named as ``flow_key`` names it."""
    ref = run.cell.reference
    p = run.cell.config["platform"]
    coll = run.cell.traffic["collective"]
    R = int(coll["ranks"])
    dag = ref.rdb_dag(R)
    system, delay = ref.dag_system(
        p["topo"], float(p["bw_bytes_per_s"]), float(p["lat_s"]),
        state["rank_hosts"], dag)
    sizes = np.full(len(dag.src), float(coll["payload_bytes"]))
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
