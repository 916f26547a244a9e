"""The ring allreduce cell (``dfly65k-allreduce-lr.drain``) at its tiny
twin's geometry, on the CPU: 128 ranks, one a host, on the 128-host
dragonfly, the self-copy and 4 ring steps of SMPI's ``lr`` allreduce,
640 messages.  The placement its driver draws, the reference's own
graph against the program's head, the cut of the head proved at 1,024
ranks, the harness end to end against the reference, each control of
its ``correct`` (the reference in bfloat16, and the faults a ring tape
can have), the refusal of a program that lowers no head, and the
reader the cell brings."""

import json
import time
import types

import numpy as np
import pytest

import tiny
from configs import dragonfly_lv08_ring as ringref
from drivers import coll_lr
from lib import manifest as mf

CELL = "tiny128-allreduce-lr.drain"
R, S = 128, 4
COLL = {"ranks": R}
#: the cell's platform, for the reference at 1,024 ranks
TOPO_65K = "16,3;4,2;16,2;64"


def over(result):
    return {k for k, row in result["compared"].items()
            if not row["value"] <= row["limit"]}


def rid(rank, step):
    """The program's record of rank's send in ``step`` (rank-major, in
    send program order; step 0 the self-copy), and the reference's flow
    of the same name."""
    return rank % R * (S + 1) + step


# -- the placement and the files --------------------------------------------

def test_a_seed_rotates_the_ranks_and_keeps_the_host_pairs():
    a = coll_lr.rank_hosts(COLL, 128, 2**31 + 5)
    b = coll_lr.rank_hosts(COLL, 128, 2**31 + 5)
    c = coll_lr.rank_hosts(COLL, 128, 6)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len({int(coll_lr.rank_hosts(COLL, 128, s)[0])
                for s in range(40)}) > 8
    for hosts in (a, c):
        assert sorted(hosts) == list(range(128))         # one rank a host
        assert set(np.diff(hosts) % R) == {1}            # a rotation
    pairs = [{(int(h[r]), int(h[(r + d) % R])) for r in range(R)
              for d in (0, 1)} for h in (a, c, np.arange(R))]
    assert pairs[0] == pairs[1] == pairs[2]


@pytest.mark.parametrize("ranks", [2, 256])
def test_ranks_that_do_not_fit_the_platform_are_refused(ranks):
    with pytest.raises(ValueError, match="does not fit"):
        coll_lr.rank_hosts({"ranks": ranks}, 128, 1)


def test_the_deployment_is_dfly65k_allreduces_but_for_its_algorithm():
    ours = mf.Cell(mf.load_manifest(), "dfly65k-allreduce-lr.drain")
    theirs = mf.Cell(mf.load_manifest(), "dfly65k-allreduce.drain")
    for key in ("platform", "network_model", "precision", "engine_flags",
                "guarantees"):
        assert ours.config[key] == theirs.config[key], key
    assert ours.traffic["limits"] == theirs.traffic["limits"]
    for key in ("superstep", "lap_advances"):
        assert ours.traffic[key] == theirs.traffic[key], key
    coll, ar = ours.traffic["collective"], ours.config["allreduce"]
    assert coll["ranks"] == ours.config["ranks"] \
        == ours.config["platform"]["hosts"] == 65536
    assert (ar["algorithm"], ar["count"], ar["elem_bytes"],
            ar["steps_held"]) == (coll["algo"], coll["count"],
                                  coll["elem_bytes"], coll["steps_held"])
    assert ar["count"] == 2**30
    assert ar["steps_published"] == 2 * (coll["ranks"] - 1)
    # SMPI's OpenMPI selector sends it by the ring (coll_selectors.py)
    block = ar["count"] * ar["elem_bytes"]
    assert block >= 10000 and ar["count"] > coll["ranks"] \
        and coll["ranks"] * (1 << 20) >= block
    assert ar["chunk_bytes"] == ar["count"] // coll["ranks"] \
        * ar["elem_bytes"] == ours.config["flow_bytes"] == 131072
    assert coll["ranks"] * (ar["steps_held"] + 1) == ours.config["flows"] \
        == ours.config["shape"]["variables"]
    assert ours.config["shape"]["dag_edges"] \
        == coll["ranks"] * (2 + 3 * (ar["steps_held"] - 1))
    assert ours.config["reduced"] == ["allreduce_advances", "ring_steps"]
    assert ours.config["reference"] == "dragonfly_lv08_ring"
    assert ours.entry["chips"] == 1
    assert len(ours.config["source"]) <= 200


def test_the_tiny_twin_is_the_cell_at_128_ranks():
    ours = mf.Cell(tiny.tiny_manifest(), CELL)
    big = mf.Cell(mf.load_manifest(), "dfly65k-allreduce-lr.drain")
    assert ours.traffic["driver"] == big.traffic["driver"] == "coll_lr"
    assert ours.traffic["collective"]["ranks"] == R
    assert ours.traffic["collective"]["steps_held"] == S
    assert ours.config["allreduce"]["chunk_bytes"] \
        == big.config["allreduce"]["chunk_bytes"]
    assert ours.config["reference"] == "dragonfly_lv08_ring"


# -- the reference's own graph, and the cut of the head ------------------------

def test_the_references_dag_is_the_ring():
    dag = ringref.ring_dag(R, S)
    assert len(dag.src) == R * (S + 1)
    for r, k in ((0, 0), (5, 0), (0, 1), (127, 1), (77, 3), (127, 4)):
        f = rid(r, k)
        assert (dag.src[f], dag.dst[f]) == (r, r if k == 0 else (r + 1) % R)
        want = (set() if k == 0
                else {rid(r, 0), rid(r + 1, 0)} if k == 1
                else {rid(r - 1, k - 1), rid(r, k - 1), rid(r + 1, k - 1)})
        assert set(dag.preds[f][dag.preds[f] >= 0].tolist()) == want
    assert ringref.ring_step(R, S)[rid(9, 3)] == 3
    for ranks, steps in ((2, 1), (R, 0), (R, 2 * R - 1)):
        with pytest.raises(ValueError, match="steps"):
            ringref.ring_dag(ranks, steps)


def test_the_program_lowers_the_references_graph():
    """The head the program generates (held to smpi/coll.py's captured
    programs by tests/test_collectives.py) has the reference's edges,
    flow for flow."""
    from simgrid_tpu.collectives import generate
    sched = generate("allreduce", "lr", R, R * 16384, steps=S)
    dag = ringref.ring_dag(R, S)
    assert len(sched.records) == len(dag.src)
    src = np.array([r.src for r in sched.records])
    steps = coll_lr.ring_steps(src)
    for rec in sched.records:
        f = rid(rec.src, int(steps[rec.rid]))
        assert f == rec.rid and rec.size == 131072.0
        assert (rec.src, rec.dst) == (dag.src[f], dag.dst[f])
        assert {p.rid for p in rec.preds} \
            == set(dag.preds[f][dag.preds[f] >= 0].tolist())


def first_advances(ranks, steps, advances):
    dag = ringref.ring_dag(ranks, steps)
    system, delay = ringref.dag_system(TOPO_65K, 125e6, 5e-5,
                                       np.arange(ranks), dag)
    done, started, info = ringref.drain(
        system, dag, delay, np.full(len(dag.src), 131072.0), advances)
    key = coll_lr.flow_key(dag.src, dag.dst,
                           ringref.ring_step(ranks, steps), ranks)
    return ([(t, int(key[f])) for t, f in done],
            [(t, int(key[f])) for t, f in started], info)


def test_a_head_of_four_steps_is_the_ring_of_eight_for_32_advances():
    """At 1,024 ranks on the cell's platform (hosts in name order), the
    reference's first 32 advances over 4 ring steps and over 8 agree
    event for event, keyed by (sender, receiver, step): the cut is
    never reached."""
    four = first_advances(1024, 4, 32)
    eight = first_advances(1024, 8, 32)
    assert four[0] == eight[0] and four[1] == eight[1]
    assert four[2]["advances"] == eight[2]["advances"] == 32
    assert four[2]["t_sim"] == eight[2]["t_sim"]
    assert len(four[0]) > 1024 and len(four[1]) > 2048


def test_flows_are_keyed_by_sender_receiver_and_step():
    dag = ringref.ring_dag(R, 2 * (R - 1))
    key = coll_lr.flow_key(dag.src, dag.dst,
                           ringref.ring_step(R, 2 * (R - 1)), R)
    assert len(set(key.tolist())) == len(key)


# -- the harness end to end, and correct shown to fail ------------------------

@pytest.mark.parametrize("seed", [3, 2**31 + 4])
def test_the_tiny_cell_is_correct(seed, monkeypatch):
    tiny.patch(monkeypatch)
    result = tiny.execute(CELL, seed=seed)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2
    assert set(result["compared"]) == {
        "date_gap", "order_gap", "events_unmatched",
        "activations_unmatched", "laps_differing", "advances_short"}
    assert result["compared"]["date_gap"]["value"] < 1e-12   # f64 here
    assert result["metrics"]["events_per_s"]["value"] > 0


@pytest.mark.parametrize("seed", [3, 2**31 + 4, 77])
def test_control_in_bfloat16_is_not_correct(seed, monkeypatch):
    tiny.patch(monkeypatch)
    real = coll_lr.check
    monkeypatch.setattr(coll_lr, "check",
                        lambda run, state, rec: real(run, state, rec,
                                                     precision="bf16"))
    result = tiny.execute(CELL, seed=seed)
    assert result["correct"] is False
    assert "date_gap" in over(result)


def cut_edges(dc, drop):
    """``dc`` without the edges ``drop`` marks; a record left with no
    predecessor waits for its latency alone."""
    keep = ~drop
    dc.edge_src, dc.edge_dst = dc.edge_src[keep], dc.edge_dst[keep]
    dc.pred0 = np.bincount(dc.edge_dst[dc.edge_dst < dc.n_v],
                           minlength=dc.n_v).astype(np.int32)
    roots = dc.pred0 == 0
    dc.ready0 = np.where(roots, dc.exec_cost, np.inf)
    return dc


def no_self_copy(dc):
    """Step 1 waits for no self-copy: it starts at its own latency."""
    copies = np.array([rid(r, 0) for r in range(R)])
    return cut_edges(dc, np.isin(dc.edge_src, copies))


def no_left_neighbour(dc):
    """A step-k message (k > 1) forgets what rank r - 1 sent it."""
    drop = np.zeros(len(dc.edge_src), bool)
    for r in range(R):
        for k in range(2, S + 1):
            drop |= (dc.edge_dst == rid(r, k)) \
                & (dc.edge_src == rid(r - 1, k - 1))
    assert drop.sum() == R * (S - 1)
    return cut_edges(dc, drop)


def no_latency(dc):
    from simgrid_tpu.collectives import DeviceCollective
    return DeviceCollective(dc.schedule, dc.topology,
                            exec_cost=np.zeros(dc.n_v))


@pytest.mark.parametrize("fault, caught_by", [
    (no_self_copy, {"date_gap", "activations_unmatched"}),
    (no_left_neighbour, {"date_gap", "order_gap", "activations_unmatched"}),
    (no_latency, {"date_gap", "activations_unmatched"}),
])
def test_a_faulty_tape_is_not_correct(fault, caught_by, monkeypatch):
    tiny.patch(monkeypatch)
    real = coll_lr.lowered
    monkeypatch.setattr(coll_lr, "lowered",
                        lambda run, hosts: fault(real(run, hosts)))
    result = tiny.execute(CELL, seconds=0.2)
    assert result["correct"] is False
    assert caught_by & over(result), result["compared"]


def test_a_self_copy_on_no_link_of_the_platform_is_not_correct(
        monkeypatch):
    """The self pair lowered onto none of the host's links: the
    self-copy rides a constraint of its own that never binds, costs no
    bandwidth and leaves the router links to the ring."""
    from simgrid_tpu.ops.lmm_jax import var_index
    tiny.patch(monkeypatch)

    def free_copies(dc):
        copies = np.arange(0, dc.n_v, S + 1, dtype=np.int32)
        assert all(dc.schedule.records[c].src == dc.schedule.records[c].dst
                   for c in copies)
        keep = ~np.isin(dc.e_var, copies)
        dc.e_var = np.concatenate([dc.e_var[keep], copies])
        dc.e_cnst = np.concatenate([dc.e_cnst[keep],
                                    np.full(len(copies), dc.n_c, np.int32)])
        dc.e_w = np.concatenate([dc.e_w[keep], np.ones(len(copies))])
        dc.c_bound = np.append(dc.c_bound, 1e30)
        dc.n_c += 1
        dc.v_ptr, dc.ve_idx = var_index(dc.e_var, dc.e_w, dc.n_v)
        return dc

    real = coll_lr.lowered
    monkeypatch.setattr(coll_lr, "lowered",
                        lambda run, hosts: free_copies(real(run, hosts)))
    result = tiny.execute(CELL, seconds=0.2)
    assert result["correct"] is False
    assert "date_gap" in over(result), result["compared"]


def test_a_self_copy_with_no_element_at_all_stops_the_drain(monkeypatch):
    """With no element at all the self-copies hold no bandwidth and
    nothing else is on the wire yet: the drain refuses loudly, it
    does not invent a date."""
    from simgrid_tpu.collectives import RoutedTopology
    from simgrid_tpu.ops.lmm_jax import SolveError
    tiny.patch(monkeypatch)
    real = RoutedTopology.lower

    def linkless(self, src, dst):
        rec, slots, w = real(self, src, dst)
        keep = np.asarray(src)[rec] != np.asarray(dst)[rec]
        return rec[keep], slots[keep], w[keep]

    monkeypatch.setattr(RoutedTopology, "lower", linkless)
    with pytest.raises(SolveError, match="no flow holds bandwidth"):
        tiny.execute(CELL, seconds=0.2)


def unsteady_laps(sim, real_run, max_advances, calls):
    real_run(sim, max_advances=max_advances)
    if len(calls) == 3:
        t, fid = sim.collective_events[-1]
        sim.collective_events[-1] = (t * (1 + 1e-9), fid)


def state_unchanged(sim, real_run, max_advances, calls):
    pass                                     # the step returns as it came


@pytest.mark.parametrize("fault, caught_by", [
    (unsteady_laps, {"laps_differing"}),
    (state_unchanged, {"date_gap", "advances_short"}),
])
def test_a_faulty_run_is_not_correct(fault, caught_by, monkeypatch):
    tiny.patch(monkeypatch)
    from simgrid_tpu.ops.lmm_drain import DrainSim
    real_run, calls = DrainSim.run, []

    def run(sim, max_advances=10_000_000):
        calls.append(max_advances)
        fault(sim, real_run, max_advances, calls)

    monkeypatch.setattr(DrainSim, "run", run)
    result = tiny.execute(CELL, seconds=0.5)
    assert result["correct"] is False
    assert caught_by & over(result), result["compared"]


def test_a_head_too_short_for_the_lap_is_not_correct(monkeypatch):
    """With one ring step held, the lap runs out of messages the
    reference (over 1 + 4 steps) finishes in the same advances."""
    tiny.patch(monkeypatch)
    real = mf.load_json

    def one_step(path):
        body = real(path)
        if path.endswith(CELL + ".json"):
            body = json.loads(json.dumps(body))
            body["collective"]["steps_held"] = 1
        return body

    monkeypatch.setattr(mf, "load_json", one_step)
    result = tiny.execute(CELL, seconds=0.2)
    assert result["correct"] is False
    assert "events_unmatched" in over(result), result["compared"]


def test_a_program_that_lowers_no_head_is_refused_before_it_starts(
        monkeypatch):
    """A ``CollectiveSpec`` without ``steps=`` would be handed the
    whole ring: the driver stops before it loads the platform."""
    from simgrid_tpu import collectives
    from drivers import _inputs
    tiny.patch(monkeypatch)

    class Whole(collectives.CollectiveSpec):
        def __init__(self, op="allreduce", algo="rdb", ranks=8, topo="nic",
                     payload=1 << 20, bw=1e9, loop_bw=0.0, core_bw=0.0):
            super().__init__(op, algo, ranks, topo, payload, bw, loop_bw,
                             core_bw)

    started = []
    monkeypatch.setattr(collectives, "CollectiveSpec", Whole)
    monkeypatch.setattr(_inputs, "start_engine",
                        lambda *a, **k: started.append(a))
    with pytest.raises(RuntimeError, match="no head"):
        tiny.execute(CELL)
    assert started == []


# -- the reader, on a hand-made run and through the harness -------------------

def test_the_record_reader_divides_the_schedules_self_seconds():
    from simgrid_tpu.ops import opstats
    read = mf.load_module("metrics", "coll.schedule_us_per_record").read
    opstats.reset()
    run = types.SimpleNamespace(
        t0=time.perf_counter(), setup_s=float("nan"), counters={},
        spans=types.SimpleNamespace(records={}, window_from=float("inf")))
    with opstats.span("coll.lower", id="schedule"):
        time.sleep(0.02)
    with opstats.span("coll.lower", id="tape"):
        time.sleep(0.01)
    run.setup_s = time.perf_counter() - run.t0
    assert read(run) is None                 # no records counted
    opstats.bump("collective_schedule_records", 1000)
    assert 20.0 <= read(run) < 20.0 + 500.0  # 0.02 s over 1,000 records
    opstats.reset()


def test_the_tiny_cell_reads_it_through_the_harness(monkeypatch):
    """BENCHMARK.json lists the cell wherever the allreduce cell is
    listed, but for the three lists accepted tests hold to the two
    older tape cells; the self pairs are routed, each once."""
    from lib import harness
    from simgrid_tpu.ops import opstats
    manifest = mf.load_manifest()
    tiny.patch(monkeypatch)
    seen = {}
    real = harness.read_metrics
    monkeypatch.setattr(harness, "read_metrics", lambda run, e2e: (
        seen.setdefault("run", run), real(run, e2e))[1])
    before = opstats.snapshot()
    tiny.execute(CELL)
    took = opstats.diff(before)
    assert took["collective_self_routes"] == R
    assert took["collective_routes"] == 3 * R   # (h, h), (h, h+1), back
    assert took["collective_schedule_records"] == R * (S + 1)
    run = seen["run"]
    assert run.shape[1:] == (R * (S + 1), run.shape[2])
    assert 0 < mf.load_module("metrics", "coll.schedule_us_per_record"
                              ).read(run) < 1e4
    ours = {m["name"] for m in mf.Cell(
        manifest, "dfly65k-allreduce-lr.drain").per_layer()}
    rdb = {m["name"] for m in mf.Cell(
        manifest, "dfly65k-allreduce.drain").per_layer()}
    assert ours == rdb - {"drain.var_entry_pct", "coll.src_walk_pct",
                          "drain.worked_elem_pct"}
    assert "coll.schedule_us_per_record" in ours
