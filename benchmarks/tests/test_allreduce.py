"""The allreduce cell (``dfly65k-allreduce.drain``) at its tiny twin's
geometry, on the CPU: 128 ranks, one a host, on the 128-host dragonfly,
7 steps of recursive doubling, 896 messages.  The placement its driver
draws, the reference's own graph against the program's, the harness end
to end against the reference, each control of its ``correct`` (the
reference in bfloat16, and the faults a collective tape can have), the
refusal of a program that routes all pairs, and the readers the cell
brings (its two shares of a burst are ``drain.var_entry_pct``'s and
``drain.worked_elem_pct``'s: ``test_var_entry.py``,
``test_ladder_metrics.py``)."""

import time
import types

import numpy as np
import pytest

import tiny
from configs import dragonfly_lv08_rdb as rdbref
from drivers import coll_allreduce
from lib import manifest as mf

CELL = "tiny128-allreduce.drain"
R, STEPS = 128, 7
COLL = {"ranks": R}


def over(result):
    return {k for k, row in result["compared"].items()
            if not row["value"] <= row["limit"]}


def rid(rank, step):
    """The program's record of rank's send in ``step`` (rank-major, in
    send program order), and the reference's flow of the same name."""
    return rank * STEPS + step


# -- the placement and the files --------------------------------------------

def test_a_seed_renumbers_the_ranks_and_keeps_every_steps_host_pairs():
    a = coll_allreduce.rank_hosts(COLL, 128, 2**31 + 5)
    b = coll_allreduce.rank_hosts(COLL, 128, 2**31 + 5)
    c = coll_allreduce.rank_hosts(COLL, 128, 6)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len({int(coll_allreduce.rank_hosts(COLL, 128, s)[0])
                for s in range(40)}) > 8
    for hosts in (a, c):
        assert sorted(hosts) == list(range(128))       # one rank a host
        assert len(set(hosts ^ np.arange(128))) == 1   # r xor s
    for k in range(STEPS):
        pairs = [{(int(h[r]), int(h[r ^ (1 << k)])) for r in range(R)}
                 for h in (a, c, np.arange(R))]
        assert pairs[0] == pairs[1] == pairs[2]


@pytest.mark.parametrize("ranks", [1, 96, 256])
def test_ranks_that_do_not_fit_the_platform_are_refused(ranks):
    with pytest.raises(ValueError, match="do not fit"):
        coll_allreduce.rank_hosts({"ranks": ranks}, 128, 1)


def test_the_deployment_is_dfly65k_pairwises_but_for_its_collective():
    ours = mf.Cell(mf.load_manifest(), "dfly65k-allreduce.drain")
    theirs = mf.Cell(mf.load_manifest(), "dfly65k-pairwise.drain")
    for key in ("platform", "network_model", "precision", "engine_flags"):
        assert ours.config[key] == theirs.config[key], key
    assert ours.config["guarantees"][:-1] \
        == theirs.config["guarantees"][:-1]
    assert "latency" in ours.config["guarantees"][-1]
    assert ours.traffic["limits"] == theirs.traffic["limits"]
    for key in ("superstep", "lap_advances"):
        assert ours.traffic[key] == theirs.traffic[key], key
    coll = ours.traffic["collective"]
    assert coll["ranks"] == ours.config["ranks"] \
        == ours.config["platform"]["hosts"] == 65536
    assert ours.config["allreduce"] == {
        "algorithm": coll["algo"], "steps": 16,
        "payload_bytes": coll["payload_bytes"]}
    assert coll["payload_bytes"] == ours.config["flow_bytes"] == 8192.0 \
        < 10000                                  # the selector's bound
    assert coll["ranks"] * 16 == ours.config["flows"] \
        == ours.config["shape"]["variables"]
    assert ours.config["reduced"] == ["allreduce_advances"]
    assert ours.config["reference"] == "dragonfly_lv08_rdb"
    assert ours.entry["chips"] == 1
    assert len(ours.config["source"]) <= 200


# -- the reference's own graph ------------------------------------------------

def test_the_references_dag_is_recursive_doubling():
    dag = rdbref.rdb_dag(R)
    assert len(dag.src) == R * STEPS
    for r, k in ((0, 0), (5, 0), (0, 1), (77, 3), (127, 6)):
        f = rid(r, k)
        peer = r ^ (1 << k)
        assert (dag.src[f], dag.dst[f]) == (r, peer)
        b = 1 << max(k - 1, 0)
        want = set() if k == 0 else {
            rid(r, k - 1), rid(r ^ b, k - 1),            # r sent, heard
            rid(peer, k - 1), rid(peer ^ b, k - 1)}      # its peer did
        assert set(dag.preds[f][dag.preds[f] >= 0].tolist()) == want
        if k:
            assert dag.dst[rid(r ^ b, k - 1)] == r       # what r heard
    with pytest.raises(ValueError, match="power of two"):
        rdbref.rdb_dag(96)


def test_the_program_lowers_the_references_graph():
    """The schedule the program generates (held to smpi/coll.py by
    tests/test_collectives.py) has the reference's edges, flow for
    flow."""
    from simgrid_tpu.collectives import generate
    sched = generate("allreduce", "rdb", R, 8192.0)
    dag = rdbref.rdb_dag(R)
    assert len(sched.records) == len(dag.src)
    index = {(int(s), int(d)): f
             for f, (s, d) in enumerate(zip(dag.src, dag.dst))}
    assert len(index) == len(dag.src)
    for rec in sched.records:
        f = index[rec.src, rec.dst]
        assert f == rec.rid and rec.size == 8192.0
        assert {index[p.src, p.dst] for p in rec.preds} \
            == set(dag.preds[f][dag.preds[f] >= 0].tolist())


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
    real = coll_allreduce.check
    monkeypatch.setattr(coll_allreduce, "check",
                        lambda run, state, rec: real(run, state, rec,
                                                     precision="bf16"))
    result = tiny.execute(CELL, seed=seed)
    assert result["correct"] is False
    assert "date_gap" in over(result)


def drop_the_binding_edge(dc):
    """The first message that waits for ONE predecessor longer than
    for the others loses that edge, and so starts too soon."""
    sim = dc.make_sim(superstep=16)
    sim.run(max_advances=48)
    done = {f: t for t, f in sim.events}
    for _, first in sorted(sim.collective_events):
        preds = dc.edge_src[dc.edge_dst == first]
        if len(preds) < 2 or any(p not in done for p in preds):
            continue
        preds = sorted(preds, key=done.get)
        if done[preds[-1]] > done[preds[-2]]:
            break
    keep = ~((dc.edge_dst == first) & (dc.edge_src == preds[-1]))
    assert keep.sum() == len(keep) - 1
    dc.edge_src, dc.edge_dst = dc.edge_src[keep], dc.edge_dst[keep]
    dc.pred0 = dc.pred0.copy()
    dc.pred0[first] -= 1
    return dc


def no_latency(dc):
    from simgrid_tpu.collectives import DeviceCollective
    return DeviceCollective(dc.schedule, dc.topology,
                            exec_cost=np.zeros(dc.n_v))


def a_step_early(dc):
    """Step 1's messages wait for nothing: they start with step 0's."""
    step1 = np.array([rid(r, 1) for r in range(R)])
    keep = ~np.isin(dc.edge_dst, step1)
    dc.edge_src, dc.edge_dst = dc.edge_src[keep], dc.edge_dst[keep]
    dc.pred0 = dc.pred0.copy()
    dc.pred0[step1] = 0
    dc.ready0 = dc.ready0.copy()
    dc.ready0[step1] = dc.exec_cost[step1]
    return dc


@pytest.mark.parametrize("fault, caught_by", [
    (drop_the_binding_edge, {"date_gap"}),
    (no_latency, {"date_gap", "activations_unmatched"}),
    (a_step_early, {"date_gap", "order_gap"}),
])
def test_a_faulty_tape_is_not_correct(fault, caught_by, monkeypatch):
    tiny.patch(monkeypatch)
    real = coll_allreduce.lowered
    monkeypatch.setattr(coll_allreduce, "lowered",
                        lambda run, hosts: fault(real(run, hosts)))
    result = tiny.execute(CELL, seconds=0.2)
    assert result["correct"] is False
    assert caught_by & over(result), result["compared"]


def half_the_activations(sim, real_run, max_advances, calls):
    real_run(sim, max_advances=max_advances)
    sim.collective_events[:] = sim.collective_events[::2]


def unsteady_laps(sim, real_run, max_advances, calls):
    real_run(sim, max_advances=max_advances)
    if len(calls) == 3:
        t, fid = sim.collective_events[-1]
        sim.collective_events[-1] = (t * (1 + 1e-9), fid)


def state_unchanged(sim, real_run, max_advances, calls):
    pass                                     # the step returns as it came


@pytest.mark.parametrize("fault, caught_by", [
    (half_the_activations, {"activations_unmatched"}),
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


def test_a_program_that_routes_every_pair_is_refused_before_it_starts(
        monkeypatch):
    """The parent's ``RoutedTopology`` routed all R x R pairs in its
    constructor: built over two hosts it already holds constraints, and
    the driver stops there, before it hands it 65,536."""
    from simgrid_tpu import collectives
    tiny.patch(monkeypatch)
    built = []

    class AllPairs(collectives.RoutedTopology):
        def __init__(self, engine, hosts):
            super().__init__(engine, hosts)
            built.append(len(hosts))
            self.lower(*np.nonzero(~np.eye(len(hosts), dtype=bool)))

    monkeypatch.setattr(collectives, "RoutedTopology", AllPairs)
    with pytest.raises(RuntimeError, match="every ordered pair"):
        tiny.execute(CELL)
    assert built == [2]


# -- the readers, on a hand-made run and through the harness ------------------

def reader(name):
    return mf.load_module("metrics", name).read


def handmade(counters, advances=64, rounds=96,
             shape=(143993, 1048576, 9234862)):
    counters = dict(counters, fixpoint_rounds=rounds) if rounds \
        else dict(counters)
    return types.SimpleNamespace(
        counters=counters, shape=shape,
        record={"advances": advances, "events": 70},
        spans=types.SimpleNamespace(window_from=float("inf")))


def test_the_two_set_up_readers_tell_the_spans_apart_by_id():
    from simgrid_tpu.ops import opstats
    sched, route = reader("coll.schedule_s"), reader("coll.route_us_per_pair")
    opstats.reset()
    run = handmade({})
    assert sched(run) is None and route(run) is None
    with opstats.span("coll.lower", id="schedule"):
        time.sleep(0.02)
    with opstats.span("coll.lower", id="tape"):
        time.sleep(0.01)
    assert 0.02 <= sched(run) < 0.03 + 0.5
    # a program that routes without counting the pairs: nothing to read
    with opstats.span("coll.lower", id="routes"):
        time.sleep(0.01)
    assert route(run) is None
    opstats.bump("collective_routes", 1000)
    assert 10.0 <= route(run) < 1000.0       # 0.01 s over 1,000 pairs
    run.spans.window_from = 0.0              # all of it inside the window
    assert sched(run) is None and route(run) is None
    opstats.reset()


def test_the_tiny_cell_reads_them_through_the_harness(monkeypatch):
    """A step's bursts are small beside the tiny ladder's one rung, so
    nothing enters from the variable side here; the readers still
    read.  BENCHMARK.json lists the cell wherever the pairwise cell is
    listed, and for the two parts of its ``coll.lower`` besides."""
    manifest = mf.load_manifest()
    tiny.patch(monkeypatch)
    from lib import harness
    seen = {}
    real = harness.read_metrics
    monkeypatch.setattr(harness, "read_metrics", lambda run, e2e: (
        seen.setdefault("run", run), real(run, e2e))[1])
    tiny.execute(CELL)
    run = seen["run"]
    assert run.shape == (391, 896, 7392)
    assert reader("drain.var_entry_pct")(run) == 0.0
    assert reader("drain.worked_elem_pct")(run) >= 100.0
    assert reader("coll.schedule_s")(run) > 0
    assert 0 < reader("coll.route_us_per_pair")(run) < 1e4
    assert reader("coll.lower_s")(run) > reader("coll.schedule_s")(run)
    assert 0.0 < reader("coll.live_flow_pct")(run) <= 100.0
    assert reader("coll.activations_per_advance")(run) > 0
    assert reader("coll.events_per_advance")(run) > 0
    ours = {m["name"] for m in mf.Cell(
        manifest, "dfly65k-allreduce.drain").per_layer()}
    pairwise = {m["name"] for m in mf.Cell(
        manifest, "dfly65k-pairwise.drain").per_layer()}
    assert ours - pairwise == {"coll.schedule_s", "coll.route_us_per_pair"}
    assert pairwise <= ours


def test_a_traced_run_reports_every_metric_the_manifest_lists(monkeypatch):
    """``coll_allreduce`` drives ``drain``'s compiled program too: on
    the recorded drain's trace the six columns of an advance read (the
    tape's 0: it never ran there), with the window's own counters."""
    tiny.patch(monkeypatch)
    tiny.traced(monkeypatch)
    result = tiny.execute(CELL, trace=True)
    assert set(result["metrics"]) == {m["name"] for m in mf.Cell(
        tiny.tiny_manifest(), CELL).per_layer()}
    got = {name: m["value"] for name, m in result["metrics"].items()}
    assert got["drain.coll_ms"] == got["drain.partition_ms"] == 0.0
    assert got["drain.solve_init_ms"] > 0 and got["drain.retire_ms"] > 0
    assert got["drain.init_ms"] > 0 and got["coll.src_walk_pct"] == 100.0
    assert "drain.advance_ms" not in got and "drain.upload_ms" not in got
