"""The pairwise cell (``dfly65k-pairwise.drain``) at its tiny twin's
geometry, on the CPU: the rank placement its driver draws, the harness
end to end against the DAG reference, each control of its ``correct``
(the reference in bfloat16, and the faults a collective tape can have:
an edge of the DAG dropped, the latency left out, a step started a step
early, activations lost, a lap that differs), and the four readers it
brings, on a hand-made run."""

import types

import numpy as np
import pytest

import tiny
from configs import dragonfly_lv08_dag as dagref
from drivers import coll_drain
from lib import manifest as mf

CELL = "tiny128-pairwise.drain"
COLL = {"ranks": 16, "stride": 8}
R = 16


def over(result):
    return {k for k, row in result["compared"].items()
            if not row["value"] <= row["limit"]}


def rid(rank, step):
    return (rank % R) * (R - 1) + (step - 1)


# -- the placement and the files --------------------------------------------

def test_a_seed_rotates_the_ranks_over_the_same_hosts():
    a = coll_drain.rank_hosts(COLL, 128, 2**31 + 5)
    b = coll_drain.rank_hosts(COLL, 128, 2**31 + 5)
    shifts = {int(coll_drain.rank_hosts(COLL, 128, s)[0]) // 8
              for s in range(40)}
    assert np.array_equal(a, b) and len(shifts) > 8
    for hosts in (a, coll_drain.rank_hosts(COLL, 128, 6)):
        assert sorted(hosts) == list(range(0, 128, 8))
        # a rotation: the next rank sits on the next host, cyclically
        assert set((np.diff(hosts) // 8) % R) == {1}
    # so a step's set of host pairs is the same whatever the seed drew
    c = coll_drain.rank_hosts(COLL, 128, 6)
    for k in (1, 5, 15):
        assert {(a[r], a[(r + k) % R]) for r in range(R)} \
            == {(c[r], c[(r + k) % R]) for r in range(R)}


@pytest.mark.parametrize("ranks, stride", [(1, 8), (16, 0), (17, 8),
                                           (16, 9)])
def test_ranks_that_do_not_fit_the_platform_are_refused(ranks, stride):
    with pytest.raises(ValueError, match="do not fit"):
        coll_drain.rank_hosts({"ranks": ranks, "stride": stride}, 128, 1)


def test_the_deployment_is_dfly65k_alltoalls_but_for_its_staging():
    ours = mf.Cell(mf.load_manifest(), "dfly65k-pairwise.drain")
    theirs = mf.Cell(mf.load_manifest(), "dfly65k-alltoall.solve")
    drain = mf.Cell(mf.load_manifest(), "dfly65k-random.drain")
    for key in ("platform", "precision", "engine_flags", "flow_bytes",
                "ranks", "flows"):
        assert ours.config[key] == theirs.config[key], key
    model = dict(ours.config["network_model"])
    assert model.pop("latency_factor") == dagref.LATENCY_FACTOR == 13.01
    assert model == theirs.config["network_model"]
    assert ours.config["guarantees"][:-1] == theirs.config["guarantees"]
    assert "latency" in ours.config["guarantees"][-1]
    limits = dict(ours.traffic["limits"])
    assert limits.pop("activations_unmatched") == 0
    assert limits == drain.traffic["limits"]
    coll = ours.traffic["collective"]
    assert (coll["ranks"], coll["stride"]) == (320, 65536 // 320)
    assert ours.config["alltoall"] == {
        "algorithm": "pairwise", "steps": 319,
        "block_bytes": coll["block_bytes"]}
    assert coll["ranks"] * (coll["ranks"] - 1) == ours.config["flows"]
    assert ours.config["reduced"] == ["pairwise_advances"]
    assert ours.config["reference"] == "dragonfly_lv08_dag"


# -- the reference's own graph ------------------------------------------------

def test_the_references_dag_is_the_selectors_pairwise_staging():
    dag = dagref.pairwise_dag(R)
    assert len(dag.src) == R * (R - 1)
    for r, k in ((0, 1), (3, 1), (0, 2), (7, 9), (15, 15)):
        f = rid(r, k)
        assert (dag.src[f], dag.dst[f]) == (r, (r + k) % R)
        want = set() if k == 1 else {
            rid(r, k - 1), rid(r - (k - 1), k - 1),        # r sent, heard
            rid(r + k, k - 1), rid(r + 1, k - 1)}          # its peer did
        assert set(dag.preds[f][dag.preds[f] >= 0].tolist()) == want
    # what the peer heard in step k - 1 came from rank r + 1
    f = rid(r + 1, k - 1)
    assert dag.dst[f] == (r + k) % R


def test_the_program_lowers_the_references_graph(monkeypatch):
    """The schedule the program generates (held to smpi/coll.py by
    tests/test_collectives.py) has the reference's edges, flow for
    flow."""
    from simgrid_tpu.collectives import generate
    sched = generate("alltoall", "pairwise", R, 1e6)
    dag = dagref.pairwise_dag(R)
    index = {(int(s), int(d)): f
             for f, (s, d) in enumerate(zip(dag.src, dag.dst))}
    for rec in sched.records:
        f = index[rec.src, rec.dst]
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
    real = coll_drain.check
    monkeypatch.setattr(coll_drain, "check",
                        lambda run, state, rec: real(run, state, rec,
                                                     precision="bf16"))
    result = tiny.execute(CELL, seed=seed)
    assert result["correct"] is False
    assert "date_gap" in over(result)


def drop_the_binding_edge(dc):
    """The first block that waits for ONE predecessor longer than for
    the others loses that edge, and so starts too soon."""
    sim = dc.make_sim(superstep=16)
    sim.run()
    done = {f: t for t, f in sim.events}
    for _, first in sorted(sim.collective_events):
        preds = sorted(dc.edge_src[dc.edge_dst == first], key=done.get)
        if len(preds) > 1 and done[preds[-1]] > done[preds[-2]]:
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
    """Step 2's blocks wait for nothing: they start with step 1's."""
    step2 = np.array([rid(r, 2) for r in range(R)])
    keep = ~np.isin(dc.edge_dst, step2)
    dc.edge_src, dc.edge_dst = dc.edge_src[keep], dc.edge_dst[keep]
    dc.pred0 = dc.pred0.copy()
    dc.pred0[step2] = 0
    dc.ready0 = dc.ready0.copy()
    dc.ready0[step2] = dc.exec_cost[step2]
    return dc


@pytest.mark.parametrize("fault, caught_by", [
    (drop_the_binding_edge, {"date_gap"}),
    (no_latency, {"date_gap", "activations_unmatched"}),
    (a_step_early, {"date_gap", "order_gap"}),
])
def test_a_faulty_tape_is_not_correct(fault, caught_by, monkeypatch):
    tiny.patch(monkeypatch)
    real = coll_drain.lowered
    monkeypatch.setattr(coll_drain, "lowered",
                        lambda run, hosts: fault(real(run, hosts)))
    result = tiny.execute(CELL, seconds=0.2)
    assert result["correct"] is False
    assert caught_by & over(result), result["compared"]


def half_the_activations(sim, real_run, max_advances):
    real_run(sim, max_advances=max_advances)
    sim.collective_events[:] = sim.collective_events[::2]


def unsteady_laps(sim, real_run, max_advances):
    real_run(sim, max_advances=max_advances)
    unsteady_laps.calls = getattr(unsteady_laps, "calls", 0) + 1
    if unsteady_laps.calls == 3:
        t, fid = sim.collective_events[-1]
        sim.collective_events[-1] = (t * (1 + 1e-9), fid)


def state_unchanged(sim, real_run, max_advances):
    pass                                     # the step returns as it came


@pytest.mark.parametrize("fault, caught_by", [
    (half_the_activations, {"activations_unmatched"}),
    (unsteady_laps, {"laps_differing"}),
    (state_unchanged, {"date_gap", "advances_short"}),
])
def test_a_faulty_run_is_not_correct(fault, caught_by, monkeypatch):
    tiny.patch(monkeypatch)
    from simgrid_tpu.ops.lmm_drain import DrainSim
    real_run = DrainSim.run
    monkeypatch.setattr(
        DrainSim, "run",
        lambda sim, max_advances=10_000_000: fault(sim, real_run,
                                                   max_advances))
    result = tiny.execute(CELL, seconds=0.2)
    assert result["correct"] is False
    assert caught_by & over(result), result["compared"]


# -- the readers, on a hand-made run ----------------------------------------

def reader(name):
    return mf.load_module("metrics", name).read


def handmade(counters, advances=64, events=70,
             shape=(8724, 102080, 1275102)):
    return types.SimpleNamespace(
        counters=counters, shape=shape,
        record={"advances": advances, "events": events},
        spans=types.SimpleNamespace(window_from=0.0))


def test_live_flow_pct_is_a_share_of_the_flow_slots():
    read = reader("coll.live_flow_pct")
    run = handmade({"collective_live_flow_advances": 64 * 320})
    assert read(run) == pytest.approx(100 * 320 / 102080)
    # a program without the counter: left out, never 0
    assert read(handmade({})) is None
    assert read(handmade({"collective_live_flow_advances": 5},
                         advances=0)) is None
    run.shape = None
    assert read(run) is None


def test_the_two_rates_are_over_the_advances_committed():
    acts, evs = (reader("coll.activations_per_advance"),
                 reader("coll.events_per_advance"))
    run = handmade({"collective_tape_fires": 640,
                    "collective_tape_slots": 102080})
    assert acts(run) == 10.0 and evs(run) == 70 / 64
    assert acts(handmade({})) is None and evs(handmade({})) is None
    assert acts(handmade({"collective_tape_fires": 1},
                         advances=0)) is None
    # completions of a drain without a tape are no reading of this one
    assert evs(handmade({"fixpoint_rounds": 9})) is None


def test_coll_lower_s_adds_the_set_ups_spans():
    import time
    from simgrid_tpu.ops import opstats
    read = reader("coll.lower_s")
    opstats.reset()
    run = handmade({})
    run.spans.window_from = time.perf_counter() + 3600
    assert read(run) is None
    with opstats.span("coll.lower", id="routes"):
        time.sleep(0.01)
    with opstats.span("coll.lower", id="tape"):
        time.sleep(0.01)
    assert 0.02 <= read(run) < 1.0
    run.spans.window_from = 0.0              # both were in the window
    assert read(run) is None


def test_the_tiny_cell_reports_the_four(monkeypatch):
    """Through the harness: 16 of 240 slots live at most, and every
    advance either starts or finishes something."""
    tiny.patch(monkeypatch)
    from lib import harness
    seen = {}
    real = harness.read_metrics
    monkeypatch.setattr(harness, "read_metrics", lambda run, e2e: (
        seen.setdefault("run", run), real(run, e2e))[1])
    tiny.execute(CELL)
    run = seen["run"]
    assert run.shape == (114, 240, 2418)
    assert 0.0 < reader("coll.live_flow_pct")(run) <= 100 * 16 / 240
    acts = reader("coll.activations_per_advance")(run)
    evs = reader("coll.events_per_advance")(run)
    assert acts > 0 and evs > 0 and acts + evs >= 1.0
    assert reader("coll.lower_s")(run) > 0
    assert reader("drain.worked_elem_pct")(run) > 0


def test_a_traced_run_reads_the_supersteps_passes(monkeypatch):
    """``coll_drain`` drives ``drain``'s compiled program: the pass
    readers find it by ITS name and the record's ``advances``.  The
    recorded trace is a drain's without a tape, so the tape's pass
    reads 0 and the five others add up to the program's time."""
    from lib import scopes
    tiny.patch(monkeypatch)
    tiny.traced(monkeypatch)
    result = tiny.execute(CELL, trace=True)
    got = {name: m["value"] for name, m in result["metrics"].items()}
    columns = ["drain.solve_init_ms", "drain.rounds_ms",
               "drain.partition_ms", "drain.coll_ms", "drain.ring_ms",
               "drain.retire_ms"]
    assert got["drain.coll_ms"] == 0.0 and got["drain.ring_ms"] > 0
    # the recorded superstep: 41.61 of its 43.03 ms inside the rounds
    assert got["drain.rounds_ms"] / sum(got[c] for c in columns) \
        == pytest.approx(0.967, abs=1e-3)
    assert got["coll.src_walk_pct"] == 100.0
    assert {n for n, _s in result["breakdown"]["idle_gaps"]} >= {
        "sg:drain.init", "sg:fetch"}
    assert scopes.SUPERSTEP == ("jit__superstep_program", "advances")
