"""The alltoall cell (``dfly65k-alltoall.solve``) at its tiny twin's
geometry, on the CPU: the pair list its driver builds, the controls of
its ``correct`` (the reference in bfloat16, a rank left out), and the
three readers it brings, on a hand-made run."""

import types

import numpy as np
import pytest

import tiny
from drivers import solve_alltoall
from lib import manifest as mf

CELL = "tiny128-alltoall.solve"
A2A = {"ranks": 16, "stride": 8}


def over(result):
    return {k for k, row in result["compared"].items()
            if not row["value"] <= row["limit"]}


# -- the pair list ------------------------------------------------------------

def test_pairs_are_every_ordered_pair_once():
    pairs = solve_alltoall.alltoall_pairs(A2A, 128, 5)
    assert pairs.shape == (16 * 15, 2)
    assert len(set(map(tuple, pairs.tolist()))) == 16 * 15
    assert np.all(pairs[:, 0] != pairs[:, 1])
    hosts = set(range(0, 128, 8))
    assert set(pairs[:, 0]) == hosts and set(pairs[:, 1]) == hosts
    # each rank sends to, and hears from, every other rank
    assert np.all(np.bincount(pairs[:, 0] // 8) == 15)
    assert np.all(np.bincount(pairs[:, 1] // 8) == 15)


def test_a_seed_only_reorders_each_ranks_sends():
    a = solve_alltoall.alltoall_pairs(A2A, 128, 2**31 + 5)
    b = solve_alltoall.alltoall_pairs(A2A, 128, 2**31 + 5)
    c = solve_alltoall.alltoall_pairs(A2A, 128, 6)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert sorted(map(tuple, a)) == sorted(map(tuple, c))
    # the ranks post in rank order, as SMPI runs its actors
    assert np.array_equal(a[:, 0], np.repeat(np.arange(16) * 8, 15))
    assert np.array_equal(a[:, 0], c[:, 0])
    # and each in an order of its own
    rows_a, rows_c = a[:, 1].reshape(16, 15), c[:, 1].reshape(16, 15)
    assert all(not np.array_equal(x, y) for x, y in zip(rows_a, rows_c))
    assert len({tuple((row - row.min()).tolist()) for row in rows_a}) > 1


def test_the_full_width_file_gives_102080_flows():
    cell = mf.Cell(mf.load_manifest(), "dfly65k-alltoall.solve")
    a2a, config = cell.traffic["alltoall"], cell.config
    pairs = solve_alltoall.alltoall_pairs(a2a, config["platform"]["hosts"],
                                          2**31 + 1)
    assert len(pairs) == config["flows"] == 320 * 319 == 102080
    assert a2a["ranks"] == config["ranks"]
    assert a2a["stride"] == config["platform"]["hosts"] // config["ranks"]
    assert a2a["block_bytes"] == config["flow_bytes"] \
        == config["alltoall"]["block_bytes"]
    assert int(pairs.max()) == 319 * 204 < 65536


@pytest.mark.parametrize("ranks, stride", [(1, 8), (16, 0), (17, 8),
                                           (16, 9)])
def test_ranks_that_do_not_fit_the_platform_are_refused(ranks, stride):
    with pytest.raises(ValueError, match="do not fit"):
        solve_alltoall.alltoall_pairs({"ranks": ranks, "stride": stride},
                                      128, 1)


def test_the_deployment_is_dfly65k_randoms_but_for_its_traffic():
    ours = mf.Cell(mf.load_manifest(), "dfly65k-alltoall.solve")
    theirs = mf.Cell(mf.load_manifest(), "dfly65k-random.solve")
    for key in ("platform", "network_model", "precision", "guarantees",
                "engine_flags", "reference", "flow_bytes"):
        assert ours.config[key] == theirs.config[key], key
    assert ours.traffic["limits"] == theirs.traffic["limits"]
    assert ours.config["reduced"] == ["alltoall_advances"]
    assert len(ours.config["assumed"]) == 4


def test_a_short_window_still_holds_two_solves(monkeypatch):
    tiny.patch(monkeypatch)
    result = tiny.execute(CELL, seconds=0.0)
    assert result["correct"] is True and result["attempted"] == 2


# -- correct has been shown to fail -------------------------------------------

@pytest.mark.parametrize("seed", [3, 2**31 + 4, 77])
def test_control_in_bfloat16_is_not_correct(seed, monkeypatch):
    tiny.patch(monkeypatch)
    real = solve_alltoall.check
    monkeypatch.setattr(solve_alltoall, "check",
                        lambda run, state, rec: real(run, state, rec,
                                                     precision="bf16"))
    result = tiny.execute(CELL, seed=seed)
    assert result["correct"] is False and over(result) == {"rate_gap"}


@pytest.mark.parametrize("rank", [0, 9])
def test_a_rank_left_out_is_not_correct(rank, monkeypatch):
    """The flows one rank sends never enter the system: every flow
    that shared a link with them runs faster than the reference's."""
    tiny.patch(monkeypatch)
    from simgrid_tpu.ops import lmm_jax
    seen = {}
    real_setup = solve_alltoall.setup

    def setup(run):
        seen["state"] = None              # warm-up solves the whole system
        seen["state"] = real_setup(run)
        return seen["state"]
    real_solve = lmm_jax.solve_arrays

    def without_the_rank(arrays, eps, *a, **k):
        state = seen["state"]
        if state is None:
            return real_solve(arrays, eps, *a, **k)
        sends = state["pairs"][state["slot_flow"], 0] == rank * 8
        assert sends.sum() == 15
        pen = np.array(arrays.v_penalty)
        pen[:arrays.n_var][sends] = 0.0
        return real_solve(arrays._replace(v_penalty=pen), eps, *a, **k)
    monkeypatch.setattr(solve_alltoall, "setup", setup)
    monkeypatch.setattr(lmm_jax, "solve_arrays", without_the_rank)
    result = tiny.execute(CELL)
    assert result["correct"] is False and over(result) == {"rate_gap"}


def test_a_solve_that_differs_is_not_correct(monkeypatch):
    tiny.patch(monkeypatch)
    from simgrid_tpu.ops import lmm_jax
    real_solve, calls = lmm_jax.solve_arrays, []

    def unsteady(arrays, eps, *a, **k):
        values, rem, use, rounds = real_solve(arrays, eps, *a, **k)
        calls.append(1)
        if len(calls) == 3:               # warm-up is the first
            values = np.array(values)
            values[5] *= 1 + 1e-9
        return values, rem, use, rounds
    monkeypatch.setattr(lmm_jax, "solve_arrays", unsteady)
    result = tiny.execute(CELL)
    assert result["correct"] is False
    assert over(result) == {"solves_differing"}


# -- the readers, on a hand-made run -------------------------------------------

def reader(name):
    return mf.load_module("metrics", name).read


def handmade(counters, solves=2, shape=(8724, 102080, 1275102)):
    return types.SimpleNamespace(
        counters=counters, record={"solves": solves}, shape=shape,
        spans=types.SimpleNamespace(window_from=0.0))


def test_rounds_per_solve_reads_the_counter_over_the_solves():
    read = reader("solve.rounds_per_solve")
    assert read(handmade({"fixpoint_rounds": 566})) == 283.0
    assert read(handmade({})) is None
    assert read(handmade({"fixpoint_rounds": 566}, solves=0)) is None


def test_live_elem_pct_is_a_share_of_the_unpadded_elements():
    read = reader("solve.live_elem_pct")
    run = handmade({"fixpoint_rounds": 4,
                    "fixpoint_live_elem_rounds": 4 * 1275102 // 3})
    assert read(run) == pytest.approx(100 / 3, rel=1e-6)
    # a program without the counter: left out, never 0
    assert read(handmade({"fixpoint_rounds": 4})) is None
    run.shape = None
    assert read(run) is None


def test_chunks_per_solve_counts_the_windows_chunk_spans():
    import time
    from simgrid_tpu.ops import opstats
    read = reader("solve.chunks_per_solve")
    opstats.reset()
    with opstats.span("solve.chunk"):     # warm-up's: before the window
        pass
    run = handmade({}, solves=2)
    run.spans.window_from = time.perf_counter()
    assert read(run) is None
    for _ in range(18):
        with opstats.span("solve.chunk"), opstats.span("fetch"):
            pass
    assert read(run) == 9.0
    run.record = {}
    assert read(run) is None


def test_the_tiny_solve_reports_the_three(monkeypatch):
    """Through the harness: 14 rounds in one chunk, two fifths live."""
    tiny.patch(monkeypatch)
    from lib import harness
    seen = {}
    real = harness.read_metrics
    monkeypatch.setattr(harness, "read_metrics", lambda run, e2e: (
        seen.setdefault("run", run), real(run, e2e))[1])
    tiny.execute(CELL)
    run = seen["run"]
    assert run.shape == (114, 240, 2418)
    assert reader("solve.rounds_per_solve")(run) == 14.0
    assert reader("solve.chunks_per_solve")(run) == 1.0
    assert 30.0 < reader("solve.live_elem_pct")(run) < 50.0
    assert reader("solve.bound_rounds")(run) == 0.0


def test_a_traced_run_reads_the_solves_passes_by_the_programs_name(
        monkeypatch):
    """The pass readers go by the compiled program's name and the
    record's ``solves``, not by the driver's file name: this cell's
    ``solve_alltoall`` reads what ``solve`` reads.  (The recorded trace
    is a drain's, so the chunk program's scopes are made up.)"""
    from lib import harness, scopes
    tiny.patch(monkeypatch)
    tiny.traced(monkeypatch)
    chunk = scopes.SOLVE_CHUNK[0] + "(3)"
    made_up = scopes.DeviceScopes({
        (chunk, "sg.lmm.init", "%a"): 0.5, (chunk, "unscoped", "%w"): 0.25,
        (chunk, "sg.lmm.level", "%b"): 2.0, (chunk, "sg.lmm.prune", "%c"): 1.0,
        (chunk, "sg.lmm.partition", "%d"): 0.125})
    real = harness.reduce_trace
    monkeypatch.setattr(harness, "reduce_trace", lambda run: (
        real(run), setattr(run, "scopes", made_up))[0])
    result = tiny.execute(CELL, trace=True)
    solves = result["attempted"]
    assert solves >= 2
    got = {name: m["value"] for name, m in result["metrics"].items()}
    assert got["solve.init_ms"] == pytest.approx(750.0 / solves)
    assert got["solve.rounds_ms"] == pytest.approx(3000.0 / solves)
    assert got["solve.partition_ms"] == pytest.approx(125.0 / solves)
    assert result["breakdown"]["device_ops"][0] == ["sg.lmm.level %b", 2.0]
