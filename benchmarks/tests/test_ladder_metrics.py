"""The three readers of ``fixpoint``'s ladder (ISSUE 30) on hand-made
runs, and on the tiny twins of their cells, whose lists lie under the
ladder's floor: one rung, the whole list every round."""

import types

import pytest

import tiny
from lib import manifest as mf

SHAPE = (141871, 100000, 1241658)


def reader(name):
    return mf.load_module("metrics", name).read


def handmade(counters, solves=2, shape=SHAPE):
    return types.SimpleNamespace(counters=counters,
                                 record={"solves": solves}, shape=shape)


@pytest.mark.parametrize("name", ["solve.worked_elem_pct",
                                  "drain.worked_elem_pct"])
def test_worked_elem_pct_is_a_share_of_rounds_times_unpadded_elements(name):
    read = reader(name)
    # the single loop over the pow2-padded list
    single = handmade({"fixpoint_rounds": 17,
                       "fixpoint_worked_elem_rounds": 17 * 2097152})
    assert read(single) == pytest.approx(100 * 2097152 / 1241658)
    # round 1 on the whole list, the sixteen others at 2^16
    walked = 2097152 + 16 * 65536
    laddered = handmade({"fixpoint_rounds": 17,
                         "fixpoint_worked_elem_rounds": walked})
    assert read(laddered) == pytest.approx(100 * walked / (17 * 1241658))
    assert read(laddered) < 20
    # a program without the counter: left out, never 0
    assert read(handmade({"fixpoint_rounds": 17})) is None
    laddered.shape = None
    assert read(laddered) is None


def test_partitions_per_solve_reads_zero_but_not_a_missing_counter(
        monkeypatch):
    from simgrid_tpu.ops import opstats
    read = reader("solve.partitions_per_solve")
    monkeypatch.setattr(opstats, "snapshot", lambda: {})
    assert read(handmade({})) is None
    monkeypatch.setattr(opstats, "snapshot",
                        lambda: {"fixpoint_partitions": 30})
    assert read(handmade({"fixpoint_partitions": 30})) == 15.0
    assert read(handmade({})) == 0.0            # counted, none ran
    assert read(handmade({"fixpoint_partitions": 30}, solves=0)) is None


def test_the_manifest_lists_them_for_their_cells():
    by = {m["name"]: m for m in mf.load_manifest()["per_layer"]}
    solves = ["dfly65k-random.solve", "dfly65k-alltoall.solve"]
    assert by["solve.worked_elem_pct"]["workloads"] == solves
    assert by["solve.partitions_per_solve"]["workloads"] == solves
    assert by["drain.worked_elem_pct"]["workloads"] == [
        "dfly65k-random.drain", "dfly65k-pairwise.drain",
        "dfly65k-allreduce.drain"]
    for name in ("solve.worked_elem_pct", "drain.worked_elem_pct"):
        assert by[name]["better"] == "lower" and by[name]["unit"] == "%"


def test_a_tapes_rounds_run_on_the_rung_their_advance_stopped_at():
    """The allreduce cell's sizes: 96 rounds on the bottom rung are a
    third of a per cent of rounds x the UNPADDED 9.2 M elements."""
    read = reader("drain.worked_elem_pct")
    shape = (143993, 1048576, 9234862)
    run = handmade({"fixpoint_rounds": 96,
                    "fixpoint_worked_elem_rounds": 96 * 36080}, shape=shape)
    assert read(run) == pytest.approx(100 * 36080 / 9234862)
    assert read(handmade({"fixpoint_rounds": 96}, shape=shape)) is None
    assert read(handmade({"fixpoint_worked_elem_rounds": 5},
                         shape=shape)) is None          # no round counted


@pytest.mark.parametrize("cell,name", [
    ("tiny128-random.solve", "solve.worked_elem_pct"),
    ("tiny128-alltoall.solve", "solve.worked_elem_pct"),
    ("tiny128-random.drain", "drain.worked_elem_pct"),
    ("tiny128-pairwise.drain", "drain.worked_elem_pct"),
    ("tiny128-allreduce.drain", "drain.worked_elem_pct")])
def test_under_the_floor_a_round_indexes_the_whole_list_it_is_given(
        cell, name, monkeypatch):
    """The superstep's list is the system's, padded to rows of 8.  On
    the CPU `solve_arrays` still repacks its list between chunks on the
    host (`lmm/compact`): its rounds index those shorter lists whole,
    never fewer elements than are live."""
    tiny.patch(monkeypatch)
    from lib import harness
    seen = []
    real = harness.measure
    monkeypatch.setattr(harness, "measure", lambda run, state: seen.append(
        run) or real(run, state))
    assert tiny.execute(cell)["correct"]
    run = seen[0]
    n_elem = run.shape[2]
    worked = reader(name)(run)
    if cell.endswith(".drain"):
        assert worked == pytest.approx(100.0 * (-(-n_elem // 8) * 8) / n_elem)
        return
    padded = 1 << (n_elem - 1).bit_length()
    assert reader("solve.live_elem_pct")(run) <= worked \
        <= 100.0 * padded / n_elem
    assert reader("solve.partitions_per_solve")(run) == 0.0
