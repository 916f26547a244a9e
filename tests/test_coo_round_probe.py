"""`tools/coo_round_probe.py` reads device times, so it measures on a
TPU or not at all; its plumbing is checked here, on the CPU, at 128
hosts, with nothing written anywhere."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(ROOT, "tools", "coo_round_probe.py")


def probe(tmp_path_factory, code):
    done = subprocess.run(
        [sys.executable, "-c", code, os.path.dirname(TOOL)],
        cwd=tmp_path_factory.mktemp("probe"),
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    return [json.loads(line) for line in done.stdout.splitlines()]


@pytest.fixture(scope="module")
def records(tmp_path_factory):
    """Every reading at 128 hosts, from a process of its own (the tool
    puts ``benchmarks/`` on ``sys.path``, as the benchmark's tests do),
    through ``readings``: the part of the tool under ``main``'s device
    check, writing nothing."""
    return probe(tmp_path_factory, (
        "import json, sys; sys.path.insert(0, sys.argv[1]); "
        "import coo_round_probe as p; "
        "p.readings(lambda **r: print(json.dumps(r)), None, "
        "'tiny128-random', reps=1)"))


@pytest.fixture(scope="module")
def ladder_records(tmp_path_factory):
    """The ladder's readings at 128 hosts, the floor brought down HERE
    so that these 5,811 elements have rungs to step down."""
    return probe(tmp_path_factory, (
        "import json, sys; sys.path.insert(0, sys.argv[1]); "
        "import coo_round_probe as p; "
        "from simgrid_tpu.ops import lmm_jax; "
        "lmm_jax._LADDER_MIN_ELEMS = 256; "
        "p.readings(lambda **r: print(json.dumps(r)), 'ladder', "
        "'tiny128-random', reps=1)"))


def test_without_a_tpu_it_measures_nothing(tmp_path):
    """No fallback: exit 2, nothing on stdout, no record appended."""
    out = os.path.join(ROOT, "chiprun_out", "coo_round_probe.jsonl")
    before = os.path.getsize(out) if os.path.exists(out) else None
    done = subprocess.run(
        [sys.executable, TOOL, "--only", "ops"], cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert done.returncode == 2
    assert done.stdout == ""
    assert "no TPU" in done.stderr
    assert (os.path.getsize(out) if os.path.exists(out) else None) == before


def test_the_round_readings_at_128_hosts(records):
    """Bounds that never bind skip the block every round; the binding
    variant takes it, solo and as a vmapped lane beside one that does
    not, and every lane has the reference's rates."""
    by = {r["cell"]: r for r in records if r["what"] == "round"}
    assert list(by) == ["solve-like", "solve-bind", "solve-vmap2",
                        "drain-like"]
    assert by["solve-like"]["bound_rounds"] == [0]
    assert by["solve-bind"]["bound_rounds"][0] > 0
    assert by["solve-bind"]["taken_round_ms"] > 0
    assert by["solve-vmap2"]["rounds"] == (by["solve-bind"]["rounds"]
                                           + by["solve-like"]["rounds"])
    assert by["solve-vmap2"]["bound_rounds"] == [
        by["solve-bind"]["bound_rounds"][0], 0]
    for cell in ("solve-like", "solve-bind", "solve-vmap2"):
        assert by[cell]["light_left"] == 0
        assert max(by[cell]["rate_gap"]) < 2e-3     # the solve cell's limit
    assert by["solve-vmap2"]["rates_sum"] == (
        by["solve-bind"]["rates_sum"] + by["solve-like"]["rates_sum"])


def test_every_op_kind_has_a_price_in_both_layouts(records):
    ops = [r for r in records if r["what"] == "op"]
    assert len({(r["layout"], r["op"]) for r in ops}) == len(ops) == 26
    assert all(r["ms_per_op"] > 0 for r in ops)
    # entry's 2-wide scatter-add beside the 1-wide and the round's 3-wide
    # one, in both layouts and into the alltoall's 16,384 rows
    wide = [(r["layout"], r["op"]) for r in ops if "scatter_add" in r["op"]
            and "i32" not in r["op"]]
    assert wide == [(lay, f"scatter_add{w}_f32_{side}")
                    for lay, sides in (("drain_2d", ["to_c"]),
                                       ("solve_pow2", ["to_c", "to_c16k"]))
                    for side in sides for w in ("", 2, 3)]
    same = [r for r in records if r["what"] == "column0"]
    assert [r["layout"] for r in same] == ["drain_2d", "solve_pow2"]
    assert all(r["equal"] is True for r in same)


@pytest.mark.parametrize("layout", ["solve_pow2", "drain_2d"])
def test_the_ladders_readings_rung_by_rung(ladder_records, records, layout):
    """Every rung has its round and its 3-wide scatter priced, and so
    has the size under the floor, half the last rung; every size but
    that one its partition down to the next, three ways that agree."""
    read = [r for r in ladder_records
            if r["what"] == "ladder" and r["layout"] == layout]
    sizes = [r["elems"] for r in read]
    assert len(read) == read[0]["rungs"] + 1 >= 5
    assert [r["rung"] for r in read] == [True] * (len(read) - 1) + [False]
    assert sizes == sorted(sizes, reverse=True)
    assert sizes[-1] <= 256 < sizes[-2] < 2 * sizes[-1] + 16
    assert [r["kept"] for r in read[:-1]] == sizes[1:]
    for r in read:
        # (a list cut this short may converge before its fifth round)
        assert r["scatter_add3_ms"] > 0 and "round_ms" in r
        assert r["rounds_run"][0] == 1 < r["rounds_run"][1] <= 5
    for r in read[:-1]:
        assert r["agree"] is True
        assert min(r["partition_ms"], r["sort_ms"], r["packed_ms"]) > 0
    assert "kept" not in read[-1]
    # under the floor as it stands: one rung, nothing to partition
    whole = [r for r in records
             if r["what"] == "ladder" and r["layout"] == layout]
    assert len(whole) == 1 and "kept" not in whole[0] and whole[0]["rung"]
