"""``drain.var_entry_pct`` (ISSUE 34), the share of a window's advances
whose solve entered from the variable side: on hand-made runs, in the
manifest (looked up by name: both tape cells), and through the harness
on their tiny twins; the pairwise one's,
whose 2,418 elements lie under the ladder's floor (one rung, so the
index is ignored and the reading is 0) until the floor is brought down
under them."""

import types

import pytest

import tiny
from lib import manifest as mf

CELL = "tiny128-pairwise.drain"


def read(run):
    return mf.load_module("metrics", "drain.var_entry_pct").read(run)


def handmade(counters, advances=64):
    return types.SimpleNamespace(counters=counters,
                                 record={"advances": advances})


@pytest.fixture
def counted():
    """The program has counted (or not) since the last reset."""
    from simgrid_tpu.ops import opstats
    opstats.reset()
    yield lambda n: opstats.bump("fixpoint_var_entries", n)
    opstats.reset()


def test_it_is_a_share_of_the_advances_committed(counted):
    # a program without the counter: left out, never 0
    assert read(handmade({"fixpoint_var_entries": 64})) is None
    counted(0)
    assert read(handmade({"fixpoint_var_entries": 64})) == 100.0
    assert read(handmade({"fixpoint_var_entries": 16})) == 25.0
    # the counter is there and did not move: every advance fell back
    assert read(handmade({})) == 0.0
    assert read(handmade({"fixpoint_var_entries": 1}, advances=0)) is None
    assert read(types.SimpleNamespace(counters={}, record={})) is None


def test_the_manifest_lists_it_for_the_two_tape_cells():
    by = {m["name"]: m for m in mf.load_manifest()["per_layer"]}
    assert by["drain.var_entry_pct"] == {
        "name": "drain.var_entry_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "fixpoint solve",
        "moves": "events_per_s",
        "workloads": ["dfly65k-pairwise.drain", "dfly65k-allreduce.drain"]}


def test_a_burst_over_the_rung_is_the_share_it_leaves(counted):
    """The allreduce cell's lap: 8 of 32 advances enter with a step's
    burst live, at the full width of the list; the complement is what
    a burst costs."""
    counted(0)
    assert read(handmade({"fixpoint_var_entries": 24}, advances=32)) == 75.0
    assert read(handmade({"fixpoint_var_entries": 46})) \
        == pytest.approx(100 - 100 * 18 / 64)


@pytest.mark.parametrize("cell,floor,want", [
    (CELL, None, 0.0), (CELL, 256, 100.0),
    ("tiny128-allreduce.drain", None, 0.0)])
def test_the_tiny_cell_reads_it_through_the_harness(monkeypatch, cell,
                                                    floor, want):
    """16 ranks: at most 16 flows and ~190 elements live, so with a
    304-element bottom rung every advance enters from its flows.  The
    allreduce's 7,392 elements lie under the ladder's floor too."""
    import jax
    from lib import harness
    from simgrid_tpu.ops import lmm_jax
    tiny.patch(monkeypatch)
    if floor:
        monkeypatch.setattr(lmm_jax, "_LADDER_MIN_ELEMS", floor)
    jax.clear_caches()
    seen = {}
    real = harness.read_metrics
    monkeypatch.setattr(harness, "read_metrics", lambda run, e2e: (
        seen.setdefault("run", run), real(run, e2e))[1])
    try:
        result = tiny.execute(cell)
    finally:
        jax.clear_caches()
    assert result["correct"] is True
    assert read(seen["run"]) == want
