"""``coll.src_walk_pct`` (ISSUE 36), the share of a window's advances
whose DAG walk started from their own completions' successor edges: on
hand-made runs, through the harness on the two tape cells' tiny twins
(whose every advance owns far fewer edges than the walk is wide), and
in the manifest, for the two tape cells."""

import types

import pytest

import tiny
from lib import manifest as mf


def read(run):
    return mf.load_module("metrics", "coll.src_walk_pct").read(run)


def handmade(counters, advances=64):
    return types.SimpleNamespace(counters=counters,
                                 record={"advances": advances})


@pytest.fixture
def counted():
    """The program has counted (or not) since the last reset."""
    from simgrid_tpu.ops import opstats
    opstats.reset()
    yield lambda n: opstats.bump("collective_src_walks", n)
    opstats.reset()


def test_it_is_a_share_of_the_advances_committed(counted):
    # a program without the counter: left out, never 0
    assert read(handmade({"collective_src_walks": 64})) is None
    counted(0)
    assert read(handmade({"collective_src_walks": 64})) == 100.0
    assert read(handmade({"collective_src_walks": 60})) == 93.75
    # the counter is there and did not move: every advance had a burst
    assert read(handmade({})) == 0.0
    assert read(handmade({"collective_src_walks": 1}, advances=0)) is None
    assert read(types.SimpleNamespace(counters={}, record={})) is None


def test_the_manifest_lists_it_for_the_two_tape_cells():
    by = {m["name"]: m for m in mf.load_manifest()["per_layer"]}
    assert by["coll.src_walk_pct"] == {
        "name": "coll.src_walk_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "advance retire ring",
        "moves": "events_per_s",
        "workloads": ["dfly65k-pairwise.drain", "dfly65k-allreduce.drain"]}


@pytest.mark.parametrize("cell", ["tiny128-pairwise.drain",
                                  "tiny128-allreduce.drain"])
def test_the_tiny_tape_cells_read_it_through_the_harness(monkeypatch, cell):
    from lib import harness
    tiny.patch(monkeypatch)
    seen = {}
    real = harness.read_metrics
    monkeypatch.setattr(harness, "read_metrics", lambda run, e2e: (
        seen.setdefault("run", run), real(run, e2e))[1])
    result = tiny.execute(cell)
    assert result["correct"] is True
    run = seen["run"]
    assert run.counters["collective_src_walks"] == run.record["advances"]
    assert read(run) == 100.0


def test_a_cell_without_a_tape_has_nothing_to_read(monkeypatch):
    from lib import harness
    from simgrid_tpu.ops import opstats
    tiny.patch(monkeypatch)
    opstats.reset()
    seen = {}
    real = harness.read_metrics
    monkeypatch.setattr(harness, "read_metrics", lambda run, e2e: (
        seen.setdefault("run", run), real(run, e2e))[1])
    tiny.execute("tiny128-random.drain")
    assert read(seen["run"]) is None
