"""``drain.ring_narrow_pct``, the share of a window's advances whose ring
ids were written as wide as their entries: on hand-made runs, through
the harness on the tape cells' tiny twins (with rungs narrow enough for
their few flows: at the real rungs a tiny tape keeps none and reads 0),
and in the manifest, for the four tape cells."""

import types

import pytest

import tiny
from lib import manifest as mf


def read(run):
    return mf.load_module("metrics", "drain.ring_narrow_pct").read(run)


def handmade(counters, advances=64):
    return types.SimpleNamespace(counters=counters,
                                 record={"advances": advances})


@pytest.fixture
def counted():
    """The program has counted (or not) since the last reset."""
    from simgrid_tpu.ops import opstats
    opstats.reset()
    yield lambda n: opstats.bump("collective_ring_narrow", n)
    opstats.reset()


def test_it_is_a_share_of_the_advances_committed(counted):
    # a program without the counter: left out, never 0
    assert read(handmade({"collective_ring_narrow": 64})) is None
    counted(0)
    assert read(handmade({"collective_ring_narrow": 64})) == 100.0
    assert read(handmade({"collective_ring_narrow": 48})) == 75.0
    # the counter is there and did not move: every advance was a burst
    assert read(handmade({})) == 0.0
    assert read(handmade({"collective_ring_narrow": 1}, advances=0)) is None
    assert read(types.SimpleNamespace(counters={}, record={})) is None


def test_the_manifest_lists_it_for_the_four_tape_cells():
    by = {m["name"]: m for m in mf.load_manifest()["per_layer"]}
    assert by["drain.ring_narrow_pct"] == {
        "name": "drain.ring_narrow_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "advance retire ring",
        "moves": "events_per_s",
        "workloads": ["dfly65k-pairwise.drain", "dfly65k-allreduce.drain",
                      "dfly65k-allreduce-lr.drain",
                      "ft8k-allreduce-2lvl.drain"]}


def seen_run(monkeypatch, cell):
    from lib import harness
    tiny.patch(monkeypatch)
    seen = {}
    real = harness.read_metrics
    monkeypatch.setattr(harness, "read_metrics", lambda run, e2e: (
        seen.setdefault("run", run), real(run, e2e))[1])
    result = tiny.execute(cell)
    return result, seen["run"]


@pytest.mark.parametrize("cell", ["tiny128-pairwise.drain",
                                  "tiny128-allreduce.drain"])
def test_the_tiny_tape_cells_read_it_through_the_harness(monkeypatch, cell):
    import jax
    from simgrid_tpu.ops import lmm_drain
    # rungs a tiny tape keeps (8 x 8 flows at most): statics of the
    # program, so nothing compiled before them is reused
    monkeypatch.setattr(lmm_drain, "_RING_RUNGS", (2, 8))
    jax.clear_caches()
    try:
        result, run = seen_run(monkeypatch, cell)
    finally:
        jax.clear_caches()
    assert result["correct"] is True
    narrow = run.counters["collective_ring_narrow"]
    assert 0 < narrow <= run.record["advances"]
    assert read(run) == 100.0 * narrow / run.record["advances"]


def test_a_cell_without_a_tape_has_nothing_to_read(monkeypatch):
    from simgrid_tpu.ops import opstats
    opstats.reset()
    _result, run = seen_run(monkeypatch, "tiny128-random.drain")
    assert read(run) is None
