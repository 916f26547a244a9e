"""The attribution of set-up (``lib/setup_ledger.py``): a made-up span
list counted by hand, the six ``setup.*`` readers that read it on the
tiny twins (``--trace 1`` on the CPU through ``tiny.traced``), and what
they give a run that opened none of the new spans."""

import importlib.util
import json
import os
import types

import jax
import pytest

import tiny
from lib import harness, manifest as mf, setup_ledger
from lib.setup_ledger import BOOT, POST, UNNAMED
from simgrid_tpu.ops import opstats

NEW = ["setup.boot_s", "setup.trace_s", "setup.lower_s",
       "setup.engine_advance_s", "setup.fetch_s", "setup.unnamed_pct"]


@pytest.fixture(autouse=True)
def fresh_process_state():
    """As a run of the benchmark starts: no counter, no span, no
    compiled program."""
    opstats.reset()
    jax.clear_caches()
    yield
    opstats.reset()


# -- by hand ---------------------------------------------------------------

#: t0 = 100, the window opens at 120; the benchmark's first span at 102
MADE_UP = [
    ("fetch", 90.0, 95.0, 7),                    # another run's: before t0
    ("platform.load", 103.0, 105.0, None),
    ("engine.advance", 106.0, 110.0, 0),         # parent of the next
    ("lmm.flatten", 107.0, 108.0, 0),
    ("drain.issue", 111.0, 116.0, 0),            # a first call: trace,
    ("xla.trace", 112.0, 112.5, "inner"),        # an inner jit in it,
    ("xla.trace", 111.5, 113.5, "outer"),        # (closed, so listed, first)
    ("xla.lower", 113.5, 114.0, "jit(outer)"),   # lowering, a cache hit
    ("xla.compile", 114.0, 115.5, "cached:jit(outer)"),
    ("fetch", 118.0, 123.0, 1),                  # crosses the window's start
    ("solve.chunk", 121.0, 122.0, 2),            # the window's own
]


def test_a_made_up_set_up_is_attributed_by_hand_counted_seconds():
    got = setup_ledger.attribute(MADE_UP, 100.0, 120.0, first_other=102.0,
                                 post_s=1.5)
    assert got.rows == {
        BOOT: 2.0, "platform.load": 2.0, "engine.advance": 3.0,
        "lmm.flatten": 1.0, "drain.issue": 1.0, "xla.trace": 2.0,
        "xla.lower": 0.5, "xla.compile": 1.5, "fetch": 2.0, POST: 1.5,
        # 102-103, 105-106, 110-111 and 116-118, less the posting
        UNNAMED: 3.5}
    assert sum(got.rows.values()) == 20.0
    assert (got.t0, got.boot_end, got.cut) == (100.0, 102.0, 120.0)
    # self seconds, by program: the outer trace less the inner one
    assert got.by_id("xla.") == {
        ("xla.trace", "outer"): 1.5, ("xla.trace", "inner"): 0.5,
        ("xla.lower", "jit(outer)"): 0.5,
        ("xla.compile", "cached:jit(outer)"): 1.5}
    assert got.by_id("engine.") == {("engine.advance", 0): 3.0}
    # in time order, touching or apart, never overlapping
    assert all(a < b for a, b, _n, _i in got.segments)
    assert all(one[1] <= two[0] for one, two
               in zip(got.segments, got.segments[1:]))
    assert got.stretches() == [
        {"start_s": 16.0, "seconds": 2.0, "prev": "drain.issue",
         "next": "fetch"},
        {"start_s": 2.0, "seconds": 1.0, "prev": None,
         "next": "platform.load"},
        {"start_s": 5.0, "seconds": 1.0, "prev": "platform.load",
         "next": "engine.advance"},
        {"start_s": 10.0, "seconds": 1.0, "prev": "engine.advance",
         "next": "drain.issue"}]


@pytest.mark.parametrize("spans,first_other,rows", [
    ([], None, {BOOT: 20.0, POST: 0.0, UNNAMED: 0.0}),
    ([], 104.0, {BOOT: 4.0, POST: 0.0, UNNAMED: 16.0}),
    # the program's span comes first; a benchmark span after the cut or
    # before the process is no start of anything
    ([("platform.load", 101.0, 102.0, None)], 130.0,
     {BOOT: 1.0, "platform.load": 1.0, POST: 0.0, UNNAMED: 18.0}),
    ([("platform.load", 101.0, 102.0, None)], 50.0,
     {BOOT: 1.0, "platform.load": 1.0, POST: 0.0, UNNAMED: 18.0}),
    # a span open over the whole stretch leaves nothing unnamed
    ([("drain.collect", 99.0, 125.0, 3)], 110.0,
     {BOOT: 0.0, "drain.collect": 20.0, POST: 0.0, UNNAMED: 0.0}),
], ids=["nothing", "benchmark-only", "late-other", "early-other",
        "covering"])
def test_the_rows_that_are_not_spans(spans, first_other, rows):
    got = setup_ledger.attribute(spans, 100.0, 120.0, first_other)
    assert got.rows == rows and sum(got.rows.values()) == 20.0


# -- the tiny twins --------------------------------------------------------

def load_tool():
    spec = importlib.util.spec_from_file_location(
        "bench_tools_setup_ledger", os.path.join(
            mf.BENCH, "tools", "setup_ledger.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def traced_run(monkeypatch, cell):
    """(result line, the harness's Run) of one traced tiny run, the Run
    remembered as ``tools/setup_ledger.py`` remembers it."""
    seen = []
    tiny.patch(monkeypatch)
    tiny.traced(monkeypatch)
    monkeypatch.setattr(harness, "Run",
                        load_tool().remembering(harness.Run, seen))
    result = tiny.execute(cell, trace=True)
    assert result["correct"] is True, result["compared"]
    return result, seen[0]


def union_s(spans):
    total, at = 0.0, float("-inf")
    for s in sorted(spans, key=lambda s: s.start):
        total += max(0.0, s.end - max(at, s.start))
        at = max(at, s.end)
    return total


@pytest.mark.parametrize("cell", ["tiny128-random.drain",
                                  "tiny128-pairwise.drain"])
def test_the_twins_report_the_rows_and_the_rows_add_up(cell, monkeypatch):
    result, run = traced_run(monkeypatch, cell)
    listed = {m["name"] for m in mf.Cell(tiny.tiny_manifest(),
                                         cell).per_layer()}
    posts = cell == "tiny128-random.drain"
    assert listed & set(NEW) == set(NEW) - (
        set() if posts else {"setup.engine_advance_s"})
    got = {name: result["metrics"][name]["value"]
           for name in listed & set(NEW)}      # each one reported

    ledger = setup_ledger.of(run)
    rows = ledger.rows
    assert sum(rows.values()) == pytest.approx(run.setup_s, abs=1e-3)
    assert ledger.cut <= run.spans.window_from
    assert list(rows)[0] == BOOT and list(rows)[-2:] == [POST, UNNAMED]
    assert all(seconds >= 0 for seconds in rows.values()), rows
    assert got["setup.boot_s"] == rows[BOOT] > 0
    assert got["setup.fetch_s"] == rows["fetch"] > 0
    assert got["setup.unnamed_pct"] == pytest.approx(
        100 * rows[UNNAMED] / run.setup_s)
    assert 0 < got["setup.unnamed_pct"] < 100

    # a cold process traces and lowers its programs during warm-up, and
    # the readers take self seconds: the union of the spans, which the
    # plain sum (the counter) overstates
    before = [s for s in opstats.spans() if s.start < ledger.cut]
    traces = [s for s in before if s.name == "xla.trace"]
    assert any(s.id == "_superstep_program" for s in traces)
    assert got["setup.trace_s"] == pytest.approx(union_s(traces))
    assert got["setup.trace_s"] < sum(s.end - s.start for s in traces)
    assert 0 < got["setup.lower_s"] == pytest.approx(union_s(
        s for s in before if s.name == "xla.lower"))
    assert ("xla.lower", "jit(_superstep_program)") in ledger.by_id("xla.")
    # what the older readers sum is in the ledger under the same names
    assert rows["platform.load"] == pytest.approx(
        result["metrics"]["setup.parse_s"]["value"])

    advances = [s for s in before if s.name == "engine.advance"]
    if posts:
        # the latency phase: generic host advances, nothing nested in
        # them, and the posting outside every span
        assert len(advances) >= 2
        assert got["setup.engine_advance_s"] == rows["engine.advance"] \
            == pytest.approx(sum(s.end - s.start for s in advances))
        assert rows[POST] == result["metrics"]["setup.post_s"]["value"] > 0
    else:
        # not listed for a cell that posts no flow; its reader would
        # say so: no engine advanced, nothing to report
        assert advances == [] and "engine.advance" not in rows
        assert mf.load_module("metrics", "setup.engine_advance_s").read(
            run) is None
        assert rows[POST] == 0.0 and "coll.lower" in rows


def test_the_tool_prints_every_row_and_the_longest_unnamed_stretches(
        monkeypatch, capfd):
    _result, run = traced_run(monkeypatch, "tiny128-random.drain")
    capfd.readouterr()
    out = load_tool().report(run)
    json.dumps(out)
    assert out["setup_s"] == run.setup_s
    assert out["rows"] == setup_ledger.of(run).rows
    assert [name for name, _id, _s in out["xla"]][0].startswith("xla.")
    assert [s for _n, _i, s in out["xla"]] == sorted(
        (s for _n, _i, s in out["xla"]), reverse=True)
    assert any(id_ == "jit(_superstep_program)" for _n, id_, _s
               in out["xla"])
    stretches = out["unnamed_stretches"]
    assert 1 <= len(stretches) <= 10
    assert [s["seconds"] for s in stretches] == sorted(
        (s["seconds"] for s in stretches), reverse=True)
    # the posting is among them: after the platform is loaded, before
    # the first advance, inside the benchmark's ``flatten``
    assert [s["under"] for s in stretches
            if (s["prev"], s["next"]) == ("platform.load",
                                          "engine.advance")] == ["flatten"]
    assert {s["under"] for s in stretches} <= {"flatten", "warmup",
                                               "lap.upload", "lap.run",
                                               "lap.events", "-"}
    printed = capfd.readouterr().err
    for name in out["rows"]:
        assert f"  {name:16s}" in printed
    assert "under bench:flatten" in printed


def test_a_span_that_never_opened_is_left_out(monkeypatch):
    """``engine.advance`` opens only where an engine runs: unopened,
    its row is left out, as ``scopes.setup_span_s`` leaves out its own.
    ``xla.trace`` and ``xla.lower`` open only on a first call: a
    process whose jits were warm reads 0 s of them, and a program
    whose listener drops those events (the parent) is left out."""
    run = types.SimpleNamespace(
        t0=100.0, setup_s=20.0, counters={},
        spans=types.SimpleNamespace(records={"flatten": [(102.0, 110.0)]},
                                    window_from=120.0))
    rows = [opstats.Span("platform.load", 103.0, 105.0, None, None, 1),
            opstats.Span("fetch", 118.0, 123.0, None, 1, 2)]
    monkeypatch.setattr(opstats, "spans", lambda: rows)

    def read():
        return [mf.load_module("metrics", name).read(run) for name in NEW]

    assert read() == [2.0, 0.0, 0.0, None, 2.0, pytest.approx(70.0)]
    monkeypatch.delattr(opstats, "note_xla")
    assert read() == [2.0, None, None, None, 2.0, pytest.approx(70.0)]
