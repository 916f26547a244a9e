"""The reductions that read the program's own names: the wire decoder of
``lib/xmeta.py`` against ``ProfileData`` on the recorded traces, device
self time by ``sg.*`` scope and idle time by ``sg:`` span
(``lib/scopes.py``), the readers that report both, and the readers on a
run with nothing to read.

Two recorded traces, both one window of ``tiny128-random.drain`` on the
v5e: ``tiny_drain`` from before the program named anything (PR 26), and
``tiny_drain_scoped``, recorded with ``benchmarks/tools/passes.py
--keep`` after ISSUE 27 put ``jax.named_scope`` on the passes and
``opstats.span`` on the host steps.
"""

import struct
import types

import pytest

import tiny
from lib import manifest as mf, scopes, trace, xmeta

SUPERSTEP = scopes.SUPERSTEP[0]
LMM = ["sg.lmm." + p for p in ("init", "neighmin", "level", "update",
                               "prune")]
DRAIN = ["sg.drain." + p for p in ("solve", "advance", "ring", "pack")]
#: what XLA inserts on its own, with no op-name path to inherit
XLA_MADE = {"copy-start", "copy-done", "data formatting", "broadcast",
            "while", "custom-call"}
#: device time by pass: six columns of an advance, three of a solve
DRAIN_PASSES = ["drain.solve_init_ms", "drain.rounds_ms",
                "drain.partition_ms", "drain.coll_ms", "drain.ring_ms",
                "drain.retire_ms"]
SOLVE_PASSES = ["solve.init_ms", "solve.rounds_ms", "solve.partition_ms"]
NEW_READERS = [m["name"] for m in mf.load_manifest()["per_layer"]
               if m["name"].split(".")[0] == "setup"
               or m["name"] in ("drain.issue_ms", "drain.demux_ms",
                                "drain.idle_unnamed_pct",
                                "solve.host_block_pct",
                                "drain.compiles_in_window",
                                "solve.compiles_in_window")]


@pytest.fixture(scope="module", params=["tiny_drain", "tiny_drain_scoped"])
def either(request, tmp_path_factory):
    path = tiny.recorded(request.param,
                         tmp_path_factory.mktemp(request.param))
    return xmeta.read(path), trace.read_xplane(path)


@pytest.fixture(scope="module", params=["tiny_drain", "tiny_drain_scoped"])
def either_summary(request, tmp_path_factory):
    path = tiny.recorded(request.param,
                         tmp_path_factory.mktemp(request.param))
    return xmeta.read(path), trace.TraceSummary(trace.read_xplane(path))


@pytest.fixture(scope="module")
def plain(tmp_path_factory):
    path = tiny.recorded("tiny_drain", tmp_path_factory.mktemp("plain"))
    return xmeta.read(path), trace.TraceSummary(trace.read_xplane(path))


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    path = tiny.recorded("tiny_drain_scoped",
                         tmp_path_factory.mktemp("scoped"))
    return xmeta.read(path), trace.TraceSummary(trace.read_xplane(path))


# -- the wire decoder ----------------------------------------------------

def test_decoder_reads_the_events_profiledata_reads(either):
    meta, planes = either
    assert sum(len(evs) for lines in planes.values()
               for evs in lines.values()) > 5000
    for plane, lines in planes.items():
        assert set(lines) == set(meta[plane].line_names)
        for line, events in lines.items():
            assert events == [(meta[plane].names[op.metadata_id],
                               op.start_ns, op.end_ns)
                              for op in meta[plane].ops(line)], (plane, line)


def test_decoder_recovers_the_op_name_path_of_every_lowered_op(either):
    meta, _planes = either
    device = meta["/device:TPU:0"]
    ids = {op.metadata_id for op in device.ops(trace.OPS_LINE)}
    assert len(ids) > 100
    for i in ids:
        path = device.stat_of(i, "tf_op")
        category = device.stat_of(i, "hlo_category")
        assert isinstance(category, str) and category
        assert isinstance(device.stat_of(i, "bytes_accessed"), int)
        if path is None:
            assert category in XLA_MADE, device.names[i]
        else:                 # a jitted op's path, or an argument's name
            assert path.endswith(":")
            assert path.startswith("jit(") or "/" not in path, path
    assert sum(device.stat_of(i, "tf_op") is not None for i in ids) \
        > 0.8 * len(ids)


def test_wire_primitives():
    buf = memoryview(bytes([0x08, 0xAC, 0x02]) + bytes([0x12, 0x02])
                     + b"hi" + bytes([0x19]) + struct.pack("<d", 1.5))
    assert [(n, w, bytes(v) if w == xmeta.BYTES else v)
            for n, w, v in xmeta.fields(buf)] == [
        (1, xmeta.VARINT, 300), (2, xmeta.BYTES, b"hi"),
        (3, xmeta.FIXED64, struct.pack("<d", 1.5))]
    assert xmeta.signed((1 << 64) - 5) == -5 and xmeta.signed(7) == 7
    with pytest.raises(ValueError):
        list(xmeta.fields(memoryview(bytes([0x0B]))))   # a group: wire 3
    # an event in one loop: id 300, a stat skipped, offset 5, duration
    # 2^21, occurrences (field 5) ignored; the last value of a repeat
    ev = bytes([0x08, 0xAC, 0x02, 0x22, 0x02, 0x08, 0x01, 0x10, 0x05,
                0x18, 0x80, 0x80, 0x80, 0x01, 0x28, 0x07, 0x10, 0x06])
    assert xmeta.event(memoryview(ev)) == (300, 6, 1 << 21)
    assert xmeta.event(memoryview(b"")) == (0, 0, 0)
    # a field numbered over 15 (a two-byte key): the general decoder
    assert xmeta.event(memoryview(ev[:3] + bytes([0x80, 0x01, 0x09])
                                  + ev[7:9])) == (300, 5, 0)
    with pytest.raises(ValueError):
        xmeta.event(memoryview(bytes([0x0B])))
    # a stat by value and by reference into the stat names
    names = {1: "tf_op", 2: "jit(f)/sg.lmm.init/mul:"}
    assert xmeta.stat(memoryview(bytes([0x08, 1, 0x38, 2])), names) == (
        "tf_op", "jit(f)/sg.lmm.init/mul:")
    assert xmeta.stat(memoryview(bytes([0x08, 1, 0x2A, 1]) + b"x"),
                      names) == ("tf_op", "x")


# -- device time by scope ------------------------------------------------

@pytest.mark.parametrize("path,scope", [
    ("jit(f)/while/body/sg.drain.solve/while/body/sg.lmm.update/add:",
     "sg.lmm.update"),
    ("jit(f)/while/body/sg.drain.ring/scatter:", "sg.drain.ring"),
    ("jit(f)/while/cond/lt:", scopes.UNSCOPED),
    ("jit(f)/msg.lmm.x/add:", scopes.UNSCOPED),
    ("", scopes.UNSCOPED), (None, scopes.UNSCOPED)])
def test_innermost_scope(path, scope):
    assert scopes.innermost_scope(path) == scope


def test_self_time_by_op_is_what_trace_self_times_reads(either_summary):
    """The decoder's events under ``trace.self_times``' own nesting give,
    per op, what ProfileData's events give inside the window."""
    meta, summary = either_summary
    by_name = {}
    for (_program, _scope, name), s in scopes.device_scopes(
            meta, summary).by_op.items():
        by_name[name] = by_name.get(name, 0.0) + s
    inside = [(n, max(a, summary.lo), min(b, summary.hi))
              for n, a, b in summary.planes[summary.devices[0]][
                  trace.OPS_LINE] if b > summary.lo and a < summary.hi]
    want = trace.self_times(inside)
    assert set(by_name) == set(want)
    for name, ns in want.items():
        assert by_name[name] == pytest.approx(ns / 1e9, rel=1e-9, abs=1e-12)


def test_a_trace_without_scopes_is_all_unscoped(plain):
    meta, summary = plain
    got = scopes.device_scopes(meta, summary)
    assert {scope for _program, scope in got.by} == {scopes.UNSCOPED}
    assert sum(got.scopes(SUPERSTEP).values()) == pytest.approx(
        summary.busy_s, rel=1e-9)


def test_every_scope_has_device_time_and_they_add_up_to_busy(scoped):
    meta, summary = scoped
    got = scopes.device_scopes(meta, summary)
    by = got.scopes(SUPERSTEP)
    assert set(LMM + DRAIN) <= set(by), sorted(by)
    assert all(by[s] > 0 for s in LMM + DRAIN)
    # the superstep's scopes and its unscoped rest are the whole of the
    # superstep's busy time (ops of one core never overlap but by nesting)
    module_s, runs = summary.module_seconds(SUPERSTEP)
    assert runs >= 1
    assert sum(by.values()) <= module_s
    everything = sum(got.by.values())
    assert everything == pytest.approx(summary.busy_s, rel=1e-9)
    # the round's passes are most of a drain's device time
    passes = sum(by[s] for s in LMM if s != "sg.lmm.init")
    assert passes > 0.5 * sum(by.values())
    program, scope, op, seconds = got.top_ops(3)[0]
    assert program == SUPERSTEP and scope in LMM and op.startswith("%")
    assert seconds == max(got.by_op.values())


# -- idle time by host span ----------------------------------------------

def test_innermost_segments():
    spans = [("sg:a", 0, 100), ("sg:b", 10, 40), ("sg:c", 20, 30),
             ("sg:d", 120, 130), ("sg:e", 125, 160)]
    assert scopes.innermost_segments(spans) == [
        (0, 10, "sg:a"), (10, 20, "sg:b"), (20, 30, "sg:c"),
        (30, 40, "sg:b"), (40, 100, "sg:a"), (120, 125, "sg:d"),
        (125, 130, "sg:e")]      # e outlasts d: cut at d's end


def summary_of(ops, notes, window=(0, 1000)):
    return trace.TraceSummary({
        "/device:TPU:0": {trace.OPS_LINE: ops, trace.MODULES_LINE: []},
        "/host:CPU": {"python": notes + [
            (trace.WINDOW, window[0], window[1])]}})


def test_idle_goes_to_the_innermost_span_open_at_the_time():
    s = summary_of(
        ops=[("%a", 100, 300), ("%b", 500, 800)],
        notes=[("sg:drain.collect", 250, 620), ("sg:fetch", 260, 520),
               ("sg:drain.demux", 530, 600), ("bench:lap", 0, 1000)])
    assert scopes.idle_by_span(s) == {
        "sg:fetch": 200, scopes.UNNAMED: 100 + 200}
    # the benchmark's spans under the same rule: the lap is what was
    # open where the program had nothing
    assert scopes.top_gaps(s) == [["lap", 300e-9], ["sg:fetch", 200e-9]]
    s = summary_of(ops=[("%a", 100, 900)], notes=[])
    assert scopes.idle_by_span(s) == {scopes.UNNAMED: 200}
    assert scopes.top_gaps(s) == [[scopes.UNNAMED, 200e-9]]


def test_a_gap_over_three_spans_is_split_three_ways():
    """One idle stretch, 100..900, under ``bench:lap.events``, then bare
    ``bench:measure``, then ``bench:lap.upload`` > ``sg:drain.init``:
    each gets the part it was innermost in, ``measure`` only its own."""
    s = summary_of(
        ops=[("%a", 0, 100), ("%b", 900, 1000)],
        notes=[("bench:measure", 0, 1000), ("bench:lap.events", 50, 300),
               ("bench:lap.upload", 450, 950), ("sg:drain.init", 500, 950)])
    assert scopes.top_gaps(s) == [
        ["sg:drain.init", 400e-9], ["lap.events", 200e-9],
        ["measure", 150e-9], ["lap.upload", 50e-9]]
    assert scopes.top_gaps(s, 2) == [["sg:drain.init", 400e-9],
                                     ["lap.events", 200e-9]]
    # the program's spans alone: what no sg: span covers, whoever's
    assert scopes.idle_by_span(s) == {"sg:drain.init": 400,
                                      scopes.UNNAMED: 400}


def test_recorded_idle_time_is_named_by_the_programs_spans(scoped, plain):
    _meta, summary = scoped
    by = scopes.idle_by_span(summary)
    idle_ns = round((summary.window_s - summary.busy_s) * 1e9)
    assert sum(by.values()) == pytest.approx(idle_ns, abs=2)
    assert {n[len(scopes.HOST_PREFIX):] for n, _a, _b
            in scopes.host_spans(summary)} >= {
        "drain.init", "drain.issue", "drain.collect", "fetch",
        "drain.demux"}
    assert by["sg:fetch"] > 0 and by[scopes.UNNAMED] < sum(by.values())
    # every gap of the breakdown, before the cut to ten: all of the
    # idle time, little of it under bare ``measure`` or nothing
    everything = dict(map(tuple, scopes.top_gaps(summary, None)))
    assert sum(everything.values()) == pytest.approx(idle_ns / 1e9,
                                                     abs=2e-9)
    assert everything["sg:drain.init"] == by["sg:drain.init"] / 1e9
    assert everything.get("measure", 0.0) \
        + everything.get(scopes.UNNAMED, 0.0) < 0.1 * idle_ns / 1e9
    _meta, old = plain
    assert set(scopes.idle_by_span(old)) == {scopes.UNNAMED}
    assert {name for name, _s in scopes.top_gaps(old)} <= {
        "lap", "run", scopes.UNNAMED}


# -- the readers ----------------------------------------------------------

def fake_run(window_from=float("inf"), trace_summary=None):
    return types.SimpleNamespace(
        trace=trace_summary, scopes=None, counters={},
        record={"wall_s": 1.0},
        spans=types.SimpleNamespace(window_from=window_from))


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_finds_nothing_in_a_program_without_the_facility(
        name, monkeypatch, plain):
    from simgrid_tpu.ops import opstats
    monkeypatch.delattr(opstats, "spans")
    monkeypatch.delattr(opstats, "span")
    monkeypatch.setattr(opstats, "_counters", {})
    run = fake_run(trace_summary=plain[1])    # a trace, but no facility
    assert mf.load_module("metrics", name).read(run) is None


def test_readers_cut_the_programs_spans_at_the_window(monkeypatch):
    from simgrid_tpu.ops import opstats
    rows = [opstats.Span("platform.load", 1.0, 3.0, None, None, 1),
            opstats.Span("xla.compile", 4.0, 4.5, None, "jit(f)", 2),
            opstats.Span("drain.issue", 5.0, 5.25, None, 0, 3),
            opstats.Span("drain.issue", 11.0, 11.001, None, 1, 4),
            opstats.Span("drain.demux", 12.0, 12.002, 5, 1, 6),
            opstats.Span("drain.issue", 13.0, 13.003, None, 2, 7)]
    monkeypatch.setattr(opstats, "spans", lambda: rows)
    monkeypatch.setattr(opstats, "_counters", {"post_ms": 2500.0})
    run = fake_run(window_from=10.0)
    run.counters = {"post_ms": 500.0, "host_block_ms": 250.0}

    def read(name):
        return mf.load_module("metrics", name).read(run)

    assert read("setup.parse_s") == 2.0
    assert read("setup.compile_s") == 0.5
    assert read("setup.lmm_flatten_s") is None          # no such span
    assert read("setup.post_s") == 2.0
    assert read("setup.post_us_per_flow") is None       # no flow counted
    opstats._counters["flows_posted"] = 1000
    assert read("setup.post_us_per_flow") == pytest.approx(2000.0)
    run.counters["flows_posted"] = 200                   # the window's own
    assert read("setup.post_us_per_flow") == pytest.approx(2500.0)
    assert read("drain.issue_ms") == pytest.approx(2.0)  # warm-up's left out
    assert read("drain.demux_ms") == pytest.approx(2.0)
    assert read("drain.compiles_in_window") == 0
    assert read("solve.host_block_pct") == 25.0
    rows.append(opstats.Span("xla.compile", 14.0, 15.0, 7, "jit(g)", 8))
    assert read("solve.compiles_in_window") == 1
    assert read("setup.compile_s") == 0.5


def test_idle_unnamed_reader(scoped, plain):
    read = mf.load_module("metrics", "drain.idle_unnamed_pct").read
    assert read(fake_run()) is None
    # idle under no sg: span, the benchmark's own or not, as before the
    # breakdown's gaps were named by the same function
    assert read(fake_run(trace_summary=scoped[1])) == pytest.approx(
        100 * 606750 / 11696359, rel=1e-12)
    # spans opened, none in the trace: a reading (the facility failed)
    assert read(fake_run(trace_summary=plain[1])) == 100.0


# -- device time by pass ---------------------------------------------------

def passes_run(meta, summary, **record):
    run = fake_run(trace_summary=summary)
    run.scopes = scopes.device_scopes(meta, summary)
    run.record = record
    return run


def read(name, run):
    return mf.load_module("metrics", name).read(run)


def test_the_six_columns_of_an_advance_add_up_to_the_module(scoped):
    meta, summary = scoped
    run = passes_run(meta, summary, advances=8)
    got = {name: read(name, run) for name in DRAIN_PASSES}
    module_s, _runs = summary.module_seconds(SUPERSTEP)
    assert sum(got.values()) == pytest.approx(1e3 * module_s / 8, rel=1e-3)
    by = run.scopes.scopes(SUPERSTEP)
    assert got["drain.rounds_ms"] == pytest.approx(
        1e3 / 8 * sum(by[scope] for scope in scopes.ROUND))
    assert got["drain.rounds_ms"] > 0.5 * sum(got.values())
    assert got["drain.solve_init_ms"] == pytest.approx(
        1e3 / 8 * (by["sg.lmm.init"] + by["sg.drain.solve"]))
    # recorded before the ladder and without a tape: those passes never
    # ran, which is a reading
    assert got["drain.partition_ms"] == got["drain.coll_ms"] == 0.0
    # the solve's program is not in this trace; no advance, no reading
    assert [read(n, passes_run(meta, summary, solves=3, advances=8))
            for n in SOLVE_PASSES] == [None] * 3
    assert [read(n, passes_run(meta, summary, advances=0))
            for n in DRAIN_PASSES] == [None] * 6


def test_the_three_columns_of_a_solve_on_made_up_scopes():
    chunk = "jit__solve_kernel_chunk(7)"
    run = fake_run()
    run.record = {"solves": 4}
    run.scopes = scopes.DeviceScopes({
        (chunk, "sg.lmm.init", "%a"): 0.2, (chunk, "unscoped", "%w"): 0.04,
        (chunk, "sg.lmm.neighmin", "%b"): 1.0,
        (chunk, "sg.lmm.update", "%c"): 0.6,
        (chunk, "sg.lmm.partition", "%d"): 0.1,
        ("jit_other(1)", "unscoped", "%e"): 9.0})
    assert [read(n, run) for n in SOLVE_PASSES] == [
        pytest.approx(60.0), pytest.approx(400.0), pytest.approx(25.0)]
    assert [read(n, run) for n in DRAIN_PASSES] == [None] * 6


@pytest.mark.parametrize("name", DRAIN_PASSES + SOLVE_PASSES)
def test_a_trace_with_no_sg_path_gives_the_pass_readers_nothing(name, plain):
    meta, summary = plain
    run = passes_run(meta, summary, advances=8, solves=8)
    assert read(name, run) is None           # unscoped time is no pass
    run.scopes = None                        # the raw trace not decoded
    assert read(name, run) is None
    assert read(name, fake_run()) is None    # not traced at all


def test_device_ops_carry_the_scope_where_the_trace_has_any(scoped, plain):
    meta, summary = scoped
    run = passes_run(meta, summary)
    ops = scopes.top_ops(run, 10)
    assert len(ops) == 10 and all(len(name) <= 80 for name, _s in ops)
    scope, op = ops[0][0].split(" ", 1)
    assert scope in LMM and op.startswith("%fusion")
    assert [s for _n, s in ops] == sorted((s for _n, s in ops),
                                          reverse=True)
    # no name of the program's in the trace: the names of today
    meta, old = plain
    run = passes_run(meta, old)
    assert scopes.top_ops(run, 10) == old.top_ops(10)
    run.scopes = None
    assert scopes.top_ops(run, 10) == old.top_ops(10)
