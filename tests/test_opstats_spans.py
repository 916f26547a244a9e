"""The program's one tracing facility (ops/opstats.py, ISSUE 27): host
spans on ``time.perf_counter``, the listener behind the ``xla.trace`` /
``xla.lower`` / ``xla.compile`` spans, the engine's ``engine.advance``,
the fetch accounting of ``solve_arrays`` and the ``jax.named_scope``
names of the device passes in the lowered programs."""

import importlib.util
import lzma
import os
import re
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from simgrid_tpu.analysis.prog import registry
from simgrid_tpu.ops import lmm_drain, lmm_jax, opstats

LMM_SCOPES = ("sg.lmm.init", "sg.lmm.neighmin", "sg.lmm.level",
              "sg.lmm.update", "sg.lmm.prune")
DRAIN_SCOPES = ("sg.drain.solve", "sg.drain.advance", "sg.drain.ring",
                "sg.drain.pack")


@pytest.fixture(autouse=True)
def fresh_opstats():
    opstats.reset()
    yield
    opstats.reset()


def small_system(n_c=12, n_v=48, seed=7):
    """(e_var, e_cnst, e_w, c_bound, sizes): every flow crosses three
    links of a ring, sizes tie-heavy so advances group."""
    rng = np.random.default_rng(seed)
    first = rng.integers(0, n_c, n_v)
    e_var = np.repeat(np.arange(n_v, dtype=np.int32), 3)
    e_cnst = ((first[:, None] + np.arange(3)) % n_c).astype(
        np.int32).reshape(-1)
    e_w = np.ones(len(e_var))
    c_bound = rng.choice([1.0, 2.0, 4.0], n_c)
    sizes = rng.choice([1.0, 2.0, 3.0], n_v)
    return e_var, e_cnst, e_w, c_bound, sizes


def coo_arrays(dtype=np.float64):
    e_var, e_cnst, e_w, c_bound, _sizes = small_system()
    n_c, n_v, n_e = len(c_bound), int(e_var.max()) + 1, len(e_var)
    E, C, V = (lmm_jax._bucket(n) for n in (n_e, n_c, n_v))
    a = lmm_jax.LmmArrays(
        e_var=np.zeros(E, np.int32), e_cnst=np.zeros(E, np.int32),
        e_w=np.zeros(E, dtype), c_bound=np.zeros(C, dtype),
        c_fatpipe=np.zeros(C, bool), v_penalty=np.zeros(V, dtype),
        v_bound=np.full(V, -1.0, dtype), n_elem=n_e, n_cnst=n_c,
        n_var=n_v)
    a.e_var[:n_e], a.e_cnst[:n_e], a.e_w[:n_e] = e_var, e_cnst, e_w
    a.c_bound[:n_c] = c_bound
    a.v_penalty[:n_v] = 1.0
    return a


# -- span(): nesting, ids, the bounded buffer ---------------------------

def test_nesting_gives_the_enclosing_span_as_parent():
    with opstats.span("drain.collect", id=4):
        with opstats.span("fetch"):
            pass
        with opstats.span("drain.demux"):
            pass
    with opstats.span("drain.issue"):
        pass
    collect, fetch, demux, issue = opstats.spans()
    assert [s.name for s in (collect, fetch, demux, issue)] == [
        "drain.collect", "fetch", "drain.demux", "drain.issue"]
    assert collect.parent is None and issue.parent is None
    assert fetch.parent == collect.seq and demux.parent == collect.seq
    # a span without an id inherits its parent's
    assert fetch.id == 4 and demux.id == 4 and issue.id is None
    assert collect.start <= fetch.start <= fetch.end <= demux.start
    assert demux.end <= collect.end <= issue.start


def test_parent_is_per_thread():
    seen = []

    def other():
        with opstats.span("solve.chunk"):
            pass
        seen.append(True)

    with opstats.span("drain.collect"):
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
    assert seen and not t.is_alive()
    chunk = [s for s in opstats.spans() if s.name == "solve.chunk"]
    assert len(chunk) == 1 and chunk[0].parent is None


def test_buffer_is_bounded_and_reset_clears_it():
    for _ in range(opstats.SPAN_BUFFER + 10):
        with opstats.span("fetch"):
            pass
    got = opstats.spans()
    assert len(got) == opstats.SPAN_BUFFER
    assert got[-1].seq - got[0].seq == opstats.SPAN_BUFFER - 1   # newest kept
    opstats.reset()
    assert opstats.spans() == []


def test_a_span_closes_when_its_body_raises():
    with pytest.raises(KeyError):
        with opstats.span("drain.issue"):
            raise KeyError("boom")
    with opstats.span("drain.issue"):
        pass
    first, second = opstats.spans()
    assert first.end >= first.start and second.parent is None


# -- the drain's spans ---------------------------------------------------

def test_drain_leaves_its_spans_in_order_and_one_id_per_dispatch():
    e_var, e_cnst, e_w, c_bound, sizes = small_system()
    sim = lmm_drain.DrainSim(e_var, e_cnst, e_w, c_bound, sizes,
                             eps=1e-9, dtype=np.float64, superstep=4,
                             repack_min=1 << 62)
    sim.run()
    assert sim.supersteps >= 2 and len(sim.events) == len(sizes)
    mine = [s for s in opstats.spans() if not s.name.startswith("xla.")]
    assert mine[0].name == "drain.init" and mine[0].parent is None
    by_seq = {s.seq: s for s in mine}
    per_dispatch = [[s.name for s in mine[1:] if s.id == d]
                    for d in range(sim.supersteps)]
    assert per_dispatch == [["drain.issue", "drain.collect", "fetch",
                             "drain.demux"]] * sim.supersteps
    for s in mine[1:]:
        if s.name in ("fetch", "drain.demux"):
            assert by_seq[s.parent].name == "drain.collect"
            assert by_seq[s.parent].id == s.id
        else:
            assert s.parent is None
    # the fetch span is what host_block_ms adds up
    fetched = sum(s.end - s.start for s in mine if s.name == "fetch")
    assert opstats.snapshot()["host_block_ms"] == pytest.approx(
        1e3 * fetched)


def test_drain_events_do_not_depend_on_the_spans():
    """Same events with the span buffer cleared mid-run (nothing in
    the drain reads the facility back)."""
    e_var, e_cnst, e_w, c_bound, sizes = small_system()
    runs = []
    for clear in (False, True):
        sim = lmm_drain.DrainSim(e_var, e_cnst, e_w, c_bound, sizes,
                                 eps=1e-9, dtype=np.float64, superstep=4,
                                 repack_min=1 << 62)
        sim.run(max_advances=4)
        if clear:
            opstats.reset()
        sim.run()
        runs.append(sim.events)
    assert runs[0] == runs[1]


# -- the compile listener ------------------------------------------------

def test_a_new_shape_counts_a_compile_and_a_repeat_does_not():
    @jax.jit
    def twice(x):
        return x * 2

    x = jnp.arange(5.0)
    twice(x).block_until_ready()
    after_first = opstats.snapshot()
    assert after_first["xla_compiles"] >= 1
    assert after_first["xla_compile_ms"] > 0
    named = [s for s in opstats.spans()
             if s.name == "xla.compile" and "twice" in str(s.id)]
    assert len(named) == 1 and named[0].end > named[0].start
    twice(x).block_until_ready()
    assert opstats.diff(after_first) == {}
    twice(jnp.arange(7.0)).block_until_ready()          # forced new shape
    assert opstats.diff(after_first)["xla_compiles"] >= 1


def test_a_compile_inside_a_span_names_it_as_parent():
    @jax.jit
    def thrice(x):
        return x * 3

    with opstats.span("drain.issue", id=0):
        thrice(jnp.arange(3.0)).block_until_ready()
    issue = opstats.spans()[0]
    inner = [s for s in opstats.spans() if "thrice" in str(s.id)]
    assert issue.name == "drain.issue"
    assert inner and all(s.parent == issue.seq for s in inner)
    assert not any(str(s.id).startswith("cached:") for s in inner)


def xla_spans(needle):
    return [s for s in opstats.spans()
            if s.name.startswith("xla.") and needle in str(s.id)]


def test_a_first_call_is_traced_lowered_and_compiled_in_that_order():
    @jax.jit
    def quadruple(x):
        return x * 4

    x = jnp.arange(5.0)
    quadruple(x).block_until_ready()
    trace, lower, compile_ = xla_spans("quadruple")
    assert [s.name for s in (trace, lower, compile_)] == [
        "xla.trace", "xla.lower", "xla.compile"]
    assert trace.id == "quadruple" and lower.id == compile_.id \
        == "jit(quadruple)"
    # each is (now - duration, now) on two clocks: room for their grain
    assert trace.start < trace.end <= lower.start + 1e-4
    assert lower.start < lower.end <= compile_.start + 1e-4
    assert compile_.start < compile_.end
    counted = opstats.snapshot()
    assert counted["xla_compile_ms"] >= 1e3 * (compile_.end
                                               - compile_.start) > 0
    quadruple(x).block_until_ready()                    # nothing new
    assert len(xla_spans("quadruple")) == 3
    assert opstats.diff(counted) == {}


def test_inner_traces_nest_in_the_outers_and_their_union_is_under_it():
    @jax.jit
    def inner_a(x):
        return jnp.sin(x) + 1

    @jax.jit
    def inner_b(x):
        return jnp.cos(x) * 2

    @jax.jit
    def outer_fn(x):
        return inner_a(x) + inner_b(inner_a(x * 3))

    x = jnp.arange(6.0)
    opstats.reset()                   # whatever making x traced
    outer_fn(x).block_until_ready()
    traces = [s for s in opstats.spans() if s.name == "xla.trace"]
    outer = [s for s in traces if s.id == "outer_fn"]
    inner = [s for s in traces if str(s.id).startswith("inner_")]
    assert len(outer) == 1 and {s.id for s in inner} == {"inner_a",
                                                         "inner_b"}
    outer = outer[0]
    for s in inner:                   # recorded when they ended: before
        assert s.seq < outer.seq      # the outer one, and inside it
        assert outer.start <= s.start + 1e-4 and s.end <= outer.end
    union, at = 0.0, outer.start
    for s in sorted(traces, key=lambda s: s.start):
        union += max(0.0, s.end - max(at, s.start))
        at = max(at, s.end)
    assert union <= (outer.end - outer.start) + 1e-4
    # the plain sum counts the nested stretches twice
    assert sum(s.end - s.start for s in traces) > union
    # only whole programs are lowered and compiled
    assert {s.id for s in opstats.spans() if s.name == "xla.lower"
            and ("inner_" in s.id or "outer_" in s.id)} == {"jit(outer_fn)"}


# -- the engine's advance -------------------------------------------------

PLATFORM = """<?xml version='1.0'?>
<platform version="4.1">
  <zone id="world" routing="Full">
    <cluster id="c" prefix="n-" radical="0-7" suffix="" speed="1Gf"
             bw="125MBps" lat="50us"/>
  </zone>
</platform>
"""


def test_every_engine_advance_is_a_span_with_its_ordinal(tmp_path):
    from simgrid_tpu import s4u
    path = tmp_path / "c8.xml"
    path.write_text(PLATFORM)
    s4u.Engine._reset()
    e = s4u.Engine(["advance", "--cfg=network/optim:Full",
                    "--cfg=lmm/backend:native"])
    try:
        e.load_platform(str(path))
        hosts = e.get_all_hosts()
        model = e.pimpl.network_model
        for k in range(1, 8):
            model.communicate(hosts[0], hosts[k], 1e6 * k, -1.0)
        opstats.reset()
        n = 0
        while e.pimpl.surf_solve(-1.0) >= 0:
            n += 1
        n += 1                                   # the one that ran dry
        assert n >= 8 and e.pimpl.advances == n
    finally:
        s4u.Engine._reset()
    advances = [s for s in opstats.spans() if s.name == "engine.advance"]
    assert [s.id for s in advances] == list(range(n))
    assert all(s.parent is None for s in advances)
    assert all(a.end <= b.start for a, b in zip(advances, advances[1:]))
    assert all(s.end > s.start for s in advances)
    assert opstats.snapshot()["native_advances"] >= 7


# -- solve_arrays: every chunk's fetch is a timed fetch ------------------

@pytest.mark.parametrize("chunk", [1, 64])
def test_solve_arrays_counts_one_fetch_per_chunk(chunk):
    arrays = coo_arrays()
    before = opstats.snapshot()
    values, _rem, _use, rounds = lmm_jax.solve_arrays(
        arrays, 1e-9, chunk=chunk)
    d = opstats.diff(before)
    assert d["fetches"] == d["dispatches"] >= 1
    if chunk == 1:
        assert d["dispatches"] == rounds > 1
    assert d["fetched_bytes"] > 0 and d["host_block_ms"] > 0
    chunks = [s for s in opstats.spans() if s.name == "solve.chunk"]
    fetches = [s for s in opstats.spans() if s.name == "fetch"]
    assert len(chunks) == len(fetches) == d["dispatches"]
    assert [f.parent for f in fetches] == [c.seq for c in chunks]
    assert np.all(np.asarray(values)[:arrays.n_var] > 0)


# -- the passes' names are in the lowered programs -----------------------

def lowered_text(jitted, args, statics):
    return jitted.lower(*args, **statics).as_text(debug_info=True)


@pytest.mark.parametrize("parallel_rounds,has_bounds",
                         [(True, False), (True, True), (False, False),
                          (False, True)])
def test_solve_chunk_program_holds_every_pass_name(parallel_rounds,
                                                   has_bounds):
    a = coo_arrays()
    text = lowered_text(
        lmm_jax._solve_kernel_chunk,
        (a.e_var, a.e_cnst, a.e_w, a.c_bound, a.c_fatpipe, a.v_penalty,
         a.v_bound, None),
        dict(eps=1e-9, n_c=len(a.c_bound), n_v=len(a.v_penalty),
             parallel_rounds=parallel_rounds, chunk=8,
             has_bounds=has_bounds, has_fatpipe=True))
    for scope in LMM_SCOPES:
        assert scope + "/" in text, scope
    assert "sg.drain." not in text


@pytest.mark.parametrize("name", ["drain/superstep", "drain/superstep_f32",
                                  "drain/superstep_tape",
                                  "drain/superstep_coll",
                                  "drain/superstep_coll_f32"])
def test_superstep_program_holds_every_pass_name(name):
    spec = {s.name: s for s in registry.iter_programs()}[name]
    args, statics = spec.make(1)
    text = lowered_text(spec.jitted, args, statics)
    for scope in DRAIN_SCOPES + LMM_SCOPES:
        assert scope + "/" in text, scope
    # the tape's activation scatter and DAG walk have a name of their
    # own, inside the ring's, and only where a tape is armed
    assert ("sg.drain.ring/sg.drain.coll/" in text) == statics["has_coll"]
    # the round's passes nest under the superstep's solve
    assert "sg.drain.solve/while/body/sg.lmm.update/" in text


def test_the_recorded_trace_holds_the_names_the_program_lowers(tmp_path):
    """No manifest metric reads the device scopes yet (PERF.md §7): the
    benchmark's recorded chip trace is what its readers are tested on, so
    a scope renamed, added or dropped here without a fresh recording
    fails HERE, not in silence on the chip."""
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    spec = importlib.util.spec_from_file_location(
        "bench_lib_xmeta", os.path.join(bench, "lib", "xmeta.py"))
    xmeta = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(xmeta)
    raw = tmp_path / "scoped.xplane.pb"
    with lzma.open(os.path.join(bench, "tests", "fixtures",
                                "tiny_drain_scoped.xplane.pb.xz")) as f:
        raw.write_bytes(f.read())
    device = xmeta.read(str(raw))["/device:TPU:0"]
    recorded = {part.rstrip(":") for stats in device.stats.values()
                for part in str(stats.get("tf_op", "")).split("/")
                if part.startswith("sg.")}
    spec = {s.name: s for s in registry.iter_programs()}["drain/superstep_f32"]
    args, statics = spec.make(1)
    lowered = set(re.findall(r"sg\.[a-z]+\.[a-z]+(?=/)",
                             lowered_text(spec.jitted, args, statics)))
    assert recorded == lowered == set(DRAIN_SCOPES + LMM_SCOPES)
