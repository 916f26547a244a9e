"""The collective tape's DAG walk from the completions' own successor
edges (ISSUE 36).

An advance of a tape finishes a handful of flows; the walk that
decrements their successors' predecessor counts used to read every edge
of the schedule.  With the DAG's source-major index
(``lmm_drain.succ_index``) an advance whose completions own at most
``_SRC_WALK_EDGES`` edges walks those alone (``_succ_walk``), and one
that owns more falls back to the edge-wide walk.  The counts are
integers and the adds commute, so:

* whatever the width, and whichever side an advance takes, the tape's
  state, events, activations and clock are the same to the bit, at any
  dispatch grouping, and ``HostMaestro``'s;
* the side is chosen by the advance's own completions: exactly the
  width still walks from the source, one more edge does not (by the
  counter ``collective_src_walks``);
* the index follows the edge arrays a sim is made from, so a DAG cut
  after the collective was lowered is the DAG that is walked."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from simgrid_tpu import s4u
from simgrid_tpu.collectives import (CollectiveSpec, HostMaestro,
                                     RoutedTopology)
from simgrid_tpu.ops import lmm_drain, opstats
from simgrid_tpu.ops.lmm_drain import DrainSim, _succ_walk, succ_index

XML = """<?xml version='1.0'?>
<platform version="4.1">
  <zone id="world" routing="Full">
    <cluster id="dfly" prefix="node-" radical="0-127" suffix=""
             speed="1Gf" bw="125MBps" lat="50us" topology="DRAGONFLY"
             topo_parameters="4,3;2,2;4,2;4"/>
  </zone>
</platform>
"""


@pytest.fixture(scope="module")
def tapes(tmp_path_factory):
    """The 16-rank pairwise alltoall and the 128-rank recursive-doubling
    allreduce, lowered onto a 128-host dragonfly's own routes."""
    path = tmp_path_factory.mktemp("src_walk") / "dfly128.xml"
    path.write_text(XML)
    s4u.Engine._reset()
    e = s4u.Engine(["src_walk", "--cfg=network/maxmin-selective-update:no",
                    "--cfg=network/optim:Full"])
    e.load_platform(str(path))
    hosts = e.get_all_hosts()
    try:
        yield {
            "pairwise16": CollectiveSpec(
                "alltoall", "pairwise", 16,
                RoutedTopology(e, [hosts[(r + 5) % 16 * 8]
                                   for r in range(16)]), 1e6).build(),
            "rdb128": CollectiveSpec(
                "allreduce", "rdb", 128, RoutedTopology(e, list(hosts)),
                8192.0).build()}
    finally:
        s4u.Engine._reset()


@pytest.fixture
def width(monkeypatch):
    """Set the walk's width: a static of the program, so every compiled
    one goes with it."""
    def to(w):
        monkeypatch.setattr(lmm_drain, "_SRC_WALK_EDGES", w)
        jax.clear_caches()
    yield to
    jax.clear_caches()


def needs(dc, sim_batches):
    """What each advance's completions ask of the width: their count or
    the successor edges they own, whichever is more."""
    s_ptr, _ = dc.succ_index()
    deg = np.diff(s_ptr)
    return np.array([max(len(ids), int(deg[ids].sum()))
                     for _, ids in sim_batches], int)


def state(sim):
    return [np.asarray(a).tolist() for a in
            (*sim._coll, sim._coll_clk, sim._pen, sim._rem)]


def drained(dc, dtype, superstep, stop=40):
    """``stop`` advances, the tape's state there, then the rest."""
    batches = []
    before = opstats.snapshot()
    sim = dc.make_sim(superstep=superstep, dtype=dtype)
    sim.on_batches = batches.extend
    sim.run(max_advances=stop)
    assert sim.advances == stop
    mid = state(sim)
    sim.run()
    took = opstats.diff(before)
    return sim, mid, batches, took.get("collective_src_walks", 0)


# ---------------------------------------------------------------------------
# the index
# ---------------------------------------------------------------------------

def test_the_index_lists_each_flows_successors_in_the_lists_order(tapes):
    for dc in tapes.values():
        s_ptr, s_dst = dc.succ_index()
        assert s_ptr.dtype == s_dst.dtype == np.int32
        assert len(s_ptr) == dc.n_v + 1 and len(s_dst) == len(dc.edge_src)
        assert s_ptr[0] == 0 and s_ptr[-1] == dc.n_edges
        for f in range(dc.n_v):
            assert s_dst[s_ptr[f]:s_ptr[f + 1]].tolist() \
                == dc.edge_dst[dc.edge_src == f].tolist()
        # built once, with the edge list
        assert dc.succ_index()[1] is s_dst


def test_rows_that_count_for_nothing_lie_behind_the_last_flows():
    # a schedule without a dependency keeps one dropped row
    s_ptr, s_dst = succ_index([0], [4], 4)
    assert s_ptr.tolist() == [0, 0, 0, 0, 0] and s_dst.tolist() == [4]
    s_ptr, s_dst = succ_index([2, 0, 9, 2, -1, 0], [1, 3, 0, 0, 2, 7], 4)
    assert s_ptr.tolist() == [0, 1, 1, 3, 3]
    assert s_dst.tolist() == [3, 1, 0, 4, 4, 4]


# ---------------------------------------------------------------------------
# the walk alone
# ---------------------------------------------------------------------------

def edge_wide(pred, done, edge_src, edge_dst):
    out = np.array(pred)
    np.subtract.at(out, edge_dst[done[edge_src]], 1)
    return out


@pytest.mark.parametrize("seed", range(4))
def test_the_walk_is_the_edge_wide_walk_on_a_random_dag(seed):
    rng = np.random.default_rng(seed)
    n_v, n_d, width = 40, 128, 64
    edge_src = rng.integers(0, n_v, n_d).astype(np.int32)
    edge_dst = rng.integers(0, n_v, n_d).astype(np.int32)
    s_ptr, s_dst = succ_index(edge_src, edge_dst, n_v)
    pred = rng.integers(0, 9, n_v).astype(np.int32)
    for n_done in (0, 1, 7, 12):
        flows = np.sort(rng.choice(n_v, n_done, replace=False))
        if np.diff(s_ptr)[flows].sum() > width:
            continue
        done = np.zeros(n_v, bool)
        done[flows] = True
        n_ev = int(rng.integers(0, 30))
        ring = rng.integers(-50, n_v, 2 * n_v).astype(np.int32)
        ring[n_ev:n_ev + n_done] = flows
        got = _succ_walk(jnp.asarray(pred), jnp.asarray(ring), n_ev, n_done,
                         jnp.asarray(s_ptr), jnp.asarray(s_dst), width)
        assert np.array_equal(got, edge_wide(pred, done, edge_src, edge_dst))


def test_completions_at_the_very_end_of_the_ring_are_read_back_right():
    """The ring's last entries, with fewer slots behind ``n_ev`` than
    the walk is wide: a slice of the width would start early."""
    n_v, width = 6, 8
    edge_src = np.array([0, 0, 1, 3, 4, 5, 5], np.int32)
    edge_dst = np.array([1, 2, 2, 4, 5, 0, 1], np.int32)
    s_ptr, s_dst = succ_index(edge_src, edge_dst, n_v)
    pred = np.full(n_v, 5, np.int32)
    ring = np.array([3, 1, 2, 0, 1, 2, 3, 4, 0, 5], np.int32)
    n_ev, n_done = 8, 2                   # flows 0 and 5, at [8, 10)
    done = np.zeros(n_v, bool)
    done[[0, 5]] = True
    got = _succ_walk(jnp.asarray(pred), jnp.asarray(ring), n_ev, n_done,
                     jnp.asarray(s_ptr), jnp.asarray(s_dst), width)
    assert got.tolist() == edge_wide(pred, done, edge_src,
                                     edge_dst).tolist() == [4, 3, 4, 5, 5, 5]


# ---------------------------------------------------------------------------
# the tape, whichever side its advances take
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [np.float64, np.float32],
                         ids=["f64", "f32"])
@pytest.mark.parametrize("name", ["pairwise16", "rdb128"])
def test_every_width_and_grouping_is_the_host_maestro(tapes, width, name,
                                                      dtype):
    """The module's own width holds every advance of these tapes; 0
    holds only the advances that finish nothing; one in between is
    passed by some.  All of them, at supersteps of 1, 4 and 16, leave
    the same state after 40 advances and the same events, activations
    and clock at the end: ``HostMaestro``'s."""
    dc = tapes[name]
    ma = HostMaestro(dc, dtype=dtype)
    ma.run()
    assert lmm_drain._SRC_WALK_EDGES == 4096
    own, mid, batches, walks = drained(dc, dtype, 16)
    assert own.events == ma.events
    assert own.collective_events == ma.collective_events
    assert own.t == ma.clock[0]
    need = needs(dc, batches)
    assert len(need) == own.advances == walks and need.max() < 4096
    assert 0 in need                      # an advance of activations only
    some = int(np.sort(need)[len(need) * 3 // 4])
    assert 0 < some < need.max()
    for w, groupings in ((0, (16,)), (some, (1, 4, 16))):
        width(w)
        for k in groupings:
            alt, mid_alt, batches_alt, walks_alt = drained(dc, dtype, k)
            assert mid_alt == mid
            assert alt.events == own.events
            assert alt.collective_events == own.collective_events
            assert (alt.t, alt.rounds, alt.advances) \
                == (own.t, own.rounds, own.advances)
            assert batches_alt == batches
            assert walks_alt == np.count_nonzero(need <= w) < len(need)


def test_the_width_itself_walks_from_the_source_and_one_more_does_not(
        tapes, width):
    dc = tapes["rdb128"]
    _, _, batches, walks = drained(dc, np.float64, 16)
    need = needs(dc, batches)
    assert walks == len(need)
    top = int(need.max())
    at_top = int(np.count_nonzero(need == top))
    width(top)
    assert drained(dc, np.float64, 16)[3] == len(need)
    width(top - 1)
    assert drained(dc, np.float64, 16)[3] == len(need) - at_top


def test_the_index_follows_the_edge_arrays(tapes):
    """A fault planted as the benchmark's tests plant theirs: the edge
    arrays reassigned after the collective was lowered.  The sim made
    next walks the NEW edges (``HostMaestro`` reads the arrays as they
    are), and the first DAG's sims are not disturbed."""
    dc = tapes["pairwise16"]
    whole = dc.make_sim(superstep=16)
    whole.run()
    edges, pred0 = (dc.edge_src, dc.edge_dst), dc.pred0
    index = dc.succ_index()
    # the first block that waits for one predecessor longer than for the
    # others loses that edge, and so starts too soon
    done = {f: t for t, f in whole.events}
    for _, first in sorted(whole.collective_events):
        preds = sorted(dc.edge_src[dc.edge_dst == first], key=done.get)
        if len(preds) > 1 and done[preds[-1]] > done[preds[-2]]:
            break
    keep = ~((dc.edge_dst == first) & (dc.edge_src == preds[-1]))
    assert keep.sum() == len(keep) - 1
    try:
        dc.edge_src, dc.edge_dst = dc.edge_src[keep], dc.edge_dst[keep]
        dc.pred0 = dc.pred0.copy()
        dc.pred0[first] -= 1
        cut = dc.succ_index()
        assert cut[1] is not index[1] and len(cut[1]) == keep.sum()
        assert dc.succ_index()[1] is cut[1]
        before = opstats.snapshot()
        sim = dc.make_sim(superstep=16)
        sim.run()
        assert opstats.diff(before)["collective_src_walks"] == sim.advances
        ma = HostMaestro(dc)
        ma.run()
        assert sim.events == ma.events != whole.events
        assert sim.collective_events == ma.collective_events
    finally:
        (dc.edge_src, dc.edge_dst), dc.pred0 = edges, pred0
    assert dc.succ_index()[1] is not cut[1]
    again = dc.make_sim(superstep=16)
    again.run()
    assert again.events == whole.events


def test_a_sim_given_the_bare_tuple_builds_the_index_itself(tapes):
    dc = tapes["pairwise16"]
    ref = dc.make_sim(superstep=16)
    ref.run()
    before = opstats.snapshot()
    sim = DrainSim(dc.e_var, dc.e_cnst, dc.e_w, dc.c_bound, dc.sizes,
                   superstep=16, penalty=dc.penalty0, dtype=np.float64,
                   collective=dc.drain_args())
    for mine, theirs in zip(sim._succ_index, dc.succ_index()):
        assert np.array_equal(mine, theirs)
    sim.run()
    assert opstats.diff(before)["collective_src_walks"] == sim.advances
    assert sim.events == ref.events
    assert sim.collective_events == ref.collective_events


# ---------------------------------------------------------------------------
# the census: what each side of the walk indexes, and who lowers as before
# ---------------------------------------------------------------------------

GATHERS = ("gather",)
SCATTERS = ("scatter", "scatter-add", "scatter_add")


def sub_jaxprs(eqn):
    for val in eqn.params.values():
        for v in (val if isinstance(val, (tuple, list)) else (val,)):
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                yield sub


def indexed_widths(jaxpr):
    """How many elements each gather fetches and each scatter writes in
    ``jaxpr``, loops and branches included: an indexed op costs by its
    indices, not by the table it reads (PERF.md §5)."""
    out = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in GATHERS:
            out.append(int(np.prod(eqn.outvars[0].aval.shape)))
        elif name in SCATTERS:
            out.append(int(np.prod(eqn.invars[2].aval.shape)))
        for sub in sub_jaxprs(eqn):
            out += indexed_widths(sub)
    return out


def conds(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            yield eqn
        for sub in sub_jaxprs(eqn):
            yield from conds(sub)


@pytest.mark.parametrize("dtype", [np.float64, np.float32],
                         ids=["f64", "f32"])
def test_the_source_side_indexes_nothing_as_wide_as_the_edge_list(
        tapes, width, dtype):
    """In the superstep's program the edge-wide gather and scatter-add
    sit in ONE branch of one ``cond`` and nowhere else; the other branch
    runs six indexed ops, none wider than twice the walk (the start and
    end of a flow's edges ride one gather)."""
    import functools
    from simgrid_tpu.analysis.prog.registry import _capture
    dc = tapes["rdb128"]
    n_edges = len(dc.edge_src)
    w = 64
    assert n_edges == 3072 and 2 * w < 2 * dc.n_v < n_edges < len(dc.e_var)
    width(w)
    sim = dc.make_sim(superstep=4, dtype=dtype)
    args, statics = _capture(lmm_drain, "_drain_superstep",
                             lambda: sim.superstep_batch(k=1))
    assert statics["has_coll"] and args[-1].shape == (n_edges,)
    jaxpr = jax.make_jaxpr(functools.partial(
        lmm_drain._superstep_program, **statics))(*args).jaxpr
    assert indexed_widths(jaxpr).count(n_edges) == 2
    walks = [[indexed_widths(br.jaxpr) for br in eqn.params["branches"]]
             for eqn in conds(jaxpr)]
    walks = [sides for sides in walks if n_edges in sum(sides, [])]
    assert len(walks) == 1
    # lax.cond(few, from the source, edge-wide): branches[1] is True's
    wide, narrow = walks[0]
    assert wide == [n_edges, n_edges]
    assert sorted(narrow) == [w] * 5 + [2 * w]


def test_the_registry_shows_proglint_the_index():
    from simgrid_tpu.analysis.prog.registry import iter_programs
    specs = {s.name: s for s in iter_programs()}
    for name in ("drain/superstep_coll", "drain/superstep_coll_f32"):
        args, statics = specs[name].make(1)
        s_ptr, s_dst = args[-2:]
        assert statics["has_coll"] and s_ptr.dtype == s_dst.dtype == np.int32
        assert len(s_ptr) == statics["n_v"] + 1
        text = specs[name].jitted.lower(*args, **statics).as_text()
        assert "stablehlo.case" in text


#: sha256 of the lowered text of the programs with a collective that
#: pass NO source-major index: the fleet's (its ``vmap`` would run both
#: sides of the ``cond``), and the solo tape's without the last two
#: arguments; re-pinned when the ring's dates became one range select
#: and its ids one scatter (tests/test_drain_ring.py).  The six
#: programs without a collective are pinned in
#: ``test_collectives_routed.py``.
PARENT_TEXT = {
    "fleet/superstep_coll": "430b7d6c241ecf79",
    "drain/superstep_coll": "aaa0b06f08276b20",
    "drain/superstep_coll_f32": "2e3a4ccee93b9a7d",
}


@pytest.mark.parametrize("name", sorted(PARENT_TEXT))
def test_a_tape_without_the_index_lowers_as_before(name):
    import hashlib
    from simgrid_tpu.analysis.prog.registry import iter_programs
    spec = {s.name: s for s in iter_programs()}[name]
    args, statics = spec.make(1)
    assert statics["has_coll"] is True
    if name.startswith("drain/"):
        args = args[:-2]
    text = spec.jitted.lower(*args, **statics).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_TEXT[name]
