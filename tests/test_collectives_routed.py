"""The routed flavor of the collective tapes (ISSUE 33): a collective
lowered onto a LOADED platform's own routes, and the tape in the solve
dtype ``lmm/dtype:auto`` resolves.

* the flavor's routes, elements, capacities and delays are, pair for
  pair, what ``NetworkCm02Model.communicate`` + ``lmm_jax.flatten``
  give the same hosts (a 128-host dragonfly, LV08);
* the pairwise alltoall on it in float64 is BIT-identical to
  ``HostMaestro`` (events, activations, clock), at any dispatch
  grouping; in float32 it agrees with the float64 run event for event
  inside the benchmark's ``date_gap`` limit, in the same order;
* a drain without a collective lowers to the program it lowered to
  before the tape had a float32 form."""

import hashlib

import numpy as np
import pytest

from simgrid_tpu import s4u
from simgrid_tpu.collectives import (CollectiveSpec, DeviceCollective,
                                     HostMaestro, RoutedTopology,
                                     Topology, generate)
from simgrid_tpu.ops import lmm_jax, opstats

XML = """<?xml version='1.0'?>
<platform version="4.1">
  <zone id="world" routing="Full">
    <cluster id="dfly" prefix="node-" radical="0-127" suffix=""
             speed="1Gf" bw="125MBps" lat="50us" topology="DRAGONFLY"
             topo_parameters="4,3;2,2;4,2;4"/>
  </zone>
</platform>
"""
RANKS, STRIDE = 16, 8
#: the benchmark's drain limit on the relative gap of a common date
DATE_GAP = 1e-5


@pytest.fixture
def engine(tmp_path):
    path = tmp_path / "dfly128.xml"
    path.write_text(XML)
    s4u.Engine._reset()
    e = s4u.Engine(["routed", "--cfg=network/maxmin-selective-update:no",
                    "--cfg=network/optim:Full"])
    e.load_platform(str(path))
    yield e
    s4u.Engine._reset()


def rank_hosts(e, shift=0):
    hosts = e.get_all_hosts()
    return [hosts[(r + shift) % RANKS * STRIDE] for r in range(RANKS)]


def test_routes_and_capacities_are_communicates(engine):
    hosts = rank_hosts(engine)
    topo = RoutedTopology(engine, hosts)
    model = engine.pimpl.network_model
    pairs = [(a, b) for a in range(RANKS) for b in range(RANKS) if a != b]
    actions = [model.communicate(hosts[a], hosts[b], 1e6, -1.0)
               for a, b in pairs]
    # the delay is the latency communicate makes the flow wait
    assert np.array_equal(
        topo.delays(*np.array(pairs).T),
        np.array([act.latency for act in actions]))
    assert set(np.unique(topo.delays(*np.array(pairs).T)).round(7)) \
        <= {round(13.01 * 5e-5 * hops, 7) for hops in range(2, 8)}
    while model.latency_phase_count:           # pay them, then flatten
        assert engine.pimpl.surf_solve(-1.0) >= 0
    cnsts = list(model.system.active_constraint_set)
    arrays, in_order = lmm_jax.flatten(cnsts)
    assert (arrays.n_cnst, arrays.n_var, arrays.n_elem) \
        == (topo.n_c, len(pairs), 2418)
    slot = {id(v): k for k, v in enumerate(in_order)}
    E = arrays.n_elem
    rec, cn, w = topo.lower(*np.array(pairs).T)
    assert len(rec) == E
    for k, ((a, b), act) in enumerate(zip(pairs, actions)):
        mine = arrays.e_var[:E] == slot[id(act.variable)]
        flat = sorted((cnsts[c].id.name, float(x)) for c, x in zip(
            arrays.e_cnst[:E][mine], arrays.e_w[:E][mine]))
        ours = sorted((topo.links[c].name, float(x))
                      for c, x in zip(cn[rec == k], w[rec == k]))
        assert ours == flat, (a, b)
        # route() is routing/'s route, hop for hop
        links = []
        hosts[a].route_to(hosts[b], links)
        assert [topo.links[c] for c in topo.route(a, b)] == links
        assert len(topo.route(a, b)) == int((w[rec == k] == 1.0).sum())
    for ci in range(arrays.n_cnst):
        at = topo.links.index(cnsts[ci].id)
        assert topo.c_bound[at] == arrays.c_bound[ci] == 0.97 * (
            cnsts[ci].id.get_bandwidth())


@pytest.mark.parametrize("crosstraffic", [True, False],
                         ids=["crosstraffic", "no-crosstraffic"])
def test_a_rank_sending_to_itself_rides_communicates_self_route(
        tmp_path, crosstraffic):
    """A self pair (the lr allreduce's self-copy) is lowered as
    ``NetworkCm02Model.communicate(h, h)`` expands it into the engine's
    system: the host's router link up and back down at weight 1 and,
    under cross-traffic, the same two links again at 0.05, kept apart
    as ``expand`` keeps them; its delay is the latency the action
    waits."""
    path = tmp_path / "dfly128.xml"
    path.write_text(XML)
    s4u.Engine._reset()
    flags = ["--cfg=network/maxmin-selective-update:no",
             "--cfg=network/optim:Full"]
    if not crosstraffic:
        flags.append("--cfg=network/crosstraffic:0")
    try:
        e = s4u.Engine(["self"] + flags)
        e.load_platform(str(path))
        hosts = rank_hosts(e)
        topo = RoutedTopology(e, hosts)
        before = opstats.snapshot()
        rec, cn, w = topo.lower(np.array([3, 3, 5]), np.array([3, 4, 5]))
        took = opstats.diff(before)
        model = e.pimpl.network_model
        for k, h in ((0, 3), (2, 5)):
            act = model.communicate(hosts[h], hosts[h], 1e6, -1.0)
            want = [(el.constraint.id.name, el.consumption_weight)
                    for el in act.variable.cnsts]
            ours = [(topo.links[c].name, float(x))
                    for c, x in zip(cn[rec == k], w[rec == k])]
            assert ours == want
            assert len(want) == (4 if crosstraffic else 2)
            assert [n for n, _ in want[:2]] == [
                n for n, _ in want[2:]] or not crosstraffic
            assert topo.delays([h], [h])[0] == act.latency
            assert act.latency == pytest.approx(13.01 * 2 * 5e-5, rel=1e-12)
            links = []
            hosts[h].route_to(hosts[h], links)
            assert [topo.links[c] for c in topo.route(h, h)] == links
        # (3, 3), (5, 5), (3, 4) and, with cross-traffic, (4, 3): each
        # routed once, the two self pairs counted apart
        assert took["collective_self_routes"] == 2
        assert took["collective_routes"] == (4 if crosstraffic else 3)
    finally:
        s4u.Engine._reset()


FLAT_XML = """<?xml version='1.0'?>
<platform version="4.1">
  <zone id="world" routing="Full">
    <host id="a" speed="1Gf"/>
    <host id="b" speed="1Gf"/>
    <link id="l" bandwidth="125MBps" latency="50us"/>
    <route src="a" dst="b"><link_ctn id="l"/></route>
  </zone>
</platform>
"""


def test_a_host_with_no_route_to_itself_rides_the_models_loopback(
        tmp_path):
    """Where the platform routes no host to itself, ``communicate``
    puts the flow on the network model's loopback: so does the routed
    flavor, with its latency."""
    path = tmp_path / "flat.xml"
    path.write_text(FLAT_XML)
    s4u.Engine._reset()
    try:
        e = s4u.Engine(["flat"])
        e.load_platform(str(path))
        hosts = e.get_all_hosts()
        topo = RoutedTopology(e, hosts)
        rec, cn, w = topo.lower(np.array([0]), np.array([0]))
        act = e.pimpl.network_model.communicate(hosts[0], hosts[0], 1e6,
                                                -1.0)
        assert [(topo.links[c].name, float(x)) for c, x in zip(cn, w)] \
            == [(el.constraint.id.name, el.consumption_weight)
                for el in act.variable.cnsts]
        assert topo.links[cn[0]] is e.pimpl.network_model.loopback
        assert topo.delays([0], [0])[0] == act.latency > 0
    finally:
        s4u.Engine._reset()


def test_a_topology_for_other_ranks_is_refused(engine):
    topo = RoutedTopology(engine, rank_hosts(engine))
    with pytest.raises(ValueError, match="places 16 ranks"):
        CollectiveSpec("alltoall", "pairwise", 8, topo, 1e6)


def test_the_spec_is_addressed_by_its_placement(engine):
    a = CollectiveSpec("alltoall", "pairwise", RANKS,
                       RoutedTopology(engine, rank_hosts(engine)), 1e6)
    b = CollectiveSpec("alltoall", "pairwise", RANKS,
                       RoutedTopology(engine, rank_hosts(engine)), 1e6)
    c = CollectiveSpec("alltoall", "pairwise", RANKS,
                       RoutedTopology(engine, rank_hosts(engine, 3)), 1e6)
    assert a.key() == b.key() != c.key()
    assert a.label() == "alltoall/pairwise r16 routed 1e+06B"
    with pytest.raises(ValueError, match="not from JSON"):
        CollectiveSpec.from_json(a.to_json())


def test_the_vector_lowering_is_the_loop_it_replaced():
    """``DeviceCollective`` lowers through ``Topology.lower`` and the
    edge list through one sort: on the synthetic flavors both are what
    the per-record, per-link loops gave."""
    sched = generate("allreduce", "lr", 5, 23)
    for flavor in ("nic", "star", "ring"):
        topo = Topology(5, flavor, bw=1e8)
        dc = DeviceCollective(sched, topo)
        ev, ec, es, ed = [], [], [], []
        for rec in sched.records:
            for c in topo.route(rec.src, rec.dst):
                ev.append(rec.rid)
                ec.append(c)
            for p in sorted(r.rid for r in rec.preds):
                es.append(p)
                ed.append(rec.rid)
        assert dc.e_var.tolist() == ev and dc.e_cnst.tolist() == ec
        assert dc.edge_src.tolist() == es and dc.edge_dst.tolist() == ed
        assert np.all(dc.e_w == 1.0) and not dc.exec_cost.any()


@pytest.fixture
def pairwise(engine):
    topo = RoutedTopology(engine, rank_hosts(engine, 5))
    before = opstats.snapshot()
    dc = CollectiveSpec("alltoall", "pairwise", RANKS, topo, 1e6).build()
    names = [(s.name, s.id) for s in opstats.spans()
             if s.name == "coll.lower"]
    assert names[-2:] == [("coll.lower", "routes"), ("coll.lower", "tape")]
    assert not opstats.diff(before).get("flows_posted")
    return dc


def test_float64_tape_is_the_host_maestro_bit_for_bit(pairwise):
    dc = pairwise
    assert (dc.n_c, dc.n_v, len(dc.e_var), dc.n_edges) \
        == (114, 240, 2418, 896)
    # every block waits for its route's latency, step 1 for nothing else
    assert np.all(dc.exec_cost > 1.3e-3) and not dc.penalty0.any()
    assert np.isfinite(dc.ready0).sum() == RANKS
    before = opstats.snapshot()
    sim = dc.make_sim(superstep=16)
    assert sim.dtype == np.float64           # lmm/dtype:auto on the CPU
    sim.run()
    d = opstats.diff(before)
    assert len(sim.events) == len(sim.collective_events) == dc.n_v
    assert d["collective_tape_fires"] == dc.n_v
    # the flows live as each advance entered: never more than a step's
    assert 0 < d["collective_live_flow_advances"] <= RANKS * sim.advances
    ma = HostMaestro(dc)
    ma.run()
    assert ma.events == sim.events
    assert ma.collective_events == sim.collective_events
    clk = np.asarray(sim._coll_clk)
    assert ma.clock == (float(clk[0]), float(clk[1]))
    assert sim.t == ma.clock[0] == sim.events[-1][0]
    for k, depth in ((1, 0), (5, 2)):
        alt = dc.make_sim(superstep=k, pipeline=depth)
        alt.run()
        assert alt.events == sim.events and alt.t == sim.t
        assert alt.collective_events == sim.collective_events


def test_float32_tape_agrees_event_for_event(pairwise):
    dc = pairwise
    ref = dc.make_sim(superstep=16)
    ref.run()
    before = opstats.snapshot()
    sim = dc.make_sim(superstep=16, dtype=np.float32)
    assert sim.dtype == np.float32
    sim.run()
    d = opstats.diff(before)
    assert d["collective_tape_fires"] == dc.n_v
    for got, want in ((sim.events, ref.events),
                      (sim.collective_events, ref.collective_events)):
        t_ref = dict((f, t) for t, f in want)
        assert len(got) == len(want) == dc.n_v
        assert max(abs(t - t_ref[f]) / t_ref[f] for t, f in got) < DATE_GAP
        # order_gap 0: nothing listed before a flow the float64 run
        # finished earlier (flows of one date are one unordered group)
        high = 0.0
        for t, f in sorted(got, key=lambda e: (e[0], t_ref[e[1]])):
            assert t_ref[f] >= high
            high = t_ref[f]
    # the carried clock is float64 in both; the float32 run's dates
    # are its replayed pair's, not the ring's float32 ones
    assert np.asarray(sim._coll_clk).dtype == np.float64
    assert sim.t == float(np.asarray(sim._coll_clk)[0])
    assert abs(sim.t - ref.t) / ref.t < DATE_GAP
    # and the host maestro at float32 replays the same recurrence
    ma = HostMaestro(dc, dtype=np.float32)
    ma.run()
    assert ma.events == sim.events
    assert ma.collective_events == sim.collective_events
    one = dc.make_sim(superstep=1, dtype=np.float32)
    one.run()
    assert one.events == sim.events and one.t == sim.t


def test_a_replayed_dispatch_needs_its_fetch(pairwise):
    sim = pairwise.make_sim(superstep=4)
    with pytest.raises(ValueError, match="replayed from the fetched"):
        sim.superstep_batch(fetch=False)


#: sha256 of ``_drain_superstep.lower(...).as_text()`` for the
#: registry's scale-1 example: the programs WITHOUT a collective lower
#: to the same text whatever the collective arm does.  Re-pinned when
#: the ring's dates became one range select (tests/test_drain_ring.py);
#: if a later change moves the drain's program on purpose, re-pin from
#: the failing assert.
PARENT_TEXT = {
    "drain/superstep": "01d68c3371f59e95",
    "drain/superstep_f32": "57e1addca67ae5b7",
    "drain/superstep_tape": "88c0c385ff46f3b9",
    "fleet/superstep": "51adb3f95efade91",
    "fleet/superstep_f32": "864abf4ce6f1bfab",
    "fleet/superstep_tape": "7491209d2a533229",
}


@pytest.mark.parametrize("name", sorted(PARENT_TEXT))
def test_a_drain_without_a_collective_lowers_as_before(name):
    from simgrid_tpu.analysis.prog.registry import iter_programs
    spec = {s.name: s for s in iter_programs()}[name]
    args, statics = spec.make(1)
    assert statics["has_coll"] is False
    text = spec.jitted.lower(*args, **statics).as_text()
    assert "sg.drain.coll" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_TEXT[name]


def test_the_collective_program_names_its_scope():
    from simgrid_tpu.analysis.prog.registry import iter_programs
    spec = {s.name: s for s in iter_programs()}["drain/superstep_coll"]
    for dtype in (np.float64, np.float32):
        args, statics = spec.make(1, dtype)
        assert statics["has_coll"] is True
        text = spec.jitted.lower(*args, **statics).as_text(
            debug_info=True)
        assert "sg.drain.coll" in text


# ---------------------------------------------------------------------------
# routes on demand (ISSUE 35): a schedule's own pairs, each looked up once
# ---------------------------------------------------------------------------

def all_ranks(e):
    return list(e.get_all_hosts())


@pytest.fixture
def route_calls(monkeypatch):
    """Every ``route_to`` call, as (source host, destination host)."""
    from simgrid_tpu.models.host import Host
    calls, real = [], Host.route_to

    def counted(self, dst, links):
        calls.append((self.name, dst.name))
        return real(self, dst, links)

    monkeypatch.setattr(Host, "route_to", counted)
    return calls


def test_a_route_is_looked_up_once_and_only_if_a_record_uses_it(
        engine, route_calls):
    hosts = all_ranks(engine)
    topo = RoutedTopology(engine, hosts)
    assert route_calls == [] and topo.n_c == 0        # nothing routed yet
    before = opstats.snapshot()
    dc = CollectiveSpec("allreduce", "rdb", 128, topo, 8192.0).build()
    used = {(hosts[r.src].name, hosts[r.dst].name)
            for r in dc.schedule.records}
    # recursive doubling: every pair's way back is a pair of its own
    assert len(used) == dc.n_v == 128 * 7
    assert len(route_calls) == len(set(route_calls)) == len(used)
    assert set(route_calls) == used
    assert opstats.diff(before)["collective_routes"] == len(used)
    names = [s.id for s in opstats.spans() if s.name == "coll.lower"][-4:]
    assert names == ["schedule", "tape", "routes", "tape"]
    assert dc.n_c == topo.n_c == len(topo.links) == len(dc.c_bound)
    # asking again routes nothing; a new pair routes itself and its way
    # back, and the slots handed out so far stay
    slots = topo.route(0, 1)
    topo.delays([0, 5], [1, 4])
    assert len(route_calls) == len(used)
    assert (hosts[0].name, hosts[3].name) not in used
    topo.route(0, 3)
    assert route_calls[len(used):] == [(hosts[0].name, hosts[3].name),
                                       (hosts[3].name, hosts[0].name)]
    assert topo.route(0, 1) == slots and topo.n_c >= dc.n_c


def test_a_binomial_bcast_routes_the_ways_back_too(engine, route_calls):
    """A pair whose way back no record uses is still crossed by the
    cross-traffic of the way there: both are looked up, once."""
    hosts = rank_hosts(engine)
    dc = CollectiveSpec("bcast", "binomial_tree", RANKS,
                        RoutedTopology(engine, hosts), 1e6).build()
    assert dc.n_v == RANKS - 1
    assert len(route_calls) == len(set(route_calls)) == 2 * dc.n_v
    fwd = dc.e_w == 1.0
    assert fwd.any() and (~fwd).any()
    assert np.all(dc.e_w[~fwd] == RoutedTopology.CROSSTRAFFIC_WEIGHT)


def lowering_digest(dc):
    h = hashlib.sha256()
    for a in (dc.e_var, dc.e_cnst, dc.e_w, dc.c_bound, dc.exec_cost):
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


#: ``lowering_digest`` of the pairwise alltoall at the parent commit
#: (8f4321b), whose constructor routed all R x R pairs in rank order:
#: 16 ranks at stride 8, rotated by 5, on the 128-host dragonfly, and
#: ``dfly65k-pairwise.drain``'s 320 ranks at stride 204 on config #4's
PARENT_LOWERING = {16: "09737e93014b9d39", 320: "c40c35b5b82543f7"}

XML_65K = XML.replace("0-127", "0-65535").replace("4,3;2,2;4,2;4",
                                                  "16,3;4,2;16,2;64")


def test_the_16_rank_pairwise_lowers_to_the_parents_arrays(engine):
    dc = CollectiveSpec("alltoall", "pairwise", RANKS,
                        RoutedTopology(engine, rank_hosts(engine, 5)),
                        1e6).build()
    assert lowering_digest(dc) == PARENT_LOWERING[16]


def test_the_320_rank_pairwise_lowers_to_the_parents_arrays(tmp_path):
    """The benchmark cell's program input: slots numbered by first
    crossing, pairs in rank order, as the all-pairs walk numbered
    them."""
    path = tmp_path / "dfly65k.xml"
    path.write_text(XML_65K)
    s4u.Engine._reset()
    try:
        e = s4u.Engine(["routed65k",
                        "--cfg=network/maxmin-selective-update:no",
                        "--cfg=network/optim:Full"])
        e.load_platform(str(path))
        hosts = e.get_all_hosts()
        before = opstats.snapshot()
        dc = CollectiveSpec(
            "alltoall", "pairwise", 320,
            RoutedTopology(e, [hosts[r * 204] for r in range(320)]),
            1e6).build()
        assert opstats.diff(before)["collective_routes"] == 320 * 319
        assert (dc.n_c, dc.n_v, len(dc.e_var), dc.n_edges) \
            == (8724, 102080, 1275102, 407040)
        assert lowering_digest(dc) == PARENT_LOWERING[320]
    finally:
        s4u.Engine._reset()


# ---------------------------------------------------------------------------
# recursive doubling on the routed flavor: bursts of R flows
# ---------------------------------------------------------------------------

@pytest.fixture
def rdb128(engine):
    return CollectiveSpec("allreduce", "rdb", 128,
                          RoutedTopology(engine, all_ranks(engine)),
                          8192.0).build()


def ran(dc, **kw):
    before = opstats.snapshot()
    sim = dc.make_sim(superstep=16, **kw)
    sim.run()
    return sim, opstats.diff(before)


def test_the_rdb_tape_is_the_host_maestro_in_float64_and_close_in_float32(
        rdb128):
    dc = rdb128
    assert (dc.n_v, dc.n_edges) == (896, 4 * 128 * 6)
    sim, took = ran(dc)
    assert sim.dtype == np.float64
    assert len(sim.events) == len(sim.collective_events) == dc.n_v
    assert took["collective_tape_fires"] == dc.n_v
    ma = HostMaestro(dc)
    ma.run()
    assert ma.events == sim.events
    assert ma.collective_events == sim.collective_events
    assert sim.t == ma.clock[0] == sim.events[-1][0]
    # a step's 128 flows are on the wire together
    assert took["collective_live_flow_advances"] > 128
    low, _ = ran(dc, dtype=np.float32)
    for got, want in ((low.events, sim.events),
                      (low.collective_events, sim.collective_events)):
        t_ref = dict((f, t) for t, f in want)
        assert len(got) == len(want)
        assert max(abs(t - t_ref[f]) / t_ref[f] for t, f in got) < DATE_GAP
        # the same order, but for dates float64 itself tells apart by
        # an ulp (two ways of adding up to one activation date): float32
        # may take those either way round
        high = 0.0
        for t, f in sorted(got, key=lambda e: (e[0], t_ref[e[1]])):
            assert t_ref[f] >= high * (1 - 1e-12)
            high = max(high, t_ref[f])


@pytest.mark.parametrize("dtype", [np.float64, np.float32],
                         ids=["f64", "f32"])
def test_a_burst_over_the_rungs_below_the_list_falls_back_with_the_index_present(
        rdb128, dtype, monkeypatch):
    """The full-width side of ``fixpoint``'s entry: an advance whose
    live elements fit no rung below the list takes the full-width entry
    and the descent, index or no index; one that fits a rung between
    enters there from the variable side (ISSUE 39).  With a bottom rung
    of 120 elements the flows of a step that start on one date are
    over it in a quarter of the advances: on the ratio-2 ladder they
    enter at a middle rung, and on one whose second rung holds 232
    (``_LADDER_TOP_ELEMS`` brought down to 256, as the allreduce cell's
    list steps from 9.2 M elements to 1.15 M) the largest fit none and
    fall back.  The events, the activations, the clock, the rounds and
    the tape's counters are those of the same tape on a ladder whose
    bottom rung (1,848) fits every burst, where every advance enters
    at the bottom."""
    import jax
    from simgrid_tpu.ops import lmm_jax
    dc = rdb128

    def under(floor, top=lmm_jax._LADDER_TOP_ELEMS):
        monkeypatch.setattr(lmm_jax, "_LADDER_MIN_ELEMS", floor)
        monkeypatch.setattr(lmm_jax, "_LADDER_TOP_ELEMS", top)
        jax.clear_caches()
        sizes = lmm_jax._ladder_sizes((-(-len(dc.e_var) // 8), 8))
        sim, took = ran(dc, dtype=dtype)
        return sizes, sim, took

    try:
        sizes, fits, took_fits = under(1500)
        assert sizes == [7392, 3696, 1848]
        assert took_fits["fixpoint_var_entries"] == fits.advances
        sizes, burst, took = under(64)
        assert sizes[-1] == 120 and len(sizes) == 7
        assert took["fixpoint_var_entries"] == burst.advances
        wide_sizes, wide, took_wide = under(64, 256)
        assert wide_sizes == [7392, 232, 120]
        assert 0 < took_wide["fixpoint_var_entries"] < wide.advances
    finally:
        jax.clear_caches()
    for sim, got in ((burst, took), (wide, took_wide)):
        assert sim.events == fits.events
        assert sim.collective_events == fits.collective_events
        assert (sim.t, sim.rounds, sim.advances) \
            == (fits.t, fits.rounds, fits.advances)
        for k in ("collective_live_flow_advances", "collective_tape_fires",
                  "fixpoint_rounds"):
            assert got[k] == took_fits[k]
    # what the bursts' rounds indexed is wider than the bottom rung
    assert took["fixpoint_worked_elem_rounds"] \
        > sizes[-1] * took["fixpoint_rounds"]


# ---------------------------------------------------------------------------
# the logical ring on the routed flavor: a self-copy, then a ring step
# ---------------------------------------------------------------------------

def ring_reference():
    """``benchmarks/configs/dragonfly_lv08_ring.py`` (numpy, float64,
    nothing of the program), loaded with the package beside it under a
    name of its own."""
    import importlib
    import importlib.util
    import os
    import sys
    name = "benchmark_references"
    if name not in sys.modules:
        folder = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs")
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(folder, "__init__.py"),
            submodule_search_locations=[folder])
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return importlib.import_module(name + ".dragonfly_lv08_ring")


RING_STEPS = 4


@pytest.fixture
def lr128(engine):
    before = opstats.snapshot()
    dc = CollectiveSpec("allreduce", "lr", 128,
                        RoutedTopology(engine, all_ranks(engine)),
                        128 * 16384, steps=RING_STEPS).build()
    took = opstats.diff(before)
    assert took["collective_schedule_records"] == dc.n_v \
        == 128 * (RING_STEPS + 1)
    assert took["collective_self_routes"] == 128
    return dc


def ring_dates(events, dc, dag):
    """{reference flow: date}, the program's records named as the
    reference names its flows (both rank-major, the self-copy first)."""
    src = np.array([r.src for r in dc.schedule.records])
    dst = np.array([r.dst for r in dc.schedule.records])
    assert np.array_equal(src, dag.src) and np.array_equal(dst, dag.dst)
    return {int(f): t for t, f in events}


def test_the_lr_head_is_the_ring_reference_in_float64_and_close_in_float32(
        lr128):
    """The routed ``lr`` tape at 128 ranks, drained whole: every
    completion and activation at the reference's date to 1e-12 and in
    its order in float64 (and the host maestro's bit for bit); within
    the benchmark cell's date limit in float32, nothing unmatched, in
    the same order but for dates the float64 reference itself tells
    apart by an ulp (two ways of adding up to one activation date:
    float32 may take those either way round)."""
    ref = ring_reference()
    dc = lr128
    dag = ref.ring_dag(128, RING_STEPS)
    system, delay = ref.dag_system("4,3;2,2;4,2;4", 125e6, 5e-5,
                                   np.arange(128), dag)
    assert (dc.n_c, dc.n_v, len(dc.e_var), dc.n_edges) \
        == (*system.shape, int((dag.preds >= 0).sum()))
    assert np.allclose(dc.exec_cost, delay, rtol=1e-12, atol=0)
    done, started, _ = ref.drain(system, dag, delay,
                                 np.full(dc.n_v, 131072.0), 10**6)
    want = (ring_dates(done, dc, dag), ring_dates(started, dc, dag))
    sim, _ = ran(dc)
    assert sim.dtype == np.float64
    ma = HostMaestro(dc)
    ma.run()
    assert ma.events == sim.events
    assert ma.collective_events == sim.collective_events
    low, _ = ran(dc, dtype=np.float32)
    for run, gap, ulp in ((sim, 1e-12, 0.0), (low, DATE_GAP, 1e-12)):
        for got, ref_t in ((run.events, want[0]),
                           (run.collective_events, want[1])):
            assert len(got) == len(ref_t) == dc.n_v
            assert max(abs(t - ref_t[f]) / ref_t[f] for t, f in got) < gap
            high = 0.0
            for t, f in sorted(got, key=lambda e: (e[0], ref_t[e[1]])):
                assert ref_t[f] >= high * (1 - ulp)
                high = max(high, ref_t[f])
