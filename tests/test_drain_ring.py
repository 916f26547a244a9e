"""The superstep's ring: what an advance logs, and what writing it costs.

An advance logs its completions in slot order, then the fault it fired,
then the activations it fired, all at its one date: one run of entries
``[n_ev, n_new)`` of the ring.  The program writes the dates of that
run with one range select, and every id but the fault's with one
scatter as wide as the flows, the activations riding the completions'
index vector.  Held here:

* the census: in the traced program one flow-wide scatter into the id
  ring and none into the date ring, the fault's id a write of its own,
  whatever the program is armed with; a tape with the source-major
  index adds one write as wide as each rung it keeps, the flow-wide
  scatter the last side of their switch (``test_drain_ring_narrow.py``
  holds those writes to the bit);
* the ring to the bit: dispatches of several advances against a numpy
  statement of the writes (in slot order, completions, then the fault,
  then the activations, what lies past the ring dropped), read from
  one-advance dispatches' state: a plain drain, a fault tape that
  fires, a routed collective whose advances both complete flows and
  fire activations with a fault tape beside it, the vmapped fleet, in
  float32 and float64;
* the start the one scatter rests on: a collective tuple whose live
  flow waits or holds a ready date, or whose dated flow waits, is
  refused by ``DrainSim`` and ``BatchDrainSim``, and every tape the
  collectives lower starts as it must."""

import functools
import inspect

import numpy as np
import pytest

import jax

from simgrid_tpu import s4u
from simgrid_tpu.analysis.prog.registry import _capture
from simgrid_tpu.collectives import CollectiveSpec, RoutedTopology
from simgrid_tpu.collectives.schedule import GENERATORS
from simgrid_tpu.collectives.topology import FLAVORS
from simgrid_tpu.ops import lmm_batch, lmm_drain
from simgrid_tpu.ops.lmm_batch import BatchDrainSim, ReplicaOverrides
from simgrid_tpu.ops.lmm_drain import (_STATS_HEAD, DrainSim,
                                       _check_collective_start)

from tests.test_coll_src_walk import XML, conds, sub_jaxprs

#: sizes no two of which coincide, nor with any ring's width below
N_C, N_V, DEG, K = 7, 40, 3, 3
PARAMS = list(inspect.signature(lmm_drain._superstep_program).parameters)
DTYPES = pytest.mark.parametrize("dtype", [np.float64, np.float32],
                                 ids=["f64", "f32"])


def arrays(dtype):
    """40 flows over 7 links, three each: flows ``i`` and ``i + 20``
    cross the same links at the same weight and hold the same size, so
    they finish in the same advance."""
    rng = np.random.default_rng(5)
    route = np.stack([rng.choice(N_C, DEG, replace=False)
                      for _ in range(N_V // 2)])
    e_var = np.repeat(np.arange(N_V, dtype=np.int32), DEG)
    e_cnst = np.concatenate([route, route]).reshape(-1).astype(np.int32)
    e_w = np.ones(N_V * DEG, dtype)
    c_bound = rng.uniform(1.0, 4.0, N_C).astype(dtype)
    sizes = np.tile(rng.uniform(1.0, 6.0, N_V // 2), 2)
    return e_var, e_cnst, e_w, c_bound, sizes


def chain(n_v):
    """A chain DAG whose root starts live: flow i+1 waits on flow i."""
    pred = np.ones(n_v, np.int32)
    pred[0] = 0
    pen = np.zeros(n_v)
    pen[0] = 1.0
    return pen, (pred, np.full(n_v, np.inf),
                 np.arange(n_v - 1, dtype=np.int32),
                 np.arange(1, n_v, dtype=np.int32), np.full(n_v, 0.125))


def plain_sim(dtype, superstep=K, tape=None, coll=False):
    kw = dict(eps=1e-9, dtype=dtype, superstep=superstep,
              repack_min=1 << 62, tape=tape)
    if coll:
        kw["penalty"], kw["collective"] = chain(N_V)
    return DrainSim(*arrays(dtype), **kw)


def tape_at(t_end, c_bound):
    """A link that loses three quarters of its capacity at 0.3 of the
    drain and gets it back at 0.6."""
    return (np.array([0.3, 0.6]) * t_end, np.array([1, 1], np.int32),
            np.array([c_bound[1] / 4, c_bound[1]]))


@pytest.fixture(scope="module")
def routed():
    """A recursive-doubling allreduce of 16 ranks on a 128-host
    dragonfly: the routes' latencies date the activations, and some
    fall in the advance that completes a flow."""
    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        path = f"{tmp}/dfly128.xml"
        with open(path, "w") as f:
            f.write(XML)
        s4u.Engine._reset()
        e = s4u.Engine(["drain_ring",
                        "--cfg=network/maxmin-selective-update:no",
                        "--cfg=network/optim:Full"])
        e.load_platform(path)
        try:
            yield CollectiveSpec(
                "allreduce", "rdb", 16,
                RoutedTopology(e, list(e.get_all_hosts()[:16])),
                8192.0).build()
        finally:
            s4u.Engine._reset()


def make(kind, dtype, routed_dc=None, superstep=K):
    """The sim of a case: ``plain``, ``fault`` (a tape that fires) or
    ``coll`` (the routed allreduce with a fault tape beside it)."""
    if kind == "coll":
        whole = routed_dc.make_sim(superstep=16, dtype=dtype)
        whole.run()
        return routed_dc.make_sim(
            superstep=superstep, dtype=dtype,
            tape=tape_at(whole.t, routed_dc.c_bound))
    if kind == "fault":
        whole = plain_sim(dtype, superstep=16)
        whole.run()
        return plain_sim(dtype, superstep,
                         tape=tape_at(whole.t, arrays(dtype)[3]))
    return plain_sim(dtype, superstep)


def captured(sim):
    """The exact arguments the sim's next dispatch passes, by name."""
    args, statics = _capture(lmm_drain, "_drain_superstep",
                             lambda: sim.superstep_batch(k=1))
    return dict(zip(PARAMS, args)), statics


def dispatch(a, statics, **state):
    """One call of the jitted program on ``a`` with ``state`` swapped
    in, every output fetched."""
    kw = {**a, **state}
    out = lmm_drain._drain_superstep(*[kw[p] for p in PARAMS[:len(a)]],
                                     **statics)
    return [np.asarray(o) for o in out]


def ring_n(statics):
    n_v = statics["n_v"]
    return (n_v + statics["k_max"] * statics["has_tape"]
            + n_v * statics["has_coll"])


def unpack(packed, statics):
    """(stats, adv_dt, adv_nev, ring_t, ring_id) of a packed vector."""
    k, n = statics["k_max"], ring_n(statics)
    cuts = np.cumsum([_STATS_HEAD, k, k, n, n])
    stats, adv_dt, adv_nev, ring_t, ring_id, _tail = np.split(packed, cuts)
    return (stats, adv_dt, adv_nev.astype(np.int64), ring_t,
            ring_id.astype(np.int64))


def old_ring(advances, n, n_c, ids, dtype):
    """The ring as the writes of one scatter per kind of entry left it:
    each advance's completions in slot order, its fault, its
    activations, at its date; entries past the ring dropped."""
    ring_t, ring_id = np.zeros(n, dtype), np.zeros(n, np.int64)
    nev, at = [], 0
    for done, slot, act, date in advances:
        entries = [*ids[np.flatnonzero(done)],
                   *([-(1 + slot)] if slot is not None else []),
                   *(-(1 + n_c + ids[np.flatnonzero(act)]))]
        for entry in entries:
            if at < n:
                ring_t[at], ring_id[at] = date, entry
            at += 1
        nev.append(at)
    return ring_t, ring_id, nev


def same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape \
        and a.tobytes() == b.tobytes()


STATE = ("pen", "rem", "c_bound", "tape_pos", "coll_pred", "coll_ready",
         "coll_clk")


def stepped(a, statics, advances):
    """Up to ``advances`` one-advance dispatches from ``a``'s state,
    each starting where the dispatch of several would be at that
    advance (the same state, and for a drain without a collective the
    same absolute date: its in-dispatch clock replayed as the Kahan
    pair it is).  Each one's own ring is held to the numpy statement;
    returns per advance (done, fault slot, activated, date in the
    dispatch of several, dt) and the state after the last."""
    dtype = a["e_w"].dtype.type
    coll = statics["has_coll"]
    state = {name: a[name] for name in STATE}
    ids = np.asarray(a["ids"])
    tape_slot = np.asarray(a["tape_slot"])
    s, comp = dtype(0), dtype(0)
    log = []
    for _ in range(advances):
        t0 = a["t0"] if coll else np.float64(a["t0"]) + np.float64(s)
        out = dispatch(a, statics, k=np.int32(1), t0=t0, **state)
        stats, adv_dt, _nev, ring_t, ring_id = unpack(out[7], statics)
        if stats[1] == 0:
            break
        pen0, pen1 = np.asarray(state["pen"]), out[0]
        done, act = (pen0 > 0) & (pen1 <= 0), (pen0 <= 0) & (pen1 > 0)
        tpos = int(state["tape_pos"])
        slot = int(tape_slot[tpos]) if int(out[3]) > tpos else None
        dt = adv_dt[0]
        if coll:
            date = own = dtype(out[6][0])
        else:
            y = dtype(dt - comp)
            t = dtype(s + y)
            s, comp = t, dtype(dtype(t - s) - y)
            date, own = s, dt
        want = old_ring([(done, slot, act, own)], len(ring_t), statics["n_c"],
                        ids, dtype)
        assert same_bits(ring_t, want[0])
        assert np.array_equal(ring_id, want[1]) and _nev[0] == want[2][0]
        log.append((done, slot, act, date, dt))
        state = dict(zip(STATE, out[:7]))
    return log, state


def held(a, statics, k):
    """One dispatch of up to ``k`` advances from ``a`` against the
    one-advance dispatches' numpy statement: the packed ring, tables
    and stats, and the state it returns, to the bit.  Returns the
    advances it made, its log and its outputs."""
    out = dispatch(a, statics, k=np.int32(k))
    stats, adv_dt, adv_nev, ring_t, ring_id = unpack(out[7], statics)
    adv = int(stats[1])
    log, state = stepped(a, statics, adv)
    assert len(log) == adv
    dtype = a["e_w"].dtype.type
    want_t, want_id, nev = old_ring([(d, s, ac, t) for d, s, ac, t, _ in log],
                                    len(ring_t), statics["n_c"],
                                    np.asarray(a["ids"]), dtype)
    assert same_bits(ring_t, want_t)
    assert np.array_equal(ring_id, want_id)
    assert adv_nev.tolist() == nev + [0] * (len(adv_nev) - adv)
    assert same_bits(adv_dt[:adv], np.array([dt for *_, dt in log], dtype))
    assert int(stats[2]) == (nev[-1] if nev else 0)
    if adv:
        assert same_bits(stats[3], log[-1][3])
    for name, got in zip(STATE, out[:7]):
        assert same_bits(got, state[name]), name
    return adv, log, out


def drive(sim, check):
    """The sim's whole drain as dispatches of its superstep, each held
    by ``check(a, statics)``, the state carried from one to the next as
    the sim carries it; returns every advance's log."""
    a, statics = captured(sim)
    logs = []
    for _ in range(200):
        adv, log, out = check(a, statics)
        if not adv:
            return logs
        logs += log
        a = {**a, **dict(zip(STATE, out[:7]))}
        if not statics["has_coll"]:
            a["t0"] = np.float64(a["t0"]) + np.float64(
                unpack(out[7], statics)[0][3])
    raise AssertionError("the drain did not end")


# ---------------------------------------------------------------------------
# the census
# ---------------------------------------------------------------------------

def ring_scatters(jaxpr, n):
    """(operand dtype, elements written) of every scatter into an
    ``n``-wide vector, loops and branches included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter"):
            operand = eqn.invars[0].aval
            if operand.shape == (n,):
                out.append((np.dtype(operand.dtype),
                            int(np.prod(eqn.invars[2].aval.shape))))
        for sub in sub_jaxprs(eqn):
            out += ring_scatters(sub, n)
    return out


@pytest.mark.parametrize("tape,coll,index", [
    (True, False, False), (False, True, True), (False, True, False),
    (True, True, True)], ids=["tape", "coll", "coll_bare", "tape_coll"])
def test_an_advance_writes_its_ids_with_one_scatter_and_no_date_scatter(
        tape, coll, index, monkeypatch):
    """Exactly one scatter into the ring is as wide as the flows, and
    it writes ids; none writes dates; the fault's id is the one other
    write into the ring, one element wide.  A tape with the source-major
    index adds one write as wide as each rung it keeps (none at this
    size, two once the rungs are narrow enough), and its flow-wide
    scatter is then the last side of one switch, each other side a
    rung's write."""
    sim = plain_sim(np.float64, tape=_tape() if tape else None, coll=coll)
    a, statics = captured(sim)
    assert (statics["has_tape"], statics["has_coll"]) == (tape, coll)
    args = [a[p] for p in PARAMS[:len(a)]]
    if coll and not index:
        args = args[:-2]
    n = ring_n(statics)
    assert len({n, N_V, N_C, N_V * DEG, N_V - 1, K}) == 6
    trace = lambda: jax.make_jaxpr(functools.partial(
        lmm_drain._superstep_program, **statics))(*args).jaxpr
    jaxpr = trace()
    writes = ring_scatters(jaxpr, n)
    assert [w for w in writes if w[0].kind == "f"] == []
    assert writes.count((np.dtype(np.int32), N_V)) == 1
    assert writes.count((np.dtype(np.int32), 1)) == tape
    assert len(writes) == 1 + tape
    # the rungs as an indexed tape of 40 flows keeps them: 8 x 4 <= 40
    rungs = (2, 4)
    assert not {n, N_V, N_C, N_V * DEG, N_V - 1, K, 1} & set(rungs)
    monkeypatch.setattr(lmm_drain, "_RING_RUNGS", rungs)
    jaxpr = trace()
    writes = ring_scatters(jaxpr, n)
    assert [w for w in writes if w[0].kind == "f"] == []
    assert writes.count((np.dtype(np.int32), N_V)) == 1
    assert writes.count((np.dtype(np.int32), 1)) == tape
    for w in rungs:
        assert writes.count((np.dtype(np.int32), w)) == index
    assert len(writes) == 1 + tape + len(rungs) * index
    sides = [[ring_scatters(br.jaxpr, n) for br in eqn.params["branches"]]
             for eqn in conds(jaxpr)]
    sides = [s for s in sides if any(s)]
    i32 = np.dtype(np.int32)
    assert sides == ([[[(i32, w)] for w in rungs] + [[(i32, N_V)]]]
                     if index else [])


def _tape():
    return (np.array([0.25, 0.75]), np.array([0, 1], np.int32),
            np.array([1.5, 2.5]))


# ---------------------------------------------------------------------------
# the ring to the bit
# ---------------------------------------------------------------------------

@DTYPES
@pytest.mark.parametrize("kind", ["plain", "fault", "coll"])
def test_the_ring_is_the_one_the_writes_of_each_entry_left(routed, kind,
                                                           dtype):
    sim = make(kind, dtype, routed)
    log = drive(sim, lambda a, statics: held(a, statics, K))
    done = [d.any() for d, *_ in log]
    fired = [s is not None for _, s, *_ in log]
    act = [ac.any() for _, _, ac, *_ in log]
    # the cases the ring's writes have to meet: advances of several
    # completions, a fault fired, and in the collective advances that
    # complete flows and fire activations both
    assert any(d.sum() > 1 for d, *_ in log)
    assert any(fired) == (kind != "plain")
    assert any(d and a for d, a in zip(done, act)) == (kind == "coll")
    # and the sim that drives it finishes the same flows
    ref = make(kind, dtype, routed)
    ref.run()
    assert len(ref.events) == sum(int(d.sum()) for d, *_ in log)


def fleet_lanes(a, statics, scales):
    """The fleet program's arguments for lanes that are ``a`` with its
    links scaled by each of ``scales``, as a fleet stacks them."""
    lanes = [{**a, "c_bound": (np.asarray(a["c_bound"]) * s).astype(
        np.asarray(a["c_bound"]).dtype)} for s in scales]
    per = ("c_bound", "pen", "rem", "thresh", "tape_t", "tape_slot",
           "tape_val", "tape_pos", "coll_pred", "coll_ready", "coll_clk",
           "t0")
    stacked = {p: np.stack([np.asarray(lane[p]) for lane in lanes])
               for p in per}
    stacked["alive"] = np.ones(len(scales), bool)
    names = inspect.signature(lmm_batch._batch_superstep_program).parameters
    args = [stacked[p] if p in stacked else a[p] for p in names
            if p in stacked or p in a]
    fleet_statics = {key: statics[key] for key in
                     ("eps", "n_c", "n_v", "k_max", "group", "has_bounds",
                      "has_tape", "has_coll")}
    return lanes, args, fleet_statics


@DTYPES
@pytest.mark.parametrize("kind", ["plain", "fault", "coll"])
def test_each_lane_of_the_fleet_is_its_solo_dispatch(routed, kind, dtype):
    """The vmapped fleet (no index, no stop below zero live flows): each
    lane is the solo program's dispatch on that lane's state, packed
    ring and state to the bit, and that dispatch is the numpy
    statement's."""
    sim = make(kind, dtype, routed)
    a, statics = captured(sim)
    # what the fleet hands its lanes: neither index, no live-count stop
    a = {p: v for p, v in a.items()
         if p not in ("v_ptr", "ve_idx", "s_ptr", "s_dst")}
    a["stop_live"] = np.int32(0)
    a["k"] = np.int32(K)
    lanes, args, fleet_statics = fleet_lanes(a, statics, (1.0, 1.25))
    out = [np.asarray(o) for o in lmm_batch._batch_superstep(
        *args, **fleet_statics)]
    made = 0
    for b, lane in enumerate(lanes):
        adv, log, solo = held(lane, statics, K)
        made += adv
        for got, want in zip(out, solo):
            assert same_bits(got[b], want)
    assert made > K


# ---------------------------------------------------------------------------
# the start the one scatter rests on
# ---------------------------------------------------------------------------

def broken(how):
    """The chain with one flow started against the rule."""
    pen, (pred, ready, src, dst, cost) = chain(N_V)
    pred, ready, pen = pred.copy(), ready.copy(), pen.copy()
    if how == "live_waiting":
        pen[3] = 1.0
    elif how == "live_dated":
        ready[0] = 0.5
    else:                                 # dated_waiting
        ready[3] = 0.5
    return pen, (pred, ready, src, dst, cost)


WHY = {"live_waiting": "live with predecessors outstanding",
       "live_dated": "live with a ready date",
       "dated_waiting": "dated with predecessors outstanding"}


@pytest.mark.parametrize("how", sorted(WHY))
@pytest.mark.parametrize("fleet", [False, True], ids=["solo", "fleet"])
def test_a_start_the_ring_cannot_log_is_refused(how, fleet):
    pen, coll = broken(how)
    e_var, e_cnst, e_w, c_bound, sizes = arrays(np.float64)
    with pytest.raises(ValueError, match=WHY[how]):
        if fleet:
            BatchDrainSim(e_var, e_cnst, e_w, c_bound, sizes,
                          [ReplicaOverrides()], dtype=np.float64,
                          penalty=pen, collective=coll)
        else:
            DrainSim(e_var, e_cnst, e_w, c_bound, sizes, dtype=np.float64,
                     penalty=pen, collective=coll)
    # and the chain as the rule has it runs
    pen, coll = chain(N_V)
    DrainSim(e_var, e_cnst, e_w, c_bound, sizes, dtype=np.float64,
             penalty=pen, collective=coll)


def test_a_collective_without_penalties_is_refused():
    """Every flow live, the chain's successors too: they wait."""
    _pen, coll = chain(N_V)
    with pytest.raises(ValueError, match="flow 1\\)"):
        DrainSim(*arrays(np.float64), dtype=np.float64, collective=coll)


@pytest.mark.parametrize("op,algo", sorted(GENERATORS))
@pytest.mark.parametrize("flavor", FLAVORS)
def test_every_lowered_tape_starts_as_the_ring_needs(op, algo, flavor):
    """Roots live, or dated when a cost delays them; every other flow
    dormant, undated and waiting."""
    spec = CollectiveSpec(op, algo, 8, flavor, 4096.0)
    for cost in (None, 1e-4):
        dc = spec.build() if cost is None else spec.build(
            np.full(len(spec.build().pred0), cost))
        _check_collective_start(dc.penalty0, dc.pred0, dc.ready0)
        assert np.any(dc.penalty0 > 0) != (cost is not None)
        assert np.any(np.isfinite(dc.ready0)) == (cost is not None)
