"""Superstepped device-resident drain (ISSUE 2): relative-precision
completion grouping, K-advance supersteps with the completion ring
buffer, on-device repacks, and the engine's drain fast path.

The seeded 1k-flow FAT-TREE drain is the tier-1 anchor: the flow set is
built through the real platform/routing stack (cluster fat-tree, d-mod-k
routing), flattened once, then drained at every dispatch grouping.
The per-advance reference is K = 1: one advance a dispatch, the f64
clock summed on the host.  The acceptance contract (ISSUE 2):

  (a) f32 relative-grouping event order == the f64 oracle order,
  (b) DrainSim.syncs <= advances/K + repacks + 2 under supersteps,
  (c) an ``advance()`` loop bit-identical to ``run()`` at K = 1 on CPU.
"""

import os

import numpy as np
import pytest

from simgrid_tpu import s4u
from simgrid_tpu.ops.lmm_drain import DrainSim
from simgrid_tpu.utils.config import config

HERE = os.path.dirname(__file__)
K = 16


@pytest.fixture(autouse=True)
def fresh_engine():
    s4u.Engine._reset()
    yield
    s4u.Engine._reset()


def fat_tree_platform(tmp_path, hosts=64):
    assert hosts == 64
    xml = """<?xml version='1.0'?>
<platform version="4.1">
  <zone id="world" routing="Full">
    <cluster id="ft" prefix="node-" radical="0-63" suffix=""
             speed="1Gf" bw="125MBps" lat="50us" topology="FAT_TREE"
             topo_parameters="2;8,8;1,2;1,1"/>
  </zone>
</platform>
"""
    path = os.path.join(tmp_path, "fat_tree64.xml")
    with open(path, "w") as f:
        f.write(xml)
    return path


def build_drain_arrays(tmp_path, flows=1000, seed=3):
    """Post `flows` seeded random-pair comms on the 64-host fat tree,
    pay the latency phase, and flatten the pure-drain LMM system."""
    from simgrid_tpu.ops import lmm_jax

    e = s4u.Engine(["drain", "--cfg=lmm/backend:list",
                    "--cfg=network/maxmin-selective-update:no",
                    "--cfg=network/optim:Full",
                    "--cfg=drain/fastpath:off"])
    e.load_platform(fat_tree_platform(tmp_path))
    hosts = e.get_all_hosts()
    n_hosts = len(hosts)
    model = e.pimpl.network_model
    rng = np.random.default_rng(seed)
    pairs = rng.integers(0, n_hosts, size=(flows, 2))
    # tie-heavy sizes: completions group, keeping the drain fast while
    # still exercising ~hundreds of advances
    sizes = rng.choice(np.linspace(1e5, 2e6, 16), flows)
    actions = []
    for k in range(flows):
        src, dst = int(pairs[k, 0]), int(pairs[k, 1])
        if src == dst:
            dst = (dst + 1) % n_hosts
        actions.append(model.communicate(hosts[src], hosts[dst],
                                         float(sizes[k]), -1.0))
    for _ in range(200):
        n_live = sum(1 for a in actions
                     if a.variable is not None
                     and a.variable.sharing_penalty > 0)
        if n_live == len(actions):
            break
        e.pimpl.surf_solve(-1.0)
    arrays, vars_in_order = lmm_jax.flatten(
        list(model.system.active_constraint_set))
    var_slot = {id(a.variable): k for k, a in enumerate(actions)}
    slot_flow = np.array([var_slot[id(v)] for v in vars_in_order])
    order = np.argsort(slot_flow)
    # re-use remains (some latency-phase drain may have nibbled sizes)
    rem = np.array([actions[int(f)].get_remains_no_update()
                    for f in slot_flow])
    return arrays, rem, slot_flow


def make_sim(arrays, sizes, dtype, eps, **kw):
    E = arrays.n_elem
    return DrainSim(arrays.e_var[:E], arrays.e_cnst[:E],
                    arrays.e_w[:E].astype(dtype),
                    arrays.c_bound[:arrays.n_cnst].astype(dtype),
                    sizes, eps=eps, dtype=dtype, repack_min=64, **kw)


@pytest.fixture(scope="module")
def fat_tree_drain(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("ft"))
    s4u.Engine._reset()
    try:
        return build_drain_arrays(tmp)
    finally:
        s4u.Engine._reset()


@pytest.fixture(scope="module")
def drained(fat_tree_drain):
    """Every dispatch grouping drained ONCE over the same seeded
    system; the parity tests below share these (each drain costs
    hundreds of dispatches — the tier-1 suite is wall-clock-bound).
    ``u64`` is driven one ``advance()`` at a time, the rest by
    ``run()``."""
    arrays, sizes, _ = fat_tree_drain
    sims = {}
    for label, dtype, eps, kw in (
            ("u64", np.float64, 1e-9, dict(superstep=1)),
            ("k64", np.float64, 1e-9, dict(superstep=1)),
            ("s64", np.float64, 1e-9, dict(superstep=K)),
            ("k32", np.float32, 1e-5, dict(superstep=1)),
            ("s32", np.float32, 1e-5, dict(superstep=K))):
        sim = make_sim(arrays, sizes, dtype, eps, **kw)
        if label == "u64":
            while sim.advance():
                pass
        else:
            sim.run()
        sims[label] = sim
    return sims


class TestFatTreeDrainParity:
    """ISSUE 2 acceptance: identical completion-event order across
    {f64 K=1, f32 K=1, f32 superstep K=16} on the seeded 1k-flow
    fat-tree drain, and syncs-per-advance < 0.2 under supersteps."""

    def test_order_and_sync_budget(self, fat_tree_drain, drained):
        arrays, _, _ = fat_tree_drain
        s64, f32_k1, f32_ss = (drained["u64"], drained["k32"],
                               drained["s32"])
        assert len(s64.events) == arrays.n_var
        order64 = [f for _, f in s64.events]
        assert [f for _, f in f32_k1.events] == order64
        # K = 1: 1 dispatch+fetch per advance (modulo rare rescues)
        assert f32_k1.syncs <= f32_k1.advances + f32_k1.repacks + 2
        assert [f for _, f in f32_ss.events] == order64
        # (b) the superstep sync budget: ~1/K syncs per advance
        assert f32_ss.syncs <= f32_ss.advances / K + f32_ss.repacks + 2
        assert f32_ss.syncs / f32_ss.advances < 0.2
        # same advance structure as the f64 oracle (the tie-group
        # contract that broke the round-5 TPU drain)
        assert f32_ss.advances == s64.advances

    def test_advance_loop_bit_identical_to_run_k1(self, drained):
        """(c) ``advance()`` is the K = 1 dispatch ``run()`` issues:
        the event stream (times AND ids), the clock and the dispatch
        census must match bit-for-bit."""
        a, b = drained["u64"], drained["k64"]
        assert a.events == b.events
        assert a.t == b.t
        assert (a.advances, a.supersteps, a.syncs, a.repacks) == \
            (b.advances, b.supersteps, b.syncs, b.repacks)

    def test_superstep_f64_matches_k1_order(self, drained):
        a, b = drained["u64"], drained["s64"]
        assert [f for _, f in a.events] == [f for _, f in b.events]
        # the superstep clock is Kahan-compensated per dispatch and
        # f64 host-accumulated across dispatches: timestamps stay tight
        for (ta, _), (tb, _) in zip(a.events, b.events):
            assert tb == pytest.approx(ta, rel=1e-9, abs=1e-9)


class TestRelativeGrouping:
    def test_equal_flows_one_tie_group(self):
        """Uniform flows at uniform rates retire in ONE advance on
        every backend/mode — the grouping the alltoall drain needs
        (f32 absolute-epsilon completion split these groups, the
        diagnosed round-5 TPU blocker)."""
        n = 1000
        idx = np.arange(n, dtype=np.int32)
        e_w = np.ones(n)
        c_bound = np.full(n, 1e6)
        sizes = np.full(n, 1e6)
        for dtype, eps, kw in ((np.float64, 1e-9, dict(superstep=1)),
                               (np.float32, 1e-5, dict(superstep=1)),
                               (np.float32, 1e-5, dict(superstep=K))):
            sim = DrainSim(idx, idx, e_w.astype(dtype),
                           c_bound.astype(dtype), sizes, eps=eps,
                           dtype=dtype, **kw)
            sim.run()
            assert len(sim.events) == n
            assert sim.advances == 1

    def test_absolute_mode_still_available(self):
        from bench import build_arrays
        rng = np.random.default_rng(11)
        arrays = build_arrays(rng, 64, 300, 2, np.float64)
        sizes = rng.uniform(1e5, 2e6, 300)
        rel = make_sim(arrays, sizes, np.float64, 1e-9, superstep=1)
        rel.run()
        ab = make_sim(arrays, sizes, np.float64, 1e-9, done_mode="abs",
                      superstep=1)
        ab.run()
        assert len(ab.events) == 300
        # relative grouping only merges near-ties: per-flow completion
        # times agree to the relative threshold
        t_rel = {f: t for t, f in rel.events}
        for t, f in ab.events:
            assert t_rel[f] == pytest.approx(t, rel=2e-4)
        # grouping can only coarsen: rel never needs more advances
        assert rel.advances <= ab.advances


class TestSuperstepSaturation:
    """ISSUE 4 satellite: the superstep's two partial-batch exits —
    the round budget expiring mid-superstep (_FLAG_BUDGET) and the
    completion ring filling to capacity in one dispatch — must both
    replay to the exact per-advance (K = 1) event order."""

    @staticmethod
    def _chain_system(groups=6, per=40):
        """`groups` staggered tie-groups over one shared backbone plus
        per-group links: every advance retires a whole group, so a
        superstep with k >= groups drains EVERYTHING in one dispatch
        (ring filled to capacity), and the backbone's saturation chain
        keeps each solve multi-round (budget pressure)."""
        n_v = groups * per
        e_var, e_cnst, e_w = [], [], []
        for g in range(groups):
            for j in range(per):
                v = g * per + j
                e_var += [v, v]
                e_cnst += [0, 1 + g]          # backbone + group link
                e_w += [1.0, 1.0]
        c_bound = np.array([1e6 * groups] + [1e6] * groups)
        # group g completes at its own distinct time: one tie group
        # per advance, `groups` advances total
        sizes = np.repeat(1e6 * (1.0 + np.arange(groups)), per)
        return (np.array(e_var, np.int32), np.array(e_cnst, np.int32),
                np.array(e_w), c_bound, sizes, n_v)

    def test_ring_at_capacity_single_superstep(self):
        ev, ec, ew, cb, sizes, n_v = self._chain_system()
        ref = DrainSim(ev, ec, ew, cb, sizes, eps=1e-9,
                       dtype=np.float64, superstep=1,
                       repack_min=1 << 62)
        ref.run()
        sim = DrainSim(ev, ec, ew, cb, sizes, eps=1e-9,
                       dtype=np.float64, superstep=K,
                       repack_min=1 << 62)
        sim.run()
        # every flow's completion landed in ONE superstep: the ring
        # held n_v events — its full capacity
        assert sim.supersteps == 1
        assert len(sim.events) == n_v
        assert sim.events == ref.events       # bit-identical, not ~=

    def test_budget_exhaustion_partial_batches_replay_exactly(self):
        """A tiny per-dispatch round budget forces _FLAG_BUDGET exits
        inside (and between) advances: the partial-batch handling —
        committing only completed advances, then finishing one advance
        via the full-budget K = 1 rescue — must reproduce the
        per-advance event stream bit-for-bit."""
        ev, ec, ew, cb, sizes, n_v = self._chain_system()
        ref = DrainSim(ev, ec, ew, cb, sizes, eps=1e-9,
                       dtype=np.float64, superstep=1,
                       repack_min=1 << 62)
        ref.run()
        sim = DrainSim(ev, ec, ew, cb, sizes, eps=1e-9,
                       dtype=np.float64, superstep=K,
                       superstep_rounds=3, repack_min=1 << 62)
        sim.run()
        # the budget really bit: more supersteps than the unconstrained
        # path's single dispatch
        assert sim.supersteps > 1
        assert sim.events == ref.events
        assert sim.t == ref.t

    def test_budget_batch_fleet_matches_k1(self):
        """The BATCHED executor under the same budget pressure: every
        replica's partial-batch rescue replays to its own solo K = 1
        order (the fleet-level mirror of the test above)."""
        from simgrid_tpu.parallel.campaign import Campaign, ScenarioSpec

        ev, ec, ew, cb, sizes, n_v = self._chain_system(groups=4, per=24)
        specs = [ScenarioSpec(seed=s, bw_scale=1.0 + 0.25 * s)
                 for s in range(3)]
        camp = Campaign(ev, ec, ew, cb, sizes, specs, eps=1e-9,
                        dtype=np.float64, superstep=K)
        results = camp.run_batched(batch=3, superstep_rounds=3)
        for b, spec in enumerate(specs):
            scb = cb * spec.bw_scale
            ref = DrainSim(ev, ec, ew, scb, sizes, eps=1e-9,
                           dtype=np.float64, superstep=1,
                           repack_min=1 << 62)
            ref.run()
            assert results[b].events == ref.events
            assert results[b].t == ref.t


class TestOneDrainProgram:
    """The drain has one device program, the superstep: ``advance()``
    and the budget rescue are K = 1 dispatches of it."""

    def test_advance_is_one_dispatch_and_one_fetch(self):
        from simgrid_tpu.ops import opstats

        ev, ec, ew, cb, sizes, n_v = \
            TestSuperstepSaturation._chain_system()
        sim = DrainSim(ev, ec, ew, cb, sizes, eps=1e-9,
                       dtype=np.float64, repack_min=1 << 62)
        before = opstats.snapshot()
        n = sim.advance()
        d = opstats.diff(before)
        assert (sim.supersteps, sim.syncs, sim.advances) == (1, 1, 1)
        assert d.get("dispatches", 0) == 1 and d.get("fetches", 0) == 1
        # one tie group of 40 retired; the live count comes back
        assert n == n_v - 40 == n_v - len(sim.events)
        while n:
            n = sim.advance()
        assert sim.supersteps == sim.syncs == sim.advances == 6

    def test_starved_budget_on_a_plain_sim_is_rescued_by_k1(self):
        """No tape, no collective: a budget that expires inside the
        first solve is finished by a full-budget K = 1 superstep, which
        reports the element rows its rounds worked like any other."""
        from bench import build_arrays
        from simgrid_tpu.ops import opstats

        rng = np.random.default_rng(11)
        arrays = build_arrays(rng, 64, 300, 2, np.float64)
        sizes = rng.uniform(1e5, 2e6, 300)
        ref = make_sim(arrays, sizes, np.float64, 1e-9, superstep=1)
        ref.run()
        sim = make_sim(arrays, sizes, np.float64, 1e-9, superstep=K,
                       superstep_rounds=2)
        rescued, n = 0, sim.n_v
        while n:
            before, s0, a0 = opstats.snapshot(), sim.supersteps, \
                sim.advances
            elems = sim._dev[0].size
            n = sim.advance()
            d = opstats.diff(before)
            assert sim.advances == a0 + 1
            if sim.supersteps == s0 + 2:
                # the starved K = 1 dispatch, then its rescue: both
                # report every element row their rounds indexed (one
                # rung at this size)
                rescued += 1
                assert d["dispatches"] == d["fetches"] == 2
                assert d["fixpoint_rounds"] > 2
                assert d["fixpoint_worked_elem_rounds"] == \
                    d["fixpoint_rounds"] * elems
        assert rescued > 0
        assert sim.events == ref.events and sim.t == ref.t

    def test_superstep_below_one_refused_by_name(self, tmp_path):
        ev, ec, ew, cb, sizes, _ = \
            TestSuperstepSaturation._chain_system()
        with pytest.raises(ValueError, match=r"superstep=0\b"):
            DrainSim(ev, ec, ew, cb, sizes, superstep=0)
        with pytest.raises(ValueError, match="drain/superstep:0"):
            _run_engine_drain(
                str(tmp_path),
                ["lmm/backend:jax", "network/optim:Full",
                 "network/maxmin-selective-update:no",
                 "drain/fastpath:auto", "drain/min-flows:64",
                 "drain/superstep:0"])


class TestRetraceSentinel:
    def test_steady_state_superstep_does_not_retrace(self):
        """The ``opstats.retraces`` sentinel (simlint PR): the superstep
        program bodies bump it at TRACE time only, so a repeat drain of
        an identically-shaped system must re-enter the jit cache and
        leave the counter flat.  A nonzero delta here means shape or
        static churn is busting the cache on the steady-state path."""
        from simgrid_tpu.ops import opstats

        ev, ec, ew, cb, sizes, n_v = \
            TestSuperstepSaturation._chain_system()

        def drain():
            sim = DrainSim(ev, ec, ew, cb, sizes, eps=1e-9,
                           dtype=np.float64, superstep=K,
                           repack_min=1 << 62)
            sim.run()
            return sim

        first = drain()
        assert len(first.events) == n_v
        # the programs really carry the sentinel: the cumulative counter
        # is nonzero once any superstep program has ever been traced
        assert opstats.snapshot().get("retraces", 0) > 0
        before = opstats.snapshot()
        second = drain()
        assert second.events == first.events
        assert opstats.diff(before).get("retraces", 0) == 0


class TestClockAccumulation:
    def test_host_clock_is_f64(self, drained):
        """The master clock accumulates per-advance dts in f64 on the
        host even when the device dtype is f32 (satellite: no
        timestamp drift between backends)."""
        s64, s32 = drained["u64"], drained["s32"]
        assert isinstance(s32.t, float)
        # end-of-drain clocks agree to f32 relative precision bounds,
        # NOT f32-accumulation bounds (which would be ~30x looser at
        # ~1.5k advances)
        assert s32.t == pytest.approx(s64.t, rel=5e-5)


def _run_engine_drain(tmp_path, cfg, flows=300, seed=5, bound_step=0.0):
    """Drive the real model layer (communicate + surf_solve + done-
    action extraction, the maestro's loop) to a full drain; returns the
    completion event stream [(finish_time, flow_idx)] and the model."""
    e = s4u.Engine(["engine-drain"] + [f"--cfg={c}" for c in cfg])
    e.load_platform(fat_tree_platform(tmp_path))
    hosts = e.get_all_hosts()
    n_hosts = len(hosts)
    model = e.pimpl.network_model
    rng = np.random.default_rng(seed)
    pairs = rng.integers(0, n_hosts, size=(flows, 2))
    sizes = rng.choice(np.linspace(1e5, 2e6, 12), flows)
    actions = []
    for k in range(flows):
        src, dst = int(pairs[k, 0]), int(pairs[k, 1])
        if src == dst:
            dst = (dst + 1) % n_hosts
        a = model.communicate(hosts[src], hosts[dst],
                              float(sizes[k]), -1.0)
        a.drain_idx = k
        actions.append(a)
    events = []
    for _ in range(100_000):
        # reap completions exactly like the kernel activity layer
        while True:
            done = model.extract_done_action()
            if done is None:
                break
            events.append((done.finish_time, done.drain_idx))
            done.unref()
        if not len(model.started_action_set):
            break
        # bound_step forces run-until-style partial advances: the fast
        # path must roll back deterministically and hand the partial
        # delta to the generic loop
        max_date = e.pimpl.now + bound_step if bound_step else -1.0
        if e.pimpl.surf_solve(max_date) < 0 and not bound_step:
            break
    while True:
        done = model.extract_done_action()
        if done is None:
            break
        events.append((done.finish_time, done.drain_idx))
        done.unref()
    return events, model


class TestEngineFastPath:
    """The drain fast path serves batches of advances from the
    superstep executor with event ordering identical to the generic
    per-advance path."""

    def test_event_parity_and_batching(self, tmp_path):
        base = ["lmm/backend:jax", "network/maxmin-selective-update:no",
                "network/optim:Full"]
        ev_off, m_off = _run_engine_drain(
            str(tmp_path), base + ["drain/fastpath:off"])
        s4u.Engine._reset()
        ev_on, m_on = _run_engine_drain(
            str(tmp_path), base + ["drain/fastpath:auto",
                                   "drain/min-flows:64",
                                   f"drain/superstep:{K}"])
        fp = m_on.drain_fastpath
        assert fp.plans >= 1
        assert fp.advances_served > 0
        assert [f for _, f in ev_on] == [f for _, f in ev_off]
        for (ta, _), (tb, _) in zip(ev_off, ev_on):
            assert tb == pytest.approx(ta, rel=1e-9, abs=1e-12)

    def test_partial_advance_rollback(self, tmp_path):
        """A run-until bound mid-drain forces partial advances: the
        plan rolls back by replay, writes remains/rates back, and the
        generic loop finishes the step — event parity must hold."""
        base = ["lmm/backend:jax", "network/maxmin-selective-update:no",
                "network/optim:Full"]
        step = 0.002
        ev_off, _ = _run_engine_drain(
            str(tmp_path), base + ["drain/fastpath:off"],
            flows=150, bound_step=step)
        s4u.Engine._reset()
        ev_on, m_on = _run_engine_drain(
            str(tmp_path), base + ["drain/fastpath:auto",
                                   "drain/min-flows:32",
                                   f"drain/superstep:{K}"],
            flows=150, bound_step=step)
        fp = m_on.drain_fastpath
        assert fp.advances_served > 0
        assert fp.rollbacks > 0       # the bound really interrupted plans
        assert [f for _, f in ev_on] == [f for _, f in ev_off]
        for (ta, _), (tb, _) in zip(ev_off, ev_on):
            assert tb == pytest.approx(ta, rel=1e-9, abs=1e-12)

    def test_fastpath_off_by_scale(self, tmp_path):
        """Default drain/min-flows keeps the fast path out of small
        simulations entirely."""
        base = ["lmm/backend:jax", "network/maxmin-selective-update:no",
                "network/optim:Full"]
        _, model = _run_engine_drain(str(tmp_path), base, flows=40)
        assert model.drain_fastpath.plans == 0


class TestLatencyCensus:
    def test_counter_lifecycle(self, tmp_path):
        """The latency-phase counter reaches zero once every flow is
        past its latency (enabling the O(V)-walk skip) and stays
        consistent through completions."""
        e = s4u.Engine(["census", "--cfg=network/optim:Full",
                        "--cfg=network/maxmin-selective-update:no"])
        e.load_platform(fat_tree_platform(str(tmp_path)))
        hosts = e.get_all_hosts()
        model = e.pimpl.network_model
        acts = [model.communicate(hosts[0], hosts[i + 1], 1e5, -1.0)
                for i in range(8)]
        assert model.latency_phase_count == len(acts)
        for _ in range(1000):
            if not len(model.started_action_set):
                break
            e.pimpl.surf_solve(-1.0)
            while model.extract_done_action() is not None:
                pass
        assert model.latency_phase_count == 0
