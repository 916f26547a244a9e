"""Device-resident fault event tapes (ISSUE 10): seeded link failure
schedules compiled into per-lane ``(date, slot, bound)`` tapes that the
superstep drain consults between advances — mid-drain capacity flips,
bit-identical to driving the same seeded schedule through engine-side
Profiles, composing with batching, speculation and mesh sharding."""

import os

import numpy as np
import pytest

from simgrid_tpu import s4u
from simgrid_tpu.faults import FaultCampaign
from simgrid_tpu.ops import opstats
from simgrid_tpu.ops.lmm_drain import DrainSim
from simgrid_tpu.parallel.campaign import (Campaign, MIN_LINK_FACTOR,
                                           ScenarioSpec)


@pytest.fixture(autouse=True)
def fresh_engine():
    s4u.Engine._reset()
    yield
    s4u.Engine._reset()


# ---------------------------------------------------------------------------
# compile_tape: the schedule-to-tape projection
# ---------------------------------------------------------------------------

def _two_link_campaign(seed=5):
    fc = FaultCampaign(seed=seed, horizon=60.0)
    fc.add_link("wire", mtbf=5.0, mttr=3.0, dist="fixed")
    fc.add_link("wire2", mtbf=13.0, mttr=4.0, dist="fixed")
    return fc


def test_compile_tape_matches_generate_bitwise():
    fc = _two_link_campaign()
    tape = fc.compile_tape(floor=0.5)
    sched = sorted((date, kind, name, 1.0 if value > 0 else 0.5)
                   for (kind, name), pts in fc.generate().items()
                   for date, value in pts)
    assert tape == sched
    # repeatable projection: same campaign, same tape, bitwise
    assert fc.compile_tape(floor=0.5) == tape
    # and a fresh same-seed campaign draws the identical schedule
    assert _two_link_campaign().compile_tape(floor=0.5) == tape
    dates = [d for d, _, _, _ in tape]
    assert dates == sorted(dates)


def test_compile_tape_rejects_bad_floor():
    fc = _two_link_campaign()
    for floor in (0.0, -0.5, 1.5):
        with pytest.raises(ValueError):
            fc.compile_tape(floor=floor)


def test_fork_gives_a_schedulable_same_spec_campaign(tmp_path):
    fc = FaultCampaign(seed=9, horizon=200.0)
    fc.add_link("wire", mtbf=20.0, mttr=5.0)       # exponential draws
    fc._scheduled = True                   # as if schedule() had run
    child = fc.fork()
    assert child.compile_tape(floor=0.5) == fc.compile_tape(floor=0.5)
    assert not child._scheduled            # fork resets the one-shot
    shifted = fc.fork(seed_offset=1)
    assert shifted.compile_tape(0.5) != fc.compile_tape(0.5)


# ---------------------------------------------------------------------------
# DrainSim tape kernel: fires, determinism, API contract
# ---------------------------------------------------------------------------

def _hand_sim(tape, **kw):
    """2 independent flows, one per constraint, f64: rate == bound."""
    return DrainSim(np.array([0, 1], np.int32), np.array([0, 1], np.int32),
                    np.ones(2), np.array([1e6, 1e6]),
                    np.array([8e6, 1.4e7]), eps=1e-9, dtype=np.float64,
                    superstep=kw.pop("superstep", 1), tape=tape, **kw)


_HAND_TAPE = (np.array([5.0, 8.0, 13.0, 17.0]),
              np.array([0, 0, 1, 1], np.int32),
              np.array([5e5, 1e6, 5e5, 1e6]))


def test_tape_fires_at_exact_dates_and_clamps_dt():
    sim = _hand_sim(_HAND_TAPE)
    sim.run()
    # hand-computed: flow0 5s@1e6 + 3s@5e5 + 1.5s@1e6 -> 9.5;
    # flow1 13s@1e6 + 2s@5e5 -> 15.0 (repair at 17 never fires)
    assert sim.events == [(9.5, 0), (15.0, 1)]
    assert sim.t == 15.0
    assert sim.fault_events == [(5.0, 0), (8.0, 0), (13.0, 1)]
    # bit-reproducible
    sim2 = _hand_sim(_HAND_TAPE)
    sim2.run()
    assert (sim2.events, sim2.t, sim2.fault_events) \
        == (sim.events, sim.t, sim.fault_events)


def test_superstep_below_one_refused_by_name():
    for k in (0, -1):
        with pytest.raises(ValueError, match=rf"superstep={k}\b"):
            _hand_sim(_HAND_TAPE, superstep=k)


def test_tape_validates_slots_and_order():
    bad_slot = (np.array([1.0]), np.array([7], np.int32),
                np.array([5e5]))
    with pytest.raises(ValueError):
        _hand_sim(bad_slot)
    unsorted = (np.array([8.0, 5.0]), np.array([0, 0], np.int32),
                np.array([5e5, 1e6]))
    with pytest.raises(ValueError):
        _hand_sim(unsorted)


def test_tape_counters_are_bumped():
    before = opstats.snapshot()
    sim = _hand_sim(_HAND_TAPE)
    sim.run()
    d = opstats.diff(before)
    assert d.get("fault_tape_slots") == 4
    assert d.get("fault_tape_events") == 3


def test_tape_composes_with_pipeline():
    ref = _hand_sim(_HAND_TAPE, superstep=2)
    ref.run()
    piped = _hand_sim(_HAND_TAPE, superstep=2, pipeline=2)
    piped.run()
    assert (piped.events, piped.t, piped.fault_events) \
        == (ref.events, ref.t, ref.fault_events)
    assert piped.spec_rolled_back > 0, \
        "a fire must discard the in-flight speculative superstep"


# ---------------------------------------------------------------------------
# Campaign fleets: batched == solo, static mode, mesh sharding
# ---------------------------------------------------------------------------

def _fleet(n_c=10, n_v=20, seed=3, **kw):
    rng = np.random.default_rng(seed)
    e_var = np.repeat(np.arange(n_v), 2).astype(np.int32)
    e_cnst = rng.integers(0, n_c, size=2 * n_v).astype(np.int32)
    c_bound = rng.uniform(50.0, 150.0, n_c)
    sizes = rng.uniform(100.0, 900.0, n_v)
    specs = [ScenarioSpec(seed=s, fault_mtbf=(40.0 if s % 3 else None),
                          fault_mttr=15.0, fault_horizon=300.0)
             for s in range(5)]
    return Campaign(e_var, e_cnst, np.ones(2 * n_v), c_bound, sizes,
                    specs, superstep=4, **kw)


def test_fleet_tape_lanes_bit_identical_to_solo():
    camp = _fleet(fault_mode="on")
    fleet = camp.run_batched(batch=5)
    fired = 0
    for j, got in enumerate(fleet):
        solo = camp.run_solo(j)
        assert got.error is None and solo.error is None
        assert got.events == solo.events
        assert got.t == solo.t
        assert got.fault_events == solo.fault_events
        fired += len(got.fault_events)
        if camp.specs[j].fault_mtbf is None:
            assert got.fault_events == []
    assert fired > 0, "no tape event ever fired (nothing tested)"


def test_fleet_tape_composes_with_pipeline_and_mesh():
    camp = _fleet(fault_mode="on")
    ref = camp.run_batched(batch=5)
    for kw in (dict(pipeline=2), dict(mesh=2),
               dict(mesh=2, pipeline=2)):
        got = camp.run_batched(batch=5, **kw)
        for a, b in zip(got, ref):
            assert a.events == b.events
            assert a.t == b.t
            assert a.fault_events == b.fault_events


def test_static_mode_reproduces_mean_availability_folding():
    camp = _fleet(fault_mode="static")
    for spec in camp.specs:
        ov = camp.overrides_for(spec)
        if spec.fault_mtbf is None:
            assert ov.link_scale == {}
            continue
        fc, names = camp._fault_campaign(spec)
        for (kind, name), avail in fc.mean_availability().items():
            slot = names[name]
            if avail >= 1.0:
                assert slot not in ov.link_scale
            else:
                assert ov.link_scale[slot] \
                    == max(avail, MIN_LINK_FACTOR)
    # and static fleets never compile tapes or fire events
    for rep in camp.run_batched(batch=5):
        assert rep.fault_events == []


def test_off_mode_ignores_the_fault_dimension():
    camp = _fleet(fault_mode="off")
    assert all(camp.tape_for(s) is None for s in camp.specs)
    assert all(camp.overrides_for(s).link_scale == {}
               for s in camp.specs)


def test_campaign_rejects_unknown_fault_mode():
    with pytest.raises(ValueError, match="fault_mode"):
        _fleet(fault_mode="sometimes")


# ---------------------------------------------------------------------------
# The standing invariant: tape == engine-side Profile injection
# ---------------------------------------------------------------------------

_PLATFORM = """<?xml version='1.0'?>
<platform version="4.1">
  <zone id="world" routing="Full">
    <host id="alpha" speed="100Mf"/>
    <host id="beta" speed="100Mf"/>
    <host id="gamma" speed="100Mf"/>
    <link id="wire" bandwidth="1MBps" latency="0"/>
    <link id="wire2" bandwidth="1MBps" latency="0"/>
    <route src="alpha" dst="beta"><link_ctn id="wire"/></route>
    <route src="alpha" dst="gamma"><link_ctn id="wire2"/></route>
  </zone>
</platform>
"""


def test_tape_drain_equals_engine_profile_injection(tmp_path):
    """Replica-with-tape == solo engine driving the same seeded
    schedule through bandwidth Profiles (FaultCampaign.
    schedule_degrade): every completion lands at the EXACT same date.
    Exact-arithmetic setup: bandwidth-factor 1.0, floor 0.5 (a power
    of two), one flow per link so rate == bound, fixed-dist dates —
    every intermediate is exactly representable, so == is fair."""
    path = os.path.join(tmp_path, "tape.xml")
    with open(path, "w") as f:
        f.write(_PLATFORM)
    e = s4u.Engine(["tape", "--cfg=network/crosstraffic:0",
                    "--cfg=network/bandwidth-factor:1.0"])
    e.load_platform(path)

    finish = {}

    def sender(mb, size):
        mb.put("x", size)

    def receiver(mb, key):
        mb.get()
        finish[key] = s4u.Engine.get_clock()

    mb1, mb2 = s4u.Mailbox.by_name("f0"), s4u.Mailbox.by_name("f1")
    s4u.Actor.create("s0", e.host_by_name("alpha"), sender, mb1, 8e6)
    s4u.Actor.create("r0", e.host_by_name("beta"), receiver, mb1, 0)
    s4u.Actor.create("s1", e.host_by_name("alpha"), sender, mb2, 1.4e7)
    s4u.Actor.create("r1", e.host_by_name("gamma"), receiver, mb2, 1)

    engine_tape = _two_link_campaign().schedule_degrade(e, floor=0.5)
    e.run()
    assert finish == {0: 9.5, 1: 15.0}     # exact, hand-computed

    # the device side: same schedule compiled against the same bounds
    names = {"wire": 0, "wire2": 1}
    entries = _two_link_campaign().compile_tape(floor=0.5)
    assert entries == engine_tape          # one-shot guard aside, same
    tape = (np.array([d for d, _, _, _ in entries]),
            np.array([names[n] for _, _, n, _ in entries], np.int32),
            np.array([1e6 * f for _, _, _, f in entries]))
    sim = _hand_sim(tape)
    sim.run()
    assert sim.events == [(9.5, 0), (15.0, 1)]
    assert [t for t, _ in sim.events] == [finish[0], finish[1]]
    # fires up to the final completion: wire fails again at 13 (its
    # fixed 5s/3s cycle), one iteration before wire2's first failure
    assert sim.fault_events == [(5.0, 0), (8.0, 0), (13.0, 0),
                                (13.0, 1)]
