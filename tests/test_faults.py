"""Fault-injection subsystem tests: deterministic campaigns, the
programmatic injector, retry policies, failure-cause disambiguation in
comm post paths, the fault_stats plugin, and LMM solver graceful
degradation (ISSUE 1)."""

import math
import os

import numpy as np
import pytest

from simgrid_tpu import s4u
from simgrid_tpu.exceptions import (HostFailureException,
                                    NetworkFailureException,
                                    TimeoutException)
from simgrid_tpu.faults import FaultCampaign, Injector
from simgrid_tpu.models.host import Host
from simgrid_tpu.models.network import LinkImpl
from simgrid_tpu.ops import make_new_maxmin_system, lmm_jax, opstats
from simgrid_tpu.parallel.campaign import (Campaign, MIN_LINK_FACTOR,
                                           ScenarioSpec)
from simgrid_tpu.plugins import fault_stats
from simgrid_tpu.utils.config import config


@pytest.fixture(autouse=True)
def fresh_engine():
    s4u.Engine._reset()
    yield
    s4u.Engine._reset()


PLATFORM = """<?xml version='1.0'?>
<platform version="4.1">
  <zone id="world" routing="Full">
    <host id="alpha" speed="100Mf"/>
    <host id="beta" speed="100Mf"/>
    <host id="gamma" speed="100Mf"/>
    <link id="wire" bandwidth="1MBps" latency="0"/>
    <link id="wire2" bandwidth="1MBps" latency="0"/>
    <route src="alpha" dst="beta"><link_ctn id="wire"/></route>
    <route src="alpha" dst="gamma"><link_ctn id="wire2"/></route>
    <route src="beta" dst="gamma"><link_ctn id="wire2"/></route>
  </zone>
</platform>
"""


def _platform(tmp_path):
    path = os.path.join(tmp_path, "faults.xml")
    with open(path, "w") as f:
        f.write(PLATFORM)
    return path


def _engine(tmp_path, *cfg):
    e = s4u.Engine(["faults", "--cfg=network/crosstraffic:0", *cfg])
    e.load_platform(_platform(tmp_path))
    return e


# ---------------------------------------------------------------------------
# FaultCampaign: generation + end-to-end determinism
# ---------------------------------------------------------------------------

def _campaign(seed):
    c = FaultCampaign(seed=seed, horizon=100.0)
    c.add_host("beta", mtbf=10.0, mttr=3.0)
    c.add_link("wire", mtbf=25.0, mttr=5.0, dist="weibull", shape=1.5)
    c.add_host("gamma", mtbf=40.0, mttr=4.0, dist="fixed")
    return c


def test_campaign_generation_is_seed_deterministic():
    a = _campaign(7).generate()
    b = _campaign(7).generate()
    assert a == b                       # bit-identical, not just approx
    c = _campaign(8).generate()
    assert a != c
    # sanity on shape: alternating fail(0)/recover(1), sorted dates
    for points in a.values():
        dates = [d for d, _ in points]
        assert dates == sorted(dates)
        assert [v for _, v in points] == [i % 2 for i in range(len(points))]
    # fixed dist: failure every 40s, repair 4s later, within horizon 100
    assert a[("host", "gamma")] == [(40.0, 0.0), (44.0, 1.0), (84.0, 0.0),
                                    (88.0, 1.0)]


def test_campaign_rejects_bad_specs():
    c = FaultCampaign(seed=1, horizon=10.0)
    with pytest.raises(ValueError):
        c.add_host("x", mtbf=0.0, mttr=1.0)
    with pytest.raises(ValueError):
        c.add_host("x", mtbf=1.0, mttr=1.0, dist="uniform")
    with pytest.raises(ValueError):
        FaultCampaign(seed=1, horizon=-1.0)


def _run_campaign_trace(tmp_path, seed):
    """One simulated run under a seeded campaign; returns the
    (date, kind, name, is_on) state-change trace and the final clock."""
    e = _engine(tmp_path)
    trace = []

    def on_host(host, *a):
        trace.append((e.pimpl.now, "host", host.name, host.is_on()))

    def on_link(link, *a):
        trace.append((e.pimpl.now, "link", link.name, link.is_on()))
    e.pimpl.connect_signal(Host.on_state_change, on_host)
    e.pimpl.connect_signal(LinkImpl.on_state_change, on_link)

    campaign = _campaign(seed)
    campaign.schedule(e)

    def sleeper():
        s4u.this_actor.sleep_for(120.0)
    s4u.Actor.create("sleeper", e.host_by_name("alpha"), sleeper)
    e.run()
    return trace, e.clock


def test_campaign_two_runs_bit_identical(tmp_path):
    trace1, clock1 = _run_campaign_trace(tmp_path, seed=42)
    s4u.Engine._reset()
    trace2, clock2 = _run_campaign_trace(tmp_path, seed=42)
    assert trace1 == trace2             # identical event traces
    assert clock1 == clock2             # identical final clocks
    assert trace1, "campaign injected no events at all"
    # and the trace is exactly the generated schedule
    expected = []
    for (kind, name), points in _campaign(42).generate().items():
        for date, value in points:
            expected.append((date, kind, name, bool(value)))
    expected.sort()
    assert sorted(trace1) == expected


def test_campaign_schedules_only_once(tmp_path):
    e = _engine(tmp_path)
    campaign = _campaign(3)
    campaign.schedule(e)
    with pytest.raises(RuntimeError):
        campaign.schedule(e)


def test_mean_availability_clamps_only_in_campaign_folding():
    # a link down for essentially the whole horizon: fails at t=1 and
    # its 1000 s repair never lands, so availability is 1/100 — far
    # below MIN_LINK_FACTOR.  mean_availability() reports the raw
    # fraction (never exactly zero: the first failure date is > 0);
    # the static fleet folding is what clamps it to the floor.
    fc = FaultCampaign(seed=11, horizon=100.0)
    fc.add_link("wire", mtbf=1.0, mttr=1000.0, dist="fixed")
    avail = fc.mean_availability()[("link", "wire")]
    assert avail == pytest.approx(0.01)
    assert 0.0 < avail < MIN_LINK_FACTOR

    specs = [ScenarioSpec(seed=0, fault_mtbf=1.0, fault_mttr=1000.0,
                          fault_horizon=100.0, fault_dist="fixed")]
    camp = Campaign(np.array([0, 1], np.int32),
                    np.array([0, 1], np.int32), np.ones(2),
                    np.array([1e6, 1e6]), np.array([8e6, 1.4e7]),
                    specs, superstep=1, fault_mode="static")
    ov = camp.overrides_for(specs[0])
    assert ov.link_scale, "static folding produced no link scales"
    assert all(v == MIN_LINK_FACTOR for v in ov.link_scale.values())


def test_mean_availability_default_horizon_matches_explicit():
    fc = _campaign(7)
    assert fc.mean_availability() == fc.mean_availability(horizon=100.0)
    assert fc.mean_availability(horizon=50.0) != fc.mean_availability()
    with pytest.raises(ValueError):
        fc.mean_availability(horizon=0.0)


# ---------------------------------------------------------------------------
# End-to-end lifecycle: kill mid-Exec, auto-restart reboot, watched hosts
# ---------------------------------------------------------------------------

def test_campaign_kills_mid_exec_and_autorestart_reruns(tmp_path):
    e = _engine(tmp_path)
    stats = fault_stats.fault_stats_plugin_init(e)
    state = {"starts": 0, "done": [], "watched": {}}

    # fixed dist: beta fails at t=5, recovers at t=8
    campaign = FaultCampaign(seed=0, horizon=10.0)
    campaign.add_host("beta", mtbf=5.0, mttr=3.0, dist="fixed")
    campaign.schedule(e)

    def worker():
        state["starts"] += 1
        s4u.this_actor.execute(1e9)      # 10 s at 100Mf
        state["done"].append(s4u.Engine.get_clock())

    actor = s4u.Actor.create("worker", e.host_by_name("beta"), worker)
    actor.set_auto_restart(True)

    def keepalive():
        s4u.this_actor.sleep_for(30.0)
    s4u.Actor.create("keepalive", e.host_by_name("alpha"), keepalive)

    # probe the watched-host set while beta is down and after recovery
    e.pimpl.timer_set(6.0, lambda: state["watched"].update(
        down=set(e.pimpl.watched_hosts)))
    e.pimpl.timer_set(9.0, lambda: state["watched"].update(
        up=set(e.pimpl.watched_hosts)))
    e.run()

    assert state["starts"] == 2, "auto-restart actor did not reboot"
    # first run killed mid-exec; rerun starts at t=8 and takes 10 s
    assert state["done"] == [pytest.approx(18.0)]
    assert state["watched"]["down"] == {"beta"}, \
        "failed host with pending actions must join watched_hosts"
    assert state["watched"]["up"] == set(), \
        "recovered host must leave watched_hosts"
    summary = stats.summary()
    assert summary["hosts"]["beta"]["failures"] == 1
    assert summary["hosts"]["beta"]["downtime"] == pytest.approx(3.0)
    assert summary["actors_killed"] >= 1
    assert summary["actors_restarted"] == 1


# ---------------------------------------------------------------------------
# Injector + failure-cause disambiguation in comm post paths
# ---------------------------------------------------------------------------

def test_link_failure_mid_comm_raises_network_failure(tmp_path):
    e = _engine(tmp_path)
    got = {}

    def sender(mb):
        try:
            mb.put("x", 1e7)             # ~10.3 s on wire
        except NetworkFailureException as exc:
            got["sender"] = (str(exc), s4u.Engine.get_clock())

    def receiver(mb):
        try:
            mb.get()
        except NetworkFailureException as exc:
            got["receiver"] = (str(exc), s4u.Engine.get_clock())

    mb = s4u.Mailbox.by_name("mb")
    s4u.Actor.create("sender", e.host_by_name("alpha"), sender, mb)
    s4u.Actor.create("receiver", e.host_by_name("beta"), receiver, mb)
    Injector(e).at(2.0).link_off("wire")
    e.run()
    assert got["sender"] == ("Link failure", pytest.approx(2.0))
    assert got["receiver"] == ("Link failure", pytest.approx(2.0))


def test_peer_host_failure_mid_comm_reports_peer_not_link(tmp_path):
    e = _engine(tmp_path)
    got = {}

    def sender(mb):
        try:
            mb.put("x", 1e7)
        except NetworkFailureException as exc:
            got["sender"] = (str(exc), s4u.Engine.get_clock())

    def receiver(mb):
        mb.get()                         # killed with its host

    mb = s4u.Mailbox.by_name("mb")
    s4u.Actor.create("sender", e.host_by_name("alpha"), sender, mb)
    s4u.Actor.create("receiver", e.host_by_name("beta"), receiver, mb)
    Injector(e).at(2.0).host_off("beta")
    e.run()
    assert got["sender"] == ("Remote peer failed", pytest.approx(2.0))


def test_injector_degrade_and_restore(tmp_path):
    e = _engine(tmp_path)
    done = {}

    def sender(mb):
        mb.put("x", 1e6)

    def receiver(mb):
        mb.get()
        done["t"] = s4u.Engine.get_clock()

    mb = s4u.Mailbox.by_name("mb")
    s4u.Actor.create("sender", e.host_by_name("alpha"), sender, mb)
    s4u.Actor.create("receiver", e.host_by_name("beta"), receiver, mb)
    inj = Injector(e)
    inj.at(0.0).link_degrade("wire", 0.5)
    e.run()
    # halved bandwidth: 1e6 B at 0.97 * 5e5 B/s
    assert done["t"] == pytest.approx(1e6 / (0.97 * 5e5), rel=1e-6)
    assert e.link_by_name("wire").bandwidth_peak == pytest.approx(5e5)
    inj.restore_all()
    assert e.link_by_name("wire").bandwidth_peak == pytest.approx(1e6)


def test_injector_restore_all_mid_superstep_matches_native(tmp_path):
    """restore_all() firing from an engine timer while the device
    drain is mid-superstep must be absorbed by the transition
    classifier (degrade and restore are both resumable c_bound flips),
    with completion times bit-identical to the native per-event loop."""

    def run(*cfg):
        s4u.Engine._reset()
        e = _engine(tmp_path, "--cfg=network/optim:Full",
                    "--cfg=network/maxmin-selective-update:no",
                    "--cfg=lmm/backend:jax", *cfg)
        done = {}

        def sender(mb, size):
            mb.put("x", size)

        def receiver(mb, key):
            mb.get()
            done[key] = s4u.Engine.get_clock()

        # 10 concurrent flows: above the fast path's hard floor of 8
        # started flows (ops.drain_path._MIN_FLOWS_FLOOR)
        sizes = [1.0e6 + 0.3e6 * k for k in range(10)]
        for k, size in enumerate(sizes):
            mb = s4u.Mailbox.by_name(f"mb{k}")
            s4u.Actor.create(f"s{k}", e.host_by_name("alpha"), sender,
                             mb, size)
            s4u.Actor.create(f"r{k}", e.host_by_name("beta"), receiver,
                             mb, k)
        inj = Injector(e)
        inj.at(2.0).link_degrade("wire", 0.5)
        inj.at(5.0).restore_all()
        e.run()
        assert e.link_by_name("wire").bandwidth_peak \
            == pytest.approx(1e6), "restore_all never fired"
        return done, e.clock

    ref = run("--cfg=drain/fastpath:off")
    before = opstats.snapshot()
    got = run("--cfg=drain/fastpath:auto", "--cfg=drain/min-flows:8",
              "--cfg=drain/superstep:8")
    d = opstats.diff(before)
    assert got == ref                      # bit-identical, not approx
    assert max(got[0].values()) > 5.0, \
        "every flow finished before restore_all fired"
    assert d.get("fastpath_advances"), \
        "the device plan never served an advance (nothing was tested)"
    assert d.get("drain_transitions"), \
        "degrade/restore never hit the transition classifier"


def test_injector_partition_heals(tmp_path):
    e = _engine(tmp_path)
    log = []

    def sender(mb):
        try:
            mb.put("x", 1e6, timeout=-1.0)
            log.append(("sent", s4u.Engine.get_clock()))
        except NetworkFailureException:
            log.append(("cut", s4u.Engine.get_clock()))
        s4u.this_actor.sleep_until(6.0)
        mb.put("y", 1e6)
        log.append(("sent2", s4u.Engine.get_clock()))

    def receiver(mb):
        try:
            mb.get()
        except NetworkFailureException:
            pass
        mb.get()

    mb = s4u.Mailbox.by_name("mb")
    s4u.Actor.create("sender", e.host_by_name("alpha"), sender, mb)
    s4u.Actor.create("receiver", e.host_by_name("gamma"), receiver, mb)
    Injector(e).at(1.0).partition(["alpha", "beta"], ["gamma"],
                                  duration=2.0)
    e.run()
    assert log[0] == ("cut", pytest.approx(1.0))
    # partition healed at t=3; retry at t=6 succeeds
    assert log[1][0] == "sent2"
    assert log[1][1] == pytest.approx(6.0 + 1e6 / (0.97 * 1e6), rel=1e-6)


# ---------------------------------------------------------------------------
# Retry policies
# ---------------------------------------------------------------------------

def test_retry_policy_backoff_deterministic():
    p = s4u.RetryPolicy(base_delay=1.0, multiplier=2.0, max_delay=5.0)
    assert [p.backoff(i) for i in (1, 2, 3, 4)] == [1.0, 2.0, 4.0, 5.0]
    j1 = s4u.RetryPolicy(base_delay=1.0, jitter=0.5, seed=9)
    j2 = s4u.RetryPolicy(base_delay=1.0, jitter=0.5, seed=9)
    seq1 = [j1.backoff(1) for _ in range(5)]
    seq2 = [j2.backoff(1) for _ in range(5)]
    assert seq1 == seq2                  # same seed: bit-identical jitter
    assert all(0.5 <= d <= 1.0 for d in seq1)
    j3 = s4u.RetryPolicy(base_delay=1.0, jitter=0.5, seed=10)
    assert seq1 != [j3.backoff(1) for _ in range(5)]


def test_send_with_retry_recovers_from_timeout(tmp_path):
    e = _engine(tmp_path)
    stats = fault_stats.fault_stats_plugin_init(e)
    out = {}

    def sender(mb):
        policy = s4u.RetryPolicy(max_attempts=5, base_delay=0.5)
        out["attempts"] = s4u.Comm.send_with_retry(
            mb, "payload", 1e6, policy=policy, timeout=2.0)

    def receiver(mb):
        s4u.this_actor.sleep_for(2.2)    # miss the first attempt
        out["got"] = mb.get()

    mb = s4u.Mailbox.by_name("mb")
    s4u.Actor.create("sender", e.host_by_name("alpha"), sender, mb)
    s4u.Actor.create("receiver", e.host_by_name("beta"), receiver, mb)
    e.run()
    assert out["got"] == "payload"
    assert out["attempts"] == 2
    assert stats.summary()["comms_retried"] == 1


def test_send_with_retry_exhausts_and_reraises(tmp_path):
    e = _engine(tmp_path)
    out = {}

    def sender(mb):
        policy = s4u.RetryPolicy(max_attempts=2, base_delay=0.25)
        try:
            s4u.Comm.send_with_retry(mb, "x", 1e6, policy=policy,
                                     timeout=1.0)
        except TimeoutException:
            out["raised_at"] = s4u.Engine.get_clock()

    s4u.Actor.create("sender", e.host_by_name("alpha"), sender,
                     s4u.Mailbox.by_name("void"))
    e.run()
    # attempt 1 [0,1), backoff 0.25, attempt 2 [1.25, 2.25) -> raise
    assert out["raised_at"] == pytest.approx(2.25)


def test_exec_with_retry_waits_out_host_failure(tmp_path):
    e = _engine(tmp_path)
    stats = fault_stats.fault_stats_plugin_init(e)
    e.host_by_name("gamma").turn_off()
    out = {}

    def driver():
        exec_ = s4u.Exec()
        exec_.set_host(e.host_by_name("gamma")).set_flops_amount(1e8)
        policy = s4u.RetryPolicy(max_attempts=5, base_delay=2.0,
                                 multiplier=2.0)
        exec_.with_retry(policy)
        out["done"] = s4u.Engine.get_clock()

    s4u.Actor.create("driver", e.host_by_name("alpha"), driver)
    Injector(e).at(5.0).host_on("gamma")
    e.run()
    # attempts at t=0 (fail), t=2 (fail), t=6 (runs 1 s) -> done at 7
    assert out["done"] == pytest.approx(7.0)
    assert stats.summary()["execs_retried"] == 2


# ---------------------------------------------------------------------------
# Solver graceful degradation
# ---------------------------------------------------------------------------

def _jax_system():
    s = make_new_maxmin_system(False)
    lmm_jax.install(s, "jax")
    cnst = s.constraint_new(None, 3.0)
    var = s.variable_new(None, 1.0)
    s.expand(cnst, var, 1.0)
    return s, cnst, var


def test_lmm_nonconvergence_falls_back_to_host_solver(monkeypatch):
    s, cnst, var = _jax_system()

    def explode(arrays, eps, **kw):
        raise lmm_jax.SolveError(
            "LMM JAX solve did not converge (forced)")
    monkeypatch.setattr(lmm_jax, "solve_arrays", explode)
    before = lmm_jax.get_fallback_count()
    s.solve()                            # lmm/strict defaults to off
    assert var.value == pytest.approx(3.0), \
        "fallback must produce the exact host solution"
    assert lmm_jax.get_fallback_count() == before + 1
    assert s.fallback_count == 1


def test_lmm_nan_falls_back_to_host_solver(monkeypatch):
    s, cnst, var = _jax_system()

    def poisoned(arrays, eps, **kw):
        n_v, n_c = len(arrays.v_penalty), len(arrays.c_bound)
        return (np.full(n_v, np.nan), np.zeros(n_c), np.zeros(n_c), 1)
    monkeypatch.setattr(lmm_jax, "solve_arrays", poisoned)
    before = lmm_jax.get_fallback_count()
    s.solve()
    assert var.value == pytest.approx(3.0)
    assert lmm_jax.get_fallback_count() == before + 1


def test_lmm_strict_mode_preserves_the_raise(monkeypatch):
    config["lmm/strict"] = True
    s, cnst, var = _jax_system()

    def explode(arrays, eps, **kw):
        raise lmm_jax.SolveError(
            "LMM JAX solve did not converge (forced)")
    monkeypatch.setattr(lmm_jax, "solve_arrays", explode)
    before = lmm_jax.get_fallback_count()
    with pytest.raises(RuntimeError, match="did not converge"):
        s.solve()
    assert lmm_jax.get_fallback_count() == before
