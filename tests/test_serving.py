"""The always-on campaign service (ISSUE 11): spec → plan → executor
staging with content-addressed AOT plan caching, mid-flight admission
batching, and surrogate triage (simgrid_tpu/serving).

The acceptance contract: ScenarioSpec hashing/serialization is stable
across processes and field orderings; a warm restart over a populated
disk plan cache performs zero XLA traces (plan_cache_hits > 0,
plan_compile_ms == 0); a scenario admitted into a partially-drained
fleet is bit-identical to ScenarioPlan.solo — including lanes whose
previous occupant died with fault activity and admissions that land
while pipeline speculation is in flight (rollback counter must fire);
scenarios the fleet cannot absorb are refused/deferred, never wrong;
exact=True always bypasses the surrogate and escalated queries return
exact device results."""

import json
import os

import numpy as np
import pytest

from bench import build_arrays
from simgrid_tpu.ops.lmm_batch import AdmissionError
from simgrid_tpu.parallel.campaign import ScenarioPlan, ScenarioSpec
from simgrid_tpu.serving import (CampaignService, PlanCache,
                                 RuntimeSurrogate)

# pinned ScenarioSpec.key() values: cache keys MUST be stable across
# processes and releases — if either moves, every on-disk artifact and
# every cross-process corpus row silently misses
PIN_DEFAULT = \
    "0efb0fdb244a7e8331faaba28b28d2a9b2b60232a04ecd3393308edfcb05d58a"
PIN_FAULTED = \
    "4a32347a0c203b5c5a268718b4c2eb033dee720be7c4ff28101278e1ab342ce0"


@pytest.fixture(scope="module")
def plan():
    rng = np.random.default_rng(43)
    n_c, n_v = 24, 64
    arrays = build_arrays(rng, n_c, n_v, 3, np.float64)
    E = arrays.n_elem
    sizes = rng.choice(np.linspace(1e5, 2e6, 16), n_v)
    return ScenarioPlan(arrays.e_var[:E], arrays.e_cnst[:E],
                        arrays.e_w[:E], arrays.c_bound[:n_c], sizes,
                        eps=1e-9, superstep=4, fault_mode="on")


def faulted_spec(seed, label=None):
    """A spec whose seeded tape actually fires mid-drain on the
    module fixture's system (asserted where it matters)."""
    return ScenarioSpec(seed=seed, bw_scale=1.0 + 0.1 * (seed % 5),
                        fault_mtbf=150.0, fault_mttr=50.0,
                        fault_horizon=900.0, label=label)


class TestSpecSerialization:
    def test_key_pinned(self):
        """Regression pin: the content hash of a default spec and a
        representative faulted spec must never move (plan-cache and
        corpus addressing depend on it across processes)."""
        assert ScenarioSpec().key() == PIN_DEFAULT
        assert ScenarioSpec(seed=3, link_scale={2: 0.5},
                            fault_mtbf=40.0).key() == PIN_FAULTED

    def test_json_round_trip(self):
        spec = ScenarioSpec(seed=9, bw_scale=1.25, size_scale=0.75,
                            link_scale={5: 0.5, 2: 0.25},
                            flow_scale={1: 2.0}, dead_flows=(7, 3),
                            elem_w={4: 1.5}, fault_mtbf=120.0,
                            fault_mttr=30.0, fault_dist="weibull",
                            fault_shape=1.5, fault_horizon=400.0,
                            label="rt")
        back = ScenarioSpec.from_json(spec.to_json())
        assert back.to_dict() == spec.to_dict()
        assert back.key() == spec.key()
        assert back.label == "rt"

    def test_key_invariant_under_field_reordering(self):
        """Same content, different construction / dict orders → same
        hash: map insertion order, dead-flow order and serialized
        key order are all non-semantic."""
        a = ScenarioSpec(seed=1, link_scale={2: 0.5, 7: 0.25},
                         dead_flows=(5, 1))
        b = ScenarioSpec(seed=1, link_scale={7: 0.25, 2: 0.5},
                         dead_flows=(1, 5))
        assert a.key() == b.key()
        # a reordered json payload decodes to the same identity
        d = json.loads(a.to_json())
        shuffled = dict(reversed(list(d.items())))
        assert ScenarioSpec.from_dict(shuffled).key() == a.key()

    def test_key_ignores_label(self):
        assert ScenarioSpec(seed=2, label="x").key() \
            == ScenarioSpec(seed=2, label="y").key()
        assert ScenarioSpec(seed=2).key() \
            != ScenarioSpec(seed=3).key()


class TestPlanCacheWarmRestart:
    def test_warm_restart_skips_tracing(self, plan, tmp_path):
        """THE warm-restart contract: a second PlanCache over the same
        populated directory (a fresh process in spirit) serves every
        program from disk — hits > 0, zero misses, zero compile
        milliseconds — and the results stay bit-identical."""
        specs = [ScenarioSpec(seed=s, bw_scale=1.0 + 0.1 * s,
                              label=f"w{s}") for s in range(4)]
        cold = PlanCache(str(tmp_path))
        svc = CampaignService(plan, batch=2, plan_cache=cold)
        t_cold = svc.submit_many(specs, exact=True)
        svc.drain()
        assert cold.misses > 0 and cold.compile_ms > 0
        assert any(f.endswith(".xplan") for f in os.listdir(tmp_path))

        warm = PlanCache(str(tmp_path))
        svc2 = CampaignService(plan, batch=2, plan_cache=warm)
        t_warm = svc2.submit_many(specs, exact=True)
        svc2.drain()
        assert warm.hits > 0
        assert warm.misses == 0
        assert warm.compile_ms == 0.0
        assert warm.disk_hits > 0
        # ... and the deserialized executables actually RAN (on the
        # 8-device test backend they used to fail on first call and
        # be answered by the traced jit)
        assert warm.fallbacks == 0
        for a, b in zip(t_cold, t_warm):
            assert a.result.events == b.result.events
            assert a.result.t == b.result.t

    def test_corrupt_artifact_recompiles(self, plan, tmp_path):
        """A truncated/garbage artifact is never trusted: the cache
        recompiles (counted as a miss) and results stay correct."""
        spec = ScenarioSpec(seed=0, label="c")
        cache = PlanCache(str(tmp_path))
        svc = CampaignService(plan, batch=1, plan_cache=cache)
        svc.submit(spec, exact=True)
        ref = svc.drain()[0].result
        for name in os.listdir(tmp_path):
            with open(os.path.join(tmp_path, name), "wb") as f:
                f.write(b"not a pickle")
        fresh = PlanCache(str(tmp_path))
        svc2 = CampaignService(plan, batch=1, plan_cache=fresh)
        svc2.submit(spec, exact=True)
        got = svc2.drain()[0].result
        assert fresh.disk_hits == 0 and fresh.misses > 0
        assert got.events == ref.events and got.t == ref.t


class TestAdmission:
    def test_admit_into_fault_death_and_completion_death(self, plan):
        """Both kinds of dead lane accept admissions bit-identically:
        one initial occupant dies having fired fault tape events, the
        other drains clean; a clean spec admitted into the fault-death
        lane and a faulted spec admitted into the clean lane must both
        match ScenarioPlan.solo exactly (events, fired faults, Kahan
        clocks) — stale tape entries from the previous occupant must
        not leak into the admitted lane."""
        first = [faulted_spec(0, "f0"), ScenarioSpec(seed=1, label="c1")]
        later = [ScenarioSpec(seed=2, label="c2"), faulted_spec(3, "f3")]
        assert plan.solo(first[0]).fault_events, \
            "fixture spec must fire a tape event for this test to bite"
        tape_slots = max(plan.tape_len(s) for s in (first[0], later[1]))
        sim = plan.executor(first, width=2, tape_slots=tape_slots)
        sim.run()
        assert not sim._alive.any()
        assert sim.replicas[0].fault_events     # died WITH fault fires
        assert not sim.replicas[1].fault_events  # died clean
        for b, spec in enumerate(later):
            sim.admit_lane(b, plan.overrides_for(spec),
                           tape=plan.tape_for(spec))
        sim.run()
        for b, spec in enumerate(later):
            solo = plan.solo(spec)
            assert sim.replicas[b].events == solo.events
            assert sim.replicas[b].t == solo.t
            assert sim.replicas[b].fault_events == solo.fault_events
        # f3's tape fired in its OWN lane; c2's lane stayed clean even
        # though its slot previously held f0's tape
        assert not sim.replicas[0].fault_events
        assert sim.replicas[1].fault_events

    def test_admission_rolls_back_pipeline_speculation(self, plan):
        """Admissions landing while pipeline=2 speculation is in
        flight must discard the speculative supersteps (they assumed
        the old alive mask): the rollback counter fires AND every
        served result is still bit-identical to solo."""
        specs = [ScenarioSpec(seed=s, bw_scale=1.0 + 0.15 * s,
                              label=f"p{s}") for s in range(6)]
        svc = CampaignService(plan, batch=2, pipeline=2)
        tickets = svc.submit_many(specs, exact=True)
        svc.drain()
        assert svc.lanes_admitted > 0
        assert svc.spec_rolled_back > 0
        for t in tickets:
            solo = plan.solo(t.spec)
            assert t.result.source == "device"
            assert t.result.events == solo.events
            assert t.result.t == solo.t

    def test_tape_overflow_is_refused_then_deferred(self, plan):
        """A faulted spec whose tape exceeds the fleet's reserved
        width raises AdmissionError on the direct path; the service
        turns that refusal into a deferral and serves the spec on a
        fresh fleet sized for it — correct either way, never wrong."""
        clean = ScenarioSpec(seed=1, label="c")
        wide = faulted_spec(0, "wide")
        sim = plan.executor([clean], width=1, tape_slots=0)
        sim.run()
        with pytest.raises(AdmissionError, match="tape"):
            sim.admit_lane(0, plan.overrides_for(wide),
                           tape=plan.tape_for(wide))
        # service path: queue order forces the fleet to be born clean
        # (no faulted spec visible), then the wide spec arrives late
        svc = CampaignService(plan, batch=1)
        t_clean = svc.submit(clean, exact=True)
        svc._start_fleet()
        t_wide = svc.submit(wide, exact=True)
        svc.drain()
        assert svc.deferrals > 0
        assert t_wide.defer_reason is not None
        assert svc.fleets == 2
        solo = plan.solo(wide)
        assert t_wide.result.events == solo.events
        assert t_wide.result.t == solo.t
        assert t_wide.result.fault_events == solo.fault_events
        assert t_clean.result.t == plan.solo(clean).t

    def test_alive_lane_refused(self, plan):
        sim = plan.executor([ScenarioSpec(seed=0)], width=1)
        with pytest.raises(AdmissionError, match="alive"):
            sim.admit_lane(0, plan.overrides_for(ScenarioSpec(seed=1)))


class TestSurrogateTriage:
    def _trained(self, n=48):
        """A surrogate fitted on a noiseless linear family — the
        conformal quantile collapses to ~0, so every in-family query
        triages to the surrogate."""
        sur = RuntimeSurrogate(min_corpus=40)
        for s in range(n):
            spec = ScenarioSpec(seed=s, bw_scale=1.0 + 0.1 * (s % 5),
                                size_scale=1.0 + 0.05 * (s % 3))
            sur.observe(spec, 100.0 * spec.size_scale / spec.bw_scale)
        assert sur.fitted
        return sur

    def test_exact_always_bypasses_surrogate(self, plan):
        sur = self._trained()
        svc = CampaignService(plan, batch=1, surrogate=sur)
        spec = ScenarioSpec(seed=100, bw_scale=1.2, size_scale=1.05,
                            label="ex")
        t = svc.submit(spec, exact=True)
        assert t.status == "queued"
        assert svc.surrogate_answers == 0
        assert svc.surrogate_escalations == 0
        svc.drain()
        assert t.result.source == "device"
        assert t.result.t == plan.solo(spec).t

    def test_surrogate_answers_carry_bounds(self, plan):
        sur = self._trained()
        svc = CampaignService(plan, batch=1, surrogate=sur)
        spec = ScenarioSpec(seed=101, bw_scale=1.1, size_scale=1.0)
        t = svc.submit(spec, exact=False)
        assert t.status == "done"
        assert t.result.source == "surrogate"
        assert t.result.lo <= t.result.t <= t.result.hi
        assert t.result.confidence == sur.confidence
        assert svc.surrogate_answers == 1
        truth = 100.0 * spec.size_scale / spec.bw_scale
        assert t.result.lo - 1e-6 <= truth <= t.result.hi + 1e-6

    def test_escalation_returns_exact_device_result(self, plan):
        """An unfitted surrogate (or a wide interval) escalates: the
        query is answered by exact device simulation, audited via the
        escalation counter and source == "device"."""
        svc = CampaignService(plan, batch=1,
                              surrogate=RuntimeSurrogate())
        spec = ScenarioSpec(seed=5, label="esc")
        t = svc.submit(spec, exact=False)
        assert t.status == "queued"
        assert svc.surrogate_escalations == 1
        svc.drain()
        assert t.result.source == "device"
        assert t.result.events == plan.solo(spec).events

    def test_corpus_seeds_from_jsonl_and_hits_majority(self, tmp_path):
        """The serving corpus loop: jsonl rows (spec dict + final
        clock, the bench_results/corpus-log format) seed the
        predictor, and a replayed in-family sweep is answered by the
        surrogate for well over half its queries."""
        path = tmp_path / "corpus.jsonl"
        with open(path, "w") as f:
            for s in range(64):
                spec = ScenarioSpec(seed=s,
                                    bw_scale=1.0 + 0.1 * (s % 5),
                                    size_scale=1.0 + 0.05 * (s % 3))
                f.write(json.dumps(
                    {"spec": spec.to_dict(),
                     "t": 100.0 * spec.size_scale / spec.bw_scale,
                     "source": "device"}) + "\n")
        sur = RuntimeSurrogate(min_corpus=40)
        assert sur.load_corpus(str(path)) == 64
        assert sur.fitted
        answered = 0
        for s in range(32):
            spec = ScenarioSpec(seed=1000 + s,
                                bw_scale=1.0 + 0.1 * (s % 5),
                                size_scale=1.0 + 0.05 * (s % 3))
            if sur.triage(spec) is not None:
                answered += 1
        assert answered >= 16  # the >= 50% acceptance bar


class TestCounters:
    def test_service_counters_surface_everything(self, plan, tmp_path):
        """The counters the CLIs print: plan-cache hits/misses/
        compile-ms, admissions and surrogate routing all present."""
        cache = PlanCache(str(tmp_path))
        svc = CampaignService(plan, batch=2, plan_cache=cache,
                              surrogate=RuntimeSurrogate())
        svc.submit_many([ScenarioSpec(seed=s, label=f"k{s}")
                         for s in range(4)], exact=True)
        svc.drain()
        c = svc.counters()
        for key in ("fleets", "lanes_admitted", "surrogate_answers",
                    "surrogate_escalations", "deferrals",
                    "plan_cache_hits", "plan_cache_misses",
                    "plan_cache_disk_hits", "plan_cache_fallbacks",
                    "plan_compile_ms"):
            assert key in c
        assert c["fleets"] == 1
        assert c["lanes_admitted"] == 2
        assert c["plan_cache_hits"] > 0
