"""Speculative pipelined drain (ISSUE 5): double-buffered completion
rings, async superstep dispatch, and discard-and-replay speculation
rollback.

The acceptance contract: with ``pipeline=D`` (DrainSim), a pipelined
fleet (BatchDrainSim via Campaign) or ``drain/pipeline`` (the engine
fast path), results are BIT-IDENTICAL — event order, timestamps, final
clock — to the unpipelined superstep path, including when a mid-drain
mutation (device repack, round-budget rescue, partial engine advance,
plan invalidation) forces the in-flight speculative superstep to be
discarded and replayed.
"""

import numpy as np
import pytest

from bench import build_arrays
from simgrid_tpu import s4u
from simgrid_tpu.ops import opstats
from simgrid_tpu.ops.lmm_drain import DrainSim
from simgrid_tpu.ops.lmm_batch import BatchDrainSim, ReplicaOverrides
from simgrid_tpu.parallel.campaign import Campaign, ScenarioSpec

K = 8


@pytest.fixture(autouse=True)
def fresh_engine():
    s4u.Engine._reset()
    yield
    s4u.Engine._reset()


@pytest.fixture(scope="module")
def drain_system():
    rng = np.random.default_rng(29)
    n_c, n_v = 48, 300
    arrays = build_arrays(rng, n_c, n_v, 3, np.float64)
    E = arrays.n_elem
    sizes = rng.choice(np.linspace(1e5, 2e6, 16), n_v)
    return (arrays.e_var[:E], arrays.e_cnst[:E], arrays.e_w[:E],
            arrays.c_bound[:n_c], sizes)


def run_solo(system, **kw):
    ev, ec, ew, cb, sizes = system
    sim = DrainSim(ev, ec, ew, cb, sizes, eps=1e-9, dtype=np.float64,
                   superstep=K, **kw)
    sim.run()
    return sim


class TestSoloPipelineBitIdentity:
    def test_depths_match_unpipelined(self, drain_system):
        """THE pipelining contract: depths 1 and 2 reproduce the
        unpipelined superstep drain bit-for-bit (events, clock,
        advance structure), and speculation really commits."""
        ref = run_solo(drain_system, repack_min=1 << 62, pipeline=0)
        for depth in (1, 2):
            sim = run_solo(drain_system, repack_min=1 << 62,
                           pipeline=depth)
            assert sim.events == ref.events
            assert sim.t == ref.t
            assert sim.advances == ref.advances
            assert sim.spec_committed > 0

    def test_repack_mispredict_discards_and_replays(self, drain_system):
        """A mid-drain device repack mutates the arrays the in-flight
        superstep assumed frozen: speculation must roll back and the
        replay must still be bit-identical to the unpipelined drain
        under the same repack schedule."""
        ref = run_solo(drain_system, repack_min=32, pipeline=0)
        sim = run_solo(drain_system, repack_min=32, pipeline=2)
        assert sim.repacks > 0          # the mutation really happened
        assert sim.spec_rolled_back > 0  # and really mispredicted
        assert sim.events == ref.events
        assert sim.t == ref.t

    def test_budget_rescue_mispredict(self, drain_system):
        """A starved round budget forces _FLAG_BUDGET exits and K = 1
        rescues between supersteps — the rescue mutates flow state, so
        in-flight speculation is discarded; the replayed drain must
        match the unpipelined one bit-for-bit."""
        ref = run_solo(drain_system, repack_min=1 << 62,
                       superstep_rounds=3, pipeline=0)
        sim = run_solo(drain_system, repack_min=1 << 62,
                       superstep_rounds=3, pipeline=1)
        assert sim.spec_rolled_back > 0
        assert sim.events == ref.events
        assert sim.t == ref.t

    def test_ring_saturation_rescue(self):
        """The ring-saturation shape (whole drain in one superstep)
        under a starved budget: partial batches + rescue advances
        replay to the per-advance (K = 1) event stream with
        pipelining on."""
        groups, per = 6, 40
        n_v = groups * per
        e_var, e_cnst, e_w = [], [], []
        for g in range(groups):
            for j in range(per):
                v = g * per + j
                e_var += [v, v]
                e_cnst += [0, 1 + g]
                e_w += [1.0, 1.0]
        c_bound = np.array([1e6 * groups] + [1e6] * groups)
        sizes = np.repeat(1e6 * (1.0 + np.arange(groups)), per)
        args = (np.array(e_var, np.int32), np.array(e_cnst, np.int32),
                np.array(e_w), c_bound, sizes)
        ref = DrainSim(*args, eps=1e-9, dtype=np.float64, superstep=1,
                       repack_min=1 << 62)
        ref.run()
        sim = DrainSim(*args, eps=1e-9, dtype=np.float64, superstep=K,
                       superstep_rounds=3, repack_min=1 << 62,
                       pipeline=2)
        sim.run()
        assert sim.events == ref.events
        assert sim.t == ref.t

    def test_pipeline_requires_superstep(self, drain_system):
        ev, ec, ew, cb, sizes = drain_system
        with pytest.raises(ValueError, match="superstep=0"):
            DrainSim(ev, ec, ew, cb, sizes, superstep=0, pipeline=1)


class TestFleetPipeline:
    def test_fleet_matches_unpipelined_and_solo(self, drain_system):
        """8-wide mixed fleet: pipelined lockstep supersteps are
        bit-identical per replica to the unpipelined fleet AND to the
        solo oracle; lane deaths mid-fleet force speculation
        rollbacks (the alive mask changed under the in-flight
        dispatch)."""
        specs = [ScenarioSpec(seed=s, bw_scale=1.0 + 0.2 * (s % 4),
                              size_scale=1.0 + 0.05 * (s % 3),
                              dead_flows=(s % 5,) if s % 3 == 0 else ())
                 for s in range(8)]
        camp = Campaign(*drain_system, specs, eps=1e-9,
                        dtype=np.float64, superstep=K)
        ref = camp.run_batched(batch=8, pipeline=0)
        got = camp.run_batched(batch=8, pipeline=2)
        for j in range(8):
            assert got[j].events == ref[j].events
            assert got[j].t == ref[j].t
        solo = camp.run_solo(3)
        assert got[3].events == solo.events
        assert got[3].t == solo.t

    def test_lane_death_rolls_back_speculation(self, drain_system):
        """A replica finishing early flips the alive mask — a fleet
        mutation the in-flight superstep did not see: it must be
        discarded (counted) and the stragglers' results stay exact."""
        ev, ec, ew, cb, sizes = drain_system
        ovs = [ReplicaOverrides(bw_scale=50.0),   # finishes early
               ReplicaOverrides(bw_scale=1.0),
               ReplicaOverrides(bw_scale=0.5)]

        def fleet(depth):
            sim = BatchDrainSim(ev, ec, ew, cb, sizes, ovs, eps=1e-9,
                                dtype=np.float64, superstep=K,
                                pipeline=depth)
            sim.run()
            return sim

        ref, got = fleet(0), fleet(2)
        assert got.spec_rolled_back > 0
        for b in range(3):
            assert got.replicas[b].events == ref.replicas[b].events
            assert got.replicas[b].t == ref.replicas[b].t


class TestCompactElemWeights:
    def test_elem_w_override_matches_solo(self, drain_system):
        """Per-replica element weights ride the indexed payload and
        are materialized on device: each lane must match the solo run
        over host-derived weights bit-for-bit."""
        ev, ec, ew, cb, sizes = drain_system
        E = len(ev)
        specs = [ScenarioSpec(seed=s,
                              elem_w={(7 * s + j) % E: 0.5 + 0.25 * j
                                      for j in range(s % 3)})
                 for s in range(4)]
        camp = Campaign(*drain_system, specs, eps=1e-9,
                        dtype=np.float64, superstep=K)
        got = camp.run_batched(batch=4)
        for j in range(4):
            solo = camp.run_solo(j)
            assert got[j].events == solo.events
            assert got[j].t == solo.t
        # weights really differed between replicas
        assert got[0].t != got[2].t

    def test_upload_bytes_scale_with_overrides_not_BxE(self,
                                                       drain_system):
        """The satellite contract: the per-replica weight payload
        bytes scale with overridden slots, not B×E — a 16-wide fleet
        with 2 overrides each ships far less than the dense B×E dtype
        table the old e_w_batch upload required."""
        ev, ec, ew, cb, sizes = drain_system
        E = len(ev)
        B = 16
        ovs = [ReplicaOverrides(elem_w={(3 * b) % E: 2.0,
                                        (3 * b + 1) % E: 0.5})
               for b in range(B)]
        with opstats.scoped("elem-w-payload") as st:
            BatchDrainSim(ev, ec, ew, cb, sizes, ovs, eps=1e-9,
                          dtype=np.float64, superstep=K)
        dense = B * E * np.dtype(np.float64).itemsize
        # payload = B * max-overrides * (int32 idx + f64 value) plus
        # the other per-replica payload fields; far under dense B×E
        assert st["uploaded_bytes_delta"] < dense / 10


class TestHostBlockInstrumentation:
    def test_fetch_counters_and_stage_scope(self, drain_system):
        """opstats satellite: drain fetches are counted, classified
        blocking/ready, and host-block milliseconds accumulate — all
        visible through a scoped() stage."""
        with opstats.scoped("pipe-instr") as st:
            run_solo(drain_system, repack_min=1 << 62, pipeline=1)
        assert st["fetches"] >= 1
        assert 0 <= st.get("blocking_fetches", 0) <= st["fetches"]
        assert st["host_block_ms"] > 0
        assert st["speculations_issued"] >= 1
        assert opstats.get_stage("pipe-instr")["fetches"] == \
            st["fetches"]


def fat_tree_platform(tmp_path):
    from tests.test_drain_superstep import fat_tree_platform as ft
    return ft(tmp_path)


class TestEnginePipelinedFastPath:
    """drain/pipeline in the engine fast path: one speculative
    superstep rides in flight while the engine consumes the current
    ring's batches; plan invalidations discard it."""

    def _drain(self, tmp_path, cfg, flows=300, seed=5, bound_step=0.0):
        from tests.test_drain_superstep import _run_engine_drain
        return _run_engine_drain(str(tmp_path), cfg, flows=flows,
                                 seed=seed, bound_step=bound_step)

    def test_event_parity_with_speculation(self, tmp_path):
        base = ["lmm/backend:jax", "network/maxmin-selective-update:no",
                "network/optim:Full"]
        ev_off, _ = self._drain(tmp_path,
                                base + ["drain/fastpath:off"])
        s4u.Engine._reset()
        ev_on, m_on = self._drain(
            tmp_path, base + ["drain/fastpath:auto",
                              "drain/min-flows:64",
                              f"drain/superstep:{K}",
                              "drain/pipeline:1"])
        fp = m_on.drain_fastpath
        assert fp.speculations > 0
        assert fp.spec_commits > 0
        assert [f for _, f in ev_on] == [f for _, f in ev_off]
        for (ta, _), (tb, _) in zip(ev_off, ev_on):
            assert tb == pytest.approx(ta, rel=1e-9, abs=1e-12)

    def test_partial_advance_discards_speculation(self, tmp_path):
        """A run-until bound interrupts plans mid-batch (the partial-
        advance mutation): the in-flight speculative superstep must be
        discarded, the replay rollback must run, and event parity must
        hold."""
        base = ["lmm/backend:jax", "network/maxmin-selective-update:no",
                "network/optim:Full"]
        step = 0.002
        ev_off, _ = self._drain(tmp_path,
                                base + ["drain/fastpath:off"],
                                flows=150, bound_step=step)
        s4u.Engine._reset()
        ev_on, m_on = self._drain(
            tmp_path, base + ["drain/fastpath:auto",
                              "drain/min-flows:32",
                              f"drain/superstep:{K}",
                              "drain/pipeline:1"],
            flows=150, bound_step=step)
        fp = m_on.drain_fastpath
        assert fp.rollbacks > 0
        assert fp.spec_discards > 0
        assert [f for _, f in ev_on] == [f for _, f in ev_off]
        for (ta, _), (tb, _) in zip(ev_off, ev_on):
            assert tb == pytest.approx(ta, rel=1e-9, abs=1e-12)

    def test_pipeline_off_keeps_fast_path_synchronous(self, tmp_path):
        base = ["lmm/backend:jax", "network/maxmin-selective-update:no",
                "network/optim:Full", "drain/fastpath:auto",
                "drain/min-flows:64", f"drain/superstep:{K}",
                "drain/pipeline:0"]
        _, model = self._drain(tmp_path, base)
        fp = model.drain_fastpath
        assert fp.plans >= 1
        assert fp.speculations == 0
