"""Collective schedule tapes (ISSUE 13): the mirrored generators are
proved against the REAL smpi/coll.py algorithms via the recording
harness, the compiled tapes replay bit-identically to the host
maestro, CollectiveSpec rides ScenarioSpec serialization without
moving legacy keys, and the tape opstats counters move.  The full
matrix (fleets, fault composition, pipeline depths, the live-captured
NAS C kernel) runs in tools/check_determinism.py
--runtime-collective; its small-N instance rides tier-1 through
tests/test_determinism_lint.py."""

import numpy as np
import pytest

from simgrid_tpu.collectives import (CollectiveSpec, HostMaestro,
                                     generate)
from simgrid_tpu.collectives import schedule as S
from simgrid_tpu.ops import opstats
from simgrid_tpu.ops.drain_path import classify_phase
from simgrid_tpu.smpi import coll
from simgrid_tpu.smpi.schedule_capture import (CaptureError,
                                               capture_schedule,
                                               default_payload,
                                               record_algorithm)


def test_tags_match_smpi():
    """The generator tag constants are the runtime's collective tags —
    a captured schedule and a generated one must key identically."""
    assert S.TAG_BCAST == coll.TAG_BCAST
    assert S.TAG_REDUCE == coll.TAG_REDUCE
    assert S.TAG_ALLREDUCE == coll.TAG_ALLREDUCE
    assert S.TAG_ALLTOALL == coll.TAG_ALLTOALL


@pytest.mark.parametrize("op,algo,ranks,gen_pay,nbytes", [
    ("bcast", "binomial_tree", 6, 4096, 4096),
    ("allreduce", "redbcast", 5, 8192, 8192),
    ("allreduce", "rdb", 5, 4096, 4096),
    ("allreduce", "lr", 5, 23, 23 * 8),     # elems vs bytes; remainder
    ("alltoall", "pairwise", 5, 2e5, 2e5),
    ("alltoall", "bruck", 6, 64, 64),
    ("reduce", "default", 7, 8192, 8192),
])
def test_capture_matches_generator(op, algo, ranks, gen_pay, nbytes):
    """The comm sequence (src, dst, tag, size, dependency order) the
    real coll.py algorithm posts on recording threads equals the
    mirrored generator — at non-power-of-two rank counts, so the
    remainder/fallback arms are exercised."""
    gen = generate(op, algo, ranks, gen_pay)
    cap = capture_schedule(op, algo, ranks,
                           default_payload(op, ranks, nbytes))
    assert cap.ranks == gen.ranks
    assert cap.sequence() == gen.sequence()


@pytest.mark.parametrize("steps", [1, 3])
def test_the_lr_head_is_the_captured_ring_cut_after_its_steps(steps):
    """``steps=S`` emits the head of the logical ring: each rank's
    self-copy and first S ring ``sendrecv``s, exactly the programs the
    real coll.allreduce_lr posts, cut after that rank's first S + 1
    ``sendrecv``s (four ops each), with the predecessors the cut
    programs give.  The remainder's rdb, after the ring, is not in it."""
    ranks, elems = 16, 16 * 3 + 5
    progs = record_algorithm("allreduce", "lr", ranks,
                             default_payload("allreduce", ranks, elems * 8))
    cut = S.build_schedule([(p.ops if isinstance(p, S.Prog) else p)
                            [:4 * (steps + 1)] for p in progs])
    before = opstats.snapshot()
    gen = generate("allreduce", "lr", ranks, elems, steps=steps)
    assert opstats.diff(before)["collective_schedule_records"] \
        == gen.n_comms == ranks * (steps + 1)
    assert gen.sequence() == cut.sequence()
    # the whole schedule is the head of every ring step and its remainder
    whole = generate("allreduce", "lr", ranks, elems)
    assert generate("allreduce", "lr", ranks, elems,
                    steps=2 * (ranks - 1)).sequence() == whole.sequence()


@pytest.mark.parametrize("op, algo, ranks, payload, steps, match", [
    ("allreduce", "lr", 16, 160, 31, "allreduce/lr among 16 ranks has 30"),
    ("allreduce", "lr", 16, 160, -1, "allreduce/lr among 16 ranks"),
    ("allreduce", "lr", 16, 8, 1, "allreduce/lr of 8 elements"),
    ("allreduce", "rdb", 16, 8192, 1, "allreduce/rdb has no schedule head"),
    ("alltoall", "pairwise", 4, 1e6, 1, "alltoall/pairwise has no"),
])
def test_a_head_the_schedule_cannot_give_is_refused_by_name(
        op, algo, ranks, payload, steps, match):
    with pytest.raises(ValueError, match=match):
        generate(op, algo, ranks, payload, steps=steps)
    if algo != "lr":
        with pytest.raises(ValueError, match=match):
            CollectiveSpec(op, algo, ranks, "nic", payload, steps=steps)


def test_a_head_is_part_of_the_specs_identity():
    """A whole schedule keeps the key it had before heads existed; a
    head is another spec, and says so in its label and its JSON."""
    whole = CollectiveSpec("allreduce", "lr", 8, "nic", 64)
    head = CollectiveSpec("allreduce", "lr", 8, "nic", 64, steps=3)
    assert "steps" not in whole.to_dict()
    assert head.to_dict()["steps"] == 3
    assert head.key() != whole.key()
    assert CollectiveSpec.from_json(head.to_json()).key() == head.key()
    assert CollectiveSpec.from_json(whole.to_json()).steps is None
    assert head.label() == "allreduce/lr r8 nic 64B steps3"
    assert head.build().n_v == 8 * 4


def test_barrier_is_not_capturable():
    """barrier's linear algorithm receives from MPI_ANY_SOURCE, which
    cannot be compiled into a static tape: the recorder must refuse,
    not emit a wrong schedule."""
    with pytest.raises(CaptureError):
        record_algorithm("barrier", "default", 4, b"")


def test_tape_matches_host_maestro():
    """The superstep-resident DAG walk is bit-identical — completion
    events, fired activations AND the Kahan clock pair — to the
    dispatch-per-advance HostMaestro, and invariant under superstep
    regrouping."""
    dc = CollectiveSpec("allreduce", "rdb", 6, "nic", 4096,
                        bw=1e8).build()
    sim = dc.make_sim(superstep=8)
    sim.run()
    assert len(sim.events) == dc.n_v
    ma = HostMaestro(dc)
    ma.run()
    assert ma.events == sim.events
    assert ma.collective_events == sim.collective_events
    clk = np.asarray(sim._coll_clk)
    assert ma.clock == (float(clk[0]), float(clk[1]))
    assert ma.dispatches > sim.supersteps
    s1 = dc.make_sim(superstep=1)
    s1.run()
    assert s1.events == sim.events
    assert s1.collective_events == sim.collective_events


def test_scenario_spec_collective_serialization():
    """CollectiveSpec rides ScenarioSpec's canonical dict/JSON forms;
    legacy specs (no collective) keep their exact key material."""
    from simgrid_tpu.parallel.campaign import ScenarioSpec
    legacy = ScenarioSpec(seed=3, link_scale={2: 0.5})
    assert "collective" not in legacy.to_dict()
    cs = CollectiveSpec("alltoall", "pairwise", 5, "star", 2e5, bw=1e8)
    spec = ScenarioSpec(seed=1, collective=cs, label="c")
    back = ScenarioSpec.from_json(spec.to_json())
    assert back.key() == spec.key()
    assert back.collective.key() == cs.key()
    assert spec.key() != ScenarioSpec(seed=1, label="c").key()
    assert CollectiveSpec.from_json(cs.to_json()).key() == cs.key()


def test_phase_classifier_sees_collectives():
    """ops.drain_path.classify_phase distinguishes the four phase
    kinds and bumps the matching opstats counter."""
    dc = CollectiveSpec("bcast", "binomial_tree", 6, "ring", 4096,
                        bw=1e8).build()
    ft = (np.asarray([1.0]), np.asarray([0], np.int32),
          np.asarray([5e7]))
    before = opstats.snapshot()
    assert classify_phase(dc.make_sim(superstep=4)) == "collective-tape"
    assert classify_phase(dc.make_sim(superstep=4, tape=ft)) \
        == "collective-tape+faults"
    d = opstats.diff(before)
    assert d.get("phase_collective_tape") == 1
    assert d.get("phase_collective_tape_faults") == 1


def test_collective_counters_move():
    """The tape opstats counters: slots at compile (n_v solo, n_v*B
    batched), one fire per activation, and pipelined runs account
    their discarded speculative tail as replays."""
    from simgrid_tpu.parallel.campaign import Campaign, ScenarioSpec
    cs = CollectiveSpec("allreduce", "rdb", 5, "nic", 4096, bw=1e8)
    dc = cs.build()
    before = opstats.snapshot()
    sim = dc.make_sim(superstep=4)
    sim.run()
    d = opstats.diff(before)
    assert d.get("collective_tape_slots") == dc.n_v
    assert d.get("collective_tape_fires") == len(sim.collective_events)
    assert sim.collective_events

    before = opstats.snapshot()
    piped = dc.make_sim(superstep=2, pipeline=2)
    piped.run()
    d = opstats.diff(before)
    assert piped.events == sim.events
    assert d.get("collective_replays", 0) > 0

    specs = [ScenarioSpec(seed=0, collective=cs),
             ScenarioSpec(seed=1, bw_scale=0.5, collective=cs)]
    camp = Campaign.for_collective(cs, specs, fault_mode="off",
                                   superstep=4, dtype=np.float64)
    before = opstats.snapshot()
    camp.run_batched(batch=2)
    d = opstats.diff(before)
    assert d.get("collective_tape_slots") == dc.n_v * 2
    assert d.get("collective_tape_fires", 0) > 0
