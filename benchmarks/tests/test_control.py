"""``correct`` has been shown to fail: the control (the reference in
bfloat16, the precision below the float32 the configuration states, put
in the program's place) and each fault a cell can have, planted under
the timed path, come out as NOT correct, through the rest of a run as
the harness drives it.  Tiny geometry, CPU; the same readings at the
cells' own size are in PERF.md (tools/limits.py on the chip)."""

import numpy as np
import pytest

import tiny
from lib import manifest as mf

SOLVE, DRAIN = "tiny128-random.solve", "tiny128-random.drain"


def over(result):
    return {k for k, row in result["compared"].items()
            if not row["value"] <= row["limit"]}


@pytest.mark.parametrize("cell", [SOLVE, DRAIN])
@pytest.mark.parametrize("seed", [3, 2**31 + 4, 77])
def test_control_in_bfloat16_is_not_correct(cell, seed, monkeypatch):
    tiny.patch(monkeypatch)
    loaded = mf.Cell(tiny.tiny_manifest(), cell)
    real = loaded.driver.check
    monkeypatch.setattr(loaded.driver, "check",
                        lambda run, state, rec: real(run, state, rec,
                                                     precision="bf16"))
    result = tiny.execute(cell, seed=seed)
    assert result["correct"] is False
    assert over(result) & {"rate_gap", "date_gap", "events_unmatched"}


def break_solve(monkeypatch, change):
    from simgrid_tpu.ops import lmm_jax
    real = lmm_jax.solve_arrays

    def broken(arrays, eps, *a, **k):
        return change(real, arrays, eps)
    monkeypatch.setattr(lmm_jax, "solve_arrays", broken)


def altered_rate(real, arrays, eps):
    values, rem, use, rounds = real(arrays, eps)
    values = np.array(values)
    values[17] *= 1.01
    return values, rem, use, rounds


def half_the_flows(real, arrays, eps):
    pen = np.array(arrays.v_penalty)
    pen[: arrays.n_var // 2] = 0.0          # not in the system at all
    return real(arrays._replace(v_penalty=pen), eps)


@pytest.mark.parametrize("fault", [altered_rate, half_the_flows])
def test_solve_faults_are_not_correct(fault, monkeypatch):
    tiny.patch(monkeypatch)
    break_solve(monkeypatch, fault)
    result = tiny.execute(SOLVE)
    assert result["correct"] is False and over(result) == {"rate_gap"}


def state_unchanged(sim, real_run, max_advances):
    pass                                     # the step returns as it came


def altered_event(sim, real_run, max_advances):
    real_run(sim, max_advances=max_advances)
    t, fid = sim.events[0]
    sim.events[0] = (t * 1.001, fid)


def half_the_batch(sim, real_run, max_advances):
    real_run(sim, max_advances=max_advances)
    sim.events[:] = [(t, f) for t, f in sim.events if f % 2]


def unsteady_laps(sim, real_run, max_advances):
    real_run(sim, max_advances=max_advances)
    unsteady_laps.calls = getattr(unsteady_laps, "calls", 0) + 1
    if unsteady_laps.calls == 3:
        sim.events[-1] = (sim.events[-1][0] * (1 + 1e-9),
                          sim.events[-1][1])


@pytest.mark.parametrize("fault, caught_by", [
    (state_unchanged, {"date_gap", "events_unmatched", "advances_short"}),
    (altered_event, {"date_gap"}),
    (half_the_batch, {"events_unmatched"}),
    (unsteady_laps, {"laps_differing"}),
])
def test_drain_faults_are_not_correct(fault, caught_by, monkeypatch):
    tiny.patch(monkeypatch)
    from simgrid_tpu.ops.lmm_drain import DrainSim
    real_run = DrainSim.run
    monkeypatch.setattr(
        DrainSim, "run",
        lambda sim, max_advances=10_000_000: fault(sim, real_run,
                                                   max_advances))
    result = tiny.execute(DRAIN, seconds=0.2)
    assert result["correct"] is False
    assert caught_by & over(result), result["compared"]


def test_a_limit_is_an_upper_bound_and_exact_means_zero():
    from lib.compare import Compared
    c = Compared()
    assert c.correct is False              # nothing compared, not correct
    c.add("gap", 1e-6, 1e-5)
    c.add("exact", 0, 0)
    assert c.correct is True
    c.add("nan", float("nan"), 1.0)
    assert c.correct is False and c.lines()[-1].endswith("OVER")
