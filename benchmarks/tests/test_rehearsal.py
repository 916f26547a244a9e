"""CPU rehearsal of every driver end to end, and the yardstick's own
arithmetic: trace reduction, roofline bytes, traffic, the reference."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import tiny
from configs import dragonfly_lv08 as ref
from lib import manifest as mf, roofline, scopes, trace, traffic
from lib.compare import events_gap, rate_gap

CELLS = [w["name"] for w in tiny.tiny_manifest()["workloads"]]
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "compared"]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The small trace recorded on the v5e (one superstep dispatch of
    the 128-host drain under ``bench:lap`` / ``bench:run``)."""
    return trace.TraceSummary(trace.read_xplane(tiny.recorded(
        "tiny_drain", tmp_path_factory.mktemp("trace"))))


@pytest.mark.parametrize("cell", CELLS)
def test_driver_end_to_end_prints_the_contracts_keys(cell, monkeypatch):
    tiny.patch(monkeypatch)
    result = tiny.execute(cell)
    assert list(result) == RESULT_KEYS        # 'compared' comes last
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    wanted = {m["name"] for m in mf.Cell(tiny.tiny_manifest(),
                                         cell).end_to_end()}
    assert set(result["metrics"]) == wanted and "setup_s" in wanted
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    for row in result["compared"].values():
        assert row["value"] <= row["limit"]
    json.dumps(result)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics(cell, monkeypatch, capfd):
    tiny.patch(monkeypatch)
    tiny.traced(monkeypatch)
    result = tiny.execute(cell, trace=True)
    assert list(result) == RESULT_KEYS[:5] + ["breakdown", "compared"]
    assert result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] >= result["device"]["busy_s"]
    per_layer = {m["name"] for m in mf.Cell(tiny.tiny_manifest(),
                                            cell).per_layer()}
    assert set(result["metrics"]) <= per_layer
    assert "flatten_s" in result["metrics"]
    if cell.endswith(".drain"):
        # the recorded trace is a drain's, with the program's names in
        # it: every metric of the cell reads, a tape cell's too (the
        # tape's scope never ran in it: 0 ms)
        assert set(result["metrics"]) == per_layer
        assert result["metrics"]["drain.rounds_ms"]["value"] > 0
    else:
        assert not {"solve.init_ms", "solve.rounds_ms",
                    "solve.partition_ms"} & set(result["metrics"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    for rows in result["breakdown"].values():
        assert 1 <= len(rows) <= 10
        for name, seconds in rows:
            assert isinstance(name, str) and 0 < len(name) <= 80
            assert isinstance(seconds, float) and seconds > 0
    assert result["breakdown"]["device_ops"][0][0].startswith("sg.lmm.")
    assert "op-name paths" in capfd.readouterr().err   # both parses timed


def test_same_seed_same_run_and_laps_agree(monkeypatch):
    tiny.patch(monkeypatch)
    from lib import harness
    seen = []
    real = harness.measure
    monkeypatch.setattr(harness, "measure", lambda run, state: seen.append(
        real(run, state)) or seen[-1])
    for _ in range(2):
        assert tiny.execute("tiny128-random.drain", seed=5)["correct"]
    a, b = seen
    assert a["laps"] >= 2 and len(set(a["digests"])) == 1
    assert a["digests"][0] == b["digests"][0]
    assert a["first_lap"] == b["first_lap"] and a["first_lap"]


def test_without_a_tpu_the_command_fails_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = mf.load_manifest()["workloads"][0]["name"]
    done = subprocess.run(
        [sys.executable, os.path.join(mf.BENCH, "run.py"), "--workload",
         cell, "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert not [ln for ln in done.stdout.splitlines()
                if ln.startswith("{")], done.stdout
    assert "no TPU" in done.stderr


# -- the trace reduction -----------------------------------------------------

def test_interval_arithmetic():
    assert trace.union([(5, 9), (0, 3), (2, 4), (9, 9)]) == [(0, 4), (5, 9)]
    assert trace.total(trace.clip([(0, 4), (5, 9)], 3, 7)) == 3
    assert trace.gaps([(2, 4), (6, 7)], 0, 10) == [(0, 2), (4, 6), (7, 10)]
    # a loop of 10 holds two bodies of 3: 4 are the loop's own
    own = trace.self_times([("while", 0, 10), ("body", 1, 4),
                            ("body", 5, 8), ("after", 12, 13)])
    assert own == {"while": 4, "body": 6, "after": 1}
    assert trace.short_op("%fusion.163 = f32[141871]{0:T(1024)S(1)} "
                          "fusion(s32[1241664]{0} %x), kind=kCustom") \
        == "%fusion.163 f32[141871] fusion"


def test_summary_of_made_up_planes():
    planes = {
        "/device:TPU:0": {
            "XLA Ops": [("%while = x", 100, 400), ("%f = y", 150, 250),
                        ("%g = z", 600, 700)],
            "XLA Modules": [("jit__superstep_program(1)", 100, 400),
                            ("jit_other(2)", 600, 700)]},
        "/device:CUSTOM:Megascale Trace": {},
        "/host:CPU": {"python": [("bench:window", 0, 1000),
                                 ("bench:lap.upload", 400, 600),
                                 ("$numpy asarray", 0, 50)]}}
    s = trace.TraceSummary(planes)
    assert s.window_s == 1000e-9 and s.busy_s == pytest.approx(400e-9)
    assert s.module_seconds("_superstep_program") == (300e-9, 1)
    assert scopes.top_gaps(s) == [["unannotated", 400e-9],
                                  ["lap.upload", 200e-9]]
    assert dict(map(tuple, s.top_ops()))["%while x"] \
        == pytest.approx(200e-9)
    with pytest.raises(ValueError, match="no device operation"):
        trace.TraceSummary({"/host:CPU": planes["/host:CPU"]})


def test_recorded_trace(recorded):
    """The fixture: 8 advances, 64 rounds in one superstep program."""
    assert recorded.devices == ["/device:TPU:0"]
    seconds, runs = recorded.module_seconds("jit__superstep_program")
    assert runs == 1 and seconds == pytest.approx(0.0441870, rel=1e-4)
    assert recorded.busy_s <= recorded.window_s
    assert recorded.busy_s == pytest.approx(seconds, rel=1e-2)
    assert {n for n, _a, _b in recorded.notes} == {"bench:lap",
                                                   "bench:run"}
    top = recorded.top_ops(10)
    assert len(top) == 10 and all(name.startswith("%") for name, _ in top)
    # own times never add up to more than the chip was busy
    assert sum(s for _n, s in top) <= recorded.busy_s


# -- operations and bytes ------------------------------------------------------

def test_roofline_bytes_of_a_hand_counted_system():
    # 3 links, 4 flows, 7 (flow, link) elements, float32:
    # elements 7 x (4 + 4 + 4) = 84; links 3 x 2 x 4 = 24;
    # flows 4 x 3 x 4 = 48
    assert roofline.round_bytes(3, 4, 7) == 84 + 24 + 48
    assert roofline.round_bytes(3, 4, 7, itemsize=8) == 7 * 16 + 48 + 96
    assert roofline.roofline_pct(819e9, 2.0, 819e9) == pytest.approx(50.0)
    assert roofline.roofline_pct(0, 2.0, 819e9) is None
    assert roofline.roofline_pct(1e6, 0.0, 819e9) is None
    from lib import peaks
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v9", "hbm_bytes_per_s")


# -- traffic ------------------------------------------------------------------

def test_traffic_is_a_function_of_the_seed():
    flows = dict(count=600, base_seed=42)
    a = traffic.flow_pairs(flows, 128, 2**31 + 5)
    b = traffic.flow_pairs(flows, 128, 2**31 + 5)
    c = traffic.flow_pairs(flows, 128, 6)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.all(a[:, 0] != a[:, 1])
    # every seed: the same flows in another order
    assert sorted(map(tuple, a)) == sorted(map(tuple, c))
    assert sorted(map(tuple, a)) \
        == sorted(map(tuple, traffic.draw_pairs(128, 600, 42)))


def test_window_spans_leave_warm_up_out():
    from lib.spans import Spans
    spans = Spans()
    with spans.span("lap.upload"):
        pass
    assert spans.window_s("lap.upload") == []      # no window yet
    spans.window_from = time.perf_counter()
    with spans.span("lap.upload"):
        pass
    assert len(spans.window_s("lap.upload")) == 1
    assert len(spans.records["lap.upload"]) == 2
    assert spans.total_s("lap.upload") >= spans.window_s("lap.upload")[0]


# -- the reference ------------------------------------------------------------

@pytest.mark.parametrize("seed", [42, 2**31 + 1])
def test_reference_builds_the_programs_system(seed, monkeypatch):
    """Tie the independent reference to the deployment: on the tiny
    platform it flattens to the program's sizes and its float64 rates
    are the exact host solver's."""
    tiny.patch(monkeypatch)
    from drivers import _inputs
    from lib import harness
    from simgrid_tpu.ops import lmm_native
    cell = mf.Cell(tiny.tiny_manifest(), "tiny128-random.solve")
    run = harness.Run(cell, seed, 0.1, False, 0.0,
                      harness.find_devices(1))
    pairs = traffic.flow_pairs(cell.traffic["flows"], 128, seed)
    a, slot_flow = _inputs.flattened(run, pairs)
    mine = _inputs.reference_system(run, pairs, unit_penalty=False)
    assert mine.shape == (a.n_cnst, a.n_var, a.n_elem)
    exact, _, _ = lmm_native.solve_coo(
        a.e_var, a.e_cnst, a.e_w, a.c_bound, a.c_fatpipe, a.v_penalty,
        a.v_bound, 1e-9, a.n_elem, a.n_cnst, a.n_var)
    by_flow = np.zeros(len(pairs))
    by_flow[slot_flow] = np.asarray(exact)[:a.n_var]
    rates, rounds = ref.maxmin_solve(mine, eps=1e-9)
    assert rounds > 1 and rate_gap(rates, by_flow, 1.0) < 1e-12


def test_reference_on_a_system_solved_by_hand():
    # link A (cap 10): flows 0, 1;  link B (cap 4): flows 1, 2
    # max-min: B gives 1 and 2 a rate of 2 each; A leaves 8 to flow 0
    s = ref.RefSystem(np.array([0, 1, 1, 2]), np.array([0, 0, 1, 1]),
                      np.ones(4), np.array([10.0, 4.0]), np.ones(3),
                      np.full(3, -1.0))
    rates, _ = ref.maxmin_solve(s)
    assert rates.tolist() == [8.0, 2.0, 2.0]
    # a window bound of 5 on flow 0 holds it there
    rates, _ = ref.maxmin_solve(s._replace(v_bound=np.array([5., -1, -1])))
    assert rates.tolist() == [5.0, 2.0, 2.0]
    # the drain: flows 1 and 2 (size 4) end at t=2 together, then flow 0
    # (size 40, 24 left) runs alone at 10 and ends at t=4.4
    events, info = ref.drain(s, np.array([40.0, 4.0, 4.0]), 10)
    assert [f for _t, f in events] == [1, 2, 0] and info["advances"] == 2
    assert [t for t, _f in events] == pytest.approx([2.0, 2.0, 4.4])
    assert ref._round_bf16(np.array([1.00390625, 3.14159])).tolist() \
        == [1.0, 3.140625]


def test_events_gap_reads_order_dates_and_missing_flows():
    base = [(1.0, 7), (2.0, 3), (2.0, 4), (3.0, 9)]
    same = events_gap(base, [(1.0, 7), (2.0, 4), (2.0, 3), (3.0, 9)])
    assert same == dict(date_gap=0.0, unmatched=0.0, order_gap=0.0)
    late = events_gap(base, [(1.0, 7), (2.0, 3), (2.0, 4), (3.003, 9)])
    assert late["date_gap"] == pytest.approx(1e-3)
    swapped = events_gap(base, [(1.0, 3), (2.0, 7), (2.0, 4), (3.0, 9)])
    assert swapped["order_gap"] == pytest.approx(0.5)
    lost = events_gap(base, [(1.0, 7), (2.0, 4), (3.0, 9)])
    assert lost["unmatched"] == 1.0
    # the last advance's group may be cut differently at the window's edge
    assert events_gap(base, base[:3])["unmatched"] == 0.0
    assert events_gap(base, [])["date_gap"] == float("inf")
