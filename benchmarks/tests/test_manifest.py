"""Every string the driver reads is inside its limits BEFORE a run is
spent on it (PR 22 was refused for a ``source`` of over 200 characters),
and every file a cell names is there."""

import json
import os
import re

import pytest

from lib import manifest as mf

M = mf.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|head")


def line_ok(s, most=200):
    return (isinstance(s, str) and 1 <= len(s) <= most and s.isascii()
            and s.isprintable() and "\t" not in s and "\n" not in s)


def metrics():
    return M["end_to_end"] + M["per_layer"]


def test_top_level_keys_and_sizes():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(mf.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert 1 <= len(M["command"]) <= 32
    assert all(line_ok(w) for w in M["command"])
    assert 1 <= len(M["paths"]) <= 16
    for p in M["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(mf.ROOT, p))
    # the command names no file of the repo outside paths
    for word in M["command"]:
        if os.path.exists(os.path.join(mf.ROOT, word)):
            assert any(word.startswith(p + "/") for p in M["paths"]), word
    assert 1 <= len(M["configs"]) <= 24 and 1 <= len(M["workloads"]) <= 24
    assert 1 <= len(M["end_to_end"]) <= 16
    assert 1 <= len(M["per_layer"]) <= 128


@pytest.mark.parametrize("config", M["configs"], ids=lambda c: c["name"])
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert line_ok(config["source"]) and line_ok(config["why"])
    assert any(config["file"].startswith(p + "/") for p in M["paths"])
    assert len(config["reduced"]) <= 16
    for key in config["reduced"]:
        assert NAME.match(key) and not WIDTHS.search(key), key
    body = mf.load_json(os.path.join(mf.ROOT, config["file"]))
    assert body["source"] == config["source"]
    assert body["reduced"] == config["reduced"]
    assert all(key in body for key in config["reduced"])
    assert os.path.isfile(os.path.join(
        mf.BENCH, "configs", body["reference"] + ".py"))
    assert any(w["config"] == config["name"] for w in M["workloads"])
    files = [c["file"] for c in M["configs"]]
    assert files.count(config["file"]) == 1


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["config"] in {c["name"] for c in M["configs"]}
    assert cell["chips"] in (1, 4) and line_ok(cell["why"])
    loaded = mf.Cell(M, cell["name"])           # loads every file
    for hook in ("setup", "window", "release", "check", "end_to_end"):
        assert callable(getattr(loaded.driver, hook)), hook
    reported = {m["name"] for m in loaded.end_to_end()}
    assert "setup_s" in reported and len(reported) >= 2
    assert loaded.per_layer(), "a cell reports a per-layer metric"
    for m in loaded.end_to_end():
        if m["name"] != "setup_s":
            assert m["name"] in _driver_metrics(loaded), m["name"]
    assert set(loaded.traffic["limits"]), "correct needs its limits"


def _driver_metrics(cell):
    src = open(cell.driver.__file__, encoding="ascii").read()
    return set(re.findall(r'"([a-z_0-9.]+)":', src.split(
        "def end_to_end")[1]))


def test_cells_are_unique_and_few_take_four_chips():
    names = [w["name"] for w in M["workloads"]]
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(set(names)) == len(names) and len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, len(names) // 2)


@pytest.mark.parametrize("metric", metrics(), ids=lambda m: m["name"])
def test_metric_entry(metric):
    e2e = metric in M["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if e2e else {"layer", "moves"})
    assert set(metric) - {"workloads"} == keys
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {w["name"] for w in M["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if e2e:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
        return
    assert line_ok(metric["layer"])
    moved = {m["name"]: m for m in M["end_to_end"]}[metric["moves"]]
    # each cell that reports this metric reports what it moves
    assert set(metric.get("workloads", cells)) \
        <= set(moved.get("workloads", cells))
    if "workloads" not in metric:
        assert "workloads" not in moved
    reader = os.path.join(mf.BENCH, "metrics", metric["name"] + ".py")
    assert os.path.isfile(reader), "a per-layer metric is a reader"
    if metric["name"].endswith("_roofline") or "roofline" in metric["name"]:
        assert metric["unit"] == "%"


def test_names_are_unique_and_setup_s_is_there():
    names = [m["name"] for m in metrics()]
    assert len(set(names)) == len(names)
    setup = [m for m in M["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup[0]["bound"] <= 0.25


def test_files_under_paths_are_ascii_and_well_named():
    for path in M["paths"]:
        for folder, dirs, files in os.walk(os.path.join(mf.ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), mf.ROOT)
                assert PATH.match(rel), rel
                if name.endswith((".json", ".py", ".md")):
                    with open(os.path.join(folder, name), "rb") as f:
                        assert f.read().isascii(), rel


def test_traffic_is_data_and_limits_are_numbers():
    folder = os.path.join(mf.BENCH, "traffic")
    for name in os.listdir(folder):
        assert name.endswith(".json"), name
        body = json.load(open(os.path.join(folder, name)))
        assert os.path.isfile(os.path.join(
            mf.BENCH, "drivers", body["driver"] + ".py"))
        assert all(isinstance(v, (int, float))
                   for v in body["limits"].values())
