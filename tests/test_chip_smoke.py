"""chip_smoke.py's contract, exercised where there is no chip: the
``--tiny`` geometry on the CPU backend runs every leg green and prints
the JSON the driver reads; a leg that raises fails the run; without a
TPU (and without ``--tiny``) nothing runs at all — and the same for
``bench.py`` without ``--cpu``.  Plus the compile-cache placement rule
of simgrid_tpu/ops/__init__.py, which only a fresh process can show."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LEGS = ["dtypes", "solve", "drain", "engine", "serve", "compile"]


def _run(argv, timeout=300, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full["JAX_PLATFORMS"] = "cpu"
    full.update(env)
    return subprocess.run([sys.executable] + argv, cwd=ROOT, env=full,
                          capture_output=True, text=True, timeout=timeout)


def test_tiny_smoke_runs_every_leg(tmp_path):
    proc = _run(["chip_smoke.py", "--tiny", "--out", str(tmp_path)])
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = [json.loads(line) for line in proc.stdout.splitlines()]
    by_leg = {r["leg"]: r for r in rows[:-1]}
    assert list(by_leg) == ["device", "inputs"] + LEGS + ["summary"]
    assert by_leg["device"]["platform"] == "cpu"
    assert "compile_cache_from" in by_leg["device"]
    for name in LEGS:
        assert by_leg[name]["ok"] is True
    assert by_leg["solve"]["first_call_s"] >= by_leg["solve"]["second_call_s"]
    assert by_leg["drain"]["dispatches"] >= 2
    assert by_leg["engine"]["opstats"]["fastpath_advances"] > 0
    assert by_leg["serve"]["warm_cache"]["plan_cache_disk_hits"] > 0
    assert by_leg["compile"]["programs"] == 12

    # the driver reads the last line: exactly these keys, no other
    verdict = rows[-1]
    assert list(verdict) == ["ok", "device"] and verdict["ok"] is True
    assert list(verdict["device"]) == ["platform", "kind", "count"]
    assert verdict["device"]["platform"] == "cpu"
    assert isinstance(verdict["device"]["kind"], str)
    assert type(verdict["device"]["count"]) is int

    summary = by_leg["summary"]
    assert summary["ok"] is True
    assert summary["legs"] == {name: True for name in LEGS}
    assert not any(summary["fallbacks"].values())
    assert len(summary["reduced"]) >= 3
    # no end-to-end number is claimed, and the line says so last
    assert list(summary)[-1] == "claim" and summary["claim"] is None


def test_failing_leg_fails_the_run(tmp_path):
    """No try/except turns a failed leg into a printed line: the run
    dies with the traceback, a non-zero code, no summary and no
    verdict."""
    code = (
        "import sys, chip_smoke\n"
        "def boom(ctx):\n"
        "    raise RuntimeError('injected leg failure')\n"
        "chip_smoke.LEGS['solve'] = boom\n"
        f"sys.exit(chip_smoke.main(['--tiny', '--out', {str(tmp_path)!r},"
        " '--legs', 'dtypes,solve']))\n")
    proc = _run(["-c", code])
    assert proc.returncode != 0
    assert "injected leg failure" in proc.stderr
    legs = [json.loads(line).get("leg")
            for line in proc.stdout.splitlines()]
    assert legs == ["device", "inputs", "dtypes"]   # and nothing after


def test_leg_subset_is_not_ok(tmp_path):
    """``ok`` means all six legs: a subset says false and exits 1."""
    proc = _run(["chip_smoke.py", "--tiny", "--out", str(tmp_path),
                 "--legs", "dtypes,compile"])
    assert proc.returncode == 1, proc.stderr[-3000:]
    rows = [json.loads(line) for line in proc.stdout.splitlines()]
    assert rows[-2]["leg"] == "summary" and rows[-2]["legs_skipped"]
    assert list(rows[-1]) == ["ok", "device"] and rows[-1]["ok"] is False


def test_no_tpu_means_no_run():
    proc = _run(["chip_smoke.py"])
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr

    proc = _run(["bench.py"])
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no accelerator" in proc.stderr


def test_compile_cache_placement(tmp_path):
    show = ("import simgrid_tpu.ops as o; import json; "
            "print(json.dumps(o.compile_cache()))")
    # placed from outside: JAX reads the variable, code sets nothing
    outside = str(tmp_path / "cc")
    path, source = json.loads(_run(
        ["-c", show], JAX_COMPILATION_CACHE_DIR=outside,
        JAX_PLATFORMS="").stdout)
    assert path == outside and "JAX_COMPILATION_CACHE_DIR" in source
    # a process pinned to the CPU backend runs without one
    path, source = json.loads(_run(["-c", show]).stdout)
    assert path is None and source.startswith("off")
    # otherwise: one fixed directory inside the checkout
    path, source = json.loads(_run(["-c", show], JAX_PLATFORMS="").stdout)
    assert path == os.path.join(ROOT, ".jax_cache")
