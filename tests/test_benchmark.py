"""The benchmark's own tests (``benchmarks/tests``: the manifest's limits,
the CPU rehearsal of every driver, the trace and scope reductions, the
controls of ``correct``) under tier-1's eye: each file runs in a
process of its own, as ``python -m pytest benchmarks/tests`` runs them,
because they put ``benchmarks/`` on ``sys.path`` and pin their own JAX
platform."""

import glob
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILES = sorted(os.path.relpath(p, ROOT) for p in glob.glob(
    os.path.join(ROOT, "benchmarks", "tests", "test_*.py")))


def test_the_benchmark_has_its_tests():
    assert len(FILES) >= 4, FILES


@pytest.mark.parametrize("path", FILES, ids=[
    os.path.basename(p)[len("test_"):-len(".py")] for p in FILES])
def test_benchmark_tests_pass(path):
    done = subprocess.run(
        [sys.executable, "-m", "pytest", path, "-q", "-p",
         "no:cacheprovider", "-p", "no:randomly"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, (done.stdout[-4000:]
                                  + done.stderr[-2000:])
