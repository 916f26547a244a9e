"""Test harness config: run JAX on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding tests run over
virtual CPU devices instead (the driver separately dry-run-compiles the
multi-chip path via __graft_entry__.dryrun_multichip).
"""

import os
import sys

# Repo root on sys.path: tests import helpers from root-level modules
# (e.g. bench.build_arrays) regardless of how pytest was invoked.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Force CPU even when the environment points JAX at an accelerator: the
# suite needs 8 virtual devices for mesh tests, and host-solver
# comparisons need IEEE f64.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# no persistent compile cache under test, wherever the environment
# points it: the suite must not load executables another machine wrote
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: full-scale property instances excluded from the tier-1 "
        "budget (`-m 'not slow'`); run explicitly before releases")


@pytest.fixture(autouse=True, scope="module")
def _drop_compiled_programs():
    """Drop every compiled executable between test modules.  In one
    process the suite otherwise dies with SIGSEGV inside XLA:CPU's
    compiler at tests/test_lmm_batch.py (after ~780 tests' worth of
    resident executables; every file passes alone).  With this the
    tier-1 command reaches the end of the suite."""
    yield
    jax.clear_caches()


@pytest.fixture(autouse=True)
def _fresh_config():
    """Snapshot/restore the global flag registry around each test
    (both values and defaults: model initializers use set_default)."""
    from simgrid_tpu.utils.config import config
    saved = {name: (f.value, f.default, f.touched)
             for name, f in config._flags.items()}
    yield
    for name, (value, default, touched) in saved.items():
        flag = config._flags[name]
        flag.value = value
        flag.default = default
        flag.touched = touched
