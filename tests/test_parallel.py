"""Mesh-sharded / batched LMM solves vs the exact host oracle."""

import numpy as np
import pytest

import jax

from simgrid_tpu.ops import lmm_host, lmm_jax
from simgrid_tpu.parallel import (batched_solve, make_mesh, sharded_solve,
                                  sharded_step)
from simgrid_tpu.utils.config import config


def _random_system(rng, n_cnst, n_var, fatpipe_frac=0.2, bound_frac=0.3):
    sys = lmm_host.System()
    cnsts = []
    for _ in range(n_cnst):
        policy = (lmm_host.SharingPolicy.FATPIPE
                  if rng.random() < fatpipe_frac
                  else lmm_host.SharingPolicy.SHARED)
        c = sys.constraint_new(None, float(rng.uniform(1.0, 10.0)))
        c.sharing_policy = policy
        cnsts.append(c)
    for _ in range(n_var):
        bound = float(rng.uniform(0.1, 2.0)) if rng.random() < bound_frac else -1.0
        v = sys.variable_new(None, float(rng.uniform(0.5, 2.0)), bound,
                             rng.integers(1, 4))
        picks = rng.choice(n_cnst, size=rng.integers(1, 4), replace=False)
        for ci in picks:
            sys.expand(cnsts[ci], v, float(rng.uniform(0.5, 1.5)))
    return sys


def _oracle_values(sys):
    sys.solve_exact()
    return {id(v): v.value for v in sys.variable_set}


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sharded_matches_oracle(seed):
    rng = np.random.default_rng(seed)
    sys = _random_system(rng, 24, 60)
    flat = lmm_jax.flatten(list(sys.active_constraint_set))
    assert flat is not None
    arrays, vars_in_order = flat

    mesh = make_mesh(8, sim=1)
    eps = config["maxmin/precision"]
    values, remaining, usage, rounds = sharded_solve(arrays, eps, mesh)

    oracle = _oracle_values(sys)
    for slot, var in enumerate(vars_in_order):
        assert values[slot] == pytest.approx(oracle[id(var)], rel=1e-9, abs=1e-12)


def test_sharded_matches_single_device():
    rng = np.random.default_rng(42)
    sys = _random_system(rng, 16, 40)
    arrays, _ = lmm_jax.flatten(list(sys.active_constraint_set))
    eps = config["maxmin/precision"]

    v1, r1, u1, _ = lmm_jax.solve_arrays(arrays, eps)
    mesh = make_mesh(8, sim=1)
    v8, r8, u8, _ = sharded_solve(arrays, eps, mesh)
    np.testing.assert_allclose(v8, v1, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(r8, r1, rtol=1e-12, atol=1e-12)


def test_batched_solve_matches_oracle():
    rng = np.random.default_rng(7)
    batch_systems = [_random_system(rng, 8, 16) for _ in range(4)]
    flats = [lmm_jax.flatten(list(s.active_constraint_set))
             for s in batch_systems]
    arrays = [f[0] for f in flats]
    E = max(len(a.e_var) for a in arrays)
    C = max(len(a.c_bound) for a in arrays)
    V = max(len(a.v_penalty) for a in arrays)

    def pad(a, n, fill=0):
        out = np.full(n, fill, a.dtype)
        out[:len(a)] = a
        return out

    batch = lmm_jax.LmmArrays(
        e_var=np.stack([pad(a.e_var, E) for a in arrays]),
        e_cnst=np.stack([pad(a.e_cnst, E) for a in arrays]),
        e_w=np.stack([pad(a.e_w, E) for a in arrays]),
        c_bound=np.stack([pad(a.c_bound, C) for a in arrays]),
        c_fatpipe=np.stack([pad(a.c_fatpipe, C) for a in arrays]),
        v_penalty=np.stack([pad(a.v_penalty, V) for a in arrays]),
        v_bound=np.stack([pad(a.v_bound, V, -1.0) for a in arrays]),
        n_elem=E, n_cnst=C, n_var=V)

    mesh = make_mesh(4, sim=4)
    eps = config["maxmin/precision"]
    values, remaining, usage, rounds = batched_solve(batch, eps, mesh)

    for bi, (sys, (a, vars_in_order)) in enumerate(zip(batch_systems, flats)):
        oracle = _oracle_values(sys)
        for slot, var in enumerate(vars_in_order):
            assert values[bi, slot] == pytest.approx(
                oracle[id(var)], rel=1e-9, abs=1e-12), (bi, slot)


def test_sharded_step_runs_and_advances():
    mesh = make_mesh(8, sim=2)
    step = sharded_step(mesh)
    S, E, C, V = 2, 16, 8, 8
    rng = np.random.default_rng(3)
    e_var = np.tile(np.arange(E, dtype=np.int32) % V, (S, 1))
    e_cnst = np.tile(np.arange(E, dtype=np.int32) % C, (S, 1))
    e_w = np.ones((S, E))
    c_bound = np.full((S, C), 4.0)
    c_fatpipe = np.zeros((S, C), bool)
    v_penalty = np.ones((S, V))
    v_bound = np.full((S, V), -1.0)
    v_remains = rng.uniform(1.0, 5.0, (S, V))

    values, new_remains, dt = step(
        e_var, e_cnst, e_w, c_bound, c_fatpipe, v_penalty, v_bound,
        v_remains, np.asarray(1e-5))
    values, new_remains, dt = map(np.asarray, (values, new_remains, dt))
    assert (values > 0).all()
    assert (dt > 0).all()
    # At least one action per sim completes exactly at the min date.
    assert ((new_remains < 1e-12).any(axis=1)).all()
    assert (new_remains <= v_remains + 1e-12).all()


def test_sharded_100k_flows_matches_single_device():
    """VERDICT item 9: the BASELINE-scale system (100k flows over 16k
    links) sharded over the 8-device CPU mesh must equal the
    single-device solve (same helper the driver's dryrun_multichip
    runs, so the recorded artifact and CI check cannot drift)."""
    from simgrid_tpu.parallel.sharded import assert_sharded_matches_at_scale
    msg = assert_sharded_matches_at_scale(8)
    assert "8 devices" in msg


def test_graft_entry_script_runs_on_the_devices_that_are_there():
    """`python __graft_entry__.py` touches JAX for entry() and then
    dry-runs the mesh step in the SAME process: the dry run has to use
    the devices that process already has (here 8 virtual CPU ones)."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run([sys.executable, "__graft_entry__.py"], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "entry OK" in proc.stdout
    assert "dryrun_multichip OK on 8 devices" in proc.stdout
