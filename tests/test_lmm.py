"""Unit tests for the max-min solver, mirroring the reference's Catch2
coverage (/root/reference/src/kernel/lmm/maxmin_test.cpp) plus randomized
cross-checks of the JAX backend against the exact list solver."""

import numpy as np
import pytest

from simgrid_tpu.ops import (System, SharingPolicy, make_new_maxmin_system,
                             double_equals, lmm_jax)
from simgrid_tpu.utils.config import config

EPS = 1e-5


def both_backends(test):
    return pytest.mark.parametrize("backend", ["list", "jax", "native"])(test)


def make_system(backend, selective=False):
    sys_ = make_new_maxmin_system(selective)
    if backend == "jax":
        sys_.solve_fn = lmm_jax.solve_jax
    elif backend == "native":
        from simgrid_tpu.ops import lmm_native
        if not lmm_native.available():
            pytest.skip("native solver unavailable (no g++?)")
        sys_.solve_fn = lmm_native.solve_native
    return sys_


class TestSharedSingleConstraint:
    """A variable with twice the penalty gets half of the share, etc."""

    @both_backends
    def test_variable_penalty(self, backend):
        s = make_system(backend)
        cnst = s.constraint_new(None, 3)
        rho1 = s.variable_new(None, 1)
        rho2 = s.variable_new(None, 2)
        s.expand(cnst, rho1, 1)
        s.expand(cnst, rho2, 1)
        s.solve()
        assert double_equals(rho1.value, 2, EPS)
        assert double_equals(rho2.value, 1, EPS)

    @both_backends
    def test_consumption_weight(self, backend):
        s = make_system(backend)
        cnst = s.constraint_new(None, 3)
        rho1 = s.variable_new(None, 1)
        rho2 = s.variable_new(None, 1)
        s.expand(cnst, rho1, 1)
        s.expand(cnst, rho2, 2)
        s.solve()
        assert double_equals(rho1.value, 1, EPS)
        assert double_equals(rho2.value, 1, EPS)

    @both_backends
    def test_weight_and_penalty(self, backend):
        s = make_system(backend)
        cnst = s.constraint_new(None, 20)
        rho1 = s.variable_new(None, 1)
        rho2 = s.variable_new(None, 2)
        s.expand(cnst, rho1, 1)
        s.expand(cnst, rho2, 2)
        s.solve()
        assert double_equals(rho1.value, 10, EPS)
        assert double_equals(rho2.value, 5, EPS)

    @both_backends
    def test_multiple_constraints(self, backend):
        # System: rho1 + 2*rho2 <= C1=20 ; 2*rho1 + rho3 <= C2=60
        # First constraint saturates first; rho1=2*rho2, rho1+2*rho2=C1
        s = make_system(backend)
        c1 = s.constraint_new(None, 20)
        c2 = s.constraint_new(None, 60)
        rho1 = s.variable_new(None, 1, -1, 2)
        rho2 = s.variable_new(None, 2)
        rho3 = s.variable_new(None, 1)
        s.expand(c1, rho1, 1)
        s.expand(c1, rho2, 2)
        s.expand(c2, rho1, 2)
        s.expand(c2, rho3, 1)
        s.solve()
        assert double_equals(rho1.value, 10, EPS)
        assert double_equals(rho2.value, 5, EPS)
        assert double_equals(rho3.value, 40, EPS)


class TestFatpipe:
    @both_backends
    def test_fatpipe_max_semantics(self, backend):
        # FATPIPE: max(w*rho) <= C -> every variable gets the full capacity.
        s = make_system(backend)
        cnst = s.constraint_new(None, 10)
        cnst.sharing_policy = SharingPolicy.FATPIPE
        rho1 = s.variable_new(None, 1)
        rho2 = s.variable_new(None, 1)
        s.expand(cnst, rho1, 1)
        s.expand(cnst, rho2, 1)
        s.solve()
        assert double_equals(rho1.value, 10, EPS)
        assert double_equals(rho2.value, 10, EPS)

    @both_backends
    def test_fatpipe_mixed_weights(self, backend):
        s = make_system(backend)
        cnst = s.constraint_new(None, 10)
        cnst.sharing_policy = SharingPolicy.FATPIPE
        rho1 = s.variable_new(None, 1)
        rho2 = s.variable_new(None, 1)
        s.expand(cnst, rho1, 2)   # 2*rho1 <= 10
        s.expand(cnst, rho2, 1)   # rho2 <= 10
        s.solve()
        # Both variables are saturated in the same round and therefore both
        # get min_usage-based shares (reference maxmin.cpp:578-596: the
        # var_list drains with the round's min_usage before it is
        # recomputed), even though max-semantics would allow rho2=10.
        assert double_equals(rho1.value, 5, EPS)
        assert double_equals(rho2.value, 5, EPS)


class TestVariableBounds:
    @both_backends
    def test_bounded_variable_frees_share(self, backend):
        # rho1 bounded at 1 out of C=10 shared by 2 vars: rho2 gets the rest.
        s = make_system(backend)
        cnst = s.constraint_new(None, 10)
        rho1 = s.variable_new(None, 1, 1.0)
        rho2 = s.variable_new(None, 1)
        s.expand(cnst, rho1, 1)
        s.expand(cnst, rho2, 1)
        s.solve()
        assert double_equals(rho1.value, 1, EPS)
        assert double_equals(rho2.value, 9, EPS)

    @both_backends
    def test_staged_bound_rounds(self, backend):
        # Three vars, two with different low bounds -> three fix rounds.
        s = make_system(backend)
        cnst = s.constraint_new(None, 12)
        rho1 = s.variable_new(None, 1, 1.0)
        rho2 = s.variable_new(None, 1, 3.0)
        rho3 = s.variable_new(None, 1)
        for v in (rho1, rho2, rho3):
            s.expand(cnst, v, 1)
        s.solve()
        assert double_equals(rho1.value, 1, EPS)
        assert double_equals(rho2.value, 3, EPS)
        assert double_equals(rho3.value, 8, EPS)


class TestDisabledAndUpdates:
    @both_backends
    def test_zero_penalty_variable_ignored(self, backend):
        s = make_system(backend)
        cnst = s.constraint_new(None, 10)
        rho1 = s.variable_new(None, 1)
        rho2 = s.variable_new(None, 0)   # disabled
        s.expand(cnst, rho1, 1)
        s.expand(cnst, rho2, 1)
        s.solve()
        assert double_equals(rho1.value, 10, EPS)
        assert rho2.value == 0.0

    @both_backends
    def test_update_constraint_bound_resolves(self, backend):
        s = make_system(backend)
        cnst = s.constraint_new(None, 10)
        rho1 = s.variable_new(None, 1)
        s.expand(cnst, rho1, 1)
        s.solve()
        assert double_equals(rho1.value, 10, EPS)
        s.update_constraint_bound(cnst, 4)
        s.solve()
        assert double_equals(rho1.value, 4, EPS)

    @both_backends
    def test_variable_free_redistributes(self, backend):
        s = make_system(backend)
        cnst = s.constraint_new(None, 10)
        rho1 = s.variable_new(None, 1)
        rho2 = s.variable_new(None, 1)
        s.expand(cnst, rho1, 1)
        s.expand(cnst, rho2, 1)
        s.solve()
        assert double_equals(rho1.value, 5, EPS)
        s.variable_free(rho2)
        s.solve()
        assert double_equals(rho1.value, 10, EPS)


class TestConcurrency:
    def test_concurrency_limit_stages_variables(self):
        # With a limit of 1 concurrent variable, the second one is staged
        # and only enabled when the first leaves (maxmin.hpp:104-129).
        s = make_new_maxmin_system(False)
        cnst = s.constraint_new(None, 10)
        cnst.set_concurrency_limit(1)
        rho1 = s.variable_new(None, 1)
        s.expand(cnst, rho1, 1)
        rho2 = s.variable_new(None, 1)
        s.expand(cnst, rho2, 1)
        s.solve()
        assert double_equals(rho1.value, 10, EPS)
        assert rho2.sharing_penalty == 0.0  # staged, not running
        assert rho2.staged_penalty == 1.0
        s.variable_free(rho1)
        s.solve()
        # rho2 is re-enabled once the slot frees up...
        assert rho2.sharing_penalty == 1.0
        assert rho2.staged_penalty == 0.0
        # ...but the element added while it was staged had its consumption
        # weight zeroed (reference maxmin.cpp:254), so it consumes nothing.
        assert rho2.cnsts[0].consumption_weight == 0.0
        assert rho2.value == 0.0

    def test_crosstraffic_weight_does_not_count(self):
        # Elements with weight < 1 (cross-traffic 0.05) don't consume a
        # concurrency slot (maxmin.cpp:30-34).
        s = make_new_maxmin_system(False)
        cnst = s.constraint_new(None, 10)
        cnst.set_concurrency_limit(2)
        rho1 = s.variable_new(None, 1)
        s.expand(cnst, rho1, 1)
        assert cnst.concurrency_current == 1
        ghost = s.variable_new(None, 1)
        s.expand(cnst, ghost, 0.05)
        assert ghost.sharing_penalty == 1.0   # enabled (slack was 1)
        assert cnst.concurrency_current == 1  # 0.05-weight elem counts 0


class TestSelectiveUpdate:
    @both_backends
    def test_selective_update_only_touches_modified(self, backend):
        s = make_system(backend, selective=True)
        c1 = s.constraint_new(None, 10)
        c2 = s.constraint_new(None, 8)
        rho1 = s.variable_new(None, 1)
        rho2 = s.variable_new(None, 1)
        s.expand(c1, rho1, 1)
        s.expand(c2, rho2, 1)
        s.solve()
        assert double_equals(rho1.value, 10, EPS)
        assert double_equals(rho2.value, 8, EPS)
        # Modify only c1: rho2's value must survive untouched.
        s.update_constraint_bound(c1, 6)
        assert len(list(s.modified_constraint_set)) == 1
        s.solve()
        assert double_equals(rho1.value, 6, EPS)
        assert double_equals(rho2.value, 8, EPS)

    def test_selective_update_propagates_through_shared_vars(self):
        s = make_new_maxmin_system(True)
        c1 = s.constraint_new(None, 10)
        c2 = s.constraint_new(None, 8)
        shared = s.variable_new(None, 1, -1, 2)
        s.expand(c1, shared, 1)
        s.expand(c2, shared, 1)
        s.solve()
        s.update_constraint_bound(c1, 5)
        # c2 must be in the modified set: it shares a variable with c1.
        assert set(s.modified_constraint_set) == {c1, c2}


def _random_system(rng, n_cnst, n_var, backend, p_bound=0.3, p_fat=0.2):
    s = make_system(backend)
    cnsts = [s.constraint_new(None, float(rng.uniform(1, 100))) for _ in range(n_cnst)]
    for c in cnsts:
        if rng.random() < p_fat:
            c.sharing_policy = SharingPolicy.FATPIPE
    variables = []
    for _ in range(n_var):
        bound = float(rng.uniform(0.5, 50)) if rng.random() < p_bound else -1.0
        penalty = float(rng.choice([0.5, 1.0, 1.0, 2.0, 3.0]))
        n_links = int(rng.integers(1, min(5, n_cnst) + 1))
        var = s.variable_new(None, penalty, bound, n_links)
        for ci in rng.choice(n_cnst, size=n_links, replace=False):
            s.expand(cnsts[int(ci)], var, float(rng.choice([0.5, 1.0, 1.0, 2.0])))
        variables.append(var)
    return s, variables


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("shape", [(3, 6), (10, 25), (25, 80)])
def test_jax_matches_exact_solver(seed, shape):
    """Property test: the vectorized backend reproduces the oracle."""
    rng = np.random.default_rng(seed)
    s_exact, v_exact = _random_system(rng, *shape, backend="list")
    rng = np.random.default_rng(seed)
    s_jax, v_jax = _random_system(rng, *shape, backend="jax")
    s_exact.solve()
    s_jax.solve()
    exact = np.array([v.value for v in v_exact])
    vect = np.array([v.value for v in v_jax])
    np.testing.assert_allclose(vect, exact, rtol=1e-9, atol=1e-9)


@pytest.mark.parametrize("seed", range(4))
def test_jax_matches_after_incremental_updates(seed):
    rng = np.random.default_rng(seed)
    s_exact, v_exact = _random_system(rng, 12, 30, backend="list")
    rng = np.random.default_rng(seed)
    s_jax, v_jax = _random_system(rng, 12, 30, backend="jax")
    for s, vs in ((s_exact, v_exact), (s_jax, v_jax)):
        s.solve()
        rng2 = np.random.default_rng(seed + 1000)
        for _ in range(5):
            victim = vs[int(rng2.integers(len(vs)))]
            s.update_variable_bound(victim, float(rng2.uniform(0.5, 20)))
            s.solve()
    exact = np.array([v.value for v in v_exact])
    vect = np.array([v.value for v in v_jax])
    np.testing.assert_allclose(vect, exact, rtol=1e-9, atol=1e-9)


@both_backends
def test_tiny_usage_constraint_not_pruned(backend):
    """Regression: a constraint whose only element has w/penalty <= eps must
    still be solved (it is only pruned when *touched* by a fixed variable,
    maxmin.cpp:607-609), so its variable gets bound/w, not 0."""
    s = make_system(backend)
    big = s.constraint_new(None, 10)
    tiny = s.constraint_new(None, 10)
    rho1 = s.variable_new(None, 1)
    rho2 = s.variable_new(None, 1)
    s.expand(big, rho1, 1)
    s.expand(tiny, rho2, 5e-6)   # w/penalty = 5e-6 <= maxmin/precision
    s.solve()
    assert double_equals(rho1.value, 10, EPS)
    assert rho2.value == pytest.approx(10 / 5e-6, rel=1e-9)


def test_constraint_feasibility_invariant():
    """Solved systems never violate a constraint (within precision)."""
    rng = np.random.default_rng(42)
    s, variables = _random_system(rng, 15, 40, backend="list")
    s.solve()
    for cnst in s.active_constraint_set:
        assert cnst.get_usage() <= cnst.bound * (1 + EPS) + EPS
    for var in variables:
        if var.bound > 0:
            assert var.value <= var.bound * (1 + EPS) + EPS


@pytest.mark.parametrize("rounds_mode", ["global", "local"])
@pytest.mark.parametrize("seed", range(6))
def test_round_modes_match_oracle(seed, rounds_mode):
    """Both device round strategies (one global bottleneck level per round
    vs all local-minimum constraints per round) must reproduce the exact
    list solver on systems mixing bounds, penalties and FATPIPE."""
    from simgrid_tpu.utils.config import config
    config["lmm/rounds"] = rounds_mode
    rng = np.random.default_rng(seed)
    s_exact, v_exact = _random_system(rng, 20, 60, backend="list",
                                      p_bound=0.5, p_fat=0.3)
    rng = np.random.default_rng(seed)
    s_jax, v_jax = _random_system(rng, 20, 60, backend="jax",
                                  p_bound=0.5, p_fat=0.3)
    s_exact.solve()
    s_jax.solve()
    exact = np.array([v.value for v in v_exact])
    vect = np.array([v.value for v in v_jax])
    np.testing.assert_allclose(vect, exact, rtol=1e-9, atol=1e-9)


@pytest.mark.parametrize("rounds_mode", ["global", "local"])
@pytest.mark.parametrize("seed,n_c,n_v,p_bound,p_fat", [
    (10, 100, 300, 0.0, 0.0),    # plain shared constraints at scale
    (11, 100, 300, 0.8, 0.0),    # bound-heavy (bound-first rule stress)
    (12, 100, 300, 0.0, 0.8),    # FATPIPE-heavy (max-sharing stress)
    (13, 150, 400, 0.5, 0.5),    # heavy mix of both
    (14, 60, 600, 0.3, 0.2),     # many variables per constraint
])
@pytest.mark.parametrize("layout", ["coo", "ell"])
def test_round_modes_match_oracle_large(seed, n_c, n_v, p_bound, p_fat,
                                        rounds_mode, layout):
    """Larger randomized systems with heavy bound/FATPIPE mixes: both round
    strategies must still agree with the exact list solver, on BOTH
    element layouts (the accelerator default is ELL; CPU's is COO —
    forcing each makes the matrix cover what the TPU actually runs)."""
    from simgrid_tpu.utils.config import config
    config["lmm/rounds"] = rounds_mode
    config["lmm/layout"] = layout
    try:
        rng = np.random.default_rng(seed)
        s_exact, v_exact = _random_system(rng, n_c, n_v, backend="list",
                                          p_bound=p_bound, p_fat=p_fat)
        rng = np.random.default_rng(seed)
        s_jax, v_jax = _random_system(rng, n_c, n_v, backend="jax",
                                      p_bound=p_bound, p_fat=p_fat)
        s_exact.solve()
        s_jax.solve()
    finally:
        config["lmm/layout"] = "auto"
    exact = np.array([v.value for v in v_exact])
    vect = np.array([v.value for v in v_jax])
    np.testing.assert_allclose(vect, exact, rtol=1e-9, atol=1e-9)


def _bench_arrays(rng, n_c, n_v, deg, dtype):
    """maxmin_bench-style COO system (the exact generator bench.py times,
    so the f32-convergence regression covers the benched system)."""
    from bench import build_arrays
    return build_arrays(rng, n_c, n_v, deg, dtype)


def test_chunked_solve_matches_single_dispatch():
    """Chunked execution (tiny chunk => many dispatches with carry
    continuation) must give the same answer as one big dispatch."""
    from simgrid_tpu.ops.lmm_jax import solve_arrays
    arrays = _bench_arrays(np.random.default_rng(5), 50, 200, 3, np.float64)
    v1, r1, u1, rounds1 = solve_arrays(arrays, 1e-9, parallel_rounds=False)
    v2, r2, u2, rounds2 = solve_arrays(arrays, 1e-9, parallel_rounds=False,
                                       chunk=3)
    assert rounds1 == rounds2
    np.testing.assert_array_equal(v1, v2)
    np.testing.assert_array_equal(r1, r2)
    np.testing.assert_array_equal(u1, u2)


@pytest.mark.parametrize("rounds_mode", [False, True])
@pytest.mark.parametrize("dtype,eps", [(np.float64, 1e-9),
                                       (np.float32, 1e-5)])
def test_compaction_bit_identical(rounds_mode, dtype, eps):
    """Active-set compaction (lmm/compact) shrinks the element list AND
    the variable/constraint rows between chunks; the result must be
    bit-identical to the dense run — retired rows only ever contribute
    exact identities (0.0 to adds/maxes, inf to mins), and a retired
    row's state is frozen the moment its last live element dies."""
    from simgrid_tpu.utils.config import config
    from simgrid_tpu.ops.lmm_jax import solve_arrays
    arrays = _bench_arrays(np.random.default_rng(11), 600, 2000, 3,
                           dtype)
    # exercise the bound-first rule and FATPIPE rows through the
    # shrinking system too
    arrays.v_bound[:400] = 0.25
    arrays.c_fatpipe[:100] = True
    try:
        config["lmm/compact"] = "off"
        dense = solve_arrays(arrays, eps, parallel_rounds=rounds_mode)
        config["lmm/compact"] = "on"
        packed = solve_arrays(arrays, eps, parallel_rounds=rounds_mode)
    finally:
        config["lmm/compact"] = "auto"
    assert dense[3] == packed[3]
    for d, p in zip(dense[:3], packed[:3]):
        np.testing.assert_array_equal(np.asarray(d), np.asarray(p))


# Sequential rounds at the full 100k scale run the fixpoint one
# constraint-round at a time (~minutes of single-core compute) — the
# full-scale instance is `slow` (tier-2); the reference sequential
# semantics stay in tier-1 at a scale that still needs >1k rounds.
@pytest.mark.parametrize("rounds_mode,n_c,n_v", [
    pytest.param(False, 16384, 100_000, marks=pytest.mark.slow),
    (False, 2048, 12_500),
    (True, 16384, 100_000),
])
def test_f32_convergence_100k_flows(rounds_mode, n_c, n_v):
    """The round-1 TPU failure mode: a 100k-flow / 16k-link system in f32
    must converge (stuck constraints with no live variables are pruned
    even when f32 rounding keeps their usage residual above eps) — and
    produce a feasible, near-f64 solution."""
    from simgrid_tpu.ops.lmm_jax import solve_arrays
    deg = 4
    arrays32 = _bench_arrays(np.random.default_rng(9), n_c, n_v, deg,
                             np.float32)
    v32, r32, u32, rounds = solve_arrays(arrays32, 1e-5,
                                         parallel_rounds=rounds_mode)
    assert rounds < 100_000
    assert np.all(v32[:n_v] > 0)
    # feasibility: per-constraint usage within bound (+f32 slack)
    used = np.zeros(len(arrays32.c_bound), np.float64)
    np.add.at(used, arrays32.e_cnst[:n_v * deg],
              (arrays32.e_w[:n_v * deg].astype(np.float64)
               * v32[arrays32.e_var[:n_v * deg]].astype(np.float64)))
    assert np.all(used <= arrays32.c_bound.astype(np.float64) * (1 + 1e-3)
                  + 1e-3)


from simgrid_tpu.ops.bench_systems import build_bench_system as \
    _bench_system_python  # shared with tools/measure_baseline.py


def test_native_bench_matches_python_oracle():
    """The native maxmin_bench binary's 'test' mode output (first 16
    variable values, 2 iterations of the small class) must match the
    Python solver run on the identically-constructed system."""
    import os
    import subprocess
    from simgrid_tpu.ops import lmm_native

    if not lmm_native.available():
        pytest.skip("native solver unavailable")
    bench = os.path.join(lmm_native._NATIVE_DIR, "maxmin_bench")
    # make is a no-op when the binary is newer than its sources
    subprocess.run(["make", "-C", lmm_native._NATIVE_DIR, "maxmin_bench"],
                   check=True, capture_output=True)
    out = subprocess.run([bench, "small", "2", "test"], check=True,
                         capture_output=True, text=True).stdout
    native_vals = [float(line.split("=")[1]) for line in out.splitlines()
                   if line.startswith("var ")]
    assert len(native_vals) == 20

    config["maxmin/precision"] = 1e-5
    py_vals = []
    for it in range(2):
        s, variables = _bench_system_python(
            # small class: nb_elem = (1<<1) + (1<<(8*2/10)) = 4 (int division,
            # maxmin_bench.cpp:172)
            seed=it + 1, nb_cnst=10, nb_var=10, nb_elem=4,
            pw_base_limit=1, pw_max_limit=2, rate_no_limit=0.2, max_share=2)
        s.solve_exact()
        py_vals.extend(v.value for v in variables)
    np.testing.assert_allclose(native_vals, py_vals, rtol=1e-6, atol=1e-9)


@pytest.mark.parametrize("rounds_mode", ["global", "local"])
def test_ell_layout_matches_coo(rounds_mode):
    """The ELL (dense padded rows) kernel is the accelerator-native
    layout; it must reproduce the COO kernel's solutions and round
    counts exactly on randomized systems (same algorithm, different
    storage)."""
    from simgrid_tpu.ops import lmm_jax as lj

    parallel = rounds_mode == "local"
    for seed in range(6):
        rng = np.random.default_rng(seed)
        n_c, n_v, deg = 40, 120, 3
        e_var = np.repeat(np.arange(n_v, dtype=np.int32), deg)
        e_cnst = rng.integers(0, n_c, size=n_v * deg).astype(np.int32)
        e_w = rng.uniform(0.5, 1.5, size=n_v * deg)
        E, C, V = lj._bucket(n_v * deg), lj._bucket(n_c), lj._bucket(n_v)
        arrays = lj.LmmArrays(
            e_var=np.resize(e_var, E).astype(np.int32),
            e_cnst=np.resize(e_cnst, E).astype(np.int32),
            e_w=np.concatenate([e_w, np.zeros(E - n_v * deg)]),
            c_bound=np.concatenate([rng.uniform(1, 10, n_c),
                                    np.zeros(C - n_c)]),
            c_fatpipe=np.zeros(C, bool),
            v_penalty=np.concatenate([np.ones(n_v), np.zeros(V - n_v)]),
            v_bound=np.full(V, -1.0),
            n_elem=n_v * deg, n_cnst=n_c, n_var=n_v)
        # resized e_var/e_cnst padding is inert (zero weights)
        try:
            config["lmm/layout"] = "coo"
            v1, r1, u1, rounds1 = lj.solve_arrays(
                arrays, 1e-9, parallel_rounds=parallel)
            config["lmm/layout"] = "ell"
            v2, r2, u2, rounds2 = lj.solve_arrays(
                arrays, 1e-9, parallel_rounds=parallel)
        finally:
            config["lmm/layout"] = "auto"
        assert rounds1 == rounds2
        np.testing.assert_allclose(v1[:n_v], v2[:n_v], rtol=1e-12)
        np.testing.assert_allclose(r1[:n_c], r2[:n_c], rtol=1e-12)


def test_ell_conversion_refuses_skew():
    """A backbone-style constraint touching every flow must fall back
    to COO (the ELL row would explode)."""
    from simgrid_tpu.ops import lmm_jax as lj

    n_v = 2000
    e_var = np.arange(n_v, dtype=np.int32)
    e_cnst = np.zeros(n_v, np.int32)     # all on one constraint
    arrays = lj.LmmArrays(
        e_var=e_var, e_cnst=e_cnst, e_w=np.ones(n_v),
        c_bound=np.array([5.0]), c_fatpipe=np.zeros(1, bool),
        v_penalty=np.ones(n_v), v_bound=np.full(n_v, -1.0),
        n_elem=n_v, n_cnst=1, n_var=n_v)
    assert lj.ell_from_arrays(arrays) is None


@pytest.mark.parametrize("rounds_mode", ["global", "local"])
@pytest.mark.parametrize("layout", ["coo", "ell"])
def test_unrolled_matches_while_loop(rounds_mode, layout):
    """The unrolled straight-line round loop (the accelerator mode that
    dodges gather-in-while_loop lowering pathologies) must reproduce
    the lax.while_loop solve exactly: same values, same round counts,
    including chunk-boundary carry continuation."""
    from simgrid_tpu.ops import lmm_jax as lj

    parallel = rounds_mode == "local"
    arrays = _bench_arrays(np.random.default_rng(11), 60, 250, 3,
                           np.float64)
    try:
        config["lmm/layout"] = layout
        v1, r1, u1, rounds1 = lj.solve_arrays(
            arrays, 1e-9, parallel_rounds=parallel, unroll=False)
        # chunk smaller than the round count to exercise the carry path
        v2, r2, u2, rounds2 = lj.solve_arrays(
            arrays, 1e-9, parallel_rounds=parallel, unroll=True, chunk=4)
    finally:
        config["lmm/layout"] = "auto"
    assert rounds1 == rounds2
    np.testing.assert_array_equal(v1, v2)
    np.testing.assert_array_equal(r1, r2)
    np.testing.assert_array_equal(u1, u2)


def test_array_view_tracks_structural_churn():
    """Property test for the incremental ArrayView: a full-update
    system driven through random structural churn (new flows, frees,
    enable/disable via penalty, bound updates) must keep producing the
    exact list-solver's solution on every re-solve."""
    from simgrid_tpu.ops import lmm_jax as lj
    from simgrid_tpu.ops.lmm_host import System

    rng = np.random.default_rng(3)
    s = System(selective_update=False)
    lj.install(s, "jax")
    cnsts = [s.constraint_new(None, float(rng.uniform(1, 10)))
             for _ in range(25)]
    live = []

    def add_flow():
        deg = int(rng.integers(1, 4))
        var = s.variable_new(None, float(rng.uniform(0.5, 2.0)), -1.0, deg)
        for ci in rng.choice(len(cnsts), size=deg, replace=False):
            s.expand(cnsts[ci], var, float(rng.uniform(0.5, 1.5)))
        live.append(var)

    def check():
        s.solve()
        got = [(v.value) for v in live]
        # re-solve the same state on a fresh exact system
        s2 = System(selective_update=False)
        c2 = [s2.constraint_new(None, c.bound) for c in cnsts]
        idx = {id(c): i for i, c in enumerate(cnsts)}
        v2 = []
        for v in live:
            nv = s2.variable_new(None, v.sharing_penalty or v.staged_penalty,
                                 v.bound, len(v.cnsts))
            for elem in v.cnsts:
                s2.expand(c2[idx[id(elem.constraint)]], nv,
                          elem.consumption_weight)
            v2.append(nv)
        s2.solve_exact()
        np.testing.assert_allclose(got, [v.value for v in v2],
                                   rtol=1e-9, atol=1e-9)

    for _ in range(8):
        add_flow()
    check()
    for round_ in range(12):
        op = rng.integers(0, 4)
        if op == 0 or len(live) < 4:
            add_flow()
        elif op == 1:
            victim = live.pop(int(rng.integers(len(live))))
            s.variable_free(victim)
        elif op == 2:
            v = live[int(rng.integers(len(live)))]
            s.update_variable_bound(v, float(rng.uniform(0.5, 5)))
        else:
            s.update_constraint_bound(
                cnsts[int(rng.integers(len(cnsts)))],
                float(rng.uniform(1, 10)))
        check()


def test_array_view_sees_post_solve_fatpipe():
    """A constraint whose sharing_policy is set to FATPIPE after the
    view already exists must be solved with max-sharing (regression:
    the view cached c_fatpipe at creation only)."""
    from simgrid_tpu.ops import lmm_jax as lj
    from simgrid_tpu.ops.lmm_host import SharingPolicy, System

    s = System(selective_update=False)
    lj.install(s, "jax")
    c = s.constraint_new(None, 10.0)
    v1 = s.variable_new(None, 1.0)
    s.expand(c, v1, 1.0)
    s.solve()          # view created now, c is SHARED
    c2 = s.constraint_new(None, 6.0)
    c2.sharing_policy = SharingPolicy.FATPIPE   # post-view mutation
    v2 = s.variable_new(None, 1.0)
    v3 = s.variable_new(None, 1.0)
    s.expand(c2, v2, 1.0)
    s.expand(c2, v3, 1.0)
    s.solve()
    # FATPIPE: both variables get the full bound, not bound/2
    assert v2.value == pytest.approx(6.0, rel=1e-9)
    assert v3.value == pytest.approx(6.0, rel=1e-9)


def test_limit_raise_wakes_staged_variable():
    """Raising a concurrency limit must (eventually) enable a staged
    variable — the waiter registry must not lose it (regression for
    the blocker-cache wake-up path)."""
    from simgrid_tpu.ops.lmm_host import System

    s = System(selective_update=False)
    c = s.constraint_new(None, 10.0)
    c.set_concurrency_limit(1)
    v1 = s.variable_new(None, 1.0, -1.0, 1)
    s.expand(c, v1, 1.0)          # takes the only slot
    v2 = s.variable_new(None, 1.0, -1.0, 1)
    s.expand(c, v2, 1.0)          # staged: no slack
    assert v2.sharing_penalty == 0 and v2.staged_penalty > 0
    c.set_concurrency_limit(4)
    assert v2.sharing_penalty > 0, "staged variable never woke up"
    # NB: the staged expand zeroed the element weight (reference
    # maxmin.cpp:255 does the same), so only enablement is asserted.
    s.solve_exact()
    assert v1.value > 0


@pytest.mark.parametrize("dtype,eps", [(np.float64, 1e-9),
                                       (np.float32, 1e-5)])
def test_ell_chain_matches_dense(dtype, eps):
    """The device-resident compaction chain (lmm/chain) partitions
    variable rows live-first between stages; the partition is stable
    and dropped rows only contribute exact identities, so the chain
    must agree with the dense ELL run (up to summation-order ulps in
    the init row-sums) and converge in the same number of rounds.
    Also pins _vc_round_body to fixpoint_ell's body_local_vc.

    Tolerances: the chain is a DIFFERENT compiled program than the
    dense chunk, and XLA may reassociate float reductions differently
    per program, so agreement is up to reduction-order ulps — plus one
    eps-clamp width on `remaining` (an ulp at the clamp threshold
    flips a value to exact 0.0)."""
    from simgrid_tpu.utils.config import config
    from simgrid_tpu.ops.lmm_jax import solve_arrays
    # big enough to trigger the chain (V0 >= 2 * _CHAIN_MIN_V after
    # pow2 bucketing) but CPU-fast; deg 3 keeps the ELL width small
    arrays = _bench_arrays(np.random.default_rng(13), 4096, 33000, 3,
                           dtype)
    try:
        config["lmm/layout"] = "ell"
        config["lmm/chain"] = "off"
        dense = solve_arrays(arrays, eps, parallel_rounds=True)
        config["lmm/chain"] = "on"
        chain = solve_arrays(arrays, eps, parallel_rounds=True)
    finally:
        config["lmm/layout"] = "auto"
        config["lmm/chain"] = "auto"
    assert dense[3] == chain[3], "round counts diverged"
    rtol = 1e-4 if dtype is np.float32 else 1e-9
    atol = 2 * eps * float(np.max(arrays.c_bound))
    for d, p in zip(dense[:3], chain[:3]):
        np.testing.assert_allclose(np.asarray(d), np.asarray(p),
                                   rtol=rtol, atol=atol)


def test_ell_chain_overflow_falls_back():
    """A chain stage that cannot halve the live set within its round
    cap must flag overflow and the solve must fall back to the dense
    path with a correct result."""
    from simgrid_tpu.utils.config import config
    from simgrid_tpu.ops import lmm_jax
    from simgrid_tpu.ops.lmm_jax import solve_arrays
    arrays = _bench_arrays(np.random.default_rng(17), 4096, 33000, 3,
                           np.float64)
    cap = lmm_jax._CHAIN_STAGE_CAP
    try:
        config["lmm/layout"] = "ell"
        config["lmm/chain"] = "off"
        dense = solve_arrays(arrays, 1e-9, parallel_rounds=True)
        config["lmm/chain"] = "on"
        lmm_jax._CHAIN_STAGE_CAP = 1   # force overflow
        chain = solve_arrays(arrays, 1e-9, parallel_rounds=True)
    finally:
        lmm_jax._CHAIN_STAGE_CAP = cap
        config["lmm/layout"] = "auto"
        config["lmm/chain"] = "auto"
    for d, p in zip(dense[:3], chain[:3]):
        np.testing.assert_allclose(np.asarray(d), np.asarray(p))
