"""The COO round's budget of element-wide indexed ops, entry's, and the
exactness of what keeps them small (ISSUES 28, 32).

On the chip one gather or scatter over the element list costs 9-11 ms
at config #4's size whatever it moves (PERF.md §5), so the number of
them a round issues IS its cost.  The first half counts them in the
jaxpr of one round and pins what `lmm_jax.fixpoint` reaches, so an
edit that adds a pass fails here, on a CPU.  The second half holds the
things the count rests on: the bound block skipped when no bound
binds, element liveness carried (and rebuilt from a mid-solve carry),
the `lax.cond` under `vmap`, and entry's one gather and one scatter
against a plain numpy statement of maxmin.cpp's start."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from bench import build_arrays
from simgrid_tpu.ops import (SharingPolicy, lmm_jax, make_new_maxmin_system,
                             opstats)
from simgrid_tpu.ops.lmm_batch import solve_arrays_batch
from simgrid_tpu.parallel.sharded import make_mesh, sharded_solve
from simgrid_tpu.utils.config import config

INDEXED = ("gather", "scatter", "scatter-add", "scatter_add", "scatter-min",
           "scatter_min", "scatter-max", "scatter_max", "scatter-mul",
           "scatter_mul")

#: sizes no two of which coincide, so "element-wide" is a size test
N_C, N_V, DEG = 64, 256, 3


def system(dtype=np.float64, seed=3, bounds=None, fatpipe=False):
    """A bench-class COO system; ``bounds`` = "bind" | "never" | None."""
    rng = np.random.default_rng(seed)
    a = build_arrays(rng, N_C, N_V, DEG, dtype)
    if bounds == "bind":
        a.v_bound[:N_V // 2] = rng.uniform(0.01, 0.5, N_V // 2)
    elif bounds == "never":
        a.v_bound[:N_V // 2] = 1e6
    if fatpipe:
        a.c_fatpipe[:N_C // 4] = True
    return a


# ---------------------------------------------------------------------------
# the count
# ---------------------------------------------------------------------------

def sub_jaxprs(eqn):
    for val in eqn.params.values():
        for v in (val if isinstance(val, (tuple, list)) else (val,)):
            if hasattr(v, "jaxpr"):       # ClosedJaxpr
                yield v.jaxpr
            elif hasattr(v, "eqns"):
                yield v


def whole(count):
    """A count with the branches of its conds summed in."""
    outside, conds = count
    return outside + sum(map(sum, conds))


def count_indexed(jaxpr, n_elem):
    """(element-wide gathers and scatters outside any cond, the same
    per branch of each cond met): conds are not summed in, so a block
    that is skipped at run time is budgeted on its own."""
    outside, conds = 0, []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in INDEXED:
            sizes = [int(np.prod(v.aval.shape))
                     for v in list(eqn.invars) + list(eqn.outvars)
                     if hasattr(v, "aval")]
            if max(sizes) >= n_elem:
                outside += 1
        elif name == "cond":
            conds.append([whole(count_indexed(br.jaxpr, n_elem))
                          for br in eqn.params["branches"]])
        else:
            for sub in sub_jaxprs(eqn):
                o, c = count_indexed(sub, n_elem)
                outside += o
                conds += c
    return outside, conds


def traced(parallel_rounds, has_bounds, has_fatpipe, carried):
    """(jaxpr of one `fixpoint` call, its element count); ``carried``:
    the call a chunked caller makes, its 6-tuple carry handed back."""
    a = system(bounds="bind" if has_bounds else None, fatpipe=has_fatpipe)
    n_c, n_v = len(a.c_bound), len(a.v_penalty)
    n_elem = len(a.e_var)
    assert n_elem not in (n_c, n_v) and n_elem > 3 * n_c
    dtype = a.e_w.dtype

    def run(carry, *args):
        return lmm_jax.fixpoint(*args, jnp.asarray(1e-9, dtype), n_c, n_v,
                                parallel_rounds=parallel_rounds, carry=carry,
                                return_carry=True, has_bounds=has_bounds,
                                has_fatpipe=has_fatpipe)

    carry = (np.zeros(n_v, dtype), np.zeros(n_v, bool), a.c_bound,
             np.ones(n_c, dtype), np.ones(n_c, bool),
             np.int32(0)) if carried else None
    closed = jax.make_jaxpr(run)(carry, a.e_var, a.e_cnst, a.e_w, a.c_bound,
                                 a.c_fatpipe, a.v_penalty, a.v_bound)
    return closed.jaxpr, n_elem


def round_loops(jaxpr):
    return [e for e in jaxpr.eqns if e.primitive.name == "while"]


def round_and_entry(parallel_rounds, has_bounds, has_fatpipe, carried):
    jaxpr, n_elem = traced(parallel_rounds, has_bounds, has_fatpipe, carried)
    loops = round_loops(jaxpr)
    # under the ladder's floor: exactly one round loop, no partition
    assert len(loops) == 1
    assert not [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    body = count_indexed(loops[0].params["body_jaxpr"].jaxpr, n_elem)
    entry = count_indexed(jaxpr, n_elem)[0] - body[0]
    return body, entry


#: (local rounds, bounds, FATPIPE) -> (ops of a round outside any cond,
#: ops of the cond's [skipped, taken] branches or None, ops at a cold
#: call's entry).  Local: neighmin 4 (gather by e_cnst, scatter to v,
#: gather by e_var, scatter to c) + level 2 + update 2 (one gather, one
#: 3-wide scatter).  Entry: one gather by e_var (the penalty, the carry's
#: v_fixed in its sign) and one scatter by e_cnst (usage and the live
#: count, 2-wide); FATPIPE adds the max of a cold call.
BUDGETS = {
    (True, False, False): (8, None, 2),
    (True, False, True): (9, None, 3),
    (True, True, False): (8, [0, 8], 2),
    (True, True, True): (9, [0, 8], 3),
    (False, False, False): (4, None, 2),
    (False, False, True): (5, None, 3),
    (False, True, False): (4, None, 2),
    (False, True, True): (5, None, 3),
}
#: entry of a carried call (the carry brings its usage): the gather and
#: the live count's scatter, FATPIPE or not
CARRIED_ENTRY = 2

#: what one partition may issue over the list it cuts ([not taken: a
#: slice, taken]) as (indexed ops, sorts): the scatter that builds the
#: live-first permutation and ONE gather of the kept head, e_var,
#: e_cnst, e_w and e_upen side by side in its rows (the head's liveness
#: needs none); no sort (6.8 ms against 16.4 on the chip, but 1.6-1.7x
#: the program's compile time: PERF.md §5)
PARTITION_BUDGET = [(0, 0), (2, 0)]


def count_sorts(jaxpr):
    return sum(e.primitive.name == "sort" for e in jaxpr.eqns) + sum(
        count_sorts(sub) for e in jaxpr.eqns for sub in sub_jaxprs(e))


def within(got, outside, block):
    """One round body's count against its budget."""
    got_outside, conds = got
    assert got_outside <= outside
    if block is None:
        assert conds == []
    else:
        assert len(conds) == 1
        assert sorted(conds[0])[0] <= block[0]
        assert sorted(conds[0])[1] <= block[1]
        # under vmap both sides run: no dearer than the 12 ops the
        # bound block cost before it stood behind a cond
        assert 2 + sum(conds[0]) <= 12


CALLS = pytest.mark.parametrize("carried", [False, True],
                                ids=["cold", "carried"])


@CALLS
@pytest.mark.parametrize("parallel_rounds,has_bounds,has_fatpipe",
                         sorted(BUDGETS))
def test_round_issues_no_more_indexed_ops_than_budgeted(
        parallel_rounds, has_bounds, has_fatpipe, carried):
    outside, block, entry = BUDGETS[parallel_rounds, has_bounds, has_fatpipe]
    got, got_entry = round_and_entry(parallel_rounds, has_bounds,
                                     has_fatpipe, carried)
    within(got, outside, block)
    assert got_entry <= (CARRIED_ENTRY if carried else entry)


@CALLS
@pytest.mark.parametrize("parallel_rounds,has_bounds,has_fatpipe",
                         sorted(BUDGETS))
def test_every_rung_holds_the_rounds_budget_and_a_partition_its_own(
        parallel_rounds, has_bounds, has_fatpipe, carried, monkeypatch):
    """The ladder (its floor brought down to these thousand elements)
    is one round loop a rung, each body within the round's budget at
    ITS size, and between two rungs one cond: a slice, or a partition
    within `PARTITION_BUDGET`.  Entry is the single loop's."""
    monkeypatch.setattr(lmm_jax, "_LADDER_MIN_ELEMS", 32)
    outside, block, entry = BUDGETS[parallel_rounds, has_bounds, has_fatpipe]
    if carried:
        entry = CARRIED_ENTRY
    jaxpr, n_elem = traced(parallel_rounds, has_bounds, has_fatpipe, carried)
    sizes = lmm_jax._ladder_sizes((n_elem,))
    loops = round_loops(jaxpr)
    assert len(loops) == len(sizes) >= 3
    in_loops = 0
    for loop, size in zip(loops, sizes):
        body = loop.params["body_jaxpr"].jaxpr
        got = count_indexed(body, size)
        within(got, outside, block)
        in_loops += got[0]
    assert count_indexed(jaxpr, sizes[-1])[0] - in_loops <= entry
    cuts = [sorted((whole(count_indexed(br.jaxpr, sizes[-1])),
                    count_sorts(br.jaxpr)) for br in e.params["branches"])
            for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert cuts == [PARTITION_BUDGET] * (len(sizes) - 1)
    assert count_sorts(jaxpr) == 0


def widest(jaxpr):
    """The most elements any equation of ``jaxpr`` (sub-jaxprs
    included) puts out."""
    most = 0
    for eqn in jaxpr.eqns:
        most = max([most] + [int(np.prod(v.aval.shape))
                             for v in eqn.outvars if hasattr(v, "aval")]
                   + [widest(sub) for sub in sub_jaxprs(eqn)])
    return most


@CALLS
@pytest.mark.parametrize("two_d", [False, True], ids=["1d", "2d"])
@pytest.mark.parametrize("parallel_rounds,has_bounds,has_fatpipe",
                         sorted(BUDGETS))
def test_the_variable_side_runs_nothing_as_wide_as_the_list(
        parallel_rounds, has_bounds, has_fatpipe, carried, two_d,
        monkeypatch):
    """With the list's variable-major index (ISSUE 34) entry is one cond:
    one branch is the entry and the descent above, within their budgets
    at full width; the other builds the bottom rung from the live
    variables' elements and puts out NOTHING as wide as the list (no
    gather, scatter, sort, scan, copy or elementwise pass: the widest
    thing it makes is n_v wide), with entry's two indexed ops, a
    handful of gathers and one scatter of start marks, at the rung's
    width; the only sort stands behind the cond that asks whether the
    rung's positions came out ascending."""
    monkeypatch.setattr(lmm_jax, "_LADDER_MIN_ELEMS", 32)
    _, _, entry = BUDGETS[parallel_rounds, has_bounds, has_fatpipe]
    if carried:
        entry = CARRIED_ENTRY
    a = system(bounds="bind" if has_bounds else None, fatpipe=has_fatpipe)
    n_c, n_v, n_elem = len(a.c_bound), len(a.v_penalty), len(a.e_var)
    lists = [x.reshape(-1, 8) if two_d else x
             for x in (a.e_var, a.e_cnst, a.e_w)]
    sizes = lmm_jax._ladder_sizes(lists[0].shape)
    assert n_elem > 2 * n_v > 2 * sizes[-1] and len(sizes) >= 4

    def run(carry, index, *args):
        return lmm_jax.fixpoint(*args, jnp.asarray(1e-9, a.e_w.dtype), n_c,
                                n_v, parallel_rounds=parallel_rounds,
                                carry=carry, return_carry=True,
                                has_bounds=has_bounds,
                                has_fatpipe=has_fatpipe, var_index=index)

    carry = (np.zeros(n_v), np.zeros(n_v, bool), a.c_bound, np.ones(n_c),
             np.ones(n_c, bool), np.int32(0)) if carried else None
    jaxpr = jax.make_jaxpr(run)(
        carry, lmm_jax.var_index(a.e_var, a.e_w, n_v), *lists, a.c_bound,
        a.c_fatpipe, a.v_penalty, a.v_bound).jaxpr
    # outside the cond: the bottom rung's round loop and n_v-wide math
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1 and len(round_loops(jaxpr)) == 1
    assert count_indexed(jaxpr, n_elem)[0] == 0
    # (index 0 is the false branch: the lists' side)
    from_lists, from_vars = (br.jaxpr for br in conds[0].params["branches"])
    assert widest(from_lists) >= n_elem
    assert len(round_loops(from_lists)) == len(sizes) - 1
    in_loops = sum(count_indexed(loop.params["body_jaxpr"].jaxpr, size)[0]
                   for loop, size in zip(round_loops(from_lists), sizes))
    assert count_indexed(from_lists, sizes[-1])[0] - in_loops <= entry
    assert widest(from_vars) <= n_v < n_elem
    got, inner = count_indexed(from_vars, sizes[-1])
    # marks, shift by owner, ve_idx, e_cnst, e_w, and entry's own
    assert got <= 5 + entry and inner == [[0, 0]]
    assert count_sorts(from_vars) == 1 == sum(
        count_sorts(br.jaxpr) for e in from_vars.eqns
        if e.primitive.name == "cond" for br in e.params["branches"])
    assert not round_loops(from_vars)


# ---------------------------------------------------------------------------
# exactness
# ---------------------------------------------------------------------------

PRECISIONS = [(np.float64, 1e-9), (np.float32, 1e-5)]


def solve_counting(arrays, eps, local, chunk=None):
    before = opstats.snapshot()
    out = lmm_jax.solve_arrays(arrays, eps, parallel_rounds=local,
                               chunk=chunk)
    took = opstats.diff(before).get("fixpoint_bound_rounds", 0)
    return [np.asarray(x) for x in out[:3]] + [int(out[3])], took


@pytest.mark.parametrize("local", [True, False], ids=["local", "global"])
@pytest.mark.parametrize("dtype,eps", PRECISIONS, ids=["f64", "f32"])
@pytest.mark.parametrize("fatpipe", [False, True], ids=["shared", "fatpipe"])
def test_bounds_that_never_bind_change_nothing(dtype, eps, local, fatpipe):
    """has_bounds=True on a system whose bounds sit over every level
    takes the bound-free side each round and gives has_bounds=False's
    results bit for bit."""
    free, took_free = solve_counting(system(dtype, fatpipe=fatpipe), eps,
                                     local)
    held, took_held = solve_counting(
        system(dtype, bounds="never", fatpipe=fatpipe), eps, local)
    assert took_free == took_held == 0
    assert free[3] == held[3]
    for f, h in zip(free[:3], held[:3]):
        np.testing.assert_array_equal(f, h)


def host_system(seed, n_cnst=20, n_var=60):
    """A random host System whose variable bounds bind, and its exact
    list-solver rates."""
    rng = np.random.default_rng(seed)
    s = make_new_maxmin_system(False)
    cnsts = [s.constraint_new(None, float(rng.uniform(1, 100)))
             for _ in range(n_cnst)]
    for c in cnsts[:n_cnst // 5]:
        c.sharing_policy = SharingPolicy.FATPIPE
    for _ in range(n_var):
        bound = float(rng.uniform(0.05, 5)) if rng.random() < 0.5 else -1.0
        var = s.variable_new(None, float(rng.choice([0.5, 1.0, 2.0])),
                             bound, 3)
        for ci in rng.choice(n_cnst, size=3, replace=False):
            s.expand(cnsts[int(ci)], var, float(rng.choice([0.5, 1.0, 2.0])))
    return s


@pytest.mark.parametrize("local", [True, False], ids=["local", "global"])
@pytest.mark.parametrize("dtype,eps,rtol", [(np.float64, 1e-9, 1e-9),
                                            (np.float32, 1e-5, 1e-4)],
                         ids=["f64", "f32"])
@pytest.mark.parametrize("seed", [0, 1])
def test_binding_bounds_match_the_host_solver_and_are_counted(
        seed, dtype, eps, rtol, local):
    s = host_system(seed)
    arrays, vars_in_order = lmm_jax.flatten(
        list(s.active_constraint_set), dtype)
    got, took = solve_counting(arrays, eps, local)
    s.solve()
    want = np.array([v.value for v in vars_in_order])
    np.testing.assert_allclose(got[0][:len(want)], want, rtol=rtol,
                               atol=rtol)
    assert 0 < took <= got[3]


@pytest.mark.parametrize("chunk", [1, 3])
@pytest.mark.parametrize("local", [True, False], ids=["local", "global"])
@pytest.mark.parametrize("dtype,eps", PRECISIONS, ids=["f64", "f32"])
def test_a_carry_handed_back_rebuilds_liveness(dtype, eps, local, chunk):
    """Chunked solves re-enter `fixpoint` with a mid-solve carry: the
    element liveness and live counts the loop keeps are rebuilt from
    the carry's v_fixed, so every chunking gives the one-shot solve,
    and the chunks' bound rounds add up to its count."""
    arrays = system(dtype, bounds="bind", fatpipe=True)
    assert arrays.n_elem < lmm_jax._COMPACT_MIN_ELEMS   # the carry alone
    whole, took_whole = solve_counting(arrays, eps, local)
    parts, took_parts = solve_counting(arrays, eps, local, chunk=chunk)
    assert whole[3] == parts[3] > chunk
    assert took_whole == took_parts > 0
    for w, p in zip(whole[:3], parts[:3]):
        np.testing.assert_array_equal(w, p)


def odd_system(dtype, fatpipe, seed=3, dyadic=False):
    """`system` with every kind of variable and element entry has to
    tell apart: penalties that vary, variables disabled (penalty 0),
    already fixed (< 0), parked (inf) and NaN, elements of weight zero;
    the pow2 tail is padding (weight 0 on variable 0, constraint 0).
    ``dyadic``: weights in sixteenths over penalties that are powers of
    two, so that a constraint's usage is the same sum in any order."""
    a = system(dtype, seed, bounds="bind", fatpipe=fatpipe)
    rng = np.random.default_rng(seed + 1)
    pens = [0.5, 1.0, 2.0, 4.0] if dyadic else [0.5, 1.0, 1.5, 3.0]
    a.v_penalty[:N_V] = rng.choice(pens, N_V)
    for first, pen in ((5, 0.0), (6, -1.0), (7, np.inf), (8, np.nan)):
        a.v_penalty[first:N_V:16] = pen
    if dyadic:
        a.e_w[:] = np.round(a.e_w * 16) / 16
    a.e_w[:a.n_elem:11] = 0
    assert a.n_elem < len(a.e_w)
    return a


def maxmin_start(a, v_fixed=None):
    """maxmin.cpp's start in plain numpy, an element at a time in list
    order: (e_valid, e_upen, usage, e_live, n_live_c).  An element
    counts if its weight is positive and its variable enabled (penalty
    > 0); usage adds (SHARED) or maxes (FATPIPE) weight / penalty; it
    is live while its variable is not fixed (cold: the fixed ones are
    those of negative penalty, none of them enabled)."""
    dtype, n_c = a.e_w.dtype, len(a.c_bound)
    n = len(a.e_var)
    e_valid, e_live = np.zeros(n, bool), np.zeros(n, bool)
    e_upen = np.zeros(n, dtype)
    u_sum, u_max = np.zeros(n_c, dtype), np.zeros(n_c, dtype)
    n_live_c = np.zeros(n_c, np.int32)
    for k, (v, c, w) in enumerate(zip(a.e_var, a.e_cnst, a.e_w)):
        pen = a.v_penalty[v]
        if not (w > 0 and pen > 0):
            continue
        e_valid[k] = True
        e_upen[k] = w / pen
        u_sum[c] += e_upen[k]
        u_max[c] = max(u_max[c], e_upen[k])
        if v_fixed is None or not v_fixed[v]:
            e_live[k] = True
            n_live_c[c] += 1
    return (e_valid, e_upen, np.where(a.c_fatpipe, u_max, u_sum), e_live,
            n_live_c)


def entry_on(n_dev, a, v_fixed, fatpipe):
    """`lmm_jax._entry` on ``a``: solo, or under ``axis`` with the
    element list sharded over ``n_dev`` devices."""
    n_c = len(a.c_bound)
    lists = (a.e_var, a.e_cnst, a.e_w)
    if not n_dev:
        return jax.jit(lambda *rest: lmm_jax._entry(
            *lists, *rest, n_c, fatpipe, lambda x: x, lambda x: x))(
                a.c_fatpipe, a.v_penalty, v_fixed)

    def shard(e_var, e_cnst, e_w, c_fatpipe, v_penalty, v_fixed):
        return lmm_jax._entry(
            e_var, e_cnst, e_w, c_fatpipe, v_penalty, v_fixed, n_c, fatpipe,
            lambda x: jax.lax.psum(x, "elem"),
            lambda x: jax.lax.pmax(x, "elem"))

    el, rep = P("elem"), P()
    return jax.jit(jax.shard_map(
        shard, mesh=make_mesh(n_dev), in_specs=(el, el, el, rep, rep, rep),
        out_specs=(el, el, el, rep, rep), check_vma=False))(
            *lists, a.c_fatpipe, a.v_penalty, v_fixed)


@pytest.mark.parametrize("n_dev", [0, 4], ids=["solo", "mesh4"])
@CALLS
@pytest.mark.parametrize("dtype", [np.float64, np.float32],
                         ids=["f64", "f32"])
@pytest.mark.parametrize("fatpipe", [False, True], ids=["shared", "fatpipe"])
def test_entry_is_maxmins_start_to_the_bit(dtype, fatpipe, carried, n_dev):
    """One gather and one scatter give what six did: the start of
    maxmin.cpp on every kind of variable and element, bitwise; a carried
    call tells the carry's fixed variables from the live ones (and takes
    its usage from the carry).  Across shards the usage is summed in
    another order, so there it is held on sums that no order moves."""
    a = odd_system(dtype, fatpipe, dyadic=bool(n_dev))
    v_fixed = None
    if carried:
        v_fixed = a.v_penalty < 0
        v_fixed[::3] = True
    want = maxmin_start(a, v_fixed)
    e_valid, e_upen, e_live, n_live_c, usage = entry_on(n_dev, a, v_fixed,
                                                        fatpipe)
    assert carried == (usage is None)
    got = [e_valid, e_upen, want[2] if carried else usage, e_live, n_live_c]
    for g, w in zip(got, want):
        assert np.asarray(g).dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), w)
    # every kind is there, and a carried call has valid elements that
    # are no longer live
    assert 0 < want[0].sum() < a.n_elem
    assert (want[0] & ~want[3]).any() == carried
    assert np.isfinite(want[2]).all() and (want[1][want[0]] == 0).any()


@pytest.mark.parametrize("local", [True, False], ids=["local", "global"])
@pytest.mark.parametrize("dtype,eps", PRECISIONS, ids=["f64", "f32"])
@pytest.mark.parametrize("fatpipe", [False, True], ids=["shared", "fatpipe"])
def test_entry_is_the_same_for_every_caller(dtype, eps, local, fatpipe):
    """The odd system solved in chunks of one and three rounds (entry
    from a carry handed back) and as a `vmap` lane beside another
    system is the one-dispatch solo solve, bit for bit; with its
    element list sharded over a mesh it is that solve round for round,
    to the rounding of sums taken in another order."""
    a = odd_system(dtype, fatpipe)
    solo, _ = solve_counting(a, eps, local)
    assert solo[3] > 3
    start = maxmin_start(a)
    assert (solo[0][~np.isin(np.arange(len(solo[0])),
                             a.e_var[start[0]])] == 0).all()
    for chunk in (1, 3):
        parts, _ = solve_counting(a, eps, local, chunk=chunk)
        assert parts[3] == solo[3]
        for w, p in zip(solo[:3], parts[:3]):
            np.testing.assert_array_equal(w, p)

    b = odd_system(dtype, fatpipe, seed=4)
    other, _ = solve_counting(b._replace(e_var=a.e_var, e_cnst=a.e_cnst,
                                         e_w=a.e_w, c_fatpipe=a.c_fatpipe),
                              eps, local)
    vals, rem, use, rounds = solve_arrays_batch(
        a.e_var, a.e_cnst, a.e_w, np.stack([a.c_bound, b.c_bound]),
        a.c_fatpipe, np.stack([a.v_penalty, b.v_penalty]),
        np.stack([a.v_bound, b.v_bound]), eps, parallel_rounds=local)
    for lane, (v, r, u, n) in enumerate((solo, other)):
        assert int(rounds[lane]) == n
        np.testing.assert_array_equal(np.asarray(vals[lane]), v)
        np.testing.assert_array_equal(np.asarray(rem[lane]), r)
        np.testing.assert_array_equal(np.asarray(use[lane]), u)

    config["lmm/rounds"] = "local" if local else "global"
    try:
        shard = sharded_solve(a, eps, make_mesh(4))
    finally:
        config["lmm/rounds"] = "local"
    assert shard[3] == solo[3]
    for w, p in zip(solo[:3], shard[:3]):
        # (bounds are up to 10: a residue of their cancellation is
        # absolute)
        tol = 1e4 * np.finfo(dtype).eps
        np.testing.assert_allclose(p, w, rtol=tol, atol=tol)


@pytest.mark.parametrize("local", [True, False], ids=["local", "global"])
@pytest.mark.parametrize("dtype,eps", PRECISIONS, ids=["f64", "f32"])
def test_vmapped_lanes_take_their_own_side_of_the_cond(dtype, eps, local):
    """Under vmap the cond is a select: a lane whose bounds bind and a
    lane whose bounds never do each get their solo results."""
    lanes = [system(dtype, bounds="bind"), system(dtype, bounds="never")]
    solo = [solve_counting(a, eps, local)[0] for a in lanes]
    a = lanes[0]
    vals, rem, use, rounds = solve_arrays_batch(
        a.e_var, a.e_cnst, a.e_w,
        np.stack([x.c_bound for x in lanes]), a.c_fatpipe,
        np.stack([x.v_penalty for x in lanes]),
        np.stack([x.v_bound for x in lanes]), eps, parallel_rounds=local)
    for b, (v, r, u, n) in enumerate(solo):
        assert int(rounds[b]) == n
        np.testing.assert_array_equal(np.asarray(vals[b]), v)
        np.testing.assert_array_equal(np.asarray(rem[b]), r)
        np.testing.assert_array_equal(np.asarray(use[b]), u)
    assert solo[0][3] != solo[1][3] or not np.array_equal(solo[0][0],
                                                          solo[1][0])


@pytest.mark.parametrize("local", [True, False], ids=["local", "global"])
def test_a_bound_still_binds_where_the_level_overflows(local):
    """A light constraint whose remaining/usage overflows f32 has rou
    inf: its flow is saturated all the same and takes its bound.  The
    level scatter must therefore tell "no processable element" from "a
    processable element at inf" (`isfinite(level)` alone cannot)."""
    n = 16
    a = lmm_jax.LmmArrays(
        e_var=np.zeros(n, np.int32), e_cnst=np.zeros(n, np.int32),
        e_w=np.zeros(n, np.float32), c_bound=np.zeros(n, np.float32),
        c_fatpipe=np.zeros(n, bool), v_penalty=np.zeros(n, np.float32),
        v_bound=np.full(n, -1, np.float32), n_elem=2, n_cnst=2, n_var=2)
    a.e_var[:2], a.e_cnst[:2], a.e_w[:2] = [0, 1], [0, 1], [1e-10, 1.0]
    a.c_bound[:2], a.v_penalty[:2], a.v_bound[:2] = [1e30, 10], 1, [5, -1]
    assert 1e30 / 1e-10 > float(np.finfo(np.float32).max)
    got, took = solve_counting(a, 1e-5, local)
    np.testing.assert_array_equal(got[0][:2], [5.0, 10.0])
    assert took == 1


def test_the_ell_layout_leaves_the_counter_alone():
    """The ELL bodies do not count their bound rounds: a solve there,
    its bounds binding, must leave `fixpoint_bound_rounds` absent ("not
    counted"), not at a 0 that would read "skipped every round"."""
    arrays = system(np.float64, bounds="bind")
    opstats.reset()
    config["lmm/layout"] = "ell"
    try:
        lmm_jax.solve_arrays(arrays, 1e-9, parallel_rounds=True)
    finally:
        config["lmm/layout"] = "auto"
    assert opstats.snapshot()["fixpoint_rounds"] > 0
    assert "fixpoint_bound_rounds" not in opstats.snapshot()
    lmm_jax.solve_arrays(arrays, 1e-9, parallel_rounds=True)
    assert opstats.snapshot()["fixpoint_bound_rounds"] > 0


@pytest.mark.parametrize("dtype,fits", [(np.float32, False),
                                        (np.float64, True)],
                         ids=["f32", "f64"])
def test_more_elements_than_the_dtype_counts_are_refused(dtype, fits):
    """The fixed-element count rides the float scatter of d_rem/d_use:
    exact only while a constraint cannot hold 2^24 elements in f32."""
    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt)

    def run(*args):
        return lmm_jax.fixpoint(*args, jnp.asarray(1e-5, dtype), 8, 8,
                                parallel_rounds=True, has_bounds=False,
                                has_fatpipe=False)

    args = (spec((1 << 24,), np.int32), spec((1 << 24,), np.int32),
            spec((1 << 24,), dtype), spec((8,), dtype), spec((8,), bool),
            spec((8,), dtype), spec((8,), dtype))
    if fits:
        jax.eval_shape(run, *args)
    else:
        with pytest.raises(ValueError, match="counts exactly"):
            jax.eval_shape(run, *args)
