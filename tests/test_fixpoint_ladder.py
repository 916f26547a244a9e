"""`lmm_jax.fixpoint`'s ladder (ISSUE 30): the round loop steps down
live-first partitioned element lists of falling static size, so a round
indexes what its live set needs and not the whole padded list.

The floor (`_LADDER_MIN_ELEMS`, 2^15, and every rung holds more: tier-1's
systems have one rung and lower to the single loop) is brought down here, so that bench-class
systems of a thousand elements take four rungs on the CPU.  On XLA:CPU
the laddered solve IS the single loop, bit for bit: the partition is
stable, so every segment reduction sees its live terms in the same
order, and a dead element only ever contributed an identity."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import build_arrays
from simgrid_tpu.ops import lmm_jax, opstats
from simgrid_tpu.ops.lmm_batch import solve_arrays_batch
from simgrid_tpu.ops.lmm_drain import DrainSim
from simgrid_tpu.parallel import make_mesh, sharded_solve
from simgrid_tpu.parallel import sharded
from simgrid_tpu.utils.config import config

N_C, N_V, DEG = 64, 256, 3
SINGLE = 1 << 15            # the floor as it stands: these lists have one rung
LOW = 32
PRECISIONS = [(np.float64, 1e-9), (np.float32, 1e-5)]


def system(dtype=np.float64, seed=3, bounds=None, fatpipe=False):
    """A bench-class COO system (768 elements padded to 1,024);
    ``bounds`` = "bind" | "never" | None."""
    rng = np.random.default_rng(seed)
    a = build_arrays(rng, N_C, N_V, DEG, dtype)
    if bounds == "bind":
        a.v_bound[:N_V // 2] = rng.uniform(0.01, 0.5, N_V // 2)
    elif bounds == "never":
        a.v_bound[:N_V // 2] = 1e6
    if fatpipe:
        a.c_fatpipe[:N_C // 4] = True
    return a


@pytest.fixture
def floor(monkeypatch):
    """``floor(n)`` sets the ladder's floor and drops every program
    compiled under another."""
    def drop():
        jax.clear_caches()
        sharded._sharded_run.cache_clear()

    def set_floor(n):
        monkeypatch.setattr(lmm_jax, "_LADDER_MIN_ELEMS", n)
        drop()

    yield set_floor
    drop()


def flags(a):
    return dict(has_bounds=bool(np.any(a.v_bound > 0)),
                has_fatpipe=bool(np.any(a.c_fatpipe)))


def run(a, eps, local, two_d=False, **kw):
    """One `fixpoint` call under a jit of its own, every output as
    numpy (the carry flattened in)."""
    n_c, n_v = len(a.c_bound), len(a.v_penalty)
    elems = [x.reshape(-1, 8) if two_d else x
             for x in (a.e_var, a.e_cnst, a.e_w)]

    def call(*args):
        return lmm_jax.fixpoint(*args, jnp.asarray(eps, a.e_w.dtype), n_c,
                                n_v, parallel_rounds=local,
                                return_carry=True, **flags(a), **kw)

    # (the last output says which side the call entered from:
    # tests/test_fixpoint_var_entry.py)
    out = jax.jit(call)(*elems, a.c_bound, a.c_fatpipe, a.v_penalty,
                        a.v_bound)[:9]
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(out)]


def count(pair):
    return lmm_jax._live_elem_rounds(pair)


def rungs_walked(live, sizes):
    """(elements indexed, partitions) of ONE `fixpoint` call whose
    rounds enter with ``live`` elements each: a round runs on the
    smallest rung that holds its live set; a descent, over however many
    rungs, is one partition."""
    worked = parts = rung = 0
    for n in live:
        down = rung
        while down + 1 < len(sizes) and n <= sizes[down + 1]:
            down += 1
        parts += down != rung
        rung = down
        worked += sizes[rung]
    return worked, parts


def live_per_round(a, eps, local):
    """Live elements entering each round, from the single loop run one
    round a call (the floor must stand)."""
    live, carry = [], None
    while True:
        out = lmm_jax._solve_kernel_chunk(
            a.e_var, a.e_cnst, a.e_w, a.c_bound, a.c_fatpipe, a.v_penalty,
            a.v_bound, carry, eps=eps, n_c=len(a.c_bound),
            n_v=len(a.v_penalty), parallel_rounds=local, chunk=1,
            **flags(a))
        if int(out[3]) == len(live):
            return live
        live.append(count(np.asarray(out[6])))
        carry = out[4]


def test_rung_sizes():
    sizes = lmm_jax._ladder_sizes
    # config #4 as `solve_arrays` pads it and as the drain hands it over
    assert sizes((1 << 21,)) == [1 << k for k in range(21, 15, -1)]
    assert sizes((155208, 8)) == [1241664, 620832, 310416, 155208, 77608,
                                  38808]
    # up to twice the floor: one rung, the single loop
    assert sizes((65536,)) == [65536]
    assert sizes((8192, 8)) == [65536]
    assert sizes((65664,)) == [65664, 32896]
    for shape in ((1 << 21,), (155208, 8), (1000003,), (99999, 8)):
        group = shape[1] if len(shape) == 2 \
            else lmm_jax._pos_group(shape[0])
        got = sizes(shape)
        assert got[-1] > lmm_jax._LADDER_MIN_ELEMS
        for above, below in zip(got, got[1:]):
            assert above > below >= above / 2 and below % group == 0


@pytest.mark.parametrize("shape,want", [
    # the pairwise alltoall's list and the largest plain ladder
    ((159388, 8), [1275104, 637552, 318776, 159392, 79696, 39848]),
    ((1 << 22,), [1 << k for k in range(22, 15, -1)]),
    # longer: no rung but the list holds more than 2^21 (a copy of the
    # round that size is four times as dear to compile); the full-machine
    # allreduce's 9,234,862 elements keep seven of nine rungs
    ((1154358, 8), [9234864, 1154360, 577184, 288592, 144296, 72152,
                    36080]),
    (((1 << 22) + 8,), [4194312, 1048584, 524296, 262152, 131080, 65544,
                        32776]),
])
def test_rung_sizes_of_a_long_list(shape, want):
    got = lmm_jax._ladder_sizes(shape)
    assert got == want
    assert all(size <= lmm_jax._LADDER_TOP_ELEMS for size in got[1:])


# ---------------------------------------------------------------------------
# the laddered solve is the single loop
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fatpipe", [False, True], ids=["shared", "fatpipe"])
@pytest.mark.parametrize("bounds", [None, "never", "bind"])
@pytest.mark.parametrize("local", [True, False], ids=["local", "global"])
@pytest.mark.parametrize("dtype,eps", PRECISIONS, ids=["f64", "f32"])
def test_the_ladder_is_the_single_loop_bit_for_bit(floor, dtype, eps, local,
                                                   bounds, fatpipe):
    """Values, remaining, usage, rounds, the 6-tuple carry, bound_rounds
    and live_elem_rounds; the rungs walked and the partitions counted
    are what the live profile says."""
    a = system(dtype, bounds=bounds, fatpipe=fatpipe)
    floor(SINGLE)
    one = run(a, eps, local)
    live = live_per_round(a, eps, local)
    floor(LOW)
    sizes = lmm_jax._ladder_sizes(a.e_var.shape)
    assert len(sizes) >= 3
    lad = run(a, eps, local)
    for x, y in zip(one[:-2], lad[:-2]):
        np.testing.assert_array_equal(x, y)
    rounds = int(one[3])
    assert len(live) == rounds > 3 and count(lad[-3]) == sum(live)
    # the single loop indexes the whole list every round
    assert (count(one[-2]), int(one[-1])) == (rounds * sizes[0], 0)
    assert (count(lad[-2]), int(lad[-1])) == rungs_walked(live, sizes)
    assert int(lad[-1]) > 0 and count(lad[-2]) < count(one[-2])
    if bounds == "bind":
        assert int(lad[-4]) > 0


@pytest.mark.parametrize("local", [True, False], ids=["local", "global"])
@pytest.mark.parametrize("dtype,eps", PRECISIONS, ids=["f64", "f32"])
def test_the_drains_two_dimensional_lists_keep_their_shape(floor, dtype, eps,
                                                           local):
    """`DrainSim` hands the element arrays as [E / 8, 8]: every rung is
    a whole number of rows."""
    a = system(dtype)
    floor(SINGLE)
    one = run(a, eps, local, two_d=True)
    floor(LOW)
    sizes = lmm_jax._ladder_sizes((len(a.e_var) // 8, 8))
    assert len(sizes) > len(lmm_jax._ladder_sizes(a.e_var.shape))
    lad = run(a, eps, local, two_d=True)
    for x, y in zip(one[:-2], lad[:-2]):
        np.testing.assert_array_equal(x, y)
    assert int(lad[-1]) > 0 and count(lad[-2]) < count(one[-2])


# ---------------------------------------------------------------------------
# leaving a rung early
# ---------------------------------------------------------------------------

def solve_counting(arrays, eps, local, chunk=None):
    before = opstats.snapshot()
    out = lmm_jax.solve_arrays(arrays, eps, parallel_rounds=local,
                               chunk=chunk)
    return ([np.asarray(x) for x in out[:3]] + [int(out[3])],
            opstats.diff(before))


@pytest.mark.parametrize("chunk", [1, 3])
@pytest.mark.parametrize("local", [True, False], ids=["local", "global"])
@pytest.mark.parametrize("dtype,eps", PRECISIONS, ids=["f64", "f32"])
def test_a_carry_handed_back_in_the_middle_of_a_rung(floor, dtype, eps,
                                                     local, chunk):
    """The budget exit: a chunk ends on `max_rounds` wherever its rung
    stands, the caller gets the 6-tuple, and the next call rebuilds
    liveness at full width and walks down again.  Same answer, same
    total rounds, same counts as the one-shot single loop."""
    a = system(dtype, bounds="bind", fatpipe=True)
    assert a.n_elem < lmm_jax._COMPACT_MIN_ELEMS        # the ladder alone
    floor(SINGLE)
    whole, took_whole = solve_counting(a, eps, local)
    live = live_per_round(a, eps, local)
    floor(LOW)
    sizes = lmm_jax._ladder_sizes(a.e_var.shape)
    parts, took = solve_counting(a, eps, local, chunk=chunk)
    assert whole[3] == parts[3] > 2 * chunk
    for w, p in zip(whole[:3], parts[:3]):
        np.testing.assert_array_equal(w, p)
    for name in ("fixpoint_bound_rounds", "fixpoint_live_elem_rounds"):
        assert took[name] == took_whole[name] > 0
    # every chunk walks down from the top: hand-count chunk by chunk
    walked = [rungs_walked(live[i:i + chunk], sizes)
              for i in range(0, len(live), chunk)]
    assert took["fixpoint_worked_elem_rounds"] == sum(w for w, _ in walked)
    assert took["fixpoint_partitions"] == sum(p for _, p in walked) > 0
    assert took_whole["fixpoint_worked_elem_rounds"] == whole[3] * sizes[0]
    assert took_whole.get("fixpoint_partitions", 0) == 0


@pytest.mark.parametrize("local", [True, False], ids=["local", "global"])
def test_a_rung_left_on_convergence_with_more_live_than_the_next_holds(
        floor, local):
    """Flows whose every link has no capacity are never fixed: the solve
    converges on the first rung with most elements still live.  Every
    later rung's condition is false, nothing is partitioned, and no
    round ran on a list cut too short."""
    a = system()
    a.c_bound[4:] = 0.0
    floor(SINGLE)
    one = run(a, 1e-9, local)
    floor(LOW)
    sizes = lmm_jax._ladder_sizes(a.e_var.shape)
    lad = run(a, 1e-9, local)
    for x, y in zip(one, lad):
        np.testing.assert_array_equal(x, y)
    valid = (a.e_w > 0) & (a.v_penalty[a.e_var] > 0)
    left = int(np.sum(valid & ~one[5][a.e_var]))        # carry's v_fixed
    assert left > sizes[1] and not one[8].any()         # no light left
    assert int(one[3]) >= 1
    assert (count(lad[-2]), int(lad[-1])) == (int(lad[3]) * sizes[0], 0)


# ---------------------------------------------------------------------------
# every caller gets the same path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("local", [True, False], ids=["local", "global"])
@pytest.mark.parametrize("dtype,eps", PRECISIONS, ids=["f64", "f32"])
def test_vmapped_lanes_step_down_each_at_its_own_pace(floor, dtype, eps,
                                                      local):
    """Lanes with different live profiles (bounds that bind, bounds that
    never do, half the flows switched off) share one batched loop per
    rung; each gets its solo single-loop results."""
    lanes = [system(dtype, bounds="bind"), system(dtype, bounds="never"),
             system(dtype, bounds="never")]
    lanes[2].v_penalty[::2] = 0.0
    floor(SINGLE)
    solo = [solve_counting(a, eps, local)[0] for a in lanes]
    profiles = [live_per_round(a, eps, local) for a in lanes]
    assert len({tuple(p) for p in profiles}) == 3
    floor(LOW)
    a = lanes[0]
    assert len(lmm_jax._ladder_sizes(a.e_var.shape)) >= 3
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


@pytest.mark.parametrize("mode", ["local", "global"])
@pytest.mark.parametrize("n_dev", [2, 4])
def test_shards_step_down_together(floor, mode, n_dev):
    """Under ``axis`` the rung test reads the fullest shard's live count
    (`allmax`), so all shards leave a rung in the same round and the
    collectives inside the loops line up: the laddered sharded solve is
    the single-loop sharded solve."""
    a = system(bounds="bind", fatpipe=True)
    mesh = make_mesh(n_dev)
    config["lmm/rounds"] = mode
    try:
        floor(SINGLE)
        one = sharded_solve(a, 1e-9, mesh)
        floor(16)
        shard = len(a.e_var) // n_dev
        assert len(lmm_jax._ladder_sizes((shard,))) >= 2
        lad = sharded_solve(a, 1e-9, mesh)
    finally:
        config["lmm/rounds"] = "local"
    assert one[3] == lad[3] > 3
    for x, y in zip(one[:3], lad[:3]):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("dtype", [np.float64, np.float32],
                         ids=["f64", "f32"])
def test_the_drain_meets_the_ladder_once_an_advance(floor, dtype):
    """Every advance of the superstep is a cold solve: it walks down the
    ladder from the top.  Same events to the bit, fewer elements
    indexed; the count rides the packed stats."""
    a = system(dtype)
    sizes_b = np.random.default_rng(5).uniform(1e5, 1e6, N_V)

    def drain():
        before = opstats.snapshot()
        sim = DrainSim(a.e_var[:a.n_elem], a.e_cnst[:a.n_elem],
                       a.e_w[:a.n_elem], a.c_bound, sizes_b, dtype=dtype,
                       superstep=4)
        sim.run()
        return sim, opstats.diff(before)

    floor(SINGLE)
    one, took_one = drain()
    floor(LOW)
    lad, took_lad = drain()
    assert one.events == lad.events and len(one.events) == N_V
    assert one.rounds == lad.rounds and one.advances == lad.advances > 4
    # one.rounds counts the rounds of its dispatches: each indexed the
    # whole [E / 8, 8] list
    assert took_one["fixpoint_worked_elem_rounds"] \
        == took_one["fixpoint_rounds"] * a.n_elem
    assert 0 < took_lad["fixpoint_worked_elem_rounds"] \
        < took_one["fixpoint_worked_elem_rounds"]


def test_unrolled_rounds_keep_the_single_loop(floor):
    """``unroll=True`` has no loop to cut into stages."""
    a = system()
    floor(LOW)
    lad = run(a, 1e-9, True, max_rounds=4)
    flat = run(a, 1e-9, True, max_rounds=4, unroll=True)
    for x, y in zip(lad[:-2], flat[:-2]):
        np.testing.assert_array_equal(x, y)
    assert (count(flat[-2]), int(flat[-1])) == (4 * len(a.e_var), 0)
