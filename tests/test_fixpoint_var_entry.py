"""`lmm_jax.fixpoint`'s variable side (ISSUE 34): a call that enters
with few live variables builds the ladder's bottom rung from their own
elements, through the list's variable-major index, instead of paying
entry and one descent at the width of the list.

The rung it builds is the one the stable live-first partition leaves —
the live elements in the list's order — so on XLA:CPU every result and
counter is the full-width entry's bit for bit: held here on `fixpoint`
itself (cold and carried, lists that are variable-major and lists that
are not, one- and two-dimensional) and on the collective tape's drain
(a routed pairwise alltoall, synthetic flavors with their lists
shuffled, a fault tape beside the collective, dispatch groupings, a
budget rescue).  The ladder's floor is brought down as in
`test_fixpoint_ladder.py`, so that these small lists have rungs.  The
programs that get no index lower to the parent commit's text."""

import functools
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import build_arrays
from simgrid_tpu.collectives import CollectiveSpec, RoutedTopology
from simgrid_tpu.collectives.maestro import HostMaestro
from simgrid_tpu.ops import lmm_drain, lmm_jax, opstats
from simgrid_tpu.ops.lmm_drain import DrainSim
from simgrid_tpu.parallel.sharded import make_mesh, sharded_solve

from tests.test_collectives_routed import (RANKS, engine,  # noqa: F401
                                           rank_hosts)

N_C, N_V, DEG = 64, 256, 3
SINGLE = 1 << 15
PRECISIONS = [(np.float64, 1e-9), (np.float32, 1e-5)]


@pytest.fixture
def floor(monkeypatch):
    """``floor(n)`` sets the ladder's floor and drops every program
    compiled under another."""
    def set_floor(n):
        monkeypatch.setattr(lmm_jax, "_LADDER_MIN_ELEMS", n)
        jax.clear_caches()

    yield set_floor
    jax.clear_caches()


@pytest.fixture
def full_width(monkeypatch):
    """``full_width()``: from here on the drain hands `fixpoint` no
    index, so every solve enters over the whole list."""
    def drop():
        monkeypatch.setattr(
            lmm_drain, "fixpoint",
            lambda *a, var_index=None, **kw: lmm_jax.fixpoint(*a, **kw))
        jax.clear_caches()

    return drop


def system(dtype, seed, shuffled, fatpipe=False):
    """A bench-class COO system (768 elements padded to 1,024), its
    list variable-major as built or in a drawn order."""
    rng = np.random.default_rng(seed)
    a = build_arrays(rng, N_C, N_V, DEG, dtype)
    if fatpipe:
        a.c_fatpipe[:N_C // 4] = True
    if shuffled:
        order = rng.permutation(a.n_elem)
        for name in ("e_var", "e_cnst", "e_w"):
            getattr(a, name)[:a.n_elem] = getattr(a, name)[order]
    return a


def some_live(a, n_live, seed):
    pen = np.zeros(N_V, a.e_w.dtype)
    pen[np.random.default_rng(seed).choice(N_V, n_live, replace=False)] = 1
    return pen


@functools.partial(jax.jit, static_argnames=("eps", "local", "has_fatpipe",
                                             "max_rounds"))
def _call(ev, ec, ew, cb, fat, pen, vb, carry, index, eps, local,
          has_fatpipe, max_rounds):
    return lmm_jax.fixpoint(
        ev, ec, ew, cb, fat, pen, vb, jnp.asarray(eps, ew.dtype), N_C, N_V,
        parallel_rounds=local, carry=carry, max_rounds=max_rounds,
        return_carry=True, has_bounds=False, has_fatpipe=has_fatpipe,
        var_index=index)


def run(a, pen, eps, local, index, two_d=False, carry=None,
        max_rounds=None):
    """One `fixpoint` call (one compile a shape of its arguments): (the
    nine outputs a call without an index has, as numpy leaves; the side
    it took)."""
    elems = [x.reshape(-1, 8) if two_d else x
             for x in (a.e_var, a.e_cnst, a.e_w)]
    out = _call(*elems, a.c_bound, a.c_fatpipe, pen, a.v_bound, carry,
                index, eps=eps, local=local,
                has_fatpipe=bool(a.c_fatpipe.any()), max_rounds=max_rounds)
    return ([np.asarray(x) for x in jax.tree_util.tree_leaves(out[:9])],
            int(out[9]))


def same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)


# ---------------------------------------------------------------------------
# the index
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shuffled", [False, True],
                         ids=["var-major", "shuffled"])
def test_the_index_groups_the_elements_that_count_by_variable(shuffled):
    a = system(np.float64, 4, shuffled)
    a.e_w[5] = 0.0                        # a weightless element
    a.e_var[7] = N_V + 3                  # one of no variable
    v_ptr, ve_idx = lmm_jax.var_index(a.e_var, a.e_w, N_V)
    assert v_ptr.dtype == ve_idx.dtype == np.int32
    assert v_ptr.shape == (N_V + 1,) and ve_idx.shape == a.e_var.shape
    counts = (a.e_w > 0) & (a.e_var < N_V)
    assert v_ptr[0] == 0 and v_ptr[-1] == counts.sum() == a.n_elem - 2
    for v in range(N_V):
        mine = ve_idx[v_ptr[v]:v_ptr[v + 1]]
        np.testing.assert_array_equal(
            mine, np.flatnonzero(counts & (a.e_var == v)))
    # the same of the drain's [E / 8, 8] lists: positions are flat
    two = lmm_jax.var_index(a.e_var.reshape(-1, 8), a.e_w.reshape(-1, 8),
                            N_V)
    np.testing.assert_array_equal(two[0], v_ptr)
    np.testing.assert_array_equal(two[1], ve_idx)
    # ascending as a whole exactly when the list is variable-major
    assert bool(np.all(np.diff(ve_idx[:v_ptr[-1]]) > 0)) != shuffled


# ---------------------------------------------------------------------------
# fixpoint, one call
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shuffled,fatpipe",
                         [(False, False), (True, False), (True, True)],
                         ids=["var-major", "shuffled", "shuffled-fatpipe"])
@pytest.mark.parametrize("two_d", [False, True], ids=["1d", "2d"])
@pytest.mark.parametrize("local", [True, False], ids=["local", "global"])
@pytest.mark.parametrize("dtype,eps", PRECISIONS, ids=["f64", "f32"])
def test_the_variable_side_is_the_full_width_entry_bit_for_bit(
        floor, dtype, eps, local, two_d, shuffled, fatpipe):
    """Few live variables: the rung comes from their elements, no
    partition runs, and values, remaining, usage, rounds, carry and the
    three counters are the full-width entry's.  More than the bottom
    rung holds: the other branch, which is that entry."""
    floor(32)
    a = system(dtype, 3, shuffled, fatpipe)
    bottom = lmm_jax._ladder_sizes(
        (len(a.e_var) // 8, 8) if two_d else a.e_var.shape)[-1]
    assert bottom in (64, 128)
    index = lmm_jax.var_index(a.e_var, a.e_w, N_V)
    for n_live in (1, 7, bottom // DEG, bottom // DEG + 1, N_V):
        pen = some_live(a, n_live, n_live)
        want, side = run(a, pen, eps, local, None, two_d)
        assert side == 0
        got, side = run(a, pen, eps, local, index, two_d)
        fits = n_live * DEG <= bottom
        assert side == int(fits)
        same(got[:-1], want[:-1])
        assert int(want[3]) > 0
        # a descent is one partition; the variable side needs none
        assert int(want[-1]) >= 1 and int(got[-1]) == \
            (0 if fits else int(want[-1]))


@pytest.mark.parametrize("chunk,shuffled", [(1, False), (2, True)],
                         ids=["1-var-major", "2-shuffled"])
@pytest.mark.parametrize("local", [True, False], ids=["local", "global"])
@pytest.mark.parametrize("dtype,eps", PRECISIONS, ids=["f64", "f32"])
def test_a_carry_handed_back_enters_from_its_unfixed_variables(
        floor, dtype, eps, local, chunk, shuffled):
    """A carried call's live variables are the enabled ones the carry
    has not fixed: chunk by chunk the solve is the one-call solve, and
    every chunk whose live elements fit enters from the variables."""
    floor(32)
    a = system(dtype, 5, shuffled)
    index = lmm_jax.var_index(a.e_var, a.e_w, N_V)
    pen = some_live(a, 40, 9)
    whole, _ = run(a, pen, eps, local, None)
    assert int(whole[3]) > 2 * chunk
    for idx in (None, index):
        carry, sides, calls = None, 0, 0
        while True:
            out, side = run(a, pen, eps, local, idx, carry=carry,
                            max_rounds=chunk)
            sides += side
            calls += 1
            # leaves 4..9 are the carry
            carry = tuple(jnp.asarray(x) for x in out[4:10])
            if not out[8].any():
                break
        same(out[:4], whole[:4])
        # 40 live variables x 3 elements fit the 128-element rung from
        # the first call on
        assert sides == (calls if idx is not None else 0)


def test_a_list_of_one_rung_takes_no_notice_of_the_index(floor):
    """Under the ladder's floor there is no rung to build: with the
    index the call lowers to the text it has without."""
    a = system(np.float64, 3, False)
    assert len(lmm_jax._ladder_sizes(a.e_var.shape)) == 1
    index = lmm_jax.var_index(a.e_var, a.e_w, N_V)
    pen = some_live(a, 5, 5)
    want, _ = run(a, pen, 1e-9, True, None)
    got, side = run(a, pen, 1e-9, True, index)
    assert side == 0
    same(got, want)

    def text(idx):
        def call(ev, ec, ew, cb, fat, pen, vb, idx):
            return lmm_jax.fixpoint(
                ev, ec, ew, cb, fat, pen, vb, jnp.asarray(1e-9), N_C, N_V,
                parallel_rounds=True, return_carry=True, has_bounds=False,
                has_fatpipe=False, var_index=idx)[:9]
        return jax.jit(call, keep_unused=True).lower(
            a.e_var, a.e_cnst, a.e_w, a.c_bound, a.c_fatpipe, pen,
            a.v_bound, idx).as_text()

    with_index = text(index)
    # (the index is two more arguments of the function, nothing else)
    assert with_index.count("stablehlo.") == text(None).count("stablehlo.")
    floor(32)
    assert text(index).count("stablehlo.") > with_index.count("stablehlo.")


def test_the_index_is_of_the_whole_list_not_of_a_shard(floor):
    floor(32)
    a = system(np.float64, 3, False)
    index = lmm_jax.var_index(a.e_var, a.e_w, N_V)
    with pytest.raises(ValueError, match="not of a shard"):
        lmm_jax.fixpoint(a.e_var, a.e_cnst, a.e_w, a.c_bound, a.c_fatpipe,
                         a.v_penalty, a.v_bound, 1e-9, N_C, N_V,
                         axis="elems", var_index=index)
    # and the sharded solve hands none
    assert sharded_solve(a, 1e-9, make_mesh(2))[3] > 0


#: sha256 of ``_solve_kernel_chunk.lower(...).as_text()`` at the parent
#: commit (516f2e5), floor 32 (four rungs) and 2^15 (one), for
#: `system(dtype, 3, False)`: (floor, dtype, carried, local) -> text.
#: Without an index `fixpoint` must lower to the same text now.
PARENT_CHUNK_TEXT = {
    (32, "float64", False, True): "69983e6b7a69",
    (32, "float64", True, False): "2b157fef7ee2",
    (32, "float32", False, True): "0502982e9bb7",
    (32, "float32", True, True): "f0476eaf11b9",
    (SINGLE, "float64", False, True): "2ab668a58cba",
    (SINGLE, "float32", True, False): "d0280c2b5938",
}


@pytest.mark.parametrize("key", sorted(PARENT_CHUNK_TEXT),
                         ids=lambda k: "-".join(map(str, k)))
def test_a_solve_without_an_index_lowers_as_before(floor, key):
    low, dtype, carried, local = key
    floor(low)
    dtype = np.dtype(dtype).type
    a = build_arrays(np.random.default_rng(3), N_C, N_V, DEG, dtype)
    carry = (np.zeros(N_V, dtype), np.zeros(N_V, bool), a.c_bound,
             np.ones(N_C, dtype), np.ones(N_C, bool),
             np.int32(0)) if carried else None
    text = lmm_jax._solve_kernel_chunk.lower(
        a.e_var, a.e_cnst, a.e_w, a.c_bound, a.c_fatpipe, a.v_penalty,
        a.v_bound, carry, eps=1e-9, n_c=N_C, n_v=N_V,
        parallel_rounds=local, chunk=16, has_bounds=False,
        has_fatpipe=False).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:12] \
        == PARENT_CHUNK_TEXT[key]


# ---------------------------------------------------------------------------
# the collective tape's drain
# ---------------------------------------------------------------------------

def drained(make, **kw):
    """``make(**kw)``'s sim run to the end: (what it produced, its
    counters)."""
    before = opstats.snapshot()
    sim = make(**kw)
    sim.run()
    clk = np.asarray(sim._coll_clk)
    return ((sim.events, sim.collective_events, sim.fault_events, sim.t,
             (float(clk[0]), float(clk[1])), sim.rounds, sim.advances),
            opstats.diff(before))


COUNTED = ("fixpoint_rounds", "fixpoint_worked_elem_rounds",
           "collective_live_flow_advances", "collective_tape_fires")


@pytest.mark.parametrize("dtype", [np.float64, np.float32],
                         ids=["f64", "f32"])
def test_the_routed_pairwise_tape_enters_every_advance_from_its_flows(
        engine, floor, full_width, dtype):
    """16 ranks on the 128-host dragonfly (114 x 240 x 2,418, at most
    16 flows and ~190 elements live): with a 304-element bottom rung
    every advance takes the variable side; events, activations, clock,
    rounds and the counters are the full-width run's and the host
    maestro's, at every dispatch grouping and through a budget rescue
    of every advance."""
    dc = CollectiveSpec("alltoall", "pairwise", RANKS,
                        RoutedTopology(engine, rank_hosts(engine, 5)),
                        1e6).build()
    assert np.all(np.diff(dc.ve_idx) > 0)         # flow-major as lowered
    floor(256)
    sizes = lmm_jax._ladder_sizes((-(-len(dc.e_var) // 8), 8))
    assert sizes == [2424, 1216, 608, 304]
    got, took = drained(dc.make_sim, superstep=16, dtype=dtype)
    assert len(got[0]) == len(got[1]) == dc.n_v
    assert took["fixpoint_var_entries"] == got[6] > 100
    assert took["fixpoint_worked_elem_rounds"] == 304 * got[5]
    for kw in (dict(superstep=1), dict(superstep=5, pipeline=2)):
        alt, took_alt = drained(dc.make_sim, dtype=dtype, **kw)
        assert alt == got
        # (a pipelined run also counts the speculative tail it threw
        # away: its rounds, not its advances)
        if "pipeline" not in kw:
            assert [took_alt[k] for k in COUNTED + ("fixpoint_var_entries",)] \
                == [took[k] for k in COUNTED + ("fixpoint_var_entries",)]
    # one round a dispatch: every advance of two rounds or more is
    # finished by the K = 1 rescue, which enters the same way
    rescued, took_r = drained(dc.make_sim, superstep=16, dtype=dtype,
                              superstep_rounds=1)
    assert rescued[:5] == got[:5] and rescued[6] == got[6]
    assert took_r["dispatches"] > 2 * took["dispatches"]
    assert took_r["fixpoint_var_entries"] == got[6]
    ma = HostMaestro(dc, dtype=dtype)
    ma.run()
    assert (ma.events, ma.collective_events) == got[:2]
    full_width()
    want, took_full = drained(dc.make_sim, superstep=16, dtype=dtype)
    assert want == got
    assert "fixpoint_var_entries" not in took_full
    assert [took_full[k] for k in COUNTED] == [took[k] for k in COUNTED]


@pytest.mark.parametrize("flavor,low,sides", [("nic", 32, "all"),
                                              ("ring", 16, "some")])
def test_a_list_that_is_not_flow_major_and_a_fault_tape_beside(
        floor, full_width, flavor, low, sides):
    """The synthetic flavors with their element lists in a drawn order
    (the rung's positions then need their sort) and a link that fails
    and comes back while the collective runs: the same events, fault
    fires and counters as the full-width entry.  On the ring a step's
    live elements pass the 32-element bottom rung in some advances:
    those fall back by themselves."""
    dc = CollectiveSpec("alltoall", "pairwise", RANKS, flavor, 1e6).build()
    order = np.random.default_rng(11).permutation(len(dc.e_var))
    lists = [a[order] for a in (dc.e_var, dc.e_cnst, dc.e_w)]
    assert np.any(np.diff(lists[0]) < 0)
    tape = (np.array([2e-3, 9e-3]), np.array([1, 1], np.int32),
            np.array([dc.c_bound[1] / 4, dc.c_bound[1]]))
    kw = dict(e_var=lists[0], e_cnst=lists[1], e_w=lists[2],
              c_bound=dc.c_bound, sizes=dc.sizes, penalty=dc.penalty0,
              dtype=np.float64, tape=tape, collective=dc.drain_args())
    floor(low)
    got, took = drained(DrainSim, superstep=16, **kw)
    assert len(got[0]) == dc.n_v and len(got[2]) == 2
    entries = took.get("fixpoint_var_entries", 0)
    if sides == "all":
        assert entries == got[6]
    else:
        assert 0 < entries < got[6]
    one, took_one = drained(DrainSim, superstep=1, **kw)
    assert one == got
    assert took_one.get("fixpoint_var_entries", 0) == entries
    # the index DeviceCollective brings is of ITS list: of the shuffled
    # one the sim builds its own, and the unshuffled run agrees on
    # every date (the sums' terms are in another order, not their sets)
    plain, _ = drained(dc.make_sim, superstep=16, dtype=np.float64, tape=tape)
    assert [f for _, f in plain[0]] == [f for _, f in got[0]]
    np.testing.assert_allclose([t for t, _ in plain[0]],
                               [t for t, _ in got[0]], rtol=1e-12)
    full_width()
    want, took_full = drained(DrainSim, superstep=16, **kw)
    assert want == got
    assert [took_full[k] for k in COUNTED] == [took[k] for k in COUNTED]


def test_a_transition_into_the_element_list_drops_the_index():
    """`apply_transitions` may rewrite elements under the index: the sim
    then solves at full width again rather than from a stale index."""
    dc = CollectiveSpec("alltoall", "pairwise", 4, "nic", 1e6).build()
    sim = dc.make_sim(superstep=4)
    assert all(a is not None for a in sim._var_index)
    sim.apply_transitions({"c_bound": ([0], [dc.c_bound[0]])})
    assert all(a is not None for a in sim._var_index)
    sim.apply_transitions({"e_w": ([0], [dc.e_w[0]])})
    assert sim._var_index == (None, None)
    sim.run()
    ref = dc.make_sim(superstep=4)
    ref.run()
    assert sim.events == ref.events


@pytest.mark.parametrize("name,indexed", [("drain/superstep", False),
                                          ("drain/superstep_tape", False),
                                          ("drain/superstep_coll", True),
                                          ("drain/superstep_coll_f32", True)])
def test_only_a_collective_dispatch_carries_the_index(name, indexed):
    """The registry's captured dispatches (what proglint and the chip
    smoke's compile leg lower): the solo superstep's two new inputs are
    int32 arrays for a sim with a collective and None for every other,
    which is no argument at all."""
    import inspect
    from simgrid_tpu.analysis.prog.registry import iter_programs
    spec = {s.name: s for s in iter_programs()}[name]
    args, statics = spec.make(1)
    names = list(inspect.signature(spec.program).parameters)
    given = dict(zip(names, args))
    assert statics["has_coll"] is indexed
    if not indexed:
        assert given["v_ptr"] is None and given["ve_idx"] is None
        return
    assert given["v_ptr"].dtype == given["ve_idx"].dtype == np.int32
    assert given["v_ptr"].shape == (statics["n_v"] + 1,)
    assert given["ve_idx"].size * 4 == given["e_var"].nbytes
