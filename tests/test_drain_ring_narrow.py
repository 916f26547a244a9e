"""An advance's ring ids written as wide as its entries.

A tape with the DAG's source-major index writes the ids of an advance's
run ``[n_ev, n_new)`` on the narrowest of ``lmm_drain._RING_RUNGS``
they fit (``_ring_narrow``: the entries' flows found from the running
counts, one gather of ids, one scatter), and by the flow-wide scatter
above the top rung.  Held here:

* the write to the bit against the flow-wide scatter as the program
  wrote it before the rungs (kept below verbatim), on hand-built masks
  at a flow count where every rung is kept: no entry, one, each rung's
  width less one, the width, one more, a burst of every flow, a run
  ending at the ring's last slot or past it, completions, the fault and
  activations in one advance;
* whole tapes (the 16-rank pairwise alltoall and the 128-rank
  recursive doubling) with rungs narrow enough to take most advances:
  every dispatch gives the outputs of the program without the index (the
  parent's lowered text, pinned in ``test_coll_src_walk.py``), its
  packed vector but for the two scalars at the tail; the last of them,
  ``collective_ring_narrow``, counts exactly the advances whose entries
  fit the top rung, in float32 and float64; and so do dispatches that
  their round budget cuts inside an advance, which the indexed tape
  logs past the ring's end instead of selecting the old ring back."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from simgrid_tpu.ops import lmm_drain, opstats
from simgrid_tpu.ops.lmm_drain import _STATS_HEAD, _ring_ids
from simgrid_tpu.ops.lmm_jax import _pos_group

from tests.test_coll_src_walk import tapes  # noqa: F401  (a fixture)
from tests.test_drain_ring import PARAMS, captured, same_bits

DTYPES = pytest.mark.parametrize("dtype", [np.float64, np.float32],
                                 ids=["f64", "f32"])

#: the least flow count at which the program keeps every rung
N_V = 8 * max(lmm_drain._RING_RUNGS)
N_C, K_MAX = 11, 16
RING = 2 * N_V + K_MAX
LOW, TOP = min(lmm_drain._RING_RUNGS), max(lmm_drain._RING_RUNGS)


def parent_write(ring_id, ring_t, done, act, ids, n_ev, f_fire, slot,
                 t_ring):
    """The ring's writes of one advance as the program made them before
    the rungs: every id by one scatter as wide as the flows, the
    fault's by one of its own, the dates by one range select."""
    ring_n = ring_id.shape[0]
    group = _pos_group(done.shape[0])
    dcount = jnp.cumsum(done.astype(jnp.int32))
    n_done = dcount[-1]
    pos = jnp.where(done, n_ev + dcount - 1, ring_n)
    val = ids
    n_new = n_ev + n_done
    fpos = jnp.where(f_fire, n_new, ring_n)
    n_new = n_new + f_fire.astype(jnp.int32)
    acount = jnp.cumsum(act.astype(jnp.int32))
    pos = jnp.where(done, pos, jnp.where(act, n_new + acount - 1, ring_n))
    val = jnp.where(done, ids, -(1 + N_C + ids))
    n_new = n_new + acount[-1]
    ring_id2 = ring_id.at[pos.reshape(-1, group)].set(
        val.reshape(-1, group), mode="drop")
    ring_id2 = ring_id2.at[fpos].set(-(1 + slot), mode="drop")
    at = lax.iota(jnp.int32, ring_n)
    ring_t2 = jnp.where((at >= n_ev) & (at < n_new), t_ring, ring_t)
    return ring_id2, ring_t2, n_new


def rung_write(ring_id, ring_t, done, act, ids, n_ev, f_fire, slot,
               t_ring):
    """The same writes with the ids on the program's rungs."""
    ring_n = ring_id.shape[0]
    n_v = done.shape[0]
    group = _pos_group(n_v)
    dcount = jnp.cumsum(done.astype(jnp.int32))
    acount = jnp.cumsum(act.astype(jnp.int32))
    n_done = dcount[-1]
    fault = f_fire.astype(jnp.int32)
    n_new = n_ev + n_done + fault + acount[-1]
    pos = jnp.where(done, n_ev + dcount - 1, jnp.where(
        act, n_ev + n_done + fault + acount - 1, ring_n))
    val = jnp.where(done, ids, -(1 + N_C + ids))
    wide = lambda ring_id: ring_id.at[pos.reshape(-1, group)].set(
        val.reshape(-1, group), mode="drop")
    rungs = [w for w in lmm_drain._RING_RUNGS if 8 * w <= n_v]
    ring_id2, narrow = _ring_ids(ring_id, wide, dcount, acount, ids, n_ev,
                                 n_new - n_ev, fault, N_C, rungs)
    ring_id2 = ring_id2.at[jnp.where(f_fire, n_ev + n_done, ring_n)].set(
        -(1 + slot), mode="drop")
    at = lax.iota(jnp.int32, ring_n)
    ring_t2 = jnp.where((at >= n_ev) & (at < n_new), t_ring, ring_t)
    return ring_id2, ring_t2, n_new, narrow


PARENT, RUNGS = jax.jit(parent_write), jax.jit(rung_write)


def masks(count, fault, how, seed):
    """``count`` entries of one advance, the fault's among them when
    ``fault``: completions, activations, or both, on flows drawn from
    ``seed``."""
    rng = np.random.default_rng(seed)
    n = count - fault
    n_done = {"done": n, "act": 0, "both": n // 3}[how]
    flows = rng.permutation(N_V)[:n]
    done, act = np.zeros(N_V, bool), np.zeros(N_V, bool)
    done[flows[:n_done]] = True
    act[flows[n_done:]] = True
    return done, act


COUNTS = sorted({0, 1, 2, N_V, N_V + 1}
                | {w + d for w in lmm_drain._RING_RUNGS for d in (-1, 0, 1)})


@DTYPES
@pytest.mark.parametrize("place", ["start", "end", "past"])
@pytest.mark.parametrize("count", COUNTS)
def test_the_rung_write_is_the_flow_wide_one(count, place, dtype):
    assert len(lmm_drain._RING_RUNGS) >= 2 and 8 * TOP == N_V
    rng = np.random.default_rng(count)
    ids = rng.permutation(N_V).astype(np.int32)
    ring_id0 = rng.integers(-N_V, N_V, RING).astype(np.int32)
    ring_t0 = rng.uniform(0.0, 1.0, RING).astype(dtype)
    t_ring = dtype(1.5)
    for fault in ((0, 1) if count else (0,)):
        if count - fault > N_V:
            continue
        for how in ("done", "act", "both"):
            done, act = masks(count, fault, how, count + fault)
            n_ev = {"start": 5, "end": RING - count,
                    "past": RING - count + 3}[place]
            args = (ring_id0, ring_t0, done, act, ids, np.int32(n_ev),
                    np.bool_(fault), np.int32(3), t_ring)
            want = [np.asarray(x) for x in PARENT(*args)]
            got = [np.asarray(x) for x in RUNGS(*args)]
            assert int(want[2]) == n_ev + count
            for w, g in zip(want, got):
                assert same_bits(w, g), (fault, how)
            assert bool(got[3]) == (count <= TOP)


# ---------------------------------------------------------------------------
# whole tapes
# ---------------------------------------------------------------------------

@pytest.fixture
def rungs(monkeypatch):
    """Set the rungs: statics of the program, so every compiled one
    goes with them."""
    def to(ws):
        monkeypatch.setattr(lmm_drain, "_RING_RUNGS", ws)
        jax.clear_caches()
    yield to
    jax.clear_caches()


STATE = ("pen", "rem", "c_bound", "tape_pos", "coll_pred", "coll_ready",
         "coll_clk")


def entries(packed, statics):
    """The entries each advance of one dispatch logged."""
    k = statics["k_max"]
    adv = int(packed[1])
    nev = packed[_STATS_HEAD + k:_STATS_HEAD + k + adv].astype(np.int64)
    return np.diff(np.concatenate([[0], nev]))


@DTYPES
@pytest.mark.parametrize("name,ws", [("pairwise16", (2, 4)),
                                     ("rdb128", (4, 16))])
def test_a_tape_drains_as_the_program_without_the_index(tapes, rungs, name,
                                                        ws, dtype):
    """Dispatch by dispatch, the whole drain: the same state and ring to
    the bit, the packed vector the parent's but for its tail's two
    last scalars, and the last counts the advances the rungs took; a
    sim driving the same program counts as many."""
    dc = tapes[name]
    rungs(ws)
    assert 8 * max(ws) <= dc.n_v
    sim = dc.make_sim(superstep=4, dtype=dtype)
    a, statics = captured(sim)
    assert statics["has_coll"] and "s_dst" in a
    program = lmm_drain._drain_superstep
    sizes = []
    for _ in range(500):
        args = [a[p] for p in PARAMS[:len(a)]]
        new = [np.asarray(o) for o in program(*args, **statics)]
        old = [np.asarray(o) for o in program(*args[:-2], **statics)]
        for n, o in zip(new[:7], old[:7]):
            assert same_bits(n, o)
        assert same_bits(new[7][:-2], old[7])
        ent = entries(new[7], statics)
        if not len(ent):
            break
        assert new[7][-1] == np.count_nonzero(ent <= max(ws))
        sizes += ent.tolist()
        a = {**a, **dict(zip(STATE, new[:7]))}
    else:
        raise AssertionError("the drain did not end")
    sizes = np.array(sizes)
    # every side of the switch ran
    assert (sizes <= min(ws)).any() and (sizes > max(ws)).any()
    assert ((sizes > min(ws)) & (sizes <= max(ws))).any()
    # and the sim that drives the program counts what it took
    before = opstats.snapshot()
    sim = dc.make_sim(superstep=4, dtype=dtype)
    sim.run()
    took = opstats.diff(before)
    assert sim.advances == len(sizes)
    assert took.get("collective_ring_narrow", 0) \
        == np.count_nonzero(sizes <= max(ws))
    ref = dc.make_sim(superstep=4, dtype=dtype)
    rungs(())
    ref.run()
    assert sim.events == ref.events
    assert sim.collective_events == ref.collective_events
    assert sim.t == ref.t


def test_a_tiny_tape_keeps_no_rung(tapes):
    """A rung is kept only at an eighth of the flows or below: the tiny
    tapes write every advance flow-wide, and count none."""
    dc = tapes["pairwise16"]
    assert 8 * LOW > dc.n_v
    before = opstats.snapshot()
    sim = dc.make_sim(superstep=4)
    sim.run()
    assert sim.advances > 0
    assert opstats.snapshot().get("collective_ring_narrow", 0) \
        == before.get("collective_ring_narrow", 0)


@pytest.mark.parametrize("name,ws", [
    ("pairwise16", (2, 4)), ("rdb128", (4, 16)), ("pairwise16", ()),
    ("rdb128", ())])
def test_an_advance_the_budget_cuts_logs_nothing(tapes, rungs, name, ws):
    """A dispatch whose round budget runs out inside an advance commits
    the advances before it and not that one, though that one may have
    completed flows on its rates so far: the indexed tape logs it past
    the ring's end, where the program without the index selects the old
    ring back, and the two leave the same ring and state, on a rung or
    flow-wide."""
    dc = tapes[name]
    rungs(ws)
    program = lmm_drain._drain_superstep
    cut = 0
    for start in range(0, 60, 4):
        sim = dc.make_sim(superstep=4, dtype=np.float64)
        sim.run(max_advances=start)
        a, statics = captured(sim)
        a["k"] = np.int32(statics["k_max"])
        for budget in range(2, 8):
            a["round_budget"] = np.int32(budget)
            args = [a[p] for p in PARAMS[:len(a)]]
            new = [np.asarray(o) for o in program(*args, **statics)]
            old = [np.asarray(o) for o in program(*args[:-2], **statics)]
            for n, o in zip(new[:7], old[:7]):
                assert same_bits(n, o)
            assert same_bits(new[7][:-2], old[7])
            cut += (int(new[7][5]) == lmm_drain._FLAG_BUDGET
                    and new[7][1] > 0)
    # some dispatch committed advances, then ran out inside one
    assert cut
