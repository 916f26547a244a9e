"""The deep end of `solve_arrays` (ISSUE 29): an alltoall's max-min
system converges in many dependent rounds, so its solve runs the chunk
loop more than once, and `fixpoint` counts the live elements each round
entered with (`opstats` `fixpoint_live_elem_rounds`).

First half: a 16-rank alltoall on a 128-host dragonfly (the benchmark's
tiny twin of `dfly65k-alltoall`) through `Engine` -> `communicate` ->
`flatten` -> `solve_arrays` in float32, against the benchmark's plain
reference per flow, and chunked against unchunked.  Second half: the
counter against a numpy recount."""

import importlib.util
import os

import numpy as np
import pytest

from bench import build_arrays
from simgrid_tpu import s4u
from simgrid_tpu.ops import lmm_jax, opstats
from simgrid_tpu.utils.config import config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOPO, HOSTS, RANKS = "4,3;2,2;4,2;4", 128, 16
BW, LAT = 125e6, 50e-6
EPS = 1e-5

PLATFORM = f"""<?xml version='1.0'?>
<platform version="4.1">
  <zone id="world" routing="Full">
    <cluster id="dfly" prefix="node-" radical="0-{HOSTS - 1}" suffix=""
             speed="1Gf" bw="125MBps" lat="50us" topology="DRAGONFLY"
             topo_parameters="{TOPO}"/>
  </zone>
</platform>
"""


def reference():
    """benchmarks/configs/dragonfly_lv08.py: numpy, float64, imports
    nothing of the program."""
    spec = importlib.util.spec_from_file_location(
        "dragonfly_lv08", os.path.join(ROOT, "benchmarks", "configs",
                                       "dragonfly_lv08.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rank_major(rng=None):
    """Ranks post in rank order; each its sends in rank order, or in an
    order of its own drawn from ``rng``."""
    stride = HOSTS // RANKS
    return np.array([
        (i * stride, j * stride) for i in range(RANKS)
        for j in (np.delete(np.arange(RANKS), i) if rng is None
                  else rng.permutation(np.delete(np.arange(RANKS), i)))])


def alltoall_pairs(order_seed):
    """The pairs in an order drawn over all of them."""
    pairs = rank_major()
    return pairs[np.random.default_rng(order_seed).permutation(len(pairs))]


def flattened(tmp_path, pairs):
    """(float32 LmmArrays of the posted alltoall, flow of each slot)."""
    path = tmp_path / "dfly128.xml"
    path.write_text(PLATFORM)
    s4u.Engine._reset()
    e = s4u.Engine(["alltoall", "--cfg=network/maxmin-selective-update:no",
                    "--cfg=network/optim:Full", "--cfg=lmm/backend:native"])
    e.load_platform(str(path))
    hosts = e.get_all_hosts()
    model = e.pimpl.network_model
    actions = [model.communicate(hosts[s], hosts[d], 1e6, -1.0)
               for s, d in pairs.tolist()]
    for _ in range(400):
        if not model.latency_phase_count:
            break
        assert e.pimpl.surf_solve(-1.0) >= 0
    src, vars_in_order = lmm_jax.flatten(
        list(model.system.active_constraint_set))
    slot = {id(a.variable): k for k, a in enumerate(actions)}
    slot_flow = np.array([slot[id(v)] for v in vars_in_order])
    f32 = np.float32
    return src._replace(
        e_w=src.e_w.astype(f32), c_bound=src.c_bound.astype(f32),
        v_penalty=src.v_penalty.astype(f32),
        v_bound=src.v_bound.astype(f32)), slot_flow


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    pairs = alltoall_pairs(7)
    arrays, slot_flow = flattened(tmp_path_factory.mktemp("a2a"), pairs)
    return pairs, arrays, slot_flow


def test_the_tiny_alltoall_flattens_to_the_references_system(tiny):
    pairs, arrays, _ = tiny
    mine = reference().dragonfly_system(TOPO, BW, LAT, pairs,
                                        unit_penalty=False)
    assert (arrays.n_cnst, arrays.n_var, arrays.n_elem) == mine.shape
    assert mine.shape == (114, 240, 2418)


@pytest.mark.parametrize("order_seed", [7, 2**31 + 11])
def test_float32_rates_are_the_references_per_flow(order_seed, tmp_path):
    ref = reference()
    pairs = alltoall_pairs(order_seed)
    arrays, slot_flow = flattened(tmp_path, pairs)
    values, _, _, rounds = lmm_jax.solve_arrays(arrays, EPS)
    got = np.zeros(len(pairs))
    got[slot_flow] = np.asarray(values)[:arrays.n_var]
    system = ref.dragonfly_system(TOPO, BW, LAT, pairs, unit_penalty=False)
    want, _ = ref.maxmin_solve(system, eps=1e-9)
    floor = 2 * EPS * float(np.max(system.c_bound))
    gap = np.max(np.abs(got - want) / np.maximum(want, floor))
    assert gap < 1e-5, gap              # the cell's limit is 2e-3
    assert rounds > 8                   # random pairs need 12 at 600 flows
    # the bfloat16 control is far over the cell's limit
    low, _ = ref.maxmin_solve(system, eps=EPS, precision="bf16")
    assert np.max(np.abs(low - want) / np.maximum(want, floor)) > 2e-3


def rate_by_pair(tmp_path, pairs):
    arrays, slot_flow = flattened(tmp_path, pairs)
    values, _, _, rounds = lmm_jax.solve_arrays(arrays, EPS)
    values = np.asarray(values)
    return {tuple(pairs[f]): values[k]
            for k, f in enumerate(slot_flow)}, int(rounds)


@pytest.mark.parametrize("order_seed", [1, 2**31 + 5])
def test_a_ranks_own_order_of_sends_leaves_the_solve_as_it_was(
        order_seed, tmp_path):
    """The benchmark's seeds: ranks post in rank order, each its sends
    in an order of its own.  The float32 solve is then the same to the
    bit, flow by flow; an order drawn over all pairs is not (at 65,536
    hosts it moves the round count: 283 against 306-318, PERF.md)."""
    want = rate_by_pair(tmp_path, rank_major())
    got = rate_by_pair(tmp_path, rank_major(
        np.random.default_rng(order_seed)))
    assert got == want
    over_all, rounds = rate_by_pair(tmp_path, alltoall_pairs(order_seed))
    assert over_all != want[0] and rounds == want[1]
    gaps = [abs(over_all[k] - v) / v for k, v in want[0].items()]
    assert max(gaps) < 1e-5


@pytest.mark.parametrize("chunk", [1, 3, 4])
def test_a_chunked_solve_is_the_one_chunk_solve_bit_for_bit(tiny, chunk):
    _, arrays, _ = tiny
    whole = lmm_jax.solve_arrays(arrays, EPS)
    before = opstats.snapshot()
    parts = lmm_jax.solve_arrays(arrays, EPS, chunk=chunk)
    took = opstats.diff(before)
    assert took["dispatches"] == -(-int(whole[3]) // chunk) >= 4
    assert int(parts[3]) == int(whole[3])
    for a, b in zip(whole[:3], parts[:3]):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


# ---------------------------------------------------------------------------
# fixpoint_live_elem_rounds
# ---------------------------------------------------------------------------

def bench_system(bounds, fatpipe, dtype=np.float32, n_c=64, n_v=256):
    a = build_arrays(np.random.default_rng(5), n_c, n_v, 3, dtype)
    if bounds == "bind":
        a.v_bound[:n_v // 2] = np.random.default_rng(6).uniform(
            0.01, 0.5, n_v // 2)
    elif bounds == "never":
        a.v_bound[:n_v // 2] = 1e6
    if fatpipe:
        a.c_fatpipe[:n_c // 4] = True
    return a


def recount(arrays, parallel_rounds):
    """Live elements entering each round, counted in numpy from the
    fixed flags a one-round-a-dispatch solve hands back."""
    has_bounds = bool(np.any((arrays.v_bound > 0) & (arrays.v_penalty > 0)))
    has_fatpipe = bool(np.any(arrays.c_fatpipe))
    valid = (arrays.e_w > 0) & (arrays.v_penalty[arrays.e_var] > 0)
    fixed = np.asarray(arrays.v_penalty < 0)
    carry, per_round = None, []
    while True:
        out = lmm_jax._solve_kernel_chunk(
            arrays.e_var, arrays.e_cnst, arrays.e_w, arrays.c_bound,
            arrays.c_fatpipe, arrays.v_penalty, arrays.v_bound, carry,
            eps=EPS, n_c=len(arrays.c_bound), n_v=len(arrays.v_penalty),
            parallel_rounds=parallel_rounds, chunk=1,
            has_bounds=has_bounds, has_fatpipe=has_fatpipe)
        carry = out[4]
        if int(out[3]) == len(per_round):     # converged: no round ran
            return per_round
        per_round.append(int(np.sum(valid & ~fixed[arrays.e_var])))
        assert lmm_jax._live_elem_rounds(np.asarray(out[6])) \
            == per_round[-1]
        fixed = np.asarray(carry[1])


def counted(arrays, **kw):
    before = opstats.snapshot()
    rounds = int(lmm_jax.solve_arrays(arrays, EPS, **kw)[3])
    took = opstats.diff(before)
    assert took["fixpoint_rounds"] == rounds
    return took.get("fixpoint_live_elem_rounds", 0), rounds


@pytest.mark.parametrize("parallel_rounds", [True, False],
                         ids=["local", "global"])
@pytest.mark.parametrize("fatpipe", [False, True], ids=["shared", "fatpipe"])
@pytest.mark.parametrize("bounds", ["bind", "never", None])
def test_live_elements_are_a_numpy_recount(bounds, fatpipe, parallel_rounds):
    config["lmm/layout"] = "coo"
    arrays = bench_system(bounds, fatpipe)
    per_round = recount(arrays, parallel_rounds)
    assert per_round[0] == int(np.sum(
        (arrays.e_w > 0) & (arrays.v_penalty[arrays.e_var] > 0)))
    assert per_round == sorted(per_round, reverse=True) and per_round[-1] > 0
    live, rounds = counted(arrays, parallel_rounds=parallel_rounds)
    assert (live, rounds) == (sum(per_round), len(per_round))
    # additive over chunks, whatever the cut
    for chunk in (1, 2, 5):
        assert counted(arrays, parallel_rounds=parallel_rounds,
                       chunk=chunk) == (live, rounds)


def test_live_elements_of_the_tiny_alltoall(tiny):
    _, arrays, _ = tiny
    per_round = recount(arrays, True)
    live, rounds = counted(arrays)
    assert (live, rounds) == (sum(per_round), len(per_round))
    assert per_round[0] == arrays.n_elem == 2418
    share = live / (rounds * arrays.n_elem)
    assert 0.2 < share < 0.6              # most indexed work is on the dead


@pytest.mark.parametrize("compact", ["on", "off"])
def test_compaction_leaves_the_count_alone(compact):
    """A repacked element list holds exactly the live elements."""
    config["lmm/layout"] = "coo"
    arrays = bench_system("never", False, n_c=256, n_v=2048)
    assert arrays.n_elem >= lmm_jax._COMPACT_MIN_ELEMS
    want = sum(recount(arrays, True))
    config["lmm/compact"] = compact
    assert counted(arrays)[0] == want


def test_the_pair_is_exact_past_two_to_the_24():
    """The chunk fetch ships its head in the solve's dtype: each half
    of the pair survives float32, their sum is a Python int."""
    total = 283 * 1_275_102 + 12_345
    pair = np.array([total >> lmm_jax._LIVE_LOW_BITS,
                     total & ((1 << lmm_jax._LIVE_LOW_BITS) - 1)])
    assert float(np.float32(total)) != total
    assert lmm_jax._live_elem_rounds(pair.astype(np.float32)) == total


def test_an_ell_solve_counts_no_live_elements():
    config["lmm/layout"] = "ell"
    opstats.reset()
    lmm_jax.solve_arrays(bench_system(None, False), EPS)
    assert "fixpoint_live_elem_rounds" not in opstats.snapshot()
    config["lmm/layout"] = "coo"
    lmm_jax.solve_arrays(bench_system(None, False), EPS)
    assert opstats.snapshot()["fixpoint_live_elem_rounds"] > 0
