"""The host's replay of a fetched ring (``DrainSim._demux``), held to
the per-entry loop it replaced.

``_demux_loop`` below is that loop, kept word for word as the statement
of the semantics: every ring entry of every advance, in ring order, a
completion into ``events`` and its advance's batch, a tagged entry into
the fault stream (``idx < n_c``) or the activation stream, each dated by
the collective's replayed Kahan pair or by the dispatch's base clock plus
the entry's own offset.  Both run on the same hand-built packed vectors,
on stand-ins that carry only the state they read and write, and must
agree on every stream, every batch, the fire count, the clocks and the
tape cursor, to the bit and to the Python type: dates ``float`` and ids
``int``, never numpy scalars."""

import types
from typing import List, Tuple

import numpy as np
import pytest

from simgrid_tpu.ops import opstats
from simgrid_tpu.ops.lmm_drain import _STATS_HEAD, DrainSim


def _demux_loop(self, p: np.ndarray, adv: int, k_max: int, t_sum: float
                ) -> Tuple[List[Tuple[float, List[int]]], int]:
    """Replay one fetched ring into ``events`` (and the fault and
    collective streams) and the f64 master clock; returns the
    per-advance ``(dt, [flow ids])`` batches and how many fault
    entries fired."""
    o = _STATS_HEAD
    adv_dt = p[o:o + k_max]
    adv_nev = p[o + k_max:o + 2 * k_max].astype(np.int64)
    o += 2 * k_max
    ring_n = (self.n_v + (k_max if self.has_tape else 0)
              + (self.n_v if self.has_coll else 0))
    ring_t = p[o:o + ring_n]
    ring_id = p[o + ring_n:o + 2 * ring_n].astype(np.int64)
    batches: List[Tuple[float, List[int]]] = []
    start = 0
    # collective dates are ABSOLUTE (the Kahan clock pair is carried
    # across dispatches), so the base folds to zero
    t_base = 0.0 if self.has_coll else self.t
    fired = 0
    if self.has_tape or self.has_coll:
        # demux the ring: negative ids are tagged entries — fault
        # fires (idx < n_c, into the fault stream) or collective
        # activations (idx >= n_c, flow idx - n_c fired into the
        # activation stream) — neither joins the completion batches
        for i in range(adv):
            end = int(adv_nev[i])
            batch_ids: List[int] = []
            if self.has_coll:
                # the ring's dates are in the solve dtype; the
                # advance's own is the device's float64 pair, one
                # step of the same recurrence on its exact dt (the
                # step HostMaestro takes)
                t_c, comp = self._coll_clk_host
                y = float(adv_dt[i]) - comp
                t_adv = t_c + y
                self._coll_clk_host = (t_adv, (t_adv - t_c) - y)
            for j in range(start, end):
                fid = int(ring_id[j])
                tj = (t_adv if self.has_coll
                      else t_base + float(ring_t[j]))
                if fid < 0:
                    idx = -fid - 1
                    if idx >= self.n_c:
                        self.collective_events.append(
                            (tj, idx - self.n_c))
                    else:
                        self.fault_events.append((tj, idx))
                        fired += 1
                else:
                    batch_ids.append(fid)
                    self.events.append((tj, fid))
            batches.append((float(adv_dt[i]), batch_ids))
            start = end
        self._tpos_host += fired
        self._last_fired = fired > 0
        if fired:
            opstats.bump("fault_tape_events", fired)
    else:
        for i in range(adv):
            end = int(adv_nev[i])
            batches.append((float(adv_dt[i]),
                            [int(f) for f in ring_id[start:end]]))
            for j in range(start, end):
                self.events.append((t_base + float(ring_t[j]),
                                    int(ring_id[j])))
            start = end
    # f64 master clock: one Kahan-compensated dtype total per
    # superstep, accumulated on host in f64 (a collective's is the
    # absolute clock of the pair replayed above)
    self.t = (self._coll_clk_host[0] if self.has_coll
              else t_base + t_sum)
    return batches, fired


N_V, N_C, K_MAX = 12, 5, 4


def _sim(kind, t0=0.0):
    """A stand-in carrying the state ``_demux`` reads and writes."""
    has_tape = kind in ("tape", "tape_coll")
    has_coll = kind in ("coll", "tape_coll")
    s = types.SimpleNamespace(
        n_v=N_V, n_c=N_C, has_tape=has_tape, has_coll=has_coll, t=t0,
        events=[], fault_events=[], _tpos_host=0, _last_fired=False)
    if has_coll:
        s.collective_events = []
        s._coll_clk_host = (t0, 0.0)
    return s


def _ring_n(kind):
    return (N_V + (K_MAX if kind in ("tape", "tape_coll") else 0)
            + (N_V if kind in ("coll", "tape_coll") else 0))


def _packed(kind, advances, dtype, rng):
    """One packed vector: the stats head (``p[3]`` the dispatch's
    total), the dt and cumulative-count tables, the date ring and the id
    ring, then a tail ``_demux`` never reads.  ``advances`` lists each
    advance's entries as flow ids, a fault fire as ``("f", slot)`` and
    an activation as ``("a", flow)``."""
    ring_n = _ring_n(kind)
    head = np.zeros(_STATS_HEAD)
    dt = np.zeros(K_MAX)
    nev = np.zeros(K_MAX)
    ring_t = np.full(ring_n, -7.0)     # past the last entry: junk
    ring_id = np.full(ring_n, -99.0)
    n = 0
    for i, ents in enumerate(advances):
        dt[i] = rng.uniform(0.01, 3.0)
        for e in ents:
            if isinstance(e, tuple):
                tag, x = e
                fid = -1 - (x if tag == "f" else N_C + x)
            else:
                fid = e
            ring_id[n] = fid
            ring_t[n] = dt[:i + 1].sum() - rng.uniform(0, 1e-3)
            n += 1
        nev[i] = n
    head[3] = dt.sum()
    p = np.concatenate([head, dt, nev, ring_t, ring_id,
                        rng.uniform(size=5)]).astype(dtype)
    return p, len(advances)


# each case: the sim's kind and one or more dispatches, each a list of
# advances (the lists of entries one advance logs)
CASES = {
    "plain": ("plain", [[[3, 0, 7], [1], [11, 2, 4]]]),
    "tape": ("tape", [[[3, ("f", 2), 0], [("f", 4)], [5, 6]],
                      [[1, ("f", 0), ("f", 3)]]]),
    "coll": ("coll", [[[3, ("a", 9), ("a", 1)], [0, 2, ("a", 11)]],
                      [[("a", 4)], [4, 9, 1, 11]]]),
    "tape_coll": ("tape_coll",
                  [[[3, 7, ("f", 1), ("a", 0), ("a", 10)],
                    [0, ("a", 6)], [("f", 4)]],
                   [[6, ("f", 2), ("a", 3)], [10, 1]]]),
    "silent_advance": ("tape_coll",
                       [[[3, ("a", 2)], [], [2, ("f", 0)], []]]),
    "no_advance": ("tape_coll", [[]]),
    "no_advance_plain": ("plain", [[]]),
    "full_ring_plain": ("plain", [[list(range(6)),
                                   list(range(6, N_V))]]),
    "full_ring_coll": ("tape_coll", [[
        list(range(N_V)) + [("f", 1)],
        [("a", j) for j in range(N_V)] + [("f", k) for k in (0, 2, 3)]]]),
}


def _state(s):
    return {k: v for k, v in vars(s).items()
            if k not in ("n_v", "n_c", "has_tape", "has_coll")}


def _assert_types(state, batches):
    for name in ("events", "fault_events", "collective_events"):
        for t, fid in state.get(name, ()):
            assert type(t) is float and type(fid) is int, (name, t, fid)
    for dt, ids in batches:
        assert type(dt) is float and type(ids) is list
        assert all(type(f) is int for f in ids)
    assert type(state["t"]) is float
    if "_coll_clk_host" in state:
        assert all(type(x) is float for x in state["_coll_clk_host"])


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("case", list(CASES))
def test_demux_matches_the_entry_loop(case, dtype):
    kind, dispatches = CASES[case]
    rng = np.random.default_rng(sum(map(ord, case)))
    old, new = _sim(kind, 1.25), _sim(kind, 1.25)
    for advances in dispatches:
        p, adv = _packed(kind, advances, dtype, rng)
        t_sum = float(p[3])
        got = DrainSim._demux(new, p, adv, K_MAX, t_sum)
        want = _demux_loop(old, p, adv, K_MAX, t_sum)
        assert got == want
        assert _state(new) == _state(old)
        _assert_types(_state(new), got[0])
    # the stand-ins saw every entry the dispatches logged
    n_logged = sum(len(a) for d in dispatches for a in d)
    assert (len(new.events) + len(new.fault_events)
            + len(getattr(new, "collective_events", ()))) == n_logged


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("kind", ["plain", "tape", "coll", "tape_coll"])
def test_demux_matches_the_entry_loop_on_random_rings(kind, dtype):
    rng = np.random.default_rng(7 + len(kind))
    old, new = _sim(kind, 0.5), _sim(kind, 0.5)
    for _ in range(6):
        adv = int(rng.integers(0, K_MAX + 1))
        advances = []
        for _ in range(adv):
            ents = [int(f) for f in rng.permutation(N_V)[:rng.integers(
                0, 4)]]
            if kind in ("tape", "tape_coll") and rng.random() < 0.5:
                ents.append(("f", int(rng.integers(N_C))))
            if kind in ("coll", "tape_coll"):
                ents += [("a", int(f)) for f in rng.permutation(N_V)[
                    :rng.integers(0, 3)]]
            advances.append(ents)
        p, adv = _packed(kind, advances, dtype, rng)
        t_sum = float(p[3])
        got = DrainSim._demux(new, p, adv, K_MAX, t_sum)
        assert got == _demux_loop(old, p, adv, K_MAX, t_sum)
        assert _state(new) == _state(old)
        _assert_types(_state(new), got[0])


def test_demux_counts_the_fires():
    kind, dispatches = CASES["tape_coll"]
    rng = np.random.default_rng(3)
    s = _sim(kind)
    before = opstats.snapshot().get("fault_tape_events", 0)
    p, adv = _packed(kind, dispatches[0], np.float32, rng)
    _batches, fired = DrainSim._demux(s, p, adv, K_MAX, float(p[3]))
    assert fired == 2 and s._tpos_host == 2 and s._last_fired
    assert [f for _t, f in s.fault_events] == [1, 4]
    # a fault fire takes its advance's date, as the completions do
    assert s.fault_events[0][0] == s.events[0][0]
    assert [f for _t, f in s.collective_events] == [0, 10, 6]
    assert (opstats.snapshot().get("fault_tape_events", 0)
            - before) == 2
