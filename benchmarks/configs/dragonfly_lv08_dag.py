"""The plain reference of the pairwise alltoall: numpy only, float64,
nothing of the program.

``MPI_Alltoall`` as SimGrid's OpenMPI selector stages it for blocks
over 3,000 bytes (``smpi_openmpi_selector.cpp`` ->
``alltoall-pair.cpp``): R - 1 steps, and in step k rank r does one
``sendrecv``: its block to rank (r + k) mod R, a block from rank
(r - k) mod R.  This module builds that dependency graph ITSELF
(``pairwise_dag``), the LV08 max-min system of its R(R - 1) blocks on
the dragonfly (``dag_system``, from ``dragonfly_lv08`` beside it:
routes, constants, the solver), and drains it (``drain``):

* a block is posted when both its ranks have finished step k - 1, the
  send AND the receive of each (four blocks; none for step 1);
* it is on the wire ``latency-factor`` x the sum of its route's link
  latencies later (LV08: 13.01), at penalty 1 and with no window
  bound, as the drain's other cells run their flows;
* the flows on the wire share the links max-min fairly; the clock
  goes to the nearest of the next completion and the next activation
  (both, where they coincide); a flow retires when what is left of it
  falls under ``done_eps`` x its size; flows that retire, or start,
  in one advance share its date.

Flow f is block (r, k) with f = r x (R - 1) + (k - 1); ``Dag`` names
its ranks, so the comparison goes by (sender, receiver) and the two
sides need not number their flows alike.

``precision="bf16"`` is the control (see ``dragonfly_lv08``).
"""

from __future__ import annotations

from typing import List, NamedTuple, Tuple

import numpy as np

from . import dragonfly_lv08 as lv08

#: LV08's latency factor, as published with the constants of
#: ``dragonfly_lv08.LV08``
LATENCY_FACTOR = 13.01

Events = List[Tuple[float, int]]


class Dag(NamedTuple):
    src: np.ndarray        # int64 [F]: the sending rank
    dst: np.ndarray        # int64 [F]: the receiving rank
    preds: np.ndarray      # int64 [F, 4]: flows to wait for, -1: none


def pairwise_dag(ranks: int) -> Dag:
    R = int(ranks)
    r, k = np.divmod(np.arange(R * (R - 1)), R - 1)
    k = k + 1

    def flow(rank, step):
        return (rank % R) * (R - 1) + (step - 1)

    # the sender's send and receive of step k - 1, then the receiver's
    before = np.stack([flow(r, k - 1), flow(r - (k - 1), k - 1),
                       flow(r + k, k - 1), flow(r + 1, k - 1)], axis=1)
    preds = np.where((k > 1)[:, None], before, -1)
    return Dag(r, (r + k) % R, preds)


def dag_system(topo: str, bandwidth: float, latency: float,
               rank_hosts: np.ndarray, dag: Dag
               ) -> Tuple[lv08.RefSystem, np.ndarray]:
    """(the system of every block at penalty 1, each block's delay)."""
    hosts = np.asarray(rank_hosts, np.int64)
    pairs = np.stack([hosts[dag.src], hosts[dag.dst]], axis=1)
    system = lv08.dragonfly_system(topo, bandwidth, latency, pairs,
                                   unit_penalty=True)
    t = lv08.parse_topo(topo)
    ranks = lv08.host_ranks(t["hosts"])[pairs]
    links, _ = lv08.dragonfly_routes(t, ranks[:, 0], ranks[:, 1])
    delay = LATENCY_FACTOR * latency * (links >= 0).sum(axis=1)
    return system, delay


class _Rows:
    """The elements by variable, so that a solve sees the live flows'
    rows and nothing else (320 of 102,080 at a time)."""

    def __init__(self, system: lv08.RefSystem):
        n_v = len(system.v_penalty)
        self.system = system
        self.order = np.argsort(system.e_var, kind="stable")
        self.count = np.bincount(system.e_var, minlength=n_v)
        self.start = np.concatenate([[0], np.cumsum(self.count)[:-1]])

    def solve(self, live: np.ndarray, eps: float, precision: str
              ) -> Tuple[np.ndarray, int]:
        """Rates of the ``live`` flows (indices), the others absent."""
        s = self.system
        n = self.count[live]
        at = self.order[np.arange(n.sum()) - np.repeat(np.cumsum(n) - n, n)
                        + np.repeat(self.start[live], n)]
        sub = lv08.RefSystem(np.repeat(np.arange(len(live)), n),
                             s.e_cnst[at], s.e_w[at], s.c_bound,
                             s.v_penalty[live], s.v_bound[live])
        return lv08.maxmin_solve(sub, eps, precision)


def drain(system: lv08.RefSystem, dag: Dag, delay: np.ndarray,
          sizes: np.ndarray, max_advances: int, eps: float = 1e-9,
          done_eps: float = 1e-4, precision: str = "f64"
          ) -> Tuple[Events, Events, dict]:
    """(completions, activations, counts) of the first ``max_advances``
    advances, each event a (date, flow)."""
    q = lv08.rounder(precision)
    sizes = np.asarray(sizes, np.float64)
    F = len(sizes)
    waits = (dag.preds >= 0).sum(axis=1)
    # successors: the flows that wait for flow f
    after, pred = np.nonzero(dag.preds >= 0)
    pred = dag.preds[after, pred]
    by_pred = np.argsort(pred, kind="stable")
    succ = after[by_pred]
    succ_at = np.concatenate([[0], np.cumsum(np.bincount(pred,
                                                         minlength=F))])
    rows = _Rows(system)
    ready = np.where(waits == 0, delay, np.inf)
    live = np.zeros(F, bool)
    rem = q(sizes.copy())
    t = 0.0
    done_ev: Events = []
    start_ev: Events = []
    advances = rounds = live_sum = 0
    while advances < max_advances and (live.any()
                                       or np.isfinite(ready).any()):
        on = np.flatnonzero(live)
        live_sum += len(on)
        rate = np.zeros(F)
        if len(on):
            rate[on], r = rows.solve(on, eps, precision)
            rounds += r
        flowing = live & (rate > 0)
        dt_plan = float(np.min(q(rem[flowing] / rate[flowing]))) \
            if flowing.any() else np.inf
        next_start = float(ready.min())
        starts = next_start <= t + dt_plan
        dt = next_start - t if starts else dt_plan
        if not np.isfinite(dt):
            raise RuntimeError("reference drain stalled: nothing on the "
                               "wire and nothing waiting for a date")
        rem = np.where(flowing, q(rem - q(rate * dt)), rem)
        done = flowing & (rem < done_eps * sizes)
        t = next_start if starts else t + dt
        advances += 1
        finished = np.flatnonzero(done)
        done_ev.extend((t, int(f)) for f in finished)
        rem[done] = 0.0
        live &= ~done
        if starts:
            started = np.flatnonzero(ready <= next_start)
            start_ev.extend((t, int(f)) for f in started)
            live[started] = True
            ready[started] = np.inf
        for f in finished:
            nxt = succ[succ_at[f]:succ_at[f + 1]]
            waits[nxt] -= 1
            posted = nxt[waits[nxt] == 0]
            ready[posted] = t + delay[posted]
    return done_ev, start_ev, dict(advances=advances, rounds=rounds,
                                   t_sim=t, live_flow_advances=live_sum,
                                   completed=len(done_ev))
