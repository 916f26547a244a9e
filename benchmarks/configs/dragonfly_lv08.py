"""The plain reference: numpy only, nothing of the program.

From the deployment's own description (dragonfly ``topo_parameters``,
link bandwidth and latency, the LV08 network model's published
constants) and the host pairs the traffic generator drew, this module builds
the max-min system itself (``dragonfly_system``), solves it
(``maxmin_solve``) and drains it (``drain``), in float64.  The
program's answers are compared per FLOW, so the two sides need not
number their links or variables alike.

``precision="bf16"`` is the control: the same code with every
arithmetic result rounded to bfloat16, the nearest precision below the
float32 the configuration states.

Semantics (SimGrid, ``DragonflyZone.cpp`` minimal routing,
``network_cm02.cpp`` LV08, ``maxmin.cpp``):

* a flow uses every directed link of its route with weight 1 and every
  link of the way back with weight 0.05 (cross-traffic);
* a link's capacity is ``bandwidth-factor`` x its bandwidth, and a link
  class with n parallel cables has n times the bandwidth;
* a flow's sharing penalty is the sum of its route's latencies plus
  ``weight-S / bandwidth`` for each link of the route; its rate is
  capped at ``TCP-gamma / (2 x latency)``;
* rates are the weighted max-min fair allocation: ``rate_i = level /
  penalty_i`` at the flow's bottleneck, found by saturating, round after
  round, every constraint whose level is the least among the
  constraints it shares a flow with (a local minimum is final: nothing
  that is fixed later can lower it).
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

#: LV08 (SimGrid's default network model), as published
LV08 = dict(bandwidth_factor=0.97, weight_s=20537.0, tcp_gamma=4194304.0,
            crosstraffic_weight=0.05)


class RefSystem(NamedTuple):
    """A max-min system in the reference's own numbering; variable i is
    flow i of the pairs it was built from."""
    e_var: np.ndarray      # int64 [E]
    e_cnst: np.ndarray     # int64 [E]
    e_w: np.ndarray        # float64 [E]
    c_bound: np.ndarray    # float64 [C]
    v_penalty: np.ndarray  # float64 [V]
    v_bound: np.ndarray    # float64 [V], <= 0: unbounded

    @property
    def shape(self) -> Tuple[int, int, int]:
        return len(self.c_bound), len(self.v_penalty), len(self.e_var)


# ---------------------------------------------------------------------------
# precision: float64, or the control's bfloat16
# ---------------------------------------------------------------------------

def _round_bf16(x: np.ndarray) -> np.ndarray:
    """Round to the nearest bfloat16 (ties to even), kept as float64."""
    f = np.asarray(x, np.float32)
    bits = f.view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    out = bits.astype(np.uint32).view(np.float32).astype(np.float64)
    return np.where(np.isfinite(f), out, f.astype(np.float64))


def rounder(precision: str):
    if precision == "f64":
        return lambda x: x
    if precision == "bf16":
        return _round_bf16
    raise ValueError(f"unknown precision {precision!r} (f64 or bf16)")


# ---------------------------------------------------------------------------
# the deployment: dragonfly routes and the LV08 system
# ---------------------------------------------------------------------------

def parse_topo(topo: str) -> Dict[str, int]:
    """'groups,blue;chassis,black;blades,green;nodes'."""
    parts = [p.split(",") for p in topo.split(";")]
    if len(parts) != 4 or [len(p) for p in parts] != [2, 2, 2, 1]:
        raise ValueError(f"not a dragonfly topo_parameters: {topo!r}")
    g, blue = map(int, parts[0])
    c, black = map(int, parts[1])
    b, green = map(int, parts[2])
    return dict(groups=g, blue=blue, chassis=c, black=black, blades=b,
                green=green, nodes=int(parts[3][0]),
                hosts=g * c * b * int(parts[3][0]))


def host_ranks(n_hosts: int, prefix: str = "node-") -> np.ndarray:
    """rank[i]: the cluster rank of the i-th host by name ("node-10"
    sorts before "node-2"): a pair's index counts the hosts in the
    order of their names, as SimGrid lists them."""
    return np.array(sorted(range(n_hosts), key=lambda r: f"{prefix}{r}"),
                    np.int64)


def dragonfly_routes(t: Dict[str, int], src: np.ndarray, dst: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Minimal routing, every flow at once.  Returns (links, mult):
    ``links[F, 7]`` directed link ids (-1: hop not taken) and the
    number of parallel cables of each hop's class, in hop order: local
    up, green, black, blue, green, black, local down."""
    ng, nc, nb, nn = t["groups"], t["chassis"], t["blades"], t["nodes"]
    per_group = nc * nb * nn

    def coords(rank):
        g, r = np.divmod(rank, per_group)
        c, r = np.divmod(r, nb * nn)
        b, n = np.divmod(r, nn)
        return g, c, b, n

    mg, mc, mb, mn = coords(np.asarray(src, np.int64))
    tg, tc, tb, tn = coords(np.asarray(dst, np.int64))
    n_routers = ng * nc * nb
    # directed link ids, one block per class
    off_up = 0
    off_down = off_up + n_routers * nn
    off_green = off_down + n_routers * nn
    off_black = off_green + ng * nc * nb * nb
    off_blue = off_black + ng * nb * nc * nc

    def green(g, c, frm, to):
        return off_green + ((g * nc + c) * nb + frm) * nb + to

    def black(g, b, frm, to):
        return off_black + ((g * nb + b) * nc + frm) * nc + to

    F = len(mg)
    links = np.full((F, 7), -1, np.int64)
    my_router = (mg * nc + mc) * nb + mb
    to_router = (tg * nc + tc) * nb + tb
    links[:, 0] = off_up + my_router * nn + mn
    links[:, 6] = off_down + to_router * nn + tn

    other_router = my_router != to_router
    other_group = other_router & (tg != mg)
    # towards the gateway of our group: the blade numbered like the
    # target group, in chassis 0
    cur_c, cur_b = mc.copy(), mb.copy()
    hop = other_group & (cur_b != tg)
    links[hop, 1] = green(mg, mc, mb, tg)[hop]
    cur_b = np.where(hop, tg, cur_b)
    hop = other_group & (cur_c != 0)
    links[hop, 2] = black(mg, cur_b, cur_c, 0)[hop]
    cur_c = np.where(hop, 0, cur_c)
    links[other_group, 3] = (off_blue + mg * ng + tg)[other_group]
    # the peer gateway is the router of flat in-group offset mg
    cur_c = np.where(other_group, mg // nb, cur_c)
    cur_b = np.where(other_group, mg % nb, cur_b)
    hop = other_router & (tb != cur_b)
    links[hop, 4] = green(tg, cur_c, cur_b, tb)[hop]
    # DragonflyZone.cpp lands on the flat offset tb: chassis tb // nb
    cur_c = np.where(hop, tb // nb, cur_c)
    cur_b = np.where(hop, tb % nb, cur_b)
    hop = other_router & (tc != cur_c)
    links[hop, 5] = black(tg, cur_b, cur_c, tc)[hop]

    mult = np.array([1, t["green"], t["black"], t["blue"], t["green"],
                     t["black"], 1], np.float64)
    return links, np.broadcast_to(mult, links.shape)


def dragonfly_system(topo: str, bandwidth: float, latency: float,
                     pairs: np.ndarray, model: Dict[str, float] = LV08,
                     unit_penalty: bool = False) -> RefSystem:
    """The LV08 max-min system of ``pairs`` on the dragonfly, every flow
    past its latency phase.  ``unit_penalty`` gives the drain's model:
    every flow at penalty 1 and no window bound."""
    t = parse_topo(topo)
    ranks = host_ranks(t["hosts"])[np.asarray(pairs, np.int64)]
    fwd, mult = dragonfly_routes(t, ranks[:, 0], ranks[:, 1])
    back, _ = dragonfly_routes(t, ranks[:, 1], ranks[:, 0])
    F = len(pairs)
    taken = fwd >= 0
    hops = taken.sum(axis=1)
    lat = hops * latency
    penalty = lat + np.where(taken, model["weight_s"]
                             / (bandwidth * mult), 0.0).sum(axis=1)
    v_bound = model["tcp_gamma"] / (2.0 * lat)
    flow = np.broadcast_to(np.arange(F)[:, None], fwd.shape)
    btaken = back >= 0
    link = np.concatenate([fwd[taken], back[btaken]])
    e_var = np.concatenate([flow[taken], flow[btaken]])
    e_w = np.concatenate([np.ones(int(taken.sum())),
                          np.full(int(btaken.sum()),
                                  model["crosstraffic_weight"])])
    cap = np.concatenate([mult[taken], mult[btaken]])
    used, e_cnst = np.unique(link, return_inverse=True)
    c_mult = np.zeros(len(used))
    c_mult[e_cnst] = cap
    c_bound = model["bandwidth_factor"] * bandwidth * c_mult
    if unit_penalty:
        penalty = np.ones(F)
        v_bound = np.full(F, -1.0)
    return RefSystem(e_var.astype(np.int64), e_cnst.astype(np.int64),
                     e_w, c_bound, penalty, v_bound)


# ---------------------------------------------------------------------------
# weighted max-min fair rates
# ---------------------------------------------------------------------------

class _Segments:
    """The elements sorted by variable, for per-variable minima."""

    def __init__(self, e_var: np.ndarray, n_var: int):
        self.order = np.argsort(e_var, kind="stable")
        counts = np.bincount(e_var, minlength=n_var)
        self.has = counts > 0
        self.starts = np.concatenate([[0], np.cumsum(counts)[:-1]])[self.has]
        self.n_var = n_var

    def min_per_var(self, per_elem: np.ndarray) -> np.ndarray:
        out = np.full(self.n_var, np.inf)
        if len(self.starts):
            out[self.has] = np.minimum.reduceat(per_elem[self.order],
                                                self.starts)
        return out


def maxmin_solve(sys: RefSystem, eps: float = 1e-9,
                 precision: str = "f64", live: Optional[np.ndarray] = None,
                 max_rounds: int = 100_000) -> Tuple[np.ndarray, int]:
    """Rates of every variable (0 for one that is not ``live``) and the
    number of saturation rounds."""
    q = rounder(precision)
    n_c, n_v, _ = sys.shape
    e_var, e_cnst = sys.e_var, sys.e_cnst
    active = (sys.v_penalty > 0) if live is None \
        else (live & (sys.v_penalty > 0))
    keep = active[e_var] & (sys.e_w > 0)
    e_var, e_cnst, e_w = e_var[keep], e_cnst[keep], q(sys.e_w[keep])
    seg = _Segments(e_var, n_v)
    pen = q(np.where(active, sys.v_penalty, 1.0))
    share = q(e_w / pen[e_var])            # usage a flow adds per level
    bound_level = np.where(sys.v_bound > 0, q(q(sys.v_bound) * pen), np.inf)
    remaining = q(sys.c_bound.astype(np.float64))
    value = np.zeros(n_v)
    free = active & seg.has
    value[active & ~seg.has] = 0.0
    rounds = 0
    while free.any():
        rounds += 1
        if rounds > max_rounds:
            raise RuntimeError("reference max-min did not converge")
        fe = free[e_var]
        usage = q(np.bincount(e_cnst[fe], weights=share[fe], minlength=n_c))
        busy = usage > 0
        level = np.where(busy, q(remaining / np.where(busy, usage, 1.0)),
                         np.inf)
        level = np.maximum(level, 0.0)
        # a variable's level: the least over its constraints and its
        # own window bound
        v_level = np.minimum(seg.min_per_var(level[e_cnst]), bound_level)
        # a constraint is a local minimum when no free variable on it
        # sees a lower level anywhere else
        c_floor = np.full(n_c, np.inf)
        np.minimum.at(c_floor, e_cnst[fe], v_level[e_var[fe]])
        saturated = busy & (level <= c_floor * (1.0 + eps))
        on_sat = np.zeros(n_v, bool)
        on_sat[e_var[fe & saturated[e_cnst]]] = True
        # a bounded variable whose bound lies under every level it sees
        at_bound = free & (bound_level <= v_level * (1.0 + eps)) \
            & np.isfinite(bound_level)
        fix = free & (on_sat | at_bound)
        if not fix.any():
            raise RuntimeError("reference max-min stalled")
        value[fix] = q(v_level[fix] / pen[fix])
        fx = fix[e_var]
        remaining = q(remaining - q(np.bincount(
            e_cnst[fx], weights=q(e_w[fx] * value[e_var[fx]]),
            minlength=n_c)))
        remaining = np.maximum(remaining, 0.0)
        free &= ~fix
    return value, rounds


# ---------------------------------------------------------------------------
# the drain: solve, advance to the first completion, retire, repeat
# ---------------------------------------------------------------------------

def drain(sys: RefSystem, sizes: np.ndarray, max_advances: int,
          eps: float = 1e-9, done_eps: float = 1e-4,
          precision: str = "f64"
          ) -> Tuple[List[Tuple[float, int]], dict]:
    """Completion events (date, flow) of the first ``max_advances``
    advances.  A flow retires when what is left of it falls under
    ``done_eps`` x its size (SimGrid's relative sg_maxmin_precision
    rule); flows retiring in one advance share its date."""
    q = rounder(precision)
    sizes = np.asarray(sizes, np.float64)
    rem = q(sizes.copy())
    live = np.ones(len(sizes), bool)
    t = 0.0
    events: List[Tuple[float, int]] = []
    advances = rounds = 0
    while live.any() and advances < max_advances:
        rate, r = maxmin_solve(sys, eps, precision, live=live)
        rounds += r
        flowing = live & (rate > 0)
        dt = float(np.min(q(rem[flowing] / rate[flowing]))) \
            if flowing.any() else np.inf
        if not np.isfinite(dt):
            raise RuntimeError("reference drain stalled")
        rem = np.where(flowing, q(rem - q(rate * dt)), rem)
        done = flowing & (rem < done_eps * sizes)
        t += dt
        advances += 1
        events.extend((t, int(f)) for f in np.flatnonzero(done))
        rem[done] = 0.0
        live &= ~done
    return events, dict(advances=advances, rounds=rounds, t_sim=t)
