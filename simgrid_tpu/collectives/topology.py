"""Topology flavors for compiled collective schedules.

A topology maps a (src, dst) rank pair to the constraint slots the
transfer's LMM variable rides — the route half of the tape record.
Three flavors cover the sweep axes the campaign layer exposes:

* ``nic``  — per-rank full-duplex NICs over a non-blocking fabric:
  route = [tx(src), rx(dst)].  The distributed-ML default (a pod's
  ICI/optical fabric is provisioned so endpoints, not the core, are
  the contended resource).
* ``star`` — per-rank NICs plus ONE shared core constraint:
  route = [tx(src), core, rx(dst)] — an oversubscribed aggregation
  switch, the adversarial case for ring-free algorithms.
* ``ring`` — R physical links; a transfer crosses every link on the
  shorter arc from src to dst (ties go clockwise).  Ring allreduce is
  contention-free here; rdb hop distances grow with the mask.

Every such flavor also provisions a per-rank LOOPBACK constraint: the
lr allreduce posts a literal sendrecv-to-self (allreduce-lr.cpp:69-73)
and self-transfers must ride a dedicated resource, mirroring the
reference platform's loopback link, not the fabric.

* ``routed`` — :class:`RoutedTopology`, built from a LOADED platform
  and the ranks' hosts: the constraints are the platform's own links
  that the ranks' routes cross, a transfer rides ``routing/``'s route
  (and, under ``network/crosstraffic``, the way back at weight 0.05,
  as ``NetworkCm02Model.communicate`` expands it), and it starts only
  after the route's latency.  No loopback: a rank sending to itself
  is refused.

:meth:`Topology.lower` and :meth:`Topology.delays` are what the tape
compiler calls; the three synthetic flavors answer them from
``route()``, the routed one from its route table without a Python
loop per element.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

from ..ops import opstats

FLAVORS = ("nic", "star", "ring")


class Topology:
    """Constraint layout + route function for one flavor instance."""

    __slots__ = ("flavor", "ranks", "bw", "loop_bw", "core_bw", "n_c",
                 "c_bound")

    def __init__(self, ranks: int, flavor: str = "nic",
                 bw: float = 1e9, loop_bw: float = 0.0,
                 core_bw: float = 0.0):
        if flavor not in FLAVORS:
            raise ValueError(f"unknown topology flavor {flavor!r} "
                             f"(expected one of {FLAVORS})")
        if ranks < 1:
            raise ValueError("topology needs at least one rank")
        self.flavor = flavor
        self.ranks = int(ranks)
        self.bw = float(bw)
        # loopback rides memory, not the fabric: default 4x the NIC
        self.loop_bw = float(loop_bw) if loop_bw else 4.0 * self.bw
        # star core: R/4 NICs' worth of aggregate (oversubscription 4)
        self.core_bw = (float(core_bw) if core_bw
                        else self.bw * max(self.ranks // 4, 1))
        R = self.ranks
        if flavor == "nic":
            self.n_c = 3 * R
            cb = np.full(self.n_c, self.bw)
            cb[2 * R:] = self.loop_bw
        elif flavor == "star":
            self.n_c = 3 * R + 1
            cb = np.full(self.n_c, self.bw)
            cb[2 * R] = self.core_bw
            cb[2 * R + 1:] = self.loop_bw
        else:  # ring
            self.n_c = 2 * R
            cb = np.full(self.n_c, self.bw)
            cb[R:] = self.loop_bw
        self.c_bound = cb

    def route(self, src: int, dst: int) -> List[int]:
        R = self.ranks
        if src == dst:
            if self.flavor == "nic":
                return [2 * R + src]
            if self.flavor == "star":
                return [2 * R + 1 + src]
            return [R + src]
        if self.flavor == "nic":
            return [src, R + dst]
        if self.flavor == "star":
            return [src, 2 * R, R + dst]
        # ring: walk the shorter arc, clockwise on ties; link i spans
        # rank i -> i+1 (mod R)
        cw = (dst - src) % R
        ccw = (src - dst) % R
        if cw <= ccw:
            return [(src + j) % R for j in range(cw)]
        return [(src - 1 - j) % R for j in range(ccw)]

    def lower(self, src: np.ndarray, dst: np.ndarray
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The element rows of transfers ``src[i] -> dst[i]``: (the
        transfer's index, the constraint slot, the weight) per element,
        transfer-major."""
        routes = [self.route(int(a), int(b)) for a, b in zip(src, dst)]
        rec = np.repeat(np.arange(len(routes)), [len(r) for r in routes])
        slots = np.fromiter((c for r in routes for c in r), np.int64,
                            count=len(rec))
        return rec, slots, np.ones(len(rec))

    def delays(self, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
        """Seconds between a transfer's last predecessor and its first
        byte on the wire (the record's default ``exec_cost``)."""
        return np.zeros(len(src))

    def key(self) -> tuple:
        return ("topo", self.flavor, self.ranks, self.bw,
                self.loop_bw, self.core_bw)


class RoutedTopology(Topology):
    """The ``routed`` flavor: rank ``r`` sits on ``hosts[r]`` of the
    platform ``engine`` has loaded.

    Constraint slots are the links the R(R-1) routes cross, numbered by
    first crossing (pairs in rank order); a slot's capacity is the
    link's bandwidth under the network model's bandwidth factor, which
    is what the link's own LMM constraint holds.  ``route(src, dst)``
    is ``routing/``'s route in slots; a transfer's elements are that
    route at weight 1 and, when the model runs with cross-traffic, the
    route back at weight 0.05; its delay is the route's latency under
    the model's latency factor (LV08: 13.01) — the three things
    ``NetworkCm02Model.communicate`` gives the same pair of hosts.
    """

    __slots__ = ("links", "_off", "_slot", "_w", "_n_fwd", "_delay",
                 "_hosts")

    #: weight of a flow on the links of its way back
    #: (network_cm02.cpp, as ``communicate`` expands it)
    CROSSTRAFFIC_WEIGHT = 0.05

    def __init__(self, engine, hosts):
        from ..utils.config import config

        R = len(hosts)
        if R < 2:
            raise ValueError("a routed topology needs at least 2 ranks")
        model = engine.pimpl.network_model
        self.flavor = "routed"
        self.ranks = R
        self._hosts = tuple(h.name for h in hosts)
        lat_factor = model.get_latency_factor(0.0)
        with opstats.span("coll.lower", id="routes"):
            slot_of: dict = {}
            fwd: List[List[int]] = []
            delay = np.zeros(R * R)
            for a in range(R):
                for b in range(R):
                    links: list = []
                    if a != b:
                        delay[a * R + b] = lat_factor * hosts[a].route_to(
                            hosts[b], links)
                    fwd.append([slot_of.setdefault(link, len(slot_of))
                                for link in links])
            self.links = list(slot_of)
            # pair (a, b): its route, then (cross-traffic) b's route to a
            back = ([fwd[b * R + a] for a in range(R) for b in range(R)]
                    if config["network/crosstraffic"] else [[]] * (R * R))
            self._n_fwd = np.array([len(r) for r in fwd])
            n = self._n_fwd + np.array([len(r) for r in back])
            self._off = np.concatenate([[0], np.cumsum(n)])
            self._slot = np.fromiter(
                (c for f, k in zip(fwd, back) for c in f + k), np.int64,
                count=self._off[-1])
            within = np.arange(self._off[-1]) - np.repeat(self._off[:-1], n)
            self._w = np.where(within < np.repeat(self._n_fwd, n), 1.0,
                               self.CROSSTRAFFIC_WEIGHT)
            self._delay = delay
        self.n_c = len(self.links)
        self.c_bound = np.array(
            [model.get_bandwidth_factor(0.0) * link.get_bandwidth()
             for link in self.links])

    def _pairs(self, src, dst) -> np.ndarray:
        src, dst = np.asarray(src, np.int64), np.asarray(dst, np.int64)
        if np.any(src == dst):
            raise ValueError("routed topology: a rank sends to itself "
                             "(no loopback is lowered)")
        return src * self.ranks + dst

    def route(self, src: int, dst: int) -> List[int]:
        p = int(self._pairs([src], [dst])[0])
        o = self._off[p]
        return self._slot[o:o + self._n_fwd[p]].tolist()

    def lower(self, src, dst):
        p = self._pairs(src, dst)
        n = self._off[p + 1] - self._off[p]
        rec = np.repeat(np.arange(len(p)), n)
        # element j of transfer i sits at off[p[i]] + j
        at = np.arange(n.sum()) - np.repeat(np.cumsum(n) - n, n) \
            + np.repeat(self._off[p], n)
        return rec, self._slot[at], self._w[at]

    def delays(self, src, dst) -> np.ndarray:
        return self._delay[self._pairs(src, dst)]

    def key(self) -> tuple:
        return ("topo", self.flavor, self.ranks, self._hosts, self.n_c,
                float(self.c_bound.sum()), float(self._delay.sum()))
