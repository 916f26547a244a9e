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
  after the route's latency.  A rank sending to itself rides what
  ``communicate`` gives a host sending to itself: its route to itself
  (on a dragonfly, its router link up and back down), else the
  model's loopback, with the same links as its way back.

:meth:`Topology.lower` and :meth:`Topology.delays` are what the tape
compiler calls; the three synthetic flavors answer them from
``route()``, the routed one from its route table without a Python
loop per element.  A flavor's ``n_c`` and ``c_bound`` are read AFTER
``lower``: the routed one knows its constraints only then.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

from ..ops import opstats
from ..utils.gc_pause import collector_paused

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

    A pair's route is looked up when a schedule first asks for it
    (:meth:`lower`, :meth:`delays`, :meth:`route`), once, together with
    its way back where the model runs with cross-traffic: a collective
    over R ranks uses a few of the R(R-1) ordered pairs (recursive
    doubling: R log2 R), and all of them are 4.3 x 10^9 at the 65,536
    hosts of a machine.  Constraint slots are the links those routes
    cross, numbered by first crossing, each call's new pairs in rank
    order (so a schedule that uses every pair numbers them as a walk
    over all pairs would); a slot's capacity is the link's bandwidth
    under the network model's bandwidth factor, which is what the
    link's own LMM constraint holds.  ``n_c``, ``c_bound`` and
    ``links`` therefore stand once the collective is lowered, and
    ``DeviceCollective`` reads them then.  ``route(src, dst)`` is
    ``routing/``'s route in slots; a transfer's elements are that
    route at weight 1 and, with cross-traffic, the route back at weight
    0.05; its delay is the route's latency under the model's latency
    factor (LV08: 13.01) — the three things
    ``NetworkCm02Model.communicate`` gives the same pair of hosts.
    """

    __slots__ = ("links", "_hosts", "_host_objs", "_loopback",
                 "_lat_factor", "_bw_factor", "_crosstraffic", "_slot_of",
                 "_pair", "_at", "_off", "_slot", "_delay")

    #: weight of a flow on the links of its way back
    #: (network_cm02.cpp, as ``communicate`` expands it)
    CROSSTRAFFIC_WEIGHT = 0.05

    def __init__(self, engine, hosts):
        from ..utils.config import config

        if len(hosts) < 2:
            raise ValueError("a routed topology needs at least 2 ranks")
        model = engine.pimpl.network_model
        self.flavor = "routed"
        self.ranks = len(hosts)
        self._host_objs = list(hosts)
        self._loopback = model.loopback
        self._hosts = tuple(h.name for h in hosts)
        self._lat_factor = model.get_latency_factor(0.0)
        self._bw_factor = model.get_bandwidth_factor(0.0)
        self._crosstraffic = bool(config["network/crosstraffic"])
        self.links: list = []
        self._slot_of: dict = {}
        # the routed pairs (src * ranks + dst): sorted, with where each
        # one's route sits in the route table (rows in the order routed)
        self._pair = np.zeros(0, np.int64)
        self._at = np.zeros(0, np.int64)
        self._off = np.zeros(1, np.int64)
        self._slot = np.zeros(0, np.int64)
        self._delay = np.zeros(0)

    @property
    def n_c(self) -> int:
        return len(self.links)

    @property
    def c_bound(self) -> np.ndarray:
        return np.array([self._bw_factor * link.get_bandwidth()
                         for link in self.links])

    def _pairs(self, src, dst) -> np.ndarray:
        src, dst = np.asarray(src, np.int64), np.asarray(dst, np.int64)
        return src * self.ranks + dst

    def _self_route(self, host, links: list) -> float:
        """A host's route to itself as ``communicate`` finds it: the
        platform's, else the model's loopback."""
        try:
            latency = host.route_to(host, links)
        except AssertionError:
            links.clear()
            latency = 0.0
        if not links and latency <= 0:
            links.append(self._loopback)
            latency = self._loopback.get_latency()
        return latency

    def _back(self, p: np.ndarray) -> np.ndarray:
        """Pairs ``p`` the other way round."""
        return p % self.ranks * self.ranks + p // self.ranks

    def _rows(self, p: np.ndarray) -> np.ndarray:
        """The route-table rows of pairs ``p``, each routed here first
        if it never was (and with it, under cross-traffic, the pair
        the other way round: for a rank sending to itself, itself)."""
        R = self.ranks
        want = np.unique(np.concatenate([p, self._back(p)])
                         if self._crosstraffic else p)
        new = np.setdiff1d(want, self._pair, assume_unique=True)
        if len(new):
            with opstats.span("coll.lower", id="routes"), \
                    collector_paused():
                hosts, slot_of = self._host_objs, self._slot_of
                slots: List[int] = []
                n = np.zeros(len(new), np.int64)
                delay = np.zeros(len(new))
                for i, (a, b) in enumerate(zip((new // R).tolist(),
                                               (new % R).tolist())):
                    links: list = []
                    delay[i] = (hosts[a].route_to(hosts[b], links)
                                if a != b
                                else self._self_route(hosts[a], links))
                    slots += [slot_of.setdefault(link, len(slot_of))
                              for link in links]
                    n[i] = len(links)
                opstats.bump("collective_routes", len(new))
                opstats.bump("collective_self_routes",
                             int(np.count_nonzero(new // R == new % R)))
                self.links = list(slot_of)
                pair = np.concatenate([self._pair, new])
                at = np.concatenate([self._at, len(self._pair)
                                     + np.arange(len(new))])
                order = np.argsort(pair, kind="stable")
                self._pair, self._at = pair[order], at[order]
                self._off = np.concatenate(
                    [self._off, self._off[-1] + np.cumsum(n)])
                self._slot = np.concatenate(
                    [self._slot, np.asarray(slots, np.int64)])
                self._delay = np.concatenate(
                    [self._delay, self._lat_factor * delay])
        return self._at[np.searchsorted(self._pair, p)]

    def route(self, src: int, dst: int) -> List[int]:
        row = int(self._rows(self._pairs([src], [dst]))[0])
        return self._slot[self._off[row]:self._off[row + 1]].tolist()

    def lower(self, src, dst):
        p = self._pairs(src, dst)
        # transfer i: its route and, with cross-traffic, its peer's
        # route back; one table row and one weight each
        rows, w = [self._rows(p)], [1.0]
        if self._crosstraffic:
            rows.append(self._rows(self._back(p)))
            w.append(self.CROSSTRAFFIC_WEIGHT)
        rows = np.stack(rows, axis=1)
        n = self._off[rows + 1] - self._off[rows]
        rec = np.repeat(np.arange(len(p)), n.sum(axis=1))
        rows, n = rows.ravel(), n.ravel()
        # element j of table row r sits at off[r] + j
        at = np.arange(n.sum()) - np.repeat(np.cumsum(n) - n, n) \
            + np.repeat(self._off[rows], n)
        return rec, self._slot[at], np.repeat(np.tile(w, len(p)), n)

    def delays(self, src, dst) -> np.ndarray:
        rows = self._rows(self._pairs(src, dst))
        return self._delay[rows]

    def key(self) -> tuple:
        return ("topo", self.flavor, self.ranks, self._hosts,
                self._lat_factor, self._bw_factor, self._crosstraffic)
