"""CollectiveSpec: the sweep dimension campaigns/serving put on
ScenarioSpec/ScenarioPlan.

A spec names (op, algo, ranks, topology flavor, payload and, for a
schedule too long to hold, the head of it that is lowered: ``steps``)
— everything needed to regenerate the schedule and compile the tape —
in the same content-addressed style as ScenarioSpec: canonical dict
form, stable sha256 ``key()``, JSON round trip.  ``topo`` is a
synthetic flavor's name or a :class:`~.topology.RoutedTopology` (the
``routed`` flavor: a loaded platform and the ranks' hosts, which no
JSON can carry, so such a spec is addressed by the topology's
``key()`` and ``from_dict`` refuses it).  ``build()`` materializes the
DeviceCollective (schedule generation + topology lowering); plan
construction caches it, so fleets sweeping rank counts × algorithms ×
topologies pay one compile per distinct spec.
"""

from __future__ import annotations

import hashlib
import json
from typing import Dict, Optional

from ..ops import opstats
from .schedule import GENERATORS, HEADED, generate
from .tape import DeviceCollective
from .topology import FLAVORS, RoutedTopology, Topology


class CollectiveSpec:
    """One collective workload: algorithm × rank count × topology."""

    __slots__ = ("op", "algo", "ranks", "topo", "payload", "bw",
                 "loop_bw", "core_bw", "steps")

    def __init__(self, op: str = "allreduce", algo: str = "rdb",
                 ranks: int = 8, topo: str = "nic",
                 payload: float = 1 << 20, bw: float = 1e9,
                 loop_bw: float = 0.0, core_bw: float = 0.0,
                 steps: Optional[int] = None):
        if (op, algo) not in GENERATORS:
            raise ValueError(f"unknown collective {op}/{algo}; known: "
                             f"{sorted(GENERATORS)}")
        if steps is not None and (op, algo) not in HEADED:
            raise ValueError(f"{op}/{algo} has no schedule head (steps=); "
                             f"heads: {sorted(HEADED)}")
        if isinstance(topo, RoutedTopology):
            if topo.ranks != int(ranks):
                raise ValueError(f"the routed topology places "
                                 f"{topo.ranks} ranks, the collective "
                                 f"has {ranks}")
        elif topo not in FLAVORS:
            raise ValueError(f"unknown topology flavor {topo!r}")
        if ranks < 2:
            raise ValueError("a collective needs at least 2 ranks")
        self.op = str(op)
        self.algo = str(algo)
        self.ranks = int(ranks)
        self.topo = topo
        #: payload bytes (elements for lr — see schedule.GENERATORS)
        self.payload = float(payload)
        self.bw = float(bw)
        self.loop_bw = float(loop_bw)
        self.core_bw = float(core_bw)
        #: the head lowered (see schedule.HEADED); None: the whole
        self.steps = None if steps is None else int(steps)

    # -- stable serialization / content addressing -------------------------

    def to_dict(self) -> Dict:
        topo = (self.topo if isinstance(self.topo, str)
                else list(self.topo.key()))
        d = {"op": self.op, "algo": self.algo, "ranks": self.ranks,
             "topo": topo, "payload": self.payload,
             "bw": self.bw, "loop_bw": self.loop_bw,
             "core_bw": self.core_bw}
        if self.steps is not None:
            # only a head says so: a whole schedule keeps its key
            d["steps"] = self.steps
        return d

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), sort_keys=True,
                          separators=(",", ":"))

    @classmethod
    def from_dict(cls, d: Dict) -> "CollectiveSpec":
        if not isinstance(d.get("topo", "nic"), str):
            raise ValueError("a routed collective is rebuilt from its "
                             "platform and rank hosts, not from JSON")
        return cls(op=d.get("op", "allreduce"),
                   algo=d.get("algo", "rdb"),
                   ranks=d.get("ranks", 8),
                   topo=d.get("topo", "nic"),
                   payload=d.get("payload", 1 << 20),
                   bw=d.get("bw", 1e9),
                   loop_bw=d.get("loop_bw", 0.0),
                   core_bw=d.get("core_bw", 0.0),
                   steps=d.get("steps"))

    @classmethod
    def from_json(cls, text: str) -> "CollectiveSpec":
        return cls.from_dict(json.loads(text))

    def key(self) -> str:
        """Stable sha256 of the collective identity (same convention
        as ScenarioSpec.key)."""
        return hashlib.sha256(self.to_json().encode("utf-8")).hexdigest()

    def label(self) -> str:
        topo = getattr(self.topo, "flavor", self.topo)
        head = "" if self.steps is None else f" steps{self.steps}"
        return (f"{self.op}/{self.algo} r{self.ranks} {topo} "
                f"{self.payload:g}B{head}")

    # -- materialization ---------------------------------------------------

    def topology(self) -> Topology:
        if not isinstance(self.topo, str):
            return self.topo
        return Topology(self.ranks, self.topo, bw=self.bw,
                        loop_bw=self.loop_bw, core_bw=self.core_bw)

    def build(self, exec_cost=None) -> DeviceCollective:
        with opstats.span("coll.lower", id="schedule"):
            sched = generate(self.op, self.algo, self.ranks, self.payload,
                             steps=self.steps)
        return DeviceCollective(sched, self.topology(),
                                exec_cost=exec_cost)
