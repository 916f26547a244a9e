"""The comparison that decides ``correct``: what the timed window
produced against the plain reference, each number beside its limit."""

from __future__ import annotations

import hashlib
from typing import Dict, List, Sequence, Tuple

import numpy as np

Events = Sequence[Tuple[float, int]]


def digest(obj) -> str:
    if isinstance(obj, np.ndarray):
        return hashlib.sha256(np.ascontiguousarray(obj).tobytes()
                              ).hexdigest()[:16]
    return hashlib.sha256(repr(obj).encode()).hexdigest()[:16]


class Compared:
    """Numbers compared, each with its limit; correct while none is
    over (a limit of 0 is an exact comparison)."""

    def __init__(self):
        self.rows: List[Dict] = []

    def add(self, name: str, value: float, limit: float) -> None:
        value = float(value)
        ok = bool(np.isfinite(value) and value <= limit)
        self.rows.append(dict(name=name, value=value, limit=float(limit),
                              ok=ok))

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def as_dict(self) -> Dict[str, Dict[str, float]]:
        return {r["name"]: {"value": r["value"], "limit": r["limit"]}
                for r in self.rows}

    def lines(self) -> List[str]:
        return [f"compared {r['name']} = {r['value']:.6g} "
                f"(limit {r['limit']:.6g}) "
                f"{'ok' if r['ok'] else 'OVER'}" for r in self.rows]


def rate_gap(got: np.ndarray, ref: np.ndarray, floor: float) -> float:
    """Widest gap between two rate vectors, relative to the reference's
    rate or to ``floor`` where that is larger (the clamp a saturated
    link's epsilon allows a starved flow)."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - ref) / np.maximum(ref, floor)))


def events_gap(ref: Events, got: Events) -> Dict[str, float]:
    """Two drains' completion events, as numbers:

    ``date_gap``      widest relative gap between the two dates of one
                      flow, over the flows both finished;
    ``unmatched``     flows only one side finished, the last advance's
                      group excepted (the two sides may cut a tie group
                      at the window's edge differently);
    ``order_gap``     widest relative distance, in the reference's
                      dates, by which ``got`` lists a flow before one
                      the reference finished earlier (0: same order;
                      flows of one advance are one unordered group).
    """
    t_ref = {f: t for t, f in ref}
    t_got = {f: t for t, f in got}
    if len(t_ref) != len(ref) or len(t_got) != len(got) \
            or not ref or not got:
        return dict(date_gap=float("inf"), unmatched=float("inf"),
                    order_gap=float("inf"))
    common = [f for _, f in got if f in t_ref]
    horizon = min(ref[-1][0], got[-1][0])
    unmatched = sum(
        1 for f in set(t_ref) ^ set(t_got)
        if (t_ref[f] if f in t_ref else t_got[f]) < horizon)
    if not common:
        return dict(date_gap=float("inf"), unmatched=float(unmatched),
                    order_gap=float("inf"))
    date_gap = max(abs(t_got[f] - t_ref[f]) / t_ref[f] for f in common)
    order_gap, high = 0.0, 0.0
    for f in sorted(common, key=lambda f: (t_got[f], t_ref[f])):
        t = t_ref[f]
        if t < high:
            order_gap = max(order_gap, (high - t) / high)
        high = max(high, t)
    return dict(date_gap=float(date_gap), unmatched=float(unmatched),
                order_gap=float(order_gap))
