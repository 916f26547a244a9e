"""From the profiler's ``.xplane.pb`` to numbers: device busy time, the
device time of each compiled program and the operations that took most
time (``lib/scopes.py`` names both, and the idle gaps, as the program
does).

Read with nothing but JAX (``jax.profiler.ProfileData``).  Everything
below works on plain ``(name, start_ns, end_ns)`` tuples, so the
arithmetic is tested without a profiler (tests/) and on the small
recorded trace kept beside them.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

from .spans import PREFIX

Event = Tuple[str, int, int]          # name, start_ns, end_ns

#: lines of a device plane, as XLA:TPU's profiler names them
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = PREFIX + "window"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_xplane(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """plane name -> line name -> events."""
    from jax.profiler import ProfileData
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for e in line.events:
                start = int(e.start_ns)
                evs.append((e.name, start, start + int(e.duration_ns)))
    return out


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name.upper()


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[int]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Tuple[int, int]], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Tuple[int, int]]) -> int:
    return sum(b - a for a, b in intervals)


def gaps(busy: Sequence[Tuple[int, int]], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    """What ``busy`` (merged) leaves of [lo, hi]."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(events: Sequence[Event]) -> Dict[str, int]:
    """Per name, the time of each event that no event nested inside it
    covers (a ``while`` holds its body's operations: the body's time is
    the body's, not the loop's)."""
    out: Dict[str, int] = {}
    stack: List[List] = []            # [name, end, self_ns]

    def close(upto: int) -> None:
        while stack and stack[-1][1] <= upto:
            name, _end, own = stack.pop()
            out[name] = out.get(name, 0) + own

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        close(a)
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    close(1 << 62)
    return out


def short_op(name: str) -> str:
    """'%fusion.163 = f32[141871]{0:T(1024)} fusion(...)' ->
    '%fusion.163 f32[141871] fusion': XLA prints the whole instruction."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:80]
    shape = "(tuple)" if rest.startswith("(") else rest.split("{", 1)[0]
    kind = re.search(r"(?:^|[ )}])([a-z][a-z\-]*)\(", rest)
    return " ".join(x for x in (head, shape.strip(),
                                kind.group(1) if kind else "") if x)[:80]


class TraceSummary:
    """One traced window, reduced."""

    def __init__(self, planes: Dict[str, Dict[str, List[Event]]],
                 chips: int = 1):
        self.planes = planes
        devices = sorted(p for p in planes if is_device_plane(p)
                         and planes[p].get(OPS_LINE))
        if not devices:
            raise ValueError("the trace holds no device operation "
                             f"(planes: {sorted(planes)})")
        self.devices = devices[:chips]
        self.notes: List[Event] = [
            ev for p, lines in planes.items() if not is_device_plane(p)
            for evs in lines.values() for ev in evs
            if ev[0].startswith(PREFIX)]
        window = [ev for ev in self.notes if ev[0] == WINDOW]
        if window:
            self.lo, self.hi = window[0][1], window[0][2]
        else:                         # a trace with no window annotation
            ops = [ev for d in self.devices for ev in planes[d][OPS_LINE]]
            self.lo = min(e[1] for e in ops)
            self.hi = max(e[2] for e in ops)
        self.window_s = (self.hi - self.lo) / 1e9
        #: chip -> the merged intervals, cut to the window, in which an
        #: operation ran on it
        self.busy = {d: clip(union((a, b) for _n, a, b
                                   in planes[d][OPS_LINE]),
                             self.lo, self.hi) for d in self.devices}
        self.busy_s = sum(total(b) for b in self.busy.values()) \
            / 1e9 / len(self.devices)

    def module_seconds(self, needle: str) -> Tuple[float, int]:
        """Device seconds and runs of the compiled programs whose name
        holds ``needle``, inside the window, summed over the chips."""
        ns = runs = 0
        for d in self.devices:
            for name, a, b in self.planes[d].get(MODULES_LINE, ()):
                if needle in name and b > self.lo and a < self.hi:
                    ns += min(b, self.hi) - max(a, self.lo)
                    runs += 1
        return ns / 1e9, runs

    def top_ops(self, n: int = 10) -> List[List]:
        own: Dict[str, int] = {}
        for d in self.devices:
            inside = [(nm, max(a, self.lo), min(b, self.hi))
                      for nm, a, b in self.planes[d][OPS_LINE]
                      if b > self.lo and a < self.hi]
            for name, ns in self_times(inside).items():
                own[name] = own.get(name, 0) + ns
        top = sorted(own.items(), key=lambda kv: -kv[1])[:n]
        return [[short_op(name), ns / 1e9 / len(self.devices)]
                for name, ns in top]
