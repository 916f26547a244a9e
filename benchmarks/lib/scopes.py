"""Device time by the program's own names.

The program names its device passes with ``jax.named_scope``
(``sg.lmm.*`` in the fixpoint round, ``sg.drain.*`` in the superstep)
and its host steps with ``opstats.span`` (``sg:<name>`` annotations on
the profiler's host timeline).  Two reductions of one traced window:

* :func:`device_scopes` - device SELF time (a ``while`` does not hold
  its body, as ``trace.self_times``) per compiled program and innermost
  ``sg.*`` scope of each op's op-name path, ``unscoped`` for ops whose
  path has none (loop plumbing, the copies XLA inserts).  Needs the
  metadata ``lib/xmeta.py`` decodes from the raw ``.xplane.pb``.
* :func:`idle_by_span` - the device's idle time per innermost ``sg:``
  host span covering it, ``unnamed`` for the rest.  Needs only what
  ``lib/trace.py`` already read.

Both work on the window and the chips of a ``trace.TraceSummary``
(``lo``, ``hi``, ``devices``, ``planes``).  :func:`program_spans` reads
the same host spans from the program's own buffer, on the clock of
``lib/spans.py``, traced or not.
"""

from __future__ import annotations

from bisect import bisect_right
from typing import Dict, Iterable, List, Optional, Tuple

from . import trace, xmeta

#: what an op-name path component / a host annotation of the program
#: starts with
DEVICE_PREFIX = "sg."
HOST_PREFIX = "sg:"
UNSCOPED = "unscoped"
UNNAMED = "unnamed"

Segment = Tuple[int, int, str]        # start_ns, end_ns, name


def innermost_scope(tf_op: Optional[str]) -> str:
    """'jit(f)/while/body/sg.drain.solve/while/body/sg.lmm.update/add:'
    -> 'sg.lmm.update'."""
    last = UNSCOPED
    for part in (tf_op or "").split("/"):
        if part.startswith(DEVICE_PREFIX):
            last = part.rstrip(":")
    return last


class DeviceScopes:
    """Self seconds by (compiled program, scope, op) over the window,
    the chips averaged as ``TraceSummary.busy_s`` does."""

    def __init__(self, by_op: Dict[Tuple[str, str, str], float]):
        #: (program, scope, the op's HLO text) -> self seconds
        self.by_op = by_op
        #: (program, scope) -> self seconds
        self.by: Dict[Tuple[str, str], float] = {}
        for (program, scope, _op), s in by_op.items():
            self.by[program, scope] = self.by.get((program, scope), 0.0) + s

    def scopes(self, needle: str) -> Dict[str, float]:
        """Scope -> self seconds, over the programs whose name holds
        ``needle``."""
        out: Dict[str, float] = {}
        for (program, sc), s in self.by.items():
            if needle in program:
                out[sc] = out.get(sc, 0.0) + s
        return out

    def top_ops(self, n: int = 10) -> List[List]:
        """[program, scope, op, self seconds], the largest first."""
        top = sorted(self.by_op.items(), key=lambda kv: -kv[1])[:n]
        return [[program.split("(")[0], scope, trace.short_op(op), s]
                for (program, scope, op), s in top]


def device_scopes(meta: Dict[str, xmeta.Plane],
                  summary: trace.TraceSummary) -> DeviceScopes:
    by_op: Dict[Tuple[str, str, str], float] = {}
    lo, hi = summary.lo, summary.hi
    for device in summary.devices:
        plane = meta[device]
        modules = sorted((m.start_ns, m.end_ns, plane.names[m.metadata_id])
                         for m in plane.ops(trace.MODULES_LINE))
        starts = [m[0] for m in modules]

        def program_of(op: xmeta.Op) -> str:
            at = bisect_right(starts, op.start_ns) - 1
            return (modules[at][2] if at >= 0
                    and op.start_ns < modules[at][1] else "")

        # trace.self_times adds up by an event's first field: here the
        # op's program and metadata id, cut to the window, not its name
        own = trace.self_times(
            [((program_of(op), op.metadata_id),
              max(op.start_ns, lo), min(op.end_ns, hi))
             for op in plane.ops(trace.OPS_LINE)
             if op.end_ns > lo and op.start_ns < hi])
        for (program, mid), ns in own.items():
            key = (program,
                   innermost_scope(plane.stat_of(mid, "tf_op")),
                   plane.names[mid])
            by_op[key] = by_op.get(key, 0.0) \
                + ns / 1e9 / len(summary.devices)
    return DeviceScopes(by_op)


def innermost_segments(spans: Iterable[trace.Event]) -> List[Segment]:
    """Nested spans cut into the non-overlapping stretches in which each
    is the innermost one open.  A span that outlasts the one it opened
    in (another thread's) is cut at that one's end."""
    out: List[Segment] = []
    stack: List[Tuple[str, int]] = []     # name, end
    at = 0

    def emit(upto: int) -> None:
        nonlocal at
        if stack and upto > at:
            out.append((at, upto, stack[-1][0]))
        at = max(at, upto)

    for name, a, b in sorted(spans, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            emit(stack[-1][1])
            stack.pop()
        if stack:
            emit(a)
            b = min(b, stack[-1][1])
        at = max(at, a)
        stack.append((name, b))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def host_spans(summary: trace.TraceSummary) -> List[trace.Event]:
    """The program's ``sg:`` annotations on the host planes."""
    return [ev for plane, lines in summary.planes.items()
            if not trace.is_device_plane(plane)
            for events in lines.values() for ev in events
            if ev[0].startswith(HOST_PREFIX)]


def idle_by_span(summary: trace.TraceSummary) -> Dict[str, int]:
    """Idle nanoseconds of the first chip inside the window by the
    innermost ``sg:`` span open at the time (the prefix dropped), and
    ``unnamed`` where none was: all of it, for a program that opens no
    span."""
    spans = host_spans(summary)
    device = summary.planes[summary.devices[0]]
    busy = trace.clip(trace.union((a, b) for _n, a, b
                                  in device[trace.OPS_LINE]),
                      summary.lo, summary.hi)
    idle = trace.gaps(busy, summary.lo, summary.hi)
    segments = innermost_segments(spans)
    out: Dict[str, int] = {}
    named = i = 0
    for a, b in idle:
        while i < len(segments) and segments[i][1] <= a:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < b:
            sa, sb, name = segments[j]
            cover = min(b, sb) - max(a, sa)
            if cover > 0:
                key = name[len(HOST_PREFIX):]
                out[key] = out.get(key, 0) + cover
                named += cover
            j += 1
    out[UNNAMED] = trace.total(idle) - named
    return out


def program_spans(run, name: str, in_window: bool
                  ) -> Optional[List[float]]:
    """Seconds of each closed ``opstats`` span called ``name`` that
    began inside the measured window (``in_window``) or before it
    (set-up and warm-up); None when the program records no spans."""
    from simgrid_tpu.ops import opstats

    if not hasattr(opstats, "spans"):
        return None
    cut = run.spans.window_from
    return [s.end - s.start for s in opstats.spans()
            if s.name == name and (s.start >= cut) == in_window]


def window_span_ms(run, name: str) -> Optional[float]:
    """Mean milliseconds of the window's spans called ``name``."""
    spans = program_spans(run, name, in_window=True)
    return 1e3 * sum(spans) / len(spans) if spans else None


def setup_span_s(run, name: str) -> Optional[float]:
    """Seconds under ``name`` before the window; None when the program
    opened no such span (or records none)."""
    spans = program_spans(run, name, in_window=False)
    return sum(spans) if spans else None


def window_compiles(run) -> Optional[int]:
    """``xla.compile`` spans that began after the window did."""
    spans = program_spans(run, "xla.compile", in_window=True)
    return None if spans is None else len(spans)
