"""Device time by the program's own names.

The program names its device passes with ``jax.named_scope``
(``sg.lmm.*`` in the fixpoint round, ``sg.drain.*`` in the superstep)
and its host steps with ``opstats.span`` (``sg:<name>`` annotations on
the profiler's host timeline).  Two reductions of one traced window:

* :func:`device_scopes` - device SELF time (a ``while`` does not hold
  its body, as ``trace.self_times``) per compiled program and innermost
  ``sg.*`` scope of each op's op-name path, ``unscoped`` for ops whose
  path has none (loop plumbing, the copies XLA inserts).  Needs the
  metadata ``lib/xmeta.py`` decodes from the raw ``.xplane.pb``; the
  harness keeps it as ``run.scopes`` before the raw file goes, and
  :func:`pass_ms` reads a pass's milliseconds a unit of work from it.
* :func:`idle_by_span` - the device's idle time per innermost host
  span covering it, the program's ``sg:`` spans and the benchmark's
  ``bench:`` ones under one nesting rule, ``unannotated`` for the
  rest.  Needs only what ``lib/trace.py`` already read.

Both work on the window and the chips of a ``trace.TraceSummary``
(``lo``, ``hi``, ``devices``, ``planes``).  :func:`program_spans` reads
the same host spans from the program's own buffer, on the clock of
``lib/spans.py``, traced or not.
"""

from __future__ import annotations

from bisect import bisect_right
from typing import Dict, Iterable, List, Optional, Tuple

from . import trace, xmeta
from .spans import PREFIX as BENCH_PREFIX

#: what an op-name path component / a host annotation of the program
#: starts with
DEVICE_PREFIX = "sg."
HOST_PREFIX = "sg:"
UNSCOPED = "unscoped"
UNNAMED = "unannotated"

#: the compiled programs the cells drive, as XLA names them, and the
#: key of a driver's record that counts the unit of work each pays for
SUPERSTEP = ("jit__superstep_program", "advances")
SOLVE_CHUNK = ("jit__solve_kernel_chunk", "solves")
#: the four passes of one saturation round
ROUND = tuple("sg.lmm." + p for p in ("neighmin", "level", "update",
                                      "prune"))

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


def host_spans(summary: trace.TraceSummary,
               prefixes: Tuple[str, ...] = (HOST_PREFIX,)
               ) -> List[trace.Event]:
    """The annotations on the host planes named like ``prefixes`` (the
    program's ``sg:`` spans alone, unless told otherwise), less the
    benchmark's mark of the window itself."""
    return [ev for plane, lines in summary.planes.items()
            if not trace.is_device_plane(plane)
            for events in lines.values() for ev in events
            if ev[0].startswith(prefixes) and ev[0] != trace.WINDOW]


def idle_by_span(summary: trace.TraceSummary,
                 prefixes: Tuple[str, ...] = (HOST_PREFIX,)
                 ) -> Dict[str, int]:
    """Idle nanoseconds of the first chip inside the window by the
    innermost span (of those named like ``prefixes``) open at the time,
    under its annotation's whole name, and ``unannotated`` where none
    was: all of it, for a program that opens no span.  The values add
    up to the chip's idle time, to the nanosecond."""
    idle = trace.gaps(summary.busy[summary.devices[0]],
                      summary.lo, summary.hi)
    segments = innermost_segments(host_spans(summary, prefixes))
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
                out[name] = out.get(name, 0) + cover
                named += cover
            j += 1
    out[UNNAMED] = trace.total(idle) - named
    return out


def top_gaps(summary: trace.TraceSummary, n: Optional[int] = 10
             ) -> List[List]:
    """``breakdown.idle_gaps``: [name, idle seconds], the largest
    first, by the innermost of the program's AND the benchmark's spans;
    a ``bench:`` name bare (``lap.events``), a program's with its
    prefix (``sg:drain.demux``)."""
    by = idle_by_span(summary, (BENCH_PREFIX, HOST_PREFIX))
    top = sorted(((name, ns) for name, ns in by.items() if ns),
                 key=lambda kv: -kv[1])[:n]
    return [[name[len(BENCH_PREFIX):] if name.startswith(BENCH_PREFIX)
             else name, ns / 1e9] for name, ns in top]


def top_ops(run, n: int = 10) -> List[List]:
    """``breakdown.device_ops``: [name, self seconds], the largest
    first, each op behind its innermost scope (``sg.drain.ring
    %fusion.560 f32[204160] fusion``); as XLA names it alone where the
    trace carries no ``sg.`` name or was not decoded."""
    got = run.scopes
    if got is None or all(scope == UNSCOPED for _program, scope in got.by):
        return run.trace.top_ops(n)
    return [[f"{scope} {op}"[:80], s]
            for _program, scope, op, s in got.top_ops(n)]


def pass_ms(run, program: Tuple[str, str], *names: str
            ) -> Optional[float]:
    """Device self milliseconds of ``program`` (its name's needle and
    the record key of its unit of work) under the scopes ``names``,
    over the window's units: PERF.md section 5's columns.  A pass that
    never ran reads 0; nothing to read where the raw trace was not
    decoded, the program carries no ``sg.`` name at all or the window
    finished no unit."""
    needle, unit = program
    units = run.record.get(unit)
    if run.scopes is None or not units:
        return None
    by = run.scopes.scopes(needle)
    if not any(scope.startswith(DEVICE_PREFIX) for scope in by):
        return None
    return 1e3 * sum(by.get(name, 0.0) for name in names) / units


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
