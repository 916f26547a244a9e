"""Where ``setup_s`` went, by the program's own spans.

``setup_s`` runs from the start of the process to the window's first
timed call.  Over that stretch every instant goes to the INNERMOST
span of the program open at the time (``opstats.spans()``, the
``xla.*`` ones JAX timed among them): the nesting rule
``scopes.idle_by_span`` applies to the device's idle gaps, here over
host time.  Three rows are not spans:

* ``boot``    - from the start of the process to the first span of
  either kind, the program's or the benchmark's: the interpreter, the
  imports, the chip's client;
* ``post``    - ``NetworkCm02Model.communicate``'s counter ``post_ms``
  before the window.  A flow is posted outside every span of the
  program (100,000 of them: a counter pair, not a span), so its
  seconds are taken out of what no span covers, never counted twice;
* ``unnamed`` - the rest: the benchmark's own Python, and whatever the
  program still runs under no span.

The rows add up to ``setup_s``.  A nested ``xla.trace`` (an inner jit
traced inside an outer trace) is innermost for its own stretch, so a
name's row is its SELF seconds, never the plain sum of its spans.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, NamedTuple, Optional, Tuple

from . import manifest as mf
from .scopes import innermost_segments

BOOT, POST, UNNAMED = "boot", "post", "unnamed"

#: name, start, end, id: what an ``opstats.Span`` gives a reader
Spanned = Tuple[str, float, float, Any]


class Ledger(NamedTuple):
    #: ``boot``, then each span name in the order it first was
    #: innermost, then ``post`` and ``unnamed``: seconds, adding up to
    #: ``cut - t0``
    rows: Dict[str, float]
    #: (start, end, name, id) of every stretch a span was innermost
    #: for, in time order, none overlapping
    segments: List[Tuple[float, float, str, Any]]
    t0: float
    boot_end: float
    cut: float

    def by_id(self, prefix: str) -> Dict[Tuple[str, Any], float]:
        """(name, id) -> self seconds of the spans named like
        ``prefix``: which program a ``xla.*`` row was paid for."""
        out: Dict[Tuple[str, Any], float] = {}
        for a, b, name, id_ in self.segments:
            if name.startswith(prefix):
                out[name, id_] = out.get((name, id_), 0.0) + (b - a)
        return out

    def stretches(self) -> List[Dict[str, Any]]:
        """The stretches between ``boot_end`` and ``cut`` under no span
        of the program, the longest first, each with the spans on
        either side (None at an end).  ``post`` is inside them."""
        out = []
        at, prev = self.boot_end, None
        for a, b, name, _id in self.segments + [
                (self.cut, self.cut, None, None)]:
            if a > at:
                out.append({"start_s": at - self.t0, "seconds": a - at,
                            "prev": prev, "next": name})
            at, prev = max(at, b), name
        return sorted(out, key=lambda s: -s["seconds"])


def attribute(spans: Iterable[Spanned], t0: float, cut: float,
              first_other: Optional[float] = None,
              post_s: float = 0.0) -> Ledger:
    """The ledger of ``[t0, cut]`` from the program's ``spans``, the
    start of the benchmark's first span (``first_other``) and the
    seconds inside ``communicate``.  A span is cut to the stretch; one
    that ended before it or began after it is none of its business."""
    cut_spans = [((name, id_), max(a, t0), min(b, cut))
                 for name, a, b, id_ in spans if b > t0 and a < cut]
    segments = sorted(((a, b, name, id_) for a, b, (name, id_)
                       in innermost_segments(cut_spans)),
                      key=lambda seg: seg[0])
    starts = [a for _key, a, _b in cut_spans]
    if first_other is not None and t0 <= first_other < cut:
        starts.append(first_other)
    boot_end = min(starts, default=cut)
    named: Dict[str, float] = {}
    for a, b, name, _id in segments:
        named[name] = named.get(name, 0.0) + (b - a)
    rest = (cut - boot_end) - sum(named.values())
    rows = {BOOT: boot_end - t0, **named, POST: post_s,
            UNNAMED: rest - post_s}
    return Ledger(rows, segments, t0, boot_end, cut)


def of(run) -> Optional[Ledger]:
    """The ledger of a run's set-up; None for a program that records
    no spans."""
    from simgrid_tpu.ops import opstats

    if not hasattr(opstats, "spans"):
        return None
    bench = [a for records in run.spans.records.values()
             for a, _b in records if a >= run.t0]
    post = mf.load_module("metrics", "setup.post_s").read(run)
    return attribute(((s.name, s.start, s.end, s.id)
                      for s in opstats.spans()),
                     run.t0, run.t0 + run.setup_s,
                     min(bench, default=None), post or 0.0)


def row(run, name: str) -> Optional[float]:
    """Seconds of one row of the run's ledger; None where no such span
    was innermost before the window (as ``scopes.setup_span_s`` reads
    an unopened span), or the program records none at all."""
    ledger = of(run)
    return None if ledger is None else ledger.rows.get(name)


def xla_row(run, name: str) -> Optional[float]:
    """``row`` for ``xla.trace`` / ``xla.lower``, which JAX reports on
    a first call only: a process whose jits were warm (the benchmark's
    tests run many cells in one) traced nothing, and reads 0 where the
    program's listener records those steps (``opstats.note_xla``);
    None where it does not, as the parent commit."""
    from simgrid_tpu.ops import opstats

    ledger = of(run)
    if ledger is None:
        return None
    return ledger.rows.get(
        name, 0.0 if hasattr(opstats, "note_xla") else None)
