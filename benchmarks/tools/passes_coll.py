#!/usr/bin/env python3
"""``tools/passes.py`` for the collective-tape cell: ``drivers/
coll_drain.py`` drives ``drain``'s compiled program
(``jit__superstep_program``, ``has_coll=True``), so it reads as
``drain`` here, with the same arguments and the same line.
(``passes_by_entry.py`` reads a driver named ``<entry>_<what>``; this
one is named the other way round so that tool's census of drivers
stays what its test pins.)  The tape's own device time is the scope
``sg.drain.coll`` of ``passes.by_program``; ``breakdown`` counts it
into ``retire_ms`` with the other ``sg.drain.*`` scopes.  The line's
``passes.breakdown`` also carries the window's ``opstats`` counters
(``counters``): ``drain.worked_elem_pct`` is not in this cell's list
(PERF.md section 7), its counter is read from here."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import passes  # noqa: E402



def breakdown_with_counters(run, scoped, real=passes.breakdown):
    out = real(run, scoped)
    if out is not None:
        out["counters"] = dict(run.counters)
    return out


if __name__ == "__main__":
    passes.PROGRAMS.setdefault("coll_drain", passes.PROGRAMS["drain"])
    passes.breakdown = breakdown_with_counters
    sys.exit(passes.main())
