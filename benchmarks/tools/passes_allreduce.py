#!/usr/bin/env python3
"""``tools/passes.py`` for the allreduce cell: ``drivers/
coll_allreduce.py`` drives ``drain``'s compiled program
(``jit__superstep_program``, ``has_coll=True``) as ``coll_drain.py``
does, so it reads as ``drain`` here, with the same arguments and the
same line; ``passes_coll.py`` beside this names ``coll_drain`` alone.
The line's ``passes.breakdown`` carries the window's ``opstats``
counters (``counters``, as ``passes_coll.py``'s) and, under
``readers``, the four readings the cell brings that BENCHMARK.json
cannot list yet (``metrics/coll.wide_entry_pct.py``,
``coll.worked_elem_pct.py``, ``coll.route_us_per_pair.py``,
``coll.schedule_s.py``: ``tests/test_var_entry.py`` holds the
manifest's last per-layer entry to be ``drain.var_entry_pct``, so
nothing can be appended behind it; PERF.md section 7)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import passes  # noqa: E402
from passes_coll import breakdown_with_counters  # noqa: E402

READERS = ("coll.wide_entry_pct", "coll.worked_elem_pct",
           "coll.route_us_per_pair", "coll.schedule_s")


def breakdown_with_readers(run, scoped):
    from lib import manifest as mf

    out = breakdown_with_counters(run, scoped)
    if out is not None:
        out["readers"] = {name: mf.load_module("metrics", name).read(run)
                          for name in READERS}
    return out


if __name__ == "__main__":
    passes.PROGRAMS.setdefault("coll_allreduce", passes.PROGRAMS["drain"])
    passes.breakdown = breakdown_with_readers
    sys.exit(passes.main())
