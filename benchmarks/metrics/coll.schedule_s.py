"""Host seconds of set-up inside the program's ``coll.lower`` spans of
id ``schedule``: the generator (the per-rank programs emitted, their
sends and receives matched into records, the per-rank frontier walk
that gives each record its predecessors), apart from the routes and
the tape beside it in ``coll.lower_s``.  A program that does not tell
the generator apart has nothing to read."""

from simgrid_tpu.ops import opstats


def read(run):
    cut = run.spans.window_from
    spans = [s.end - s.start for s in getattr(opstats, "spans", list)()
             if s.name == "coll.lower" and s.id == "schedule"
             and s.start < cut]
    return sum(spans) if spans else None
