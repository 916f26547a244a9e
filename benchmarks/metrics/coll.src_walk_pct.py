"""Share of the window's advances whose DAG walk started from the
source side: ``opstats`` ``collective_src_walks`` (counted by the
superstep, read from the tail of its packed vector) / the advances
committed.  The tape takes that side when an advance's completions own
at most ``lmm_drain._SRC_WALK_EDGES`` successor edges: it then
decrements the predecessor counts from those edges alone, through the
DAG's source-major index, and runs no op as wide as the edge list,
where the other side gathers and scatter-adds over every edge of the
schedule.  100 where every advance finishes a handful of flows; under
it by the advances that finish a burst.  A program without the counter
has nothing to read."""

from simgrid_tpu.ops import opstats


def read(run):
    advances = run.record.get("advances")
    if "collective_src_walks" not in opstats.snapshot() or not advances:
        return None
    return 100.0 * run.counters.get("collective_src_walks", 0.0) / advances
