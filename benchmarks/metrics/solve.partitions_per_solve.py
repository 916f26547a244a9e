"""Live-first partitions of the element list a whole-system solve of the
window ran: ``opstats`` ``fixpoint_partitions`` / the solves it fetched.
One for every descent of ``fixpoint``'s ladder that rounds followed (a
descent over several rungs is one partition and a slice a rung), and
every chunk of a chunked solve walks down again from the whole list.
0 for a list under the ladder's floor; a program without the counter
has nothing to read."""

from simgrid_tpu.ops import opstats


def read(run):
    solves = run.record.get("solves")
    if "fixpoint_partitions" not in opstats.snapshot() or not solves:
        return None
    return run.counters.get("fixpoint_partitions", 0.0) / solves
