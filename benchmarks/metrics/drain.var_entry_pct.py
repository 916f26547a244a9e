"""Share of the window's advances whose solve entered from the variable
side: ``opstats`` ``fixpoint_var_entries`` (counted by the superstep,
read from the tail of its packed vector) / the advances committed.
``fixpoint`` takes that side when the live flows' elements fit the
ladder's bottom rung: it then builds the rung from the element list's
variable-major index and runs no op as wide as the list, where the
other side pays entry and one descent at full width.  100 where every
advance of a collective tape finds few flows live; 0 where each has a
burst over the rung.  A program without the counter has nothing to
read."""

from simgrid_tpu.ops import opstats


def read(run):
    advances = run.record.get("advances")
    if "fixpoint_var_entries" not in opstats.snapshot() or not advances:
        return None
    return 100.0 * run.counters.get("fixpoint_var_entries", 0.0) / advances
