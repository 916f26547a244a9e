"""Share of the window's advances whose solve entered at the FULL width
of the element list: 100 x (the advances committed - ``opstats``
``fixpoint_var_entries``) / the advances committed.  An advance that
finds more live elements than the ladder's bottom rung holds (a burst:
a step's messages on the wire together) pays ``fixpoint``'s entry and
one descent over the whole list, index or no index; the others build
the bottom rung from the live flows' own elements.  The complement of
``drain.var_entry_pct``, named for what a burst costs.  A program
without the counter has nothing to read."""

from simgrid_tpu.ops import opstats


def read(run):
    advances = run.record.get("advances")
    if "fixpoint_var_entries" not in opstats.snapshot() or not advances:
        return None
    return 100.0 * (advances - run.counters.get("fixpoint_var_entries", 0.0)
                    ) / advances
