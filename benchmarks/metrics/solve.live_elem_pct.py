"""Mean share of the system's elements that were still live (valid,
their variable not fixed yet) when a round of the window entered:
``opstats`` ``fixpoint_live_elem_rounds`` / (``fixpoint_rounds`` x the
UNPADDED element count).  Every element-wide gather and scatter of the
round works on all elements, live or not, so 100 / this is the most a
perfect compaction of the live set could gain.  A program without the
counter has nothing to read."""


def read(run):
    live = run.counters.get("fixpoint_live_elem_rounds")
    rounds = run.counters.get("fixpoint_rounds")
    if not live or not rounds or run.shape is None:
        return None
    return 100.0 * live / (rounds * run.shape[2])
