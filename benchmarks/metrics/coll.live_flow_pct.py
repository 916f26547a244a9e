"""Mean share of the collective's flow slots that were live (on the
wire) when an advance of the window entered: ``opstats``
``collective_live_flow_advances`` (summed by the superstep, read from
the tail of its packed vector) / (the advances committed x the flow
slots).  Every advance is a cold solve that enters at the full width
of the element lists, whatever is live, so 100 / this bounds what an
entry at the live set's width could save.  A program without the
counter has nothing to read."""


def read(run):
    live = run.counters.get("collective_live_flow_advances")
    advances = run.record.get("advances")
    if live is None or not advances or run.shape is None:
        return None
    return 100.0 * live / (advances * run.shape[1])
