"""Flows the collective tape put on the wire per advance of the window:
``opstats`` ``collective_tape_fires`` / the advances committed.  An
activation date is an advance of its own (a whole cold solve), beside
the completions'.  A program without the counter has nothing to
read."""


def read(run):
    fires = run.counters.get("collective_tape_fires")
    advances = run.record.get("advances")
    if fires is None or not advances:
        return None
    return fires / advances
