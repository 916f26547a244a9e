"""Completion events per advance of the window: what an advance, the
unit the drain pays for, yields of the end-to-end rate's numerator.
Read only where the collective tape ran (``collective_tape_slots``
counted): a program without it has nothing to read."""


def read(run):
    advances = run.record.get("advances")
    if not run.counters.get("collective_tape_slots") or not advances:
        return None
    return run.record["events"] / advances
