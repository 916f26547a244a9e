"""Saturation rounds a whole-system solve of the window ran on the
device: ``opstats`` ``fixpoint_rounds`` over the window / the solves it
fetched.  The depth of the deployment's contention: 17 for 100,000
random pairs, ~283 for the 320-rank alltoall."""


def read(run):
    rounds = run.counters.get("fixpoint_rounds")
    solves = run.record.get("solves")
    if not rounds or not solves:
        return None
    return rounds / solves
