"""Elements the window's rounds INDEXED, as a share of rounds x the
UNPADDED element count: ``opstats`` ``fixpoint_worked_elem_rounds`` (the
size of the ladder rung each round ran on, summed) / (``fixpoint_rounds``
x n_elem).  A gather or scatter costs by the index, so this is what the
round loop paid; ``solve.live_elem_pct`` beside it is the floor it can
reach.  The single loop reads the padded size over n_elem (169 for
2,097,152 over 1,241,658).  A program without the counter has nothing
to read."""


def read(run):
    worked = run.counters.get("fixpoint_worked_elem_rounds")
    rounds = run.counters.get("fixpoint_rounds")
    if not worked or not rounds or run.shape is None:
        return None
    return 100.0 * worked / (rounds * run.shape[2])
