"""``opstats`` ``fixpoint_bound_rounds`` over the window: the saturation
rounds of its solves that took the bound-first block of the round
(``lmm_jax.fixpoint`` enters it only when some saturated variable's
bound sits under its level).  Config #4's LV08 window bounds never
bind, so the expected reading is 0: every round of the cell skipped
the block.  A program without the counter has nothing to read."""

from simgrid_tpu.ops import opstats


def read(run):
    if "fixpoint_bound_rounds" not in opstats.snapshot():
        return None
    return run.counters.get("fixpoint_bound_rounds", 0.0)
