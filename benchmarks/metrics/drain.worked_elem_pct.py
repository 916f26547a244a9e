"""``solve.worked_elem_pct`` of the drain's supersteps: the elements
their rounds indexed (``opstats`` ``fixpoint_worked_elem_rounds``, a
pair of scalars in the superstep's packed stats) / (``fixpoint_rounds``
x the UNPADDED element count).  Every advance is a cold solve, so every
advance walks the ladder down from the whole list: 100 and a little
(the padding to rows of 8) for the single loop.  A program without the
counter has nothing to read."""

from lib import manifest as mf

read = mf.load_module("metrics", "solve.worked_elem_pct").read
