"""``solve.worked_elem_pct`` of a collective tape's supersteps: the
elements their rounds indexed (``opstats``
``fixpoint_worked_elem_rounds``: the size of the ladder rung each round
ran on, summed) / (``fixpoint_rounds`` x the UNPADDED element count).
A burst's rounds run wherever its descent stopped, a narrow advance's
on the bottom rung.  A program without the counter has nothing to
read."""

from lib import manifest as mf

read = mf.load_module("metrics", "solve.worked_elem_pct").read
