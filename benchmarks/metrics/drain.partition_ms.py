"""Device milliseconds an advance pays to step down ``fixpoint``'s
ladder: the superstep's self time under ``sg.lmm.partition`` over the
advances committed.  0 where every advance entered at its bottom
rung."""

from lib.scopes import SUPERSTEP, pass_ms


def read(run):
    return pass_ms(run, SUPERSTEP, "sg.lmm.partition")
