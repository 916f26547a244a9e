"""Device milliseconds an advance spends in the collective tape: the
superstep's self time under ``sg.drain.coll`` (the activation-ring
scatters and the DAG walk) over the advances committed."""

from lib.scopes import SUPERSTEP, pass_ms


def read(run):
    return pass_ms(run, SUPERSTEP, "sg.drain.coll")
