"""Device milliseconds an advance spends writing its completions into
the ring: the superstep's self time under ``sg.drain.ring`` over the
advances committed."""

from lib.scopes import SUPERSTEP, pass_ms


def read(run):
    return pass_ms(run, SUPERSTEP, "sg.drain.ring")
