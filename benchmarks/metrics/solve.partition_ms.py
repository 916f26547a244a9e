"""Device milliseconds a solve pays to step down ``fixpoint``'s ladder:
the chunk programs' self time under ``sg.lmm.partition`` over the
window's solves."""

from lib.scopes import SOLVE_CHUNK, pass_ms


def read(run):
    return pass_ms(run, SOLVE_CHUNK, "sg.lmm.partition")
