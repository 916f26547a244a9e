"""Device milliseconds a solve pays outside its rounds and partitions:
the chunk programs' self time under ``sg.lmm.init`` (entry, once a
chunk) and no scope at all, over the window's solves."""

from lib.scopes import SOLVE_CHUNK, UNSCOPED, pass_ms


def read(run):
    return pass_ms(run, SOLVE_CHUNK, "sg.lmm.init", UNSCOPED)
