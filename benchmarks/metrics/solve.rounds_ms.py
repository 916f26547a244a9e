"""Device milliseconds of a solve inside its saturation rounds: the
chunk programs' self time under ``sg.lmm.neighmin``, ``level``,
``update`` and ``prune`` over the window's solves."""

from lib.scopes import ROUND, SOLVE_CHUNK, pass_ms


def read(run):
    return pass_ms(run, SOLVE_CHUNK, *ROUND)
