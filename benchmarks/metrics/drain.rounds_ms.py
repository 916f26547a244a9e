"""Device milliseconds of an advance inside its saturation rounds: the
superstep's self time under ``sg.lmm.neighmin``, ``level``, ``update``
and ``prune`` over the advances committed, entry and the partitions
left out (``drain.round_ms`` holds them)."""

from lib.scopes import ROUND, SUPERSTEP, pass_ms


def read(run):
    return pass_ms(run, SUPERSTEP, *ROUND)
