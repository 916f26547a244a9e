"""Device milliseconds of an advance outside the solve, the tape and
the ring: the superstep's self time under ``sg.drain.advance``,
``sg.drain.pack`` and no scope at all (loop plumbing, the copies XLA
inserts) over the advances committed.  With the five columns beside it
(``drain.solve_init_ms``, ``rounds_ms``, ``partition_ms``, ``coll_ms``,
``ring_ms``) it adds up to the program's device time an advance."""

from lib.scopes import SUPERSTEP, UNSCOPED, pass_ms


def read(run):
    return pass_ms(run, SUPERSTEP, "sg.drain.advance", "sg.drain.pack",
                   UNSCOPED)
