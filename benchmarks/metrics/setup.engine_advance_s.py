"""Host seconds of set-up inside ``EngineImpl.surf_solve``: self
seconds of the program's ``engine.advance`` spans before the window
(what a span nested in one took - a fast-path dispatch, a plan's
compile - goes to that span).  In these cells: the generic host
advances that take the posted flows past their latency phase.  Left
out where no engine advanced before the window."""

from lib.setup_ledger import row


def read(run):
    return row(run, "engine.advance")
