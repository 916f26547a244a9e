"""Device milliseconds an advance pays to ENTER its cold solve: the
superstep's self time under ``sg.lmm.init`` and bare ``sg.drain.solve``
over the advances committed.  At the full width of the element list,
unless ``drain.var_entry_pct`` says the advance came in by its flows."""

from lib.scopes import SUPERSTEP, pass_ms


def read(run):
    return pass_ms(run, SUPERSTEP, "sg.lmm.init", "sg.drain.solve")
