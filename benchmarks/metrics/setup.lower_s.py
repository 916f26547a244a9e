"""Host seconds of set-up and warm-up inside JAX's lowering of a jaxpr
to an MLIR module: self seconds of the program's ``xla.lower`` spans
before the window, paid on every first call whatever the persistent
compilation cache holds (its key is computed from the module).  0 for
a process that started with its jits warm; left out where the program
records no such span."""

from lib.setup_ledger import xla_row


def read(run):
    return xla_row(run, "xla.lower")
