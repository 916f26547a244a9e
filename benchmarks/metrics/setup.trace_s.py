"""Host seconds of set-up and warm-up inside JAX's tracing of the
program's jitted functions into jaxprs: SELF seconds of the program's
``xla.trace`` spans before the window (an inner jit traced inside an
outer trace is counted once), Python the persistent compilation cache
never saves.  0 for a process that started with its jits warm; left
out where the program records no such span."""

from lib.setup_ledger import xla_row


def read(run):
    return xla_row(run, "xla.trace")
