"""Host seconds from the start of the process to the first span of
either kind, the program's or the benchmark's (the ``boot`` row of
``lib/setup_ledger.py``): the interpreter, ``import jax`` and the
program's modules, the chip's client.  No span of the program can
cover it: it is over before the program runs."""

from lib.setup_ledger import BOOT, row


def read(run):
    return row(run, BOOT)
