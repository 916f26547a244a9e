"""Programs that went through XLA's backend compile (persistent-cache
loads among them) after the measured window began: the program's
``xla.compile`` spans.  0 is the only good reading."""

from lib.scopes import window_compiles


def read(run):
    return window_compiles(run)
