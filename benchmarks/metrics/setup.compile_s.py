"""Host seconds before the window inside XLA's backend compile (the
program's ``xla.compile`` spans of set-up and warm-up; a persistent-
cache load counts for the time it took).  0 where the program records
spans and compiled nothing."""

from lib.scopes import program_spans


def read(run):
    spans = program_spans(run, "xla.compile", in_window=False)
    return None if spans is None else sum(spans)
