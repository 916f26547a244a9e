"""Host seconds of set-up inside the program's ``coll.lower`` spans:
every rank pair's route looked up and put in constraint slots
(``RoutedTopology``), the schedule generated and its records and DAG
compiled into the tape's arrays (``CollectiveSpec.build``).  A program
without the span has nothing to read."""

from lib.scopes import setup_span_s


def read(run):
    return setup_span_s(run, "coll.lower")
