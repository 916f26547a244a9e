"""Host milliseconds of one superstep dispatch's issue (the program's
``drain.issue`` span: the async enqueue, with trace and compile on a
first call), mean over the dispatches of the window."""

from lib.scopes import window_span_ms


def read(run):
    return window_span_ms(run, "drain.issue")
