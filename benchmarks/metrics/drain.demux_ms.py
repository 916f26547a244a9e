"""Host milliseconds replaying one fetched ring into the event stream
(the program's ``drain.demux`` span), mean over the window's dispatches."""

from lib.scopes import window_span_ms


def read(run):
    return window_span_ms(run, "drain.demux")
