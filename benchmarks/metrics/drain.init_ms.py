"""Host milliseconds the program spends building one fresh ``DrainSim``
(its ``drain.init`` span: the host arrays handed to the device), mean
over the window's laps; warm-up's is not among them."""

from lib.scopes import window_span_ms


def read(run):
    return window_span_ms(run, "drain.init")
