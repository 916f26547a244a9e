"""Share of the device's idle time in the traced window that no ``sg:``
host span of the program covers: the tracing's own coverage.  Nothing
to read where the program has no spans to open (a commit before
``opstats.span``: its 100 would be an absence, not a reading) or the
device never idled."""

from simgrid_tpu.ops import opstats

from lib.scopes import UNNAMED, idle_by_span


def read(run):
    if run.trace is None or not hasattr(opstats, "span"):
        return None
    by = idle_by_span(run.trace)
    idle = sum(by.values())
    return 100.0 * by[UNNAMED] / idle if idle else None
