"""Hold the cyclic garbage collector while a builder allocates.

A builder that allocates millions of small containers which all stay
alive (a collective's records and their predecessor sets, a route
table's lists) makes CPython's collector run generation after
generation over objects none of which is garbage: at 65,536 ranks the
collector was 7 of the 14 s a recursive-doubling schedule takes to
build.  Reference counting still frees what dies; cycles wait for the
``with`` block's end."""

from __future__ import annotations

import contextlib
import gc


@contextlib.contextmanager
def collector_paused():
    """No generational collection inside the block (a no-op where the
    collector is already off; nests)."""
    was_on = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was_on:
            gc.enable()
