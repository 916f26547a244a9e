"""Host seconds of set-up inside ``NetworkCm02Model.communicate`` (the
program's ``post_ms`` counter, one firing a flow): route lookup,
variable and expands.  The window posts no flow, but what it counted is
taken off all the same."""

from simgrid_tpu.ops import opstats


def read(run):
    posted = opstats.snapshot().get("post_ms")
    if not posted:
        return None
    return (posted - run.counters.get("post_ms", 0.0)) / 1e3
