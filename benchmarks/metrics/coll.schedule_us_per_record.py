"""Host microseconds a comm record costs the set-up: the self seconds
of the program's ``coll.lower`` spans of id ``schedule`` before the
window (``lib/setup_ledger.py``) / ``opstats``
``collective_schedule_records`` (the records the generator emitted:
per-rank programs, sends matched to receives, the frontier walk that
gives each its predecessors), so that schedules of different lengths
compare.  A program that does not count the records it generates has
nothing to read."""

from lib import setup_ledger
from simgrid_tpu.ops import opstats


def read(run):
    records = opstats.snapshot().get("collective_schedule_records")
    ledger = setup_ledger.of(run)
    if not records or ledger is None:
        return None
    seconds = ledger.by_id("coll.lower").get(("coll.lower", "schedule"))
    return 1e6 * seconds / records if seconds else None
