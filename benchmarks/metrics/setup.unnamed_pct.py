"""Share of ``setup_s`` under no span of the program and no counter
pair: 100 x the ``unnamed`` row of ``lib/setup_ledger.py`` over the
whole (boot, the spans' self seconds and ``post_ms`` taken out).  The
tracing's own coverage of set-up, as ``drain.idle_unnamed_pct`` is of
the window's idle time: the benchmark's own Python, and whatever the
program still runs unnamed."""

from lib.setup_ledger import UNNAMED, of


def read(run):
    ledger = of(run)
    if ledger is None or not ledger.cut > ledger.t0:
        return None
    return 100.0 * ledger.rows[UNNAMED] / (ledger.cut - ledger.t0)
