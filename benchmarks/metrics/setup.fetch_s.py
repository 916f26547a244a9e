"""Host seconds of set-up and warm-up inside ``opstats.timed_fetch``
(the program's ``fetch`` spans before the window): the host waiting
for the warm-up's device work and copying its results back.  The
window's own fetches are ``*.host_block_pct``."""

from lib.setup_ledger import row


def read(run):
    return row(run, "fetch")
