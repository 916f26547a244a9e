"""Host microseconds of one ``NetworkCm02Model.communicate`` during
set-up: the program's counter pair ``post_ms`` over ``flows_posted``,
what the window counted taken off both.  The per-flow cost a change to
the posting path moves, whatever the number of flows."""

from simgrid_tpu.ops import opstats


def read(run):
    now = opstats.snapshot()
    flows = now.get("flows_posted", 0) - run.counters.get("flows_posted", 0)
    if not flows:
        return None
    ms = now.get("post_ms", 0.0) - run.counters.get("post_ms", 0.0)
    return 1e3 * ms / flows
