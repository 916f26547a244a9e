"""``opstats`` ``host_block_ms`` over the window's wall: the share of the
window the host spent blocked inside the chunk fetches of
``solve_arrays`` (the twin of ``drain.host_block_pct``).  Host blocking,
NOT device busy: the fetch's own copy is in it."""


def read(run):
    blocked = run.counters.get("host_block_ms")
    if not blocked or not run.record.get("wall_s"):
        return None
    return 100.0 * (blocked / 1e3) / run.record["wall_s"]
