"""Share of the window's advances whose ring ids were written as wide
as their entries: ``opstats`` ``collective_ring_narrow`` (counted by the
superstep, read from the tail of its packed vector) / the advances
committed.  A tape with the DAG's source-major index writes an
advance's completion and activation ids on the narrowest of
``lmm_drain._RING_RUNGS`` they fit: it finds their flows from the
running counts and runs one gather and one scatter that wide, where the
other side scatters a row for every flow slot.  100 where every advance
logs a few entries; under it by the bursts.  A program without the
counter has nothing to read."""

from simgrid_tpu.ops import opstats


def read(run):
    advances = run.record.get("advances")
    if "collective_ring_narrow" not in opstats.snapshot() or not advances:
        return None
    return 100.0 * run.counters.get("collective_ring_narrow", 0.0) / advances
