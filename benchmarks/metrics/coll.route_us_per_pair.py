"""Host microseconds a rank pair's route costs the set-up: the
program's ``coll.lower`` spans of id ``routes`` before the window /
``opstats`` ``collective_routes`` (the pairs ``RoutedTopology`` looked
up through ``routing/``, each once).  A program that does not count
the pairs it routes has nothing to read."""

from simgrid_tpu.ops import opstats


def read(run):
    pairs = opstats.snapshot().get("collective_routes")
    cut = run.spans.window_from
    spans = [s.end - s.start for s in getattr(opstats, "spans", list)()
             if s.name == "coll.lower" and s.id == "routes"
             and s.start < cut]
    if not pairs or not spans:
        return None
    return 1e6 * sum(spans) / pairs
