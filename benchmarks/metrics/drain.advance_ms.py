"""Window wall over the advances committed in it (uploads between laps
included)."""


def read(run):
    advances = run.record.get("advances")
    return 1e3 * run.record["wall_s"] / advances if advances else None
