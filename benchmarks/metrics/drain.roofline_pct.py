"""Memory-roofline share of one saturation round inside the superstep
program (bandwidth-bound; bytes from the unpadded sizes)."""

from lib.readers import round_roofline_pct

NEEDLE = "jit__superstep_program"


def read(run):
    return round_roofline_pct(run, NEEDLE)
