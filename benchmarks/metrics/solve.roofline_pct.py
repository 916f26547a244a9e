"""Memory-roofline share of one saturation round of ``solve_arrays``
(bandwidth-bound; bytes from the unpadded sizes, lib/roofline.py)."""

from lib.readers import round_roofline_pct

NEEDLE = "jit__solve_kernel_chunk"


def read(run):
    return round_roofline_pct(run, NEEDLE)
