"""Bytes a kernel must move, from the UNPADDED problem sizes: the same
work whatever layout or kernel implements it."""


def round_bytes(n_cnst: int, n_var: int, n_elem: int,
                itemsize: int = 4, index_size: int = 4) -> int:
    """Least bytes ONE saturation round of the max-min fixpoint moves
    between HBM and the chip, every gather served from on-chip memory:

    * each element once: its variable index, its constraint index and
      its weight (2 indices + 1 value);
    * each constraint: what is left of its capacity, read and written
      back (2 values);
    * each variable: its penalty and its bound read, its rate written
      (3 values).
    """
    return (n_elem * (2 * index_size + itemsize)
            + n_cnst * 2 * itemsize
            + n_var * 3 * itemsize)


def roofline_pct(bytes_moved: float, seconds: float,
                 peak_bytes_per_s: float):
    """Share of the memory roofline, in percent; None when there is
    nothing to divide by (never 0 for a share of a roofline)."""
    if not seconds or seconds <= 0 or not bytes_moved:
        return None
    return 100.0 * (bytes_moved / peak_bytes_per_s) / seconds
