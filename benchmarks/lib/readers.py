"""Arithmetic the metric readers share.  A reader that finds nothing to
read returns None and the harness leaves the metric out."""

from __future__ import annotations

from typing import Optional

from .peaks import peak
from .roofline import roofline_pct, round_bytes


def program_round_ms(run, needle: str) -> Optional[float]:
    """Device milliseconds of the compiled programs named like
    ``needle`` over the saturation rounds the program counted, both
    over the window (all of it is traced)."""
    if run.trace is None:
        return None
    seconds, runs = run.trace.module_seconds(needle)
    rounds = run.counters.get("fixpoint_rounds", 0)
    if not runs or not rounds:
        return None
    return 1e3 * seconds / rounds


def round_roofline_pct(run, needle: str) -> Optional[float]:
    """Least time the chip's memory needs for one round's bytes (from
    the UNPADDED sizes) over the measured device time of a round; the
    round is bandwidth-bound (no matrix product in it)."""
    ms = program_round_ms(run, needle)
    if ms is None or run.shape is None:
        return None
    itemsize = {"float32": 4, "float64": 8}[
        run.cell.config["precision"]["solve_dtype"]]
    return roofline_pct(round_bytes(*run.shape, itemsize=itemsize),
                        ms / 1e3, peak(run.device_kind, "hbm_bytes_per_s"))


def idle_pct(run) -> Optional[float]:
    """1 minus the union of device-op intervals over the traced window."""
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
