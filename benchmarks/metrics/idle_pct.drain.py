"""The device's idle share of the traced window: 1 minus the union of
device-op intervals over it."""

from lib.readers import idle_pct


def read(run):
    return idle_pct(run)
