#!/usr/bin/env python3
"""``tools/passes.py`` for a cell whose driver is a variant of an entry
point it knows (``solve_alltoall`` runs ``solve``'s compiled program):
the same arguments, the same line.  ``passes.PROGRAMS`` is keyed by the
driver's file name; a driver named ``<entry>_<what>`` reads as
``<entry>`` here."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import passes  # noqa: E402


def register() -> None:
    """Every traffic file's driver under its entry point's program."""
    from lib import manifest as mf
    folder = os.path.join(mf.BENCH, "traffic")
    for name in sorted(os.listdir(folder)):
        driver = mf.load_json(os.path.join(folder, name))["driver"]
        entry = driver.split("_")[0]
        if entry in passes.PROGRAMS:
            passes.PROGRAMS.setdefault(driver, passes.PROGRAMS[entry])


if __name__ == "__main__":
    register()
    sys.exit(passes.main())
