#!/usr/bin/env python3
"""One run of a cell, and where its ``setup_s`` went, on the chip:

    chiprun -- python benchmarks/tools/setup_ledger.py \\
        --workload dfly65k-random.drain --seed 2147483659 --seconds 45

``run.py --trace 1`` reports six rows of the attribution as metrics
(``setup.boot_s`` ... ``setup.unnamed_pct``).  This tool prints all of
``lib/setup_ledger.py``'s to stderr, in seconds and per cent of
``setup_s``: ``boot``, every span of the program by self seconds,
``post`` and ``unnamed``; the ``xla.*`` rows by ``id`` (which program
was traced, lowered, compiled or loaded from the cache); the ten
longest stretches under no span of the program, each with the spans on
either side and the benchmark's own span open at the time; and adds the
same to the result line as ``setup_ledger``.  ``--trace 0`` (the
default) keeps the profiler off: the attribution needs the program's
spans, not the device's trace.  The benchmark's own runs never run
this; PERF.md section 5's set-up tables are filled from it.
"""

import argparse
import functools
import json
import os
import sys
import time

_T0 = time.perf_counter()

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]


def remembering(base, seen: list):
    """The harness's ``Run``, each one made appended to ``seen``."""

    class Run(base):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            seen.append(self)

    return Run


def report(run) -> dict:
    from lib import setup_ledger
    from lib.scopes import innermost_segments

    ledger = setup_ledger.of(run)
    setup_s = ledger.cut - ledger.t0
    bench = innermost_segments(
        (name, a, b) for name, records in run.spans.records.items()
        for a, b in records)
    stretches = ledger.stretches()[:10]
    for s in stretches:
        # the benchmark's innermost span open at the stretch's middle
        at = ledger.t0 + s["start_s"] + s["seconds"] / 2
        s["under"] = next((name for a, b, name in bench if a <= at < b),
                          "-")
    out = {"setup_s": setup_s, "rows": ledger.rows,
           "xla": [[name, str(id_), s] for (name, id_), s in sorted(
               ledger.by_id("xla.").items(), key=lambda kv: -kv[1])],
           "unnamed_stretches": stretches}
    say = functools.partial(print, file=sys.stderr)
    say(f"setup_s {setup_s:.3f} = the rows below "
        f"({sum(ledger.rows.values()):.3f})")
    for name, s in ledger.rows.items():
        say(f"  {name:16s} {s:9.3f} s  {100 * s / setup_s:5.1f} %")
    say("xla.* self seconds by id (the twelve dearest):")
    for name, id_, s in out["xla"][:12]:
        say(f"  {name:12s} {s:9.3f} s  {id_}")
    say("longest stretches under no span of the program "
        "(post is inside them):")
    for s in stretches:
        say(f"  +{s['start_s']:8.3f} s  {s['seconds']:8.3f} s  after "
            f"{s['prev']}, before {s['next']}, under bench:{s['under']}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from lib import harness

    seen: list = []
    harness.Run = remembering(harness.Run, seen)
    try:
        result = harness.execute(args.workload, args.seed, args.seconds,
                                 bool(args.trace), _T0)
    except SystemExit as exc:
        print(exc, file=sys.stderr)
        return 2
    result["setup_ledger"] = report(seen[0])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
