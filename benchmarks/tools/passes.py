#!/usr/bin/env python3
"""One traced run of a cell, with what its result line has no room
for, on the chip:

    chiprun -- python benchmarks/tools/passes.py \\
        --workload dfly65k-random.drain --seed 2147483659 --seconds 45

``run.py --trace 1`` already reports the device time by ``sg.*`` pass
(the ``*.solve_init_ms`` ... ``*.retire_ms`` metrics) and names its ten
dearest ops and idle gaps as the program does.  This tool adds, to the
same line, ``passes``: the whole table of self seconds by compiled
program and scope, sixteen ops, every idle gap by innermost span, the
window's ``opstats`` counters and a census of the ``xla.compile``
spans.  ``--keep DIR`` also copies the raw trace there (xz-compressed),
which is how the tests' fixture was recorded.  The benchmark's own runs
never run this.
"""

import argparse
import json
import lzma
import os
import sys
import time

_T0 = time.perf_counter()

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)

    from lib import harness, scopes, trace

    seen = []

    class Run(harness.Run):
        """The harness's own, remembered; its raw trace copied out as
        soon as the profiler has written it."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            seen.append(self)

        def stop_trace(self):
            super().stop_trace()
            if args.keep:
                os.makedirs(args.keep, exist_ok=True)
                with open(trace.find_xplane(self.trace_dir), "rb") as src, \
                        lzma.open(os.path.join(
                            args.keep, self.cell.name + ".xplane.pb.xz"),
                            "wb") as dst:
                    dst.write(src.read())

    harness.Run = Run
    try:
        result = harness.execute(args.workload, args.seed, args.seconds,
                                 True, _T0)
    except SystemExit as exc:
        print(exc, file=sys.stderr)
        return 2
    run = seen[0]
    from simgrid_tpu.ops import opstats
    compiles = [s for s in opstats.spans() if s.name == "xla.compile"]
    cut = run.spans.window_from
    result["passes"] = {
        "compiles": {
            "before_window": sum(s.start < cut for s in compiles),
            "cached": sum(str(s.id).startswith("cached:")
                          for s in compiles),
            "in_window": [str(s.id) for s in compiles if s.start >= cut],
            "slowest": [[str(s.id), s.end - s.start] for s in sorted(
                compiles, key=lambda s: s.start - s.end)[:5]]},
        "counters": dict(run.counters),
        "by_program": {f"{program}|{scope}": s for (program, scope), s
                       in sorted(run.scopes.by.items())},
        "top_ops": run.scopes.top_ops(16),
        "idle_gaps": scopes.top_gaps(run.trace, None),
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
