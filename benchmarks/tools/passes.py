#!/usr/bin/env python3
"""One traced run of a cell with the device time split by the program's
own pass names, on the chip:

    chiprun -- python benchmarks/tools/passes.py \\
        --workload dfly65k-random.drain --seed 2147483659 --seconds 45

The harness throws a run's raw ``.xplane.pb`` away once ``lib/trace.py``
has read it, before any metric reader runs, and ``ProfileData`` does
not show the op-name paths; so this tool decodes the file
(``lib/xmeta.py``) before the harness's own reduction and adds, to the
run's ordinary result line, ``passes``: per compiled program the self
seconds under each ``sg.*`` scope, the per-round / per-advance figures
PERF.md's breakdown is written from, the device's idle time by ``sg:``
host span and a census of the ``xla.compile`` spans.  ``--keep DIR`` also copies the raw trace there
(xz-compressed), which is how the tests' fixture was recorded.  The
benchmark's own runs never run this.
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

#: compiled program of a driver, the counter its rounds divide by, and
#: the record key that counts its outer unit (advances, solves)
PROGRAMS = {"drain": ("jit__superstep_program", "advances"),
            "solve": ("jit__solve_kernel_chunk", "solves")}
PASSES = ("neighmin", "level", "update", "prune")


def breakdown(run, scoped):
    """The figures of ISSUE 27's table from one run's scopes."""
    from lib.scopes import UNSCOPED

    needle, unit = PROGRAMS[run.cell.traffic["driver"]]
    rounds = run.counters.get("fixpoint_rounds", 0)
    units = run.record.get(unit, 0)
    by = scoped.scopes(needle)
    if not rounds or not units or not by:
        return None
    module_s, runs = run.trace.module_seconds(needle)
    out = {"program": needle, "rounds": rounds, unit: units,
           "dispatches": runs, "module_s": module_s,
           "scope_s": dict(sorted(by.items())),
           "scopes_over_module": sum(by.values()) / module_s,
           "round_ms": {p: 1e3 * by.get("sg.lmm." + p, 0.0) / rounds
                        for p in PASSES}}
    init = by.get("sg.lmm.init", 0.0) + by.get("sg.drain.solve", 0.0)
    rest = sum(s for sc, s in by.items() if sc.startswith("sg.drain.")
               and sc != "sg.drain.solve") + by.get(UNSCOPED, 0.0)
    if unit == "advances":
        out["solve_init_ms"] = 1e3 * init / units
        out["retire_ms"] = 1e3 * rest / units
    else:
        out["init_ms"] = 1e3 * (init + rest) / units
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)

    from lib import harness, scopes, trace, xmeta

    seen = {}
    reduce_trace = harness.reduce_trace

    def reduce_keeping_scopes(run):
        path = trace.find_xplane(run.trace_dir)
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            with open(path, "rb") as src, lzma.open(os.path.join(
                    args.keep, run.cell.name + ".xplane.pb.xz"),
                    "wb") as dst:
                dst.write(src.read())
        meta = xmeta.read(path)
        reduce_trace(run)              # run.trace; the raw file goes
        seen["run"] = run
        seen["scoped"] = scopes.device_scopes(meta, run.trace)

    harness.reduce_trace = reduce_keeping_scopes
    try:
        result = harness.execute(args.workload, args.seed, args.seconds,
                                 True, _T0)
    except SystemExit as exc:
        print(exc, file=sys.stderr)
        return 2
    run, scoped = seen["run"], seen["scoped"]
    idle = scopes.idle_by_span(run.trace)
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
        "breakdown": breakdown(run, scoped),
        "by_program": {f"{program}|{scope}": s for (program, scope), s
                       in sorted(scoped.by.items())},
        "top_ops": scoped.top_ops(16),
        "idle_s_by_span": {k: ns / 1e9 for k, ns in sorted(idle.items())},
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
