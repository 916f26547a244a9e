#!/usr/bin/env python3
"""Read the two ends of every limit of ``correct``, on the chip, at the
cells' own sizes, in ONE process (set-up is most of a run):

    chiprun -- python benchmarks/tools/limits.py --cells a,b --seeds 12

For each seed the program's numbers (the lower reading: the largest
over the seeds) and, on the first ``--control`` seeds, the control's
(the upper reading: the smallest): the reference in bfloat16 put in the
program's place.  Cells of one configuration share one flattening per
seed.  Prints one JSON line per (cell, seed) and a summary; writes the
same under ``chiprun_out/limits/``.  The benchmark's own runs never run
this.
"""

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    ap.add_argument("--seconds", type=float, default=0.1)
    args = ap.parse_args(argv)

    from drivers import _inputs
    from lib import harness, manifest as mf

    t0 = time.perf_counter()
    manifest = mf.load_manifest()
    devices = harness.find_devices(1)
    out_dir = os.path.join(mf.ROOT, "chiprun_out", "limits")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, "readings.jsonl"), "a")

    flat, real = {}, _inputs.flattened

    def shared(run, pairs):
        key = (run.cell.entry["config"], pairs.tobytes())
        if key not in flat:
            flat.clear()               # one flattening alive at a time
            flat[key] = real(run, pairs)
        arrays, slot_flow = flat[key]
        run.shape = (arrays.n_cnst, arrays.n_var, arrays.n_elem)
        return arrays, slot_flow
    _inputs.flattened = shared

    readings = {}
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        for name in args.cells.split(","):
            cell = mf.Cell(manifest, name)
            run = harness.Run(cell, seed, args.seconds, False,
                              time.perf_counter(), devices)
            state = cell.driver.setup(run)
            rec = harness.measure(run, state)
            cell.driver.release(run, state)
            rows = {"program": cell.driver.check(run, state, rec)}
            if k < args.control:
                rows["control"] = cell.driver.check(run, state, rec,
                                                    precision="bf16")
            line = dict(cell=name, seed=seed,
                        shape=run.shape, wall_s=rec["wall_s"],
                        **{who: dict(correct=c.correct, **c.as_dict())
                           for who, c in rows.items()})
            print(json.dumps(line), flush=True)
            log.write(json.dumps(line) + "\n")
            log.flush()
            for who, c in rows.items():
                for row in c.rows:
                    readings.setdefault((name, row["name"], who),
                                        []).append(row["value"])
    summary = {f"{cell}:{number}:{who}":
               dict(n=len(v), least=min(v), largest=max(v))
               for (cell, number, who), v in sorted(readings.items())}
    line = dict(summary=summary, total_s=time.perf_counter() - t0,
                device=str(devices[0]))
    print(json.dumps(line), flush=True)
    log.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
