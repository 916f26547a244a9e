#!/usr/bin/env python3
"""Run one cell as the driver's check does and read the spreads the
bounds are set from:

    chiprun -- python benchmarks/tools/sets.py --workload <cell> \\
               [--runs 6] [--sets 2] [--traced 3] [--seconds S]

Two sets of ``--runs`` runs, the same seeds in both, each run a fresh
process of the benchmark's own command; then ``--traced`` runs with
``--trace 1`` on further seeds.  For each end-to-end metric the spread
of a set is the distance between its first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of its median; each
side's first run compiles, so ``setup_s`` is taken without it.  This
process never touches JAX: a chip belongs to one process at a time.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def one_run(manifest, workload, seed, seconds, trace):
    cmd = manifest["command"] + ["--workload", workload, "--seed",
                                 str(seed), "--seconds", str(seconds),
                                 "--trace", str(trace)]
    t0 = time.perf_counter()
    done = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    wall = time.perf_counter() - t0
    lines = [ln for ln in done.stdout.splitlines() if ln.strip()]
    try:
        result = json.loads(lines[-1]) if done.returncode == 0 else None
    except (IndexError, ValueError):
        result = None
    return dict(seed=seed, trace=trace, rc=done.returncode,
                process_s=wall, result=result,
                stderr_tail=done.stderr[-1500:] if result is None
                or not result.get("correct") else "")


def spread(values):
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--traced", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--first-seed", type=int, default=2**31 + 50021)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    seconds = args.seconds or manifest["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out", "sets")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, args.workload + ".jsonl"), "a")
    seeds = [args.first_seed + 104729 * k for k in range(args.runs)]
    sets, ok = [], True
    for s in range(args.sets):
        rows = []
        for seed in seeds:
            row = dict(one_run(manifest, args.workload, seed, seconds, 0),
                       set=s)
            rows.append(row)
            log.write(json.dumps(row) + "\n")
            log.flush()
            r = row["result"]
            ok &= bool(r and r["correct"])
            print(json.dumps(dict(
                set=s, seed=seed, rc=row["rc"],
                process_s=round(row["process_s"], 1),
                correct=r and r["correct"],
                metrics=r and {k: v["value"]
                               for k, v in r["metrics"].items()},
                compared=r and {k: v["value"]
                                for k, v in r["compared"].items()},
                peak=r and r["device"]["memory_peak_bytes"],
                err=row["stderr_tail"][-600:])), flush=True)
        sets.append(rows)
    summary = {}
    names = [m["name"] for m in manifest["end_to_end"]
             if args.workload in m.get("workloads", [args.workload])]
    first_seen = True
    for name in names:
        per_set = []
        for rows in sets:
            vals = [r["result"]["metrics"][name]["value"] for r in rows
                    if r["result"]]
            if name == "setup_s" and first_seen and rows is sets[0]:
                vals = vals[1:]          # the run that compiled
            if len(vals) >= 2:
                per_set.append(dict(median=statistics.median(vals),
                                    spread=spread(vals), n=len(vals),
                                    least=min(vals), most=max(vals)))
        summary[name] = per_set
    print(json.dumps(dict(workload=args.workload, seconds=seconds,
                          summary=summary)), flush=True)
    log.write(json.dumps(dict(summary=summary)) + "\n")
    for k in range(args.traced):
        seed = args.first_seed + 15485863 * (k + 1)
        row = one_run(manifest, args.workload, seed, seconds, 1)
        log.write(json.dumps(row) + "\n")
        log.flush()
        r = row["result"]
        ok &= bool(r and r["correct"])
        print(json.dumps(dict(traced=k, seed=seed, rc=row["rc"],
                              process_s=round(row["process_s"], 1),
                              result=r, err=row["stderr_tail"][-600:])),
              flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
