#!/usr/bin/env python3
"""The benchmark's one command (see BENCHMARK.json, PERF.md):

    python benchmarks/run.py --workload <cell> --seed <n> \\
                             --seconds <s> --trace <0|1>

Each run is a fresh process: it asserts a TPU, builds the cell's inputs
from the seed, warms the cell's own shapes (all of that is ``setup_s``),
measures one window, holds what the window produced to the plain
reference, prints ONE JSON line last on stdout and exits.
"""

import time

_T0 = time.perf_counter()        # set-up counts from process start

import argparse                  # noqa: E402
import os                        # noqa: E402
import sys                       # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


if __name__ == "__main__":
    args = parse()
    from lib import harness
    sys.exit(harness.main(args, _T0))
