#!/usr/bin/env python
"""lint_all — the one-stop static gate: simlint + proglint + the
opstats counter registry, one merged exit code.

Usage::

    python tools/lint_all.py [--json]

Runs, in order:

1. **simlint** — the AST invariant rules over the audited source
   paths, against ``tools/simlint_baseline.json``;
2. **proglint** — the compiled-program contract rules over every
   registered jitted kernel program, against
   ``tools/proglint_baseline.json`` (expected steady state: empty);
3. **opstats registry** — the counter table in
   ``ops/opstats.py``'s docstring must parse and carry the core
   counters every tool dashboards on.

Exit 0 only when all three are clean; 1 when any has findings; 2 on
operational errors.  ``check_determinism.py --quick`` runs the same
bundle (via :func:`collect_problems`), so CI and the command line
can't drift apart.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

#: counters the bench/serve tooling hard-depends on — their
#: disappearance from the registry is a lint failure even though the
#: docstring would still parse
CORE_COUNTERS = ("dispatches", "fetches", "fetched_bytes",
                 "blocking_fetches", "host_block_ms", "retraces",
                 "plan_cache_hits", "plan_cache_misses")


def simlint_problems(root: str) -> List[str]:
    from simgrid_tpu import analysis

    findings = analysis.lint_paths(root, ("simgrid_tpu", "tools"))
    baseline_path = os.path.join(root, "tools",
                                 "simlint_baseline.json")
    baseline = None
    if os.path.exists(baseline_path):
        baseline = analysis.load_baseline(baseline_path)
    new, stale = analysis.apply_baseline(findings, baseline)
    out = [f"simlint: {f.path}:{f.line}: [{f.rule}] {f.message}"
           for f in new]
    out += [f"simlint: {e['path']}: stale baseline entry "
            f"[{e['rule']}] {e['snippet']!r}" for e in stale]
    return out


def proglint_problems(root: str) -> List[str]:
    from simgrid_tpu import analysis
    from simgrid_tpu.analysis.prog import lint_programs

    findings = lint_programs()
    baseline_path = os.path.join(root, "tools",
                                 "proglint_baseline.json")
    baseline = None
    if os.path.exists(baseline_path):
        baseline = analysis.load_baseline(baseline_path)
    new, stale = analysis.apply_baseline(findings, baseline)
    out = [f"proglint: {f.path}: [{f.rule}] {f.message}"
           for f in new]
    out += [f"proglint: {e['path']}: stale baseline entry "
            f"[{e['rule']}] {e['snippet']!r}" for e in stale]
    return out


def opstats_registry_problems(root: str) -> List[str]:
    from simgrid_tpu.analysis.rules.opstats_discipline import \
        declared_counters
    from simgrid_tpu.ops import opstats

    doc = opstats.__doc__ or ""
    exact, wild = declared_counters(doc)
    out: List[str] = []
    if not exact:
        out.append("opstats: counter registry parsed EMPTY from the "
                   "module docstring — the table format drifted")
        return out
    for name in CORE_COUNTERS:
        if name not in exact:
            out.append(f"opstats: core counter `{name}` missing from "
                       f"the registry docstring")
    if not wild:
        out.append("opstats: no wildcard counter families declared "
                   "(expected e.g. ``lane_quarantined_<cause>``)")
    return out


def collect_problems(root: str = REPO_ROOT) -> List[str]:
    """Every problem from all three gates (empty = clean); the hook
    ``check_determinism.py --quick`` calls."""
    problems = simlint_problems(root)
    problems += proglint_problems(root)
    problems += opstats_registry_problems(root)
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="lint_all", description=__doc__.splitlines()[0])
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--root", default=REPO_ROOT,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    try:
        problems = collect_problems(args.root)
    except Exception as e:  # noqa: BLE001 — operational failure
        print(f"lint_all: gate crashed: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2

    if args.json:
        print(json.dumps({"problems": problems,
                          "clean": not problems}, indent=1))
    else:
        for p in problems:
            print(p)
        print(f"lint_all: {len(problems)} problem(s) "
              f"(simlint + proglint + opstats registry)")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
