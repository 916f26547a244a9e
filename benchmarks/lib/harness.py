"""One run of one cell: assert the chip, set up, measure one window,
check what the window produced, print one line.

    python benchmarks/run.py --workload <cell> --seed <n>
                             --seconds <s> --trace <0|1>

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` runs
the window under the profiler and prints its per-layer metrics, with
``busy_s`` / ``window_s`` in ``device`` and a ``breakdown``.  Without a
TPU, or with fewer chips than the cell asks for, the run exits non-zero
and prints no result.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

from . import manifest as mf
from .compare import Compared
from .spans import PREFIX, Spans

#: what the benchmark writes (the platform file, the trace of a
#: --trace 1 run): inside the checkout, at a fixed path
SCRATCH = os.path.join(mf.ROOT, ".bench_cache")


def note(run, msg: str) -> None:
    print(f"[bench +{time.perf_counter() - run.t0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def find_devices(chips: int) -> List:
    """The chips this run measures on, or SystemExit: a measurement
    path that finds no accelerator fails, it does not fall back."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"benchmark: no TPU - JAX's default device is "
                         f"{devices[0]}; nothing is measured on "
                         f"{devices[0].platform}")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chip(s), "
                         f"JAX sees {len(devices)}")
    return devices[:chips]


class Run:
    """What the harness, a driver and the metric readers share."""

    def __init__(self, cell: mf.Cell, seed: int, seconds: float,
                 trace: bool, t0: float, devices: List):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.tracing = bool(trace)
        self.t0 = t0
        self.devices = devices
        self.device_kind = devices[0].device_kind
        self.spans = Spans(annotate=self.tracing)
        self.scratch = SCRATCH
        os.makedirs(self.scratch, exist_ok=True)
        #: unpadded (n_cnst, n_var, n_elem) of the system the window
        #: solves; the driver sets it
        self.shape: Optional[tuple] = None
        self.record: Dict[str, Any] = {}
        #: the program's counters (``opstats``) over the window
        self.counters: Dict[str, float] = {}
        self.trace = None
        #: device self time by the program's ``sg.*`` names
        #: (``scopes.DeviceScopes``), once a traced window is reduced
        self.scopes = None
        self.setup_s = float("nan")
        self._window_note = None

    @property
    def trace_dir(self) -> str:
        return os.path.join(self.scratch, "trace", self.cell.name)

    def start_trace(self) -> None:
        if not self.tracing:
            return
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax_profiler().start_trace(self.trace_dir)
        self._window_note = jax_profiler().TraceAnnotation(
            PREFIX + "window")
        self._window_note.__enter__()

    def stop_trace(self) -> None:
        if not self.tracing:
            return
        self._window_note.__exit__(None, None, None)
        jax_profiler().stop_trace()

    def memory_peak_bytes(self) -> Optional[int]:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        return int(max(peaks)) if peaks else None


def jax_profiler():
    import jax
    return jax.profiler


def measure(run: Run, state) -> Dict[str, Any]:
    """The window, under the program's own counters and, when tracing,
    the profiler."""
    from simgrid_tpu.ops import opstats

    before = opstats.snapshot()
    run.spans.window_from = time.perf_counter()
    run.start_trace()
    try:
        with run.spans.span("measure"):
            rec = run.cell.driver.window(run, state)
    finally:
        run.stop_trace()
    run.counters = opstats.diff(before)
    run.record = rec
    return rec


def reduce_trace(run: Run) -> None:
    """``run.trace``, and ``run.scopes`` from what only the raw file
    holds (the op-name paths), before the raw file goes."""
    from . import scopes, trace, xmeta
    path = trace.find_xplane(run.trace_dir)
    t1 = time.perf_counter()
    run.trace = trace.TraceSummary(trace.read_xplane(path),
                                   len(run.devices))
    t2 = time.perf_counter()
    run.scopes = scopes.device_scopes(xmeta.read(path), run.trace)
    note(run, f"trace of {os.path.getsize(path) / 1e6:.1f} MB parsed: "
              f"events {t2 - t1:.2f} s, op-name paths "
              f"{time.perf_counter() - t2:.2f} s")
    shutil.rmtree(run.trace_dir, ignore_errors=True)


def read_metrics(run: Run, end_to_end: Dict[str, float]
                 ) -> Dict[str, Dict[str, Any]]:
    out: Dict[str, Dict[str, Any]] = {}
    if not run.tracing:
        for m in run.cell.end_to_end():
            if m["name"] not in end_to_end:
                raise KeyError(f"driver {run.cell.traffic['driver']!r} "
                               f"gave no {m['name']}")
            out[m["name"]] = {"value": float(end_to_end[m["name"]]),
                              "unit": m["unit"]}
        return out
    for m in run.cell.per_layer():
        value = mf.load_module("metrics", m["name"]).read(run)
        if value is not None:     # nothing to read: left out, never 0
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def execute(workload: str, seed: int, seconds: float, trace: bool,
            t0: float) -> Dict[str, Any]:
    """The whole run; returns the result line as a dict."""
    cell = mf.Cell(mf.load_manifest(), workload)
    devices = find_devices(cell.chips)
    import jax
    run = Run(cell, seed, seconds, trace, t0, devices)
    note(run, f"{workload} seed {seed} on {devices[0].device_kind} x"
              f"{len(devices)}; setting up")
    state = cell.driver.setup(run)
    run.setup_s = time.perf_counter() - t0
    note(run, f"set-up done; measuring {seconds:g} s"
              + (" under the profiler" if trace else ""))
    rec = measure(run, state)
    peak = run.memory_peak_bytes()
    cell.driver.release(run, state)
    note(run, f"window closed after {rec['wall_s']:.2f} s; checking")
    compared: Compared = cell.driver.check(run, state, rec)
    end_to_end = dict(cell.driver.end_to_end(run, rec),
                      setup_s=run.setup_s)
    if trace:
        reduce_trace(run)
    result: Dict[str, Any] = {
        "correct": compared.correct,
        "attempted": int(rec["attempted"]),
        "failed": int(rec["failed"]),
        "metrics": read_metrics(run, end_to_end),
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": peak},
    }
    if trace:
        result["device"]["busy_s"] = run.trace.busy_s
        result["device"]["window_s"] = run.trace.window_s
        from . import scopes
        result["breakdown"] = {"device_ops": scopes.top_ops(run, 10),
                               "idle_gaps": scopes.top_gaps(run.trace, 10)}
    result["compared"] = compared.as_dict()
    for line in compared.lines():
        print(line, file=sys.stderr, flush=True)
    return result


def main(args, t0: float) -> int:
    try:
        result = execute(args.workload, args.seed, args.seconds,
                         bool(args.trace), t0)
    except SystemExit as exc:
        print(exc, file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0
