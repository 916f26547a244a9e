#!/usr/bin/env python
"""Measure the LMM solver baseline across backends on the reference's
maxmin_bench classes (maxmin_bench.cpp:110-129) and emit a markdown
table + JSON.

Backends:
  ref-native  the C++ solver in native/ driven through the reference's
              exact bench protocol (native/maxmin_bench binary). The
              reference itself cannot be compiled in this image (SimGrid
              3.23 hard-requires boost::intrusive; no boost is installed),
              so this — same construction LCG, solver pinned bit-for-bit
              against the Python oracle, which is pinned against the
              reference's tesh outputs — is the C++ proxy baseline.
  host-python the exact Python list solver (simgrid_tpu/ops/lmm_host.py)
  jax-cpu     the vectorized fixpoint on CPU
  jax-dev     the vectorized fixpoint on the default accelerator, if any

Usage: python tools/measure_baseline.py [--classes small,medium,big,huge]
           [--iters 5] [--json out.json]
"""
import argparse
import json
import os
import re
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")


def time_native(cls, iters, timeout=3600):
    bench = os.path.join(NATIVE_DIR, "maxmin_bench")
    # make rebuilds only when a source is newer than the binary
    subprocess.run(["make", "-C", NATIVE_DIR, "maxmin_bench"], check=True)
    out = subprocess.run([bench, cls, str(iters), "perf"],
                         capture_output=True, text=True, timeout=timeout)
    m = re.search(r"mean_us=([\d.]+) stdev_us=([\d.]+)", out.stdout)
    if not m:
        return {"error": out.stderr[-500:]}
    return {"mean_ms": float(m.group(1)) / 1000,
            "stdev_ms": float(m.group(2)) / 1000}


def time_host_python(cls, iters):
    from simgrid_tpu.ops.bench_systems import build_class
    times = []
    for it in range(iters):
        s, _ = build_class(cls, seed=it + 1)
        t0 = time.perf_counter()
        s.solve_exact()
        times.append(time.perf_counter() - t0)
    return _stats(times)


def time_jax(cls, iters, platform):
    """Time the device fixpoint: flatten once per seed, then time
    steady-state solve_arrays (compile cached after warmup)."""
    import jax
    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from simgrid_tpu.ops import lmm_jax
    from simgrid_tpu.ops.bench_systems import build_class
    from simgrid_tpu.utils.config import config

    devs = [d for d in jax.devices() if d.platform == platform]
    if not devs:
        return {"error": f"no {platform} device"}
    dev = devs[0]
    eps = config["maxmin/precision"]
    times, flat_times, rounds = [], [], 0
    for it in range(iters):
        s, _ = build_class(cls, seed=it + 1)
        t0 = time.perf_counter()
        flat = lmm_jax.flatten(list(s.active_constraint_set), np.float64)
        flat_times.append(time.perf_counter() - t0)
        arrays, _vars = flat
        # warmup (compile + first solve)
        lmm_jax.solve_arrays(arrays, eps, device=dev)
        t0 = time.perf_counter()
        _, _, _, rounds = lmm_jax.solve_arrays(arrays, eps, device=dev)
        times.append(time.perf_counter() - t0)
    st = _stats(times)
    st["flatten_ms"] = round(sum(flat_times) / len(flat_times) * 1000, 3)
    st["rounds"] = rounds
    return st


def _stats(times):
    n = len(times)
    mean = sum(times) / n
    var = sum((t - mean) ** 2 for t in times) / n
    return {"mean_ms": round(mean * 1000, 3),
            "stdev_ms": round(var ** 0.5 * 1000, 3)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--classes", default="small,medium,big")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--huge-iters", type=int, default=1)
    ap.add_argument("--json", default=None)
    ap.add_argument("--skip", default="",
                    help="comma list of backends to skip")
    args = ap.parse_args()
    skip = set(filter(None, args.skip.split(",")))

    results = {}
    for cls in args.classes.split(","):
        iters = args.huge_iters if cls == "huge" else args.iters
        row = {}
        if "native" not in skip:
            row["ref-native"] = time_native(cls, iters)
            print(f"{cls} ref-native: {row['ref-native']}", flush=True)
        if "python" not in skip:
            row["host-python"] = time_host_python(cls, iters)
            print(f"{cls} host-python: {row['host-python']}", flush=True)
        if "jax-cpu" not in skip:
            row["jax-cpu"] = _run_jax_subprocess(cls, iters, "cpu")
            print(f"{cls} jax-cpu: {row['jax-cpu']}", flush=True)
        if "jax-dev" not in skip:
            row["jax-dev"] = _run_jax_subprocess(cls, iters, "device")
            print(f"{cls} jax-dev: {row['jax-dev']}", flush=True)
        results[cls] = row

    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    print(json.dumps(results, indent=1))


def _run_jax_subprocess(cls, iters, kind):
    """Run the jax timing in a subprocess so a wedged accelerator or OOM
    cannot take down the whole measurement run (bench.py's lesson)."""
    env = dict(os.environ)
    if kind == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
        platform = "cpu"
    else:
        platform = env.get("MEASURE_DEVICE_PLATFORM", "tpu")
    code = (
        "import sys, json; sys.path.insert(0, {root!r})\n"
        "import tools.measure_baseline as mb\n"
        "print('RESULT ' + json.dumps(mb.time_jax({cls!r}, {iters}, "
        "{platform!r})))\n").format(
            root=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            cls=cls, iters=iters, platform=platform)
    try:
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=1800)
    except subprocess.TimeoutExpired:
        return {"error": "timeout"}
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    return {"error": (out.stderr or out.stdout)[-500:]}


if __name__ == "__main__":
    main()
