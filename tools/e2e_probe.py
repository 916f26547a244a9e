#!/usr/bin/env python3
"""Per-solve cost on the REAL config-#4 system (65k-host dragonfly,
alltoall flow set): native C++ list solver vs JAX backend on the
current platform (set JAX_PLATFORMS / SCALE_PLATFORM).

Builds the platform once, posts R*(R-1) alltoall flows from R ranks
spread over the hosts, flattens the LMM system, then times:
  - native C++ solve (ops.lmm_native solve path on the flattened copy)
  - JAX solve_arrays (the production device path), warm, median of 3

Prints a JSON line; append with --out.

This is an unguarded probe: nothing holds its rates to a reference.
The guarded twin of its system (same platform, ranks, placement and
block size) is the benchmark cell ``dfly65k-alltoall.solve``:
``benchmarks/configs/dfly65k-alltoall.json`` names this tool as the
source of its shape, ``benchmarks/drivers/solve_alltoall.py`` posts the
same 102,080 flows and times ``solve_arrays`` on the chip, and
``benchmarks/tools/limits.py --cells dfly65k-alltoall.solve`` reads
``rate_gap`` against the float64 reference and its bfloat16 control.
"""
import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ranks", type=int, default=320)
    ap.add_argument("--platform", default=None,
                    help="jax platform override (cpu/tpu)")
    ap.add_argument("--skip-native", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)
    import numpy as np

    from simgrid_tpu import s4u
    from simgrid_tpu.ops import lmm_jax, lmm_native
    from simgrid_tpu.utils.config import config
    from tools.scale_proof import build_platform

    rec = {}
    t0 = time.perf_counter()
    platform = build_platform(
        os.path.join(tempfile.gettempdir(), "dragonfly65k.xml"), 65536)
    e = s4u.Engine(["e2e", "--cfg=lmm/backend:list",
                    "--cfg=network/maxmin-selective-update:no",
                    "--cfg=network/optim:Full"])
    e.load_platform(platform)
    hosts = e.get_all_hosts()
    n_hosts = len(hosts)
    rec["build_s"] = round(time.perf_counter() - t0, 1)

    # R ranks spread evenly; alltoall: every ordered pair, 1 MB
    R = args.ranks
    stride = n_hosts // R
    rank_hosts = [hosts[i * stride] for i in range(R)]
    model = e.pimpl.network_model
    t0 = time.perf_counter()
    actions = []
    for i in range(R):
        for j in range(R):
            if i != j:
                actions.append(model.communicate(
                    rank_hosts[i], rank_hosts[j], 1e6, -1.0))
    rec["flows"] = len(actions)
    rec["route_s"] = round(time.perf_counter() - t0, 1)

    # advance past the latency phase so every flow's variable is live
    t0 = time.perf_counter()
    for _ in range(200):
        n_live = sum(1 for a in actions
                     if a.variable is not None
                     and a.variable.sharing_penalty > 0)
        if n_live == len(actions):
            break
        e.pimpl.surf_solve(-1.0)
    rec["latency_adv_s"] = round(time.perf_counter() - t0, 1)

    system = model.system
    flat = lmm_jax.flatten(list(system.active_constraint_set))
    arrays, _ = flat
    rec.update(n_cnst=arrays.n_cnst, n_var=arrays.n_var,
               n_elem=arrays.n_elem)
    print(json.dumps(rec), flush=True)

    eps = config["maxmin/precision"]
    if not args.skip_native:
        t0 = time.perf_counter()
        vals = lmm_native._solve_flat(arrays, eps)
        rec["native_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
        rec["native_val0"] = float(vals[0][0])
        print(f"native: {rec['native_ms']} ms", flush=True)

    import jax
    dtype = np.float32 if jax.devices()[0].platform != "cpu" \
        else np.float64
    arrays_t = lmm_jax.LmmArrays(
        arrays.e_var, arrays.e_cnst, arrays.e_w.astype(dtype),
        arrays.c_bound.astype(dtype), arrays.c_fatpipe,
        arrays.v_penalty.astype(dtype), arrays.v_bound.astype(dtype),
        arrays.n_elem, arrays.n_cnst, arrays.n_var)
    rec["jax_platform"] = jax.devices()[0].platform
    t0 = time.perf_counter()
    v, r, u, rounds = lmm_jax.solve_arrays(arrays_t, eps)
    rec["jax_first_s"] = round(time.perf_counter() - t0, 1)
    rec["jax_rounds"] = int(rounds)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        v, r, u, rounds = lmm_jax.solve_arrays(arrays_t, eps)
        times.append(time.perf_counter() - t0)
    rec["jax_warm_ms"] = round(float(np.median(times)) * 1e3, 1)
    # cold-path cost (fresh arrays each solve: ELL re-pack + re-upload)
    times = []
    for _ in range(2):
        arrays_c = lmm_jax.LmmArrays(
            arrays_t.e_var.copy(), arrays_t.e_cnst.copy(),
            arrays_t.e_w.copy(), arrays_t.c_bound.copy(),
            arrays_t.c_fatpipe.copy(), arrays_t.v_penalty.copy(),
            arrays_t.v_bound.copy(), arrays.n_elem, arrays.n_cnst,
            arrays.n_var)
        t0 = time.perf_counter()
        v, r, u, rounds = lmm_jax.solve_arrays(arrays_c, eps)
        times.append(time.perf_counter() - t0)
    rec["jax_cold_ms"] = round(float(np.median(times)) * 1e3, 1)
    rec["jax_val0"] = float(v[0])
    print(json.dumps(rec), flush=True)
    if args.out:
        with open(args.out, "a") as fh:
            fh.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
