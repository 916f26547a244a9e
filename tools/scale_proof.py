#!/usr/bin/env python
"""Scale proof (BASELINE config #4): a 65k-host dragonfly with 100k+
concurrent flows, solved by the JAX backend without crashing.

Drives the model layer directly (network_model.communicate per flow —
the same calls the kernel's comm activities make) because the flow
count, not actor count, is the scaling axis under test: route
resolution over the dragonfly topology, LMM system construction, the
vectorized solve, and a few time advances.

Usage: python tools/scale_proof.py [--flows 100000]
           [--backend jax] [--out SCALE_PROOF.md]
"""

import argparse
import os
import resource
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


#: BASELINE config #4's dragonfly: 16 groups x 4 chassis x 16 routers x
#: 64 nodes = 65,536 hosts
CONFIG4_TOPO = "16,3;4,2;16,2;64"


def build_platform(path: str, n_hosts: int = 65536,
                   topo: str = CONFIG4_TOPO) -> str:
    """Write a one-cluster dragonfly platform.  ``topo`` is
    "groups,links;chassis,links;routers,links;nodes"; hosts = groups *
    chassis * routers * nodes, and minimal routing needs
    routers-per-chassis >= groups."""
    dims = [int(part.split(",")[0]) for part in topo.split(";")]
    if dims[0] * dims[1] * dims[2] * dims[3] != n_hosts:
        raise ValueError(f"topo {topo!r} has "
                         f"{dims[0] * dims[1] * dims[2] * dims[3]} hosts, "
                         f"not {n_hosts}")
    xml = f"""<?xml version='1.0'?>
<platform version="4.1">
  <zone id="world" routing="Full">
    <cluster id="dfly" prefix="node-" radical="0-{n_hosts - 1}" suffix=""
             speed="1Gf" bw="125MBps" lat="50us" topology="DRAGONFLY"
             topo_parameters="{topo}"/>
  </zone>
</platform>
"""
    with open(path, "w") as f:
        f.write(xml)
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--flows", type=int, default=100_000)
    ap.add_argument("--backend", default="jax")
    ap.add_argument("--layout", default="auto")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax
    jax.config.update("jax_platforms", os.environ.get(
        "SCALE_PLATFORM", "cpu"))
    import numpy as np

    from simgrid_tpu import s4u

    lines = []

    def log(msg):
        print(msg, flush=True)
        lines.append(msg)

    t0 = time.perf_counter()
    platform = build_platform(
        os.path.join(tempfile.gettempdir(), "dragonfly65k.xml"), 65536)
    e = s4u.Engine(["scale", f"--cfg=lmm/backend:{args.backend}",
                    f"--cfg=lmm/layout:{args.layout}",
                    "--cfg=network/maxmin-selective-update:no",
                    "--cfg=network/optim:Full"])
    e.load_platform(platform)
    n_hosts = e.get_host_count()
    log(f"platform: {n_hosts} hosts, {len(e.get_all_links())} links, "
        f"parsed+built in {time.perf_counter() - t0:.1f}s")

    hosts = e.get_all_hosts()
    rng = np.random.default_rng(42)
    pairs = rng.integers(0, n_hosts, size=(args.flows, 2))

    t0 = time.perf_counter()
    model = e.pimpl.network_model
    actions = []
    for k in range(args.flows):
        src, dst = int(pairs[k, 0]), int(pairs[k, 1])
        if src == dst:
            dst = (dst + 1) % n_hosts
        actions.append(model.communicate(hosts[src], hosts[dst], 1e6, -1.0))
    t_routes = time.perf_counter() - t0
    n_cnst = sum(1 for _ in model.system.active_constraint_set)
    log(f"{args.flows} flows routed + expanded in {t_routes:.1f}s "
        f"({n_cnst} active link constraints)")

    t0 = time.perf_counter()
    model.system.solve()
    t_solve1 = time.perf_counter() - t0
    log(f"first solve ({args.backend}): {t_solve1 * 1e3:.0f} ms")
    # Kernel time advances: flows pay their (hop-dependent) latencies
    # over the first few events, then hold real bandwidth.
    t0 = time.perf_counter()
    advances = 0
    opened = False
    for _ in range(10):
        delta = e.pimpl.surf_solve(-1.0)
        if delta < 0:
            break
        advances += 1
        rates = [a.variable.value for a in actions[:5] if a.variable]
        if rates and all(r > 0 for r in rates):
            log(f"flows hold bandwidth after {advances} advances: "
                f"{[f'{r:.3g}' for r in rates]}")
            opened = True
            break
    assert opened, "sampled flows never received bandwidth"
    log(f"{advances} time advances in {time.perf_counter() - t0:.1f}s, "
        f"clock={e.clock:.4f}")
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
    log(f"peak RSS: {peak:.2f} GB")
    log("RESULT: OK")

    if args.out:
        with open(args.out, "a") as f:
            f.write("\n".join(lines) + "\n")


if __name__ == "__main__":
    main()
