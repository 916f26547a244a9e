"""The program's front end, as a user drives it: parse the platform,
post the flows, pay their latencies, flatten.  (Input building copied
from chip_smoke.py / tools/scale_proof.py; the spans are the
benchmark's.)"""

from __future__ import annotations

import os
from typing import Any, Dict, Tuple

import numpy as np

PLATFORM_XML = """<?xml version='1.0'?>
<platform version="4.1">
  <zone id="world" routing="Full">
    <cluster id="dfly" prefix="node-" radical="0-{last}" suffix=""
             speed="1Gf" bw="{bw}" lat="{lat}" topology="DRAGONFLY"
             topo_parameters="{topo}"/>
  </zone>
</platform>
"""


def write_platform(run, config: Dict[str, Any]) -> str:
    p = config["platform"]
    path = os.path.join(run.scratch, run.cell.entry["config"] + ".xml")
    with open(path, "w") as f:
        f.write(PLATFORM_XML.format(last=p["hosts"] - 1, bw=p["bw"],
                                    lat=p["lat"], topo=p["topo"]))
    return path


def start_engine(run, name: str, pairs: np.ndarray):
    """A fresh engine on the cell's platform with one flow per pair."""
    from simgrid_tpu import s4u

    config = run.cell.config
    s4u.Engine._reset()
    flags = sorted(config["engine_flags"].items())
    e = s4u.Engine([name] + [f"--cfg={k}:{v}" for k, v in flags])
    e.load_platform(write_platform(run, config))
    hosts = e.get_all_hosts()
    if len(hosts) != config["platform"]["hosts"]:
        raise RuntimeError(f"platform has {len(hosts)} hosts")
    model = e.pimpl.network_model
    size = float(config["flow_bytes"])
    actions = [model.communicate(hosts[src], hosts[dst], size, -1.0)
               for src, dst in pairs.tolist()]
    return e, model, actions


def pay_latencies(e, model) -> int:
    """Advance until every posted flow is past its latency phase."""
    advances = 0
    while model.latency_phase_count:
        if e.pimpl.surf_solve(-1.0) < 0:
            raise RuntimeError("engine ran dry in the latency phase")
        advances += 1
        if advances >= 400:
            raise RuntimeError("latency phase did not end")
    return advances


def flattened(run, pairs: np.ndarray) -> Tuple[Any, np.ndarray]:
    """(LmmArrays in float64, flow index of each variable slot)."""
    from simgrid_tpu.ops import lmm_jax

    with run.spans.span("flatten"):
        e, model, actions = start_engine(run, "bench", pairs)
        pay_latencies(e, model)
        arrays, vars_in_order = lmm_jax.flatten(
            list(model.system.active_constraint_set))
        slot = {id(a.variable): k for k, a in enumerate(actions)}
        slot_flow = np.array([slot[id(v)] for v in vars_in_order],
                             np.int64)
    run.shape = (arrays.n_cnst, arrays.n_var, arrays.n_elem)
    return arrays, slot_flow


def solve_precision(run) -> Tuple[Any, float]:
    """(dtype, eps) the program resolves on this device, held to what
    the configuration states."""
    from simgrid_tpu.ops.device import solve_dtype

    dtype = solve_dtype(None, "benchmark")
    stated = run.cell.config["precision"]
    on_chip = run.devices[0].platform == "tpu"
    if on_chip and dtype.name != stated["solve_dtype"]:
        raise RuntimeError(f"the program solves in {dtype.name}, the "
                           f"configuration states {stated['solve_dtype']}")
    return dtype, float(stated["eps"])


def reference_system(run, pairs: np.ndarray, unit_penalty: bool):
    p = run.cell.config["platform"]
    return run.cell.reference.dragonfly_system(
        p["topo"], float(p["bw_bytes_per_s"]), float(p["lat_s"]), pairs,
        unit_penalty=unit_penalty)


def n_hosts(run) -> int:
    return int(run.cell.config["platform"]["hosts"])
