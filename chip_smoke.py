#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives BASELINE.json config #4 (the 65,536-host dragonfly of
tools/scale_proof.py, 100,000 random host pairs from
``np.random.default_rng(42)``, 1 MB each: 141,871 constraints x 100,000
variables x 1,241,658 elements) once through the entry points a user
calls, in ONE process on ONE chip, and holds every result to a host
reference.  No width is cut.  Depth is: the full drain is 1,484
advances and about an hour on a host, so the drain and engine legs run
a fixed window of it, and every cut is listed under ``reduced``.

Legs, in order (``LEGS``):

  dtypes   what the device does with f64/i64/f32, held against the table
           in simgrid_tpu/ops/device.py that the code decides by
  solve    lmm_jax.solve_arrays under the device's defaults, rates
           against lmm_native.solve_coo in f64
  drain    DrainSim(superstep=16).run(max_advances=W), events against
           tools/e2e_drain.drain_native over the same window
  engine   s4u.Engine with lmm/backend:jax: the latency phase through
           solve_jax, the drain through DrainFastPath, run_until(date),
           completions against lmm/backend:native + drain/fastpath:off
  serve    CampaignService over a ScenarioPlan captured from an engine
           on the same platform, a PlanCache on disk, a warm restart
           under a DispatchWatchdog, answers against ScenarioPlan.solo
  compile  every ProgramSpec of analysis/prog/registry lowered AND
           compiled on the device

Output: one JSON line per leg, one ``"leg": "summary"`` line (per-leg
verdicts, ``reduced``, the fallback counters, ``"claim": null``), then —
last line of stdout — the verdict the driver reads, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
A leg that fails raises: the traceback is the report, the exit code is
not 0 and neither line is printed.  ``ok`` means all six legs; a
``--legs`` subset prints ``"ok": false`` and exits 1.  Without a TPU
(and without ``--tiny``) the script exits non-zero before doing any
work.  Timings are smoke timings: compile included, one sample, not a
speed.

``--tiny`` swaps in a 128-host dragonfly and a few hundred flows and
skips only the platform assertion, so the script is debugged on
``JAX_PLATFORMS=cpu`` before chip time is spent.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import sys
import time
from importlib import metadata

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

#: config #4 at its own width; the windows are the depth cuts
FULL = dict(hosts=65536, topo="16,3;4,2;16,2;64", flows=100_000,
            drain_advances=32, engine_flows=100_000, engine_advances=12,
            min_flows=4096, serve_flows=1000, serve_batch=4,
            serve_requests=6)
#: the same legs on a 128-host dragonfly, for debugging on a CPU
TINY = dict(hosts=128, topo="4,3;2,2;4,2;4", flows=600,
            drain_advances=32, engine_flows=600, engine_advances=12,
            min_flows=64, serve_flows=96, serve_batch=2,
            serve_requests=3)
#: what seed 42 flattens to at FULL (bench_results history, PERF.md)
CONFIG4_SHAPE = (141_871, 100_000, 1_241_658)
FLOW_BYTES = 1e6

_T0 = time.perf_counter()


def say(**rec) -> None:
    print(json.dumps(rec, default=float), flush=True)


def note(msg: str) -> None:
    print(f"[chip_smoke +{time.perf_counter() - _T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def digest(events) -> str:
    """Fingerprint of an event list: two runs on one device must agree
    on it (the simulation is deterministic), compile cache warm or not."""
    return hashlib.sha256(repr(events).encode()).hexdigest()[:16]


def expect_refusal(fn, needle: str) -> str:
    """fn() must raise a ValueError whose text names ``needle``."""
    try:
        fn()
    except ValueError as exc:
        assert needle in str(exc), f"refused, but not by name: {exc}"
        return str(exc)
    raise AssertionError(f"expected a ValueError naming {needle!r}")


class Ctx:
    """What the legs share: geometry, device, inputs built once."""

    def __init__(self, args, geom, device, outdir):
        from simgrid_tpu.ops.device import solve_dtype
        self.args = args
        self.geom = geom
        self.device = device
        self.outdir = outdir
        self.dtype = solve_dtype(None, "chip_smoke")
        self.eps = 1e-5 if self.dtype == np.float32 else 1e-9
        self.xml = None
        self.arrays = None        # f64 flattening of the full flow set
        self.slot_flow = None
        self.reduced = []

    def peak_hbm(self):
        stats = self.device.memory_stats()
        return None if not stats else stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# Inputs: platform, engines, the flattened system
# ---------------------------------------------------------------------------

HOST_FLAGS = {"lmm/backend": "native", "drain/fastpath": "off"}


def start_engine(ctx: Ctx, name: str, flags: dict, n_flows: int):
    """A fresh engine on the smoke's platform with the first
    ``n_flows`` of the seeded host pairs posted as 1 MB flows."""
    from simgrid_tpu import s4u

    s4u.Engine._reset()
    cfg = {"network/maxmin-selective-update": "no",
           "network/optim": "Full", "lmm/dtype": "auto",
           "drain/transitions": "off",
           "drain/min-flows": ctx.geom["min_flows"], **flags}
    e = s4u.Engine([name] + [f"--cfg={k}:{v}"
                             for k, v in sorted(cfg.items())])
    e.load_platform(ctx.xml)
    hosts = e.get_all_hosts()
    assert len(hosts) == ctx.geom["hosts"]
    pairs = np.random.default_rng(ctx.args.seed).integers(
        0, len(hosts), size=(ctx.geom["flows"], 2))[:n_flows]
    model = e.pimpl.network_model
    actions = []
    for src, dst in pairs.tolist():
        if src == dst:
            dst = (dst + 1) % len(hosts)
        actions.append(model.communicate(hosts[src], hosts[dst],
                                         FLOW_BYTES, -1.0))
    return e, model, actions


def pay_latencies(e, model) -> int:
    """Advance until every posted flow is past its latency phase."""
    advances = 0
    while model.latency_phase_count:
        assert e.pimpl.surf_solve(-1.0) >= 0, "engine ran dry in latency"
        advances += 1
        assert advances < 400, "latency phase did not end"
    return advances


def build_inputs(ctx: Ctx) -> dict:
    from simgrid_tpu.ops import lmm_jax
    from tools.scale_proof import build_platform

    g = ctx.geom
    ctx.xml = build_platform(os.path.join(ctx.outdir, "dragonfly.xml"),
                             g["hosts"], g["topo"])
    t0 = time.perf_counter()
    e, model, actions = start_engine(ctx, "flatten", HOST_FLAGS,
                                     g["flows"])
    t1 = time.perf_counter()
    lat_adv = pay_latencies(e, model)
    arrays, vars_in_order = lmm_jax.flatten(
        list(model.system.active_constraint_set))
    slot = {id(a.variable): k for k, a in enumerate(actions)}
    ctx.arrays = arrays
    ctx.slot_flow = np.array([slot[id(v)] for v in vars_in_order],
                             np.int64)
    shape = (arrays.n_cnst, arrays.n_var, arrays.n_elem)
    if not ctx.args.tiny and ctx.args.seed == 42:
        assert shape == CONFIG4_SHAPE, \
            f"config #4 flattened to {shape}, not {CONFIG4_SHAPE}"
    return dict(leg="inputs", ok=True, hosts=g["hosts"],
                flows=g["flows"], n_cnst=shape[0], n_var=shape[1],
                n_elem=shape[2], latency_advances=lat_adv,
                build_route_s=round(t1 - t0, 1),
                latency_flatten_s=round(time.perf_counter() - t1, 1))


# ---------------------------------------------------------------------------
# Legs
# ---------------------------------------------------------------------------

def leg_dtypes(ctx: Ctx) -> dict:
    """What the device does with each dtype, against ops/device.py."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from simgrid_tpu.ops import device as dev
    from simgrid_tpu.ops.lmm_drain import (DrainSim, _ZERO_BITS,
                                           _rounded_product)
    from simgrid_tpu.analysis.prog.registry import _arrays

    rng = np.random.default_rng(ctx.args.seed)
    n = 4096

    def same(got, want):
        got = np.asarray(got)
        assert got.dtype == want.dtype, (got.dtype, want.dtype)
        view = np.int64 if want.dtype == np.float64 else np.int32
        return float(np.mean(got.view(view) == want.view(view)))

    a = rng.uniform(0.5, 2.0, n) * 10.0 ** rng.integers(-3, 9, n)
    b = rng.uniform(0.5, 2.0, n) * 10.0 ** rng.integers(-3, 9, n)
    da, db = jax.device_put(a), jax.device_put(b)
    f64 = dict(
        roundtrip=same(da, a),
        add=same(jax.jit(jnp.add)(da, db), a + b),
        mul=same(jax.jit(jnp.multiply)(da, db), a * b),
        div=same(jax.jit(jnp.divide)(da, db), a / b),
        keeps_1e200=bool(np.asarray(jax.device_put(np.float64(1e200)))
                         == 1e200))
    x = rng.uniform(1e-3, 1e6, n).astype(np.float32)
    y = rng.uniform(1e-3, 1e6, n).astype(np.float32)
    rem = rng.uniform(1e5, 1e6, n).astype(np.float32)
    dt = np.float32(1.2345e-3)
    f32 = dict(
        add=same(jax.jit(jnp.add)(x, y), x + y),
        mul=same(jax.jit(jnp.multiply)(x, y), x * y),
        div=same(jax.jit(jnp.divide)(x, y), x / y),
        # rem - rate*dt with the product rounded first (numpy's order):
        # plainly, and through _rounded_product's integer detour
        product_plain=same(
            jax.jit(lambda r, v, d: r - v * d)(rem, y, dt), rem - y * dt),
        product_detour=same(
            jax.jit(lambda r, v, d, z: r - _rounded_product(v, d, z))(
                rem, y, dt, _ZERO_BITS), rem - y * dt))
    i = rng.integers(-2 ** 62, 2 ** 62, n)
    j = rng.integers(-2 ** 30, 2 ** 30, n)
    i64 = float(np.mean(np.asarray(
        jax.jit(lambda p, q: p + q * 3)(i, j)) == i + j * 3))

    platform = ctx.device.platform
    ieee = all(v == 1.0 for v in (f64["roundtrip"], f64["add"],
                                  f64["mul"], f64["div"])) \
        and f64["keeps_1e200"]
    assert ieee == dev.f64_is_ieee(platform), (
        f"ops/device.py records f64_is_ieee({platform!r}) = "
        f"{dev.f64_is_ieee(platform)} but the device measures {f64}")
    assert i64 == 1.0, f"int64 arithmetic is not exact: {i64}"
    assert f32["add"] == f32["mul"] == 1.0, f32
    assert f32["product_detour"] == 1.0, f32
    refused = {}
    if ieee:
        assert ctx.dtype == np.float64
    else:
        # the device has no IEEE double: every entry point ends up in
        # float32 when nothing is asked, and refuses float64 by name
        assert ctx.dtype == np.float32
        e_var, e_cnst, e_w, c_bound, sizes = _arrays(1, np.float64)
        refused["lmm/dtype"] = expect_refusal(
            lambda: dev.solve_dtype("float64", "lmm/dtype"), "lmm/dtype")
        refused["DrainSim"] = expect_refusal(
            lambda: DrainSim(e_var, e_cnst, e_w, c_bound, sizes,
                             dtype=np.float64, superstep=2),
            "DrainSim(dtype=)")
        # and the f64<->i64 bitcast the f64 drain programs would need
        # is not implemented there (why _rounded_product cannot run)
        try:
            jax.jit(lambda v: lax.bitcast_convert_type(v, jnp.int64))(da)
            refused["bitcast_f64_i64"] = "compiles"
        except jax.errors.JaxRuntimeError as exc:
            refused["bitcast_f64_i64"] = str(exc).split("\n")[0][:160]
    return dict(leg="dtypes", ok=True, platform=platform,
                f64_is_ieee=ieee, solve_dtype=ctx.dtype.name,
                bit_equal_to_numpy=dict(f64=f64, f32=f32, i64=i64),
                refused=refused)


def leg_solve(ctx: Ctx) -> dict:
    """One max-min solve of the whole system (BASELINE's first metric)."""
    from simgrid_tpu.ops import lmm_jax, lmm_native, opstats
    from simgrid_tpu.ops.lmm_warm import _ell_selected

    src = ctx.arrays
    arrays = src._replace(
        e_w=src.e_w.astype(ctx.dtype), c_bound=src.c_bound.astype(ctx.dtype),
        v_penalty=src.v_penalty.astype(ctx.dtype),
        v_bound=src.v_bound.astype(ctx.dtype))
    with opstats.scoped("smoke/solve") as stats:
        t0 = time.perf_counter()
        values, _rem, _use, rounds = lmm_jax.solve_arrays(arrays, ctx.eps)
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        values2, _, _, rounds2 = lmm_jax.solve_arrays(arrays, ctx.eps)
        second = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref, _, _ = lmm_native.solve_coo(
        src.e_var, src.e_cnst, src.e_w, src.c_bound, src.c_fatpipe,
        src.v_penalty, src.v_bound, ctx.eps, src.n_elem, src.n_cnst,
        src.n_var)
    native_s = time.perf_counter() - t0
    got = np.asarray(values)[:src.n_var].astype(np.float64)
    assert np.all(np.isfinite(got)) and got.shape == ref.shape
    assert np.array_equal(np.asarray(values), np.asarray(values2)), \
        "two solves of the same arrays disagree"
    # the repo's solver tolerance (tests/test_lmm.py): 10x the solve's
    # epsilon relative, plus the eps-clamp width on a saturated link
    atol = 2 * ctx.eps * float(np.max(src.c_bound))
    np.testing.assert_allclose(got, ref, rtol=10 * ctx.eps, atol=atol)
    return dict(leg="solve", ok=True, dtype=ctx.dtype.name, eps=ctx.eps,
                n_cnst=src.n_cnst, n_var=src.n_var, n_elem=src.n_elem,
                # ELL where lmm/layout selects it AND the width/fill
                # guard of ell_from_arrays admits the system
                layout=("ell" if _ell_selected()
                        and lmm_jax._ell_cached(arrays) is not None
                        else "coo"),
                rounds=int(rounds), first_call_s=round(first, 3),
                second_call_s=round(second, 3),
                native_f64_s=round(native_s, 3),
                max_rel_err=float(np.max(np.abs(got - ref)
                                         / np.maximum(ref, atol))),
                opstats=stats, peak_hbm_bytes=ctx.peak_hbm())


def leg_drain(ctx: Ctx) -> dict:
    """A window of the device-resident superstep drain."""
    from simgrid_tpu.ops import opstats
    from simgrid_tpu.ops.lmm_drain import DrainSim
    from tools.e2e_drain import compare_events, drain_native

    a, window = ctx.arrays, ctx.geom["drain_advances"]
    E = a.n_elem
    sim = DrainSim(a.e_var[:E], a.e_cnst[:E], a.e_w[:E].astype(ctx.dtype),
                   a.c_bound[:a.n_cnst].astype(ctx.dtype),
                   np.full(a.n_var, FLOW_BYTES), eps=ctx.eps,
                   dtype=ctx.dtype, superstep=16)
    marks = [time.perf_counter()]
    sim.on_batches = lambda _b: marks.append(time.perf_counter())
    with opstats.scoped("smoke/drain") as stats:
        sim.run(max_advances=window)
    per_dispatch = np.diff(marks)
    events = [(t, int(ctx.slot_flow[fid])) for t, fid in sim.events]
    assert events and (sim.advances == window
                       or len(events) == a.n_var), sim.advances
    assert sim.supersteps >= 2, sim.supersteps
    ref, ref_info = drain_native(a, ctx.slot_flow, FLOW_BYTES,
                                 min_events=len(events))
    census = compare_events(ref, events)
    ctx.reduced.append(
        f"drain: {window} of the drain's advances "
        f"({len(events)} of {a.n_var} completions)")
    return dict(leg="drain", ok=True, dtype=ctx.dtype.name,
                superstep=16, advances=sim.advances,
                dispatches=sim.supersteps, rounds=sim.rounds,
                syncs=sim.syncs, repacks=sim.repacks,
                first_dispatch_s=round(float(per_dispatch[0]), 3),
                later_dispatch_s=[round(float(s), 3)
                                  for s in per_dispatch[1:]],
                native_advances=ref_info["advances"],
                native_wall_s=ref_info["wall_s"], t_sim=sim.t,
                t_sim_native=ref_info["t_sim"], **census,
                events_digest=digest(events),
                opstats=stats, peak_hbm_bytes=ctx.peak_hbm())


def leg_engine(ctx: Ctx) -> dict:
    """The same flows through s4u.Engine on the jax backend."""
    from simgrid_tpu.ops import lmm_jax, opstats
    from tools.e2e_drain import compare_events

    g = ctx.geom
    n_flows, n_adv = g["engine_flows"], g["engine_advances"]
    assert n_flows >= 2 * g["min_flows"]

    # host reference: native solver, generic advance loop
    t0 = time.perf_counter()
    e, model, actions = start_engine(ctx, "engine-ref", HOST_FLAGS,
                                     n_flows)
    lat_adv = pay_latencies(e, model)
    dates = []
    for _ in range(n_adv + 1):
        assert e.pimpl.surf_solve(-1.0) >= 0
        dates.append(e.clock)
    # stop between two completion dates, so neither side's last
    # advance straddles the bound
    until = 0.5 * (dates[-2] + dates[-1])
    ref = sorted((a.finish_time, k) for k, a in enumerate(actions)
                 if 0 <= a.finish_time <= until)
    ref_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    lmm_jax.reset_fallback_count()
    with opstats.scoped("smoke/engine") as stats:
        e, model, actions = start_engine(
            ctx, "engine-jax",
            {"lmm/backend": "jax", "drain/fastpath": "auto"}, n_flows)
        e.run_until(until)
    got = sorted((a.finish_time, k) for k, a in enumerate(actions)
                 if a.finish_time >= 0)
    jax_s = time.perf_counter() - t0
    fast = model.drain_fastpath
    assert stats.get("fastpath_advances", 0) > 0, \
        f"the drain fast path served nothing: {stats}"
    assert stats.get("solver_fallbacks", 0) == 0 \
        and lmm_jax.get_fallback_count() == 0, "host solver fallback"
    assert e.clock == until, (e.clock, until)
    census = compare_events(ref, got)
    if n_flows != g["flows"]:
        ctx.reduced.append(f"engine: {n_flows} of {g['flows']} flows "
                           "(this leg only)")
    ctx.reduced.append(
        f"engine: run_until a date {n_adv} completion advances into "
        f"the drain ({len(got)} of {n_flows} completions)")
    return dict(leg="engine", ok=True, flows=n_flows,
                flags="lmm/backend:jax drain/fastpath:auto "
                      "drain/transitions:off network/optim:Full",
                latency_advances=lat_adv, run_until=until,
                plans=fast.plans, advances_served=fast.advances_served,
                invalidations=fast.invalidations,
                reference_wall_s=round(ref_s, 1),
                jax_wall_s=round(jax_s, 1), **census,
                events_digest=digest(got), opstats=stats,
                peak_hbm_bytes=ctx.peak_hbm())


def leg_serve(ctx: Ctx) -> dict:
    """A campaign service answering what-if requests on the platform."""
    from simgrid_tpu.ops import opstats
    from simgrid_tpu.ops.lmm_batch import DispatchWatchdog
    from simgrid_tpu.parallel.campaign import (ScenarioPlan, ScenarioSpec,
                                               capture_plan_snapshot)
    from simgrid_tpu.serving import CampaignService, PlanCache
    from tools.e2e_drain import compare_events

    g, mesh = ctx.geom, ctx.args.mesh
    e, model, _actions = start_engine(ctx, "serve", HOST_FLAGS,
                                      g["serve_flows"])
    pay_latencies(e, model)
    snap = capture_plan_snapshot(model)
    plan = ScenarioPlan(
        snap["e_var"], snap["e_cnst"], snap["e_w"], snap["c_bound"],
        snap["sizes"], remains=snap["remains"], penalty=snap["penalty"],
        v_bound=snap["v_bound"], link_names=snap["link_names"])
    assert plan.dtype == ctx.dtype

    # size the fault dimension from the plain scenario's own drain: a
    # handful of link failures inside its simulated duration
    base = plan.solo(ScenarioSpec(seed=0, label="base"))
    assert base.error is None and len(base.events) == g["serve_flows"]
    links = len(np.unique(plan.e_cnst[plan.e_w > 0]))
    specs = [ScenarioSpec(seed=s, bw_scale=1.0 + 0.1 * s, label=f"q{s}")
             for s in range(g["serve_requests"] - 1)]
    specs.append(ScenarioSpec(
        seed=7, fault_mtbf=base.t * links / 8.0, fault_mttr=base.t / 8.0,
        fault_horizon=base.t, label="faulted"))

    # the plan cache starts empty on every run, so "cold" means cold
    cache_dir = os.path.join(ctx.outdir, "plancache")
    shutil.rmtree(cache_dir, ignore_errors=True)
    cold = PlanCache(cache_dir)
    svc = CampaignService(plan, batch=g["serve_batch"], plan_cache=cold,
                          mesh=mesh)
    with opstats.scoped("smoke/serve") as stats:
        t0 = time.perf_counter()
        tickets = svc.submit_many(specs, exact=True)
        svc.drain(stop_after=1)
        first = time.perf_counter() - t0
        placed = sorted(str(d) for d in svc._fleet._pen.devices())
        svc.drain()
        total = time.perf_counter() - t0

        # a warm restart: a second cache object over the same directory,
        # and a watchdog around every dispatch
        warm = PlanCache(cache_dir)
        dog = DispatchWatchdog()
        svc2 = CampaignService(plan, batch=g["serve_batch"],
                               plan_cache=warm, mesh=mesh, watchdog=dog)
        t0 = time.perf_counter()
        again = svc2.submit_many(specs[-2:], exact=True)
        svc2.drain()
        warm_s = time.perf_counter() - t0
    assert len(placed) == (mesh or 1), placed
    for t in tickets + again:
        assert t.status == "done" and t.result.source == "device" \
            and t.result.error is None, (t.spec.label, t.status,
                                         t.result and t.result.error)
        assert len(t.result.events) == g["serve_flows"]
    assert tickets[-1].result.fault_events, "the fault tape never fired"
    assert cold.misses > 0 and cold.fallbacks == warm.fallbacks == 0
    assert any(f.endswith(".xplan") for f in os.listdir(cache_dir))
    assert warm.disk_hits > 0, warm.stats()
    assert dog.retries == dog.exhausted == 0
    for t_cold, t_warm in zip(tickets[-2:], again):
        assert t_cold.result.events == t_warm.result.events \
            and t_cold.result.t == t_warm.result.t, \
            "the warm restart answered differently"
    checks = {}
    for t in (tickets[1], tickets[-1]):
        solo = plan.solo(t.spec)
        assert solo.error is None
        checks[t.spec.label] = dict(
            compare_events(solo.events, t.result.events),
            bit_identical=(solo.events == t.result.events
                           and solo.t == t.result.t),
            fault_events=len(t.result.fault_events))
        assert [s for _, s in solo.fault_events] \
            == [s for _, s in t.result.fault_events]
    ctx.reduced.append(
        f"serve: {g['serve_flows']} flows per replica on the same "
        f"platform, fleet width {g['serve_batch']}, "
        f"{len(specs)} requests, each drained to completion")
    return dict(leg="serve", ok=True, dtype=plan.dtype.name,
                flows=g["serve_flows"], n_cnst=len(plan.c_bound),
                batch=g["serve_batch"], requests=len(specs), mesh=mesh,
                replica_devices=placed,
                first_superstep_s=round(first, 3),
                all_answers_s=round(total, 3),
                warm_restart_s=round(warm_s, 3),
                cold_cache=cold.stats(), warm_cache=warm.stats(),
                against_solo=checks,
                events_digest=digest([t.result.events for t in tickets]),
                counters=svc.counters(),
                opstats=stats, peak_hbm_bytes=ctx.peak_hbm())


def leg_compile(ctx: Ctx) -> dict:
    """Every registered program meets this device's compiler."""
    from simgrid_tpu.analysis.prog.registry import iter_programs
    from simgrid_tpu.ops.device import f64_is_ieee

    ieee = f64_is_ieee(ctx.device.platform)
    rows = {}
    for spec in iter_programs():
        if spec.contract.solve_dtype == "float64" and not ieee:
            # an f64 program on a device without IEEE doubles (refused
            # by name, see the dtypes leg) is staged in the device's
            # dtype; the collective tapes too, since their clock pair
            # and dates became the f64 spine of an f32 program
            args, statics = spec.make(1, np.float32)
        else:
            args, statics = spec.make(1)
        t0 = time.perf_counter()
        spec.jitted.lower(*args, **statics).compile()
        rows[spec.name] = round(time.perf_counter() - t0, 3)
    assert len(rows) == 12, sorted(rows)
    return dict(leg="compile", ok=True, programs=len(rows),
                compile_s=rows)


LEGS = {"dtypes": leg_dtypes, "solve": leg_solve, "drain": leg_drain,
        "engine": leg_engine, "serve": leg_serve, "compile": leg_compile}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="128-host geometry, any backend (debugging)")
    ap.add_argument("--seed", type=int, default=42,
                    help="seed of the host pairs and every probe input")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "chip_smoke"),
        help="directory for the generated platform and the plan cache")
    ap.add_argument("--legs", default=",".join(LEGS),
                    help="comma list, a subset of: " + ",".join(LEGS))
    ap.add_argument("--mesh", type=int, default=None,
                    help="shard the serve leg's replica axis over this "
                         "many devices")
    args = ap.parse_args(argv)
    legs = args.legs.split(",")
    unknown = [name for name in legs if name not in LEGS]
    if unknown:
        ap.error(f"unknown leg(s) {unknown}")

    import jax
    import jaxlib
    from simgrid_tpu.ops import compile_cache

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.tiny:
        print(f"chip_smoke: no TPU — JAX's default device is {device} "
              f"(use --tiny to debug on another backend)", file=sys.stderr)
        return 1
    os.makedirs(args.out, exist_ok=True)
    cache_dir, cache_source = compile_cache()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    info = dict(platform=device.platform, kind=device.device_kind,
                count=len(jax.devices()))
    say(leg="device", **info, jax=jax.__version__,
        jaxlib=jaxlib.__version__, libtpu=libtpu,
        compile_cache_dir=cache_dir, compile_cache_from=cache_source,
        compile_cache_entries=(len(os.listdir(cache_dir))
                               if cache_dir and os.path.isdir(cache_dir)
                               else 0),
        geometry="tiny" if args.tiny else "config4", seed=args.seed)

    geom = TINY if args.tiny else FULL
    ctx = Ctx(args, geom, device, args.out)
    note("building the platform and posting the flows")
    say(**build_inputs(ctx))
    verdicts = {}
    for name in legs:
        note(f"leg {name}")
        t0 = time.perf_counter()
        rec = LEGS[name](ctx)
        say(**rec, leg_s=round(time.perf_counter() - t0, 1))
        verdicts[name] = rec["ok"]

    from simgrid_tpu.ops import lmm_jax, opstats
    counters = opstats.snapshot()
    hidden = {k: counters.get(k, 0) for k in
              ("solver_fallbacks", "plan_cache_fallbacks",
               "watchdog_retries", "watchdog_exhausted",
               "watchdog_solo_fallbacks", "serve_solo_results")}
    hidden["lmm_jax.get_fallback_count"] = lmm_jax.get_fallback_count()
    assert not any(hidden.values()), \
        f"a fallback answered for the device: {hidden}"
    ok = all(verdicts.get(name) for name in LEGS)
    say(leg="summary", ok=ok, legs=verdicts,
        legs_skipped=[n for n in LEGS if n not in legs],
        reduced=ctx.reduced, fallbacks=hidden,
        peak_hbm_bytes=ctx.peak_hbm(),
        wall_s=round(time.perf_counter() - _T0, 1), claim=None)
    # the driver's contract: these two keys and no other, last on stdout
    say(ok=ok, device=info)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
