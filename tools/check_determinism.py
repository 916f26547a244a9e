#!/usr/bin/env python3
"""Static determinism lint for the simulation core.

The kernel, the solver and the fault-injection subsystem must be
bit-reproducible: all randomness goes through the seeded RngStream
(simgrid_tpu/utils/rngstream.py) and all time through the simulated
clock.  The static half is simlint (simgrid_tpu/analysis +
tools/simlint.py): run bare, this tool runs the ``wallclock-rng``
rule — an AST lint with import/alias resolution, so ``from time
import time`` or ``import random as rnd`` can't dodge it — over the
audited packages; ``--quick`` runs the FULL simlint rule set (FMA
pinning, hidden host syncs, dtype discipline, iteration order,
opstats registry) against the checked-in
tools/simlint_baseline.json.
Run directly (exit 1 on violations) or through tests/test_determinism_lint.py.

``--runtime-drain`` additionally executes the drain executor at two
dispatch groupings (superstep K=1, superstep K=k) twice each on a
seeded system and verifies (a) run-to-run bit-reproducibility and (b)
cross-grouping completion-order equality — the dynamic counterpart of the
static lint for the superstep path, whose ring-buffer event extraction
must stay deterministic.

``--runtime-warmstart`` runs a seeded mutating workload (flow churn
over clustered constraints plus a deep background chain) twice per
solve mode — cold full restart every solve vs warm-started selective
(ops.lmm_warm) — and asserts (a) run-to-run bit-reproducibility per
mode and (b) bit-identical completion-event order and final clocks
ACROSS modes, plus that the warm runs actually reused their carry.

``--runtime-batch`` drains a 64-replica scenario fleet (mixed fault
seeds + sweep overrides over one shared platform flattening) through
the batched executor (ops.lmm_batch via parallel.campaign) and
asserts that sampled replicas extracted from the batch have
bit-identical event order AND clocks to the same scenario run solo
through ops.lmm_drain.DrainSim — the batching determinism contract.

``--runtime-pipeline`` runs the speculative pipelined drain (solo
DrainSim at depths 1 and 2, and a batched fleet through
parallel.campaign) against the unpipelined superstep path and asserts
bit-identical event order, timestamps and final clocks — INCLUDING
forced-mispredict runs (mid-drain device repacks and
round-budget-starved rescue exits, both of which must discard the
in-flight speculative superstep and replay it), where it additionally
asserts that speculation really was rolled back (otherwise nothing
was tested).

``--runtime-shard`` drains mesh-sharded scenario fleets — the replica
axis of the batched executor split across devices with
``NamedSharding(mesh, PartitionSpec("batch"))`` (ops.lmm_batch
``mesh=``) — and asserts every replica is bit-identical (event order,
timestamps, Kahan clocks) to the single-device vmapped fleet AND to
sampled solo runs, including ragged fleets (B not divisible by the
mesh: dead padding lanes must log zero events), budget-rescue exits
and pipeline depth >= 2 (where it additionally asserts the forced
mispredicts really rolled speculation back).  Needs >= 2 devices: on
CPU run under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
(the standalone tool sets this itself before JAX initializes).

``--runtime-phase`` runs an NAS-style compute/comm alternation (every
completion posts its successor exec or comm — the mutating-phase
shape the device-resident transition payloads exist for) with the
drain fast path on vs off and asserts bit-identical completion
events, timestamps and engine clocks, including forced RESUMABLE
mutations (a mid-phase bandwidth change, absorbed as a bound
scatter), forced NON-RESUMABLE mutations (a deadline'd flow, which
must take the replay fallback — asserted via the invalidation-cause
histogram), and the pipelined fleet variant (speculative supersteps
riding the mutating phase).

``--runtime-fault`` drains a small fleet with per-replica fault event
tapes (2 faulted lanes + 1 clean lane) and asserts the tape contract:
``FaultCampaign.compile_tape`` carries bitwise the same dates as the
``generate()`` schedule an engine-side Profile would replay; every
lane of the batched fleet is bit-identical — events, fault fires AND
Kahan clocks — to the same scenario run solo; at least one tape event
actually FIRED mid-drain (otherwise nothing was tested) and the drain
kept completing after it; ``fault_mode="static"`` still reproduces
the pre-tape mean-availability folding exactly; and the tape composes
with pipeline depth 2 and a 2-device mesh unchanged.

``--runtime-serve`` drives the always-on campaign service
(simgrid_tpu/serving) with more exact queries than the resident fleet
has lanes, so ADMISSION BATCHING must revive dead lanes mid-flight,
and asserts the serving determinism contract: every device-served
ticket — including every lane admitted into a partially-drained fleet
— is bit-identical (completion events, fired fault events AND Kahan
clocks) to ``ScenarioPlan.solo`` on the same spec; at least one lane
really was admitted and at least one fault tape event fired
(otherwise nothing was tested); under pipeline depth 2 the admissions
must additionally have rolled speculation back; and the whole thing
routes through the AOT plan cache, so the executable path is the
audited path.

``--runtime-resume`` audits the preemption-safe campaign contract: a
service is KILLED at an arbitrary collect boundary (checkpoint +
halt), the object discarded, and a fresh service rebuilt with
``CampaignService.resume`` — every ticket must come out bit-identical
(events, fired fault events AND Kahan clocks) to the uninterrupted
run and to ``ScenarioPlan.solo``, including pipeline depth 2 (the
kill lands with speculation in flight, which is never persisted) and
active fault tapes; the resumed fleet must rebuild WARM through the
AOT plan cache (zero new compiles); resuming the same token twice is
idempotent; and a single NaN-poisoned lane quarantines with a
``nan_solve`` LaneFault on its own ticket while every other lane
stays bit-identical to solo.

``--runtime-collective`` proves the collective schedule tapes: the
captured comm sequences of the real ``smpi/coll.py`` algorithms equal
the mirrored generators at non-power-of-two rank counts, and the
tape-driven superstep runs — solo, k=1, pipelined, batched fleets and
fault-tape-composed — are bit-identical (completion events, fired
activations AND Kahan clocks) to the dispatch-per-advance
``HostMaestro`` baseline, at a fraction of its dispatch count; with a
C compiler present, a real NAS-style IS kernel (allreduce + alltoall
iterations through ``smpi/c_api``) is captured live and replayed on
the tape path end to end.

``--quick`` is the CI mode: the static lint plus small-N instances of
every runtime check (drain, warm-start, batch, pipeline, shard,
phase, fault, serve, resume, collective), sized to finish in seconds so the tier-1 suite
can run it on every test pass (tests/test_determinism_lint.py, whose
conftest forces an 8-virtual-device CPU so the mesh path is exercised
on every run).
"""

from __future__ import annotations

import os
import sys
from typing import List, Tuple

#: what the static half audits (simlint path scopes govern per-rule
#: coverage inside these)
AUDITED_PATHS = ("simgrid_tpu", "tools")

_OWN_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _simlint():
    """The simlint package (from THIS repo, wherever the checker was
    loaded from — tests exec this file via importlib)."""
    if _OWN_ROOT not in sys.path:
        sys.path.insert(0, _OWN_ROOT)
    from simgrid_tpu import analysis
    return analysis


def collect_violations(repo_root: str) -> List[Tuple[str, int, str]]:
    """(relative path, line number, stripped line) for every
    wall-clock / global-RNG use under the audited packages.

    Backed by the simlint ``wallclock-rng`` AST rule (import/alias
    resolution, so ``from time import time`` or ``import random as
    rnd`` can't dodge it) — the successor of the old regex scan, same
    return shape."""
    analysis = _simlint()
    rules = [r for r in analysis.ALL_RULES if r.id == "wallclock-rng"]
    findings = analysis.lint_paths(repo_root, AUDITED_PATHS, rules)
    return [(f.path.replace("/", os.sep), f.line, f.snippet)
            for f in findings]


def collect_simlint_problems(repo_root: str) -> List[str]:
    """The full simlint rule set against the checked-in baseline:
    formatted problem strings for every NEW finding and every stale
    baseline entry (empty = clean)."""
    analysis = _simlint()
    findings = analysis.lint_paths(repo_root, AUDITED_PATHS)
    baseline_path = os.path.join(repo_root, "tools",
                                 "simlint_baseline.json")
    baseline = None
    if os.path.exists(baseline_path):
        baseline = analysis.load_baseline(baseline_path)
    new, stale = analysis.apply_baseline(findings, baseline)
    problems = [f"{f.path}:{f.line}: [{f.rule}] {f.message}"
                for f in new]
    problems += [f"{e['path']}: stale simlint baseline entry "
                 f"[{e['rule']}] {e['snippet']!r} — fixed findings "
                 f"must leave tools/simlint_baseline.json too"
                 for e in stale]
    return problems


def check_drain_runtime(seed: int = 13, n_c: int = 128, n_v: int = 800,
                        k: int = 8) -> List[str]:
    """Dynamic determinism of the drain executor: two runs per
    dispatch grouping must be bit-identical (events, advance count,
    clock) and both groupings must agree on completion ORDER.
    Returns a list of problem descriptions (empty = OK)."""
    import numpy as np
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import build_arrays
    from simgrid_tpu.ops.lmm_drain import DrainSim

    rng = np.random.default_rng(seed)
    arrays = build_arrays(rng, n_c, n_v, 3, np.float64)
    sizes = rng.choice(np.linspace(1e5, 2e6, 32), n_v)
    E = arrays.n_elem

    def run(**kw):
        sim = DrainSim(arrays.e_var[:E], arrays.e_cnst[:E],
                       arrays.e_w[:E].astype(np.float64),
                       arrays.c_bound[:arrays.n_cnst].astype(np.float64),
                       sizes, eps=1e-9, dtype=np.float64,
                       repack_min=64, **kw)
        sim.run()
        return sim

    problems: List[str] = []
    streams = {}
    for label, kw in (("superstep K=1", dict(superstep=1)),
                      (f"superstep K={k}", dict(superstep=k))):
        a, b = run(**kw), run(**kw)
        if a.events != b.events or a.advances != b.advances \
                or a.t != b.t:
            problems.append(f"{label}: two identical runs diverged "
                            f"({a.advances} vs {b.advances} advances)")
        streams[label] = [f for _, f in a.events]
    if streams[f"superstep K={k}"] != streams["superstep K=1"]:
        problems.append(f"superstep K={k}: completion order differs "
                        "from superstep K=1")
    return problems


def check_warmstart_runtime(seed: int = 17, n_clusters=24, per=12,
                            chain=48, steps=20) -> List[str]:
    """Dynamic determinism of the warm-started selective solve path: a
    seeded churny mini-drain (solve -> advance to next completion ->
    retire+replace flows) must produce bit-identical completion order,
    event times and final clock whether every solve restarts cold or
    warm-starts from the carried modified component."""
    import numpy as np
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from simgrid_tpu.ops import lmm_jax, make_new_maxmin_system
    from simgrid_tpu.utils.config import config

    def run(mode):
        saved = config["lmm/warm-start"], config["lmm/delta-upload"]
        config["lmm/warm-start"] = mode
        config["lmm/delta-upload"] = "on"
        try:
            rng = np.random.default_rng(seed)
            s = make_new_maxmin_system(True)
            s.solve_fn = lmm_jax.solve_jax
            # background chain: deep cold fixpoint, untouched by churn
            cs = [s.constraint_new(None, float(2.0 ** i))
                  for i in range(chain)]
            for i in range(chain - 1):
                v = s.variable_new(None, 1, -1, 2)
                s.expand(cs[i], v, 1)
                s.expand(cs[i + 1], v, 1)
            clusters = [s.constraint_new(None, float(rng.uniform(50, 200)))
                        for _ in range(n_clusters)]
            flows = []      # (var, remains, fid) in creation order
            next_fid = [0]

            def add_flow(k):
                v = s.variable_new(None, 1.0)
                s.expand(clusters[k], v, float(rng.choice([0.5, 1.0])))
                flows.append([v, float(rng.uniform(1e3, 1e4)),
                              next_fid[0]])
                next_fid[0] += 1

            for k in range(n_clusters):
                for _ in range(per):
                    add_flow(k)
            t = 0.0
            events = []
            for step in range(steps):
                if step % 4 == 3:
                    s.update_constraint_bound(
                        clusters[int(rng.integers(n_clusters))],
                        float(rng.uniform(50, 200)))
                s.solve()
                rates = [f[0].value for f in flows]
                dts = [f[1] / r for f, r in zip(flows, rates) if r > 0]
                if not dts:
                    break
                dt = min(dts)
                t += dt
                done = []
                for f, r in zip(flows, rates):
                    if r > 0:
                        f[1] -= r * dt
                        if f[1] <= 1e-9:
                            done.append(f)
                for f in done:
                    events.append((t, f[2]))
                    k = int(rng.integers(n_clusters))
                    s.variable_free(f[0])
                    flows.remove(f)
                    add_flow(k)
            ws = s.warm_solver
            return events, t, (ws.warm_solves if ws else 0)
        finally:
            config["lmm/warm-start"], config["lmm/delta-upload"] = saved

    problems: List[str] = []
    streams = {}
    for mode in ("cold", "on"):
        a, b = run(mode), run(mode)
        if a[:2] != b[:2]:
            problems.append(f"warm-start:{mode}: two identical runs "
                            f"diverged ({len(a[0])} vs {len(b[0])} events)")
        streams[mode] = a
    if streams["cold"][:2] != streams["on"][:2]:
        problems.append(
            "warm-started selective run diverged from cold-every-solve "
            f"(events {len(streams['cold'][0])} vs "
            f"{len(streams['on'][0])}, clocks {streams['cold'][1]!r} vs "
            f"{streams['on'][1]!r})")
    if streams["on"][2] == 0:
        problems.append("warm mode never reused its carry "
                        "(nothing was actually tested)")
    return problems


def check_batch_runtime(seed: int = 23, n_c: int = 64, n_v: int = 256,
                        batch: int = 64, k: int = 8,
                        solo_check=(0, 13, 37, 63)) -> List[str]:
    """Dynamic determinism of the batched multi-replica executor:
    replica j extracted from a `batch`-wide fleet (mixed fault seeds +
    sweep overrides) must have bit-identical completion events (order
    AND times) and final clock to the same scenario drained solo.
    Returns a list of problem descriptions (empty = OK)."""
    import numpy as np
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import build_arrays
    from simgrid_tpu.parallel.campaign import Campaign, ScenarioSpec

    rng = np.random.default_rng(seed)
    arrays = build_arrays(rng, n_c, n_v, 3, np.float64)
    E = arrays.n_elem
    sizes = rng.choice(np.linspace(1e5, 2e6, 16), n_v)
    specs = [ScenarioSpec(seed=s,
                          bw_scale=1.0 + 0.1 * (s % 5),
                          size_scale=1.0 + 0.05 * (s % 3),
                          fault_mtbf=400.0 if s % 2 else None,
                          fault_mttr=50.0, fault_horizon=600.0,
                          dead_flows=(s % 7,) if s % 3 == 0 else ())
             for s in range(batch)]
    campaign = Campaign(arrays.e_var[:E], arrays.e_cnst[:E],
                        arrays.e_w[:E], arrays.c_bound[:n_c], sizes,
                        specs, eps=1e-9, dtype=np.float64, superstep=k)
    results = campaign.run_batched(batch=batch)

    problems: List[str] = []
    for r in results:
        if r.error:
            problems.append(f"replica {r.spec.label}: batched run "
                            f"errored: {r.error}")
    for j in solo_check:
        if j >= batch:
            continue
        solo = campaign.run_solo(j)
        got = results[j]
        if solo.error or got.error:
            continue        # already reported above
        if solo.events != got.events:
            ndiff = sum(1 for a, b in zip(solo.events, got.events)
                        if a != b)
            problems.append(
                f"replica {j}: batched events differ from solo "
                f"({len(got.events)} vs {len(solo.events)} events, "
                f"{ndiff} mismatched pairs)")
        if solo.t != got.t:
            problems.append(
                f"replica {j}: batched clock {got.t!r} != solo "
                f"{solo.t!r}")
    return problems


def check_pipeline_runtime(seed: int = 29, n_c: int = 64, n_v: int = 400,
                           k: int = 8, depths=(1, 2), batch: int = 8
                           ) -> List[str]:
    """Dynamic determinism of the speculative pipelined drain: the
    pipelined executors must be bit-identical — event order,
    timestamps, final clock, advance count — to the unpipelined
    superstep path, for the solo DrainSim (at every depth in `depths`,
    plus forced-mispredict runs: mid-drain repacks and a starved round
    budget, both of which discard in-flight supersteps) and for a
    `batch`-wide campaign fleet.  Also asserts that speculation
    actually happened (commits > 0) and that the forced-mispredict
    runs really rolled speculation back."""
    import numpy as np
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import build_arrays
    from simgrid_tpu.ops.lmm_drain import DrainSim
    from simgrid_tpu.parallel.campaign import Campaign, ScenarioSpec

    rng = np.random.default_rng(seed)
    arrays = build_arrays(rng, n_c, n_v, 3, np.float64)
    sizes = rng.choice(np.linspace(1e5, 2e6, 16), n_v)
    E = arrays.n_elem

    def run(**kw):
        sim = DrainSim(arrays.e_var[:E], arrays.e_cnst[:E],
                       arrays.e_w[:E].astype(np.float64),
                       arrays.c_bound[:arrays.n_cnst].astype(np.float64),
                       sizes, eps=1e-9, dtype=np.float64, **kw)
        sim.run()
        return sim

    problems: List[str] = []
    # -- solo: plain + forced-mispredict variants -----------------------
    variants = {
        "plain": dict(repack_min=1 << 62),
        # small repack_min: mid-drain device repacks fire, each one a
        # forced mispredict (the in-flight superstep ran on the
        # un-repacked arrays and must be discarded + replayed)
        "repack": dict(repack_min=32),
        # starved round budget: _FLAG_BUDGET exits + K=1 rescues,
        # the other mispredict class
        "budget": dict(repack_min=1 << 62, superstep_rounds=3),
    }
    for label, kw in variants.items():
        ref = run(superstep=k, pipeline=0, **kw)
        for depth in depths:
            a = run(superstep=k, pipeline=depth, **kw)
            b = run(superstep=k, pipeline=depth, **kw)
            if (a.events, a.t, a.advances) != (b.events, b.t, b.advances):
                problems.append(f"pipeline:{label}:d{depth}: two "
                                f"identical runs diverged")
            if a.events != ref.events or a.t != ref.t \
                    or a.advances != ref.advances:
                problems.append(
                    f"pipeline:{label}:d{depth}: diverged from the "
                    f"unpipelined superstep drain ({len(a.events)} vs "
                    f"{len(ref.events)} events, clocks {a.t!r} vs "
                    f"{ref.t!r})")
            if a.spec_committed == 0:
                problems.append(f"pipeline:{label}:d{depth}: no "
                                f"speculation committed (nothing "
                                f"was actually tested)")
            if label in ("repack", "budget") and a.spec_rolled_back == 0:
                problems.append(
                    f"pipeline:{label}:d{depth}: the forced mispredict "
                    f"never rolled speculation back (forcing failed)")
    # -- fleet: pipelined batched campaign vs unpipelined ---------------
    specs = [ScenarioSpec(seed=s, bw_scale=1.0 + 0.15 * (s % 4),
                          size_scale=1.0 + 0.05 * (s % 3),
                          dead_flows=(s % 5,) if s % 3 == 0 else ())
             for s in range(batch)]
    camp = Campaign(arrays.e_var[:E], arrays.e_cnst[:E],
                    arrays.e_w[:E], arrays.c_bound[:n_c], sizes,
                    specs, eps=1e-9, dtype=np.float64, superstep=k)
    ref_fleet = camp.run_batched(batch=batch, pipeline=0)
    for depth in depths:
        got = camp.run_batched(batch=batch, pipeline=depth)
        for j in range(batch):
            if got[j].events != ref_fleet[j].events \
                    or got[j].t != ref_fleet[j].t:
                problems.append(
                    f"pipeline:fleet:d{depth}: replica {j} diverged "
                    f"from the unpipelined fleet drain")
                break
    return problems


def check_shard_runtime(seed: int = 31, n_c: int = 48, n_v: int = 160,
                        batch: int = 8, k: int = 8, shards=(2, 4),
                        depths=(0, 2)) -> List[str]:
    """Dynamic determinism of the mesh-sharded fleet executor: a
    replica of a fleet whose batch axis is sharded over a device mesh
    must be bit-identical — events, timestamps, final Kahan clock — to
    the single-device vmapped fleet and to solo runs, for plain
    drains, ragged fleets (padded dead lanes must stay silent),
    budget-starved rescue exits, and speculative pipeline depths >= 2
    (whose forced mispredicts must actually roll back).  Returns a
    list of problem descriptions (empty = OK)."""
    import numpy as np
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    from bench import build_arrays
    from simgrid_tpu.ops import opstats
    from simgrid_tpu.parallel.campaign import Campaign, ScenarioSpec

    need = max(shards)
    if jax.device_count() < need:
        return [f"shard: only {jax.device_count()} device(s) visible; "
                f"the mesh path needs >= {need} — on CPU run under "
                f"XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{need}"]

    rng = np.random.default_rng(seed)
    arrays = build_arrays(rng, n_c, n_v, 3, np.float64)
    E = arrays.n_elem
    sizes = rng.choice(np.linspace(1e5, 2e6, 16), n_v)
    specs = [ScenarioSpec(seed=s,
                          bw_scale=1.0 + 0.1 * (s % 5),
                          size_scale=1.0 + 0.05 * (s % 3),
                          fault_mtbf=400.0 if s % 2 else None,
                          fault_mttr=50.0, fault_horizon=600.0,
                          dead_flows=(s % 7,) if s % 3 == 0 else ())
             for s in range(batch)]
    camp = Campaign(arrays.e_var[:E], arrays.e_cnst[:E],
                    arrays.e_w[:E], arrays.c_bound[:n_c], sizes,
                    specs, eps=1e-9, dtype=np.float64, superstep=k)

    problems: List[str] = []

    def diff_fleet(label, got, ref, n=None):
        for j in range(n if n is not None else len(ref)):
            if got[j].error or ref[j].error:
                problems.append(f"shard:{label}: replica {j} errored "
                                f"({got[j].error or ref[j].error})")
                return
            if got[j].events != ref[j].events or got[j].t != ref[j].t:
                problems.append(
                    f"shard:{label}: replica {j} diverged from the "
                    f"single-device fleet ({len(got[j].events)} vs "
                    f"{len(ref[j].events)} events, clocks "
                    f"{got[j].t!r} vs {ref[j].t!r})")
                return

    ref = camp.run_batched(batch=batch)          # single-device vmap
    for M in shards:
        for depth in depths:
            before = opstats.snapshot()
            got = camp.run_batched(batch=batch, mesh=M, pipeline=depth)
            d = opstats.diff(before)
            diff_fleet(f"m{M}:d{depth}", got, ref)
            if not d.get("demux_fetches"):
                problems.append(f"shard:m{M}:d{depth}: no per-shard "
                                f"demux fetch recorded (the mesh path "
                                f"was not actually exercised)")
    # vs solo (the standing oracle): one sharded fleet, sampled lanes
    got = camp.run_batched(batch=batch, mesh=shards[0])
    for j in (0, batch // 2, batch - 1):
        solo = camp.run_solo(j)
        if solo.events != got[j].events or solo.t != got[j].t:
            problems.append(f"shard:solo: replica {j} of the sharded "
                            f"fleet diverged from its solo run")
    # ragged fleet: B-1 replicas over the same mesh → one padded lane
    ragged = camp.specs[:batch - 1]
    camp_r = Campaign(arrays.e_var[:E], arrays.e_cnst[:E],
                      arrays.e_w[:E], arrays.c_bound[:n_c], sizes,
                      ragged, eps=1e-9, dtype=np.float64, superstep=k)
    got_r = camp_r.run_batched(batch=batch - 1, mesh=shards[0])
    diff_fleet(f"ragged:m{shards[0]}", got_r, ref, n=batch - 1)
    # budget-starved rescue + deep pipeline: mispredicts must roll
    # speculation back AND stay bit-identical
    if max(depths) >= 2:
        ref_b = camp.run_batched(batch=batch, superstep_rounds=3)
        before = opstats.snapshot()
        got_b = camp.run_batched(batch=batch, superstep_rounds=3,
                                 mesh=shards[0], pipeline=max(depths))
        d = opstats.diff(before)
        diff_fleet(f"budget:m{shards[0]}:d{max(depths)}", got_b, ref_b)
        if not d.get("speculations_rolled_back"):
            problems.append(
                "shard:budget: the budget-starved pipelined fleet "
                "never rolled speculation back (forcing failed — "
                "nothing was actually tested)")
    return problems


def check_fault_runtime(seed: int = 41, n_c: int = 32, n_v: int = 96,
                        k: int = 4, depths=(0, 2), mesh: int = 2
                        ) -> List[str]:
    """Dynamic determinism of the device-resident fault event tapes: a
    3-lane fleet (2 seeded fault schedules + 1 clean lane) must (a)
    compile tapes whose dates are bitwise the generate() schedule an
    engine-side Profile would replay, (b) be bit-identical per lane —
    completion events, fired fault events AND Kahan clocks — to the
    same scenarios run solo, with at least one tape event actually
    firing mid-drain and at least one completion landing after it, (c)
    reproduce the pre-tape mean-availability folding exactly in
    ``fault_mode="static"``, and (d) stay bit-identical under pipeline
    depth 2 and a `mesh`-device replica-axis sharding.  Returns a list
    of problem descriptions (empty = OK)."""
    import numpy as np
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    from bench import build_arrays
    from simgrid_tpu.parallel.campaign import (Campaign, ScenarioSpec,
                                               MIN_LINK_FACTOR)

    rng = np.random.default_rng(seed)
    arrays = build_arrays(rng, n_c, n_v, 3, np.float64)
    E = arrays.n_elem
    sizes = rng.choice(np.linspace(1e5, 2e6, 16), n_v)
    specs = [ScenarioSpec(seed=s, bw_scale=1.0 + 0.1 * s,
                          fault_mtbf=200.0 if s < 2 else None,
                          fault_mttr=60.0, fault_horizon=800.0,
                          fault_dist="weibull" if s == 1
                          else "exponential",
                          fault_shape=1.5)
             for s in range(3)]

    def make(**kw):
        return Campaign(arrays.e_var[:E], arrays.e_cnst[:E],
                        arrays.e_w[:E], arrays.c_bound[:n_c], sizes,
                        specs, eps=1e-9, dtype=np.float64,
                        superstep=k, **kw)

    problems: List[str] = []
    camp = make(fault_mode="on")

    # (a) the tape is the Profile schedule, bitwise: same dates in the
    # same order, states mapped to the clamp floor / full restore
    for s in range(2):
        fc, _ = camp._fault_campaign(specs[s])
        sched = sorted((date, name, value)
                       for (kind, name), pts in fc.generate().items()
                       for date, value in pts)
        ref = camp._fault_campaign(specs[s])[0]
        tape = ref.compile_tape(floor=MIN_LINK_FACTOR)
        if [(d, n, 1.0 if v > 0 else MIN_LINK_FACTOR)
                for d, n, v in sched] \
                != [(d, n, f) for d, _, n, f in tape]:
            problems.append(f"fault: replica {s}: compile_tape "
                            f"diverged from the generate() schedule")

    # (b) batched vs solo, bit-identical incl. the fired fault events
    fleet = camp.run_batched(batch=3)
    fired = 0
    for j in range(3):
        solo = camp.run_solo(j)
        got = fleet[j]
        if solo.error or got.error:
            problems.append(f"fault: replica {j} errored "
                            f"({got.error or solo.error})")
            continue
        if solo.events != got.events or solo.t != got.t:
            problems.append(
                f"fault: replica {j}: batched run diverged from solo "
                f"({len(got.events)} vs {len(solo.events)} events, "
                f"clocks {got.t!r} vs {solo.t!r})")
        if solo.fault_events != got.fault_events:
            problems.append(f"fault: replica {j}: fired fault events "
                            f"differ from solo ({len(got.fault_events)}"
                            f" vs {len(solo.fault_events)})")
        if j == 2 and got.fault_events:
            problems.append("fault: the clean lane fired tape events")
        fired += len(got.fault_events)
    if not fired:
        problems.append("fault: no tape event ever fired mid-drain "
                        "(nothing was actually tested)")
    for j in range(2):
        if fleet[j].fault_events and fleet[j].events:
            first_fire = fleet[j].fault_events[0][0]
            if not any(t >= first_fire for t, _ in fleet[j].events):
                problems.append(
                    f"fault: replica {j}: no completion after the "
                    f"first fire (the post-fault re-solve never ran)")

    # (c) static mode is the pre-tape behavior: identical to folding
    # the mean availabilities into explicit link_scale by hand
    camp_s = make(fault_mode="static")
    folded = []
    for spec in specs:
        ls = dict(spec.link_scale)
        if spec.fault_mtbf is not None:
            fc, names = camp_s._fault_campaign(spec)
            for (kind, name), av in fc.mean_availability().items():
                if av < 1.0:
                    slot = names[name]
                    ls[slot] = ls.get(slot, 1.0) \
                        * max(av, MIN_LINK_FACTOR)
        folded.append(ScenarioSpec(seed=spec.seed,
                                   bw_scale=spec.bw_scale,
                                   link_scale=ls))
    camp_f = Campaign(arrays.e_var[:E], arrays.e_cnst[:E],
                      arrays.e_w[:E], arrays.c_bound[:n_c], sizes,
                      folded, eps=1e-9, dtype=np.float64,
                      superstep=k, fault_mode="off")
    for j, (a, b) in enumerate(zip(camp_s.run_batched(batch=3),
                                   camp_f.run_batched(batch=3))):
        if a.events != b.events or a.t != b.t or a.fault_events:
            problems.append(f"fault: replica {j}: static mode "
                            f"diverged from the hand-folded "
                            f"mean-availability scenario")

    # (d) pipeline + mesh compose: every variant bit-identical
    variants = [("d2", dict(pipeline=2))]
    if jax.device_count() >= mesh:
        variants += [(f"m{mesh}", dict(mesh=mesh)),
                     (f"m{mesh}:d{max(depths)}",
                      dict(mesh=mesh, pipeline=max(depths)))]
    else:
        problems.append(
            f"fault: only {jax.device_count()} device(s) visible; the "
            f"mesh leg needs >= {mesh} — on CPU run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={mesh}")
    for label, kw in variants:
        got = camp.run_batched(batch=3, **kw)
        for j in range(3):
            if got[j].events != fleet[j].events \
                    or got[j].t != fleet[j].t \
                    or got[j].fault_events != fleet[j].fault_events:
                problems.append(f"fault:{label}: replica {j} diverged "
                                f"from the plain batched fleet")
                break
    return problems


def check_serve_runtime(seed: int = 43, n_c: int = 32, n_v: int = 96,
                        batch: int = 3, scenarios: int = 9, k: int = 4,
                        depths=(0, 2)) -> List[str]:
    """Dynamic determinism of the always-on campaign service: more
    exact queries than the resident fleet has lanes (``scenarios >
    batch``), so most queries are ADMITTED into dead lanes of a
    partially-drained fleet mid-flight.  Every device-served ticket —
    initial and admitted alike, fault tapes included — must be
    bit-identical (events, fired fault events, Kahan clocks) to
    ``ScenarioPlan.solo`` on the same spec; admission and at least one
    tape fire must actually have happened (otherwise nothing was
    tested); at pipeline depth >= 1 the mid-flight admissions must
    have rolled in-flight speculation back; and every fleet program
    routes through the AOT plan cache so the executable path IS the
    audited path.  Returns a list of problems (empty = OK)."""
    import numpy as np
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import build_arrays
    from simgrid_tpu.parallel.campaign import ScenarioPlan, ScenarioSpec
    from simgrid_tpu.serving import CampaignService, PlanCache

    rng = np.random.default_rng(seed)
    arrays = build_arrays(rng, n_c, n_v, 3, np.float64)
    E = arrays.n_elem
    sizes = rng.choice(np.linspace(1e5, 2e6, 16), n_v)
    specs = [ScenarioSpec(seed=s, bw_scale=1.0 + 0.1 * (s % 5),
                          size_scale=1.0 + 0.05 * (s % 3),
                          fault_mtbf=150.0 if s % 3 == 0 else None,
                          fault_mttr=50.0, fault_horizon=900.0,
                          label=f"q{s}")
             for s in range(scenarios)]
    plan = ScenarioPlan(arrays.e_var[:E], arrays.e_cnst[:E],
                        arrays.e_w[:E], arrays.c_bound[:n_c], sizes,
                        eps=1e-9, superstep=k, fault_mode="on")
    solos = {spec.label: plan.solo(spec) for spec in specs}

    problems: List[str] = []
    cache = PlanCache()  # memory-resident; same executables every depth
    for depth in depths:
        tag = f"serve:d{depth}"
        svc = CampaignService(plan, batch=batch, plan_cache=cache,
                              pipeline=depth)
        tickets = svc.submit_many(specs, exact=True)
        svc.drain()
        fired = 0
        for t in tickets:
            if t.result is None or t.result.source != "device":
                problems.append(f"{tag}: {t.spec.label} never got a "
                                f"device result")
                continue
            if t.result.error:
                problems.append(f"{tag}: {t.spec.label} errored "
                                f"({t.result.error})")
                continue
            solo = solos[t.spec.label]
            if solo.events != t.result.events \
                    or solo.t != t.result.t:
                problems.append(
                    f"{tag}: {t.spec.label}: served run diverged from "
                    f"solo ({len(t.result.events)} vs "
                    f"{len(solo.events)} events, clocks "
                    f"{t.result.t!r} vs {solo.t!r})")
            if solo.fault_events != t.result.fault_events:
                problems.append(f"{tag}: {t.spec.label}: fired fault "
                                f"events differ from solo")
            fired += len(t.result.fault_events)
        if svc.lanes_admitted == 0:
            problems.append(f"{tag}: no lane was ever admitted "
                            f"mid-flight (nothing was actually tested)")
        if not fired:
            problems.append(f"{tag}: no fault tape event ever fired")
        if depth >= 1 and svc.spec_rolled_back == 0:
            problems.append(f"{tag}: admissions never rolled "
                            f"speculation back (the clean=False "
                            f"contract was not exercised)")
    if cache.hits == 0 or cache.fallbacks:
        problems.append(f"serve: plan cache never took the AOT path "
                        f"(hits={cache.hits}, "
                        f"fallbacks={cache.fallbacks})")
    return problems


def check_resume_runtime(seed: int = 47, n_c: int = 32, n_v: int = 96,
                         batch: int = 3, scenarios: int = 8, k: int = 4,
                         depths=(0, 2), stop_after: int = 3
                         ) -> List[str]:
    """Dynamic determinism of preemption-safe campaigns (ISSUE 12):

    * kill/resume — a campaign service is KILLED at an arbitrary
      collect boundary (``drain(stop_after=...)`` checkpoints and
      halts; the service object is then discarded, simulating the
      preemption) and a fresh service is rebuilt with
      ``CampaignService.resume``: every ticket's completion events,
      fired-fault stream and Kahan clock must be bit-identical to the
      uninterrupted run AND to ``ScenarioPlan.solo`` — including
      pipeline depth 2 (in-flight speculation at the kill point is
      never persisted) and active fault tapes;
    * warm resume — the resumed fleet must rebuild through the AOT
      plan cache without ONE new compile (same plan key);
    * double resume — resuming the same token twice must re-run
      bit-identically (the token is never mutated);
    * lane quarantine — a single poisoned lane (NaN link capacity)
      must die with a ``nan_solve`` LaneFault on ITS ticket while
      every other lane stays bit-identical to solo.

    Returns a list of problems (empty = OK)."""
    import tempfile
    import numpy as np
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import build_arrays
    from simgrid_tpu.ops import opstats
    from simgrid_tpu.parallel.campaign import ScenarioPlan, ScenarioSpec
    from simgrid_tpu.serving import CampaignService, PlanCache

    rng = np.random.default_rng(seed)
    arrays = build_arrays(rng, n_c, n_v, 3, np.float64)
    E = arrays.n_elem
    sizes = rng.choice(np.linspace(1e5, 2e6, 16), n_v)
    specs = [ScenarioSpec(seed=s, bw_scale=1.0 + 0.1 * (s % 5),
                          size_scale=1.0 + 0.05 * (s % 3),
                          fault_mtbf=150.0 if s % 3 == 0 else None,
                          fault_mttr=50.0, fault_horizon=900.0,
                          label=f"q{s}")
             for s in range(scenarios)]
    plan = ScenarioPlan(arrays.e_var[:E], arrays.e_cnst[:E],
                        arrays.e_w[:E], arrays.c_bound[:n_c], sizes,
                        eps=1e-9, superstep=k, fault_mode="on")
    solos = {spec.label: plan.solo(spec) for spec in specs}

    def digest(tickets):
        """The comparable outcome of one service run: per-label
        (events, fault events, clock, error) — latency metadata and
        ticket ordering are excluded on purpose."""
        out = {}
        for t in tickets:
            r = t.result
            out[t.spec.label] = (
                None if r is None
                else (r.source, [tuple(e) for e in (r.events or [])],
                      [tuple(e) for e in (r.fault_events or [])],
                      r.t, r.error))
        return out

    problems: List[str] = []
    cache = PlanCache()  # memory-resident; shared across every leg
    tmpdir = tempfile.mkdtemp(prefix="simgrid_resume_")
    for depth in depths:
        tag = f"resume:d{depth}"
        # leg 1: the uninterrupted oracle run
        svc_a = CampaignService(plan, batch=batch, plan_cache=cache,
                                pipeline=depth)
        svc_a.submit_many(specs, exact=True)
        ref = digest(svc_a.drain())
        # leg 2: kill at a collect boundary, then resume cold
        path = os.path.join(tmpdir, f"ck_d{depth}")
        svc_b = CampaignService(plan, batch=batch, plan_cache=cache,
                                pipeline=depth)
        svc_b.submit_many(specs, exact=True)
        svc_b.drain(stop_after=stop_after, checkpoint_path=path)
        if svc_b._fleet is None:
            problems.append(f"{tag}: drain finished before "
                            f"stop_after={stop_after} — the kill "
                            f"window was never exercised")
            continue
        del svc_b  # the preemption: nothing survives but the token
        misses_before = cache.misses
        svc_c = CampaignService.resume(path, plan_cache=cache)
        if svc_c._fleet is None:
            problems.append(f"{tag}: resume rebuilt no resident fleet")
            continue
        if cache.misses != misses_before:
            problems.append(
                f"{tag}: resume compiled "
                f"{cache.misses - misses_before} new executable(s) — "
                f"the AOT plan cache was not hit warm")
        got = digest(svc_c.drain())
        if got != ref:
            bad = [lbl for lbl in ref
                   if got.get(lbl) != ref[lbl]]
            problems.append(
                f"{tag}: resumed run diverged from the uninterrupted "
                f"run on {len(bad)} quer{'y' if len(bad) == 1 else 'ies'} "
                f"({', '.join(bad[:4])})")
        for spec in specs:
            r = got.get(spec.label)
            solo = solos[spec.label]
            if r is None or r[4] is not None:
                problems.append(f"{tag}: {spec.label} has no clean "
                                f"resumed result")
                continue
            if (r[1] != [tuple(e) for e in solo.events]
                    or r[2] != [tuple(e) for e in solo.fault_events]
                    or r[3] != solo.t):
                problems.append(f"{tag}: {spec.label}: resumed run "
                                f"diverged from solo")
        if not any(r and r[2] for r in got.values()):
            problems.append(f"{tag}: no fault tape event ever fired "
                            f"(tapes were not actually exercised)")
        # leg 3: double resume from the SAME token is idempotent
        svc_d = CampaignService.resume(path, plan_cache=cache)
        got2 = digest(svc_d.drain())
        if got2 != got:
            problems.append(f"{tag}: second resume from the same "
                            f"token diverged from the first")
    if cache.hits == 0 or cache.fallbacks:
        problems.append(f"resume: plan cache never took the AOT path "
                        f"(hits={cache.hits}, "
                        f"fallbacks={cache.fallbacks})")

    # leg 4: single-lane NaN quarantine — a poisoned scenario (NaN
    # sizes: every remaining-work entry of that lane is NaN) kills
    # exactly its own lane, with a structured cause on the ticket
    poison = ScenarioSpec(seed=99, size_scale=float("nan"),
                          label="poison")
    clean = [ScenarioSpec(seed=s, bw_scale=1.0 + 0.1 * s,
                          label=f"clean{s}") for s in range(batch)]
    clean_solos = {s.label: plan.solo(s) for s in clean}
    before = opstats.snapshot()
    svc_q = CampaignService(plan, batch=batch, plan_cache=cache)
    tickets = svc_q.submit_many([poison] + clean, exact=True)
    svc_q.drain()
    d = opstats.diff(before)
    for t in tickets:
        if t.spec.label == "poison":
            if t.fault is None or t.fault.cause != "nan_solve":
                problems.append(
                    f"resume:quarantine: poisoned lane was not "
                    f"quarantined with cause nan_solve (fault="
                    f"{t.fault!r}, error={t.result and t.result.error!r})")
            continue
        r = t.result
        solo = clean_solos[t.spec.label]
        if r is None or r.error is not None \
                or r.events != solo.events or r.t != solo.t:
            problems.append(f"resume:quarantine: clean lane "
                            f"{t.spec.label} diverged from solo after "
                            f"a neighbour's NaN quarantine")
    if not d.get("lane_quarantined_nan_solve"):
        problems.append("resume:quarantine: the nan_solve quarantine "
                        "counter never moved (nothing was actually "
                        "tested)")
    return problems


_FAT_TREE_64 = """<?xml version='1.0'?>
<platform version="4.1">
  <zone id="world" routing="Full">
    <cluster id="ft" prefix="node-" radical="0-63" suffix=""
             speed="1Gf" bw="125MBps" lat="50us" topology="FAT_TREE"
             topo_parameters="2;8,8;1,2;1,1"/>
  </zone>
</platform>
"""


def check_phase_runtime(seed: int = 37, ranks: int = 48, rounds: int = 3,
                        min_flows: int = 16, superstep: int = 16,
                        depths=(0, 2)) -> List[str]:
    """Dynamic determinism of the device-resident mutating phases: an
    NAS-style compute/comm alternation (each rank chains comm -> exec
    -> comm ... over a 64-host fat tree, every completion immediately
    posting its successor) must produce bit-identical completion
    events — order AND finish timestamps — and final engine clock with
    the drain fast path on vs off, under

      * the plain alternation (every completion is a wake/send/exec
        transition the absorb classifier must turn into a payload),
      * a forced RESUMABLE mutation (a backbone link's bandwidth is
        halved mid-phase: a bound-change scatter, not a replay),
      * a forced NON-RESUMABLE mutation (a deadline'd flow joins: the
        classifier has no drain semantics for max_duration and must
        take the bit-identical replay fallback), and
      * the pipelined fleet variant (every depth in `depths`: the
        speculative superstep machinery riding the mutating phase).

    Each variant also asserts the machinery it targets actually fired
    (served advances, absorbed transitions, the unrecognized-cause
    fallback) — otherwise nothing was tested.  Returns a list of
    problem descriptions (empty = OK)."""
    import tempfile
    import numpy as np
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from simgrid_tpu import s4u
    from simgrid_tpu.ops import opstats

    plat = os.path.join(tempfile.mkdtemp(prefix="simgrid_phase_"),
                        "ft64.xml")
    with open(plat, "w") as f:
        f.write(_FAT_TREE_64)

    def bw_mutation(e, model, hosts):
        # resumable: a c_bound scatter in the transition payload
        link = next(iter(e.pimpl.links.values()))
        link.set_bandwidth(link.get_bandwidth() * 0.5)

    def deadline_mutation(e, model, hosts):
        # non-resumable: max_duration has no drain-program semantics,
        # so _absorb must refuse and _invalidate(cause="unrecognized")
        a = model.communicate(hosts[0], hosts[1], 3e5, -1.0)
        a.set_max_duration(1e9)

    def run(cfg, mutate=None):
        """One alternation phase; mutations fire at the first solve
        after t=0.005 — a pure function of the simulated timeline, so
        the fast-path-on and -off runs mutate at the same instant."""
        s4u.Engine._reset()
        try:
            e = s4u.Engine(["phase"] + [f"--cfg={c}" for c in cfg])
            e.load_platform(plat)
            hosts = e.get_all_hosts()[:ranks]
            model = e.pimpl.network_model
            rng = np.random.default_rng(seed)
            dst = rng.integers(0, ranks, size=(ranks, rounds))
            sizes = rng.choice(np.linspace(2e5, 2e6, 12),
                               (ranks, rounds))
            flops = rng.choice(np.linspace(5e5, 5e6, 8),
                               (ranks, rounds))
            stage = [0] * ranks
            tag_of = {}
            events = []

            def post_next(r):
                st = stage[r]
                k = st // 2
                if k >= rounds:
                    return
                if st % 2 == 0:
                    d = int(dst[r, k])
                    if d == r:
                        d = (d + 1) % ranks
                    a = model.communicate(hosts[r], hosts[d],
                                          float(sizes[r, k]), -1.0)
                else:
                    a = hosts[r].cpu.execution_start(float(flops[r, k]))
                tag_of[id(a)] = (r, st)
                stage[r] = st + 1

            for r in range(ranks):
                post_next(r)
            pending = mutate
            for _ in range(200_000):
                if not any(len(m.started_action_set)
                           for m in e.pimpl.models):
                    break
                if pending is not None and e.pimpl.now > 0.005:
                    pending(e, model, hosts)
                    pending = None
                e.pimpl.surf_solve(-1.0)
                for m in list(e.pimpl.models):
                    while True:
                        done = m.extract_done_action()
                        if done is None:
                            break
                        t = tag_of.pop(id(done), None)
                        if t is not None:
                            events.append((done.finish_time, t))
                            post_next(t[0])
                        done.unref()
            return events, e.pimpl.now
        finally:
            s4u.Engine._reset()

    base = ["network/optim:Full", "network/maxmin-selective-update:no",
            "lmm/backend:jax"]
    fast = base + ["drain/fastpath:auto",
                   f"drain/min-flows:{min_flows}",
                   f"drain/superstep:{superstep}"]
    variants = [("plain", [], None),
                ("resumable", [], bw_mutation),
                ("invalidate", [], deadline_mutation)]
    for depth in depths:
        if depth:
            variants.append((f"fleet:d{depth}",
                             [f"drain/pipeline:{depth}"], bw_mutation))

    problems: List[str] = []
    for label, extra, mutate in variants:
        ref = run(base + ["drain/fastpath:off"] + extra, mutate)
        before = opstats.snapshot()
        a = run(fast + extra, mutate)
        d = opstats.diff(before)
        b = run(fast + extra, mutate)
        if a != b:
            problems.append(f"phase:{label}: two identical fast-path "
                            f"runs diverged ({len(a[0])} vs "
                            f"{len(b[0])} events)")
        if a[0] != ref[0] or a[1] != ref[1]:
            ndiff = sum(1 for x, y in zip(a[0], ref[0]) if x != y)
            problems.append(
                f"phase:{label}: fast-path run diverged from the "
                f"native loop ({len(a[0])} vs {len(ref[0])} events, "
                f"{ndiff} mismatched pairs, clocks {a[1]!r} vs "
                f"{ref[1]!r})")
        if not d.get("fastpath_advances"):
            problems.append(f"phase:{label}: the device plan never "
                            f"served an advance (nothing was "
                            f"actually tested)")
        if not d.get("drain_transitions"):
            problems.append(f"phase:{label}: no transition payload was "
                            f"absorbed — the alternation ran on the "
                            f"replay fallback only")
        if label == "invalidate" \
                and not d.get("drain_cause_unrecognized"):
            problems.append(
                "phase:invalidate: the deadline'd flow never forced an "
                "unrecognized-mutation replay (forcing failed — "
                "nothing was actually tested)")
    return problems


#: IS-style NAS comm skeleton: each iteration is the integer sort's
#: bucket-count allreduce followed by the key alltoall, with data
#: checks so a wrong reduction fails the exit code (ITERS via -D).
_NAS_IS_KERNEL = r"""
#include <mpi.h>
#include <stdlib.h>

#ifndef ITERS
#define ITERS 3
#endif

int main(int argc, char **argv) {
    int rank, size, i, it;
    MPI_Init(&argc, &argv);
    MPI_Comm_rank(MPI_COMM_WORLD, &rank);
    MPI_Comm_size(MPI_COMM_WORLD, &size);
    int n = 32 * size;                 /* bucket counts */
    int per = 16;                      /* keys per destination */
    double *cnt = malloc(n * sizeof(double));
    double *tot = malloc(n * sizeof(double));
    double *keys = malloc(per * size * sizeof(double));
    double *sorted = malloc(per * size * sizeof(double));
    for (it = 0; it < ITERS; it++) {
        for (i = 0; i < n; i++) cnt[i] = rank + i + it;
        MPI_Allreduce(cnt, tot, n, MPI_DOUBLE, MPI_SUM, MPI_COMM_WORLD);
        for (i = 0; i < n; i++)
            if (tot[i] != size * (double)(i + it)
                          + size * (size - 1) / 2.0) {
                MPI_Finalize();
                return 20 + it;
            }
        for (i = 0; i < per * size; i++) keys[i] = rank * 1000.0 + i;
        MPI_Alltoall(keys, per, MPI_DOUBLE, sorted, per, MPI_DOUBLE,
                     MPI_COMM_WORLD);
        for (i = 0; i < size; i++)
            if (sorted[i * per] != i * 1000.0 + rank * per) {
                MPI_Finalize();
                return 40 + it;
            }
    }
    MPI_Finalize();
    return 0;
}
"""


def check_collective_runtime(seed: int = 53, ranks: int = 6, k: int = 8,
                             depths=(0, 2), nas: bool = True,
                             nas_ranks: int = 8, nas_iters: int = 3,
                             ratio: float = 10.0) -> List[str]:
    """Dynamic determinism of the collective schedule tapes:

    * capture parity — the comm sequence (src, dst, tag, size,
      dependency order) the REAL ``smpi/coll.py`` algorithms post on
      recording threads must equal the mirrored ``collectives.schedule``
      generators, at `ranks` and the non-power-of-two `ranks`+1;
    * tape vs maestro — the superstep-resident DAG walk (solo, k=1
      grouping and pipeline depth ``max(depths)``) must be
      bit-identical — completion events, fired activations AND the
      Kahan clock pair — to the dispatch-per-advance ``HostMaestro``
      over the same compiled arrays, while issuing at least 3x fewer
      dispatches;
    * fleets — a 3-lane ``Campaign.for_collective`` sweep (plain,
      bw-scaled, size+link-scaled), plain and pipelined, must be
      bit-identical per lane to solo runs including the activation
      stream;
    * fault composition — a seeded link-flip tape firing mid-collective
      must keep tape, maestro and the pipelined variant bit-identical
      while actually moving the event stream;
    * NAS leg (``nas=True``, needs a C compiler) — a real IS-style MPI
      C kernel (bucket-count allreduce + key alltoall per iteration)
      is compiled with ``smpi/c_api``, its live collectives captured
      via ``CaptureScope``, and the replayed schedule must complete on
      the tape path bit-identically to the maestro with >= `ratio`x
      fewer dispatches per collective step.

    Returns a list of problem descriptions (empty = OK)."""
    import numpy as np
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from simgrid_tpu.collectives import (CollectiveSpec, DeviceCollective,
                                         HostMaestro, Topology, generate)
    from simgrid_tpu.smpi.schedule_capture import (CaptureScope,
                                                   capture_schedule,
                                                   default_payload)

    problems: List[str] = []

    # (a) capture parity: real algorithm vs mirrored generator.  The
    # generator payload is bytes except lr (elements); capture always
    # takes bytes.
    cases = [("allreduce", "lr", 23, 23 * 8),
             ("allreduce", "rdb", 4096, 4096),
             ("alltoall", "pairwise", 2e5, 2e5),
             ("alltoall", "bruck", 64, 64),
             ("bcast", "binomial_tree", 4096, 4096)]
    for R in (ranks, ranks + 1):
        for op, algo, gen_pay, nbytes in cases:
            gen = generate(op, algo, R, gen_pay)
            cap = capture_schedule(op, algo, R,
                                   default_payload(op, R, nbytes))
            if cap.sequence() != gen.sequence():
                problems.append(
                    f"collective: {op}/{algo} R={R}: captured comm "
                    f"sequence diverged from the generator "
                    f"({cap.n_comms} vs {gen.n_comms} comms)")

    # (b) tape vs maestro, bit-identical at every grouping
    combos = [CollectiveSpec("allreduce", "lr", ranks - 1, "ring",
                             64, bw=1e8),
              CollectiveSpec("allreduce", "rdb", ranks, "nic",
                             4096, bw=1e8),
              CollectiveSpec("alltoall", "pairwise", ranks, "star",
                             2e5, bw=1e8),
              CollectiveSpec("bcast", "binomial_tree", ranks + 3,
                             "ring", 5e5, bw=1e8)]
    fired_acts = 0
    for cs in combos:
        tag = f"collective: {cs.label()}"
        dc = cs.build()
        sim = dc.make_sim(superstep=k)
        sim.run()
        if len(sim.events) != dc.n_v:
            problems.append(f"{tag}: tape run retired "
                            f"{len(sim.events)}/{dc.n_v} flows")
            continue
        ma = HostMaestro(dc)
        ma.run()
        clk = np.asarray(sim._coll_clk)
        if ma.events != sim.events \
                or ma.collective_events != sim.collective_events:
            problems.append(f"{tag}: tape events diverged from the "
                            f"host maestro")
        if ma.clock != (float(clk[0]), float(clk[1])):
            problems.append(f"{tag}: tape Kahan clock "
                            f"{tuple(map(float, clk))!r} != maestro "
                            f"{ma.clock!r}")
        if ma.dispatches < 3 * max(sim.supersteps, 1):
            problems.append(
                f"{tag}: tape path won no dispatch advantage "
                f"({sim.supersteps} supersteps vs {ma.dispatches} "
                f"maestro dispatches)")
        fired_acts += len(sim.collective_events)
        for label, kw in [("k1", dict(superstep=1)),
                          ("d%d" % max(depths),
                           dict(superstep=max(2, k // 2),
                                pipeline=max(depths)))]:
            alt = dc.make_sim(**kw)
            alt.run()
            if alt.events != sim.events \
                    or alt.collective_events != sim.collective_events:
                problems.append(f"{tag}:{label}: regrouped tape run "
                                f"diverged from superstep k={k}")
    if not fired_acts:
        problems.append("collective: no activation ever fired (the "
                        "DAG walk was not actually tested)")

    # (c) fleet sweep: batched + pipelined lanes == solo, incl. the
    # activation stream
    from simgrid_tpu.parallel.campaign import Campaign, ScenarioSpec
    cs = combos[0]
    specs = [ScenarioSpec(seed=seed, collective=cs, label="plain"),
             ScenarioSpec(seed=seed + 1, bw_scale=0.5, collective=cs,
                          label="bw"),
             ScenarioSpec(seed=seed + 2, size_scale=2.0,
                          link_scale={0: 0.25}, label="scaled")]
    camp = Campaign.for_collective(cs, specs, fault_mode="off",
                                   superstep=k, dtype=np.float64)
    fleet = camp.run_batched(batch=3)
    for j in range(3):
        solo = camp.run_solo(j)
        got = fleet[j]
        if got.error or solo.error:
            problems.append(f"collective: lane {j} errored "
                            f"({got.error or solo.error})")
            continue
        if got.events != solo.events or got.t != solo.t \
                or got.collective_events != solo.collective_events:
            problems.append(f"collective: lane {j}: batched run "
                            f"diverged from solo")
    for depth in depths:
        if not depth:
            continue
        piped = camp.run_batched(batch=3, pipeline=depth)
        for j in range(3):
            if piped[j].events != fleet[j].events \
                    or piped[j].collective_events \
                    != fleet[j].collective_events:
                problems.append(f"collective: lane {j}: pipelined "
                                f"d{depth} fleet diverged")
                break

    # (d) fault-tape composition: a link flip mid-collective
    dc = combos[2].build()
    base = dc.make_sim(superstep=k)
    base.run()
    mid = base.events[len(base.events) // 2][0]
    # drop rank 0's uplink far below its fair share of the star core
    # (merely shaving it would stay core-bottlenecked and move nothing)
    bw = combos[2].bw
    ft = (np.asarray([mid * 0.7, mid * 1.3]),
          np.asarray([0, 0], np.int32), np.asarray([bw * 0.02, bw]))
    simf = dc.make_sim(superstep=k, tape=ft)
    simf.run()
    maf = HostMaestro(dc, tape=ft)
    maf.run()
    clk = np.asarray(simf._coll_clk)
    if maf.events != simf.events \
            or maf.collective_events != simf.collective_events \
            or maf.fault_events != simf.fault_events \
            or maf.clock != (float(clk[0]), float(clk[1])):
        problems.append("collective:fault: composed tape run diverged "
                        "from the host maestro")
    if not simf.fault_events:
        problems.append("collective:fault: no fault event fired "
                        "mid-collective (nothing was actually tested)")
    if simf.events == base.events:
        problems.append("collective:fault: the link flip never moved "
                        "the event stream (nothing was actually tested)")
    piped = dc.make_sim(superstep=max(2, k // 2),
                        pipeline=max(depths) or 2, tape=ft)
    piped.run()
    if piped.events != simf.events \
            or piped.fault_events != simf.fault_events:
        problems.append("collective:fault: pipelined composed run "
                        "diverged")

    # (e) the NAS leg: a real MPI C kernel captured live end to end
    if nas:
        import shutil
        import tempfile
        if shutil.which("gcc") is None \
                and os.environ.get("SMPI_CC") is None:
            problems.append("collective:nas: no C compiler — the NAS "
                            "leg cannot run (install gcc or set "
                            "SMPI_CC)")
            return problems
        from simgrid_tpu.smpi.c_api import compile_program, run_c_program
        tmp = tempfile.mkdtemp(prefix="simgrid_nas_")
        src = os.path.join(tmp, "nas_is.c")
        with open(src, "w") as f:
            f.write(_NAS_IS_KERNEL)
        so = os.path.join(tmp, "nas_is.so")
        compile_program([src], so,
                        extra_flags=(f"-DITERS={nas_iters}",))
        with CaptureScope() as scope:
            _engine, codes = run_c_program(
                so, np_ranks=nas_ranks,
                configs=("smpi/simulate-computation:false",))
        if any(codes.get(r) != 0 for r in range(nas_ranks)):
            problems.append(f"collective:nas: kernel exit codes "
                            f"{codes} (data corrupted under capture)")
            return problems
        if scope.n_phases != 2 * nas_iters:
            problems.append(f"collective:nas: captured "
                            f"{scope.n_phases} collective phases, "
                            f"expected {2 * nas_iters}")
        sched = scope.schedule()
        dc = DeviceCollective(sched, Topology(nas_ranks, "nic", bw=1e8))
        sim = dc.make_sim(superstep=4 * k)
        sim.run()
        if len(sim.events) != dc.n_v:
            problems.append(f"collective:nas: tape run retired "
                            f"{len(sim.events)}/{dc.n_v} flows")
            return problems
        ma = HostMaestro(dc)
        ma.run()
        clk = np.asarray(sim._coll_clk)
        if ma.events != sim.events \
                or ma.collective_events != sim.collective_events \
                or ma.clock != (float(clk[0]), float(clk[1])):
            problems.append("collective:nas: tape run diverged from "
                            "the host maestro")
        if ma.dispatches < ratio * max(sim.supersteps, 1):
            problems.append(
                f"collective:nas: dispatch advantage below {ratio}x "
                f"({sim.supersteps} supersteps vs {ma.dispatches} "
                f"maestro dispatches over {scope.n_phases} collective "
                f"steps)")
    return problems


def quick_checks() -> List[str]:
    """The CI bundle: static lint + small-N instances of every runtime
    check, sized for seconds, so determinism regressions fail pytest
    instead of waiting for a manual tool run."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # the full static gate: simlint + proglint (compiled-program
    # contracts staged over the registered kernel programs) + the
    # opstats counter registry — same bundle as tools/lint_all.py
    tools_dir = os.path.join(repo_root, "tools")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    from lint_all import collect_problems as collect_lint_problems
    problems = collect_lint_problems(repo_root)
    problems += check_drain_runtime(n_c=32, n_v=128, k=4)
    problems += check_batch_runtime(n_c=32, n_v=96, batch=6,
                                    solo_check=(0, 3, 5))
    problems += check_pipeline_runtime(n_c=32, n_v=128, k=4,
                                       depths=(1,), batch=4)
    problems += check_shard_runtime(n_c=24, n_v=64, batch=4, k=4,
                                    shards=(2,), depths=(0, 2))
    problems += check_phase_runtime(ranks=24, rounds=2, min_flows=8,
                                    superstep=8, depths=(0, 2))
    problems += check_fault_runtime(n_c=24, n_v=64, k=4, mesh=2)
    problems += check_serve_runtime(n_c=24, n_v=64, batch=3,
                                    scenarios=7, k=4, depths=(0, 2))
    problems += check_resume_runtime(n_c=24, n_v=64, batch=3,
                                     scenarios=6, k=4, depths=(0, 2),
                                     stop_after=2)
    problems += check_collective_runtime(ranks=5, k=4, depths=(0, 2),
                                         nas=False)
    return problems


def main(argv: List[str]) -> int:
    if ("--runtime-shard" in argv or "--runtime-fault" in argv
            or "--runtime-serve" in argv or "--runtime-resume" in argv
            or "--quick" in argv) and "jax" not in sys.modules:
        # the mesh checks need >= 2 devices; the forced host-platform
        # count must land before JAX initializes and only affects the
        # CPU backend (harmless elsewhere)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4"
            ).strip()
    if "--runtime-shard" in argv:
        problems = check_shard_runtime()
        if problems:
            print("check_determinism: shard runtime check FAILED:")
            for p in problems:
                print(f"  {p}")
            return 1
        print("check_determinism: shard runtime OK (mesh-sharded "
              "replica-axis fleets — 2/4-shard, ragged padding, "
              "budget rescue, pipeline depth 2 incl. forced-rollback "
              "assertion — bit-identical to the single-device vmapped "
              "fleet and to solo runs: event order, timestamps and "
              "clocks)")
        argv = [a for a in argv if a != "--runtime-shard"]
    if "--runtime-fault" in argv:
        problems = check_fault_runtime()
        if problems:
            print("check_determinism: fault runtime check FAILED:")
            for p in problems:
                print(f"  {p}")
            return 1
        print("check_determinism: fault runtime OK (device fault "
              "tapes — 2 faulted + 1 clean lane, tape dates bitwise "
              "the generate() schedule, >= 1 event fired mid-drain, "
              "static mode = hand-folded availabilities, pipeline "
              "depth 2 and 2-device mesh compose — bit-identical to "
              "solo runs: events, fired faults and Kahan clocks)")
        argv = [a for a in argv if a != "--runtime-fault"]
    if "--runtime-serve" in argv:
        problems = check_serve_runtime()
        if problems:
            print("check_determinism: serve runtime check FAILED:")
            for p in problems:
                print(f"  {p}")
            return 1
        print("check_determinism: serve runtime OK (campaign service "
              "— queries admitted mid-flight into partially-drained "
              "fleets through the AOT plan cache, incl. fault tapes "
              "and pipeline depth 2 with forced-rollback assertion — "
              "bit-identical to ScenarioPlan.solo: events, fired "
              "faults and Kahan clocks)")
        argv = [a for a in argv if a != "--runtime-serve"]
    if "--runtime-resume" in argv:
        problems = check_resume_runtime()
        if problems:
            print("check_determinism: resume runtime check FAILED:")
            for p in problems:
                print(f"  {p}")
            return 1
        print("check_determinism: resume runtime OK (preemption-safe "
              "campaigns — service killed at a collect boundary and "
              "rebuilt from its FleetCheckpoint token, warm through "
              "the AOT plan cache, incl. fault tapes and pipeline "
              "depth 2; double resume idempotent; a NaN-poisoned "
              "lane quarantines with a nan_solve LaneFault while "
              "every other lane stays bit-identical to "
              "ScenarioPlan.solo: events, fired faults and Kahan "
              "clocks)")
        argv = [a for a in argv if a != "--runtime-resume"]
    if "--runtime-collective" in argv:
        problems = check_collective_runtime()
        if problems:
            print("check_determinism: collective runtime check FAILED:")
            for p in problems:
                print(f"  {p}")
            return 1
        print("check_determinism: collective runtime OK (schedule "
              "tapes — captured smpi/coll.py comm sequences equal the "
              "mirrored generators at non-power-of-two ranks; tape "
              "runs solo/k=1/pipelined/batched/fault-composed "
              "bit-identical to the host maestro: events, activations "
              "and Kahan clocks, at a >= 3x dispatch advantage; and a "
              "live-captured NAS IS kernel replayed end to end on the "
              "tape path at >= 10x fewer dispatches per collective "
              "step)")
        argv = [a for a in argv if a != "--runtime-collective"]
    if "--quick" in argv:
        problems = quick_checks()
        if problems:
            print("check_determinism: quick checks FAILED:")
            for p in problems:
                print(f"  {p}")
            return 1
        print("check_determinism: quick OK (lint + small-N drain + "
              "batch + pipeline + shard + phase + fault + serve + "
              "resume + collective runtime)")
        return 0
    if "--runtime-phase" in argv:
        problems = check_phase_runtime()
        if problems:
            print("check_determinism: phase runtime check FAILED:")
            for p in problems:
                print(f"  {p}")
            return 1
        print("check_determinism: phase runtime OK (device-resident "
              "mutating phases — compute/comm alternation incl. "
              "forced resumable (bandwidth change) and non-resumable "
              "(deadline'd flow) mutations and the pipelined fleet "
              "variant — bit-identical to the native loop: event "
              "order, timestamps and clocks)")
        argv = [a for a in argv if a != "--runtime-phase"]
    if "--runtime-pipeline" in argv:
        problems = check_pipeline_runtime()
        if problems:
            print("check_determinism: pipeline runtime check FAILED:")
            for p in problems:
                print(f"  {p}")
            return 1
        print("check_determinism: pipeline runtime OK (speculative "
              "pipelined drain — solo depths 1/2 incl. forced "
              "repack/budget mispredicts, and a batched fleet — "
              "bit-identical to the unpipelined superstep path: "
              "event order, timestamps and clocks)")
        argv = [a for a in argv if a != "--runtime-pipeline"]
    if "--runtime-batch" in argv:
        problems = check_batch_runtime()
        if problems:
            print("check_determinism: batch runtime check FAILED:")
            for p in problems:
                print(f"  {p}")
            return 1
        print("check_determinism: batch runtime OK (replicas from a "
              "64-wide mixed fault/sweep fleet bit-identical to solo: "
              "event order and clocks)")
        argv = [a for a in argv if a != "--runtime-batch"]
    if "--runtime-warmstart" in argv:
        problems = check_warmstart_runtime()
        if problems:
            print("check_determinism: warm-start runtime check FAILED:")
            for p in problems:
                print(f"  {p}")
            return 1
        print("check_determinism: warm-start runtime OK (cold vs "
              "warm-started selective bit-identical: event order and "
              "final clocks)")
        argv = [a for a in argv if a != "--runtime-warmstart"]
    if "--runtime-drain" in argv:
        problems = check_drain_runtime()
        if problems:
            print("check_determinism: drain runtime check FAILED:")
            for p in problems:
                print(f"  {p}")
            return 1
        print("check_determinism: drain runtime OK "
              "(superstep K=1 and K=k bit-reproducible, orders agree)")
        argv = [a for a in argv if a != "--runtime-drain"]
    repo_root = argv[1] if len(argv) > 1 else os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    violations = collect_violations(repo_root)
    if not violations:
        print("check_determinism: OK (%s clean — simlint wallclock-rng)"
              % ", ".join(AUDITED_PATHS))
        return 0
    print("check_determinism: nondeterminism sources found "
          "(use utils/rngstream.py and the simulated clock):")
    for path, lineno, text in violations:
        print(f"  {path}:{lineno}: {text}")
    return 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
