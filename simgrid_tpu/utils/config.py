"""Typed configuration/flag registry.

TPU-native re-design of SimGrid's xbt config system
(reference: /root/reference/src/xbt/config.cpp, flag declarations in
/root/reference/src/simgrid/sg_config.cpp:258-437).  Same capabilities:
typed flags with defaults, aliases, on-set callbacks, ``--cfg=key:value``
command-line parsing and ``help-cfg`` dump — implemented as a plain Python
registry (no C++ needed host-side).
"""

from __future__ import annotations

import sys
from typing import Any, Callable, Dict, List, Optional


class ConfigError(Exception):
    pass


class _Flag:
    __slots__ = ("name", "description", "default", "value", "type", "callback",
                 "aliases", "touched")

    def __init__(self, name: str, description: str, default: Any,
                 callback: Optional[Callable[[Any], None]] = None,
                 aliases: Optional[List[str]] = None):
        self.name = name
        self.description = description
        self.default = default
        self.value = default
        self.type = type(default)
        self.callback = callback
        self.aliases = aliases or []
        # Explicit-set tracking (the reference's isdefault flag,
        # config.cpp:141,171,240): an explicit set that happens to equal the
        # default still counts as touched.
        self.touched = False


_TRUTHY = {"yes", "on", "true", "1"}
_FALSY = {"no", "off", "false", "0"}


class Config:
    """A registry of typed flags (the equivalent of simgrid's sg_cfg_*)."""

    def __init__(self) -> None:
        self._flags: Dict[str, _Flag] = {}
        self._alias: Dict[str, str] = {}

    # -- declaration ------------------------------------------------------
    def declare(self, name: str, description: str, default: Any,
                callback: Optional[Callable[[Any], None]] = None,
                aliases: Optional[List[str]] = None) -> None:
        if name in self._flags:
            # Re-declaration keeps the already-set value (mirrors the
            # reference's idempotent module registration).
            return
        flag = _Flag(name, description, default, callback, aliases)
        self._flags[name] = flag
        for a in flag.aliases:
            self._alias[a] = name

    # -- access -----------------------------------------------------------
    def _resolve(self, name: str) -> _Flag:
        name = self._alias.get(name, name)
        try:
            return self._flags[name]
        except KeyError:
            raise ConfigError(f"Unknown configuration key '{name}' "
                              f"(try help-cfg for the list)") from None

    def get(self, name: str) -> Any:
        return self._resolve(name).value

    def __getitem__(self, name: str) -> Any:
        return self.get(name)

    def set(self, name: str, value: Any) -> None:
        flag = self._resolve(name)
        if isinstance(value, str) and flag.type is not str:
            value = self._parse(flag, value)
        elif flag.type is float and isinstance(value, int):
            value = float(value)
        if not isinstance(value, flag.type) and not (flag.type is float and isinstance(value, int)):
            raise ConfigError(f"Invalid value {value!r} for flag '{flag.name}' "
                              f"of type {flag.type.__name__}")
        flag.value = value
        flag.touched = True
        if flag.callback is not None:
            flag.callback(value)

    def __setitem__(self, name: str, value: Any) -> None:
        self.set(name, value)

    def is_default(self, name: str) -> bool:
        return not self._resolve(name).touched

    def set_default(self, name: str, value: Any) -> None:
        """Change the default (and the value if never explicitly set) — the
        reference's config::set_default used by model initializers."""
        flag = self._resolve(name)
        if not flag.touched:
            self.set(name, value)        # validates the type first
            flag.touched = False         # still counts as a default
        flag.default = value

    @staticmethod
    def _parse(flag: _Flag, text: str) -> Any:
        if flag.type is bool:
            low = text.lower()
            if low in _TRUTHY:
                return True
            if low in _FALSY:
                return False
            raise ConfigError(f"Invalid boolean '{text}' for flag '{flag.name}'")
        if flag.type is int:
            return int(text)
        if flag.type is float:
            return float(text)
        return text

    # -- command line -----------------------------------------------------
    def set_from_string(self, opt: str) -> None:
        """Parse a --cfg= payload: one ``key:value``, or several
        space-separated ones (the reference accepts
        --cfg='a:x b:y c:z')."""
        from . import log as _log
        # A payload with spaces is a multi-option list ONLY if every
        # token's text before its first ':' names a DECLARED flag —
        # otherwise the whole payload is one value that happens to
        # contain spaces and colons (a path list, a URL).
        tokens = [opt]
        if " " in opt:
            parts = opt.split()
            def _known(tok: str) -> bool:
                key = tok.split(":", 1)[0].strip()
                return key in self._flags or key in self._alias
            if all(":" in t and _known(t) for t in parts):
                tokens = parts
        for token in tokens:
            if ":" not in token:
                raise ConfigError(
                    f"Invalid --cfg option '{token}', expected key:value")
            key, value = token.split(":", 1)
            self.set(key.strip(), value.strip())
            # reference simgrid::config logs every CLI change (the tesh
            # oracles pin these lines)
            _log.get_category("xbt_cfg").info(
                "Configuration change: Set '%s' to '%s'"
                % (key.strip(), value.strip()))

    def parse_argv(self, argv: List[str]) -> List[str]:
        """Consume --cfg=... / --log=... / --help-cfg from argv,
        returning the rest.  Log controls apply FIRST (like the
        reference's early log_init) so the configuration-change lines
        already use the requested layout."""
        from . import log as _log
        for arg in argv:
            if arg.startswith("--log="):
                _log.apply_control(arg[len("--log="):])
        remaining: List[str] = []
        for arg in argv:
            if arg.startswith("--cfg="):
                self.set_from_string(arg[len("--cfg="):])
            elif arg.startswith("--log="):
                pass
            elif arg == "--help-cfg":
                self.dump(sys.stdout)
            else:
                remaining.append(arg)
        return remaining

    def dump(self, out) -> None:
        for name in sorted(self._flags):
            f = self._flags[name]
            out.write(f"   {name}: {f.description} (default: {f.default!r})\n")


#: Process-wide configuration registry (mirrors simgrid_config).
config = Config()


def declare_flag(name: str, description: str, default: Any,
                 callback: Optional[Callable[[Any], None]] = None,
                 aliases: Optional[List[str]] = None) -> None:
    config.declare(name, description, default, callback, aliases)


# ---------------------------------------------------------------------------
# Core solver / kernel flags, same key names as the reference
# (sg_config.cpp:258-437, maxmin.cpp:12-14).
# ---------------------------------------------------------------------------
declare_flag("maxmin/precision",
             "Numerical precision used when updating simulation variables",
             1e-5, aliases=["maxmin/epsilon"])
declare_flag("surf/precision",
             "Numerical precision used when comparing simulated times",
             1e-5)
declare_flag("path",
             "Lookup path for inclusions in platform and deployment "
             "XML files",
             "./")
declare_flag("maxmin/concurrency-limit",
             "Maximum number of concurrent variables per resource (-1: none)",
             -1)
declare_flag("host/model", "Host model to use", "default")
declare_flag("cpu/model", "CPU model to use", "Cas01")
declare_flag("network/model", "Network model to use", "LV08")
declare_flag("storage/model", "Storage model to use", "default")
declare_flag("cpu/optim", "CPU optimization mode (Lazy/TI/Full)", "Lazy")
declare_flag("network/optim", "Network optimization mode (Lazy/Full)", "Lazy")
declare_flag("cpu/maxmin-selective-update",
             "Update the constraint set selectively for CPU", False)
declare_flag("network/maxmin-selective-update",
             "Update the constraint set selectively for network", False)
declare_flag("network/crosstraffic",
             "Model cross-traffic (bidirectional flows interfere)", True)
declare_flag("network/TCP-gamma",
             "Maximum TCP window size (bytes)", 4194304.0)
# Global defaults come from the LV08 model (sg_config.cpp:270-279); the
# plain CM02 init resets them to 1.0/1.0/0.0, SMPI/IB override weight-S
# only (network_smpi.cpp:24-31, network_ib.cpp init).
declare_flag("network/latency-factor",
             "Multiplier for link latencies", 13.01)
declare_flag("network/bandwidth-factor",
             "Multiplier for link bandwidths", 0.97)
declare_flag("network/weight-S",
             "RTT cost correction added per link (LV08: 20537)", 20537.0)
declare_flag("network/loopback-bw", "Default loopback bandwidth", 498000000.0)
declare_flag("network/mtu",
             "Packet size (bytes) for the packet-level network model",
             1500.0)
declare_flag("network/loopback-lat", "Default loopback latency", 0.000015)
declare_flag("lmm/backend",
             "Max-min solver backend: list (exact host, Python), native "
             "(exact host, C++), jax (vectorized, TPU/CPU), auto (native "
             "below lmm/jax-threshold variables, jax above)", "auto")
declare_flag("lmm/jax-threshold",
             "Minimum live variable count before 'auto' switches the solve "
             "to the JAX backend", 512)
declare_flag("lmm/dtype",
             "JAX solver dtype: float64, float32, or auto (float64 where "
             "the device's float64 is IEEE double — the CPU backend — "
             "and float32 on the TPU, whose float64 is an emulated f32 "
             "pair).  An explicit float64 on such a device is an error, "
             "not a demotion (ops/device.py)", "auto")
declare_flag("lmm/layout",
             "Device solver element layout: coo (scatter/segment ops), "
             "ell (dense padded rows — accelerator-native, no scatters), "
             "auto (ell on accelerators when the graph is not too skewed)",
             "auto")
declare_flag("lmm/rounds",
             "JAX solver saturation-round strategy: global (one bottleneck "
             "level per round, the reference's sequential order) or local "
             "(fix every local-minimum constraint per round; exact because "
             "rou levels only increase, and far fewer device rounds)",
             "local")
declare_flag("lmm/compact",
             "Repack the device element list between solver chunks, "
             "dropping elements of already-fixed variables: on, off, or "
             "auto (on for the COO layout on CPU backends, where the "
             "host round-trip is free).  COO-only — combine with "
             "lmm/layout:coo on accelerators — and skipped below a few "
             "thousand elements where repacking costs more than it "
             "saves.  Bit-identical: dead elements contribute exact "
             "identities (0.0 to the scatter-adds and maxes, inf to "
             "the min-reductions)", "auto")
declare_flag("lmm/chain",
             "Device-resident active-set compaction for the ELL/vc "
             "solver path: chain jitted solve stages at halving static "
             "shapes with no host sync between them (one fetch per "
             "solve).  on, off, or auto (accelerators only — the CPU "
             "backend compacts host-side via lmm/compact instead)",
             "auto")
declare_flag("lmm/warm-start",
             "Selective-update solves on the device backend: off "
             "(legacy: re-flatten the modified constraint subset and "
             "cold-solve it each time), cold (device-resident full "
             "arrays, cold fixpoint restart every solve), on/auto "
             "(warm-started restarts: only the modified component "
             "re-enters the fixpoint, untouched components keep their "
             "previous solution — exact because the max-min solution "
             "decomposes by connected component).  Combine with "
             "network/maxmin-selective-update (or cpu/...) to get "
             "incremental device solves in mutating phases", "auto")
declare_flag("lmm/delta-upload",
             "Ship System mutations to the device-resident solver "
             "arrays as ONE indexed scatter payload per solve (bytes "
             "scale with touched slots) instead of re-uploading every "
             "dirty field wholesale: on, off, or auto (on whenever the "
             "warm-start device path serves the solve).  Off keeps "
             "per-field copy-on-write refreshes — the bench baseline "
             "and the escape hatch", "auto")
declare_flag("lmm/strict",
             "Abort on a failed device LMM solve (non-convergence, stall "
             "or non-finite rates) instead of gracefully degrading to the "
             "exact host solver for that solve", False)
declare_flag("lmm/pad",
             "Static-shape padding policy for device solver arrays: "
             "pow2 (power-of-two buckets — few XLA recompiles as a "
             "simulation's live system grows/shrinks, up to 2x padded "
             "volume) or tight (multiples of 4096 and exact ELL row "
             "widths — per-element device cost tracks the real system; "
             "right for one-shot solves of big fixed systems, wrong "
             "for hot simulation loops where every new shape is a "
             "multi-second XLA compile)", "pow2")
declare_flag("drain/fastpath",
             "Delegate pure-drain phases (every started flow past its "
             "latency phase, no deadlines, no profile event before the "
             "next completion) to the device-resident superstep "
             "executor: batches of advances run in one dispatch with "
             "event ordering preserved.  auto/on require a JAX-capable "
             "lmm/backend and at least drain/min-flows started flows; "
             "off disables the fast path", "auto")
declare_flag("drain/superstep",
             "Advances per device dispatch in the drain fast path "
             "(the K of the superstep executor; amortized host syncs "
             "are ~1/K per advance)", 16)
declare_flag("drain/min-flows",
             "Minimum started network flows before the drain fast "
             "path engages (below it the generic per-advance path is "
             "cheaper than plan bookkeeping)", 4096)
declare_flag("drain/pipeline",
             "Speculative supersteps kept in flight by the pipelined "
             "drain executors (the depth D of DrainSim/BatchDrainSim "
             "pipelining; the engine fast path keeps one token in "
             "flight whenever D > 0): while the host processes "
             "completion ring N, superstep N+1 already executes on "
             "the device, hiding the dispatch round trip.  Results "
             "are bit-identical to 0 (synchronous) — a mispredicted "
             "speculation is discarded and replayed from the "
             "committed state", 1)
declare_flag("drain/transitions",
             "Absorb recognizable actor transitions (latency wakes, "
             "new flows on existing routes, bound/weight/penalty "
             "changes, engine-driven partial advances) into a live "
             "drain plan as indexed device scatters instead of "
             "discarding it: the ArrayView mutation census becomes a "
             "resumable-vs-invalidating classifier and compute/comm "
             "alternation stays on the superstep path.  auto/on "
             "enable it whenever drain/fastpath engages; off restores "
             "the invalidate-on-any-mutation behavior", "auto")
declare_flag("faults/tape",
             "How campaign fleets realize per-replica fault schedules "
             "(parallel.campaign): on compiles each seeded "
             "FaultCampaign into a device-resident event tape the "
             "superstep drain consults between advances — link "
             "capacities flip mid-drain at exact schedule dates, "
             "bit-identical to solo Profile injection; static folds "
             "the schedule into time-averaged capacity multipliers "
             "(FaultCampaign.mean_availability, the pre-tape "
             "behavior); off ignores the fault dimension entirely",
             "on")
declare_flag("drain/done-eps",
             "Relative completion threshold of the f32 drain "
             "executor: a flow retires when its remainder falls to "
             "done-eps * size (reference sg_maxmin_precision "
             "semantics; keeps chip-precision ties in the f64 tie "
             "groups).  f64 drains use the engine's absolute "
             "maxmin*surf precision instead", 1e-4)
declare_flag("lmm/unroll",
             "Unroll the device fixpoint into straight-line XLA instead "
             "of lax.while_loop: on, off, or auto (off on every backend: "
             "while_loop gathers lower fine on the TPU and unrolling only "
             "multiplies compile time; on is the escape hatch for a "
             "backend that serializes gathers inside loops)",
             "auto")
declare_flag("serve/batch",
             "Resident fleet width of the always-on campaign service "
             "(serving.service.CampaignService): queued scenarios "
             "fill up to this many lanes; lanes freed by completed "
             "replicas are revived mid-flight by admission batching",
             16)
declare_flag("serve/plan-cache",
             "Directory for the serving AOT plan cache "
             "(serving.plancache): compiled fleet executables are "
             "serialized here so warm restarts skip XLA tracing "
             "entirely; empty = in-memory caching only", "")
declare_flag("serve/surrogate",
             "Surrogate triage for the campaign service: on answers "
             "tight-interval queries from the ridge+conformal "
             "predictor (exact=True always bypasses), off sends every "
             "query to the device path", "on")
declare_flag("serve/surrogate-min-corpus",
             "Completed rows required before the serving surrogate "
             "makes its first fit (split-conformal calibration needs "
             "a held-out stripe)", 24)
declare_flag("serve/surrogate-rel-tol",
             "Maximum conformal-interval width, relative to the "
             "predicted clock, the surrogate will answer at; wider "
             "intervals escalate the query to exact device "
             "simulation", 0.1)
declare_flag("serve/surrogate-confidence",
             "Conformal coverage level of surrogate answers (the "
             "interval quantile over held-out absolute residuals)",
             0.9)
declare_flag("smpi/rma-fast-atomics",
             "Linearize RMA atomic reads (get/fetch_op/get_accumulate/"
             "cas) immediately at the origin when all its outstanding "
             "ops to the target have been applied — sound under the "
             "MPI_WIN_UNIFIED memory model and the kernel's atomic "
             "scheduling rounds, and removes the simulated round trip "
             "(set false for strict arrival-time application)", True)
declare_flag("contexts/stack-size", "Actor stack size (bytes)", 131072)
declare_flag("contexts/factory", "Actor context factory (thread)", "thread")
declare_flag("tracing", "Enable tracing", False)
declare_flag("tracing/filename", "Trace output file", "simgrid.trace")
declare_flag("tracing/format", "Trace format (Paje|TI)", "Paje")
declare_flag("tracing/platform", "Trace platform resources", False)
declare_flag("tracing/actor", "Trace actor behavior", False)
declare_flag("tracing/uncategorized",
             "Trace uncategorized resource usage", False)
declare_flag("tracing/smpi", "Trace SMPI ranks", False)
declare_flag("tracing/smpi/computing", "Trace SMPI computing states", False)
declare_flag("smpi/async-small-thresh",
             "Maximum size of messages sent over the eager (async) protocol",
             0)
declare_flag("smpi/send-is-detached-thresh",
             "Threshold under which MPI_Send is done in a detached manner",
             65536)
declare_flag("smpi/host-speed",
             "Speed of the host running the simulation (flop/s)", 20000.0)
declare_flag("smpi/os", "Overhead of a send (size-dependent segments)", "0:0:0:0:0")
declare_flag("smpi/or", "Overhead of a receive", "0:0:0:0:0")
declare_flag("smpi/ois", "Overhead of an isend", "0:0:0:0:0")
declare_flag("smpi/bw-factor", "Piecewise bandwidth factors size:factor;...",
             "65472:0.940694;15424:0.697866;9376:0.58729;5776:1.08739;3484:0.77493;"
             "1426:0.608902;732:0.341987;257:0.338112;0:0.812084")
declare_flag("smpi/lat-factor", "Piecewise latency factors size:factor;...",
             "65472:11.6436;15424:3.48845;9376:2.59299;5776:2.18796;3484:1.88101;"
             "1426:1.61075;732:1.9503;257:1.95341;0:2.01467")
declare_flag("smpi/IB-penalty-factors",
             "InfiniBand penalty factors beta_s;beta_e;gamma", "0.965;0.925;1.35")
declare_flag("smpi/simulate-computation",
             "Simulate the computation of the application", True)
declare_flag("smpi/cpu-threshold",
             "Minimal computation time (s) not discarded", 1e-6)
declare_flag("smpi/coll-selector", "Collective algorithm selector", "default")
declare_flag("model-check/reduction", "DPOR reduction (none|dpor)", "dpor")
declare_flag("model-check/max-depth", "Maximal exploration depth", 1000)
declare_flag("model-check/send-determinism",
             "Check send-determinism only: abort the exploration as "
             "soon as any actor's send pattern diverges (reference "
             "_sg_mc_send_determinism)", False)
declare_flag("model-check/communications-determinism",
             "Classify send- AND recv-determinism per actor over the "
             "whole exploration, aborting only when an actor loses "
             "both (reference _sg_mc_comms_determinism)", True)
declare_flag("precision-tracking/jax",
             "Tolerance used when cross-checking JAX solver results", 1e-9)
