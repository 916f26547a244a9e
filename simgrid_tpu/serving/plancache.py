"""Content-addressed AOT plan cache for fleet programs.

Tracing + XLA compilation of the batched superstep program dominates a
campaign process's cold start (hundreds of ms to seconds), and the
batch CLI pays it on EVERY invocation.  This cache compiles each fleet
program once per ``(plan key, program kind, arg shapes/dtypes,
statics)`` signature via JAX's ahead-of-time path —
``jit(fn).lower(*args, **statics).compile()`` — keeps the resulting
executables resident, and (with ``cache_dir``) serializes them through
``jax.experimental.serialize_executable`` so a WARM RESTART of the
serving process loads compiled artifacts from disk and performs zero
XLA traces for repeated keys.

Keying: the plan key (``ScenarioPlan.plan_key`` — topology hash,
layout, dtype, B, superstep, pipeline, mesh, fault_mode) addresses the
scenario content; the signature appended here (concrete arg shapes +
dtypes + static kwargs + jax version + platform + device count) makes
it impossible for a stale or foreign artifact to be invoked on
mismatched inputs — any miss compiles.  An artifact read from disk
that fails to deserialize, or that this backend refuses to execute, is
evicted and recompiled (counted in ``plan_cache_fallbacks``).  A
failure of a FRESHLY compiled executable is raised: it is the device
refusing the program, and the traced jit would only hide it.

opstats counters: ``plan_cache_hits`` (memory or disk),
``plan_cache_misses`` (fresh AOT compile), ``plan_compile_ms``
(monotonic milliseconds spent lowering+compiling — 0 on a fully warm
restart), ``plan_cache_fallbacks``.
"""

from __future__ import annotations

import hashlib
import os
import pickle
import time
from typing import Any, Dict, Optional, Tuple

import jax

from ..ops import opstats

#: bumped when the serialized artifact layout changes
#: (2: records carry the ids of the devices the executable runs on)
_FORMAT_VERSION = 2


def _signature(args, statics: Dict[str, Any]) -> str:
    """Shape/dtype/static signature of one concrete call — part of the
    artifact address, so an executable can only ever be invoked on
    inputs matching the ones it was compiled for."""
    parts = []
    for a in args:
        shape = getattr(a, "shape", None)
        if shape is None:
            parts.append(f"py:{type(a).__name__}:{a!r}")
        else:
            parts.append(f"{tuple(shape)}:{getattr(a, 'dtype', '?')}")
    parts.append(repr(sorted(statics.items())))
    return "|".join(parts)


class PlanCache:
    """Process-wide (and optionally on-disk) cache of AOT-compiled
    fleet executables, shared by every fleet the serving process
    builds.  ``cache_dir=None`` keeps it memory-only (still one
    compile per signature per process); with a directory, artifacts
    are pickled ``serialize_executable`` payloads and warm restarts
    deserialize instead of tracing."""

    def __init__(self, cache_dir: Optional[str] = None):
        self.cache_dir = cache_dir or None
        if self.cache_dir:
            os.makedirs(self.cache_dir, exist_ok=True)
        self._mem: Dict[str, Any] = {}
        #: digests whose resident executable was deserialized from disk
        #: (the only ones ``call`` may recover from)
        self._from_disk: set = set()
        self.hits = 0
        self.misses = 0
        self.disk_hits = 0
        self.fallbacks = 0
        self.compile_ms = 0.0

    # -- addressing --------------------------------------------------------

    def _digest(self, key: str, kind: str, sig: str) -> str:
        backend = jax.default_backend()
        blob = "\0".join([str(_FORMAT_VERSION), key, kind, sig,
                          jax.__version__, backend,
                          str(jax.device_count())])
        return hashlib.sha256(blob.encode("utf-8")).hexdigest()

    def _path(self, digest: str) -> str:
        return os.path.join(self.cache_dir, digest + ".xplan")

    # -- executables -------------------------------------------------------

    def plan(self, key: str) -> "CompiledPlan":
        """A handle binding one plan key to this cache — what
        BatchDrainSim carries as ``plan=``."""
        return CompiledPlan(self, key)

    def _load_disk(self, digest: str):
        if not self.cache_dir:
            return None
        path = self._path(digest)
        if not os.path.exists(path):
            return None
        from jax.experimental import serialize_executable
        with open(path, "rb") as f:
            rec = pickle.load(f)
        if rec.get("format") != _FORMAT_VERSION:
            return None
        # load onto the devices the executable was compiled for: left
        # to its default, deserialize_and_load spreads a one-device
        # program over EVERY visible device and the first call fails
        by_id = {d.id: d for d in jax.devices()}
        if not all(i in by_id for i in rec["devices"]):
            return None
        return serialize_executable.deserialize_and_load(
            rec["payload"], rec["in_tree"], rec["out_tree"],
            execution_devices=[by_id[i] for i in rec["devices"]])

    def _store_disk(self, digest: str, compiled) -> None:
        if not self.cache_dir:
            return
        from jax.experimental import serialize_executable
        payload, in_tree, out_tree = serialize_executable.serialize(
            compiled)
        rec = {"format": _FORMAT_VERSION, "payload": payload,
               "in_tree": in_tree, "out_tree": out_tree,
               "devices": [d.id for d in compiled.runtime_executable()
                           .local_devices()]}
        path = self._path(digest)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            pickle.dump(rec, f)
        os.replace(tmp, path)

    def get_or_compile(self, key: str, kind: str, jitted_fn, args,
                       statics: Dict[str, Any]):
        """The compiled executable for one concrete call signature:
        memory hit, else disk hit (deserialize, no trace), else AOT
        compile (lower+compile, timed into ``plan_compile_ms``) and
        persist."""
        digest = self._digest(key, kind, _signature(args, statics))
        ex = self._mem.get(digest)
        if ex is not None:
            self.hits += 1
            opstats.bump("plan_cache_hits")
            return ex
        try:
            ex = self._load_disk(digest)
        except Exception:
            ex = None  # corrupt/foreign artifact: recompile below
        if ex is not None:
            self._mem[digest] = ex
            self._from_disk.add(digest)
            self.hits += 1
            self.disk_hits += 1
            opstats.bump("plan_cache_hits")
            opstats.bump("plan_cache_disk_hits")
            return ex
        t0 = time.perf_counter()
        ex = jitted_fn.lower(*args, **statics).compile()
        ms = (time.perf_counter() - t0) * 1e3
        self.misses += 1
        self.compile_ms += ms
        opstats.bump("plan_cache_misses")
        opstats.bump("plan_compile_ms", ms)
        self._mem[digest] = ex
        try:
            self._store_disk(digest, ex)
        except Exception:
            pass  # disk persistence is best-effort; serving continues
        return ex

    def call(self, key: str, kind: str, jitted_fn, args,
             statics: Dict[str, Any]):
        """Execute one fleet program through the cache.  An executable
        deserialized from disk that this backend refuses to run (a
        stale or foreign artifact) is evicted — from memory and from
        disk, so a restart recompiles too — and the program is compiled
        afresh.  Whatever a freshly compiled executable raises
        propagates."""
        digest = self._digest(key, kind, _signature(args, statics))
        ex = self.get_or_compile(key, kind, jitted_fn, args, statics)
        try:
            return ex(*args)
        except Exception:
            if digest not in self._from_disk:
                raise
            self._from_disk.discard(digest)
            self._mem.pop(digest, None)
            try:
                os.remove(self._path(digest))
            except OSError:
                pass
            self.fallbacks += 1
            opstats.bump("plan_cache_fallbacks")
        return self.get_or_compile(key, kind, jitted_fn, args,
                                   statics)(*args)

    def stats(self) -> Dict[str, float]:
        return {"plan_cache_hits": self.hits,
                "plan_cache_misses": self.misses,
                "plan_cache_disk_hits": self.disk_hits,
                "plan_cache_fallbacks": self.fallbacks,
                "plan_compile_ms": self.compile_ms}


class CompiledPlan:
    """One plan key bound to a PlanCache — the ``plan=`` handle
    BatchDrainSim routes its jitted programs through."""

    __slots__ = ("cache", "key")

    def __init__(self, cache: PlanCache, key: str):
        self.cache = cache
        self.key = key

    def call(self, kind: str, jitted_fn, args,
             statics: Dict[str, Any]):
        return self.cache.call(self.key, kind, jitted_fn, args,
                               statics)

    def compile(self, kind: str, jitted_fn, args,
                statics: Dict[str, Any]) -> None:
        """Make the executable resident (load or compile) without
        running it — the step a dispatch watchdog keeps outside its
        retry guard."""
        cache = self.cache
        digest = cache._digest(self.key, kind, _signature(args, statics))
        if digest not in cache._mem:
            cache.get_or_compile(self.key, kind, jitted_fn, args, statics)
