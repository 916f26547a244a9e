"""Device-side numerical kernels (JAX/XLA) + their exact host oracles.

f64 is enabled globally: the solver's epsilon semantics (maxmin/precision,
reference maxmin.cpp:12-14) are defined on doubles.  Which dtype a solve
actually runs in is decided per device by ``ops.device.solve_dtype``
(IEEE float64 on CPU, float32 on the TPU, whose float64 is emulated).

Every device path imports this package, so the persistent XLA compile
cache is placed here, once (``compile_cache()`` says where, and why):

* ``JAX_COMPILATION_CACHE_DIR`` wins when it is set — JAX reads it
  itself and no directory is set in code;
* a process pinned to the CPU backend (``JAX_PLATFORMS=cpu``) gets no
  cache: XLA:CPU compiles these programs in well under a second, logs
  two screens of machine-feature warnings per cached load, and a CPU
  executable another machine wrote is the one thing a cache could get
  wrong;
* otherwise the cache lives at ``<checkout>/.jax_cache`` — a fixed
  path, because a temporary or per-process directory starts empty and
  never hits.

Sub-second programs are cached too: a process starts with no compiled
code, the drain drivers dispatch a few dozen small programs beside the
large ones, and a lookup costs less than any compile the TPU does.

The cache's key takes in the programs' metadata
(``jax_compilation_cache_include_metadata_in_key``).  By default JAX
strips it, so two programs that differ only in their op-name paths
share one entry: a cache another commit filled would hand back
executables without this commit's ``jax.named_scope`` names
(``sg.lmm.*``, ``sg.drain.*``) and a device trace would attribute no
time to any pass, in silence.  The price: the key then also holds each
op's source path, line and recorded Python call stack, so an edit that
only moves a line, a checkout at another path or another entry script
compiles once more (43 s for the config-#4 superstep, PERF.md).

Compiles are counted here too: one listener on JAX's monitoring events
turns each step of a first call - the function traced into a jaxpr,
the jaxpr lowered to an MLIR module, the module through the backend
compiler - into an ``xla.trace`` / ``xla.lower`` / ``xla.compile`` span
of :mod:`.opstats`, and counts the last in its ``xla_compiles`` /
``xla_compile_ms`` (the compile step wraps the persistent-cache
lookup, so a hit is a short span whose id starts with ``cached:``;
tracing and lowering are Python seconds that no cache saves).
"""

import os
import threading
from typing import Optional, Tuple

import jax

jax.config.update("jax_enable_x64", True)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _CACHE_SOURCE = "env JAX_COMPILATION_CACHE_DIR"
elif (jax.config.jax_platforms or "").strip().lower() == "cpu":
    _CACHE_SOURCE = "off: process pinned to the cpu backend"
else:
    _CACHE_SOURCE = "default <checkout>/.jax_cache"
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache"))


def compile_cache() -> Tuple[Optional[str], str]:
    """(directory or None, where that choice came from)."""
    return jax.config.jax_compilation_cache_dir, _CACHE_SOURCE


from . import opstats  # noqa: E402

#: JAX's monitoring events (jax 0.9.0: jax._src.dispatch
#: JAXPR_TRACE_EVENT, JAXPR_TO_MLIR_MODULE_EVENT, BACKEND_COMPILE_EVENT;
#: jax._src.compiler): each duration event closes one step of a first
#: call and carries ``fun_name``; the compile one closes every backend
#: compile, persistent-cache lookups included, and the plain event
#: fires inside it when the lookup hit
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_cache_hit = threading.local()


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT_EVENT:
        _cache_hit.seen = True


def _on_duration(event: str, seconds: float, **kw) -> None:
    if event == _COMPILE_EVENT:
        cached = getattr(_cache_hit, "seen", False)
        _cache_hit.seen = False
        opstats.note_xla("xla.compile", seconds,
                         ("cached:" if cached else "")
                         + str(kw.get("fun_name")))
        opstats.bump("xla_compiles")
        opstats.bump("xla_compile_ms", seconds * 1e3)
    elif event == _TRACE_EVENT:
        opstats.note_xla("xla.trace", seconds, str(kw.get("fun_name")))
    elif event == _LOWER_EVENT:
        opstats.note_xla("xla.lower", seconds, str(kw.get("fun_name")))


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_duration)

from .lmm_host import (System, Constraint, Variable, Element, SharingPolicy,  # noqa: E402
                       make_new_maxmin_system, double_update, double_positive,
                       double_equals)
from . import lmm_jax  # noqa: E402

__all__ = ["System", "Constraint", "Variable", "Element", "SharingPolicy",
           "make_new_maxmin_system", "double_update", "double_positive",
           "double_equals", "lmm_jax"]
