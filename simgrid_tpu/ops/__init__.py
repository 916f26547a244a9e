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
"""

import os
from typing import Optional, Tuple

import jax

jax.config.update("jax_enable_x64", True)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

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


from .lmm_host import (System, Constraint, Variable, Element, SharingPolicy,  # noqa: E402
                       make_new_maxmin_system, double_update, double_positive,
                       double_equals)
from . import lmm_jax  # noqa: E402

__all__ = ["System", "Constraint", "Variable", "Element", "SharingPolicy",
           "make_new_maxmin_system", "double_update", "double_positive",
           "double_equals", "lmm_jax"]
