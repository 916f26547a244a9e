"""ctypes bridge to the native (C++) exact LMM solver (native/lmm.cc).

Third solver backend next to the exact Python list solver and the JAX
fixpoint: same flatten/solve/scatter handoff as the JAX backend
(lmm_jax.solve_jax), but the solve itself runs in native code — the
host-side floor of the auto dispatch (small live sets stay native-fast,
large ones go to the device; SURVEY.md hard part (e)).

The shared library is built on demand from native/lmm.cc with g++ (no
pip/pybind11 dependency; plain C ABI).  Its file name carries a hash of
the source, so a binary built from other sources is never loaded: when
lmm.cc changes, the next load builds a new one."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Optional

import numpy as np

from .lmm_host import System

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")

_lib = None
_lib_error: Optional[str] = None


def _build_library() -> str:
    """Path of the library built from the lmm.cc that is on disk now,
    compiling it first unless that exact source was built before."""
    src = os.path.join(_NATIVE_DIR, "lmm.cc")
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    path = os.path.join(_NATIVE_DIR, f"libsimgrid_lmm-{digest}.so")
    if not os.path.exists(path):
        # build beside the target and rename: a concurrent loader never
        # sees a half-written library
        tmp = f"{path}.{os.getpid()}.tmp"
        proc = subprocess.run(
            ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-o", tmp,
             src], capture_output=True, text=True)
        if proc.returncode != 0:
            raise OSError(f"g++ failed on {src} "
                          f"(rc={proc.returncode}):\n{proc.stderr}")
        os.replace(tmp, path)
    return path


def load_library():
    """Load (building if needed) the native solver.  Raises when it
    cannot be built or loaded, with the compiler's stderr — the native
    backend was asked for by name, or is needed as the oracle.  The
    ``auto`` dispatch asks :func:`available` instead."""
    global _lib, _lib_error
    if _lib is not None:
        return _lib
    if _lib_error is not None:
        raise RuntimeError(f"native LMM solver unavailable: {_lib_error}")
    try:
        lib = ctypes.CDLL(_build_library())
    except OSError as exc:      # no g++, failed compile, unloadable .so
        _lib_error = str(exc)
        raise RuntimeError(
            f"native LMM solver unavailable: {_lib_error}") from exc
    lib.lmm_solve_coo.restype = ctypes.c_int32
    # raw pointers, not np.ctypeslib.ndpointer: the per-call from_param
    # validation machinery cost ~18s of a 175s Chord run (the solver
    # itself was 10s); callers guarantee dtype/contiguity
    lib.lmm_solve_coo.argtypes = [
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_double,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
    ]
    _lib = lib
    return _lib


def available() -> bool:
    """Whether the native solver can be used — the question the
    ``auto`` dispatch asks before choosing between it and the Python
    list solver.  Anything that names the native backend calls
    :func:`load_library` and gets the reason instead."""
    try:
        load_library()
    except RuntimeError:
        return False
    return True


def solve_coo(e_var, e_cnst, e_w, c_bound, c_fatpipe, v_penalty, v_bound,
              eps: float, n_e: int, n_c: int, n_v: int):
    """Solve a flattened COO system natively; returns (values, remaining,
    usage) over the first n_v / n_c slots."""
    lib = load_library()
    values = np.empty(n_v, np.float64)
    remaining = np.empty(n_c, np.float64)
    usage = np.empty(n_c, np.float64)
    a = (np.ascontiguousarray(e_var[:n_e], np.int32),
         np.ascontiguousarray(e_cnst[:n_e], np.int32),
         np.ascontiguousarray(e_w[:n_e], np.float64),
         np.ascontiguousarray(c_bound[:n_c], np.float64),
         np.ascontiguousarray(c_fatpipe[:n_c], np.uint8),
         np.ascontiguousarray(v_penalty[:n_v], np.float64),
         np.ascontiguousarray(v_bound[:n_v], np.float64))
    lib.lmm_solve_coo(
        n_c, n_v, n_e,
        a[0].ctypes.data, a[1].ctypes.data, a[2].ctypes.data,
        a[3].ctypes.data, a[4].ctypes.data, a[5].ctypes.data,
        a[6].ctypes.data, float(eps),
        values.ctypes.data, remaining.ctypes.data, usage.ctypes.data)
    return values, remaining, usage


def _solve_flat(arrays, eps):
    return solve_coo(
        arrays.e_var, arrays.e_cnst, arrays.e_w, arrays.c_bound,
        arrays.c_fatpipe, arrays.v_penalty, arrays.v_bound, eps,
        arrays.n_elem, arrays.n_cnst, arrays.n_var)


def solve_native(system: System) -> None:
    """Backend entry: flatten host graph, solve natively, scatter back
    (same side-effect contract as lmm_jax.solve_jax)."""
    from .lmm_jax import solve_flattened
    solve_flattened(system, np.float64, _solve_flat)
