"""What the default JAX device is, and which solver dtype it can run.

Facts about the one accelerator this repo targets, taken on a TPU v5e
(jax 0.9.0 / libtpu 0.0.34) and re-checked by ``chip_smoke.py``'s
``dtypes`` leg on every run:

* XLA:TPU has no float64.  Under ``jax_enable_x64`` it rewrites f64
  into pairs of f32: ~49 mantissa bits, the f32 exponent range (1e200
  becomes inf), host<->device transfers that lose low bits, and no
  f64<->i64 ``bitcast_convert_type`` (so ``lmm_drain._rounded_product``
  does not compile in f64 there).  The event-order contract is stated
  against an IEEE-double oracle, so an f64 solve on such a device is a
  different simulation, not a slower one: it is refused by name at
  construction time, never run and never silently demoted.
* f32 add/mul are IEEE; f32 divide is not correctly rounded (within
  ~1.5 ulp of numpy), so f32 rates and dates agree with a CPU f32 run
  to tolerance, not bit for bit.  XLA:TPU does not contract
  ``rem - rate*dt`` into an FMA; the integer detour of
  ``_rounded_product`` compiles there and changes nothing.
* f32 programs keep their f64 spine (base clocks, fault-tape dates,
  payload vectors).  There the pair arithmetic only has to resolve
  finer than f32, which it does; i64 is emulated exactly.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

import jax

#: whether the platform's float64 is IEEE double.  A platform that is
#: not listed is an error, not a default: run chip_smoke.py on it and
#: record what its ``dtypes`` leg measured.
_F64_IS_IEEE = {"cpu": True, "tpu": False}


def default_platform() -> str:
    """Platform of the device every un-pinned dispatch lands on.  No
    fallback: a backend that fails to initialize raises here."""
    return jax.devices()[0].platform


def f64_is_ieee(platform: Optional[str] = None) -> bool:
    if platform is None:
        platform = default_platform()
    if platform not in _F64_IS_IEEE:
        raise ValueError(
            f"no float64 record for JAX platform {platform!r} (known: "
            f"{sorted(_F64_IS_IEEE)}); run chip_smoke.py there and add "
            f"what its dtypes leg measures to ops/device.py")
    return _F64_IS_IEEE[platform]


def solve_dtype(requested, what: str, device=None) -> np.dtype:
    """Resolve a solver dtype request for ``device`` (default device
    when None).  ``None``/``"auto"`` picks float64 where it is IEEE and
    float32 elsewhere; an explicit float64 on a device without IEEE
    doubles raises, naming ``what`` (the flag or argument that asked
    for it)."""
    auto = requested is None or (isinstance(requested, str)
                                 and requested == "auto")
    if not auto:
        dtype = np.dtype(requested)
        if dtype not in (np.float32, np.float64):
            raise ValueError(f"{what}: unknown solver dtype {requested!r} "
                             "(expected auto, float64 or float32)")
        if dtype == np.float32:
            return dtype
    platform = (device.platform if device is not None
                else default_platform())
    if f64_is_ieee(platform):
        return np.dtype(np.float64)
    if not auto:
        raise ValueError(
            f"{what}: float64 cannot run on the {platform} backend — its "
            f"float64 is an emulated f32 pair (not IEEE double, see "
            f"simgrid_tpu/ops/device.py).  Use float32 or auto there, or "
            f"run this on the CPU backend (JAX_PLATFORMS=cpu)")
    return np.dtype(np.float32)
