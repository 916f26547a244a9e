"""No fallback hides the device: only the solver's own SolveError
degrades to the host; a JAX runtime fault (compile refusal, OOM, lost
chip — also a RuntimeError) propagates from every handler that used to
swallow it.  And the decisions the code takes about the device
(ops/device.py) are taken by name, at construction time."""

import hashlib
import os

import numpy as np
import pytest

import jax

from simgrid_tpu.ops import device, lmm_batch, lmm_jax, lmm_native
from simgrid_tpu.ops import make_new_maxmin_system
from simgrid_tpu.ops.lmm_drain import DrainSim
from simgrid_tpu.parallel.campaign import ScenarioPlan, ScenarioSpec
from simgrid_tpu.serving.plancache import PlanCache
from simgrid_tpu.utils.config import config

DEVICE_FAULT = jax.errors.JaxRuntimeError(
    "RESOURCE_EXHAUSTED: injected device fault")


def _system():
    s = make_new_maxmin_system(False)
    s.solve_fn = lmm_jax.solve_jax
    c = s.constraint_new(None, 10.0)
    variables = [s.variable_new(None, 1.0) for _ in range(3)]
    for v in variables:
        s.expand(c, v, 1.0)
    return s, variables


def _raiser(exc):
    def raise_it(*args, **kwargs):
        raise exc
    return raise_it


class TestSolveJax:
    def test_device_fault_propagates_with_strict_off(self, monkeypatch):
        assert issubclass(jax.errors.JaxRuntimeError, RuntimeError)
        monkeypatch.setattr(lmm_jax, "solve_flattened",
                            _raiser(DEVICE_FAULT))
        config["lmm/strict"] = False
        s, _ = _system()
        before = lmm_jax.get_fallback_count()
        with pytest.raises(jax.errors.JaxRuntimeError,
                           match="injected device fault"):
            s.solve()
        assert lmm_jax.get_fallback_count() == before

    def test_solver_failure_still_degrades_to_host(self, monkeypatch):
        monkeypatch.setattr(
            lmm_jax, "solve_arrays",
            _raiser(lmm_jax.SolveError("LMM JAX solve stalled")))
        config["lmm/strict"] = False
        s, variables = _system()
        before = lmm_jax.get_fallback_count()
        s.solve()
        assert lmm_jax.get_fallback_count() == before + 1
        assert [v.value for v in variables] == pytest.approx([10 / 3] * 3)

    def test_strict_raises_the_solver_failure(self, monkeypatch):
        monkeypatch.setattr(lmm_jax, "solve_arrays",
                            _raiser(lmm_jax.SolveError("stalled")))
        config["lmm/strict"] = True
        s, _ = _system()
        with pytest.raises(lmm_jax.SolveError):
            s.solve()


def _tiny_plan():
    e_var = np.repeat(np.arange(8, dtype=np.int32), 2)
    e_cnst = (np.arange(16, dtype=np.int32) * 3 + 1) % 4
    return ScenarioPlan(e_var, e_cnst, np.ones(16), 2.0 + np.arange(4),
                        1.0 + np.arange(8) % 5, superstep=2)


class TestDrainHandlers:
    def test_solo_records_solver_failures_only(self, monkeypatch):
        plan, spec = _tiny_plan(), ScenarioSpec(seed=0)
        monkeypatch.setattr(DrainSim, "run",
                            _raiser(lmm_jax.SolveError("drain stalled")))
        assert plan.solo(spec).error == "drain stalled"
        monkeypatch.setattr(DrainSim, "run", _raiser(DEVICE_FAULT))
        with pytest.raises(jax.errors.JaxRuntimeError):
            plan.solo(spec)

    def test_drain_errors_are_solve_errors(self):
        """A drain in which no flow holds bandwidth raises the class
        the engine fast path and ScenarioPlan.solo catch."""
        sim = DrainSim(np.array([0], np.int32), np.array([0], np.int32),
                       np.ones(1), np.zeros(1), np.ones(1),
                       dtype=np.float64, superstep=2)
        with pytest.raises(lmm_jax.SolveError, match="stalled"):
            sim.run()


class TestPlanCache:
    ARGS = (np.arange(4.0),)

    @staticmethod
    def _fn():
        return jax.jit(lambda x: x * 2.0)

    def test_fresh_executable_failure_is_raised(self, monkeypatch):
        cache = PlanCache(None)
        monkeypatch.setattr(cache, "get_or_compile",
                            lambda *a, **k: _raiser(DEVICE_FAULT))
        with pytest.raises(jax.errors.JaxRuntimeError):
            cache.call("k", "prog", self._fn(), self.ARGS, {})
        assert cache.fallbacks == 0

    def test_stale_disk_artifact_is_recompiled(self, tmp_path):
        fn = self._fn()
        PlanCache(str(tmp_path)).call("k", "prog", fn, self.ARGS, {})
        warm = PlanCache(str(tmp_path))
        ex = warm.get_or_compile("k", "prog", fn, self.ARGS, {})
        assert warm.disk_hits == 1
        digest = next(iter(warm._from_disk))
        warm._mem[digest] = _raiser(DEVICE_FAULT)    # the artifact went bad
        out = warm.call("k", "prog", fn, self.ARGS, {})
        np.testing.assert_array_equal(np.asarray(out), ex(*self.ARGS))
        assert warm.fallbacks == 1 and warm.misses == 1
        assert not warm._from_disk


class TestWatchdog:
    def test_compile_refusal_is_not_retried(self):
        """Under a watchdog the compile runs outside the guard: what
        the compiler refuses is raised as it is — no retry, no
        DispatchExhausted for the service to answer solo."""
        plan = _tiny_plan()
        dog = lmm_batch.DispatchWatchdog()
        sim = plan.executor([ScenarioSpec(seed=0)], watchdog=dog)

        class Refused:
            @staticmethod
            def lower(*args, **statics):
                raise DEVICE_FAULT

        with pytest.raises(jax.errors.JaxRuntimeError):
            sim._call_plan("refused", Refused, (np.zeros(2),), {})
        assert dog.retries == dog.exhausted == 0

    def test_only_runtime_errors_are_retried(self):
        dog = lmm_batch.DispatchWatchdog()
        with pytest.raises(TypeError):
            dog.guard(_raiser(TypeError("a bug, not a device")))
        assert dog.retries == 0


class _Dev:
    def __init__(self, platform):
        self.platform = platform


class TestSolveDtype:
    def test_auto_follows_the_device(self):
        for asked in (None, "auto"):
            assert device.solve_dtype(asked, "x", _Dev("cpu")) == np.float64
            assert device.solve_dtype(asked, "x", _Dev("tpu")) == np.float32
        assert device.solve_dtype("float32", "x", _Dev("cpu")) == np.float32
        assert device.solve_dtype(None, "x") == np.float64     # tests: cpu

    def test_float64_on_the_tpu_is_refused_by_name(self):
        with pytest.raises(ValueError, match=r"lmm/dtype: float64 cannot"):
            device.solve_dtype("float64", "lmm/dtype", _Dev("tpu"))
        e_var = np.zeros(2, np.int32)
        with pytest.raises(ValueError, match=r"DrainSim\(dtype=\)"):
            DrainSim(e_var, e_var, np.ones(2), np.ones(1), np.ones(1),
                     dtype=np.float64, device=_Dev("tpu"))

    def test_unknown_platform_and_dtype_are_errors(self):
        with pytest.raises(ValueError, match="no float64 record"):
            device.solve_dtype(None, "x", _Dev("gpu"))
        with pytest.raises(ValueError, match="unknown solver dtype"):
            device.solve_dtype("float16", "x", _Dev("cpu"))

    def test_plan_defaults_resolve_per_device(self):
        plan = _tiny_plan()
        assert plan.dtype == np.float64 and plan.eps == 1e-9
        assert config["lmm/dtype"] == "auto"


class TestNativeBuild:
    @pytest.fixture
    def scratch_native(self, tmp_path, monkeypatch):
        monkeypatch.setattr(lmm_native, "_NATIVE_DIR", str(tmp_path))
        monkeypatch.setattr(lmm_native, "_lib", None)
        monkeypatch.setattr(lmm_native, "_lib_error", None)
        return tmp_path

    def test_failed_build_raises_with_the_compilers_words(
            self, scratch_native):
        (scratch_native / "lmm.cc").write_text("this is not C++;\n")
        assert not lmm_native.available()
        with pytest.raises(RuntimeError, match="g\\+\\+ failed"):
            lmm_native.load_library()
        with pytest.raises(RuntimeError, match="error"):
            lmm_native.solve_coo(*[np.zeros(1)] * 7, 1e-5, 1, 1, 1)

    def test_library_name_follows_the_source(self, scratch_native):
        src = os.path.join(os.path.dirname(lmm_native.__file__),
                           "..", "..", "native", "lmm.cc")
        text = open(src).read()
        (scratch_native / "lmm.cc").write_text(text)
        first = lmm_native._build_library()
        assert hashlib.sha256(text.encode()).hexdigest()[:16] in first
        assert lmm_native._build_library() == first       # built once
        (scratch_native / "lmm.cc").write_text(text + "\n// edited\n")
        assert lmm_native._build_library() != first       # never stale
