"""proglint unit tests: per-rule fixture programs (one violation
fires, the disciplined counterpart stays clean), the registry staging
path over the real kernel programs, and the CLI exit-code contract.

Fixtures are tiny jitted programs registered ad hoc through
ProgramSpec — the same staging path (``jit().trace()`` / ``.lower()``)
the real registry uses, so what fires here fires on the tree."""

import os
import sys

import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

jax = pytest.importorskip("jax")
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from simgrid_tpu.analysis.prog import (ProgramContract,  # noqa: E402
                                       ProgramSpec, iter_programs)
from simgrid_tpu.analysis.prog.rules import (ALL_PROG_RULE_IDS,  # noqa: E402
                                             lint_program,
                                             lint_programs)

F64 = ("float64", "int64", "int32", "bool")
F32 = ("float32", "int32", "bool")


def spec_of(fn, contract, make, name="fixture/prog", jit_kwargs=None):
    jitted = jax.jit(fn, **(jit_kwargs or {}))
    return ProgramSpec(name=name, jitted=jitted, program=fn,
                       contract=contract, make=make)


def rules_of(findings, rule):
    return [f for f in findings if f.rule == rule]


def vec(scale, dtype=np.float64):
    n = 4 * scale
    return (np.arange(n, dtype=dtype) + 1.0,)


# -- dtype-flow ----------------------------------------------------------

class TestDtypeFlow:
    def test_f64_leak_in_f32_program_fires(self):
        def prog(x):
            # the classic weak-scalar leak: an f64 constant promotes
            # the f32 solve state
            return x * jnp.float64(2.0)

        spec = spec_of(prog, ProgramContract(
            solve_dtype="float32", allowed_dtypes=F32),
            lambda s: (vec(s, np.float32), {}))
        fs = rules_of(lint_program(spec), "dtype-flow")
        assert fs, "f64 leak in an f32 program must fire"
        assert any("float64" in f.message for f in fs)

    def test_allowlisted_f64_clock_pair_is_clean(self):
        def prog(x, clk):
            # f64 rides along (the Kahan clock pair) but never mixes
            # into the f32 math without an explicit convert
            return x * jnp.float32(2.0), clk + jnp.float64(0.5)

        spec = spec_of(prog, ProgramContract(
            solve_dtype="float32",
            allowed_dtypes=F32 + ("float64",),
            dtype_why={"float64": "Kahan clock pair"}),
            lambda s: (vec(s, np.float32)
                       + (np.zeros(2, np.float64),), {}))
        assert rules_of(lint_program(spec), "dtype-flow") == []

    def test_implicit_promotion_fires_explicit_convert_clean(self):
        def leaky(x, clk):
            return x + clk                       # f32 + f64: implicit

        def disciplined(x, clk):
            return x + clk.astype(jnp.float32)   # explicit convert

        contract = ProgramContract(
            solve_dtype="float32",
            allowed_dtypes=F32 + ("float64",),
            dtype_why={"float64": "clock"})
        make = lambda s: (vec(s, np.float32)  # noqa: E731
                          + (np.zeros(4 * s, np.float64),), {})
        assert rules_of(lint_program(spec_of(leaky, contract, make)),
                        "dtype-flow")
        assert rules_of(
            lint_program(spec_of(disciplined, contract, make)),
            "dtype-flow") == []


# -- hidden-transfer -----------------------------------------------------

class TestHiddenTransfer:
    def test_grown_output_surface_fires(self):
        def prog(x):
            return x * 2.0, x + 1.0   # 2 outputs, contract pins 1

        spec = spec_of(prog, ProgramContract(
            solve_dtype="float64", allowed_dtypes=F64,
            expected_outputs=1),
            lambda s: (vec(s), {}))
        fs = rules_of(lint_program(spec), "hidden-transfer")
        assert any(f.snippet == "outputs:2" for f in fs)

    def test_matching_surface_is_clean(self):
        def prog(x):
            return x * 2.0

        spec = spec_of(prog, ProgramContract(
            solve_dtype="float64", allowed_dtypes=F64,
            expected_outputs=1),
            lambda s: (vec(s), {}))
        assert rules_of(lint_program(spec), "hidden-transfer") == []

    def test_host_callback_custom_call_fires(self):
        def prog(x):
            y = jax.pure_callback(
                lambda a: np.asarray(a) * 2.0,
                jax.ShapeDtypeStruct(x.shape, x.dtype), x)
            return y

        spec = spec_of(prog, ProgramContract(
            solve_dtype="float64", allowed_dtypes=F64),
            lambda s: (vec(s), {}))
        fs = rules_of(lint_program(spec), "hidden-transfer")
        assert any("custom_call" in f.snippet for f in fs), \
            "a host callback must surface as a hidden transfer"


# -- fma-pinning ---------------------------------------------------------

class TestFmaPinning:
    def test_contractible_mul_sub_fires(self):
        def prog(rem, rate, dt):
            return rem - rate * dt     # the exact pattern XLA fuses

        spec = spec_of(prog, ProgramContract(
            solve_dtype="float64", allowed_dtypes=F64,
            fma_pinned=True),
            lambda s: (vec(s) + vec(s) + vec(s), {}))
        snippets = {f.snippet
                    for f in rules_of(lint_program(spec),
                                      "fma-pinning")}
        assert "contractible-mul-sub" in snippets
        assert "bitcast-detour-missing" in snippets

    def test_bitcast_detour_is_clean(self):
        def prog(rem, rate, dt):
            # _rounded_product's int-bitcast detour: the product is
            # materialized through a bitcast round trip, so the sub
            # no longer consumes a raw mul
            prod = rate * dt
            bits = lax.bitcast_convert_type(prod, jnp.int64)
            pinned = lax.bitcast_convert_type(bits, prod.dtype)
            return rem - pinned

        spec = spec_of(prog, ProgramContract(
            solve_dtype="float64", allowed_dtypes=F64,
            fma_pinned=True),
            lambda s: (vec(s) + vec(s) + vec(s), {}))
        assert rules_of(lint_program(spec), "fma-pinning") == []

    def test_unpinned_contract_skips(self):
        def prog(rem, rate, dt):
            return rem - rate * dt

        spec = spec_of(prog, ProgramContract(
            solve_dtype="float64", allowed_dtypes=F64,
            fma_pinned=False),
            lambda s: (vec(s) + vec(s) + vec(s), {}))
        assert rules_of(lint_program(spec), "fma-pinning") == []


# -- donation ------------------------------------------------------------

class TestDonation:
    CONTRACT = ProgramContract(solve_dtype="float64",
                               allowed_dtypes=F64,
                               donated=("carry",))

    @staticmethod
    def _prog(carry, delta):
        return carry + delta, delta * 2.0

    def test_non_donated_carry_fires(self):
        spec = spec_of(self._prog, self.CONTRACT,
                       lambda s: (vec(s) + vec(s), {}))
        fs = rules_of(lint_program(spec), "donation")
        assert any(f.snippet == "not-donated:carry" for f in fs)

    def test_donated_carry_is_clean(self):
        spec = spec_of(self._prog, self.CONTRACT,
                       lambda s: (vec(s) + vec(s), {}),
                       jit_kwargs=dict(donate_argnames=("carry",)))
        assert rules_of(lint_program(spec), "donation") == []

    def test_unknown_param_name_fires(self):
        contract = ProgramContract(solve_dtype="float64",
                                   allowed_dtypes=F64,
                                   donated=("no_such_arg",))
        spec = spec_of(self._prog, contract,
                       lambda s: (vec(s) + vec(s), {}))
        fs = rules_of(lint_program(spec), "donation")
        assert any("missing-param" in f.snippet for f in fs)


# -- retrace-surface -----------------------------------------------------

class TestRetraceSurface:
    def test_shape_specialized_closure_fires(self):
        def prog(x):
            # the shape-specialized closure: a host table rebuilt
            # from the (static) input geometry at every trace — it
            # lowers as a closed-over constant whose shape tracks
            # the geometry, so every new system size recompiles
            table = np.linspace(0.0, 1.0, x.shape[0])
            return x + jnp.asarray(table)

        spec = spec_of(prog, ProgramContract(
            solve_dtype="float64", allowed_dtypes=F64),
            lambda s: (vec(s), {}))
        fs = rules_of(lint_program(spec), "retrace-surface")
        assert fs, "a geometry-tracking closure constant must fire"

    def test_argument_passed_table_is_clean(self):
        def prog(x, table):
            return x + table

        spec = spec_of(prog, ProgramContract(
            solve_dtype="float64", allowed_dtypes=F64),
            lambda s: (vec(s)
                       + (np.linspace(0.0, 1.0, 4 * s),), {}))
        assert rules_of(lint_program(spec), "retrace-surface") == []

    def test_scale_invariant_closure_is_clean(self):
        zero_bits = np.int64(0)

        def prog(x):
            bits = lax.bitcast_convert_type(x, jnp.int64) + zero_bits
            return lax.bitcast_convert_type(bits, x.dtype)

        spec = spec_of(prog, ProgramContract(
            solve_dtype="float64", allowed_dtypes=F64),
            lambda s: (vec(s), {}))
        assert rules_of(lint_program(spec), "retrace-surface") == []


# -- shape-discipline ----------------------------------------------------

class TestShapeDiscipline:
    def test_static_while_carry_is_clean(self):
        def prog(x):
            def cond(c):
                return c[1] < 3

            def body(c):
                return c[0] * 2.0, c[1] + 1

            out, _ = lax.while_loop(cond, body,
                                    (x, jnp.int32(0)))
            return out

        spec = spec_of(prog, ProgramContract(
            solve_dtype="float64", allowed_dtypes=F64),
            lambda s: (vec(s), {}))
        assert rules_of(lint_program(spec),
                        "shape-discipline") == []

    def test_stage_failure_is_reported_not_raised(self):
        def prog(x):
            return x

        def broken_make(scale):
            raise RuntimeError("factory out of sync")

        spec = spec_of(prog, ProgramContract(
            solve_dtype="float64", allowed_dtypes=F64),
            broken_make)
        fs = lint_programs([spec])
        assert len(fs) == 1 and fs[0].snippet == "stage-failure"
        assert "factory out of sync" in fs[0].message


# -- the real registry ---------------------------------------------------

class TestRegistry:
    def test_every_registered_program_stages_and_passes(self):
        specs = iter_programs()
        assert len(specs) == 12
        findings = lint_programs(specs)
        assert findings == [], "\n".join(
            f"{f.path}: [{f.rule}] {f.message}" for f in findings)

    def test_no_registered_program_aliases_an_input(self):
        """Every dispatch leaves its inputs alive (speculative issues
        and replays re-read them): no contract donates and no lowered
        module carries an aliasing attribute."""
        from simgrid_tpu.analysis.prog.rules import (_DONATION_ATTRS,
                                                     stage)
        for spec in iter_programs():
            assert spec.contract.donated == (), spec.name
            ir = stage(spec)
            assert not any(ir.donated_flags), spec.name
            assert not any(a in ir.lowered_text
                           for a in _DONATION_ATTRS), spec.name

    def test_rule_filter(self):
        spec = iter_programs()[0]
        for rid in ALL_PROG_RULE_IDS:
            assert lint_program(spec, rules=[rid]) == []


# -- CLI -----------------------------------------------------------------

def test_proglint_cli_clean_tree():
    """`python tools/proglint.py --json` exits 0 over the registry."""
    import json
    import subprocess

    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO_ROOT, "tools", "proglint.py"), "--json"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report["ok"] is True
    assert report["findings"] == []


def test_lint_all_cli_clean_tree():
    """`python tools/lint_all.py --json` merges all three gates."""
    import json
    import subprocess

    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO_ROOT, "tools", "lint_all.py"), "--json"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report["clean"] is True and report["problems"] == []
