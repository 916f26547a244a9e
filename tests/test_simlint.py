"""simlint unit tests: per-rule fixtures (one true positive caught,
one near-miss left alone, one suppression honored), engine behaviors
(alias resolution, traced-scope detection, bad suppressions), and the
baseline round trip incl. the stale-entry failure mode.

Fixtures are in-memory {path: source} dicts run through
``lint_sources`` — rule path scopes are exercised by giving fixtures
the real audited paths."""

import json
import os
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from simgrid_tpu.analysis import (apply_baseline, dump_baseline,  # noqa: E402
                                  findings_to_json, lint_sources,
                                  load_baseline, make_baseline)

KERNEL = "simgrid_tpu/ops/lmm_drain.py"        # in KERNEL_FILES
SEAM = "simgrid_tpu/collectives/maestro.py"    # in SEAM_FILES
ORDER = "simgrid_tpu/collectives/schedule.py"  # in ORDER_FILES
CORE = "simgrid_tpu/ops/somecore.py"           # under CORE_RNG_DIRS
DRIVER = "tools/campaign_run.py"               # in DRIVER_RNG_FILES


def rules_of(findings, rule):
    return [f for f in findings if f.rule == rule]


# -- wallclock-rng -------------------------------------------------------

class TestWallclockRng:
    def test_alias_imports_cannot_dodge(self):
        fs = lint_sources({CORE: (
            "from time import time as _clock\n"
            "import random as rnd\n"
            "t = _clock()\n"
            "x = rnd.random()\n")})
        lines = [f.line for f in rules_of(fs, "wallclock-rng")]
        assert lines == [1, 2, 3, 4]

    def test_getattr_and_dynamic_import_escapes(self):
        fs = lint_sources({CORE: (
            "import importlib\n"
            "import random\n"              # line 2: banned import
            "f = getattr(random, 'random')\n"
            "m = importlib.import_module('numpy.random')\n")})
        lines = [f.line for f in rules_of(fs, "wallclock-rng")]
        assert 3 in lines and 4 in lines

    def test_monotonic_clock_is_clean(self):
        fs = lint_sources({CORE: (
            "import time\n"
            "t0 = time.perf_counter()\n"
            "t1 = time.monotonic()\n")})
        assert rules_of(fs, "wallclock-rng") == []

    def test_driver_tier_allows_seeded_generators_only(self):
        fs = lint_sources({DRIVER: (
            "import numpy as np\n"
            "rng = np.random.default_rng(7)\n"   # seeded: fine
            "bad = np.random.rand()\n")})        # global RNG: not
        lines = [f.line for f in rules_of(fs, "wallclock-rng")]
        assert lines == [3]

    def test_suppression_honored(self):
        fs = lint_sources({CORE: (
            "import numpy as np\n"
            "r = np.random.default_rng(3)"
            "  # simlint: ignore[wallclock-rng] -- test harness seed\n"
        )})
        assert rules_of(fs, "wallclock-rng") == []


# -- fma-hazard ----------------------------------------------------------

FMA_HEADER = "import functools\nimport jax\nimport jax.numpy as jnp\n"


class TestFmaHazard:
    def test_bare_multiply_add_in_program_is_flagged(self):
        fs = lint_sources({KERNEL: FMA_HEADER + (
            "def _advance_program(rem, rate, dt):\n"
            "    return rem - rate * dt\n")})
        assert len(rules_of(fs, "fma-hazard")) == 1

    def test_jit_by_assignment_is_traced(self):
        fs = lint_sources({KERNEL: FMA_HEADER + (
            "def _kern(rem, rate, dt):\n"
            "    return rem - rate * dt\n"
            "_kern_j = functools.partial(jax.jit)(_kern)\n")})
        assert len(rules_of(fs, "fma-hazard")) == 1

    def test_rounded_product_and_index_math_are_clean(self):
        fs = lint_sources({KERNEL: FMA_HEADER + (
            "def _advance_program(rem, rate, dt, zb):\n"
            "    pinned = rem - _rounded_product(rate, dt, zb)\n"
            "    slot = pos * group + j\n"
            "    return pinned, slot\n")})
        assert rules_of(fs, "fma-hazard") == []

    def test_untraced_host_code_is_clean(self):
        fs = lint_sources({KERNEL: FMA_HEADER + (
            "def host_helper(a, b, c):\n"
            "    return a - b * c\n")})
        assert rules_of(fs, "fma-hazard") == []

    def test_suppression_honored(self):
        fs = lint_sources({KERNEL: FMA_HEADER + (
            "def _advance_program(rem, rate, dt):\n"
            "    # simlint: ignore[fma-hazard] -- not on the f64 path\n"
            "    return rem - rate * dt\n")})
        assert rules_of(fs, "fma-hazard") == []


# -- hidden-host-sync ----------------------------------------------------

class TestHiddenHostSync:
    def test_bare_asarray_at_seam_is_flagged(self):
        fs = lint_sources({SEAM: (
            "import numpy as np\n"
            "def collect(dev):\n"
            "    return np.asarray(dev)\n")})
        assert len(rules_of(fs, "hidden-host-sync")) == 1

    def test_coercion_and_branch_inside_program_are_flagged(self):
        fs = lint_sources({SEAM: (
            "import jax\n"
            "def _step_program(x):\n"
            "    if x > 0:\n"
            "        return float(x)\n"
            "    return x\n")})
        msgs = [f.message for f in rules_of(fs, "hidden-host-sync")]
        assert any("'if' on traced parameter" in m for m in msgs)
        assert any("'float()'" in m for m in msgs)

    def test_normalization_and_statics_are_clean(self):
        fs = lint_sources({SEAM: (
            "import numpy as np\n"
            "from . import opstats\n"
            "def collect(dev, host_list):\n"
            "    a = np.asarray(host_list, dtype=np.float64)\n"
            "    b = opstats.timed_fetch(dev)\n"
            "    return a, b\n"
            "def _step_program(x, has_tape: bool):\n"
            "    if has_tape:\n"          # static param: legal branch
            "        x = x + 1\n"
            "    return x\n")})
        assert rules_of(fs, "hidden-host-sync") == []

    def test_suppression_honored(self):
        fs = lint_sources({SEAM: (
            "import numpy as np\n"
            "def collect(host_arr):\n"
            "    return np.asarray(host_arr)"
            "  # simlint: ignore[hidden-host-sync] -- host input\n")})
        assert rules_of(fs, "hidden-host-sync") == []


# -- dtype-discipline ----------------------------------------------------

class TestDtypeDiscipline:
    def test_dtypeless_creator_and_weak_literal_are_flagged(self):
        fs = lint_sources({KERNEL: (
            "import jax.numpy as jnp\n"
            "z = jnp.zeros(4)\n"
            "w = jnp.asarray(False)\n")})
        lines = [f.line for f in rules_of(fs, "dtype-discipline")]
        assert lines == [2, 3]

    def test_explicit_dtypes_and_passthrough_are_clean(self):
        fs = lint_sources({KERNEL: (
            "import jax.numpy as jnp\n"
            "z1 = jnp.zeros(4, jnp.float64)\n"     # positional dtype
            "z2 = jnp.zeros(4, dtype=jnp.int32)\n"
            "w = jnp.asarray(False, jnp.bool_)\n"
            "def f(x):\n"
            "    return jnp.asarray(x)\n")})       # array passthrough
        assert rules_of(fs, "dtype-discipline") == []

    def test_float32_construction_is_flagged(self):
        fs = lint_sources({KERNEL: (
            "import jax.numpy as jnp\n"
            "bad = jnp.float32(0.5)\n"
            "tbl = jnp.zeros(4, dtype=jnp.float32)\n")})
        assert len(rules_of(fs, "dtype-discipline")) == 2

    def test_suppression_honored(self):
        fs = lint_sources({KERNEL: (
            "import jax.numpy as jnp\n"
            "z = jnp.zeros(4)"
            "  # simlint: ignore[dtype-discipline] -- scratch only\n")})
        assert rules_of(fs, "dtype-discipline") == []


# -- unordered-iteration -------------------------------------------------

class TestUnorderedIteration:
    def test_set_and_dict_view_iteration_are_flagged(self):
        fs = lint_sources({ORDER: (
            "slots = set([3, 1, 2])\n"
            "for s in slots:\n"
            "    print(s)\n"
            "d = {}\n"
            "for k, v in d.items():\n"
            "    print(k, v)\n")})
        lines = [f.line for f in rules_of(fs, "unordered-iteration")]
        assert lines == [2, 5]

    def test_sorted_iteration_is_clean(self):
        fs = lint_sources({ORDER: (
            "slots = set([3, 1, 2])\n"
            "for s in sorted(slots):\n"
            "    print(s)\n"
            "d = {}\n"
            "out = [k for k in sorted(d.items())]\n"
            "lst = [3, 1]\n"
            "for x in lst:\n"              # list: ordered, clean
            "    print(x)\n")})
        assert rules_of(fs, "unordered-iteration") == []

    def test_suppression_honored(self):
        fs = lint_sources({ORDER: (
            "d = {}\n"
            "# simlint: ignore[unordered-iteration] -- insertion "
            "order is the sorted admission order\n"
            "for k in d.items():\n"
            "    print(k)\n")})
        assert rules_of(fs, "unordered-iteration") == []


# -- opstats-discipline --------------------------------------------------

OPSTATS_FIXTURE = (
    '"""Counters.\n'
    "\n"
    "* ``declared``    — a declared counter\n"
    "* ``ghost``       — declared but never bumped\n"
    "* ``fam_<kind>``  — a declared dynamic family\n"
    "\n"
    "Counters only ever increase.\n"
    '"""\n'
    "def bump(name, n=1):\n"
    "    pass\n")


class TestOpstatsDiscipline:
    def lint(self, user_src):
        return lint_sources({
            "simgrid_tpu/ops/opstats.py": OPSTATS_FIXTURE,
            "simgrid_tpu/ops/user.py": (
                "from simgrid_tpu.ops import opstats\n" + user_src),
        })

    def test_declared_and_family_bumps_are_clean(self):
        fs = self.lint("opstats.bump('declared')\n"
                       "opstats.bump('ghost')\n"
                       "opstats.bump('fam_' + kind)\n")
        assert rules_of(fs, "opstats-discipline") == []

    def test_undeclared_bump_and_unknown_family_are_flagged(self):
        fs = self.lint("opstats.bump('declared')\n"
                       "opstats.bump('ghost')\n"
                       "opstats.bump('undeclared')\n"
                       "opstats.bump('other_' + kind)\n")
        got = rules_of(fs, "opstats-discipline")
        assert sorted(f.line for f in got) == [4, 5]

    def test_declared_but_never_bumped_is_flagged_at_registry(self):
        fs = self.lint("opstats.bump('declared')\n"
                       "opstats.bump('fam_' + kind)\n")
        got = rules_of(fs, "opstats-discipline")
        assert len(got) == 1
        assert got[0].path == "simgrid_tpu/ops/opstats.py"
        assert "'ghost'" in got[0].message

    def test_suppression_honored(self):
        fs = self.lint(
            "opstats.bump('declared')\n"
            "opstats.bump('ghost')\n"
            "opstats.bump('undeclared')"
            "  # simlint: ignore[opstats-discipline] -- migration\n")
        assert rules_of(fs, "opstats-discipline") == []


SPANS_FIXTURE = OPSTATS_FIXTURE.replace(
    '"""\ndef bump', (
        "\n"
        "Spans\n"
        "-----\n"
        "\n"
        "Prose naming ``not.a.bullet`` declares nothing.\n"
        "\n"
        "* ``drain.issue``  — a declared span\n"
        "* ``xla.compile``  — timed by JAX, recorded through note_xla\n"
        '"""\n'
        "def bump")) + (
    "class Span(tuple):\n"
    "    pass\n"
    "class span:\n"
    "    def __init__(self, name, id=None):\n"
    "        self.name = name\n"
    "    def __exit__(self, *exc):\n"
    "        Span(self.name)\n"
    "def note_xla(name, seconds, id):\n"
    "    Span(name)\n")

BUMPS = ("opstats.bump('declared')\nopstats.bump('ghost')\n"
         "opstats.bump('fam_' + kind)\n"
         "opstats.note_xla('xla.compile', 0.1, 'f')\n")


class TestOpstatsSpanDiscipline:
    def lint(self, user_src):
        return lint_sources({
            "simgrid_tpu/ops/opstats.py": SPANS_FIXTURE,
            "simgrid_tpu/ops/user.py": (
                "from simgrid_tpu.ops import opstats\n" + BUMPS
                + user_src),
        })

    @pytest.mark.parametrize("src,lines", [
        ("with opstats.span('drain.issue', id=3):\n    pass\n", []),
        ("with opstats.span('drain.issue'):\n    pass\n"
         "with opstats.span('drain.other'):\n    pass\n", [8]),
        ("with opstats.span('drain.issue'):\n    pass\n"
         "with opstats.span('drain.' + kind):\n    pass\n", [8]),
        ("with opstats.span('drain.issue'):\n    pass\n"
         "with opstats.span('not.a.bullet'):\n    pass\n", [8]),
        ("opstats.note_xla('drain.issue', 0.1, 'f')\n", []),
        ("with opstats.span('drain.issue'):\n    pass\n"
         "opstats.note_xla('xla.other', 0.1, 'f')\n", [8]),
        ("with opstats.span('drain.issue'):\n    pass\n"
         "opstats.note_xla(name, 0.1, 'f')\n", [8]),
    ], ids=["declared", "undeclared", "non-literal", "prose-token",
            "note-declared", "note-undeclared", "note-non-literal"])
    def test_span_sites_are_held_to_the_table(self, src, lines):
        got = rules_of(self.lint(src), "opstats-discipline")
        assert sorted(f.line for f in got) == lines
        assert all(f.path.endswith("user.py") for f in got)

    def test_declared_but_never_opened_is_flagged_at_registry(self):
        # the fixture's own note_xla() call keeps xla.compile opened
        got = rules_of(self.lint("x = 1\n"), "opstats-discipline")
        assert len(got) == 1
        assert got[0].path == "simgrid_tpu/ops/opstats.py"
        assert "'drain.issue'" in got[0].message
        assert "drain.issue" in SPANS_FIXTURE.splitlines()[got[0].line - 1]

    def test_the_real_table_declares_every_span_the_tree_opens(self):
        from simgrid_tpu.analysis.rules.opstats_discipline import \
            declared_spans
        from simgrid_tpu.ops import opstats
        names = set(declared_spans(opstats.__doc__))
        assert {"platform.load", "lmm.flatten", "coll.lower", "drain.init",
                "drain.issue", "drain.collect", "drain.demux",
                "solve.chunk", "fetch", "engine.advance", "xla.trace",
                "xla.lower", "xla.compile"} == names

    def test_the_real_listener_is_held_to_both_tables(self):
        """``ops/__init__.py`` names its three spans through
        ``note_xla`` and bumps the compile's counters itself: with one
        renamed there, the real rule over the real registry finds the
        undeclared name and the bullet left without a site."""
        import os
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        files = {}
        for rel in ("simgrid_tpu/ops/opstats.py",
                    "simgrid_tpu/ops/__init__.py"):
            with open(os.path.join(root, rel)) as f:
                files[rel] = f.read()

        def findings(src):
            return [f.message for f in rules_of(
                lint_sources({**files, "simgrid_tpu/ops/__init__.py": src}),
                "opstats-discipline")
                if "xla" in f.message]

        listener = files["simgrid_tpu/ops/__init__.py"]
        assert findings(listener) == []
        got = findings(listener.replace('"xla.lower"', '"xla.mlir"'))
        assert len(got) == 2 and "'xla.mlir'" in got[0] \
            and "'xla.lower'" in got[1]
        got = findings(listener.replace('"xla_compile_ms"', '"xla_jit_ms"'))
        assert len(got) == 2 and "'xla_jit_ms'" in got[0] \
            and "'xla_compile_ms'" in got[1]


# -- engine: suppressions ------------------------------------------------

class TestSuppressionHygiene:
    def test_reasonless_suppression_is_itself_a_finding(self):
        fs = lint_sources({KERNEL: (
            "import jax.numpy as jnp\n"
            "z = jnp.zeros(4)  # simlint: ignore[dtype-discipline]\n")})
        assert rules_of(fs, "dtype-discipline") == []   # silenced...
        bad = rules_of(fs, "bad-suppression")
        assert len(bad) == 1                            # ...but dinged

    def test_standalone_directive_covers_next_line_only(self):
        fs = lint_sources({KERNEL: (
            "import jax.numpy as jnp\n"
            "# simlint: ignore[dtype-discipline] -- scratch\n"
            "a = jnp.zeros(4)\n"
            "b = jnp.zeros(4)\n")})
        lines = [f.line for f in rules_of(fs, "dtype-discipline")]
        assert lines == [4]

    def test_unrelated_rule_not_silenced(self):
        fs = lint_sources({KERNEL: (
            "import jax.numpy as jnp\n"
            "z = jnp.zeros(4)"
            "  # simlint: ignore[fma-hazard] -- wrong rule\n")})
        assert len(rules_of(fs, "dtype-discipline")) == 1


# -- engine: baseline ----------------------------------------------------

BASELINE_SRC = {KERNEL: (
    "import jax.numpy as jnp\n"
    "a = jnp.zeros(3)\n"
    "b = jnp.zeros(5)\n")}


class TestBaseline:
    def test_round_trip_grandfathers_everything(self, tmp_path):
        fs = lint_sources(BASELINE_SRC)
        assert len(fs) == 2
        path = str(tmp_path / "baseline.json")
        dump_baseline(make_baseline(fs), path)
        new, stale = apply_baseline(lint_sources(BASELINE_SRC),
                                    load_baseline(path))
        assert new == [] and stale == []

    def test_line_shift_does_not_invalidate(self):
        baseline = make_baseline(lint_sources(BASELINE_SRC))
        shifted = {KERNEL: ("import jax.numpy as jnp\n"
                            "\n\n"     # findings move down 2 lines
                            "a = jnp.zeros(3)\n"
                            "b = jnp.zeros(5)\n")}
        new, stale = apply_baseline(lint_sources(shifted), baseline)
        assert new == [] and stale == []

    def test_new_finding_is_not_grandfathered(self):
        baseline = make_baseline(lint_sources(BASELINE_SRC))
        grown = {KERNEL: BASELINE_SRC[KERNEL]
                 + "c = jnp.zeros(7)\n"}
        new, stale = apply_baseline(lint_sources(grown), baseline)
        assert [f.line for f in new] == [4] and stale == []

    def test_fixed_finding_makes_baseline_stale(self):
        baseline = make_baseline(lint_sources(BASELINE_SRC))
        fixed = {KERNEL: ("import jax.numpy as jnp\n"
                          "a = jnp.zeros(3, jnp.float64)\n"
                          "b = jnp.zeros(5)\n")}
        new, stale = apply_baseline(lint_sources(fixed), baseline)
        assert new == []
        assert len(stale) == 1
        assert stale[0]["snippet"] == "a = jnp.zeros(3)"


# -- reporters -----------------------------------------------------------

def test_json_reporter_shape():
    fs = lint_sources(BASELINE_SRC)
    report = json.loads(findings_to_json(fs, stale=(), baselined=0))
    assert report["ok"] is False
    assert report["counts"] == {"dtype-discipline": 2}
    assert {f["rule"] for f in report["findings"]} \
        == {"dtype-discipline"}
    assert all({"rule", "path", "line", "col", "message",
                "snippet"} <= set(f) for f in report["findings"])


# -- CLI: --rule baseline scoping ----------------------------------------

class TestRuleScopedBaseline:
    """`simlint --rule X` must not report OTHER rules' grandfathered
    baseline entries as stale: a single-rule run only produces that
    rule's findings, so the baseline has to be scoped the same way
    before diffing (regression: a clean `--rule unordered-iteration`
    run used to exit 1 over every hidden-host-sync entry)."""

    def _make_tree(self, tmp_path):
        # two files, two different rules' findings
        core = tmp_path / CORE
        order = tmp_path / ORDER
        core.parent.mkdir(parents=True, exist_ok=True)
        order.parent.mkdir(parents=True, exist_ok=True)
        core.write_text("import random\nx = random.random()\n")
        order.write_text("s = {1, 2}\nfor v in s:\n    print(v)\n")
        return [CORE, ORDER]

    def _main(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "simlint_cli",
            os.path.join(REPO_ROOT, "tools", "simlint.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.main

    def test_single_rule_run_ignores_other_rules_entries(self,
                                                         tmp_path):
        main = self._main()
        paths = self._make_tree(tmp_path)
        baseline = str(tmp_path / "baseline.json")
        base_args = ["--root", str(tmp_path), "--baseline", baseline]
        assert main(paths + base_args + ["--write-baseline"]) == 0

        # full run: everything grandfathered
        assert main(paths + base_args) == 0
        # scoped runs: each rule sees only its own baseline slice
        assert main(paths + base_args
                    + ["--rule", "unordered-iteration"]) == 0
        assert main(paths + base_args
                    + ["--rule", "wallclock-rng"]) == 0

    def test_scoped_run_still_fails_on_own_stale_entry(self,
                                                       tmp_path):
        main = self._main()
        paths = self._make_tree(tmp_path)
        baseline = str(tmp_path / "baseline.json")
        base_args = ["--root", str(tmp_path), "--baseline", baseline]
        assert main(paths + base_args + ["--write-baseline"]) == 0

        # fix the unordered-iteration finding: ITS scoped run goes
        # stale, the other rule's scoped run stays clean
        (tmp_path / ORDER).write_text(
            "s = {1, 2}\nfor v in sorted(s):\n    print(v)\n")
        assert main(paths + base_args
                    + ["--rule", "unordered-iteration"]) == 1
        assert main(paths + base_args
                    + ["--rule", "wallclock-rng"]) == 0
