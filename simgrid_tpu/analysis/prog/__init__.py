"""Compiled-program contract checking (proglint).

`analysis` (simlint) guards the *source* tree with AST rules; this
subpackage guards the *compiled* programs.  Every invariant the
bit-identity contract actually rests on — f64 event ordering, the
FMA-contraction pinning in ``_rounded_product``, "one ring fetch per
superstep", jit-cache-flat shapes —
lives in the lowered jaxpr/StableHLO, where an innocuous weak-typed
scalar or a dtype-promoting op can rewrite the program without
touching any lintable syntax.

The pieces:

* :mod:`.contract` — :class:`ProgramContract`, the declared invariants
  of one jitted kernel program (allowed dtypes with an explicit f64
  allowlist, output surface, required donated carries, FMA pinning,
  forbidden ops).
* :mod:`.registry` — :class:`ProgramSpec` entries for every jitted
  kernel program in the tree, each with a small-N example-args factory
  (the production drivers' own argument assembly, captured), staged
  through the same ``jit().trace()`` / ``.lower()`` path the serving
  plan cache uses.
* :mod:`.rules` — the IR rules (`dtype-flow`, `hidden-transfer`,
  `fma-pinning`, `donation`, `retrace-surface`, `shape-discipline`)
  and :func:`lint_programs`, producing the same
  :class:`simgrid_tpu.analysis.engine.Finding` records as simlint so
  the baseline/reporter machinery is shared.

Run it via ``tools/proglint.py`` (or ``tools/lint_all.py`` /
``check_determinism.py --quick``, which run both analyzers).
"""

from .contract import ProgramContract            # noqa: F401
from .registry import ProgramSpec, iter_programs  # noqa: F401
from .rules import ALL_PROG_RULE_IDS, lint_programs  # noqa: F401
