"""Rule ``opstats-discipline`` — the counter table in
``ops/opstats.py`` and the ``bump()`` call sites must agree.

The docstring of :mod:`simgrid_tpu.ops.opstats` is the counter
registry: one ``* ``name`` — description`` bullet per counter, with
``name_<var>`` entries declaring dynamic families.  Tools and tests
navigate by that table; a counter bumped but not declared is invisible
to anyone reading the docs, and a declared counter nobody bumps is a
doc lying about instrumentation that doesn't exist.

This is a project-level rule (one pass over every linted file):

* ``bump("x")`` where ``x`` is neither declared nor covered by a
  declared ``prefix_<var>`` family → finding at the call site.
* ``bump(f"prefix_{...}")`` / ``bump("prefix_" + ...)`` whose constant
  prefix starts no declared family → finding at the call site.
* ``bump(<non-literal>)`` with no recoverable constant prefix →
  finding (the registry can't be checked against it).
* a declared exact counter that no linted file ever bumps → finding at
  its docstring bullet.  Wildcard families are exempt (their members
  are data-dependent).

The same docstring's "Spans" section is the registry of host spans,
one ``* ``dotted.name`` — description`` bullet each, held to the
``span()`` sites the same way: ``span("x")`` with ``x`` undeclared, or
``span(<non-literal>)``, is a finding at the call site (a span name
has no families: a reader finds spans by exact name), and a declared
span that no linted file opens is a finding at its bullet.
``note_xla("x", ...)``, which records a span JAX timed, is a site of
span ``x`` like ``span("x")``.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Tuple

from ..engine import FileContext, Finding, ImportMap

OPSTATS_PATH = "simgrid_tpu/ops/opstats.py"

#: end of the counter table inside the opstats docstring
_TABLE_END = "Counters only ever increase"

_TOKEN = re.compile(r"``([A-Za-z0-9_]+(?:<[A-Za-z_.]+>)?)``")

#: the span table: from this underlined heading to the docstring's end
_SPANS_HEAD = re.compile(r"^Spans\n-+\n", re.M)
_SPAN_BULLET = re.compile(r"^\* ``([a-z0-9_.]+)``\s+—")


def declared_counters(doc: str) -> Tuple[Dict[str, int],
                                         Dict[str, int]]:
    """Parse the registry out of the opstats module docstring.

    Returns (exact name -> docstring line, wildcard prefix ->
    docstring line).  Only ``* ``...```` bullet heads (and their
    ``/``-continuation lines) declare counters; tokens inside
    descriptions don't."""
    exact: Dict[str, int] = {}
    wild: Dict[str, int] = {}
    region = doc.split(_TABLE_END)[0].splitlines()
    cont = False
    for i, raw in enumerate(region):
        line = raw.strip()
        is_decl = line.startswith("* ``") or (cont
                                              and line.startswith("``"))
        cont = False
        if not is_decl:
            continue
        head = line.split("—")[0]
        for tok in _TOKEN.findall(head):
            # docstring starts on file line 1
            if "<" in tok:
                wild.setdefault(tok.split("<")[0], i + 1)
            else:
                exact.setdefault(tok, i + 1)
        if "—" not in line and head.rstrip().endswith("/"):
            cont = True
    return exact, wild


def declared_spans(doc: str) -> Dict[str, int]:
    """Span name -> docstring line, from the "Spans" section's bullet
    heads ({} when the docstring has no such section)."""
    head = _SPANS_HEAD.search(doc)
    if head is None:
        return {}
    first = doc[:head.end()].count("\n")
    out: Dict[str, int] = {}
    for i, raw in enumerate(doc[head.end():].splitlines()):
        m = _SPAN_BULLET.match(raw.strip())
        if m:
            out.setdefault(m.group(1), first + i + 1)
    return out


def _const_prefix(node: ast.AST) -> Optional[str]:
    """The leading constant string of a counter-name expression, or
    None when there isn't one.  ("abc" -> "abc"; f"abc{x}" -> "abc";
    "abc" + x -> "abc".)"""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr) and node.values:
        first = node.values[0]
        if isinstance(first, ast.Constant) \
                and isinstance(first.value, str):
            return first.value
        return ""
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        return _const_prefix(node.left)
    return None


def _is_bump(ctx: FileContext, node: ast.Call) -> bool:
    dotted = ctx.imports.resolve(node.func)
    if ImportMap.matches(dotted, "simgrid_tpu.ops.opstats.bump"):
        return True
    # inside opstats.py itself, bump is a plain local name
    return ctx.path == OPSTATS_PATH and dotted == "bump"


def _is_span(ctx: FileContext, node: ast.Call) -> bool:
    dotted = ctx.imports.resolve(node.func)
    if ImportMap.matches(dotted, "simgrid_tpu.ops.opstats.span") \
            or ImportMap.matches(dotted,
                                 "simgrid_tpu.ops.opstats.note_xla"):
        return True
    # inside opstats.py: span() by its local name, and the records
    # it and note_xla() build directly
    return ctx.path == OPSTATS_PATH and dotted in ("span", "Span")


class OpstatsDisciplineRule:
    id = "opstats-discipline"
    doc = ("bump() and span() sites and the opstats docstring registry "
           "must agree")

    def applies(self, relpath: str) -> bool:
        return False            # project-level only

    def check(self, ctx: FileContext) -> List[Finding]:
        return []

    def check_project(self, ctxs: List[FileContext]) -> List[Finding]:
        registry_ctx = next((c for c in ctxs
                             if c.path == OPSTATS_PATH), None)
        if registry_ctx is None:
            return []           # registry not in scope of this run
        doc = ast.get_docstring(registry_ctx.tree) or ""
        exact, wild = declared_counters(doc)

        spans = declared_spans(doc)

        out: List[Finding] = []
        bumped: set = set()     # literal names seen
        prefixes: set = set()   # dynamic prefixes seen
        opened: set = set()     # literal span names seen

        for ctx in ctxs:
            if not (ctx.path.startswith("simgrid_tpu/")
                    or ctx.path.startswith("tools/")):
                continue
            for node in ast.walk(ctx.tree):
                if not (isinstance(node, ast.Call) and node.args):
                    continue
                arg = node.args[0]
                if _is_span(ctx, node):
                    if isinstance(arg, ast.Constant) \
                            and isinstance(arg.value, str):
                        opened.add(arg.value)
                        if arg.value not in spans:
                            out.append(ctx.finding(
                                self.id, node,
                                f"span {arg.value!r} is opened here but "
                                f"not declared in the {OPSTATS_PATH} "
                                f"docstring's span table"))
                    elif ctx.path != OPSTATS_PATH:
                        out.append(ctx.finding(
                            self.id, node,
                            "span name is not a literal — a reader "
                            "finds spans by exact name; use a literal"))
                    continue
                if not _is_bump(ctx, node):
                    continue
                if isinstance(arg, ast.Constant) \
                        and isinstance(arg.value, str):
                    name = arg.value
                    bumped.add(name)
                    if name not in exact and not any(
                            name.startswith(w) for w in wild):
                        out.append(ctx.finding(
                            self.id, node,
                            f"counter {name!r} is bumped here but not "
                            f"declared in the {OPSTATS_PATH} "
                            f"docstring table"))
                    continue
                prefix = _const_prefix(arg)
                if prefix:
                    prefixes.add(prefix)
                    if not any(prefix.startswith(w) for w in wild):
                        out.append(ctx.finding(
                            self.id, node,
                            f"dynamic counter name with prefix "
                            f"{prefix!r} matches no declared "
                            f"``prefix_<var>`` family in "
                            f"{OPSTATS_PATH}"))
                else:
                    out.append(ctx.finding(
                        self.id, node,
                        "counter name is not a literal and has no "
                        "constant prefix — the registry cannot be "
                        "checked against it; use a literal or a "
                        "'family_' + var spelling"))

        for name, line in sorted(exact.items()):
            if name in bumped:
                continue
            if any(name.startswith(p) for p in prefixes):
                continue        # a dynamic family can mint it
            out.append(Finding(
                self.id, OPSTATS_PATH, line, 0,
                f"counter {name!r} is declared in the docstring table "
                f"but never bumped by any linted file",
                registry_ctx.snippet(line)))
        for name, line in sorted(spans.items()):
            if name not in opened:
                out.append(Finding(
                    self.id, OPSTATS_PATH, line, 0,
                    f"span {name!r} is declared in the docstring's span "
                    f"table but never opened by any linted file",
                    registry_ctx.snippet(line)))
        return out
