"""JG007 — unused imports (the autofix-driven dead-code sweep).

Unused imports are not just noise: in this codebase an import can pull
in jax machinery with real side effects (device init, x64 config), and
stale imports are where dead subsystems hide after a refactor. The rule
is deliberately conservative so its autofix is safe to run blind:

* usage = the bound name appearing as a word anywhere OUTSIDE the
  removable import statements themselves (code, annotations, docstrings,
  ``__all__`` strings) — false "used" beats false "unused". Other
  *removable* import segments are blanked before counting: a name whose
  only other appearance is inside an import this same rule may delete
  (``import os`` next to ``from os import path``) must count as unused
  NOW, or the first ``--autofix`` pass unmasks it and the second pass
  edits the file again — the idempotency bug the round-trip test pins;
* skipped entirely: ``__init__.py`` (re-export surface), ``__future__``
  imports, star imports, ``# noqa`` lines, imports inside ``try:``
  blocks (version/feature probing idiom), and
  imports sharing a source line with anything else (``import os; x=1``,
  trailing comments) — the counting and the fix are both line-grained.

The fix rewrites the import statement without the dead names, or
removes it outright; the engine applies fixes bottom-up so line numbers
stay valid.
"""
from __future__ import annotations

import ast
import re
from typing import List, Optional

from ..core import Finding, ModuleContext
from . import register


def _binding(alias: ast.alias, is_from: bool) -> str:
    if alias.asname:
        return alias.asname
    return alias.name if is_from else alias.name.split(".")[0]


def _rebuild(node, kept: List[ast.alias], indent: str) -> str:
    def fmt(a: ast.alias) -> str:
        return a.name + (" as " + a.asname if a.asname else "")
    names = [fmt(a) for a in kept]
    if not isinstance(node, ast.ImportFrom):
        # plain `import a, b` has no parenthesized form; a long line is
        # valid Python, which beats a SyntaxError
        return indent + "import " + ", ".join(names)
    mod = "." * node.level + (node.module or "")
    stmt = "from %s import %s" % (mod, ", ".join(names))
    if len(indent + stmt) <= 79:
        return indent + stmt
    # wrap: from m import (a, b,\n<align>c)
    head = indent + "from %s import (" % mod
    cont = " " * len(head)
    lines, cur = [], head
    for i, nm in enumerate(names):
        piece = nm + ("," if i < len(names) - 1 else ")")
        if cur != head and cur != cont and len(cur) + len(piece) + 1 > 79:
            lines.append(cur)
            cur = cont
        cur += piece if cur in (head, cont) else " " + piece
    lines.append(cur)
    return "\n".join(lines)


@register
class UnusedImports:
    id = "JG007"
    name = "unused-import"
    description = "import bound to a name the module never uses"

    def check(self, ctx: ModuleContext) -> List[Finding]:
        if ctx.relpath.endswith("__init__.py"):
            return []
        out: List[Finding] = []
        removable = [node for node in ast.walk(ctx.tree)
                     if self._removable(ctx, node)]
        usage_src = self._blank_segments(ctx, removable)
        for node in removable:
            is_from = isinstance(node, ast.ImportFrom)
            unused, kept = [], []
            for a in node.names:
                name = _binding(a, is_from)
                used = re.search(r"\b%s\b" % re.escape(name), usage_src)
                (kept if used else unused).append(a)
            if not unused:
                continue
            indent = ctx.lines[node.lineno - 1][
                :len(ctx.lines[node.lineno - 1])
                - len(ctx.lines[node.lineno - 1].lstrip())]
            new_text: Optional[str] = (
                _rebuild(node, kept, indent) if kept else None)
            fix = ("replace_span", (node.lineno, node.end_lineno, new_text))
            for i, a in enumerate(unused):
                out.append(ctx.finding(
                    self.id, node,
                    "imported name `%s` is never used"
                    % _binding(a, is_from),
                    fix=fix if i == 0 else None))
        return out

    def _removable(self, ctx: ModuleContext, node: ast.AST) -> bool:
        """Import statements this rule is allowed to rewrite/delete."""
        if not isinstance(node, (ast.Import, ast.ImportFrom)):
            return False
        if isinstance(node, ast.ImportFrom) and node.module == "__future__":
            return False
        if any(a.name == "*" for a in node.names):
            return False
        if not self._owns_its_lines(ctx, node):
            return False
        return not (self._in_try(ctx, node) or self._has_noqa(ctx, node))

    def _owns_its_lines(self, ctx: ModuleContext, node: ast.AST) -> bool:
        """True when nothing else shares the import's source lines.

        Both the usage count (whole-line blanking) and the fix
        (whole-line replace_span) operate on full lines, so an import
        sharing a line with other code (``import os; x = os.path``, or
        a trailing comment) must stay untouched — deleting the line
        would take the neighbour with it."""
        seg = ast.get_source_segment(ctx.source, node)
        if seg is None:
            return False
        seg_lines = seg.splitlines()
        first = ctx.lines[node.lineno - 1].strip()
        last = ctx.lines[(node.end_lineno or node.lineno) - 1].strip()
        return (first == seg_lines[0].strip()
                and last == seg_lines[-1].strip())

    def _blank_segments(self, ctx: ModuleContext, nodes) -> str:
        """Source with every removable import's lines blanked — the text
        usage is counted against. Blanking ALL of them at once (not just
        the statement under test) keeps the fix idempotent: a name whose
        only other mention is inside another deletable import would
        otherwise look used until that import is deleted, and the NEXT
        autofix pass would touch the file again."""
        lines = list(ctx.lines)
        for node in nodes:
            for ln in range(node.lineno, (node.end_lineno or node.lineno)
                            + 1):
                if 0 < ln <= len(lines):
                    lines[ln - 1] = ""
        return "\n".join(lines)

    def _in_try(self, ctx: ModuleContext, node: ast.AST) -> bool:
        cur = ctx.parent.get(node)
        while cur is not None:
            if isinstance(cur, ast.Try):
                return True
            cur = ctx.parent.get(cur)
        return False

    def _has_noqa(self, ctx: ModuleContext, node: ast.AST) -> bool:
        for ln in range(node.lineno, (node.end_lineno or node.lineno) + 1):
            if 0 < ln <= len(ctx.lines) and "# noqa" in ctx.lines[ln - 1]:
                return True
        return False
