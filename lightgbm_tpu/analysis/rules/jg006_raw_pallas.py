"""JG006 — pallas imported around its single import point.

``ops/pallas_compat.py`` re-exports the Pallas TPU API (``pl``,
``pltpu``, ``CompilerParams``, ``enable_x64``) so that a move in that
still-experimental API is followed in one file. Only the modules listed
in ``pallas_compat_allow`` (that file itself) may touch the raw import.
"""
from __future__ import annotations

import ast
from typing import List

from ..core import Finding, ModuleContext
from . import register

_RAW = "jax.experimental.pallas"


@register
class RawPallasImport:
    id = "JG006"
    name = "raw-pallas-import"
    description = ("direct jax.experimental.pallas import bypasses "
                   "ops/pallas_compat.py (the single import point)")

    def check(self, ctx: ModuleContext) -> List[Finding]:
        allowed = {p.replace("\\", "/")
                   for p in ctx.config.pallas_compat_allow}
        if ctx.relpath in allowed:
            return []
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            hit = False
            if isinstance(node, ast.Import):
                hit = any(a.name == _RAW or a.name.startswith(_RAW + ".")
                          for a in node.names)
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                mod = node.module or ""
                hit = mod == _RAW or mod.startswith(_RAW + ".") or (
                    mod == "jax.experimental"
                    and any(a.name == "pallas" for a in node.names))
            if hit:
                out.append(ctx.finding(
                    self.id, node,
                    "import pallas via ops/pallas_compat.py (pl, pltpu, "
                    "CompilerParams, enable_x64), not directly"))
        return out
