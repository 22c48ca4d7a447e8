"""Graft-lint configuration: defaults + the ``[tool.graftlint]`` table.

Python 3.10 has no ``tomllib``, and the package must not grow a toml
dependency (hard constraint: nothing gets pip-installed), so the loader
parses just the subset pyproject actually uses: one ``[tool.graftlint]``
table of ``key = value`` lines where a value is a string, int, bool, or
a (possibly multi-line) list of strings. Anything fancier belongs in
code, not config.
"""
from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_SECTION = "[tool.graftlint]"


@dataclass
class GraftlintConfig:
    """Knobs for the lint engine; see docs/COMPONENTS.md for semantics."""

    # file selection (path fragments relative to the repo root)
    include: List[str] = field(default_factory=lambda: ["lightgbm_tpu"])
    exclude: List[str] = field(default_factory=lambda: [
        "__pycache__", "lightgbm_tpu/native"])
    # rule ids disabled outright
    disable: List[str] = field(default_factory=list)
    # JG002: host-sync findings only fire inside these path fragments
    hot_paths: List[str] = field(default_factory=lambda: [
        "lightgbm_tpu/ops/", "lightgbm_tpu/predict/",
        "lightgbm_tpu/parallel/", "lightgbm_tpu/serving/"])
    # JG001/JG003a: a function whose name matches one of these regexes is
    # treated as TPU kernel code (in addition to jit-decorated functions)
    kernel_names: List[str] = field(default_factory=lambda: [
        r".*_kernel$", r"^kernel$", r"^_fill_(fwd|bwd)$"])
    # JG006: the only modules allowed to import pallas directly
    pallas_compat_allow: List[str] = field(default_factory=lambda: [
        "lightgbm_tpu/ops/pallas_compat.py"])
    # JG008: path fragments whose file writes must be atomic
    # (tmp + fsync + os.replace) — the checkpoint/state durability contract
    atomic_write_paths: List[str] = field(default_factory=lambda: [
        "lightgbm_tpu/resilience/"])
    # collective-order auditor + JG009: files/dirs holding host-side DCN
    # collective call sites (rank-consistency and guard-wrapping checks)
    collective_paths: List[str] = field(default_factory=lambda: [
        "lightgbm_tpu/parallel/", "lightgbm_tpu/resilience/"])
    # mesh-collective trace: files whose IN-PROGRAM labeled collective
    # wrappers (ops/quantize.plane_psum / vote_allgather) are extracted
    # into the collective trace's `mesh_sites` section — the wire-format
    # diff artifact of the quantized-histogram exchange. These run inside
    # jitted SPMD programs (XLA sequences them), so the guard/observed
    # audits do not apply; every site must still carry a literal label.
    mesh_collective_paths: List[str] = field(default_factory=lambda: [
        "lightgbm_tpu/ops/grow.py",
        "lightgbm_tpu/ops/grow_persist.py"])
    # JG010: ops//predict/ files whose narrowing casts are blessed —
    # their NARROW_OK tables + input contracts feed the precision-flow
    # auditor; narrowing anywhere else in the hot paths is a finding
    narrow_ok_paths: List[str] = field(default_factory=lambda: [
        "lightgbm_tpu/ops/grow.py",
        "lightgbm_tpu/ops/grow_persist.py",
        "lightgbm_tpu/ops/pallas_grow.py",
        "lightgbm_tpu/ops/pallas_histogram.py",
        "lightgbm_tpu/ops/pallas_scan.py",
        "lightgbm_tpu/ops/quantize.py"])
    # concurrency auditor + JG011/JG012: the threaded host layer —
    # modules here that own locks or spawn threads get lock-discipline,
    # blocking-hold, and lock-order analysis
    concurrency_paths: List[str] = field(default_factory=lambda: [
        "lightgbm_tpu/serving/", "lightgbm_tpu/predict/serve.py",
        "lightgbm_tpu/resilience/", "lightgbm_tpu/telemetry/"])
    # compile auditor: ceiling on the analytic distinct-compile bound
    compile_ceiling: int = 64
    # baseline suppression file, relative to the repo root
    baseline: str = "lightgbm_tpu/analysis/baseline.json"
    root: str = "."

    def baseline_path(self) -> str:
        return os.path.join(self.root, self.baseline)

    def kernel_regexes(self) -> List["re.Pattern"]:
        return [re.compile(p) for p in self.kernel_names]

    def is_excluded(self, relpath: str) -> bool:
        rp = relpath.replace(os.sep, "/")
        return any(frag in rp for frag in self.exclude)

    def is_hot_path(self, relpath: str) -> bool:
        rp = relpath.replace(os.sep, "/")
        return any(rp.startswith(frag) or frag in rp
                   for frag in self.hot_paths)


def _parse_table(text: str) -> Dict[str, object]:
    """Extract `[tool.graftlint]` key/values from pyproject text."""
    lines = text.splitlines()
    out: Dict[str, object] = {}
    in_section = False
    buf: Optional[Tuple[str, str]] = None   # (key, partial value)
    for line in lines:
        stripped = line.strip()
        if stripped.startswith("["):
            if buf is not None:
                raise ValueError("unterminated graftlint list for %r"
                                 % buf[0])
            in_section = stripped == _SECTION
            continue
        if not in_section or not stripped or stripped.startswith("#"):
            continue
        if buf is not None:
            key, part = buf
            part += " " + stripped
            if _balanced(part):
                out[key] = _parse_value(part)
                buf = None
            else:
                buf = (key, part)
            continue
        m = re.match(r"^([A-Za-z0-9_-]+)\s*=\s*(.+)$", stripped)
        if not m:
            raise ValueError("cannot parse graftlint config line: %r"
                             % stripped)
        key, val = m.group(1).replace("-", "_"), m.group(2).strip()
        if val.startswith("[") and not _balanced(val):
            buf = (key, val)
        else:
            out[key] = _parse_value(val)
    if buf is not None:
        raise ValueError("unterminated graftlint list for %r" % buf[0])
    return out


def _balanced(val: str) -> bool:
    return val.count("[") == val.count("]")


def _parse_value(val: str):
    val = val.strip()
    if val == "true":
        return True
    if val == "false":
        return False
    # strings / lists / ints share Python literal syntax once true/false
    # are gone; strip trailing comments outside quotes first
    try:
        return ast.literal_eval(val)
    except (ValueError, SyntaxError):
        raise ValueError("unsupported graftlint config value: %r" % val)


def load_config(root: Optional[str] = None) -> GraftlintConfig:
    """Config from `<root>/pyproject.toml`'s [tool.graftlint] table,
    defaults when the file or table is absent. `root` defaults to the
    package's repo checkout (the directory holding pyproject.toml)."""
    if root is None:
        here = os.path.dirname(os.path.abspath(__file__))
        root = os.path.dirname(os.path.dirname(here))
    cfg = GraftlintConfig(root=root)
    pyproject = os.path.join(root, "pyproject.toml")
    if not os.path.isfile(pyproject):
        return cfg
    with open(pyproject, "r", encoding="utf-8") as f:
        table = _parse_table(f.read())
    for key, val in table.items():
        if not hasattr(cfg, key):
            raise ValueError("unknown [tool.graftlint] key: %r" % key)
        setattr(cfg, key, val)
    return cfg
