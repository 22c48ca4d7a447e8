"""Recompile-surface auditor: bound the distinct-compile count statically.

A jitted entry point recompiles once per distinct static-argument
tuple (plus once per input-shape bucket). The serving path already pins
its shape ladder analytically (``jaxpr_audit.audit_serve_ladder``);
this module generalizes that bound to the whole program: enumerate
every ``jax.jit`` site across ``ops/``, ``predict/`` and the level
driver (``treelearner/``) via AST, read off its static-argument
signature, and multiply each argument's value-domain size from the
registry below. The audit fails on

* an **unbounded static-arg**: a name with no registered domain — the
  classic storm is a Python int that varies per iteration (a leaf
  count, a chunk index) quietly marked static;
* a total analytic bound above the configured ceiling
  (``[tool.graftlint] compile-ceiling``) — the budget a training +
  serving run is allowed to spend on compiles.

The domain registry is deliberately explicit: adding a static arg to a
kernel REQUIRES adding its domain here (or the gate fails), which is
the point — every new compile axis is a reviewed decision, the way new
lint rules require fixtures. Factory-built jits (the ``make_*`` kernel
builders' inner ``@jax.jit``) count 1 each: JG004 already polices that
builders stay out of host loops, so each contributes one compile per
payload geometry.
"""
from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..telemetry import events as telemetry
from .config import GraftlintConfig, load_config
from .core import ModuleContext
from .jaxpr_audit import AuditResult

C_ENTRIES = "analysis::compile_entries"
C_BOUND = "analysis::compile_bound"
C_UNBOUNDED = "analysis::compile_unbounded"

# directories whose jit sites form the training/serving compile surface
AUDIT_ROOTS = ("lightgbm_tpu/ops", "lightgbm_tpu/predict",
               "lightgbm_tpu/treelearner", "lightgbm_tpu/serving",
               "lightgbm_tpu/multimodel")

# static-argument value domains: name -> (size, why). A size of 1 means
# "constant for a whole run" (dataset geometry, config); sizes > 1
# enumerate the values a single run can actually see.
DOMAINS: Dict[str, Tuple[int, str]] = {
    "interpret": (1, "False outside the parity tests"),
    "do_fix": (2, "bundled datasets run both fix modes"),
    "w": (2, "per-dataset max width; <=2 pad ladder stops (128/256)"),
    "max_w": (1, "per-dataset categorical width"),
    "steps": (1, "config constant (max_cat_threshold)"),
    "use_dp": (1, "config constant"),
    "use_mc": (1, "per-dataset monotone flag"),
    "num_features": (1, "dataset geometry"),
    "gc": (1, "one GrowConfig per learner"),
    "axis_name": (1, "mesh constant"),
    "total_bins": (1, "dataset geometry"),
    "rows_per_chunk": (1, "resolved once per learner"),
    "dtype": (2, "hist dtype: run dtype + the f64 parity twin"),
    "num_class": (1, "config constant"),
    "use_l1": (1, "config constant (lambda_l1 > 0)"),
    "use_mds": (1, "config constant (max_delta_step > 0)"),
    "feat_gains_only": (2, "CEGB feature-gain pre-pass runs both modes"),
    "k": (3, "fused scan batch sizes clamp to {1..8,16} minus "
             "snapshot alignment; bounded by the batch ladder"),
    "quant": (1, "one certified HistQuant (or None) per learner — "
                 "resolved from tpu_hist_quant at config time"),
    # fused boosting iteration (PR 17): the scan-driver factory caches
    # one compiled program per (mode, objective-kernel id, k,
    # bag_spec) — `mode` and the kernel id are factory-closure axes
    # today, but registering them here makes the compile cost of any
    # future static-arg promotion a reviewed decision, and bounds the
    # per-learner driver-cache fan-out the same way
    "mode": (2, "driver program family: {gbdt, rf} (dart rides gbdt "
                "k=1 programs)"),
    "grad_kernel": (1, "one objective per learner -> one device "
                       "gradient kernel per driver cache"),
    "cls": (4, "DART delta gather-add compiles once per class id it "
               "touches; bounded by num_class (1 for the audited "
               "binary/regression surface, small for multiclass)"),
    # serving static args (serving/ rides predict's jitted entry points;
    # these bound any future serving-local jit site the same way)
    "quant_target": (2, "serving value grids: native + the certified "
                        "f16 twin (coarser grids are refused at load)"),
    "raw_score": (2, "serving transform flag: {True, False}"),
}

# site-specific domains for static_argnums on functions whose parameter
# names the AST walk cannot resolve (bound methods): keyed by
# (file basename, function-or-target name, argnum)
SITE_DOMAINS: Dict[Tuple[str, str, int], Tuple[int, str]] = {
    ("runtime.py", "self._forward_raw", 1): (2, "raw flag: {True, False}"),
}


@dataclass
class JitSite:
    """One jit construction site and its static-argument signature."""

    path: str
    line: int
    func: str                      # decorated/wrapped callable name
    kind: str                      # "decorator" | "call" | "factory"
    static_names: Tuple[str, ...] = ()
    static_nums: Tuple[int, ...] = ()
    bound: int = 1
    unbounded: List[str] = field(default_factory=list)

    def to_dict(self) -> dict:
        return {"path": self.path, "line": self.line, "func": self.func,
                "kind": self.kind,
                "static_names": list(self.static_names),
                "static_nums": list(self.static_nums),
                "bound": self.bound, "unbounded": list(self.unbounded)}


def _const_str_tuple(node: ast.AST) -> Tuple[str, ...]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                out.append(el.value)
        return tuple(out)
    return ()


def _const_int_tuple(node: ast.AST) -> Tuple[int, ...]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (int(node.value),)
    if isinstance(node, (ast.Tuple, ast.List)):
        return tuple(int(el.value) for el in node.elts
                     if isinstance(el, ast.Constant)
                     and isinstance(el.value, int))
    return ()


class _ModuleScan:
    """Jit sites of one parsed module."""

    def __init__(self, ctx: ModuleContext):
        self.ctx = ctx
        self.sites: List[JitSite] = []
        self._scan()

    def _jit_call_info(self, call: ast.Call) -> Optional[dict]:
        """Parse a jax.jit(...) / partial(jax.jit, ...) call node."""
        target = self.ctx.call_target(call)
        if target in ("jax.jit", "jax.pmap", "jit"):
            kw = {k.arg: k.value for k in call.keywords}
            fn = ""
            if call.args:
                fn = ast.get_source_segment(self.ctx.source,
                                            call.args[0]) or ""
            return {"fn": fn, "kw": kw}
        if target in ("functools.partial", "partial") and call.args \
                and self.ctx.dotted(call.args[0]) in ("jax.jit",
                                                      "jax.pmap", "jit"):
            kw = {k.arg: k.value for k in call.keywords}
            return {"fn": "", "kw": kw}
        return None

    def _site_from(self, node: ast.Call, func: str, kind: str,
                   info: dict) -> JitSite:
        names = ()
        nums = ()
        if "static_argnames" in info["kw"]:
            names = _const_str_tuple(info["kw"]["static_argnames"])
        if "static_argnums" in info["kw"]:
            nums = _const_int_tuple(info["kw"]["static_argnums"])
        return JitSite(path=self.ctx.relpath, line=node.lineno,
                       func=func or info["fn"], kind=kind,
                       static_names=names, static_nums=nums)

    def _scan(self) -> None:
        seen: set = set()
        # decorators: @jax.jit / @functools.partial(jax.jit, ...)
        for node in ast.walk(self.ctx.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call):
                    info = self._jit_call_info(dec)
                    if info is None:
                        continue
                    seen.add(dec)
                    kind = ("factory"
                            if self.ctx.enclosing_function(node) is not None
                            else "decorator")
                    self.sites.append(self._site_from(dec, node.name,
                                                      kind, info))
                elif self.ctx.dotted(dec) in ("jax.jit", "jax.pmap",
                                              "jit"):
                    kind = ("factory"
                            if self.ctx.enclosing_function(node) is not None
                            else "decorator")
                    self.sites.append(JitSite(
                        path=self.ctx.relpath, line=node.lineno,
                        func=node.name, kind=kind))
        # expression calls: jax.jit(fn, static_argnums=...) AND bare
        # partial(jax.jit, ...) factories outside decorator position
        # (assignment forms recompile just like decorators do)
        for node in ast.walk(self.ctx.tree):
            if not isinstance(node, ast.Call) or node in seen:
                continue
            info = self._jit_call_info(node)
            if info is None:
                continue
            self.sites.append(self._site_from(node, "", "call", info))


def _resolve_bounds(sites: List[JitSite],
                    extra_domains: Optional[Dict[str, Tuple[int, str]]]
                    = None) -> None:
    domains = dict(DOMAINS)
    if extra_domains:
        domains.update(extra_domains)
    for s in sites:
        bound = 1
        for name in s.static_names:
            if name in domains:
                bound *= max(domains[name][0], 1)
            else:
                s.unbounded.append(name)
        for num in s.static_nums:
            key = (os.path.basename(s.path), s.func, num)
            if key in SITE_DOMAINS:
                bound *= max(SITE_DOMAINS[key][0], 1)
            else:
                s.unbounded.append("argnum:%d" % num)
        s.bound = bound


def serve_ladder_bound(min_batch: int = 256,
                       max_batch: int = 65536) -> int:
    """The BatchServer compile bound (generalizes the PR 4 serve-ladder
    audit): every batch in [1, max] maps into <= log2(max/min)+1 pow2
    buckets, each compiling once."""
    return int(np.log2(max(max_batch // max(min_batch, 1), 1))) + 1


def mm_ladder_bound() -> int:
    """The multimodel batch-axis compile bound: the vmapped drivers take
    NO static args (B and k are inferred from argument shapes), so their
    only compile axis is the leading model-axis extent — and
    ``multimodel.driver.bucket_for`` pads every batch up to a power-of-two
    bucket in [MM_MIN_BUCKET, MM_MAX_BUCKET] (wider sweeps chunk at the
    cap), so a run sees at most log2(max/min)+1 distinct batch shapes per
    program family. The value domain of the model-batch static axis, in
    ladder form — the exact analog of :func:`serve_ladder_bound`."""
    from ..multimodel.driver import MM_MAX_BUCKET, MM_MIN_BUCKET
    return int(np.log2(max(MM_MAX_BUCKET // max(MM_MIN_BUCKET, 1), 1))) + 1


def iter_jit_sites(config: Optional[GraftlintConfig] = None
                   ) -> List[JitSite]:
    config = config or load_config()
    sites: List[JitSite] = []
    for root in AUDIT_ROOTS:
        ap = os.path.join(config.root, root)
        if not os.path.isdir(ap):
            continue
        for dirpath, dirnames, filenames in os.walk(ap):
            dirnames[:] = [d for d in sorted(dirnames)
                           if d != "__pycache__"]
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                rel = os.path.relpath(os.path.join(dirpath, fn),
                                      config.root).replace(os.sep, "/")
                with open(os.path.join(config.root, rel), "r",
                          encoding="utf-8") as f:
                    src = f.read()
                scan = _ModuleScan(ModuleContext(src, rel, config))
                sites.extend(scan.sites)
    _resolve_bounds(sites)
    return sites


def analyze_source(source: str, relpath: str,
                   config: Optional[GraftlintConfig] = None
                   ) -> List[JitSite]:
    """Audit one in-memory module (the fixture-test entry point)."""
    config = config or GraftlintConfig()
    scan = _ModuleScan(ModuleContext(source, relpath, config))
    _resolve_bounds(scan.sites)
    return scan.sites


def check_fixture(source: str) -> List[str]:
    """Uniform fixture hook: unbounded-static findings for a snippet."""
    sites = analyze_source(source, "lightgbm_tpu/ops/fixture.py")
    return ["%s:%d static arg `%s` has no registered domain"
            % (s.path, s.line, name)
            for s in sites for name in s.unbounded]


def compile_surface(config: Optional[GraftlintConfig] = None,
                    artifact=None) -> dict:
    """The full surface: sites, the analytic total, the serve ladder."""
    sites = artifact if artifact is not None else iter_jit_sites(config)
    ladder = serve_ladder_bound()
    mm_ladder = mm_ladder_bound()
    total = sum(s.bound for s in sites) + ladder + mm_ladder
    return {"sites": [s.to_dict() for s in sites],
            "serve_ladder_bound": ladder,
            "mm_ladder_bound": mm_ladder,
            # each serving registry slot owns a TPUPredictor instance
            # (its own executable cache), so a multi-model deployment
            # spends `ladder` compiles PER ACTIVE SLOT — per-slot cost
            # for capacity planning; the analytic ceiling stays a
            # single-model-surface bound
            "serving_ladder_per_slot": ladder,
            "total_bound": total}


def run(config: Optional[GraftlintConfig] = None,
        artifact=None) -> List[AuditResult]:
    """The gate entry point: one AuditResult over the whole surface.

    ``artifact`` takes a precomputed :func:`iter_jit_sites` list so the
    --json CLI path enumerates the surface once, not twice."""
    config = config or load_config()
    sites = artifact if artifact is not None else iter_jit_sites(config)
    ladder = serve_ladder_bound()
    mm_ladder = mm_ladder_bound()
    total = sum(s.bound for s in sites) + ladder + mm_ladder
    ceiling = int(getattr(config, "compile_ceiling", 64))
    unbounded = [(s, n) for s in sites for n in s.unbounded]
    telemetry.count(C_ENTRIES, len(sites), category="analysis")
    telemetry.count(C_BOUND, total, category="analysis")
    if unbounded:
        telemetry.count(C_UNBOUNDED, len(unbounded), category="analysis")
    if unbounded:
        detail = "; ".join(
            "%s:%d `%s` static arg `%s` has no registered domain "
            "(unbounded recompiles)" % (s.path, s.line, s.func, n)
            for s, n in unbounded[:3])
        ok = False
    elif total > ceiling:
        detail = ("analytic compile bound %d exceeds ceiling %d"
                  % (total, ceiling))
        ok = False
    else:
        detail = ("%d jit sites, compile bound %d <= ceiling %d "
                  "(serve ladder %d, mm ladder %d)"
                  % (len(sites), total, ceiling, ladder, mm_ladder))
        ok = True
    return [AuditResult(name="compile_surface", ok=ok, detail=detail)]
