"""Precision-flow auditor: every float narrowing must earn its place.

The repo's worst historical bug class is silent precision drift — the
persist-f32 vs v1-f64 tie-flip took three PRs to pin because an f64
value joined f32 math, shifted a noise-gain split's tie, and two
otherwise-identical runs grew different trees.  The strict jaxpr audit
forbids f64 *inside* the persist-f32 kernels; this auditor covers the
other direction: the **narrowing sites** (f64 -> f32/bf16/f16,
f32 -> bf16/f16) in the traced ``ops/``/``predict/`` programs.  Each
site must be either

* **blessed** — listed in the owning module's ``NARROW_OK`` table (the
  histogram kernel's bf16 hi/lo split is exact by construction and
  blessed in ``ops/pallas_histogram.py``), or
* **proven** — the :mod:`dataflow` interpreter, seeded from the
  module's ``*_input_contract`` annotation, proves a bounded range
  that fits the target dtype AND the narrowed value does not directly
  feed a comparison/argmax.  A *decision-relevant* narrowing can never
  be range-proven: the tie lives inside the discarded mantissa bits —
  that is the tie-flip geometry, and it is this auditor's seeded
  true-positive fixture (``check_fixture({"program": "tie_flip"})``;
  ``LGBTPU_SEED_TIE_FLIP=1`` arms it as a live audit and flips the
  gate to exit 1).

Source-level twin: lint rule JG010 flags ``.astype``/``jnp.asarray``
narrowing in non-allowlisted ``ops/``/``predict/`` files before it is
even traced.
"""
from __future__ import annotations

import os
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..telemetry import events as telemetry
from . import dataflow
from .config import GraftlintConfig
from .jaxpr_audit import AuditResult, _toy_ensemble

C_NARROW = "analysis::narrowing_sites"

SEED_TIE_FLIP_ENV = "LGBTPU_SEED_TIE_FLIP"


# ---------------------------------------------------------------------------
# audited programs
#
# Tracing is the expensive half of an audit pass (jax.make_jaxpr plus,
# for predict, a TPUPredictor build), and transfer_audit walks the SAME
# scan_pair/predict programs — so every builder memoizes its traced
# closures once per process and both auditors share them.
# ---------------------------------------------------------------------------

_PROGRAM_MEMO: dict = {}


def _memo(name: str, builder):
    if name not in _PROGRAM_MEMO:
        _PROGRAM_MEMO[name] = builder()
    return _PROGRAM_MEMO[name]


def _hist_prologue():
    """hist_window at both kernel variants: the f32 -> bf16 hi/lo split
    sites, blessed by ops/pallas_histogram.NARROW_OK."""
    from ..ops.pallas_histogram import (NARROW_OK, hist_input_contract,
                                        hist_window)
    out = []
    for w, G, C in ((256, 3, 1024), (64, 5, 512)):
        contract = hist_input_contract(w=w, rows=C)
        closed = jax.make_jaxpr(
            lambda b, g, h, _w=w: hist_window(b, g, h, w=_w))(
                jax.ShapeDtypeStruct((G, C), jnp.int32),
                jax.ShapeDtypeStruct((C,), jnp.float32),
                jax.ShapeDtypeStruct((C,), jnp.float32))
        out.append(("hist_window[w=%d]" % w, closed,
                    {0: contract["bins_t"], 1: contract["grad"],
                     2: contract["hess"]}, NARROW_OK))
    return out


def _scan_pair_program():
    from ..ops.pallas_scan import (NARROW_OK, scan_input_contract,
                                   scan_pair)
    rows = 1 << 20
    contract = scan_input_contract(rows)
    Fp, Wp = 8, 128
    f32 = jnp.float32
    closed = jax.make_jaxpr(scan_pair)(
        jax.ShapeDtypeStruct((2, 8), f32),
        jax.ShapeDtypeStruct((2, Fp, Wp), f32),
        jax.ShapeDtypeStruct((2, Fp, Wp), f32),
        jax.ShapeDtypeStruct((Fp, Wp), f32),
        jax.ShapeDtypeStruct((Fp, Wp), f32),
        jax.ShapeDtypeStruct((Fp, Wp), f32),
        jax.ShapeDtypeStruct((Fp, Wp), f32),
        jax.ShapeDtypeStruct((8, Fp), f32))
    return [("scan_pair", closed,
             {0: contract["counts"], 1: contract["gb"],
              2: contract["hb"]}, NARROW_OK)]


def _predict_program():
    from ..predict.compile import NARROW_OK
    from ..predict.runtime import TPUPredictor
    pred = TPUPredictor(_toy_ensemble(), dtype="f32", donate=False)
    closed = jax.make_jaxpr(
        lambda x: pred._forward_raw(x, False))(
            jax.ShapeDtypeStruct((64, 3), jnp.float32))
    return [("predict_forward", closed, {0: (-256.0, 256.0)},
             NARROW_OK)]


def _fused_grad_programs():
    """The fused boosting iteration's device gradient kernels (PR 17)
    traced in the persist-f32 payload contract: f32 score/label rows
    in, (grad, hess) out — binary and regression in 'payload' mode,
    multiclass softmax in the K-class snapshot mode. Input ranges
    mirror ops/grow_persist.persist_input_contract (scores bounded by
    the boosting trajectory, labels by their encoding); the strict
    f64-free check on the same traces lives in
    jaxpr_audit.audit_fused_iteration."""
    from types import SimpleNamespace

    import numpy as np

    from ..config import Config
    from ..objectives.base import create_objective

    f32 = jnp.float32
    vec = jax.ShapeDtypeStruct((128,), f32)
    score_rng = (-256.0, 256.0)
    progs = []
    lab = np.asarray([0.0, 1.0] * 8, np.float32)
    meta = SimpleNamespace(label=lab, weight=None)

    obj_b = create_objective("binary", Config(
        {"objective": "binary", "verbosity": -1}))
    obj_b.init(meta, len(lab))
    _mode, fn_b = obj_b.device_gradients()
    progs.append(("fused_grad_binary", jax.make_jaxpr(fn_b)(vec, vec),
                  {0: score_rng, 1: (0.0, 1.0)}, ()))

    obj_r = create_objective("regression", Config(
        {"objective": "regression", "verbosity": -1}))
    obj_r.init(SimpleNamespace(label=np.zeros(16, np.float32),
                               weight=None), 16)
    _mode, fn_r = obj_r.device_gradients()
    progs.append(("fused_grad_regression",
                  jax.make_jaxpr(fn_r)(vec, vec),
                  {0: score_rng, 1: score_rng}, ()))

    obj_m = create_objective("multiclass", Config(
        {"objective": "multiclass", "num_class": 3, "verbosity": -1}))
    obj_m.init(SimpleNamespace(
        label=(np.arange(16) % 3).astype(np.float32), weight=None), 16)
    _mode, fn_m = obj_m.device_gradients()
    progs.append(("fused_grad_multiclass",
                  jax.make_jaxpr(lambda s, l: fn_m(s, l, 1))(
                      jax.ShapeDtypeStruct((3, 128), f32), vec),
                  {0: score_rng, 1: (0.0, 2.0)}, ()))
    return progs


def _tie_flip_program():
    """The seeded true-positive: split gains computed in f64, narrowed
    to f32 BEFORE the argmax — the exact tie-flip geometry.  The
    contract bounds every input, yet the site must still fail: the
    narrowed value feeds the decision, and one child's ``H + lambda``
    denominator straddles zero under the abstract ranges, so neither
    blessing nor proof exists."""
    n = 4096.0

    def gains(gl, hl, gp, hp):
        lam = jnp.float64(1.0)
        gr = gp - gl
        hr = hp - hl
        gain = (gl * gl) / (hl + lam) + (gr * gr) / (hr + lam) \
            - (gp * gp) / (hp + lam)
        g32 = gain.astype(jnp.float32)      # narrowed before the decision
        return jnp.max(g32), jnp.argmax(g32)

    f64 = jnp.float64
    shape = jax.ShapeDtypeStruct((256,), f64)
    closed = jax.make_jaxpr(gains)(shape, shape, shape, shape)
    return [("tie_flip", closed,
             {0: (-n, n), 1: (0.0, n / 4), 2: (-n, n),
              3: (0.0, n / 4)}, ())]


def _bounded_narrow_program():
    """Clean twin: an f64 -> f32 narrowing whose contract-proven range
    fits f32 and that feeds plain arithmetic, not a comparison."""
    def scale(x):
        y = (x * jnp.float64(0.5)).astype(jnp.float32)
        return y + jnp.float32(1.0)

    closed = jax.make_jaxpr(scale)(
        jax.ShapeDtypeStruct((128,), jnp.float64))
    return [("bounded_narrow", closed, {0: (-1000.0, 1000.0)}, ())]


# ---------------------------------------------------------------------------
# the audit
# ---------------------------------------------------------------------------

def _violations(name: str, closed, ranges, blessed
                ) -> Tuple[List[str], int]:
    """(violation strings, narrowing-site count) for one program."""
    rep = dataflow.interpret(closed, in_ranges=ranges)
    bless = {tuple(p) for p in blessed}
    bad = []
    for site in rep.narrowings:
        if (site.src, site.dst) in bless:
            continue
        if site.weak_src and not site.decision_relevant:
            # a weak-typed scalar round-trip (python float promoted
            # under x64, narrowed straight back) is the JG003 source
            # class, policed at the AST layer — unless it decides
            continue
        if site.decision_relevant:
            bad.append("%s: %s — decision-relevant narrowing must be "
                       "blessed (the tie-flip class)"
                       % (name, site.describe()))
        elif not site.fits:
            bad.append("%s: %s — range not proven to fit %s"
                       % (name, site.describe(), site.dst))
    return bad, len(rep.narrowings)


def _programs(include_seeded: bool) -> List[Tuple]:
    progs: List[Tuple] = []
    progs += _memo("hist_prologue", _hist_prologue)
    progs += _memo("scan_pair", _scan_pair_program)
    progs += _memo("predict", _predict_program)
    progs += _memo("fused_grads", _fused_grad_programs)
    if include_seeded:
        progs += _tie_flip_program()
    return progs


def compute_artifact(config: Optional[GraftlintConfig] = None) -> dict:
    """One engine pass over the audited programs; shared by run() and
    the --json payload builder."""
    include_seeded = os.environ.get(SEED_TIE_FLIP_ENV, "") \
        not in ("", "0")
    violations: List[str] = []
    n_sites = 0
    names = []
    for name, closed, ranges, blessed in _programs(include_seeded):
        bad, n = _violations(name, closed, ranges, blessed)
        violations += bad
        n_sites += n
        names.append(name)
    return {"programs": names, "violations": violations,
            "narrowing_sites": n_sites,
            "seeded": include_seeded}


def run(config: Optional[GraftlintConfig] = None,
        artifact=None) -> List[AuditResult]:
    name = "precision_flow"
    try:
        art = artifact if isinstance(artifact, dict) \
            else compute_artifact(config)
    except Exception as e:      # pragma: no cover - defensive
        return [AuditResult(name=name, ok=False,
                            detail="auditor raised: %r" % e)]
    telemetry.count(C_NARROW, art["narrowing_sites"],
                    category="analysis")
    ok_detail = ("%d narrowing site(s) across %d program(s), all "
                 "blessed or range-proven"
                 % (art["narrowing_sites"], len(art["programs"])))
    return [AuditResult(
        name=name, ok=not art["violations"],
        detail="; ".join(art["violations"][:3]) if art["violations"]
        else ok_detail)]


def check_fixture(payload: dict) -> List[str]:
    """Uniform fixture hook: {"program": "tie_flip" | "bounded_narrow"}
    — the seeded tie-flip geometry must be flagged, the range-proven
    narrowing must not."""
    program = payload["program"]
    builders: dict = {"tie_flip": _tie_flip_program,
                      "bounded_narrow": _bounded_narrow_program}
    out: List[str] = []
    for name, closed, ranges, blessed in builders[program]():
        bad, _ = _violations(name, closed, ranges, blessed)
        out += bad
    return out
