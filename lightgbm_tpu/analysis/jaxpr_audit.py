"""Jaxpr/HLO structural audits of the real TPU entry points.

Where the AST linter reasons about source text, this module traces the
actual hot-path programs with abstract inputs (``jax.make_jaxpr`` — no
device execution, runs fine on CPU) and asserts invariants on the IR:

* **persist-f32 kernels stay f32** — no ``convert_element_type`` to
  f64 anywhere in the jaxprs of ``hist_window`` (both variants),
  ``scan_pair``, ``scan_blocks``, the persist ``split_pass``, or the
  batched level-program kernels (``level_pass`` / ``level_seg_hist`` /
  the wide ``scan_pair`` batch the level split-find feeds). This
  is the machine-checked half of the tie-flip characterization
  (tests/test_known_divergence.py tracks the residual v1-vs-persist
  gap; this audit pins that the persist side cannot silently widen).
* **no host callbacks/transfers inside loop bodies** — the predict
  traversal's ``fori_loop``/``scan`` bodies (and the kernels') must be
  free of ``pure_callback``/``io_callback``/``debug_callback``/
  ``device_put``: one of those inside a loop serializes the pipeline
  per level instead of per batch.
* **donation is real** — the predict runtime's jit wrapper must record
  input-output aliasing in its lowered IR when donation is requested,
  and the persist split kernel must alias its payload in/out (the
  in-place partition the whole design assumes).
* **the serve ladder bound holds analytically** — every batch size in
  [1, max_batch] maps into at most ceil(log2(max/min)) + 1 buckets.

Each audit returns an :class:`AuditResult`.

The traversal layer lives in :mod:`dataflow` since PR 13: one shared
walk covers every sub-jaxpr carrier (``pjit``, ``scan``, ``while``,
``cond``, ``custom_jvp/vjp``, ``closed_call``) AND the consts closed
over inside them — the old per-check recursion missed an f64 constant
captured in a ``custom_jvp`` body because consts are not equation
outputs.  The f64-free walk, the host-prim-in-loop check, and the
aliasing checks are now small queries against that engine.  Setting
``LGBTPU_SEED_CUSTOM_JVP_F64=1`` arms the seeded regression fixture
(an f64 constant closed over inside a ``jax.custom_jvp`` body) as a
live audit, flipping the gate to exit 1 — the machine-checked proof
the detector detects.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..telemetry import events as telemetry
from . import dataflow
from .dataflow import alias_sites, find_f64_consts, iter_eqns  # noqa: F401 — re-exported audit queries

C_AUDIT_FAIL = "analysis::audit_fail"

# re-exported for the transfer auditor and older callers
_HOST_PRIMS = dataflow.HOST_PRIMS

_F64 = np.dtype("float64")

SEED_CUSTOM_JVP_ENV = "LGBTPU_SEED_CUSTOM_JVP_F64"


@dataclass
class AuditResult:
    name: str
    ok: bool
    detail: str = ""
    skipped: bool = False

    def to_dict(self) -> dict:
        return {"name": self.name, "ok": self.ok, "detail": self.detail,
                "skipped": self.skipped}


# ---------------------------------------------------------------------------
# jaxpr queries (all on the shared dataflow walk)
# ---------------------------------------------------------------------------

def find_f64_converts(jaxpr) -> List[str]:
    out = []
    for eqn, _ in iter_eqns(jaxpr):
        if eqn.primitive.name == "convert_element_type" \
                and np.dtype(eqn.params.get("new_dtype")) == _F64:
            out.append(str(eqn))
    return out


def find_f64_outputs(jaxpr) -> List[str]:
    """Ops *producing* f64 anywhere (stricter than converts: catches f64
    constants and dtype-defaulted iota/broadcast)."""
    out = []
    for eqn, _ in iter_eqns(jaxpr):
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is not None \
                    and getattr(aval, "dtype", None) == _F64:
                out.append("%s -> %s" % (eqn.primitive.name, aval))
    return out


def find_host_prims_in_loops(jaxpr) -> List[str]:
    out = []
    for eqn, depth in iter_eqns(jaxpr):
        if depth > 0 and eqn.primitive.name in _HOST_PRIMS:
            out.append(eqn.primitive.name)
    return out


def _audit_jaxpr(name: str, closed, forbid_f64: bool = True,
                 strict_f64: bool = False) -> AuditResult:
    jaxpr = closed.jaxpr
    problems: List[str] = []
    if forbid_f64:
        finder = find_f64_outputs if strict_f64 else find_f64_converts
        hits = finder(jaxpr)
        if strict_f64:
            # consts are not equation outputs: an f64 array closed over
            # (even one narrowed immediately inside a custom_jvp body)
            # only shows up on the const walk
            hits = find_f64_consts(closed) + hits
        if hits:
            problems.append("f64 values in a persist-f32 program: %s"
                            % "; ".join(hits[:3]))
    loops = find_host_prims_in_loops(jaxpr)
    if loops:
        problems.append("host/transfer primitives inside loop bodies: %s"
                        % ", ".join(sorted(set(loops))))
    return AuditResult(name=name, ok=not problems,
                       detail="; ".join(problems))


# ---------------------------------------------------------------------------
# individual audits
# ---------------------------------------------------------------------------

def audit_hist_window() -> AuditResult:
    """Both histogram kernel variants (radix W=256, one-hot W<=64) trace
    f64-free with f32 gradients."""
    name = "hist_window_f32"
    from ..ops.pallas_histogram import hist_window
    problems = []
    for w, G, C in ((256, 3, 1024), (64, 5, 512)):
        bins = jax.ShapeDtypeStruct((G, C), jnp.int32)
        vec = jax.ShapeDtypeStruct((C,), jnp.float32)
        closed = jax.make_jaxpr(
            lambda b, g, h, _w=w: hist_window(b, g, h, w=_w))(
                bins, vec, vec)
        r = _audit_jaxpr(name, closed, strict_f64=True)
        if not r.ok:
            problems.append("w=%d: %s" % (w, r.detail))
    return AuditResult(name=name, ok=not problems,
                       detail="; ".join(problems))


def audit_scan_pair() -> AuditResult:
    name = "scan_pair_f32"
    from ..ops.pallas_scan import scan_pair
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
    return _audit_jaxpr(name, closed, strict_f64=True)


def audit_scan_blocks() -> AuditResult:
    name = "scan_blocks_f32"
    from ..ops.pallas_scan import BM_ROWS, scan_blocks
    Gp, Wp = 8, 128
    f32 = jnp.float32
    closed = jax.make_jaxpr(
        lambda s, g, h, m: scan_blocks(s, g, h, m, do_fix=True))(
            jax.ShapeDtypeStruct((2, 9), f32),
            jax.ShapeDtypeStruct((2, Gp, Wp), f32),
            jax.ShapeDtypeStruct((2, Gp, Wp), f32),
            jax.ShapeDtypeStruct((BM_ROWS, Gp, Wp), f32))
    return _audit_jaxpr(name, closed, strict_f64=True)


def audit_persist_split_pass() -> AuditResult:
    """The Mosaic split_pass on a toy payload geometry: f64-free, and
    the payload must be donated (input_output_aliases) — the in-place
    partition contract."""
    name = "persist_split_pass"
    from ..ops.pallas_grow import make_split_pass
    WPA, NP, G, nbw = 8, 1024, 2, 2
    plan = ((0, 0, 255), (1, 0, 255))
    sp = make_split_pass(WPA, NP, G, plan, nbw, C=256)
    closed = jax.make_jaxpr(sp)(
        jax.ShapeDtypeStruct((WPA, NP), jnp.uint32),
        jax.ShapeDtypeStruct((16,), jnp.int32))
    res = _audit_jaxpr(name, closed, strict_f64=True)
    if not res.ok:
        return res
    aliased = any(ioa for prim, ioa in alias_sites(closed.jaxpr)
                  if "pallas_call" in prim)
    if not aliased:
        return AuditResult(
            name=name, ok=False,
            detail="split_pass pallas_call lost its payload "
                   "input_output_aliases (in-place partition broken)")
    return res


def audit_persist_level_pass() -> AuditResult:
    """The batched LEVEL program kernels (PR 7) on a toy payload
    geometry: the multi-leaf ``level_pass`` must trace f64-free and keep
    the payload ``input_output_aliases`` (the in-place multi-leaf
    partition contract — one lost alias turns every level into a full
    payload copy); the batched ``level_seg_hist`` and a wider-than-pair
    ``scan_pair`` batch (the level split-find shape) must also stay
    f32. This is the level-program extension of
    :func:`audit_persist_split_pass` — the level path batches S leaves
    per launch, so a silent widening or alias loss costs S× more than
    on the per-split path."""
    name = "persist_level_pass"
    from ..ops.pallas_grow import make_level_pass, make_level_seg_hist
    from ..ops.pallas_scan import scan_pair
    WPA, NP, G, nbw = 8, 1024, 2, 2
    plan = ((0, 0, 255), (1, 0, 255))
    S_max, T_max = 4, 16
    i32 = jnp.int32
    lp = make_level_pass(WPA, NP, G, plan, nbw, S_max, T_max, C=256)
    closed = jax.make_jaxpr(lp)(
        jax.ShapeDtypeStruct((WPA, NP), jnp.uint32),
        jax.ShapeDtypeStruct((S_max, 16), i32),
        jax.ShapeDtypeStruct((T_max,), i32),
        jax.ShapeDtypeStruct((S_max,), i32),
        jax.ShapeDtypeStruct((), i32))
    res = _audit_jaxpr(name, closed, strict_f64=True)
    if not res.ok:
        return res
    aliased = any(ioa for prim, ioa in alias_sites(closed.jaxpr)
                  if "pallas_call" in prim)
    if not aliased:
        return AuditResult(
            name=name, ok=False,
            detail="level_pass pallas_call lost its payload "
                   "input_output_aliases (in-place multi-leaf "
                   "partition broken)")
    ls = make_level_seg_hist(WPA, NP, G, plan, nbw, S_max, T_max, C=256)
    closed_s = jax.make_jaxpr(ls)(
        jax.ShapeDtypeStruct((WPA, NP), jnp.uint32),
        jax.ShapeDtypeStruct((S_max, 4), i32),
        jax.ShapeDtypeStruct((T_max,), i32),
        jax.ShapeDtypeStruct((S_max,), i32),
        jax.ShapeDtypeStruct((), i32))
    res_s = _audit_jaxpr(name, closed_s, strict_f64=True)
    if not res_s.ok:
        return res_s
    B, Fp, Wp = 2 * S_max, 8, 128
    f32 = jnp.float32
    closed_b = jax.make_jaxpr(scan_pair)(
        jax.ShapeDtypeStruct((B, 8), f32),
        jax.ShapeDtypeStruct((B, Fp, Wp), f32),
        jax.ShapeDtypeStruct((B, Fp, Wp), f32),
        jax.ShapeDtypeStruct((Fp, Wp), f32),
        jax.ShapeDtypeStruct((Fp, Wp), f32),
        jax.ShapeDtypeStruct((Fp, Wp), f32),
        jax.ShapeDtypeStruct((Fp, Wp), f32),
        jax.ShapeDtypeStruct((8, Fp), f32))
    return _audit_jaxpr(name, closed_b, strict_f64=True)


def _toy_ensemble(num_class: int = 1):
    """Hand-built 3-tree CompiledEnsemble (two depth buckets, one
    categorical bitset node) — no training required. With num_class=3
    the 3 trees become one iteration of 3 classes, which makes the raw
    output shape [rows, 3] match an X of 3 features — the geometry the
    donation audit needs for input-output aliasing to be legal."""
    from ..predict.compile import CompiledEnsemble, TreeBucket
    i32 = np.int32
    b1 = TreeBucket(
        depth=2,
        tree_pos=np.array([0, 2], i32),
        split_feature=np.array([[0, 1, 0], [1, 0, 2]], i32),
        threshold=np.array([[0.5, -1.0, 1.0], [0.0, 0.25, 0.5]]),
        decision_type=np.array([[2, 0, 0], [1, 0, 2]], i32),
        left=np.array([[1, -1, -3], [1, -1, -3]], i32),
        right=np.array([[2, -2, -4], [2, -2, -4]], i32),
        leaf_value=np.array([[0.1, -0.2, 0.3, -0.4],
                             [0.5, -0.6, 0.7, -0.8]]),
        cat_offset=np.array([[0, 0, 0], [0, 0, 0]], i32),
        cat_nwords=np.array([[0, 0, 0], [1, 0, 0]], i32),
        cat_words=np.array([0b1010], np.uint32))
    b2 = TreeBucket(
        depth=1,
        tree_pos=np.array([1], i32),
        split_feature=np.array([[2]], i32),
        threshold=np.array([[0.0]]),
        decision_type=np.array([[0]], i32),
        left=np.array([[-1]], i32),
        right=np.array([[-2]], i32),
        leaf_value=np.array([[0.05, -0.05]]),
        cat_offset=np.array([[0]], i32),
        cat_nwords=np.array([[0]], i32),
        cat_words=np.array([0], np.uint32))
    return CompiledEnsemble(buckets=(b1, b2), num_trees=3,
                            num_tree_per_iteration=num_class,
                            average_output=False, max_feature_idx=2)


def audit_predict_traversal() -> AuditResult:
    """The f32 predict runtime traces f64-free and keeps its
    fori_loop/scan bodies free of host callbacks/transfers."""
    from ..predict.runtime import TPUPredictor
    name = "predict_traversal_f32"
    pred = TPUPredictor(_toy_ensemble(), dtype="f32", donate=False)
    X = jax.ShapeDtypeStruct((64, 3), jnp.float32)
    closed = jax.make_jaxpr(
        lambda x: pred._forward_raw(x, False))(X)
    return _audit_jaxpr(name, closed, strict_f64=True)


def audit_predict_donation() -> AuditResult:
    """With donation requested, the lowered predict program must record
    input-output buffer aliasing (jax drops donation silently when the
    wrapper loses the donate_argnums — this pins it structurally). Uses
    the 3-class toy so the [rows, K] output is alias-compatible with the
    [rows, F] input; an alias-incompatible program cannot witness
    donation at all."""
    import warnings

    from ..predict.runtime import TPUPredictor
    name = "predict_donation"
    pred = TPUPredictor(_toy_ensemble(num_class=3), dtype="f32",
                        donate=True)
    X = jax.ShapeDtypeStruct((64, 3), jnp.float32)
    with warnings.catch_warnings():
        # CPU emits "donated buffers were not usable" for the aliases it
        # cannot honor; the audit reads the IR, not the backend support
        warnings.simplefilter("ignore")
        txt = pred._raw_fn.lower(X, False).as_text()
    ok = ("tf.aliasing_output" in txt) or ("jax.buffer_donor" in txt)
    return AuditResult(
        name=name, ok=ok,
        detail="" if ok else "donate=True produced no input-output "
                             "aliasing in the lowered IR")


def audit_serve_ladder() -> AuditResult:
    """Every batch size in [1, max_batch] lands in at most
    ceil(log2(max/min)) + 1 buckets — the compile bound BatchServer
    guarantees and predict::serve_compile pins at runtime."""
    from ..predict.serve import BatchServer
    name = "serve_ladder_bound"

    class _Stub:
        _dtype = jnp.float32
    problems = []
    for mn, mx in ((256, 1 << 16), (64, 1024), (128, 128)):
        srv = BatchServer.__new__(BatchServer)
        srv.min_batch = mn
        srv.max_batch = mx
        buckets = {srv.bucket_rows(n) for n in range(1, mx + 1)}
        bound = int(np.log2(mx // mn)) + 1
        if len(buckets) > bound:
            problems.append("ladder [%d, %d]: %d buckets > bound %d"
                            % (mn, mx, len(buckets), bound))
    return AuditResult(name=name, ok=not problems,
                       detail="; ".join(problems))


def build_fused_iteration_programs():
    """Trace the fused boosting-iteration drivers (PR 17) on a toy
    binary dataset: the gbdt k-batch scan and the RF variant, both as
    unjitted bodies (``wrap_jit=False`` — the jaxpr walk needs the
    scan structure, not the launch wrapper), plus the lowered-IR
    donation witness for the jitted gbdt driver (the payload carry
    must alias input to output or every batch pays a full payload
    copy). Built once per process through ``precision_audit._memo``
    so transfer_audit walks the SAME traces. Returns
    ``{"programs": [(name, ClosedJaxpr), ...], "donated": bool}``."""
    import warnings

    from ..config import Config
    from ..data.dataset import BinnedDataset
    from ..objectives.base import create_objective
    from ..ops.grow_persist import (build_assets, make_persist_grower,
                                    make_scan_driver)
    from ..treelearner.serial import SerialTreeLearner

    rng = np.random.RandomState(7)
    n, F, k = 256, 6, 2
    X = rng.rand(n, F)
    y = (rng.rand(n) > 0.5).astype(np.float64)
    cfg = Config({"objective": "binary", "num_leaves": 7,
                  "max_bin": 63, "verbosity": -1})
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    learner = SerialTreeLearner(cfg, ds)
    obj = create_objective("binary", cfg)
    obj.init(ds.metadata, ds.num_data)
    # score64: the off-TPU trace carries the v1-parity f64 score
    # emulation — the mode DART/RF bit-exactness rides on
    assets = build_assets(ds, ds.metadata.label, score64=True)
    gr = make_persist_grower(assets, learner.meta, learner.grow_config,
                             kernel_impl="xla")
    gmode, gfn = obj.device_gradients()
    gc = learner.grow_config
    pay = gr.init_carry(jnp.asarray(assets.pay0),
                        jnp.zeros((n,), jnp.float64))
    fmasks = jnp.ones((k, gc.num_features), bool)
    iters = jnp.arange(k, dtype=jnp.int32)
    run = make_scan_driver(gr, gc, k, gfn, grad_mode=gmode,
                           wrap_jit=False)
    gbdt_args = (pay, fmasks, jnp.zeros((k, 2), jnp.uint32), iters,
                 learner.params, jnp.asarray(0.1, jnp.float64), ())
    run_rf = make_scan_driver(gr, gc, k, gfn, mode="rf",
                              wrap_jit=False)
    t = jnp.arange(k, dtype=jnp.float64)
    closed_r = jax.make_jaxpr(run_rf)(
        pay, fmasks, jnp.ones((k, n), jnp.float32),
        jnp.stack([t, 1.0 / (t + 1.0)], axis=1), iters,
        learner.params, jnp.asarray(0.25, jnp.float64))
    with warnings.catch_warnings():
        # CPU warns about donated buffers it cannot honor; the audit
        # reads the IR, not the backend support
        warnings.simplefilter("ignore")
        # one trace serves both the jaxpr walk and the donation
        # witness in the lowered IR
        traced = jax.jit(run, donate_argnums=(0,)).trace(*gbdt_args)
        closed_g = traced.jaxpr
        txt = traced.lower().as_text()
    donated = ("tf.aliasing_output" in txt) or ("jax.buffer_donor" in txt)
    # the fixture only traces the drivers — no stats ever accumulate —
    # but the flush discipline the health audit pins still applies to
    # the owner of any driver site, and on an untrained learner this is
    # an immediate no-op
    learner.flush_level_stats()
    return {"programs": [("fused_iter_gbdt", closed_g),
                         ("fused_iter_rf", closed_r)],
            "donated": donated}


def audit_fused_iteration() -> AuditResult:
    """The whole-iteration fused programs (PR 17): the objectives'
    device gradient kernels must trace strictly f64-free in the
    persist-f32 contract; the gbdt and RF k-iteration drivers must
    keep their scan bodies free of host callbacks/transfers (tree
    boundaries never leave the device); and the jitted gbdt driver
    must witness payload donation in the lowered IR (the carry
    aliasing the whole fast path leans on). The driver traces run the
    score64 emulation, so the f64 ban applies to the standalone
    gradient kernels — the only new math the fusion moved on-device —
    not the (deliberately) widened score rows."""
    from . import precision_audit as pa
    name = "fused_iteration"
    problems: List[str] = []
    for gname, closed, _rng, _bless in pa._memo(
            "fused_grads", pa._fused_grad_programs):
        r = _audit_jaxpr(gname, closed, strict_f64=True)
        if not r.ok:
            problems.append("%s: %s" % (gname, r.detail))
    art = pa._memo("fused_drivers", build_fused_iteration_programs)
    for dname, closed in art["programs"]:
        loops = find_host_prims_in_loops(closed.jaxpr)
        if loops:
            problems.append(
                "%s: host/transfer primitives inside the iteration "
                "scan: %s" % (dname, ", ".join(sorted(set(loops)))))
    if not art["donated"]:
        problems.append("fused_iter_gbdt: donation produced no payload "
                        "input-output aliasing in the lowered IR "
                        "(every batch would copy the payload)")
    return AuditResult(name=name, ok=not problems,
                       detail="; ".join(problems[:3]))


def build_custom_jvp_f64_fixture():
    """The satellite regression fixture: an f64 constant closed over
    inside a ``jax.custom_jvp`` body, narrowed to f32 before use — no
    equation ever OUTPUTS f64 outside a benign staging ``device_put``,
    so the old recursive walk passed it while the f64 data silently
    participated.  Returns the traced ClosedJaxpr."""
    const64 = np.arange(4, dtype=np.float64) * 1.5

    @jax.custom_jvp
    def leaky(x):
        return x * jnp.asarray(const64).astype(jnp.float32)

    @leaky.defjvp
    def leaky_jvp(primals, tangents):
        return leaky(primals[0]), tangents[0]

    return jax.make_jaxpr(lambda x: leaky(x) + jnp.float32(1))(
        jax.ShapeDtypeStruct((4,), jnp.float32))


def audit_seeded_custom_jvp_f64() -> AuditResult:
    """Armed by ``LGBTPU_SEED_CUSTOM_JVP_F64=1``: runs the strict f64
    audit against the seeded fixture, which MUST fail — proving the
    const-aware walk sees through custom_jvp call primitives."""
    res = _audit_jaxpr("seeded_custom_jvp_f64",
                       build_custom_jvp_f64_fixture(), strict_f64=True)
    if res.ok:
        return AuditResult(
            name="seeded_custom_jvp_f64", ok=False,
            detail="the seeded f64-const-in-custom_jvp fixture passed "
                   "the strict f64 audit — the const walk regressed")
    return res


AUDITS: Tuple[Callable[[], AuditResult], ...] = (
    audit_hist_window,
    audit_scan_pair,
    audit_scan_blocks,
    audit_persist_split_pass,
    audit_persist_level_pass,
    audit_predict_traversal,
    audit_predict_donation,
    audit_serve_ladder,
    audit_fused_iteration,
)


def run_audits(names: Optional[List[str]] = None) -> List[AuditResult]:
    """Run all (or the named) audits; an audit that raises reports as a
    failed result rather than killing the gate."""
    audits = AUDITS
    if os.environ.get(SEED_CUSTOM_JVP_ENV, "") not in ("", "0"):
        # the seeded true-positive: flips the gate to exit 1 on demand
        audits = audits + (audit_seeded_custom_jvp_f64,)
    out: List[AuditResult] = []
    for fn in audits:
        nm = fn.__name__.replace("audit_", "")
        if names and nm not in names and fn.__name__ not in names:
            continue
        try:
            out.append(fn())
        except Exception as e:  # pragma: no cover - defensive
            out.append(AuditResult(name=nm, ok=False,
                                   detail="audit raised: %r" % e))
    failed = sum(1 for r in out if not r.ok)
    if failed:
        telemetry.count(C_AUDIT_FAIL, failed, category="analysis")
    return out
