"""Quantization certifier: static error bounds for the quantized paths.

ROADMAP item 2 ships int8/int16 histogram payloads with stochastic
rounding over DCN (the PV-Tree regime); item 3 ships f16 leaf/threshold
serving tensors.  Both narrow the numerics exactly where the tie-flip
lived — so this auditor certifies the quantization contracts BEFORE
those PRs land, and emits a machine-checkable ``quant_certificate``
block in ``--json`` that they must ship green against.

**Histogram planes** (``kind: "histogram"``).  Input contract (seeded
from ``ops/pallas_histogram.hist_input_contract`` /
``ops/grow_persist.persist_input_contract``): per-row |grad| <= g_max,
0 <= hess <= h_max, so every per-rank bin sum AND every prefix/subset
sum is capped by ``S = rows_per_rank * cap``.  Each rank quantizes its
[G, W] planes symmetrically at that contract scale (step
``delta = 2 S / (2^bits - 2)``) with *stochastic rounding*: per-entry
error is zero-mean and bounded by ``delta``.  A split decision reads
prefix sums over at most ``W`` bins of ``R`` rank contributions —
``N = W * R`` independent bounded zero-mean errors — so by Hoeffding
the accumulated error stays within ``E = delta * sqrt(2 N ln(2/CONF))``
except with probability :data:`CONFIDENCE` per decision (the
deterministic worst case ``N * delta`` is also reported).  The
certified decision domain is the PV-Tree candidate regime: splits
whose children each hold at least :data:`H_CHILD_FRAC` of the total
hessian mass (top-k voted features are exactly the high-mass ones).
Over that domain the split-gain perturbation is bounded through the
gain's partial derivatives (``gain = G^2/(H + lambda)``, three terms:
left/right/parent)::

    d_eff  = lambda + H_CHILD_FRAC * S_h_global - E_H   (must be > 0)
    dgain <= 3 * (2 * S_g_global / d_eff * E_G
                  + (S_g_global / d_eff)^2 * E_H)

and the certificate's headline number is ``dgain`` relative to the
certified-domain gain cap ``S_g_global^2 / (lambda + frac * S_h)``,
gated against the pinned :data:`SPLIT_DECISION_BUDGET`.  int16 at the
higgs/expo geometries certifies with margin; int8 at full plane scale
blows the budget by >100x — the registry fixture pins both, and
``tests/test_dataflow.py`` checks the bound against an empirical max
over 1k random payloads.

**Leaf/threshold tensors** (``kind: "leaf"``, spec from
``predict/compile.quant_spec``).  f16 keeps 11 mantissa bits: each
stored leaf is within relative ``2^-11`` of its f64 value, so the
ensemble output error is ``num_trees * leaf_abs_max * 2^-11`` absolute
— relative ``2^-11`` of the output scale — and an f16 threshold moves
each decision boundary by at most relative ``2^-11``; both gate
against :data:`PREDICT_REL_BUDGET`.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

from ..telemetry import events as telemetry
from .config import GraftlintConfig
from .jaxpr_audit import AuditResult

C_CERTIFIED = "analysis::quant_certified"

# pinned budgets: the split-decision budget is the relative split-gain
# perturbation a certified quantization may induce over the certified
# decision domain; the predict budget is the relative output/boundary
# error the serving tensors may carry
SPLIT_DECISION_BUDGET = 0.05
PREDICT_REL_BUDGET = 1e-3

# certified decision domain: each child of a certified split holds at
# least this fraction of the total hessian mass (the PV-Tree top-k
# candidate regime — low-mass splits are exactly the ones voting prunes)
H_CHILD_FRAC = 0.25
# per-decision failure probability of the Hoeffding accumulation bound
CONFIDENCE = 1e-9

# total rows of the two geometries the gate certifies int16 planes at
# (cut over 8 ranks in default_specs): the reference's HIGGS experiment
# and the 2M-row one-hot shape the quantized exchange was built against.
# The certificate needs nothing else of a shape (bins and caps come from
# the histogram's input contract), and these are its own domain, not a
# registry: a real run is certified at config time from its own rows
# (parallel/distributed.resolve_hist_quant).
CERTIFIED_ROWS = {"higgs": 10_500_000, "expo": 2_000_000}

_BITS = {"int8": 8, "int16": 16}
_F16_REL = 2.0 ** -11


def default_specs(config: Optional[GraftlintConfig] = None
                  ) -> List[dict]:
    """The specs the gate certifies every run: int16 histogram planes
    at the higgs and expo bench geometries (contract caps from
    ops/pallas_histogram.hist_input_contract), and the f16 serving
    tensors (predict/compile.quant_spec defaults)."""
    from ..ops.pallas_histogram import hist_input_contract
    from ..predict.compile import quant_spec
    specs = []
    for name, rows in CERTIFIED_ROWS.items():
        ranks = 8
        rows_shard = rows // ranks
        contract = hist_input_contract(w=256, rows=rows_shard)
        specs.append({
            "name": "hist_int16_%s" % name,
            "kind": "histogram",
            "target": "int16",
            "stochastic": True,
            "rows_per_rank": rows_shard,
            "ranks": ranks,
            "bins": 256,
            "g_max": contract["grad"][1],
            "h_max": contract["hess"][1],
            "lambda": 1.0,
        })
    specs.append(quant_spec())
    return specs


def certify(spec: dict) -> dict:
    """One certificate: the spec, every intermediate constant, the
    bound, the budget, and the verdict — machine-checkable, and the
    empirical test recomputes the same numbers."""
    if spec.get("kind") == "histogram":
        return _certify_histogram(spec)
    return _certify_leaf(spec)


def _certify_histogram(spec: dict) -> dict:
    bits = _BITS[spec["target"]]
    rows = int(spec["rows_per_rank"])
    ranks = int(spec["ranks"])
    W = int(spec.get("bins", 256))
    g_max = float(spec.get("g_max", 1.0))
    h_max = float(spec.get("h_max", 0.25))
    lam = float(spec.get("lambda", 1.0))
    stochastic = bool(spec.get("stochastic", True))

    s_g = rows * g_max                 # per-rank plane scale (contract)
    s_h = rows * h_max
    levels = (1 << bits) - 2           # symmetric, one code reserved
    delta_g = 2.0 * s_g / levels
    delta_h = 2.0 * s_h / levels
    n_terms = W * ranks
    hoeffding = math.sqrt(2.0 * n_terms * math.log(2.0 / CONFIDENCE))
    if stochastic:
        e_g = delta_g * hoeffding
        e_h = delta_h * hoeffding
    else:                              # nearest rounding: worst case
        e_g = n_terms * delta_g / 2.0
        e_h = n_terms * delta_h / 2.0
    s_g_global = ranks * s_g
    s_h_global = ranks * s_h
    d = lam + H_CHILD_FRAC * s_h_global
    d_eff = d - e_h
    cert = {
        "spec": dict(spec),
        "scale_grad": s_g, "scale_hess": s_h,
        "step_grad": delta_g, "step_hess": delta_h,
        "accum_terms": n_terms,
        "confidence": CONFIDENCE,
        "err_grad": e_g, "err_hess": e_h,
        "err_grad_worst": n_terms * delta_g,
        "err_hess_worst": n_terms * delta_h,
        "h_child_frac": H_CHILD_FRAC,
        "budget": SPLIT_DECISION_BUDGET,
    }
    if d_eff <= 0.0:
        cert.update(gain_perturbation=float("inf"),
                    bound=float("inf"), ok=False,
                    why="hessian quantization error %.3g swamps the "
                        "certified child mass %.3g" % (e_h, d))
        return cert
    dgain = 3.0 * (2.0 * s_g_global / d_eff * e_g
                   + (s_g_global / d_eff) ** 2 * e_h)
    gain_cap = s_g_global ** 2 / d
    rel = dgain / gain_cap
    cert.update(gain_perturbation=dgain, gain_cap=gain_cap,
                bound=rel, ok=rel <= SPLIT_DECISION_BUDGET,
                margin=(SPLIT_DECISION_BUDGET / rel if rel > 0.0
                        else float("inf")))
    return cert


def _certify_leaf(spec: dict) -> dict:
    target = spec.get("target")
    if target in ("float16", "f16"):
        rel = _F16_REL
    elif target == "int8":
        # symmetric int8 value grid: step = 2*cap/254, worst relative
        # error 1/127 (~2^-7) of the tensor scale — 8x the predict
        # budget, so the serving registry's quantized-load seam refuses
        # this certificate by name (leaf_int8)
        rel = 1.0 / (((1 << _BITS["int8"]) - 2) // 2)
    else:
        rel = 2.0 ** -8     # bf16 serving would keep 8 bits
    trees = int(spec.get("num_trees", 1))
    leaf_cap = float(spec.get("leaf_abs_max", 1.0))
    out_abs = trees * leaf_cap * rel
    cert = {
        "spec": dict(spec),
        "leaf_rel_err": rel,
        "output_abs_err": out_abs,
        "output_scale": trees * leaf_cap,
        "threshold_rel_shift": rel,
        "budget": PREDICT_REL_BUDGET,
        "bound": rel,
        "ok": rel <= PREDICT_REL_BUDGET,
        "margin": PREDICT_REL_BUDGET / rel,
    }
    return cert


def compute_artifact(config: Optional[GraftlintConfig] = None
                     ) -> List[dict]:
    return [certify(s) for s in default_specs(config)]


def certificate_payload(config: Optional[GraftlintConfig] = None,
                        artifact=None) -> Dict[str, object]:
    """The ``--json`` ``quant_certificate`` block: one entry per spec
    plus the pinned budgets — the artifact the item-2/item-3 PRs must
    ship green against."""
    certs = artifact if isinstance(artifact, list) \
        else compute_artifact(config)
    return {
        "budgets": {"split_decision": SPLIT_DECISION_BUDGET,
                    "predict_rel": PREDICT_REL_BUDGET},
        "h_child_frac": H_CHILD_FRAC,
        "confidence": CONFIDENCE,
        "certificates": certs,
        "all_ok": all(c["ok"] for c in certs),
    }


def run(config: Optional[GraftlintConfig] = None,
        artifact=None) -> List[AuditResult]:
    name = "quant_certify"
    try:
        certs = artifact if isinstance(artifact, list) \
            else compute_artifact(config)
    except Exception as e:      # pragma: no cover - defensive
        return [AuditResult(name=name, ok=False,
                            detail="auditor raised: %r" % e)]
    bad = [c for c in certs if not c["ok"]]
    telemetry.count(C_CERTIFIED, len(certs) - len(bad),
                    category="analysis")
    if bad:
        bits = ["%s: bound %.3g > budget %.3g"
                % (c["spec"].get("name", c["spec"].get("kind")),
                   c["bound"], c["budget"]) for c in bad[:3]]
        return [AuditResult(name=name, ok=False,
                            detail="; ".join(bits))]
    worst = max((c["bound"] / c["budget"] for c in certs),
                default=0.0)
    return [AuditResult(
        name=name, ok=True,
        detail="%d spec(s) certified; tightest margin %.1fx"
               % (len(certs), 1.0 / worst if worst else float("inf")))]


def check_fixture(payload: dict) -> List[str]:
    """Uniform fixture hook: a spec dict — int8 at full plane scale
    must blow the split-decision budget, int16 must certify."""
    cert = certify(payload)
    if cert["ok"]:
        return []
    return ["%s: bound %.3g exceeds budget %.3g (%s)"
            % (payload.get("name", payload.get("kind", "spec")),
               cert["bound"], cert["budget"], cert.get("why", ""))]
