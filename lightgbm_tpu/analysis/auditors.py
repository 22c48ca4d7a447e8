"""Registry of the whole-program auditors behind the analysis gate.

Seven source/program-level audit engines complement the jaxpr audits
(:mod:`jaxpr_audit` traces real programs; these reason about the
source/geometry/dataflow statically):

* ``collective_order`` — rank-consistent DCN collective sequences +
  guard coverage (:mod:`collective_audit`);
* ``compile_surface`` — the analytic distinct-compile bound across the
  jitted entry points (:mod:`compile_audit`);
* ``precision_flow`` — every float narrowing in the traced ops/predict
  programs blessed or range-proven on the :mod:`dataflow` engine
  (:mod:`precision_audit`);
* ``transfer`` — no implicit device<->host transfer or oversized
  replicated intermediate in the persist/level/scan/predict programs
  (:mod:`transfer_audit`);
* ``quant_certify`` — static split-gain / leaf-output error bounds for
  the declared int8/int16/f16 quantization specs, shipped as the
  ``--json`` ``quant_certificate`` artifact (:mod:`quant_audit`);
* ``health_covered`` — every module that builds a persist/level scan
  driver must flush its device-side ``numerics::*`` health stats
  (:mod:`health_audit` — the runtime numerics sentinel's coverage
  gate);
* ``concurrency`` — lock discipline, blocking-hold, and acquisition
  order for the threaded host layer (serving loop, registry hot-swap,
  retry watchdog, telemetry registries), shipped as the ``--json``
  ``concurrency_trace`` artifact (:mod:`concurrency_audit`).

Each module exposes ``run(config) -> List[AuditResult]`` (the gate) and
``check_fixture(payload) -> List[str]`` (the seeded-violation hook the
fixture tests drive, parametrized over this registry exactly like the
JG lint rules — an auditor without fixtures fails CI by construction).
"""
from __future__ import annotations

from typing import Dict, List, Optional

from . import (collective_audit, compile_audit, concurrency_audit,
               health_audit, precision_audit, quant_audit,
               transfer_audit)
from .config import GraftlintConfig
from .jaxpr_audit import AuditResult

AUDITORS: Dict[str, object] = {
    "collective_order": collective_audit,
    "compile_surface": compile_audit,
    "precision_flow": precision_audit,
    "transfer": transfer_audit,
    "quant_certify": quant_audit,
    "health_covered": health_audit,
    "concurrency": concurrency_audit,
}


def all_auditors() -> Dict[str, object]:
    return dict(AUDITORS)


def compute_artifacts(config: Optional[GraftlintConfig] = None
                      ) -> Dict[str, object]:
    """One pass over the repo per auditor, keyed by registry name.

    The --json CLI needs both the pass/fail verdicts AND the full
    artifacts (trace, surface, certificates); computing these
    here and passing them to :func:`run_all` + the payload builders
    keeps that to a single walk instead of one per consumer."""
    return {
        "collective_order": collective_audit.audit_repo(config),
        "compile_surface": compile_audit.iter_jit_sites(config),
        "precision_flow": precision_audit.compute_artifact(config),
        "transfer": transfer_audit.compute_artifact(config),
        "quant_certify": quant_audit.compute_artifact(config),
        "health_covered": health_audit.compute_artifact(config),
        "concurrency": concurrency_audit.compute_artifact(config),
    }


def run_all(config: Optional[GraftlintConfig] = None,
            artifacts: Optional[Dict[str, object]] = None
            ) -> List[AuditResult]:
    artifacts = artifacts or {}
    out: List[AuditResult] = []
    for name in sorted(AUDITORS):
        out.extend(AUDITORS[name].run(config,
                                      artifact=artifacts.get(name)))
    return out
