"""Graft-lint: JAX-aware static analysis + jaxpr audit gate.

The repo's two worst defect classes — silent f32->f64 dtype promotion
(the persist-f32 vs v1-f64 tie-flip family pinned by
tests/test_known_divergence.py) and recompile/host-sync hazards on the
serving path — are invisible to pytest until they bite at scale. This
package machine-checks them on every run:

* :mod:`lint` — an AST rule engine (rules JG001-JG012, see
  :mod:`rules`) scanning the package for JAX/TPU pitfalls specific to
  this codebase, with inline suppressions, a checked-in baseline for
  grandfathered findings, and an autofix mode (unused imports).
* :mod:`dataflow` — a reusable abstract interpreter over closed
  jaxprs propagating dtype, interval value-range (seeded from the ops
  modules' ``*_input_contract`` annotations), and accumulated error
  bounds through every primitive including all sub-jaxpr carriers
  (``pjit``/``scan``/``while``/``cond``/``custom_jvp``/``closed_call``)
  with a fixpoint for loop bodies — the shared engine the jaxpr audits
  and the precision/transfer/quant auditors run on.
* :mod:`jaxpr_audit` — traces the real TPU entry points
  (``hist_window``, ``scan_pair``/``scan_blocks``, the persist
  ``split_pass``, the predict traversal) with abstract inputs and
  asserts structural invariants on the jaxpr: no f64 values OR consts
  anywhere in persist-f32 kernels (including consts closed over inside
  call primitives — the class the pre-dataflow walk missed), no host
  callbacks/transfers inside ``fori_loop``/``scan`` bodies, donation
  actually recorded, the serve ladder's compile bound.
* :mod:`strict` — the strict-numerics test harness (strict dtype
  promotion + debug-nans) the kernel-parity tests run under.
* the whole-program auditors (:mod:`auditors` registry):
  :mod:`collective_audit` verifies every rank-role issues the same DCN
  collective sequence (a collective under a rank-dependent branch is a
  deadlock finding) and that every site rides the resilience retry
  guard (lint twin: rule JG009);
  :mod:`compile_audit` bounds the distinct-compile count across the
  jitted entry points and fails on unbounded static args;
  :mod:`precision_audit` requires every float narrowing in the traced
  ops/predict programs to be blessed (``NARROW_OK``) or range-proven
  on the dataflow engine (lint twin: JG010); :mod:`transfer_audit`
  forbids implicit device<->host transfers and oversized replicated
  intermediates in the persist/level/scan/predict programs;
  :mod:`quant_audit` statically bounds the split-gain / leaf-output
  error of the declared int8/int16/f16 quantization specs and ships
  the ``quant_certificate`` artifact in ``--json``;
  :mod:`concurrency_audit` discovers every thread root in the threaded
  host layer (serving / predict-serve / resilience / telemetry),
  infers per-site lock sets for all shared mutable state
  (lint twins: JG011 unguarded mutation, JG012 blocking call under a
  held lock), keeps the global lock-acquisition-order graph acyclic,
  and ships the per-root abstract trace as ``concurrency_trace`` in
  ``--json``.

Gate: ``python -m lightgbm_tpu.analysis`` exits non-zero on any
unsuppressed finding or failed audit; ``tests/test_analysis.py`` runs
the same self-scan inside the tier-1 suite.
"""
from __future__ import annotations

from .auditors import all_auditors, run_all as run_auditors
from .config import GraftlintConfig, load_config
from .core import Finding
from .jaxpr_audit import AuditResult, run_audits
from .lint import LintReport, prune_baseline, run_lint
from .strict import strict_numerics

__all__ = [
    "AuditResult",
    "Finding",
    "GraftlintConfig",
    "LintReport",
    "all_auditors",
    "load_config",
    "prune_baseline",
    "run_auditors",
    "run_audits",
    "run_lint",
    "strict_numerics",
]
