"""Graft-lint CLI: ``python -m lightgbm_tpu.analysis``.

Exit codes: 0 clean (no unsuppressed findings, all audits pass),
1 findings/audit failures, 2 bad usage or parse errors.

The audit phase runs BOTH engines: the jaxpr audits (traced programs)
and the whole-program auditors (collective order, recompile surface,
precision flow — see :mod:`auditors`).

Common invocations::

    python -m lightgbm_tpu.analysis                 # full gate
    python -m lightgbm_tpu.analysis --json          # machine report
    python -m lightgbm_tpu.analysis --autofix       # apply safe fixes
    python -m lightgbm_tpu.analysis lightgbm_tpu/ops --rules JG003
    python -m lightgbm_tpu.analysis --write-baseline  # re-grandfather
    python -m lightgbm_tpu.analysis --prune-baseline  # drop stale entries
    python -m lightgbm_tpu.analysis --list-audits     # audit registry
"""
from __future__ import annotations

import argparse
import json
import sys

from . import (auditors, collective_audit, compile_audit,
               concurrency_audit, quant_audit)
from .config import load_config
from . import jaxpr_audit
from .jaxpr_audit import run_audits
from .lint import prune_baseline, run_lint, write_baseline
from .rules import all_rules


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m lightgbm_tpu.analysis",
        description="JAX-aware static analysis + jaxpr audit gate")
    p.add_argument("paths", nargs="*",
                   help="files/dirs to lint (default: [tool.graftlint] "
                        "include roots)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="emit a JSON report instead of text")
    p.add_argument("--rules", default=None,
                   help="comma-separated rule ids to run (default: all)")
    p.add_argument("--autofix", action="store_true",
                   help="apply safe textual fixes (unused imports)")
    p.add_argument("--no-baseline", action="store_true",
                   help="ignore the baseline suppression file")
    p.add_argument("--write-baseline", action="store_true",
                   help="write a baseline suppressing all current "
                        "findings, then exit 0")
    p.add_argument("--prune-baseline", action="store_true",
                   dest="prune_baseline",
                   help="drop baseline entries no current finding "
                        "matches (stale suppressions), then exit 0")
    p.add_argument("--no-audit", action="store_true",
                   help="skip the jaxpr/HLO audits")
    p.add_argument("--audit-only", action="store_true",
                   help="run only the jaxpr/HLO audits")
    p.add_argument("--show-suppressed", action="store_true",
                   help="also print suppressed findings")
    p.add_argument("--list-rules", action="store_true",
                   help="print the rule registry and exit")
    p.add_argument("--list-audits", action="store_true",
                   dest="list_audits",
                   help="print the audit registry (jaxpr audits + "
                        "whole-program auditors) and exit")
    return p


def _list_audits() -> None:
    """Mirror of --list-rules for the audit side of the gate: every
    jaxpr audit and every registered whole-program auditor, with
    one-line descriptions."""
    def first_line(doc):
        return (doc or "").strip().splitlines()[0] if doc else ""
    for fn in jaxpr_audit.AUDITS:
        print("jaxpr    %-18s %s" % (fn.__name__.replace("audit_", ""),
                                     first_line(fn.__doc__)))
    for name, mod in sorted(auditors.all_auditors().items()):
        print("auditor  %-18s %s" % (name, first_line(mod.__doc__)))


def main(argv=None) -> int:
    args = _build_parser().parse_args(argv)
    if args.list_rules:
        for rule in all_rules():
            print("%s  %-24s %s" % (rule.id, rule.name, rule.description))
        return 0
    if args.list_audits:
        _list_audits()
        return 0

    config = load_config()
    rule_ids = ([r.strip() for r in args.rules.split(",") if r.strip()]
                if args.rules else None)

    if (args.write_baseline or args.prune_baseline) \
            and (args.paths or rule_ids):
        # a filtered report would mark every out-of-scope baseline entry
        # stale (prune drops them) or omit it from the rewrite (write
        # loses it) — both silently destroy grandfathered suppressions
        print("%s requires a full unfiltered scan: drop --rules and "
              "path arguments"
              % ("--write-baseline" if args.write_baseline
                 else "--prune-baseline"), file=sys.stderr)
        return 2

    report = None
    if not args.audit_only:
        report = run_lint(paths=args.paths or None, config=config,
                          rule_ids=rule_ids,
                          use_baseline=not args.no_baseline,
                          autofix=args.autofix)
        if args.write_baseline:
            # full findings list: write_baseline keeps already-baselined
            # entries (they are suppressed, not gone) and skips only
            # inline-suppressed ones — passing unsuppressed here would
            # silently drop every grandfathered entry on refresh
            n = write_baseline(report.findings,
                               config.baseline_path())
            print("wrote %d baseline entries to %s"
                  % (n, config.baseline_path()))
            return 0
        if args.prune_baseline:
            kept, dropped = prune_baseline(report.findings,
                                           config.baseline_path())
            print("pruned %d stale baseline entr%s (%d kept) in %s"
                  % (dropped, "y" if dropped == 1 else "ies", kept,
                     config.baseline_path()))
            return 0

    run_auditors = not (args.no_audit
                        or (args.paths and not args.audit_only))
    # with --json the auditor artifacts also feed the payload below:
    # compute them once and share, instead of re-walking per consumer
    artifacts = (auditors.compute_artifacts(config)
                 if run_auditors and args.as_json else None)
    audits = [] if not run_auditors \
        else run_audits() + auditors.run_all(config, artifacts=artifacts)

    bad_audits = [a for a in audits if not a.ok]
    n_unsup = len(report.unsuppressed) if report else 0
    n_parse = len(report.parse_errors) if report else 0
    exit_code = 2 if n_parse else (1 if (n_unsup or bad_audits) else 0)

    if args.as_json:
        payload = {
            "exit_code": exit_code,
            "lint": report.to_dict() if report else None,
            "audits": [a.to_dict() for a in audits],
        }
        if run_auditors:
            # the whole-program auditors' full artifacts: the abstract
            # collective trace, the compile surface
            art = artifacts or {}
            payload["collective_trace"] = \
                collective_audit.extract_repo_trace(
                    config, artifact=art.get("collective_order"))
            payload["compile_surface"] = compile_audit.compile_surface(
                config, artifact=art.get("compile_surface"))
            # the machine-checkable quantization certificate the
            # item-2/item-3 quantization PRs must ship green against
            payload["quant_certificate"] = \
                quant_audit.certificate_payload(
                    config, artifact=art.get("quant_certify"))
            # the abstract per-root concurrency trace: thread roots,
            # the shared-site/lock-set table, the acquisition-order
            # graph (the threaded host layer's analogue of
            # collective_trace)
            payload["concurrency_trace"] = concurrency_audit.extract_trace(
                config, artifact=art.get("concurrency"))
        print(json.dumps(payload, indent=1))
        return exit_code

    if report:
        shown = report.findings if args.show_suppressed \
            else report.unsuppressed
        for f in shown:
            tag = " [suppressed:%s]" % f.suppression if f.suppressed else ""
            print("%s:%d:%d: %s %s%s"
                  % (f.path, f.line, f.col, f.rule, f.message, tag))
        for path, err in report.parse_errors:
            print("%s: PARSE ERROR: %s" % (path, err))
        if report.autofixed:
            print("autofixed %d import statement(s)" % report.autofixed)
    for a in audits:
        status = "SKIP" if a.skipped else ("ok" if a.ok else "FAIL")
        line = "audit %-24s %s" % (a.name, status)
        if a.detail:
            line += "  (%s)" % a.detail
        print(line)
    if report:
        print("graft-lint: %d file(s), %d finding(s) "
              "(%d suppressed), %d audit failure(s)"
              % (report.files_scanned, len(report.findings),
                 len(report.suppressed), len(bad_audits)))
    return exit_code


if __name__ == "__main__":
    sys.exit(main())
