"""Telemetry writers: Chrome trace JSON, JSONL metrics snapshots, text report.

``write_chrome_trace`` emits the ``chrome://tracing`` / Perfetto "JSON
Array Format": one complete ("ph": "X") event per recorded span with
microsecond timestamps, pid/tid lanes, the category string, and the span
tags under "args". ``write_metrics_jsonl`` emits one JSON object per line:
a header, one line per named counter, one per unit-less count, and one per
TrainingMonitor iteration record — grep/jq-friendly and append-safe.

``print_report`` keeps the exact shape of the original
``utils.timer.print_report`` table (sorted by total seconds) so existing
eyeballs and scripts keep working; categories show as a suffix column.
"""
from __future__ import annotations

import json
import os
import time
from typing import Optional

from . import events, histo


def process_index() -> int:
    """This process's rank in a multihost run (0 single-host / no jax).
    Never initializes a backend by itself: export runs after training,
    when the distributed runtime either exists or never will."""
    try:
        import jax
        if jax.process_count() > 1:
            return int(jax.process_index())
    except Exception:
        pass
    return 0


def rank_suffixed(base: str) -> str:
    """Per-rank telemetry_out path: a single shared path is CLOBBERED by
    every rank of a multihost run (last writer wins, the rest of the pod
    is invisible). Rank r > -1 in a multi-process run writes
    ``name.rR.ext`` instead — the seam the trace merger
    (telemetry/merge.py) consumes. Single-host paths are unchanged."""
    r = process_index()
    try:
        import jax
        multi = jax.process_count() > 1
    except Exception:
        multi = False
    if not multi:
        return base
    root, ext = os.path.splitext(base)
    return "%s.r%d%s" % (root, r, ext)


def chrome_trace_events(evs=None, pid: int = 0) -> list:
    """Recorded spans -> chrome trace event dicts (ts/dur in microseconds)."""
    if evs is None:
        evs = events.events_snapshot()
    out = []
    for ev in evs:
        rec = {"name": ev["name"], "cat": ev.get("cat", "misc"), "ph": "X",
               "ts": ev["ts"] * 1e6, "dur": ev["dur"] * 1e6,
               "pid": pid, "tid": ev.get("tid", 0)}
        args = dict(ev.get("args") or {})
        if "parent" in ev:
            args["parent"] = ev["parent"]
        if args:
            rec["args"] = args
        out.append(rec)
    return out


def write_chrome_trace(path: str, evs=None) -> str:
    """Write the span timeline as chrome://tracing JSON; returns `path`."""
    rank = process_index()
    trace = {
        "traceEvents": chrome_trace_events(evs, pid=rank),
        "displayTimeUnit": "ms",
        "otherData": {
            "producer": "lightgbm_tpu.telemetry",
            "dropped_events": events.dropped_events(),
            "process_index": rank,
        },
    }
    d = os.path.dirname(os.path.abspath(path))
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(trace, f)
    return path


def write_metrics_jsonl(path: str) -> str:
    """Counters + counts + per-iteration monitor records, one JSON/line."""
    d = os.path.dirname(os.path.abspath(path))
    if d:
        os.makedirs(d, exist_ok=True)
    snap = events.snapshot_full()
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "header", "time": time.time(),
                            "categories": events.category_totals(),
                            "dropped_events": events.dropped_events(),
                            "histo_saturation": histo.saturation_total()})
                + "\n")
        for name, (sec, n, cat) in sorted(snap.items(),
                                          key=lambda kv: -kv[1][0]):
            f.write(json.dumps({"kind": "timer", "name": name,
                                "seconds": round(sec, 6), "count": n,
                                "category": cat}) + "\n")
        for name, v in sorted(events.counts_snapshot().items()):
            f.write(json.dumps({"kind": "count", "name": name,
                                "value": v}) + "\n")
        for name, h in sorted(histo.histograms_snapshot().items()):
            # full sparse buckets: two files' histograms merge exactly
            # (Histogram.from_dict + merge), which is how multi-rank
            # latency distributions combine after a run
            f.write(json.dumps(dict({"kind": "histogram"},
                                    **h.to_dict())) + "\n")
        for rec in events.iteration_records():
            f.write(json.dumps(dict({"kind": "iteration"}, **rec)) + "\n")
    return path


def _paths(base: str):
    """telemetry_out -> (chrome trace path, metrics jsonl path)."""
    if base.endswith(".json"):
        return base, base[:-5] + ".metrics.jsonl"
    return base + ".trace.json", base + ".metrics.jsonl"


def maybe_export(out: Optional[str] = None):
    """Write trace + metrics files when TRACE mode is on (plus the
    Prometheus snapshot for a ``...prom`` out path, any enabled mode).
    Returns the (trace_path, metrics_path) pair, or None when no trace
    was written. Multihost ranks each write their own rank-suffixed
    files (see :func:`rank_suffixed`)."""
    base = out or events.out_path() or ""
    if base.endswith(".prom"):
        if events.enabled():
            from . import promexport
            promexport.write_prom(rank_suffixed(base))
        # trace/metrics (TRACE mode) land next to the prom snapshot
        base = base[:-5] + ".json"
    if not events.tracing():
        return None
    trace_path, metrics_path = _paths(rank_suffixed(
        base or "lightgbm_tpu_trace.json"))
    write_chrome_trace(trace_path)
    write_metrics_jsonl(metrics_path)
    events._exported = True
    return trace_path, metrics_path


def phase_snapshot() -> dict:
    """The registry as one JSON-able dict (category totals, per-scope
    table, histograms, counters, truncation signals): what
    ``python -m lightgbm_tpu.profile ... phases_out=PATH`` writes."""
    return {
        "categories": {k: round(v, 3)
                       for k, v in events.category_totals().items()},
        "scopes": {name: {"seconds": round(sec, 3), "count": n,
                          "category": cat}
                   for name, (sec, n, cat)
                   in events.snapshot_full().items()},
        "histograms": {k: h.to_dict(with_buckets=False)
                       for k, h in histo.histograms_snapshot().items()},
        # silent truncation is a lie in a snapshot: say what was dropped
        "dropped_events": events.dropped_events(),
        "histo_saturation": histo.saturation_total(),
        "counters": dict(events.counts_snapshot()),
    }


def format_report(snap=None) -> str:
    """Sorted-by-time table, like Timer::Print (common.h:1059)."""
    if snap is None:
        snap = events.snapshot_full()
    lines = []
    if snap:
        lines.append("[LightGBM-TPU] [Info] time-tag report "
                     "(host wall per named scope; async launches exclude "
                     "device time)")
        total = sum(v for v, _, _ in snap.values())
        width = max(len(k) for k in snap)
        for name, (sec, n, cat) in sorted(snap.items(),
                                          key=lambda kv: -kv[1][0]):
            lines.append("  %-*s %10.3fs  x%-7d %5.1f%%  [%s]"
                         % (width, name, sec, n,
                            100.0 * sec / max(total, 1e-12), cat))
        lines.append("  %-*s %10.3fs" % (width, "(sum)", total))
    lines.extend(histogram_report_lines())
    # silent-truncation visibility: a trace that dropped events or a
    # histogram that saturated is an INCOMPLETE record, and the report
    # must say so rather than present clipped numbers as the whole story
    dropped = events.dropped_events()
    if dropped:
        lines.append("  !! %d trace event(s) dropped (MAX_EVENTS=%d "
                     "reached): the timeline is truncated"
                     % (dropped, events.MAX_EVENTS))
    sat = histo.saturation_total()
    if sat:
        lines.append("  !! %d histogram sample(s) saturated out of the "
                     "bucket range: tail quantiles are clamped" % sat)
    return "\n".join(lines) if lines else ""


def histogram_report_lines(histos=None) -> list:
    """The latency/size distribution table appended to the text report."""
    if histos is None:
        histos = histo.histograms_snapshot()
    if not histos:
        return []
    lines = ["[LightGBM-TPU] [Info] distributions "
             "(log-bucketed streaming histograms)"]
    width = max(len(k) for k in histos)
    for name in sorted(histos):
        h = histos[name]
        q = h.quantiles()
        sat = (" sat=%d" % h.saturated) if h.saturated else ""
        lines.append(
            "  %-*s n=%-9d p50=%-11.4g p95=%-11.4g p99=%-11.4g "
            "p99.9=%-11.4g max=%-11.4g [%s]%s"
            % (width, name, h.count, q["p50"], q["p95"], q["p99"],
               q["p99_9"], h.vmax if h.count else float("nan"),
               h.unit or "-", sat))
    return lines


def print_report(out=None) -> None:
    text = format_report()
    if not text:
        return
    import sys
    print(text, file=out or sys.stderr)
