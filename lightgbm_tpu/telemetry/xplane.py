"""Op-level device profiles from jax.profiler xplane protos.

``jax.profiler.start_trace`` writes an ``*.xplane.pb`` proto per session.
This module reads it with ``jax.profiler.ProfileData`` (nothing else is
needed on the machine) and reports, per device plane, the time per XLA op
name, and for the first device plane the idle gaps between operations, each
put down to the program span that covers its middle. The program's spans
are on the same clock as the device's operations because
:func:`lightgbm_tpu.telemetry.events.scope` enters a
``jax.profiler.TraceAnnotation("lgbm:<name>")`` whenever it records.
This is the mechanism that attributes histogram / split / partition /
collective time *on the chip* — the host-side span registry
(:mod:`events`) can only see launches and waits.

The "XLA Ops" line of a device plane nests: a ``while`` or ``conditional``
event spans every operation of its body, which follow as events of their
own. Containers are left out by name so that busy time and gaps are those
of the operations that do work.

Entry points:

  * :func:`collect_trace` — run a block under the jax profiler, yield the
    trace directory;
  * :func:`parse_xplane_dir` / :func:`parse_xplane` — proto -> ``{"device":
    {plane: [(start_ns, end_ns, op)]}, "host": [(start_ns, end_ns, span)]}``;
  * :func:`op_totals`, :func:`idle_gaps` — the two reductions;
  * :func:`format_device_report` — the sorted text tables;
  * ``python -m lightgbm_tpu.profile`` (:mod:`lightgbm_tpu.profile`) — the
    end-to-end CLI: synthetic training run + this report.
"""
from __future__ import annotations

import contextlib
import glob
import os
import tempfile
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = "/device:"
HOST_PLANE = "/host:"
OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call", "tuple", "get-tuple-element",
              "parameter", "constant", "bitcast")
SPAN_MARK = "lgbm:"           # events.scope's TraceAnnotations

Trace = Dict[str, object]
PlaneTotals = Dict[str, Tuple[int, int]]   # op name -> (total ns, count)


@contextlib.contextmanager
def collect_trace(trace_dir: Optional[str] = None):
    """Context manager running the enclosed block under the jax profiler;
    yields the trace directory (cleared first; by default
    ``<tmp>/lgbtpu_xplane``)."""
    import shutil

    import jax
    if trace_dir is None:
        trace_dir = os.path.join(tempfile.gettempdir(), "lgbtpu_xplane")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    try:
        yield trace_dir
    finally:
        jax.profiler.stop_trace()


def find_xplane_files(trace_dir: str) -> List[str]:
    return sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))


def base_name(name: str) -> str:
    """'%fusion.12 = ...' / 'fusion.12' -> 'fusion'; 'seg_hist.3' ->
    'seg_hist'."""
    name = name.split(" = ")[0].lstrip("%")
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def parse_xplane(path: str) -> Trace:
    """One xplane proto -> the device planes' operations (containers left
    out) and the program's ``lgbm:`` spans of the host planes, as
    (start_ns, end_ns, name) triples."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device: Dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops = device.setdefault(plane.name, [])
                for ev in line.events:
                    if base_name(ev.name) not in CONTAINERS:
                        start = int(ev.start_ns)
                        ops.append((start, start + int(ev.duration_ns),
                                    ev.name))
        elif plane.name.startswith(HOST_PLANE):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_MARK):
                        start = int(ev.start_ns)
                        host.append((start, start + int(ev.duration_ns),
                                     ev.name[len(SPAN_MARK):]))
    return {"device": device, "host": host}


def parse_xplane_dir(trace_dir: str) -> Trace:
    """All xplane protos under a trace directory, merged per plane."""
    merged: Trace = {"device": {}, "host": []}
    for path in find_xplane_files(trace_dir):
        one = parse_xplane(path)
        for plane, ops in one["device"].items():
            merged["device"].setdefault(plane, []).extend(ops)
        merged["host"].extend(one["host"])
    return merged


def op_totals(trace: Trace) -> Dict[str, PlaneTotals]:
    """{plane: {op base name: (total ns, count)}}."""
    out: Dict[str, PlaneTotals] = {}
    for plane, ops in trace["device"].items():
        tot: Dict[str, list] = {}
        for start, end, name in ops:
            t = tot.setdefault(base_name(name), [0, 0])
            t[0] += end - start
            t[1] += 1
        out[plane] = {k: (v[0], v[1]) for k, v in tot.items()}
    return out


def _merge(intervals) -> List[list]:
    """Union of (start, end) intervals as a sorted list of disjoint ones."""
    out: List[list] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return out


def idle_gaps(trace: Trace) -> Tuple[float, float, Dict[str, float]]:
    """(busy seconds, traced seconds, {program span: idle seconds}) of the
    first device plane. A gap belongs to the innermost ``lgbm:`` span that
    covers its middle (``host:unmarked`` when none does)."""
    planes = sorted(trace["device"])
    if not planes:
        return 0.0, 0.0, {}
    busy = _merge((s, e) for s, e, _ in trace["device"][planes[0]])
    gaps: Dict[str, float] = {}
    for (_, a), (b, _) in zip(busy, busy[1:]):
        mid = (a + b) // 2
        cover = [(e - s, what) for s, e, what in trace["host"]
                 if s <= mid <= e]
        label = min(cover)[1] if cover else "host:unmarked"
        gaps[label] = gaps.get(label, 0.0) + (b - a) * 1e-9
    busy_s = sum(e - s for s, e in busy) * 1e-9
    traced_s = (busy[-1][1] - busy[0][0]) * 1e-9 if busy else 0.0
    return busy_s, traced_s, gaps


def format_device_report(trace: Trace, iters: int = 1, top: int = 40) -> str:
    """Per-plane sorted table of device time per XLA op name, then the idle
    gaps of the first device plane by program span, then the program's
    spans as the profiler saw them."""
    lines = []
    per = max(iters, 1)
    for plane_name, ops in op_totals(trace).items():
        lines.append("== plane: %s ==" % plane_name)
        tot_all = sum(ns for ns, _ in ops.values())
        lines.append("total device time: %.3fs (%.1f ms/iter)"
                     % (tot_all / 1e9, tot_all / 1e9 / per * 1e3))
        ranked = sorted(ops.items(), key=lambda kv: -kv[1][0])[:top]
        for name, (ns, n) in ranked:
            lines.append("%8.3fs %7.2fms/iter x%-7d %s"
                         % (ns / 1e9, ns / 1e9 / per * 1e3, n, name[:90]))
    if not lines:
        lines.append("(no device planes found — CPU backends do not emit "
                     "XLA-op lines; run on a real accelerator)")
    else:
        busy_s, traced_s, gaps = idle_gaps(trace)
        lines.append("== idle gaps of the first device plane, by program "
                     "span ==")
        lines.append("busy %.3fs of %.3fs traced (idle %.2f%%)"
                     % (busy_s, traced_s,
                        100.0 * (1.0 - busy_s / traced_s) if traced_s
                        else 0.0))
        for label, sec in sorted(gaps.items(), key=lambda kv: -kv[1])[:top]:
            lines.append("%10.6fs  %s" % (sec, label))
    spans: Dict[str, list] = {}
    for start, end, name in trace["host"]:
        t = spans.setdefault(name, [0, 0])
        t[0] += end - start
        t[1] += 1
    if spans:
        lines.append("== program spans on the host plane (lgbm:) ==")
        for name, (ns, n) in sorted(spans.items(),
                                    key=lambda kv: -kv[1][0])[:top]:
            lines.append("%10.6fs x%-5d %s" % (ns / 1e9, n, name))
    return "\n".join(lines)
