"""Op-level device profiles from jax.profiler xplane protos.

``jax.profiler.start_trace`` writes an ``*.xplane.pb`` proto per session;
the TensorBoard converter is broken against the TF build in this image, so
this module parses the proto directly (lifted from the old top-level
``prof_trace.py`` dev script) and aggregates device time per XLA op name.
This is the mechanism that attributes histogram / split / partition /
collective time *on the chip* — the host-side span registry
(:mod:`events`) can only see launches and waits.

Entry points:

  * :func:`collect_trace` — run a callable under the jax profiler, return
    the trace directory;
  * :func:`parse_xplane_dir` / :func:`parse_xplane` — proto -> per-plane
    ``{op name: (picoseconds, count)}``;
  * :func:`format_device_report` — the sorted text table;
  * ``python -m lightgbm_tpu.profile`` (:mod:`lightgbm_tpu.profile`) — the
    end-to-end CLI: synthetic training run + this report.
"""
from __future__ import annotations

import contextlib
import glob
import os
from typing import Dict, Tuple

# the C++ protobuf runtime in this image rejects the tsl descriptors;
# force the pure-python implementation before the proto import
os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")

PlaneTotals = Dict[str, Tuple[int, int]]   # op name -> (total ps, count)


@contextlib.contextmanager
def collect_trace(trace_dir: str = "/tmp/lgbtpu_xplane"):
    """Context manager running the enclosed block under the jax profiler;
    yields the trace directory (cleared first)."""
    import shutil

    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    try:
        yield trace_dir
    finally:
        jax.profiler.stop_trace()


def find_xplane_files(trace_dir: str):
    return sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))


def parse_xplane(path: str, device_only: bool = True) -> Dict[str, PlaneTotals]:
    """One xplane proto -> {plane name: {op name: (ps, count)}}.

    `device_only` keeps TPU/accelerator planes ("XLA Ops" lines); the host
    Python planes are the span registry's job.
    """
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    sp = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        sp.ParseFromString(f.read())
    out: Dict[str, PlaneTotals] = {}
    for plane in sp.planes:
        if device_only and "TPU" not in plane.name \
                and "GPU" not in plane.name:
            continue
        ev_meta = {m.id: m.name for m in plane.event_metadata.values()}
        totals: Dict[str, int] = {}
        counts: Dict[str, int] = {}
        for line in plane.lines:
            if "XLA Ops" not in line.name:
                continue
            for ev in line.events:
                name = ev_meta.get(ev.metadata_id, "?")
                totals[name] = totals.get(name, 0) + ev.duration_ps
                counts[name] = counts.get(name, 0) + 1
        if totals:
            out[plane.name] = {n: (ps, counts[n]) for n, ps in totals.items()}
    return out


def parse_xplane_dir(trace_dir: str,
                     device_only: bool = True) -> Dict[str, PlaneTotals]:
    """All xplane protos under a trace directory, merged per plane."""
    merged: Dict[str, PlaneTotals] = {}
    for path in find_xplane_files(trace_dir):
        for plane, ops in parse_xplane(path, device_only=device_only).items():
            tgt = merged.setdefault(plane, {})
            for name, (ps, n) in ops.items():
                ops0, n0 = tgt.get(name, (0, 0))
                tgt[name] = (ops0 + ps, n0 + n)
    return merged


def format_device_report(planes: Dict[str, PlaneTotals], iters: int = 1,
                         top: int = 40) -> str:
    """Per-plane sorted table of device time per grouped XLA op name."""
    lines = []
    for plane_name, ops in planes.items():
        lines.append("== plane: %s ==" % plane_name)
        tot_all = sum(ps for ps, _ in ops.values())
        lines.append("total device time: %.3fs (%.1f ms/iter)"
                     % (tot_all / 1e12, tot_all / 1e12 / max(iters, 1) * 1e3))
        ranked = sorted(ops.items(), key=lambda kv: -kv[1][0])[:top]
        for name, (ps, n) in ranked:
            lines.append("%8.3fs %7.2fms/iter x%-7d %s"
                         % (ps / 1e12, ps / 1e12 / max(iters, 1) * 1e3,
                            n, name[:90]))
    if not lines:
        lines.append("(no device planes found — CPU backends do not emit "
                     "XLA-op lines; run on a real accelerator)")
    return "\n".join(lines)
