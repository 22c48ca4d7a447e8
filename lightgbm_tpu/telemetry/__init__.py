"""Telemetry subsystem: structured tracing, metrics registry, device profiling.

Grown out of ``utils/timer.py`` (the reference's compile-gated ``Timer`` /
``FunctionTimer`` pair, include/LightGBM/utils/common.h:1026-1105) into a
real observability layer:

  * :mod:`events`  — thread-safe process-global registry of spans and
    counters (begin/end timestamps, categories, tags, an explicit
    "device_wait" category for pipeline sync points), the run record
    (counters and the O(1)-per-launch spans, on in every mode, in a
    bounded ring with ``train`` / ``launch`` identifiers) and the
    ``lgbm:`` TraceAnnotations that put the same spans on the jax
    profiler's clock;
  * :mod:`export`  — Chrome-trace (``chrome://tracing`` JSON) and JSONL
    metrics-snapshot writers plus the sorted text report;
  * :mod:`monitor` — per-iteration :class:`TrainingMonitor` wired into the
    boosting loop through the CallbackEnv protocol;
  * :mod:`xplane`  — op-level device profiles and idle gaps by program
    span from a jax profiler trace, read with ``jax.profiler.ProfileData``
    (``python -m lightgbm_tpu.profile``);
  * :mod:`devices` — ``on_tpu()``, the one backend test of the package,
    and the TPU generations known by ``device_kind``;
  * :mod:`histo`  — log-bucketed fixed-memory mergeable streaming
    histograms (p50/p95/p99/p99.9): per-collective DCN latency+bytes,
    persist program wall, serving latency/queue-wait;
  * :mod:`merge`  — cross-rank Chrome-trace merge with barrier-span
    clock alignment (``python -m lightgbm_tpu.profile --merge DIR``);
  * :mod:`flight` — crash flight recorder: bounded ring of recent
    telemetry, dumped atomically on LightGBMError / collective timeout /
    injected kill;
  * :mod:`promexport` — Prometheus text-exposition snapshots
    (``telemetry_out=<path>.prom`` enables a periodic atomic flush).

Three tiers: ``tpu_telemetry=off|timers|trace`` config param (plus
``telemetry_out=<path>`` for the trace/metrics files), the legacy
``LIGHTGBM_TPU_TIMETAG=1`` env var (timers mode), or
``LIGHTGBM_TPU_TELEMETRY=timers|trace``. The default is ``off``: the run
record only (:func:`counts_snapshot`, :func:`ring_snapshot`,
:func:`snapshot`), which never blocks and changes no compiled program;
every other instrumentation point is a no-op behind one integer check.
``timers`` records every span (and, with ``tpu_numerics_stats=auto``,
compiles the numerics probes into the fused scan); ``trace`` adds the
timeline.
"""
from . import events, flight, histo
from .events import (OFF, TIMERS, TRACE, add, configure, configure_from_config,
                     count, counts_snapshot, device_wait, disable, enable,
                     enabled, events_snapshot, iteration_records, keep_program,
                     mode, program_scopes, reset,
                     ring_snapshot, scope, snapshot, timed, tracing)
from .export import (format_report, maybe_export, print_report,
                     rank_suffixed, write_chrome_trace, write_metrics_jsonl)
from .histo import Histogram, histograms_snapshot, observe
from .monitor import TrainingMonitor

__all__ = [
    "OFF", "TIMERS", "TRACE", "Histogram", "TrainingMonitor", "add",
    "configure", "configure_from_config", "count", "counts_snapshot",
    "device_wait", "disable", "enable", "enabled", "events",
    "events_snapshot", "flight", "format_report", "histo",
    "histograms_snapshot", "iteration_records", "keep_program",
    "maybe_export", "mode", "observe", "print_report", "program_scopes",
    "rank_suffixed", "reset", "ring_snapshot",
    "scope",
    "snapshot", "timed", "tracing", "write_chrome_trace",
    "write_metrics_jsonl",
]
