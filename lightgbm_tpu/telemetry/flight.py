"""Crash flight recorder: a bounded ring of recent telemetry, dumped on death.

The trace buffer and counter tables in :mod:`events` live in the process
that just died — exactly when the resilience subsystem (PR 5) most needs
a postmortem. This module keeps a small, bounded ring buffer of the most
recent spans, collective events, and counter bumps, and dumps it
ATOMICALLY (the resilience tmp+fsync+rename writer) when the process is
about to fail:

  * ``LightGBMError`` escaping ``engine.train`` / the distributed driver;
  * a guarded DCN collective timing out or exhausting its retries
    (``resilience/retry.py`` calls :func:`dump` before raising);
  * an injected ``tpu_fault_plan`` kill (``faults.check_kill``).

A dead rank therefore leaves ``flight.r<rank>.json`` next to its
checkpoints: the last-N events before death, the counter totals, and the
latency histograms — readable with nothing but a JSON parser.

Arming: :func:`configure_from_config` arms the recorder whenever the run
can produce a postmortem worth having — telemetry is on, a fault plan is
installed, or the run is multi-host. Recording is an O(1) deque append
behind one bool; disarmed, every entry point is a no-op and the events
module's sink pointer stays ``None`` (zero overhead on the hot path).
The ring is capacity-bounded (not time-bounded): 4096 entries comfortably
cover the last seconds of any instrumented run while keeping the dump
small enough to write inside a dying process.

Telemetry-OFF caveat: most spans and the histograms live behind the
telemetry mode gate, so an armed-but-telemetry-off run (fault plan or
multihost with default params) dumps the EXPLICIT :func:`note` events —
recent collectives, retries, timeouts, the kill — plus the run record
(counters and the O(1)-per-launch spans, which are on in every mode),
with empty histogram tables. That is still a real postmortem (what
died, on which collective, when); enable ``tpu_telemetry=timers`` for
the full record.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import List, Optional

DEFAULT_CAPACITY = 4096

_lock = threading.Lock()
_ring: deque = deque(maxlen=DEFAULT_CAPACITY)
_armed = False
_dump_dir = ""
_last_dump: Optional[str] = None


def armed() -> bool:
    return _armed


def arm(dump_dir: Optional[str] = None,
        capacity: Optional[int] = None) -> None:
    """Start recording into the ring (idempotent); installs the span /
    counter sinks in :mod:`events`."""
    global _armed, _dump_dir, _ring
    from . import events
    with _lock:
        if capacity is not None and capacity != _ring.maxlen:
            _ring = deque(_ring, maxlen=max(int(capacity), 16))
        if dump_dir is not None:
            _dump_dir = str(dump_dir)
        _armed = True
    # sink install happens OUTSIDE _lock: set_flight_sinks takes the
    # events lock, and the sinks themselves take _lock — installing
    # under _lock would put a flight->events edge into the acquisition
    # graph for no benefit. Order matters: _armed flips first, so a
    # bump racing the install is dropped by the sink's armed check,
    # never recorded into a disarmed ring.
    events.set_flight_sinks(_span_sink, _count_sink)


def disarm() -> None:
    global _armed
    from . import events
    with _lock:
        _armed = False
    # mirror of arm(): _armed drops first, so a bump that still reaches
    # an installed sink (events snapshots the pointer before calling)
    # no-ops instead of landing in a ring the owner believes is off
    events.set_flight_sinks(None, None)


def configure_from_config(config) -> None:
    """Arm when this run can die in a way worth a postmortem: telemetry
    on, a fault plan installed, or a multi-host run. The dump lands next
    to the checkpoints when a checkpoint_dir exists (the resume tooling
    already looks there), else beside telemetry_out, else the cwd."""
    from . import events
    telemetry_on = events.enabled()
    fault_plan = str(getattr(config, "tpu_fault_plan", "") or "")
    multihost = int(getattr(config, "num_machines", 1)) > 1
    if not (telemetry_on or fault_plan or multihost):
        disarm()
        return
    ckpt_dir = str(getattr(config, "checkpoint_dir", "") or "")
    out = events.out_path() or ""
    # per-run scoping (the retry round-counter pattern): a new train's
    # flight record must not carry the previous run's ring or its stale
    # last-dump path (which would suppress this run's postmortem)
    reset()
    arm(dump_dir=ckpt_dir or (os.path.dirname(out) if out else "."))


def reset() -> None:
    global _last_dump
    with _lock:
        _ring.clear()
        _last_dump = None


# ---------------------------------------------------------------------------
# recording
# ---------------------------------------------------------------------------

def _span_sink(name: str, category: str, ts: float, dur: float) -> None:
    if not _armed:              # guarded-by: GIL (one atomic bool load)
        return
    with _lock:
        _ring.append({"kind": "span", "name": name, "cat": category,
                      "ts": ts, "dur": dur})


def _count_sink(name: str, inc: float, category: str) -> None:
    if not _armed:              # guarded-by: GIL (one atomic bool load)
        return
    with _lock:
        _ring.append({"kind": "count", "name": name, "inc": inc,
                      "cat": category, "ts": time.time()})


def note(event: str, **fields) -> None:
    """Record one explicit flight event of kind `event` (collective
    attempts, retries, timeouts — the retry guard's call sites). Field
    names are free-form except ``kind``/``ts``, which the record owns."""
    if not _armed:
        return
    ev = dict(fields)
    ev["kind"] = event
    ev["ts"] = time.time()
    with _lock:
        _ring.append(ev)


def snapshot() -> List[dict]:
    with _lock:
        return list(_ring)


def last_dump_path() -> Optional[str]:
    return _last_dump


# ---------------------------------------------------------------------------
# the dump
# ---------------------------------------------------------------------------

def _rank() -> int:
    from .export import process_index
    return process_index()


def dump_path(rank: Optional[int] = None) -> str:
    r = _rank() if rank is None else int(rank)
    return os.path.join(_dump_dir or ".", "flight.r%d.json" % r)


def dump(reason: str, rank: Optional[int] = None,
         path: Optional[str] = None) -> Optional[str]:
    """Write the flight record atomically; returns the path (None when
    disarmed or the write itself failed — a dying process must never die
    harder because its postmortem could not be written)."""
    global _last_dump
    if not _armed:
        return None
    from . import events, histo
    record = {
        "format": "lightgbm_tpu.flight/1",
        "reason": reason,
        "time": time.time(),
        "rank": _rank() if rank is None else int(rank),
        "pid": os.getpid(),
        "events": snapshot(),
        "counters": events.counts_snapshot(),
        "timers": {k: {"seconds": round(sec, 6), "count": n,
                       "category": cat}
                   for k, (sec, n, cat) in events.snapshot_full().items()},
        "histograms": {k: h.to_dict(with_buckets=False)
                       for k, h in histo.histograms_snapshot().items()},
        "dropped_events": events.dropped_events(),
    }
    target = path or dump_path(rank)
    try:
        d = os.path.dirname(os.path.abspath(target))
        if d:
            os.makedirs(d, exist_ok=True)
        from ..resilience.checkpoint import atomic_write_text
        atomic_write_text(target, json.dumps(record, indent=1,
                                             sort_keys=True))
    except Exception as exc:   # pragma: no cover - disk-full death path
        try:
            from ..utils.log import Log
            Log.warning("flight recorder dump failed: %r" % exc)
        except Exception:
            pass
        return None
    with _lock:
        _last_dump = target
    return target
