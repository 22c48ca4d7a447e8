"""Process-global registry of spans and counters.

The accounting model is the one ``utils/timer.py`` established (and whose
public functions now alias into this module): named wall-clock scopes on
the host side of an async device pipeline. A scope that merely *launches*
a jitted program measures launch cost, not device time; scopes that want
device time must block (``sync_value`` / :func:`device_wait`), and the
explicit ``device_wait`` category marks the points where the pipeline
actually blocks so the report separates "host work" from "waiting on the
chip". Op-level *device* attribution is a different mechanism entirely —
see :mod:`lightgbm_tpu.telemetry.xplane`.

Three modes:

  * ``OFF``    (default) — the RUN RECORD only: unit-less counters
    (:func:`count`) and the spans marked ``always=True``, which occur
    O(1) times per ``lgb.train`` or per fused launch (set-up steps, launch
    dispatches, compile events). Each such span accumulates seconds, self
    seconds and hits like any other and leaves one entry in a bounded ring
    (:data:`RING_EVENTS`, :func:`ring_snapshot`) carrying the identifiers
    of the ``engine.train`` call and of the fused launch it belongs to,
    and what each local device's HBM allocator read when the span opened
    and closed (:func:`device_memory_stats`; absent where the backend
    keeps no such statistics). Every other span is a no-op behind one int
    compare, nothing prints at exit, nothing blocks (no
    ``block_until_ready``, ``sync_value`` and :func:`device_wait` do
    nothing) and no compiled program changes.
  * ``TIMERS`` — every span: per-name accumulated seconds + hit counts
    (the TIMETAG-style report), no per-event storage beyond the ring.
  * ``TRACE``  — plus a bounded in-memory timeline of span events
    (begin timestamp, duration, thread, nesting parent, tags) that
    exports to ``chrome://tracing`` JSON via :mod:`export`.

Whenever a span records, in any mode, it also enters a
``jax.profiler.TraceAnnotation("lgbm:" + name)``: free without a profiler
session, and with one (``jax.profiler.trace``, ``python -m
lightgbm_tpu.profile``) the span lands on the host plane of the same
``.xplane.pb`` as the device's operations, on one clock.

Thread safety: one process-wide lock guards the counter tables, the ring
and the event buffer; the per-thread nesting stack lives in thread-local
storage.
"""
from __future__ import annotations

import atexit
import contextlib
import functools
import os
import re
import threading
import time
from collections import defaultdict, deque
from typing import Callable, Dict, List, Optional, Tuple

import jax
from jax._src import xla_bridge

OFF, TIMERS, TRACE = 0, 1, 2
_MODE_NAMES = {"off": OFF, "timers": TIMERS, "trace": TRACE,
               "0": OFF, "1": TIMERS, "false": OFF, "true": TIMERS}

# bounded trace buffer: ~120 bytes/event, so the cap is ~120MB worst case;
# past it events are dropped (and counted) rather than OOMing a long run
MAX_EVENTS = 1_000_000

_lock = threading.RLock()
_acc: Dict[str, float] = defaultdict(float)
_acc_self: Dict[str, float] = defaultdict(float)   # minus child-span time
_cnt: Dict[str, int] = defaultdict(int)
_cat: Dict[str, str] = {}
_counts: Dict[str, float] = defaultdict(float)
_count_cat: Dict[str, str] = {}
_events: List[dict] = []
_dropped = 0
# the run record's ring: one entry per `always` span, oldest dropped first
RING_EVENTS = 4096
_ring: deque = deque(maxlen=RING_EVENTS)
# identifiers the ring entries carry: engine.train calls are numbered from 1
# over the life of the process (reset() leaves the numbering alone), the
# fused launches of one train from 0; 0 / None outside a train
_train_seq = 0
_cur_train = 0
_cur_launch: Optional[int] = None
_iter_records: List[dict] = []
_programs: Dict[str, object] = {}   # span name -> its lowered program
_tls = threading.local()
_out_path: Optional[str] = None
_exported = False
# flight-recorder sinks (telemetry/flight.py): None when disarmed, so the
# hot path pays one is-None check; armed, every span exit / counter bump
# also lands in the crash ring buffer regardless of TRACE vs TIMERS mode
_flight_span: Optional[Callable] = None
_flight_count: Optional[Callable] = None

# perf_counter offset -> unix epoch, so trace timestamps are absolute
_EPOCH = time.time() - time.perf_counter()


def _env_mode() -> int:
    v = os.environ.get("LIGHTGBM_TPU_TELEMETRY", "").strip().lower()
    if v in _MODE_NAMES:
        return _MODE_NAMES[v]
    # legacy switch from utils/timer.py: TIMETAG=1 -> timers mode
    if os.environ.get("LIGHTGBM_TPU_TIMETAG", "") not in ("", "0"):
        return TIMERS
    return OFF


_mode = _env_mode()
# what turned telemetry on: "env" (import-time env var), "api" (an explicit
# enable()/disable() call), or "config" (tpu_telemetry= params). Only
# config-driven enablement is scoped to the run that asked for it — the next
# train with default params turns it back off (see configure()).
_mode_source = "env"


# ---------------------------------------------------------------------------
# mode control
# ---------------------------------------------------------------------------

def mode() -> int:
    return _mode


def enabled() -> bool:
    return _mode != OFF


def tracing() -> bool:
    return _mode == TRACE


def enable(new_mode="timers") -> None:
    global _mode, _mode_source
    if isinstance(new_mode, str):
        new_mode = _MODE_NAMES.get(new_mode.strip().lower(), TIMERS)
    _mode = max(int(new_mode), TIMERS)
    _mode_source = "api"


def disable() -> None:
    global _mode, _mode_source
    _mode = OFF
    _mode_source = "api"


def configure(mode_name: str, out: Optional[str] = None) -> None:
    """Apply a ``tpu_telemetry=`` / ``telemetry_out=`` pair.

    ``off`` (the default) ends any previous *config*-driven session —
    telemetry from one ``lgb.train(tpu_telemetry=...)`` call must not leak
    into the next train in the process — but never force-disables a session
    turned on by the env var or an explicit :func:`enable` call."""
    global _mode, _mode_source, _out_path
    m = str(mode_name).strip().lower()
    if m in ("", "off", "0", "false"):
        if out:
            _out_path = str(out)
        if _mode_source == "config":
            _mode = _env_mode()
            _mode_source = "env"
        return
    if m not in _MODE_NAMES:
        from ..utils.log import Log
        Log.warning("Unknown tpu_telemetry=%s (expected off|timers|trace); "
                    "telemetry stays %s"
                    % (mode_name, "off" if _mode == OFF else "on"))
        return
    if out:
        _out_path = str(out)
    enable(m)
    _mode_source = "config"


def configure_from_config(config) -> None:
    configure(getattr(config, "tpu_telemetry", "off"),
              getattr(config, "telemetry_out", "") or None)


def out_path() -> Optional[str]:
    return _out_path


def set_out_path(path: Optional[str]) -> None:
    global _out_path
    _out_path = path


def set_flight_sinks(span_sink: Optional[Callable],
                     count_sink: Optional[Callable]) -> None:
    """Install/remove the flight-recorder sinks (flight.arm/disarm).

    Published as a pair under the lock so concurrent arm/disarm calls
    serialize; the hot paths deliberately read the sink WITHOUT the lock
    (one local snapshot each — see :func:`count` / :func:`scope`), so a
    disarm landing mid-bump means that bump goes to the old sink, never
    to a half-installed pair and never through a None."""
    global _flight_span, _flight_count
    with _lock:
        _flight_span = span_sink
        _flight_count = count_sink


def reset() -> None:
    global _dropped, _exported
    with _lock:
        _acc.clear()
        _acc_self.clear()
        _cnt.clear()
        _cat.clear()
        _counts.clear()
        _count_cat.clear()
        del _events[:]
        _ring.clear()
        del _iter_records[:]
        _programs.clear()
        _dropped = 0
        _exported = False
    # the histogram registry and the flight ring are part of the same
    # run-scoped state (bench phases reset between workloads)
    from . import flight, histo
    histo.reset()
    flight.reset()


# ---------------------------------------------------------------------------
# recording
# ---------------------------------------------------------------------------

def add(name: str, seconds: float, category: str = "misc") -> None:
    """Accumulate `seconds` under `name` (counter only, no trace event)."""
    if _mode == OFF:
        return
    with _lock:
        _acc[name] += seconds
        _acc_self[name] += seconds
        _cnt[name] += 1
        _cat.setdefault(name, category)


def count(name: str, inc: float = 1.0, category: str = "count") -> None:
    """Unit-less monotonic counter (leaf counts, recompiles, drops...).
    Part of the run record: recorded in every mode, so call it per launch,
    per train or per request, never per split or per row."""
    with _lock:
        _counts[name] += inc
        _count_cat.setdefault(name, category)
    # snapshot the sink once: two separate reads of the global would
    # race flight.disarm() between the None check and the call
    sink = _flight_count          # guarded-by: GIL
    if sink is not None:
        sink(name, inc, category)


def clear_counts_prefix(prefixes) -> None:
    """Drop counters whose names start with any of `prefixes` — the
    per-run scoping hook for run-scoped counter families (the
    ``numerics::``/``health::`` reset at train arming; everything else
    stays process-cumulative as before)."""
    pfx = tuple(prefixes) if not isinstance(prefixes, str) else (prefixes,)
    with _lock:
        for k in [k for k in _counts if k.startswith(pfx)]:
            del _counts[k]
            _count_cat.pop(k, None)


def device_memory_stats() -> Optional[List[Dict[str, int]]]:
    """``bytes_in_use`` / ``peak_bytes_in_use`` / ``bytes_limit`` of every
    local device's allocator, one dict a device, or None where the backend
    keeps no such statistics (the CPU). Blocks on nothing: it is the
    allocator's state now, and PJRT allocates a program's outputs and
    temporaries when the program is dispatched."""
    out = []
    for dev in jax.local_devices():
        stats = dev.memory_stats()
        if not stats:
            return None
        out.append({key: int(stats[key]) for key in
                    ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                    if key in stats})
    return out


def _hbm_now() -> Optional[List[List[int]]]:
    """[bytes_in_use, peak_bytes_in_use] a local device, or None. Never the
    call that starts the backend: a ``Dataset.construct`` before any device
    use must not initialise the chip from inside ``io::Construct``."""
    if not xla_bridge.backends_are_initialized():
        return None
    stats = device_memory_stats()
    if not stats:
        return None
    return [[s.get("bytes_in_use", 0), s.get("peak_bytes_in_use", 0)]
            for s in stats]


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


# ---------------------------------------------------------------------------
# run identifiers
# ---------------------------------------------------------------------------

def begin_launch() -> int:
    """Number the fused launch that starts now (from 0 within the train in
    flight); every span until the next call carries it."""
    global _cur_launch
    with _lock:
        _cur_launch = 0 if _cur_launch is None else _cur_launch + 1
        return _cur_launch


def run_tags() -> dict:
    """The identifiers a span opened now would carry, as the ``train=`` /
    ``launch=`` tags :func:`scope` takes. A booster keeps them to tag what
    it does for that launch after ``engine.train`` returned."""
    return {"train": _cur_train, "launch": _cur_launch}  # guarded-by: GIL


def train_root(fn):
    """Decorator for ``engine.train``: numbers the call and runs it under
    the ``engine::train`` span, the root of one job's run record."""
    @functools.wraps(fn)
    def wrap(*a, **k):
        global _train_seq, _cur_train, _cur_launch
        with _lock:
            outer = (_cur_train, _cur_launch)
            _train_seq += 1
            _cur_train, _cur_launch = _train_seq, None
        try:
            with scope("engine::train", category="setup", always=True):
                return fn(*a, **k)
        finally:
            with _lock:
                _cur_train, _cur_launch = outer
    return wrap


def _span_ids(st: list, tags: dict) -> Tuple[int, Optional[int]]:
    """Explicit ``train=`` / ``launch=`` tags win, then the train in flight,
    then the enclosing span (a compile under a booster's late
    ``MaterializePending`` belongs to that booster's launch)."""
    if _cur_train:
        train, launch = _cur_train, _cur_launch     # guarded-by: GIL
    elif st:
        train, launch = st[-1][2], st[-1][3]
    else:
        train, launch = 0, None
    return tags.pop("train", train), tags.pop("launch", launch)


def _record_span(name: str, category: str, t0: float, elapsed: float,
                 self_s: float, parent: Optional[str], train: int,
                 launch: Optional[int], tags: Optional[dict],
                 always: bool, hbm: Optional[dict] = None) -> None:
    """Fold one finished span into the tables, the ring (`always` spans)
    and the TRACE timeline."""
    global _dropped
    with _lock:
        _acc[name] += elapsed
        _acc_self[name] += self_s
        _cnt[name] += 1
        _cat.setdefault(name, category)
        if not always and _mode != TRACE:
            return
        ev = {"name": name, "cat": category, "ts": t0 + _EPOCH,
              "dur": elapsed, "self": self_s,
              "tid": threading.get_ident(), "train": train}
        if launch is not None:
            ev["launch"] = launch
        if parent is not None:
            ev["parent"] = parent
        if tags:
            ev["args"] = tags
        if hbm is not None:
            ev["hbm"] = hbm
        if always:
            _ring.append(ev)
        if _mode == TRACE:
            if len(_events) < MAX_EVENTS:
                _events.append(ev)
            else:
                _dropped += 1


def _hand_up_rise(st: list, rise: Optional[List[int]]) -> None:
    """Add a finished span's rise of the peak to what the children of the
    enclosing span have raised it by."""
    if st and rise is not None:
        up = st[-1][4]
        st[-1][4] = rise if up is None else [a + b for a, b in zip(up, rise)]


def _hbm_closed(opened: List[List[int]], kids: Optional[List[int]],
                st: list) -> Optional[dict]:
    """The ``hbm`` field of a ring entry: the allocator's reading a local
    device when the span opened and now that it closes, and ``rise``, what
    the span itself raised each device's peak by. ``peak_bytes_in_use`` is
    a watermark over the life of the process, so a span's own rise is its
    close less its open less its child spans' rises, the rule of self
    seconds."""
    closed = _hbm_now()
    if closed is None:
        _hand_up_rise(st, kids)
        return None
    rise = [c[1] - o[1] for o, c in zip(opened, closed)]
    _hand_up_rise(st, rise)
    if kids is not None:
        rise = [r - k for r, k in zip(rise, kids)]
    return {"open": opened, "close": closed, "rise": rise}


@contextlib.contextmanager
def scope(name: str, category: str = "misc", sync_value=None,
          always: bool = False, **tags):
    """Accumulate the wall time of the enclosed block under `name`.

    ``always=True`` puts the span in the run record: it is recorded with
    telemetry OFF too and leaves a ring entry, which carries the HBM
    allocator's reading at both ends (``hbm``, see :func:`_hbm_closed`).
    Only for spans that occur O(1) times per ``lgb.train`` or per fused
    launch.

    When `sync_value` is a callable, it is invoked on exit and its result
    passed to jax.block_until_ready before the clock stops — use for
    scopes whose cost is a device computation (never with telemetry OFF:
    the run record does not block). In TRACE mode the span is also
    appended to the event timeline with its nesting parent.
    """
    if _mode == OFF and not always:
        yield
        return
    st = _stack()
    parent = st[-1][0] if st else None
    train, launch = _span_ids(st, tags)
    # [name, accumulated child-span seconds, train, launch,
    #  accumulated child-span rise of the HBM peak a device]
    st.append([name, 0.0, train, launch, None])
    note = {"train": train} if launch is None \
        else {"train": train, "launch": launch}
    t0 = time.perf_counter()
    hbm_open = _hbm_now() if always else None
    try:
        with jax.profiler.TraceAnnotation("lgbm:" + name, **note):
            yield
    finally:
        if sync_value is not None and _mode != OFF:
            try:
                jax.block_until_ready(sync_value())
            except Exception:
                pass
        entry = st.pop()
        if hbm_open is None:
            hbm = None
            _hand_up_rise(st, entry[4])
        else:
            hbm = _hbm_closed(hbm_open, entry[4], st)
        t1 = time.perf_counter()
        elapsed = t1 - t0
        if st:
            st[-1][1] += elapsed
        _record_span(name, category, t0, elapsed, elapsed - entry[1],
                     parent, train, launch, tags or None, always, hbm)
        # same single-snapshot discipline as count(): never two reads
        # of the global sink around a call
        sink = _flight_span       # guarded-by: GIL
        if sink is not None:
            sink(name, category, t0 + _EPOCH, elapsed)


def timed(name: str, category: str = "misc", always: bool = False,
          new_launch: bool = False) -> Callable:
    """Decorator form (the FunctionTimer analog). ``new_launch`` marks the
    function that issues one fused launch: every call numbers the next
    launch (:func:`begin_launch`) before its span opens."""
    def deco(fn):
        @functools.wraps(fn)
        def wrap(*a, **k):
            if new_launch:
                begin_launch()
            if _mode == OFF and not always:
                return fn(*a, **k)
            with scope(name, category=category, always=always):
                return fn(*a, **k)
        return wrap
    return deco


def _is_tracer(x) -> bool:
    try:
        from jax.core import Tracer
    except ImportError:  # pragma: no cover - jax internals moved
        from jax._src.core import Tracer
    return isinstance(x, Tracer)


def launch_wrapper(fn, name: str, category: str = "ops",
                   tracer_arg: Optional[int] = None,
                   histogram: Optional[str] = None,
                   always: bool = False, **tags) -> Callable:
    """Wrap a jitted callable in a launch-cost span (OFF: one int compare,
    unless ``always`` puts it in the run record).

    Dispatch is async, so the span measures LAUNCH cost; device time shows
    up at the next sync point or the xplane profile. When ``tracer_arg``
    names a positional argument, the span name gains a ``(trace)`` /
    ``(launch)`` suffix depending on whether that argument is a jax Tracer
    — i.e. the call is being traced into an outer jit (the fused
    K-iteration scans), costing trace-construction once per compile.

    ``histogram`` additionally streams each (non-traced) invocation's
    wall into the named log-bucketed histogram (telemetry/histo.py), so
    per-program launch-time DISTRIBUTIONS are queryable, not just
    totals — the persist level-program driver records here."""
    @functools.wraps(fn)
    def wrapper(*a, **k):
        if _mode == OFF and not always:
            return fn(*a, **k)
        n = name
        traced = False
        if tracer_arg is not None:
            traced = _is_tracer(a[tracer_arg])
            n += "(trace)" if traced else "(launch)"
        t0 = time.perf_counter()
        try:
            with scope(n, category=category, always=always, **tags):
                return fn(*a, **k)
        finally:
            if histogram is not None and not traced:
                from . import histo
                histo.observe(histogram, time.perf_counter() - t0,
                              unit="s", category=category)
    return wrapper


def keep_program(name: str, fn, args: tuple) -> None:
    """Run record, trace mode only (a no-op in off and timers): the
    program the jitted ``fn`` launches under span ``name`` on one device,
    lowered for the abstract values of ``args`` (taken before the launch
    donates them; no sharding, as the launch's uncommitted arguments have
    none, so that the jit's caches match and nothing is traced or lowered
    again). The lowered module is kept, not ``fn`` and what its closures
    hold; ``program_scopes`` compiles it only when asked, from the same
    caches. The newest program a name replaces the one before it."""
    if _mode != TRACE:
        return

    def spec(a):
        aval = jax.typeof(a)
        return jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                    weak_type=aval.weak_type)
    lowered = fn.lower(*jax.tree.map(spec, args))
    with _lock:
        _programs[name] = lowered


_OP_NAME = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*'
                      r'metadata=\{op_name="([^"]*)"', re.M)


def program_scopes(name: str, scopes) -> Dict[str, str]:
    """{HLO instruction name: scope} for the instructions of the program
    kept under ``name`` whose ``op_name`` metadata holds one of ``scopes``
    (``jax.named_scope`` names) as a path component, the outermost if
    several: what a device trace's per-instruction seconds need to be
    summed by scope (an "XLA Ops" event carries no scope of its own).
    Empty where nothing is kept under ``name`` (no launch under it in
    trace mode since the last :func:`reset`). Lowered and compiled
    through the jit's own caches, so it compiles nothing while JAX still
    holds the launched program."""
    with _lock:
        lowered = _programs.get(name)
    if lowered is None:
        return {}
    text = lowered.compile().as_text()
    out = {}
    for instr, op_name in _OP_NAME.findall(text):
        hit = [part for part in op_name.split("/") if part in scopes]
        if hit:
            out[instr] = hit[0]
    return out


def device_wait(name: str, value, **tags):
    """Block on `value` (jax.block_until_ready) inside a span of the
    explicit ``device_wait`` category; returns `value`. When telemetry is
    OFF this does NOT block — pipeline timing stays untouched — so only
    wrap values that a subsequent host read would block on anyway."""
    if _mode == OFF:
        return value
    with scope(name, category="device_wait", **tags):
        try:
            jax.block_until_ready(value)
        except Exception:
            pass
    return value


def record_iteration(rec: dict) -> None:
    """Store one TrainingMonitor per-iteration record for export."""
    if _mode == OFF:
        return
    with _lock:
        _iter_records.append(rec)


# ---------------------------------------------------------------------------
# snapshots
# ---------------------------------------------------------------------------

def snapshot() -> Dict[str, Tuple[float, int]]:
    """{name: (total seconds, hit count)} — the utils.timer contract."""
    with _lock:
        return {k: (_acc[k], _cnt[k]) for k in _acc}


def snapshot_full() -> Dict[str, Tuple[float, int, str]]:
    """{name: (total seconds, hit count, category)}."""
    with _lock:
        return {k: (_acc[k], _cnt[k], _cat.get(k, "misc")) for k in _acc}


def counts_snapshot() -> Dict[str, float]:
    with _lock:
        return dict(_counts)


def category_totals() -> Dict[str, float]:
    """SELF-seconds per category — the coarse phase breakdown.

    Nested child-span time is subtracted from each span before summing
    (boosting::TrainOneIter encloses tree_learner:: and ops:: spans; the
    inclusive per-name table would count the same second up to 4 times
    across categories), so these values near-partition the instrumented
    wall time; the ``compile`` spans made from jax.monitoring events count
    as children of the host span they fired in, like any nested span.
    The per-name tables (:func:`snapshot` / :func:`snapshot_full`) stay
    inclusive, matching the reference Timer semantics."""
    out: Dict[str, float] = defaultdict(float)
    with _lock:
        for k, sec in _acc_self.items():
            out[_cat.get(k, "misc")] += sec
    return dict(out)


def events_snapshot() -> List[dict]:
    """The TRACE-mode timeline (empty in the other modes)."""
    with _lock:
        return list(_events)


def ring_snapshot() -> List[dict]:
    """The run record's ring, oldest first: one dict per `always` span with
    ``name``, ``cat``, ``ts`` (unix seconds), ``dur``, ``self`` (``dur``
    less its child spans), ``tid``, ``train``, and where they apply
    ``launch``, ``parent`` and ``args``. Filled in every mode."""
    with _lock:
        return list(_ring)


def dropped_events() -> int:
    return _dropped


def iteration_records() -> List[dict]:
    with _lock:
        return list(_iter_records)


# ---------------------------------------------------------------------------
# XLA compile tracking: which step compiled, and for how long
# ---------------------------------------------------------------------------

_JAX_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax::jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax::lower",
    "/jax/core/compile/backend_compile_duration": "jax::backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax::cache_load",
}
_JAX_EVENTS = {
    "/jax/compilation_cache/cache_hits": "jax::cache_hits",
    "/jax/compilation_cache/cache_misses": "jax::cache_misses",
    "/jax/compilation_cache/compile_requests_use_cache":
        "jax::compile_requests",
}
# finished jax events a thread keeps until an enclosing one absorbs them
_JAX_DONE_MAX = 1024
# shorter events are not recorded: nearly all are the traces of the jitted
# jax.numpy functions a trace calls (a thousand in a tiny train, a tenth of
# its trace seconds), whose time stays in the enclosing trace's own
_JAX_MIN_S = 1e-3


def _on_jax_duration(event: str, duration: float, **kw) -> None:
    """One jax.monitoring duration event -> one run-record span that ends
    now, under the span open on this thread. JAX reports an event when it
    ends, and a trace reports the traces of the jitted functions it calls
    before itself: those become its children, so self seconds add up to
    wall seconds. ``jax::backend_compile`` wraps the persistent-cache
    look-up; a hit is recorded as ``jax::cache_load`` alone."""
    name = _JAX_DURATIONS.get(event)
    if name is None:
        return
    if name == "jax::cache_load":
        _tls.cache_hit = True
    elif name == "jax::backend_compile" and getattr(_tls, "cache_hit", False):
        _tls.cache_hit = False
        return
    if duration < _JAX_MIN_S:
        return
    t0 = time.perf_counter() - duration
    done = getattr(_tls, "jax_done", None)
    if done is None:
        done = _tls.jax_done = deque(maxlen=_JAX_DONE_MAX)
    inner = 0.0
    while done and done[-1][0] >= t0:
        inner += done.pop()[1]
    done.append((t0, duration))
    self_s = max(duration - inner, 0.0)
    st = _stack()
    parent = None
    if st:
        parent = st[-1][0]
        st[-1][1] += self_s
    tags = {}
    train, launch = _span_ids(st, tags)
    if kw.get("fun_name"):
        tags["fun"] = str(kw["fun_name"])
    _record_span(name, "compile", t0, duration, self_s, parent, train,
                 launch, tags or None, True)
    if name == "jax::backend_compile":
        # the TrainingMonitor's per-iteration recompile count
        count(name, category="compile")


def _on_jax_event(event: str, **kw) -> None:
    name = _JAX_EVENTS.get(event)
    if name is not None:
        count(name, category="compile")


# installed once, at import, in every mode
jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
jax.monitoring.register_event_listener(_on_jax_event)


# ---------------------------------------------------------------------------
# exit hook: the reference global_timer-destructor report
# ---------------------------------------------------------------------------

@atexit.register
def _report_at_exit() -> None:  # pragma: no cover - exit path
    if _mode == OFF:
        return
    from . import export
    if _mode == TRACE and _out_path and not _exported:
        try:
            export.maybe_export()
        except Exception:
            pass
    export.print_report()
