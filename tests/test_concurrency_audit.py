"""Concurrency auditor (lightgbm_tpu/analysis/concurrency_audit.py).

Contracts under test:

* the three acceptance seeded races — an unguarded shared write, a lock
  held across ``.result()``, and a two-lock ordering cycle — each flip
  the gate (``run()`` reports a failing AuditResult over a seeded
  mini-repo);
* lock-discipline semantics: one-call-level lock inheritance (the
  ``_swap_locked`` pattern), the GIL-atomic blessing table, the
  single-reference publish rule, ``__init__`` pre-publication writes,
  ``# guarded-by:`` annotations (and that a typo'd annotation is itself
  a finding), inconsistent lock sets;
* blocking-hold semantics: ``Condition.wait`` on the held lock is
  blessed, waits on foreign objects and one-call-level blocking are
  flagged, a nested thread target does not inherit its spawner's
  lexical locks;
* lock order: plain-Lock self-reentry is a self-deadlock finding,
  RLock re-entry is silent, consistent nesting stays acyclic;
* the repo self-scan is green (zero unsuppressed findings, acyclic
  order graph) and discovers the known thread roots, with the
  ``analysis::concurrency_*`` counters bumped;
* behavioral satellites: the retry watchdog's abandoned worker is
  join-with-timeout reaped on the guard's exception exit (leak counter
  when it would not die), and AsyncBatchServer.stop() racing a
  deadline flush neither hangs nor drops a request.
"""
import os
import textwrap
import threading
import time

import numpy as np
import pytest

from lightgbm_tpu.analysis import concurrency_audit as ca
from lightgbm_tpu.analysis.auditors import all_auditors
from lightgbm_tpu.analysis.config import GraftlintConfig, load_config
from lightgbm_tpu.telemetry import events


@pytest.fixture
def counters():
    prev_mode = events.mode()
    events.enable("timers")
    events.reset()
    yield events.counts_snapshot
    events.reset()
    if prev_mode == events.OFF:
        events.disable()


def _findings(src):
    return ca.check_fixture(textwrap.dedent(src))


# ---------------------------------------------------------------------
# lock discipline (JG011)


UNGUARDED_WRITE = """
    import threading

    class Server:
        def __init__(self):
            self._lock = threading.Lock()
            self._count = 0
            self._thread = None

        def start(self):
            self._thread = threading.Thread(target=self._loop,
                                            daemon=True)
            self._thread.start()

        def _loop(self):
            self._count += 1

        def submit(self):
            self._count += 1
"""


def test_unguarded_shared_write_flagged():
    hits = _findings(UNGUARDED_WRITE)
    assert any("unguarded mutation" in h and "Server._count" in h
               for h in hits)


def test_guarded_twin_silent():
    assert _findings("""
        import threading

        class Server:
            def __init__(self):
                self._lock = threading.Lock()
                self._count = 0
                self._thread = None

            def start(self):
                self._thread = threading.Thread(target=self._loop,
                                                daemon=True)
                self._thread.start()

            def _loop(self):
                with self._lock:
                    self._count += 1

            def submit(self):
                with self._lock:
                    self._count += 1
        """) == []


def test_one_call_level_lock_inheritance():
    """The _swap_locked pattern: a helper with no lexical lock whose
    EVERY call site holds the lock is analyzed as holding it."""
    assert _findings("""
        import threading

        class Registry:
            def __init__(self):
                self._lock = threading.Lock()
                self._active = None
                self._swaps = 0

            def _swap_locked(self, slot):
                self._active = slot
                self._swaps += 1

            def swap(self, slot):
                with self._lock:
                    self._swap_locked(slot)

            def load(self, slot):
                with self._lock:
                    self._swap_locked(slot)
        """) == []


def test_one_unlocked_call_site_breaks_inheritance():
    src = """
        import threading

        class Registry:
            def __init__(self):
                self._lock = threading.Lock()
                self._swaps = 0

            def _swap_locked(self, slot):
                self._swaps += 1

            def swap(self, slot):
                with self._lock:
                    self._swap_locked(slot)

            def sneak(self, slot):
                self._swap_locked(slot)
        """
    assert any("Registry._swaps" in h for h in _findings(src))


def test_gil_atomic_deque_append_blessed_dict_rmw_not():
    """deque.append is one bytecode under the GIL (blessed); a dict
    subscript += is a read-modify-write (flagged)."""
    src = """
        import threading
        from collections import deque

        _lock = threading.Lock()
        _ring = deque(maxlen=64)
        _totals = {}

        def sink(ev):
            _ring.append(ev)

        def bump(k):
            _totals[k] += 1

        def install(cb):
            cb(sink)
    """
    hits = _findings(src)
    assert not any("_ring" in h for h in hits)
    assert any("_totals" in h for h in hits)


def test_single_reference_publish_blessed():
    assert _findings("""
        import threading

        class Holder:
            def __init__(self):
                self._lock = threading.Lock()
                self._model = None

            def publish(self, model):
                self._model = model
        """) == []


def test_guarded_by_annotation_blesses_and_typo_is_finding():
    good = """
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self._hits = 0

            def bump(self):
                self._hits += 1    # guarded-by: _lock
        """
    assert _findings(good) == []
    typo = good.replace("guarded-by: _lock", "guarded-by: _lokc")
    hits = _findings(typo)
    assert any("unknown lock/root" in h for h in hits)


def test_inconsistent_lock_sets_flagged():
    hits = _findings("""
        import threading

        class S:
            def __init__(self):
                self._lock_a = threading.Lock()
                self._lock_b = threading.Lock()
                self._n = 0

            def via_a(self):
                with self._lock_a:
                    self._n += 1

            def via_b(self):
                with self._lock_b:
                    self._n += 1
        """)
    assert any("inconsistent lock sets" in h for h in hits)


# ---------------------------------------------------------------------
# blocking-hold (JG012)


HOLD_RESULT = """
    import threading

    class Pool:
        def __init__(self):
            self._lock = threading.Lock()
            self._done = 0

        def flush(self, fut):
            with self._lock:
                out = fut.result()
                self._done += 1
            return out
"""


def test_lock_held_across_result_flagged():
    hits = _findings(HOLD_RESULT)
    assert any("blocking" in h and "result" in h for h in hits)


def test_blocking_after_release_silent():
    assert _findings("""
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self._done = 0

            def flush(self, fut):
                out = fut.result()
                with self._lock:
                    self._done += 1
                return out
        """) == []


def test_condition_wait_on_held_lock_blessed():
    assert _findings("""
        import threading

        class Q:
            def __init__(self):
                self._cond = threading.Condition()
                self._items = []

            def take(self):
                with self._cond:
                    while not self._items:
                        self._cond.wait(timeout=0.01)
                    return self._items.pop()
        """) == []


def test_wait_on_foreign_object_under_lock_flagged():
    hits = _findings("""
        import threading

        class Q:
            def __init__(self):
                self._lock = threading.Lock()

            def drain(self, worker):
                with self._lock:
                    worker.join()
        """)
    assert any("blocking" in h and "join" in h for h in hits)


def test_one_call_level_blocking_propagates():
    hits = _findings("""
        import threading
        import time

        class S:
            def __init__(self):
                self._lock = threading.Lock()

            def _slow(self):
                time.sleep(0.5)

            def tick(self):
                with self._lock:
                    self._slow()
        """)
    assert any("whose body performs a blocking operation" in h
               for h in hits)


def test_nested_thread_target_does_not_inherit_spawner_locks():
    """The retry-watchdog shape: `run` is defined inside a function
    that may hold a lock at spawn time, but executes on its own thread
    with nothing held — its sleep is not a blocking-hold."""
    assert _findings("""
        import threading
        import time

        _lock = threading.Lock()

        def call_with_deadline(fn):
            result = {}

            def run():
                time.sleep(0.01)
                result["value"] = fn()

            with _lock:
                worker = threading.Thread(target=run, daemon=True)
                worker.start()
            worker.join()
            return result.get("value")
        """) == []


# ---------------------------------------------------------------------
# lock order


TWO_LOCK_CYCLE = """
    import threading

    _lock_a = threading.Lock()
    _lock_b = threading.Lock()

    def fwd():
        with _lock_a:
            with _lock_b:
                pass

    def rev():
        with _lock_b:
            with _lock_a:
                pass
"""


def test_two_lock_ordering_cycle_flagged():
    hits = _findings(TWO_LOCK_CYCLE)
    assert any("lock-acquisition-order cycle" in h for h in hits)


def test_consistent_nesting_is_acyclic():
    assert _findings("""
        import threading

        _lock_a = threading.Lock()
        _lock_b = threading.Lock()

        def one():
            with _lock_a:
                with _lock_b:
                    pass

        def two():
            with _lock_a:
                with _lock_b:
                    pass
        """) == []


def test_plain_lock_self_reentry_is_self_deadlock():
    hits = _findings("""
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()

            def outer(self):
                with self._lock:
                    with self._lock:
                        pass
        """)
    assert any("self-deadlock" in h for h in hits)


def test_rlock_reentry_silent():
    assert _findings("""
        import threading

        class S:
            def __init__(self):
                self._lock = threading.RLock()

            def outer(self):
                with self._lock:
                    with self._lock:
                        pass
        """) == []


def test_module_without_locks_or_threads_out_of_scope():
    """Owning a lock or spawning a thread is how code declares
    concurrent intent; a plain single-threaded module is not audited."""
    assert _findings("""
        _cache = {}

        def put(k, v):
            _cache[k] = v

        def bump(k):
            _cache[k] += 1
        """) == []


# ---------------------------------------------------------------------
# the gate: seeded mini-repos flip run(), the real repo stays green


def _seeded_config(tmp_path, source):
    srv = tmp_path / "srv"
    srv.mkdir()
    (srv / "seeded.py").write_text(textwrap.dedent(source))
    return GraftlintConfig(root=str(tmp_path),
                           concurrency_paths=["srv/"])


@pytest.mark.parametrize("source,audit_name", [
    (UNGUARDED_WRITE, "concurrency_discipline"),
    (HOLD_RESULT, "concurrency_blocking_hold"),
    (TWO_LOCK_CYCLE, "concurrency_lock_order"),
])
def test_seeded_race_flips_gate(tmp_path, source, audit_name):
    results = {r.name: r for r in ca.run(_seeded_config(tmp_path,
                                                        source))}
    assert not results[audit_name].ok
    assert results[audit_name].detail


def test_repo_self_scan_green_and_counters(counters):
    cfg = load_config()
    results = {r.name: r for r in ca.run(cfg)}
    assert set(results) == {"concurrency_discipline",
                            "concurrency_blocking_hold",
                            "concurrency_lock_order"}
    assert all(r.ok for r in results.values()), \
        {n: r.detail for n, r in results.items() if not r.ok}
    counts = counters()
    assert counts.get("analysis::concurrency_roots", 0) >= 2
    assert counts.get("analysis::shared_sites", 0) > 0
    assert "analysis::unguarded" not in counts
    assert "analysis::hold_blocking" not in counts


def test_repo_trace_discovers_known_roots():
    trace = ca.extract_trace(load_config())
    assert set(trace) == {"roots", "shared_sites", "lock_order",
                          "findings"}
    roots = {(r["name"], r["kind"]) for r in trace["roots"]}
    assert ("AsyncBatchServer._loop", "thread") in roots
    assert ("_call_with_deadline.run", "thread") in roots
    # the flight-recorder sinks escape as callbacks into events.py
    assert ("_span_sink", "callback") in roots
    assert trace["lock_order"]["cycles"] == []
    assert trace["findings"] == []
    # the serving loop is the condition-wait service loop
    loop = next(r for r in trace["roots"]
                if r["name"] == "AsyncBatchServer._loop")
    assert loop["cond_wait"]


def test_registered_in_auditor_registry():
    assert all_auditors()["concurrency"] is ca


def test_run_accepts_precomputed_artifact(tmp_path):
    cfg = _seeded_config(tmp_path, UNGUARDED_WRITE)
    art = ca.compute_artifact(cfg)
    results = {r.name: r for r in ca.run(cfg, artifact=art)}
    assert not results["concurrency_discipline"].ok


def test_inline_suppression_blesses_gate(tmp_path):
    suppressed = UNGUARDED_WRITE.replace(
        "self._count += 1\n",
        "self._count += 1  # graftlint: disable=JG011\n")
    assert suppressed != UNGUARDED_WRITE
    results = {r.name: r for r in ca.run(_seeded_config(tmp_path,
                                                        suppressed))}
    assert results["concurrency_discipline"].ok


# ---------------------------------------------------------------------
# satellite: retry watchdog shutdown discipline


def test_watchdog_leak_counted_on_exception_exit(counters, monkeypatch):
    """A guard exiting by exception must join-with-timeout its
    abandoned worker; one that will not die inside the grace is counted
    as a leak."""
    from lightgbm_tpu.resilience import retry
    from lightgbm_tpu.utils.log import LightGBMError
    monkeypatch.setattr(retry, "_REAP_GRACE_S", 0.01)
    release = threading.Event()
    old = retry._POLICY
    retry._POLICY = retry.RetryPolicy(timeout_s=0.05, retries=0,
                                      backoff_s=0.01)
    try:
        with pytest.raises(LightGBMError):
            retry.guard("allgather:leak", release.wait, 30.0)
        counts = counters()
        assert counts.get(retry.C_THREAD_LEAK, 0) >= 1
    finally:
        release.set()       # let the leaked worker exit promptly
        retry._POLICY = old


def test_watchdog_reaped_when_it_finishes(counters, monkeypatch):
    """A worker that finishes shortly after the deadline is joined by
    the grace sweep — no leak counter, no lingering thread."""
    from lightgbm_tpu.resilience import retry
    from lightgbm_tpu.utils.log import LightGBMError
    monkeypatch.setattr(retry, "_REAP_GRACE_S", 5.0)
    old = retry._POLICY
    retry._POLICY = retry.RetryPolicy(timeout_s=0.05, retries=0,
                                      backoff_s=0.01)
    try:
        with pytest.raises(LightGBMError):
            retry.guard("allgather:slowpoke", time.sleep, 0.3)
        counts = counters()
        assert retry.C_THREAD_LEAK not in counts
        # this guard's own worker: another file's test on the same xdist
        # worker may have abandoned a sleeping one on purpose
        # (tests/test_resilience.py::test_collective_timeout_no_hang)
        assert not [t for t in threading.enumerate()
                    if t.name == "lgbtpu-collective-allgather:slowpoke"
                    and t.is_alive()]
    finally:
        retry._POLICY = old


# ---------------------------------------------------------------------
# satellite: stop() racing a deadline flush on AsyncBatchServer


@pytest.fixture(scope="module")
def small_model():
    import lightgbm_tpu as lgb
    rng = np.random.default_rng(7)
    X = (rng.integers(0, 16, size=(600, 6)) / 4.0).astype(np.float64)
    y = (X[:, 0] - X[:, 2] > 0.5).astype(float)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "min_data_in_leaf": 5, "seed": 0, "deterministic": True}
    booster = lgb.train(dict(params), lgb.Dataset(X, y, params=params),
                        5, verbose_eval=False)
    return booster, X


def test_stop_during_deadline_flush_race(small_model):
    """stop(drain=True) issued while sub-bucket requests sit inside
    their coalescing window: the shutdown path and the deadline flush
    race on _cond, and every request must still be answered — the
    zero-drop guarantee covers shutdown (and nothing deadlocks)."""
    from lightgbm_tpu.serving import AsyncBatchServer
    booster, X = small_model
    pred = booster._booster.device_predictor()
    ref = booster.predict(X[:7], raw_score=True)
    for _ in range(5):
        server = AsyncBatchServer(pred, min_batch=64, max_batch=256,
                                  max_wait_ms=40.0).start()
        fut = server.submit(X[:7], raw_score=True)
        # land stop() inside the 40ms coalescing window, so the
        # deadline flush and the drain path contend for _cond
        stopper = threading.Thread(target=server.stop)
        stopper.start()
        out = fut.result(timeout=10.0)
        stopper.join(timeout=10.0)
        assert not stopper.is_alive(), "stop() deadlocked"
        np.testing.assert_array_equal(out, ref)


def test_stop_without_drain_fails_pending_cleanly(small_model):
    from lightgbm_tpu.serving import AsyncBatchServer, ServingError
    booster, X = small_model
    pred = booster._booster.device_predictor()
    server = AsyncBatchServer(pred, min_batch=64, max_batch=256,
                              max_wait_ms=250.0).start()
    futs = [server.submit(X[i:i + 3], raw_score=True) for i in range(4)]
    server.stop(drain=False)
    # every future resolves (value or ServingError) — nothing hangs
    for f in futs:
        try:
            f.result(timeout=10.0)
        except ServingError:
            pass
    with pytest.raises(ServingError):
        server.submit(X[:2])


# ---------------------------------------------------------------------
# config scoping


def test_concurrency_paths_config_round_trip():
    cfg = load_config()
    assert any("serving" in p for p in cfg.concurrency_paths)
    assert any("telemetry" in p for p in cfg.concurrency_paths)
    files = ca._audited_files(cfg)
    assert "lightgbm_tpu/serving/server.py" in files
    assert "lightgbm_tpu/resilience/retry.py" in files
    assert all(os.path.isfile(os.path.join(cfg.root, f))
               for f in files)
