"""Telemetry subsystem: no-op-when-off guarantees, span registry semantics,
Chrome-trace export round-trip, and the TrainingMonitor riding the
CallbackEnv protocol without altering it."""
import json
import threading
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.telemetry import events
from lightgbm_tpu.utils import timer


@pytest.fixture(autouse=True)
def _telemetry_clean():
    """Every test starts and ends with telemetry OFF and an empty registry
    (telemetry state is process-global by design)."""
    events.disable()
    events.reset()
    events.set_out_path(None)
    yield
    events.disable()
    events.reset()
    events.set_out_path(None)


def _toy(n=400, f=8, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(float)
    return X, y


TOY_PARAMS = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
              "verbosity": -1, "metric": "none"}


# ---------------------------------------------------------------------------
# disabled-by-default guarantees (tier-1 guard)
# ---------------------------------------------------------------------------

def test_disabled_by_default_noop():
    assert events.mode() == events.OFF
    assert not events.enabled() and not timer.enabled()
    with events.scope("x", category="misc"):
        pass
    events.add("y", 1.0)
    events.count("z")
    events.record_iteration({"iteration": 0})
    assert events.snapshot() == {}
    assert events.counts_snapshot() == {}
    assert events.events_snapshot() == []
    assert events.iteration_records() == []
    # device_wait must NOT block (and must hand the value back) when off
    sentinel = object()
    assert events.device_wait("w", sentinel) is sentinel


def test_atexit_hook_silent_when_disabled(capsys):
    events._report_at_exit()
    telemetry.print_report()
    out = capsys.readouterr()
    assert out.out == "" and out.err == ""


def test_configure_off_is_noop():
    events.configure("off", None)
    assert events.mode() == events.OFF
    cfg = lgb.Config({"tpu_telemetry": "off"})
    events.configure_from_config(cfg)
    assert events.mode() == events.OFF


def test_config_telemetry_does_not_leak_across_trains(tmp_path):
    """tpu_telemetry= is scoped to the trains that ask for it: the next
    lgb.train with default params goes back to OFF, while an explicit
    enable() survives config-default trains."""
    X, y = _toy(n=300)
    out = str(tmp_path / "leak.json")
    lgb.train(dict(TOY_PARAMS, tpu_telemetry="trace", telemetry_out=out),
              lgb.Dataset(X, y), 2, verbose_eval=False)
    assert events.mode() == events.TRACE
    lgb.train(dict(TOY_PARAMS), lgb.Dataset(X, y), 2, verbose_eval=False)
    assert events.mode() == events.OFF
    events.enable("timers")
    lgb.train(dict(TOY_PARAMS), lgb.Dataset(X, y), 2, verbose_eval=False)
    assert events.mode() == events.TIMERS


def test_noop_scope_overhead_is_tiny():
    """The disabled path is one int compare + generator setup; a coarse
    ceiling guards against someone adding real work to it."""
    t0 = time.perf_counter()
    for _ in range(20_000):
        with events.scope("hot"):
            pass
    elapsed = time.perf_counter() - t0
    assert elapsed < 1.0, "no-op scope path cost %.3fs / 20k calls" % elapsed


def test_training_off_records_nothing_1k_rows():
    """tpu_telemetry=off (default): a 1k-row run leaves the registry empty
    and a warm re-run stays fast (coarse per-iteration overhead guard)."""
    X, y = _toy(n=1000)
    ds = lgb.Dataset(X, y)
    lgb.train(dict(TOY_PARAMS), ds, 8, verbose_eval=False)
    assert events.snapshot() == {}
    assert events.events_snapshot() == []
    t0 = time.perf_counter()
    ds2 = lgb.Dataset(X, y)
    bst = lgb.train(dict(TOY_PARAMS), ds2, 8, verbose_eval=False)
    bst._booster._materialize_pending()
    warm = time.perf_counter() - t0
    assert events.snapshot() == {}
    assert warm < 30.0, "warm 1k-row 8-iter run took %.1fs" % warm


def test_off_vs_timers_identical_model():
    """Enabling telemetry must not change the trained model."""
    X, y = _toy(n=600)
    bst_off = lgb.train(dict(TOY_PARAMS), lgb.Dataset(X, y), 6,
                        verbose_eval=False)
    p_off = bst_off.predict(X)
    events.enable("timers")
    bst_on = lgb.train(dict(TOY_PARAMS), lgb.Dataset(X, y), 6,
                       verbose_eval=False)
    p_on = bst_on.predict(X)
    np.testing.assert_array_equal(p_off, p_on)
    assert events.snapshot(), "timers mode recorded nothing"


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_span_nesting_and_trace_events():
    events.enable("trace")
    with events.scope("outer", category="a"):
        time.sleep(0.002)
        with events.scope("inner", category="b", tag=1):
            time.sleep(0.001)
    evs = events.events_snapshot()
    by_name = {e["name"]: e for e in evs}
    assert set(by_name) == {"outer", "inner"}
    inner, outer = by_name["inner"], by_name["outer"]
    assert inner["parent"] == "outer" and "parent" not in outer
    assert inner["args"] == {"tag": 1}
    # inner nests inside outer on the timeline
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    snap = events.snapshot()
    assert snap["outer"][1] == 1 and snap["inner"][1] == 1
    assert events.snapshot_full()["inner"][2] == "b"


def test_thread_safety():
    events.enable("timers")
    threads, per = 8, 200
    barrier = threading.Barrier(threads)

    def work(i):
        barrier.wait()
        for _ in range(per):
            with events.scope("shared"):
                pass
            with events.scope("own-%d" % i):
                pass
            events.count("hits")

    ts = [threading.Thread(target=work, args=(i,)) for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    snap = events.snapshot()
    assert snap["shared"][1] == threads * per
    for i in range(threads):
        assert snap["own-%d" % i][1] == per
    assert events.counts_snapshot()["hits"] == threads * per


def test_timer_module_aliases():
    """utils.timer keeps its original surface as thin telemetry aliases."""
    timer.enable()
    assert events.mode() == events.TIMERS and timer.enabled()

    @timer.timed("alias::fn")
    def fn():
        return 42

    assert fn() == 42
    with timer.scope("alias::scope"):
        pass
    timer.add("alias::manual", 0.5)
    snap = timer.snapshot()
    assert snap["alias::fn"][1] == 1
    assert snap["alias::scope"][1] == 1
    assert snap["alias::manual"] == (0.5, 1)
    timer.disable()
    assert not timer.enabled()


def test_print_report_format(capsys):
    events.enable("timers")
    events.add("scope::a", 2.0, category="boosting")
    events.add("scope::b", 1.0)
    telemetry.print_report()
    err = capsys.readouterr().err
    assert "time-tag report" in err
    assert "scope::a" in err and "scope::b" in err and "(sum)" in err
    # sorted by total seconds, largest first
    assert err.index("scope::a") < err.index("scope::b")


# ---------------------------------------------------------------------------
# Chrome-trace export round-trip on a real (tiny) training run
# ---------------------------------------------------------------------------

def test_chrome_trace_roundtrip(tmp_path):
    out = str(tmp_path / "run.json")
    X, y = _toy(n=500)
    ds = lgb.Dataset(X, y)
    params = dict(TOY_PARAMS, tpu_telemetry="trace", telemetry_out=out)
    bst = lgb.train(params, ds, 4, verbose_eval=False)
    assert bst.num_trees() == 4
    trace = json.loads((tmp_path / "run.json").read_text())
    evs = trace["traceEvents"]
    assert evs, "trace has no events"
    for e in evs:
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["ph"] == "X"
    cats = {e["cat"] for e in evs}
    assert {"boosting", "tree_learner", "ops"} <= cats
    names = {e["name"] for e in evs}
    assert "boosting::TrainOneIter" in names
    assert "tree_learner::Train(launch)" in names
    assert any(n.startswith("ops::grow_tree") for n in names)
    # metrics snapshot JSONL next to the trace
    lines = [json.loads(ln) for ln in
             (tmp_path / "run.metrics.jsonl").read_text().splitlines()]
    kinds = {ln["kind"] for ln in lines}
    assert {"header", "timer", "iteration"} <= kinds
    iters = [ln for ln in lines if ln["kind"] == "iteration"]
    assert len(iters) == 4


@pytest.mark.slow  # tier-1 870s budget: cheaper sibling tests cover this area
def test_collective_category_on_mesh(tmp_path):
    """Sharded (data-parallel) training tags its dispatches 'collective'."""
    out = str(tmp_path / "mesh.json")
    X, y = _toy(n=512)
    ds = lgb.Dataset(X, y)
    params = dict(TOY_PARAMS, tree_learner="data", tpu_telemetry="trace",
                  telemetry_out=out)
    bst = lgb.train(params, ds, 3, verbose_eval=False)
    assert bst.num_trees() == 3
    trace = json.loads((tmp_path / "mesh.json").read_text())
    cats = {e["cat"] for e in trace["traceEvents"]}
    assert "collective" in cats
    coll = [e for e in trace["traceEvents"] if e["cat"] == "collective"]
    assert any(e["name"].startswith("collective::") for e in coll)
    assert all(e["args"]["shards"] >= 1 for e in coll
               if "args" in e and "shards" in e["args"])


# ---------------------------------------------------------------------------
# TrainingMonitor through the CallbackEnv protocol
# ---------------------------------------------------------------------------

def test_training_monitor_with_callback_consumers():
    """The monitor rides as one more post-iteration callback: per-iteration
    records exist AND print_evaluation/record_evaluation see the exact same
    CallbackEnv they always did."""
    X, y = _toy(n=500)
    Xv, yv = _toy(n=200, seed=11)
    ds = lgb.Dataset(X, y)
    vs = lgb.Dataset(Xv, yv, reference=ds)
    evals_result = {}
    rounds = 5
    params = dict(TOY_PARAMS, metric="binary_logloss",
                  tpu_telemetry="timers")
    bst = lgb.train(params, ds, rounds, valid_sets=[vs],
                    valid_names=["hold"], verbose_eval=2,
                    callbacks=[lgb.record_evaluation(evals_result)])
    # CallbackEnv contract untouched: record_evaluation populated normally
    assert list(evals_result) == ["hold"]
    assert len(evals_result["hold"]["binary_logloss"]) == rounds
    # monitor attached and recorded every iteration
    mon = bst._telemetry_monitor
    assert len(mon.records) == rounds
    for i, rec in enumerate(mon.records):
        assert rec["iteration"] == i
        assert rec["wall"] >= 0.0
        assert isinstance(rec["buckets"], dict)
        assert rec["num_evals"] >= 1
    # eval spans got bucketed, and the registry mirrors the records
    assert any("eval" in r["buckets"] or "boosting" in r["buckets"]
               for r in mon.records)
    assert len(events.iteration_records()) == rounds


def test_monitor_standalone_record():
    events.enable("timers")
    mon = telemetry.TrainingMonitor(name="unit")
    with events.scope("s", category="boosting"):
        time.sleep(0.001)
    rec = mon.record(0)
    assert rec["monitor"] == "unit" and rec["iteration"] == 0
    assert rec["buckets"].get("boosting", 0) > 0
    with events.scope("s", category="boosting"):
        time.sleep(0.001)
    rec2 = mon.record(1)
    assert rec2["wall"] > 0
    assert rec2["buckets"].get("boosting", 0) > 0


# ---------------------------------------------------------------------------
# xplane device profile (needs the TF proto bindings; CPU traces carry no
# XLA-op device planes, so this only checks the parse/report plumbing)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_xplane_parse_smoke(tmp_path):
    pytest.importorskip("tensorflow.tsl.profiler.protobuf")
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.telemetry import xplane
    tdir = str(tmp_path / "trace")
    with xplane.collect_trace(tdir):
        jax.block_until_ready(jax.jit(lambda x: x * 2)(jnp.ones(128)))
    planes = xplane.parse_xplane_dir(tdir)
    report = xplane.format_device_report(planes, iters=1)
    assert isinstance(report, str) and report


# ---------------------------------------------------------------------------
# which device, which grower: nothing is assumed and nothing is silent
# ---------------------------------------------------------------------------

class _Dev:
    def __init__(self, kind):
        self.device_kind = kind


def test_detect_profile_matches_device_kind_or_raises(monkeypatch):
    import jax
    from lightgbm_tpu.telemetry import devices
    monkeypatch.delenv("LGBTPU_DEVICE_PROFILE", raising=False)
    # the v5e chip reports itself as "TPU v5 lite", not "v5e"
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev("TPU v5 lite")])
    assert devices.detect_profile().name == "v5e"
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev("TPU v9 mega")])
    with pytest.raises(ValueError, match="no device profile"):
        devices.detect_profile()

    def no_backend(*a):
        raise RuntimeError("Unable to initialize backend")
    monkeypatch.setattr(jax, "devices", no_backend)
    with pytest.raises(RuntimeError):
        devices.detect_profile()
    # a machine without the accelerator names its device itself
    monkeypatch.setenv("LGBTPU_DEVICE_PROFILE", "v4")
    assert devices.detect_profile().name == "v4"


@pytest.mark.parametrize("backend,want", [("tpu", True), ("cpu", False),
                                          ("gpu", False)])
def test_on_tpu_is_true_on_the_tpu_only(monkeypatch, backend, want):
    import jax
    from lightgbm_tpu.telemetry import devices
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert devices.on_tpu() is want


def test_v1_fused_scan_counts_its_trees():
    """A run that misses the persist path says so: the v1 grower inside
    the fused k=16 lax.scan counts into v1_grow_trees like the
    per-iteration v1 path does."""
    X, y = _toy(n=600)
    events.enable("timers")
    lgb.train(dict(TOY_PARAMS), lgb.Dataset(X, y), 17, verbose_eval=False)
    counts = events.counts_snapshot()
    assert counts.get("tree_learner::v1_grow_trees") == 17, counts
    assert "tree_learner::persist_scan_trees" not in counts, counts
