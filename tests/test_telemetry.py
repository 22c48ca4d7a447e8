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
    """OFF is the run record only: mode-gated spans, the timeline and the
    iteration records stay empty and nothing blocks; counters and the
    `always` spans are there, the latter in a bounded ring."""
    assert events.mode() == events.OFF
    assert not events.enabled() and not timer.enabled()
    with events.scope("x", category="misc"):
        pass
    events.add("y", 1.0)
    events.record_iteration({"iteration": 0})
    assert events.snapshot() == {}
    assert events.events_snapshot() == []
    assert events.ring_snapshot() == []
    assert events.iteration_records() == []
    # device_wait must NOT block (and must hand the value back) when off
    sentinel = object()
    assert events.device_wait("w", sentinel) is sentinel
    # the run record: counters, and the spans that ask for it
    events.count("z")
    assert events.counts_snapshot() == {"z": 1.0}
    blocked = []
    with events.scope("outer", category="setup", always=True,
                      sync_value=lambda: blocked.append(1)):
        with events.scope("gated"):
            pass
        with events.scope("inner", always=True, k=16):
            pass
    assert blocked == [], "an always span blocked with telemetry off"
    assert set(events.snapshot()) == {"outer", "inner"}
    ring = events.ring_snapshot()
    assert [e["name"] for e in ring] == ["inner", "outer"]
    inner, outer = ring
    assert inner["parent"] == "outer" and "parent" not in outer
    assert inner["args"] == {"k": 16} and outer["cat"] == "setup"
    assert {"ts", "dur", "self", "tid", "train"} <= set(inner)
    assert outer["self"] <= outer["dur"] - inner["dur"] + 1e-9
    assert events.events_snapshot() == []
    for _ in range(events.RING_EVENTS + 10):
        with events.scope("many", always=True):
            pass
    assert len(events.ring_snapshot()) == events.RING_EVENTS
    assert events.snapshot()["many"][1] == events.RING_EVENTS + 10
    events.reset()
    assert events.ring_snapshot() == [] and events.counts_snapshot() == {}


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
    """tpu_telemetry=off (default): a 1k-row run leaves the run record and
    nothing else (no mode-gated span, no timeline, no iteration record),
    and a warm re-run stays fast (coarse per-iteration overhead guard)."""
    X, y = _toy(n=1000)
    ds = lgb.Dataset(X, y)
    lgb.train(dict(TOY_PARAMS), ds, 8, verbose_eval=False)
    ring_names = {e["name"] for e in events.ring_snapshot()}
    assert "engine::train" in ring_names and "io::Construct" in ring_names
    # every accumulated name is a run-record span: nothing mode-gated
    # (boosting::TrainOneIter, tree_learner::Train(launch), ...) ran
    assert set(events.snapshot()) <= ring_names
    assert "boosting::TrainOneIter" not in events.snapshot()
    assert events.events_snapshot() == []
    assert events.iteration_records() == []
    assert events.counts_snapshot()["tree_learner::v1_grow_trees"] == 8
    t0 = time.perf_counter()
    ds2 = lgb.Dataset(X, y)
    bst = lgb.train(dict(TOY_PARAMS), ds2, 8, verbose_eval=False)
    bst._booster._materialize_pending()
    warm = time.perf_counter() - t0
    assert "boosting::TrainOneIter" not in events.snapshot()
    assert events.events_snapshot() == []
    assert len(events.ring_snapshot()) <= events.RING_EVENTS
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
# the run record of a fused-launch train (telemetry off throughout)
# ---------------------------------------------------------------------------

PERSIST_PARAMS = dict(TOY_PARAMS, tpu_persist_scan="force")


def _persist_train(rounds=33, n=2000, seed=3):
    X, y = _toy(n=n, seed=seed)
    return lgb.train(dict(PERSIST_PARAMS), lgb.Dataset(X, y), rounds,
                     verbose_eval=False)


def test_run_record_of_an_off_mode_train():
    """One train: the set-up spans once, one dispatch span per fused
    launch, all under engine::train with one `train` and launches numbered
    from 0; the next train gets the next `train`."""
    assert events.mode() == events.OFF
    _persist_train(33)              # 16 + 16 + a tail of 1: three launches
    ring = events.ring_snapshot()
    by_name = {}
    for e in ring:
        by_name.setdefault(e["name"], []).append(e)
    for name in ("engine::train", "ops::BuildPersistPayload(pack)",
                 "tree_learner::InitCarry(H2D launch)", "boosting::Init",
                 "tree_learner::ToDevice(layout H2D)"):
        assert len(by_name[name]) == 1, (name, sorted(by_name))
    # the learner is built, and the layout copied, before any launch
    init, put = (by_name[n][0] for n in
                 ("boosting::Init", "tree_learner::ToDevice(layout H2D)"))
    assert "launch" not in init and "launch" not in put
    assert init["parent"] == "engine::train"
    assert put["parent"] == "boosting::Init"
    assert init["cat"] == put["cat"] == "setup"
    # the CPU backend keeps no allocator statistics: absent, not zero
    assert not [e for e in ring if "hbm" in e]
    train = by_name["engine::train"][0]["train"]
    assert train >= 1
    ours = [e for e in ring if not e["name"].startswith("jax::")]
    assert {e["train"] for e in ours} == {train}
    launches = by_name["ops::persist_scan(launch)"]
    assert [e["launch"] for e in launches] == [0, 1, 2]
    assert [e["launch"] for e in
            by_name["boosting::TrainMultiIterFast(launch)"]] == [0, 1, 2]
    assert "launch" not in by_name["engine::train"][0]
    assert by_name["ops::BuildPersistPayload(pack)"][0]["launch"] == 0
    # parents form one tree under engine::train
    names = set(by_name)
    for e in ring:
        if e["name"] == "engine::train":
            assert "parent" not in e
        else:
            assert e["parent"] in names, e
    for e in launches:
        assert e["parent"] == "boosting::TrainMultiIterFast(launch)"
    for e in by_name["boosting::TrainMultiIterFast(launch)"]:
        assert e["parent"] == "engine::train"
    root = by_name["engine::train"][0]
    for e in ring:
        assert e["ts"] >= root["ts"] - 1e-3
        assert e["ts"] + e["dur"] <= root["ts"] + root["dur"] + 1e-3
    # launch 0 compiled the fused program, under its dispatch span
    compiled = [e for e in by_name["jax::backend_compile"]
                + by_name.get("jax::cache_load", [])
                if e.get("parent") == "ops::persist_scan(launch)"]
    assert compiled and compiled[0]["launch"] == 0
    _persist_train(16, seed=4)
    trains = [e["train"] for e in events.ring_snapshot()
              if e["name"] == "engine::train"]
    assert trains == [train, train + 1]


def test_late_materialize_belongs_to_the_launch_that_grew_the_trees():
    """The host trees are built when somebody asks for the model, after
    engine.train returned: those spans still carry the train and the
    launch of their batch, split into the wait and the tree building."""
    bst = _persist_train(32)
    assert not [e for e in events.ring_snapshot()
                if e["name"].startswith("boosting::MaterializePending")]
    bst._booster._materialize_pending()
    ring = events.ring_snapshot()
    train = [e for e in ring if e["name"] == "engine::train"][0]["train"]
    for name, cat in (("boosting::MaterializePending(D2H+wait)",
                       "device_wait"),
                      ("boosting::MaterializePending(host trees)",
                       "boosting")):
        got = [e for e in ring if e["name"] == name]
        assert [e["launch"] for e in got] == [0, 1], name
        assert {e["train"] for e in got} == {train}
        assert {e["cat"] for e in got} == {cat}
        assert {e["parent"] for e in got} == {"boosting::MaterializePending"}
    tail = [e for e in ring
            if e["name"] == "tree_learner::FinalizeScores(launch)"]
    assert tail and tail[-1]["train"] == train and tail[-1]["launch"] == 1


def test_compile_event_is_a_child_of_the_open_span():
    import jax
    import jax.numpy as jnp
    x = jnp.arange(7.0)
    events.reset()                  # drop the compiles of making x
    with events.scope("holder", category="setup", always=True):
        jax.block_until_ready(jax.jit(lambda v: jnp.tanh(v) * 3.25 + 7)(x))
    ring = events.ring_snapshot()
    comp = [e for e in ring if e["name"] in ("jax::backend_compile",
                                             "jax::cache_load")]
    assert comp, [e["name"] for e in ring]
    assert all(e["parent"] == "holder" and e["cat"] == "compile"
               for e in comp)
    holder = [e for e in ring if e["name"] == "holder"][0]
    kids = sum(e["self"] for e in ring if e["name"].startswith("jax::"))
    assert holder["self"] == pytest.approx(holder["dur"] - kids, abs=1e-6)
    counts = events.counts_snapshot()
    assert counts.get("jax::compile_requests", 0) >= 1
    for e in comp:
        assert holder["ts"] <= e["ts"] + 1e-3
        assert e["ts"] + e["dur"] <= holder["ts"] + holder["dur"] + 1e-3


class _Allocator:
    """A scripted HBM allocator in ``events.device_memory_stats``'s place:
    every reading is the state left by the ``alloc`` / ``free`` calls so
    far, one dict a device, and ``reads`` counts the readings."""

    def __init__(self, devices=2):
        self.in_use = [0] * devices
        self.peak = [0] * devices
        self.reads = 0

    def alloc(self, dev, nbytes):
        self.in_use[dev] += nbytes
        self.peak[dev] = max(self.peak[dev], self.in_use[dev])

    def free(self, dev, nbytes):
        self.in_use[dev] -= nbytes

    def __call__(self):
        self.reads += 1
        return [{"bytes_in_use": b, "peak_bytes_in_use": p,
                 "bytes_limit": 1 << 34}
                for b, p in zip(self.in_use, self.peak)]


@pytest.fixture
def allocator(monkeypatch):
    script = _Allocator()
    monkeypatch.setattr(events, "device_memory_stats", script)
    monkeypatch.setattr(events.xla_bridge, "backends_are_initialized",
                        lambda: True)
    return script


@pytest.mark.parametrize("mode", ["off", "timers", "trace"])
def test_ring_entry_carries_the_allocators_reading(allocator, mode):
    """A span of the run record reads every local device's allocator when
    it opens and when it closes, in every mode (``off`` records them as it
    records the spans); what a span itself raised the peak by is its rise
    less its children's, the rule of self seconds. A span that is not of
    the run record reads nothing and hands its children's rise up."""
    if mode != "off":
        events.enable(mode)
    allocator.alloc(0, 100)                     # before any span
    with events.scope("outer", category="setup", always=True):
        allocator.alloc(0, 50)                  # outer's own: peak 150
        allocator.free(0, 50)
        with events.scope("between", category="misc"):
            with events.scope("inner", category="setup", always=True):
                allocator.alloc(0, 400)         # inner's: peak 500
                allocator.alloc(1, 7)
                allocator.free(0, 300)
        allocator.alloc(0, 350)                 # outer's own: peak 550
    by_name = {e["name"]: e for e in events.ring_snapshot()}
    assert sorted(by_name) == ["inner", "outer"]
    assert by_name["inner"]["hbm"] == {
        "open": [[100, 150], [0, 0]], "close": [[200, 500], [7, 7]],
        "rise": [350, 7]}
    assert by_name["outer"]["hbm"] == {
        "open": [[100, 100], [0, 0]], "close": [[550, 550], [7, 7]],
        "rise": [100, 0]}
    assert allocator.reads == 4                 # two a recorded span
    if mode == "trace":
        timeline = {e["name"]: e for e in events.events_snapshot()}
        assert "hbm" not in timeline["between"]
        assert timeline["inner"]["hbm"] == by_name["inner"]["hbm"]


def test_jax_entries_carry_no_reading(allocator):
    """Hundreds of ``jax::`` events a set-up, none allocates on the
    device: they are recorded without a reading, and none is taken."""
    import jax
    import jax.numpy as jnp
    x = jnp.arange(5.0)
    events.reset()
    with events.scope("holder", category="setup", always=True):
        jax.block_until_ready(jax.jit(lambda v: jnp.tanh(v) * 1.75 - 3)(x))
    ring = events.ring_snapshot()
    compiles = [e for e in ring if e["name"].startswith("jax::")]
    assert compiles and not [e for e in compiles if "hbm" in e]
    assert [e["name"] for e in ring if "hbm" in e] == ["holder"]
    assert allocator.reads == 2


def test_reading_is_not_what_starts_the_backend(monkeypatch):
    """Before a backend exists the helper is not called at all: asking
    the devices is what would initialise the chip."""
    def helper():
        raise AssertionError("read before a backend exists")
    monkeypatch.setattr(events, "device_memory_stats", helper)
    monkeypatch.setattr(events.xla_bridge, "backends_are_initialized",
                        lambda: False)
    with events.scope("early", category="io", always=True):
        pass
    (entry,) = events.ring_snapshot()
    assert entry["name"] == "early" and "hbm" not in entry


def test_construct_alone_starts_no_backend():
    """``Dataset.construct`` in a fresh process records its spans, reads
    no allocator and leaves JAX's backends uninitialised."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(lgb.__file__)))
    code = (
        "import numpy as np\n"
        "import lightgbm_tpu as lgb\n"
        "from lightgbm_tpu import telemetry\n"
        "from jax._src import xla_bridge\n"
        "X = np.random.default_rng(0).normal(size=(500, 4))\n"
        "lgb.Dataset(X, (X[:, 0] > 0).astype(float)).construct()\n"
        "ring = telemetry.ring_snapshot()\n"
        "assert [e for e in ring if e['name'] == 'io::Construct'], ring\n"
        "assert not [e for e in ring if 'hbm' in e], ring\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "print('construct-ok')\n")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300, cwd=root)
    assert done.returncode == 0, done.stderr[-2000:]
    assert "construct-ok" in done.stdout


def test_monitor_memory_is_every_local_device(allocator):
    """TrainingMonitor's ``memory`` field is the same helper's reading:
    one dict a local device, not device 0 alone."""
    from lightgbm_tpu.telemetry.monitor import TrainingMonitor
    events.enable("timers")
    allocator.alloc(1, 64)
    rec = TrainingMonitor().record(0)
    assert [m["peak_bytes_in_use"] for m in rec["memory"]] == [0, 64]
    assert not hasattr(telemetry.monitor, "device_memory_stats")


def test_fast_path_counters_readable_with_telemetry_off():
    assert events.mode() == events.OFF
    bst = _persist_train(32)
    counts = events.counts_snapshot()
    assert counts["tree_learner::persist_scan_trees"] == 32
    assert counts.get("tree_learner::v1_grow_trees", 0) == 0
    # the device-side launch counter arrives at the score finalize
    bst._booster._sync_persist_scores()
    assert events.counts_snapshot()["tree_learner::iter_launches"] == 2


def test_program_spans_on_the_profilers_clock(tmp_path):
    """Under a profiler session the recorded spans are TraceAnnotations:
    they come back from the .xplane.pb through telemetry/xplane.py."""
    import jax
    from lightgbm_tpu.telemetry import xplane
    tdir = str(tmp_path / "trace")
    with jax.profiler.trace(tdir):
        _persist_train(16, n=1500)
    trace = xplane.parse_xplane_dir(tdir)
    names = {name for _, _, name in trace["host"]}
    assert {"engine::train", "boosting::TrainMultiIterFast(launch)",
            "ops::persist_scan(launch)"} <= names, names
    spans = {name: (s, e) for s, e, name in trace["host"]}
    root, launch = spans["engine::train"], spans["ops::persist_scan(launch)"]
    assert root[0] <= launch[0] and launch[1] <= root[1]
    report = xplane.format_device_report(trace, iters=16)
    assert "lgbm:" in report and "engine::train" in report


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
            with events.scope("rec", always=True):
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
    ring = [e for e in events.ring_snapshot() if e["name"] == "rec"]
    assert len(ring) == threads * per == snap["rec"][1]
    assert len({e["tid"] for e in ring}) == threads


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
# xplane device profile (jax.profiler.ProfileData; CPU traces carry no
# XLA-op device planes, so this checks the parse/report plumbing and the
# gap attribution on a trace made by hand)
# ---------------------------------------------------------------------------

def test_xplane_parse_smoke(tmp_path):
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.telemetry import xplane
    tdir = str(tmp_path / "trace")
    with xplane.collect_trace(tdir):
        jax.block_until_ready(jax.jit(lambda x: x * 2)(jnp.ones(128)))
    trace = xplane.parse_xplane_dir(tdir)
    assert set(trace) == {"device", "host"}
    report = xplane.format_device_report(trace, iters=1)
    assert isinstance(report, str) and report


def test_xplane_idle_gaps_by_program_span():
    from lightgbm_tpu.telemetry import xplane
    ms = 1_000_000
    trace = {"device": {"/device:TPU:0": [
        (0, 10 * ms, "%split_pass.1"), (12 * ms, 20 * ms, "%split_pass.2"),
        (20 * ms, 21 * ms, "%fusion.7"), (30 * ms, 40 * ms, "seg_hist.3")]},
        "host": [(0, 50 * ms, "engine::train"),
                 (25 * ms, 29 * ms, "boosting::TrainMultiIterFast(launch)")]}
    assert xplane.op_totals(trace)["/device:TPU:0"] == {
        "split_pass": (18 * ms, 2), "fusion": (1 * ms, 1),
        "seg_hist": (10 * ms, 1)}
    busy, traced, gaps = xplane.idle_gaps(trace)
    assert busy == pytest.approx(0.029) and traced == pytest.approx(0.040)
    # the innermost covering span takes the gap
    assert gaps == {"engine::train": pytest.approx(0.002),
                    "boosting::TrainMultiIterFast(launch)":
                        pytest.approx(0.009)}
    report = xplane.format_device_report(trace, iters=2)
    assert "split_pass" in report and "idle 27.50%" in report
    assert xplane.base_name("%fusion.12 = f32[8]") == "fusion"


# ---------------------------------------------------------------------------
# which device, which grower: nothing is assumed and nothing is silent
# ---------------------------------------------------------------------------

class _Dev:
    def __init__(self, kind):
        self.device_kind = kind


def test_detect_profile_matches_device_kind_or_raises(monkeypatch):
    import jax
    from lightgbm_tpu.telemetry import devices
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
    # the CPU is not a device with a profile
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev("cpu")])
    with pytest.raises(ValueError, match="no device profile"):
        devices.detect_profile()


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
