"""Run-scale observability: streaming histograms (merge/percentile
contracts), the crash flight recorder, serving SLO metrics under an
open-loop Poisson load, cross-rank trace merge, and the Prometheus
snapshot — plus the telemetry-on overhead ceiling with histograms
enabled."""
import json
import math
import os
import sys
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.telemetry import events, export, flight, histo, merge
from lightgbm_tpu.telemetry.histo import Histogram

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


@pytest.fixture(autouse=True)
def _telemetry_clean():
    """Telemetry + flight state is process-global by design: every test
    starts and ends OFF, empty, disarmed."""
    events.disable()
    events.reset()
    events.set_out_path(None)
    flight.disarm()
    yield
    events.disable()
    events.reset()
    events.set_out_path(None)
    flight.disarm()


def _toy(n=400, f=8, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(float)
    return X, y


TOY_PARAMS = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
              "verbosity": -1, "metric": "none"}


# ---------------------------------------------------------------------------
# histograms: merge associativity + percentile error bound vs numpy
# ---------------------------------------------------------------------------

def test_histogram_percentile_error_bound_vs_numpy():
    """Quantile estimates stay within the documented relative bound
    (growth - 1) of the exact numpy percentiles, across a latency-shaped
    lognormal and a heavy uniform."""
    rng = np.random.default_rng(0)
    for vals in (rng.lognormal(-3.0, 1.0, 20_000),
                 rng.uniform(1e-4, 10.0, 20_000)):
        h = Histogram("t")
        for v in vals:
            h.record(v)
        assert h.count == len(vals)
        for q in (0.5, 0.95, 0.99, 0.999):
            est = h.percentile(q)
            ref = float(np.percentile(vals, q * 100))
            assert abs(est - ref) / ref <= (h.growth - 1.0) + 1e-9, \
                "p%g: est %g vs numpy %g" % (q * 100, est, ref)
        # extremes are exact (the min/max clamp)
        assert h.percentile(0.0) == float(vals.min())
        assert h.percentile(1.0) == float(vals.max())


def test_histogram_merge_associative_and_exact():
    rng = np.random.default_rng(1)
    vals = rng.lognormal(-2.0, 1.5, 9_000)
    parts = np.array_split(vals, 3)
    hs = []
    for part in parts:
        h = Histogram("x")
        for v in part:
            h.record(v)
        hs.append(h)
    a, b, c = hs
    left = a.copy().merge(b).merge(c)                 # (a+b)+c
    right = a.copy().merge(b.copy().merge(c))         # a+(b+c)
    assert left.to_dict() == right.to_dict()
    # merged == recorded-in-one-stream: the integer state (buckets,
    # counts, saturation) is EXACT; the float running sum matches to
    # addition-reordering rounding
    whole = Histogram("x")
    for v in vals:
        whole.record(v)
    dl, dw = left.to_dict(), whole.to_dict()
    tl, tw = dl.pop("total"), dw.pop("total")
    assert dl == dw
    assert abs(tl - tw) <= 1e-9 * abs(tw)


def test_histogram_roundtrip_layout_and_saturation():
    h = Histogram("s", lo=1e-6, hi=1e3, growth=1.1, unit="s")
    for v in (0.0, 1e-9, 0.5, -1.0, 5e3):
        h.record(v)
    # -1 underflows (negative), 5e3 overflows; 0 / 1e-9 clamp into
    # bucket 0 as legitimate below-resolution observations
    assert h.underflow == 1 and h.overflow == 1 and h.saturated == 2
    assert h.count == 5
    h2 = Histogram.from_dict(h.to_dict())
    assert h2.to_dict() == h.to_dict()
    with pytest.raises(ValueError):
        h.merge(Histogram("s", lo=1e-6, hi=1e3, growth=1.2))


def test_observe_registry_gated_on_telemetry():
    histo.observe("off::latency", 0.5)
    assert histo.histograms_snapshot() == {}
    events.enable("timers")
    histo.observe("on::latency", 0.5)
    histo.observe("on::latency", 1.5)
    snap = histo.histograms_snapshot()
    assert snap["on::latency"].count == 2
    assert abs(snap["on::latency"].total - 2.0) < 1e-12
    # events.reset clears the histogram registry with the rest
    events.reset()
    assert histo.histograms_snapshot() == {}


def test_report_and_metrics_surface_histograms_and_truncation(tmp_path,
                                                              monkeypatch):
    events.enable("timers")
    histo.observe("x::latency", 0.01)
    histo.observe("x::latency", 1e12)          # saturates (>= hi)
    report = telemetry.format_report()
    assert "x::latency" in report and "p99" in report
    assert "saturated" in report
    monkeypatch.setattr(events, "_dropped", 7)
    assert "7 trace event(s) dropped" in telemetry.format_report()
    path = str(tmp_path / "m.jsonl")
    telemetry.write_metrics_jsonl(path)
    lines = [json.loads(ln) for ln in open(path).read().splitlines()]
    header = lines[0]
    assert header["kind"] == "header"
    assert header["dropped_events"] == 7
    assert header["histo_saturation"] == 1
    hrows = [ln for ln in lines if ln["kind"] == "histogram"]
    assert len(hrows) == 1 and hrows[0]["name"] == "x::latency"
    # the jsonl histogram line round-trips into a mergeable Histogram
    h = Histogram.from_dict(hrows[0])
    assert h.count == 2 and h.overflow == 1


# ---------------------------------------------------------------------------
# collective guard: op-kind latency + bytes histograms at the guard
# ---------------------------------------------------------------------------

def test_guard_records_latency_and_bytes_histograms():
    from lightgbm_tpu.resilience import retry
    events.enable("timers")
    payload = np.zeros(1000, np.float64)
    out = retry.guard("allgather:smoke", lambda a: a * 2, payload)
    assert out.shape == payload.shape
    retry.guard("allreduce:smoke", lambda a: a, payload[:10])
    snap = histo.histograms_snapshot()
    lat = snap["collective::allgather::latency"]
    byt = snap["collective::allgather::bytes"]
    assert lat.count == 1 and lat.unit == "s"
    assert byt.count == 1 and byt.vmax == payload.nbytes
    assert snap["collective::allreduce::latency"].count == 1
    assert snap["collective::allreduce::bytes"].vmax == 80


def test_guard_failure_dumps_flight_record(tmp_path, monkeypatch):
    from lightgbm_tpu.resilience import retry
    from lightgbm_tpu.utils.log import LightGBMError
    events.enable("timers")
    flight.arm(dump_dir=str(tmp_path))
    monkeypatch.setattr(retry, "_POLICY",
                        retry.RetryPolicy(timeout_s=0, retries=1,
                                          backoff_s=0.0))

    def gone_peer():
        raise ConnectionError("peer vanished")

    with pytest.raises(LightGBMError):
        retry.guard("allgather:doomed", gone_peer)
    path = flight.last_dump_path()
    assert path is not None and os.path.exists(path)
    rec = json.loads(open(path).read())
    assert rec["reason"].startswith("collective_failed:allgather:doomed")
    kinds = {e["kind"] for e in rec["events"]}
    assert "collective_failed" in kinds
    assert rec["counters"].get("collective::retry") == 1
    # FAILED attempts count toward the latency distribution too (an
    # all-fast-successes histogram would lie about a crawling run)
    lat = histo.histograms_snapshot()["collective::allgather::latency"]
    assert lat.count == 2


# ---------------------------------------------------------------------------
# crash flight recorder on an injected kill
# ---------------------------------------------------------------------------

def test_injected_kill_leaves_readable_flight_dump(tmp_path):
    """tpu_fault_plan=kill@iter leaves an atomic flight.r0.json next to
    the checkpoints: recent spans/counter bumps, counter totals, and the
    kill event itself — the postmortem contract."""
    from lightgbm_tpu.resilience.faults import TrainingKilled
    X, y = _toy(n=300)
    ck = str(tmp_path / "ck")
    os.makedirs(ck)
    with pytest.raises(TrainingKilled):
        lgb.train(dict(TOY_PARAMS, tpu_telemetry="timers",
                       tpu_fault_plan="kill@iter=2",
                       checkpoint_dir=ck, snapshot_freq=1),
                  lgb.Dataset(X, y), 5, verbose_eval=False)
    path = os.path.join(ck, "flight.r0.json")
    assert os.path.exists(path)
    # atomic write: no orphaned tmp file beside the dump
    assert not [f for f in os.listdir(ck) if f.endswith(".tmp")]
    rec = json.loads(open(path).read())
    assert rec["format"] == "lightgbm_tpu.flight/1"
    assert rec["reason"] == "injected_kill@iter=2"
    assert rec["rank"] == 0
    kinds = {e["kind"] for e in rec["events"]}
    assert "kill" in kinds and "span" in kinds
    assert rec["counters"].get("faults::injected") == 1
    assert any(k.startswith("checkpoint::") for k in rec["counters"])


def test_flight_disarmed_records_and_dumps_nothing(tmp_path):
    events.enable("timers")
    with events.scope("x"):
        pass
    events.count("c")
    assert flight.snapshot() == []
    assert flight.dump("nope", path=str(tmp_path / "f.json")) is None
    assert not os.path.exists(str(tmp_path / "f.json"))


def test_flight_ring_is_bounded(tmp_path):
    events.enable("timers")
    flight.arm(dump_dir=str(tmp_path), capacity=64)
    for i in range(500):
        events.count("spin", 1)
    evs = flight.snapshot()
    assert len(evs) == 64                      # bounded, newest kept
    assert all(e["kind"] == "count" for e in evs)


# ---------------------------------------------------------------------------
# serving SLO: per-request latency/queue-wait + Poisson open loop
# ---------------------------------------------------------------------------

def _tiny_server(min_batch=64, max_batch=256):
    from lightgbm_tpu.predict import BatchServer
    X, y = _toy(n=600)
    bst = lgb.train(dict(TOY_PARAMS), lgb.Dataset(X, y), 5,
                    verbose_eval=False)
    bst._booster._materialize_pending()
    server = BatchServer(bst._booster.device_predictor(),
                         min_batch=min_batch, max_batch=max_batch)
    b = server.min_batch
    while b <= server.max_batch:
        server.predict(X[:b])
        b <<= 1
    return server, X


def test_batchserver_latency_and_queue_wait_histograms():
    server, X = _tiny_server()
    warm = server.stats()["requests"]
    server.predict(X[:100])
    server.predict(X[:50], arrival_t=time.perf_counter() - 0.02)
    st = server.stats()
    assert st["requests"] == warm + 2
    assert st["latency_p50"] <= st["latency_p99"]
    assert st["latency"]["count"] == st["requests"]
    # the backdated arrival shows up as queue wait >= 20ms
    assert st["queue_wait"]["max"] >= 0.02
    assert st["queue_wait_p99"] >= 0.0
    # telemetry mirror only when enabled (it was off here)
    assert histo.histograms_snapshot() == {}
    events.enable("timers")
    server.predict(X[:10])
    assert histo.histograms_snapshot()["predict::e2e_latency"].count == 1


# ---------------------------------------------------------------------------
# cross-rank trace merge
# ---------------------------------------------------------------------------

def _rank_trace(rank, skew_us, tmp_path):
    """Synthesize one rank's chrome trace: two collective barrier spans
    (the alignment anchors) plus a rank-local span, all shifted by this
    rank's clock skew — and one collective-category LAUNCH span whose
    end skews wildly per rank (async dispatch is not a rendezvous; it
    must never anchor the alignment)."""
    evs = []
    for i, (name, t0, dur) in enumerate([
            ("collective::Allgather(binning,DCN)", 1_000.0, 400.0),
            ("work::local", 2_000.0 + rank * 37, 500.0),
            ("collective::multihost_scan(launch)", 3_000.0,
             200.0 + rank * 50_000.0),
            ("collective::AllreduceMean(metrics,DCN)", 5_000.0, 300.0)]):
        cat = "collective" if name.startswith("collective") else "misc"
        evs.append({"name": name, "cat": cat, "ph": "X",
                    "ts": t0 + skew_us, "dur": dur, "pid": rank,
                    "tid": 100 + rank})
    trace = {"traceEvents": evs, "displayTimeUnit": "ms",
             "otherData": {"producer": "test", "dropped_events": rank,
                           "process_index": rank}}
    path = str(tmp_path / ("run.r%d.json" % rank))
    with open(path, "w") as f:
        json.dump(trace, f)
    return path


def test_two_rank_trace_merge_aligns_and_is_deterministic(tmp_path):
    _rank_trace(0, 0.0, tmp_path)
    _rank_trace(1, 5_000.0, tmp_path)          # rank 1's clock runs 5ms ahead
    summary = merge.merge_dir(str(tmp_path))
    out_path = summary["out"]
    assert summary["ranks"] == [0, 1]
    # the barrier-span alignment recovered the skew exactly
    assert abs(summary["clock_offsets_us"]["1"] + 5_000.0) < 1e-6
    assert summary["clock_offsets_us"]["0"] == 0.0
    assert summary["dropped_events"] == 1
    merged = json.loads(open(out_path).read())
    evs = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    # one valid chrome trace: complete events with the required keys,
    # rank-tagged pids, and rank-1 barriers now co-timed with rank 0's
    for e in evs:
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(e)
    assert {e["pid"] for e in evs} == {0, 1}
    barr = [e for e in evs if e["cat"] == "collective"
            and not e["name"].endswith("(launch)")]
    by_name = {}
    for e in barr:
        by_name.setdefault(e["name"], []).append(e["ts"] + e["dur"])
    for ends in by_name.values():
        assert len(ends) == 2 and abs(ends[0] - ends[1]) < 1e-6
    meta = [e for e in merged["traceEvents"] if e.get("ph") == "M"]
    assert {m["args"]["name"] for m in meta} == {"rank 0", "rank 1"}
    # determinism: re-merging the same inputs is byte-identical
    blob1 = open(out_path, "rb").read()
    merge.merge_dir(str(tmp_path), )
    assert open(out_path, "rb").read() == blob1


def test_merge_cli_entry(tmp_path, capsys):
    from lightgbm_tpu.profile import main
    _rank_trace(0, 0.0, tmp_path)
    _rank_trace(1, -2_500.0, tmp_path)
    assert main(["--merge", str(tmp_path), "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["ranks"] == [0, 1]
    assert os.path.exists(summary["out"])
    # empty dir fails loudly, not silently
    empty = tmp_path / "empty"
    empty.mkdir()
    assert main(["--merge", str(empty)]) == 2


def test_merge_refuses_mixed_run_directories(tmp_path):
    """Rank files from two different runs (different basenames) must not
    silently combine into a plausible-looking garbage trace."""
    _rank_trace(0, 0.0, tmp_path)
    other = json.loads((tmp_path / "run.r0.json").read_text())
    with open(str(tmp_path / "archive.r1.json"), "w") as f:
        json.dump(other, f)
    with pytest.raises(merge.MergeError, match="more than one run"):
        merge.merge_dir(str(tmp_path))


def test_rank_suffix_single_host_unchanged():
    # single-process runs keep their exact telemetry_out path (the
    # multihost suffix seam is covered by the two-process slow test)
    assert export.rank_suffixed("/tmp/x/out.json") == "/tmp/x/out.json"
    assert export.process_index() == 0


# ---------------------------------------------------------------------------
# Prometheus snapshot
# ---------------------------------------------------------------------------

def test_prom_snapshot_written_and_parseable(tmp_path):
    from lightgbm_tpu.telemetry import promexport
    events.enable("timers")
    with events.scope("boosting::X", category="boosting"):
        pass
    events.count("predict::served", 3)
    histo.observe("predict::e2e_latency", 0.012)
    path = str(tmp_path / "snap.prom")
    promexport.write_prom(path)
    text = open(path).read()
    assert 'lgbtpu_timer_seconds_total{name="boosting::X"' in text
    assert 'lgbtpu_counter_total{name="predict::served"} 3' in text
    assert 'lgbtpu_histo{name="predict::e2e_latency",quantile="0.99"}' \
        in text
    assert "lgbtpu_histo_count" in text and "lgbtpu_dropped_events" \
        in text
    # native-histogram form: cumulative le-buckets (rate()/average
    # queries + cross-rank histogram_quantile need these, the summary
    # quantile gauges cannot provide them)
    assert "# TYPE lgbtpu_histo_dist histogram" in text
    histo.observe("other::latency", 3.5)   # very different value range

    def _les(name):
        pre = 'lgbtpu_histo_dist_bucket{name="%s"' % name
        return [ln.split('le="')[1].split('"')[0]
                for ln in promexport.render().splitlines()
                if ln.startswith(pre)]
    bucket_lines = [ln for ln in text.splitlines()
                    if ln.startswith('lgbtpu_histo_dist_bucket'
                                     '{name="predict::e2e_latency"')]
    assert bucket_lines, "per-histogram _bucket lines missing"
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in bucket_lines]
    assert counts == sorted(counts), "cumulative buckets must be " \
        "monotone"
    assert bucket_lines[-1].rsplit(" ", 1) == [
        'lgbtpu_histo_dist_bucket{name="predict::e2e_latency",'
        'le="+Inf"}', "1"]
    assert 'lgbtpu_histo_dist_count{name="predict::e2e_latency"} 1' \
        in text
    assert 'lgbtpu_histo_dist_sum{name="predict::e2e_latency"}' in text
    # the le ladder is a function of the LAYOUT, not the data — every
    # histogram (and so every rank) exposes the identical edge set,
    # the precondition for sum(rate(_bucket)) by (le) aggregation
    assert _les("predict::e2e_latency") == _les("other::latency")
    assert len(_les("other::latency")) > 10
    # every sample line is NAME{labels} VALUE with a float-parseable value
    for line in text.splitlines():
        if line.startswith("#") or not line:
            continue
        name, val = line.rsplit(" ", 1)
        float(val)
    assert not [f for f in os.listdir(str(tmp_path))
                if f.endswith(".tmp")]


def test_prom_flush_via_telemetry_out(tmp_path):
    """telemetry_out=...prom: training flushes a scrapeable snapshot
    (the final maybe_export write; the periodic path is the same
    function behind a throttle)."""
    X, y = _toy(n=300)
    out = str(tmp_path / "run.prom")
    lgb.train(dict(TOY_PARAMS, tpu_telemetry="timers", telemetry_out=out),
              lgb.Dataset(X, y), 3, verbose_eval=False)
    text = open(out).read()
    assert "lgbtpu_timer_seconds_total" in text
    assert 'name="boosting::TrainOneIter"' in text


# ---------------------------------------------------------------------------
# overhead ceiling with histograms enabled (the PR 1 pattern)
# ---------------------------------------------------------------------------

def test_histogram_observe_overhead_ceiling():
    """Recording is O(1) and allocation-free: 20k observes (timers mode,
    flight armed — the worst instrumented configuration) stay under a
    coarse wall ceiling, so per-collective/per-request recording can
    never dominate the operations it measures."""
    events.enable("timers")
    flight.arm(dump_dir=".")
    t0 = time.perf_counter()
    for i in range(20_000):
        histo.observe("hot::latency", 1e-4 * (1 + (i & 7)))
    elapsed = time.perf_counter() - t0
    assert elapsed < 1.0, "20k observes took %.3fs" % elapsed
    h = histo.histograms_snapshot()["hot::latency"]
    assert h.count == 20_000 and h.saturated == 0


# ---------------------------------------------------------------------------
# two-process end-to-end: injected-kill multihost run leaves per-rank
# flight dumps + rank-suffixed traces, and profile --merge unifies them
# ---------------------------------------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MH_KILL_WORKER = r"""
import json, os, sys
import numpy as np
sys.path.insert(0, %(repo)r)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
import jax
jax.config.update("jax_num_cpu_devices", 2)
jax.config.update("jax_cpu_collectives_implementation", "gloo")

rank = int(sys.argv[1])
port = sys.argv[2]
outdir = sys.argv[3]
os.environ["JAX_PROCESS_ID"] = str(rank)

import lightgbm_tpu as lgb
from lightgbm_tpu.resilience.faults import TrainingKilled

rng = np.random.default_rng(11)
n, nf = 2400, 6
X = rng.normal(size=(n, nf))
y = (X[:, 1] + 0.5 * X[:, 4] + rng.normal(size=n) * 0.3 > 0).astype(float)

params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
          "num_machines": 2,
          "machines": "127.0.0.1:%%s,127.0.0.1:0" %% port,
          "min_data_in_leaf": 5, "tree_learner": "data",
          "tpu_telemetry": "trace",
          "telemetry_out": os.path.join(outdir, "mh.json"),
          "checkpoint_dir": outdir, "snapshot_freq": 4,
          "tpu_fault_plan": "kill@iter=8"}
try:
    lgb.train(params, lgb.Dataset(X, y), num_boost_round=12,
              verbose_eval=False)
except TrainingKilled:
    sys.exit(0)
sys.exit(3)   # the kill must fire
"""


@pytest.mark.slow
def test_multihost_kill_leaves_flight_dumps_and_mergeable_traces(tmp_path):
    """The acceptance path end to end: a two-rank run with an injected
    kill leaves (a) an atomic flight dump per rank next to its
    checkpoints and (b) rank-suffixed Chrome traces that
    `profile --merge` unifies into one valid trace."""
    import socket
    import subprocess

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    script = tmp_path / "mh_kill_worker.py"
    script.write_text(MH_KILL_WORKER % {"repo": REPO})
    procs = []
    for r in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.pop("JAX_PLATFORMS", None)
        procs.append(subprocess.Popen(
            [sys.executable, str(script), str(r), str(port),
             str(tmp_path)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE))
    for p in procs:
        try:
            _, err = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multihost kill worker timed out")
        assert p.returncode == 0, err.decode()[-2000:]

    # (a) per-rank atomic flight dumps, readable, with the kill recorded
    for r in range(2):
        fpath = tmp_path / ("flight.r%d.json" % r)
        assert fpath.exists(), sorted(os.listdir(str(tmp_path)))
        rec = json.loads(fpath.read_text())
        assert rec["rank"] == r
        assert rec["reason"] == "injected_kill@iter=8"
        assert any(e["kind"] == "kill" for e in rec["events"])
        # the guard-recorded collectives made it into the ring and the
        # histograms: every DCN kind that ran has latency+bytes
        coll = [e for e in rec["events"] if e["kind"] == "collective"]
        assert coll, "no collective events in the flight ring"
        for e in coll[:3]:
            assert "dur" in e and "bytes" in e
        kinds = {e["op"] for e in coll}
        for k in kinds:
            assert "collective::%s::latency" % k in rec["histograms"]
            assert "collective::%s::bytes" % k in rec["histograms"]
    assert not [f for f in os.listdir(str(tmp_path))
                if f.endswith(".tmp")]

    # (b) rank-suffixed traces (the telemetry_out collision fix) merge
    # into one valid chrome trace via the CLI seam
    assert (tmp_path / "mh.r0.json").exists(), \
        sorted(os.listdir(str(tmp_path)))
    assert (tmp_path / "mh.r1.json").exists()
    summary = merge.merge_dir(str(tmp_path))
    assert summary["ranks"] == [0, 1]
    merged = json.loads(open(summary["out"]).read())
    evs = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    assert {e["pid"] for e in evs} == {0, 1}
    for e in evs[:50]:
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(e)
    # both ranks contributed collective barrier spans for alignment
    assert summary["barrier_spans"][0] > 0
    assert summary["barrier_spans"][1] > 0


def test_training_with_histograms_off_leaves_no_trace():
    """tpu_telemetry off (the default): the histogram registry stays
    empty through a full train + serve — the no-op-when-off guarantee
    extends to the new subsystem."""
    X, y = _toy(n=400)
    bst = lgb.train(dict(TOY_PARAMS), lgb.Dataset(X, y), 4,
                    verbose_eval=False)
    from lightgbm_tpu.predict import BatchServer
    bst._booster._materialize_pending()
    server = BatchServer(bst._booster.device_predictor(), min_batch=64,
                         max_batch=128)
    server.predict(X[:80])
    assert histo.histograms_snapshot() == {}
    assert flight.snapshot() == []
