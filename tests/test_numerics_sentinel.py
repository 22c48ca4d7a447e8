"""Runtime numerics sentinel (tentpole PR): device-side health counters +
split-margin telemetry, cross-rank divergence fingerprints, and the
training health monitor.

Tier-1 covers: device/host margin-bucket parity and a host-side margin
recompute on a small tree, margin-count == split-count, the gradient
non-finite probe, the synthetic single-rank fingerprint mismatch
(detected at the injected iteration, component named, flight dumped),
the world=1 short-circuit path, the corrupt_hist@ fault grammar, the
monitor anomaly/abort hooks, the lgbtpu_health_* Prometheus families,
the per-run numerics-registry reset (leak regression), profile --merge
--run, the no-new-collective-sites pin, and the < 2% flush-overhead
ceiling. The REAL two-process corrupt_hist detection is the slow
sibling at the bottom.
"""
import json
import math
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.config import Config
from lightgbm_tpu.resilience import faults
from lightgbm_tpu.telemetry import events, flight, health, histo
from lightgbm_tpu.utils.log import LightGBMError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PERSIST = {"objective": "binary", "verbosity": -1, "metric": "none",
           "tpu_persist_scan": "force"}


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.enable("timers")
    telemetry.reset()
    health.reset_run()
    yield
    faults.reset()
    flight.disarm()
    telemetry.reset()
    telemetry.disable()


def _higgs(n=4000, seed=0):
    from lightgbm_tpu.data.synth import make_higgs_like
    return make_higgs_like(n, seed=seed) if "seed" in \
        make_higgs_like.__code__.co_varnames else make_higgs_like(n)


def _train_persist(params, n_iters=16, rows=4000):
    X, y = _higgs(rows)
    b = lgb.train(dict(PERSIST, **params), lgb.Dataset(X, y), n_iters,
                  verbose_eval=False)
    b._booster._materialize_pending()
    import jax
    jax.block_until_ready(b._booster.train_score.score_device(0))
    return b


# ---------------------------------------------------------------------------
# device-side health counters + split-margin histogram
# ---------------------------------------------------------------------------

def test_margin_bucket_device_host_parity():
    """The device bucketing (ops/pallas_scan.margin_bucket_index) and
    the host twin (health.margin_bucket_host) agree over ten orders of
    magnitude, including the clamp floor and the saturating top."""
    from lightgbm_tpu.ops.pallas_scan import margin_bucket_index
    import jax.numpy as jnp
    vals = [0.0, 1e-12, health.MARGIN_LO, 3e-9, 1e-6, 0.37, 1.0, 17.3,
            4096.0, 1e7, 1e12, 1e30]
    dev = np.asarray(margin_bucket_index(jnp.asarray(vals,
                                                     jnp.float32)))
    host = [health.margin_bucket_host(v) for v in vals]
    assert list(dev) == host
    assert host[0] == 0 and host[-1] == health.MARGIN_NB - 1


def test_margin_layout_matches_registry_histogram():
    """merge_counts at the health layout produces a registry histogram
    whose bucket count is EXACTLY MARGIN_NB (the fp-jitter forcing) and
    whose percentile answers sit inside the flushed buckets' edges."""
    buckets = [0] * health.MARGIN_NB
    buckets[40] = 10
    histo.merge_counts("numerics::split_margin", buckets,
                       lo=health.MARGIN_LO, growth=health.MARGIN_GROWTH,
                       unit="gain", category="numerics")
    h = histo.get("numerics::split_margin")
    assert h is not None and h.num_buckets == health.MARGIN_NB
    lo_edge = health.MARGIN_LO * health.MARGIN_GROWTH ** 40
    assert lo_edge <= h.percentile(0.5) <= lo_edge * health.MARGIN_GROWTH
    # repeated flushes merge (same layout)
    histo.merge_counts("numerics::split_margin", buckets,
                       lo=health.MARGIN_LO, growth=health.MARGIN_GROWTH)
    assert histo.get("numerics::split_margin").count == 20


def test_margin_histogram_single_split_tree_host_recompute():
    """A num_leaves=2 run records exactly one margin per tree — the
    root gain (no competing frontier candidate) — and the flushed
    device histogram equals a host-side rebucketing of the model's own
    recorded split gains."""
    b = _train_persist({"num_leaves": 2, "min_data_in_leaf": 20}, 16)
    h = histo.get(health.MARGIN_HISTO)
    assert h is not None, "persist run flushed no margin histogram"
    trees = [t for t in b._booster.models if t is not None]
    gains = [float(t.split_gain[0]) for t in trees if t.num_leaves == 2]
    assert h.count == len(gains) > 0
    expected = [0] * health.MARGIN_NB
    for g in gains:
        expected[health.margin_bucket_host(g)] += 1
    got = [0] * health.MARGIN_NB
    for i, c in (histo.get(health.MARGIN_HISTO).to_dict()["buckets"]
                 or {}).items():
        got[int(i)] = c
    assert got == expected


def test_margin_count_equals_splits_per_split_and_level():
    """One margin per split on both growth phases (per-split loop and
    the fused level program)."""
    for extra, want_level in (
            ({"num_leaves": 15}, False),
            ({"num_leaves": 16, "max_depth": 4}, True)):
        telemetry.reset()
        # 16 iters engages the batched scan (K=16); 2000 rows is enough
        # — the count==splits equality is exact at any size
        b = _train_persist(dict(extra, min_data_in_leaf=5), 16,
                           rows=2000)
        splits = sum(t.num_leaves - 1
                     for t in b._booster.models if t is not None)
        h = histo.get(health.MARGIN_HISTO)
        assert h is not None and h.count == splits, \
            "margins %s != splits %d (%s)" % (h and h.count, splits,
                                              extra)
        levels = events.counts_snapshot().get(
            "tree_learner::level_programs", 0)
        assert (levels > 0) == want_level


def test_numerics_stats_off_disables_accumulation():
    _train_persist({"num_leaves": 7, "tpu_numerics_stats": "off"}, 16,
                   rows=2000)
    assert histo.get(health.MARGIN_HISTO) is None
    counts = events.counts_snapshot()
    assert not any(k.startswith("numerics::nan") for k in counts)
    # the level/fallback counters still flush
    assert counts.get("tree_learner::persist_scan_trees", 0) > 0


def test_grad_health_counts_nonfinite_rows():
    """The gradient probe counts NaN/Inf over LIVE payload rows only."""
    b = _train_persist({"num_leaves": 7}, 16, rows=1000)
    tl = b._booster.tree_learner
    cache = tl.dataset._persist_cache
    gr = next(v for k, v in cache.items() if k[0] == "grower")
    assets = next(v for k, v in cache.items() if k[0] == "assets")
    pay = np.array(assets.pay0)
    nbw = gr.nbw
    grad_row = nbw + 2
    nan_bits = np.float32(np.nan).view(np.uint32)
    inf_bits = np.float32(np.inf).view(np.uint32)
    pay[grad_row, :3] = nan_bits          # 3 live NaN grads
    pay[grad_row + 1, 5:7] = inf_bits     # 2 live Inf hessians
    pay[grad_row, gr.n:gr.n + 50] = nan_bits   # dead lanes: not counted
    import jax.numpy as jnp
    out = np.asarray(gr.grad_health(jnp.asarray(pay)))
    assert list(out) == [3, 2]


def test_flush_overhead_under_2_percent():
    """The numerics sentinel's ONLY host-side cost is the finalize
    flush — pinned like the checkpoint write ceiling."""
    t0 = time.time()
    # same geometry as the margin-count run above: the scan program is
    # already jit-cached, so the wall measured here is dominated by the
    # iterations the flush accounts against, not a fresh compile
    _train_persist({"num_leaves": 15, "min_data_in_leaf": 5}, 16,
                   rows=2000)
    wall = time.time() - t0
    scopes = events.snapshot_full()
    flush_s, n, _ = scopes.get("numerics::flush", (0.0, 0, ""))
    assert n >= 1, "flush never ran"
    assert flush_s < 0.02 * wall, \
        "numerics::flush %.4fs of %.2fs wall" % (flush_s, wall)


# ---------------------------------------------------------------------------
# cross-rank divergence fingerprints
# ---------------------------------------------------------------------------

def _tiny_trees(n_iters=6, seed=0):
    X, y = _higgs(1500)
    b = lgb.train({"objective": "binary", "num_leaves": 7,
                   "verbosity": -1, "metric": "none",
                   "min_data_in_leaf": 5}, lgb.Dataset(X, y), n_iters,
                  verbose_eval=False)
    b._booster._materialize_pending()
    return [[t] for t in b._booster.models if t is not None]


def test_kahan_sum_matches_fsum():
    rng = np.random.default_rng(3)
    a = np.concatenate([rng.normal(size=200_000) * 1e9,
                        rng.normal(size=200_000) * 1e-9])
    from lightgbm_tpu.parallel.fingerprint import kahan_sum
    assert abs(kahan_sum(a) - math.fsum(a)) <= 1e-6 * abs(math.fsum(a)) \
        + 1e-12
    assert kahan_sum([]) == 0.0


def test_fingerprint_consistent_ranks_pass():
    from lightgbm_tpu.parallel import fingerprint as fp
    trees = _tiny_trees()
    rows = fp.batch_records(0, trees, rank=0, score_sum=1.25)
    gathered = np.stack([rows.reshape(-1), rows.reshape(-1)])
    fp.check_gathered(gathered, rank=0)       # must not raise
    assert events.counts_snapshot().get(
        "numerics::fingerprint_rounds", 0) == 1


def test_fingerprint_mismatch_detected_at_injected_iteration(tmp_path):
    """Synthetic single-rank mismatch: corrupt_hist@round=3;rank=1
    flips rank 1's hist component at iteration 3 exactly — the check
    raises there, names 'hist', lists the suspect, and dumps the
    flight ring."""
    from lightgbm_tpu.parallel import fingerprint as fp
    trees = _tiny_trees()
    plan = faults.FaultPlan("corrupt_hist@round=3;rank=1;scale=7")
    r0 = fp.batch_records(0, trees, rank=0, score_sum=1.0,
                          fault_plan=plan)
    r1 = fp.batch_records(0, trees, rank=1, score_sum=2.0,
                          fault_plan=plan)
    assert np.all(r0[:3, fp.REC_HIST] == r1[:3, fp.REC_HIST])
    assert r0[3, fp.REC_HIST] != r1[3, fp.REC_HIST]
    flight.arm(dump_dir=str(tmp_path))
    gathered = np.stack([r0.reshape(-1), r1.reshape(-1)])
    with pytest.raises(fp.DivergenceError) as ei:
        fp.check_gathered(gathered, rank=0)
    err = ei.value
    assert err.iteration == 3 and err.component == "hist"
    assert err.ranks == [0, 1]        # world=2: both named
    assert "iteration 3" in str(err) and "hist" in str(err)
    assert getattr(err, "_flight_dumped", False)
    dump = json.load(open(flight.last_dump_path()))
    assert dump["reason"].startswith("divergence:hist@iter=3")
    div = [e for e in dump["events"] if e.get("kind") == "divergence"]
    assert div and div[0]["iteration"] == 3
    assert div[0]["score_sums"] == {"0": 1.0, "1": 2.0}
    assert events.counts_snapshot().get("numerics::divergence", 0) == 1


def test_fingerprint_model_component_blamed_first():
    """A structurally different model flips the model CRC — blamed
    before hist."""
    from lightgbm_tpu.parallel import fingerprint as fp
    trees = _tiny_trees()
    r0 = fp.batch_records(0, trees, rank=0)
    other = list(trees)
    other[2] = trees[1]               # different tree at iteration 2
    r1 = fp.batch_records(0, other, rank=1)
    with pytest.raises(fp.DivergenceError) as ei:
        fp.check_gathered(np.stack([r0.reshape(-1), r1.reshape(-1)]),
                          rank=1, dump=False)
    assert ei.value.iteration == 2 and ei.value.component == "model"


def test_world1_probe_short_circuit_with_corrupt_hist():
    """The world=1 end (elastic resume small end) runs the probe end to
    end: the fault injects, the 1-row compare trivially passes, and
    training completes."""
    from lightgbm_tpu.parallel.multihost import train_multihost
    rng = np.random.default_rng(7)
    n, nf = 1000, 6
    X = rng.normal(size=(n, nf))
    y = (X[:, 0] - 0.7 * X[:, 3] > 0).astype(float)
    cfg = Config({"objective": "binary", "num_leaves": 7,
                  "verbosity": -1, "num_machines": 1,
                  "min_data_in_leaf": 5,
                  "tpu_divergence_probe": "on",
                  "tpu_fault_plan": "corrupt_hist@round=2;rank=0"})
    faults.configure_from_config(cfg)
    trees, _, _, _ = train_multihost(cfg, X, y, num_rounds=4)
    assert len(trees) == 4
    c = events.counts_snapshot()
    assert c.get("numerics::fingerprint_rounds", 0) >= 1
    assert c.get("faults::injected", 0) >= 1
    assert c.get("numerics::divergence", 0) == 0


@pytest.mark.parametrize("mode", ["off", "auto"])
def test_world1_probe_off_and_auto_record_nothing(mode):
    """'off' disables outright; 'auto' skips the per-batch CRC/D2H work
    when there is no peer to diverge from (review-finding pin)."""
    from lightgbm_tpu.parallel.multihost import train_multihost
    rng = np.random.default_rng(7)
    X = rng.normal(size=(800, 5))
    y = (X[:, 0] > 0).astype(float)
    cfg = Config({"objective": "binary", "num_leaves": 7,
                  "verbosity": -1, "num_machines": 1,
                  "min_data_in_leaf": 5, "tpu_divergence_probe": mode})
    train_multihost(cfg, X, y, num_rounds=3)
    assert events.counts_snapshot().get(
        "numerics::fingerprint_rounds", 0) == 0


def test_corrupt_hist_fault_grammar():
    p = faults.FaultPlan("corrupt_hist@round=5;rank=1")
    assert p.hist_corruption(5, 1) == 1          # default scale
    assert p.hist_corruption(5, 0) is None
    assert p.hist_corruption(4, 1) is None
    p2 = faults.FaultPlan("corrupt_hist@round=2;rank=0;scale=9")
    assert p2.hist_corruption(2, 0) == 9
    with pytest.raises(LightGBMError):
        faults.FaultPlan("corrupt_hist@round=5")          # rank required
    with pytest.raises(LightGBMError):
        faults.FaultPlan("corrupt_hist@rank=0")           # round required
    with pytest.raises(LightGBMError):                    # duplicate
        faults.FaultPlan(
            "corrupt_hist@round=1;rank=0,corrupt_hist@round=2;rank=0")
    # composes with existing verbs
    p3 = faults.FaultPlan("kill@iter=9,corrupt_hist@round=3;rank=1")
    assert p3.kill_iter == 9 and p3.corrupt_hist_round == 3


def test_no_new_collective_sites_pin():
    """The fingerprint exchange PIGGYBACKS on the existing guarded
    sites — the collective trace must show exactly the pre-PR site
    set (the collective_trace JSON diff contract)."""
    from lightgbm_tpu.analysis import collective_audit
    sites, findings = collective_audit.audit_repo()
    assert findings == []
    names = sorted(s.name for s in sites if s.name)
    assert names == [
        "allgather:binning_mappers", "allgather:binning_sizes",
        "allgather:ranking_geometry", "allgather:resume_agree",
        "allgather:row_counts", "allreduce:boost_from_average",
        "allreduce:metrics_values", "allreduce:metrics_weights"]
    assert len(sites) == 13


# ---------------------------------------------------------------------------
# training health monitor
# ---------------------------------------------------------------------------

def _healthy_margins(times=1, bucket=40, count=10):
    buckets = [0] * health.MARGIN_NB
    buckets[bucket] = count
    for _ in range(times):
        histo.merge_counts(health.MARGIN_HISTO, buckets,
                           lo=health.MARGIN_LO,
                           growth=health.MARGIN_GROWTH,
                           category="numerics")


def test_monitor_nonfinite_metric_anomaly():
    health.configure_from_config(Config({"verbosity": -1}))
    out = health.check_record(4, evals=[("valid_0", "auc",
                                         float("nan"), True)])
    assert [a["kind"] for a in out] == ["nonfinite_metric"]
    assert events.counts_snapshot().get(
        "health::nonfinite_metric", 0) == 1
    # finite metrics: clean
    assert health.check_record(5, evals=[("valid_0", "auc", 0.9,
                                          True)]) == []


def test_monitor_margin_collapse_vs_rolling_baseline():
    health.configure_from_config(Config({"verbosity": -1}))
    for i in range(4):                 # build the rolling baseline
        _healthy_margins()
        assert health.check_record(i) == []
    tiny = [0] * health.MARGIN_NB
    tiny[0] = 100_000                  # ~1.4e-9 margins swamp p01
    histo.merge_counts(health.MARGIN_HISTO, tiny, lo=health.MARGIN_LO,
                       growth=health.MARGIN_GROWTH, category="numerics")
    out = health.check_record(9)
    assert [a["kind"] for a in out] == ["margin_collapse"]
    assert out[0]["p01"] < out[0]["baseline_p01"] * \
        health.MARGIN_COLLAPSE_RATIO


def test_monitor_stall_burst_anomaly():
    health.configure_from_config(Config({"verbosity": -1}))
    assert health.check_record(0) == []
    for _ in range(health.STALL_BURST):
        events.count("collective::stall", 1, category="collective")
    out = health.check_record(1)
    assert [a["kind"] for a in out] == ["stall_burst"]
    assert health.check_record(2) == []     # delta-based, not cumulative


def test_health_abort_raises_with_flight_dump(tmp_path):
    health.configure_from_config(Config({
        "verbosity": -1, "tpu_health_abort": "nonfinite_metric"}))
    flight.arm(dump_dir=str(tmp_path))
    with pytest.raises(LightGBMError) as ei:
        health.check_record(7, evals=[("v", "auc", float("inf"), True)])
    assert "nonfinite_metric" in str(ei.value) and "iteration 7" \
        in str(ei.value)
    assert getattr(ei.value, "_flight_dumped", False)
    dump = json.load(open(flight.last_dump_path()))
    assert dump["reason"] == "health_abort:nonfinite_metric@iter=7"
    # a kind NOT in the abort set only reports
    health.configure_from_config(Config({
        "verbosity": -1, "tpu_health_abort": "stall_burst"}))
    out = health.check_record(8, evals=[("v", "auc", float("nan"),
                                         True)])
    assert [a["kind"] for a in out] == ["nonfinite_metric"]


def test_monitor_record_integration():
    from lightgbm_tpu.telemetry.monitor import TrainingMonitor
    health.configure_from_config(Config({"verbosity": -1}))
    mon = TrainingMonitor()
    rec = mon.record(0, evals=[("v", "l2", float("nan"), False)])
    assert rec["health"] == ["nonfinite_metric"]
    rec2 = mon.record(1, evals=[("v", "l2", 0.5, False)])
    assert "health" not in rec2


def test_prom_health_families_pinned():
    from lightgbm_tpu.telemetry import promexport
    events.count("health::stall_burst", 2, category="health")
    events.count("numerics::nan_grad", 3, category="numerics")
    text = promexport.render()
    assert "# TYPE lgbtpu_health_anomalies_total counter" in text
    assert 'lgbtpu_health_anomalies_total{kind="stall_burst"} 2' in text
    # explicit zeros for kinds never seen
    assert ('lgbtpu_health_anomalies_total{kind="margin_collapse"} 0'
            in text)
    assert 'lgbtpu_health_nonfinite_total{kind="grad"} 3' in text
    assert 'lgbtpu_health_nonfinite_total{kind="hist"} 0' in text
    assert "lgbtpu_health_divergence_total 0" in text


def test_numerics_registry_resets_at_arming():
    """Leak regression: an aborted run's numerics::* registry entries
    must not ride into the next engine.train of the same process."""
    _healthy_margins()
    events.count("numerics::nan_grad", 5, category="numerics")
    events.count("health::stall_burst", 1, category="health")
    events.count("collective::retry", 1, category="collective")
    assert histo.get(health.MARGIN_HISTO) is not None
    health.configure_from_config(Config({"verbosity": -1}))   # arming
    assert histo.get(health.MARGIN_HISTO) is None
    counts = events.counts_snapshot()
    assert "numerics::nan_grad" not in counts
    assert "health::stall_burst" not in counts
    assert counts.get("collective::retry") == 1    # others untouched


def test_engine_train_arms_health_reset():
    """The real seam: a second lgb.train in the same process starts
    with a clean numerics registry."""
    _healthy_margins(times=1, bucket=10, count=7)
    before = histo.get(health.MARGIN_HISTO).count
    assert before == 7
    b = _train_persist({"num_leaves": 7}, 16, rows=1000)
    h = histo.get(health.MARGIN_HISTO)
    splits = sum(t.num_leaves - 1
                 for t in b._booster.models if t is not None)
    assert h is not None and h.count == splits   # stale 7 gone


def test_tpu_health_abort_unknown_kind_warns_not_raises():
    health.configure_from_config(Config({
        "verbosity": -1, "tpu_health_abort": "bogus_kind,stall_burst"}))
    assert health.abort_kinds() == frozenset({"stall_burst"})


def test_sentinel_knobs_are_resume_volatile():
    """Review-finding pin: flipping a numerics-sentinel knob must not
    orphan a run's checkpoints (the knobs observe the computation, they
    never shape it)."""
    from lightgbm_tpu.resilience.checkpoint import config_hash
    base = Config({"objective": "binary", "num_leaves": 15,
                   "verbosity": -1})
    flipped = Config({"objective": "binary", "num_leaves": 15,
                      "verbosity": -1, "tpu_numerics_stats": "off",
                      "tpu_health_abort": "all",
                      "tpu_divergence_probe": "off"})
    assert config_hash(base) == config_hash(flipped)


def test_health_auto_follows_telemetry():
    """tpu_numerics_stats=auto accumulates only when telemetry is on
    (off-mode zero-overhead contract); 'on' forces, 'off' disables."""
    from lightgbm_tpu.treelearner.serial import SerialTreeLearner
    class _L:
        _persist_health_mode = SerialTreeLearner._persist_health_mode
    lrn = _L()
    lrn.config = Config({"verbosity": -1})
    assert lrn._persist_health_mode() is True         # fixture: timers on
    telemetry.disable()
    try:
        assert lrn._persist_health_mode() is False
        lrn.config = Config({"verbosity": -1,
                             "tpu_numerics_stats": "on"})
        assert lrn._persist_health_mode() is True
    finally:
        telemetry.enable("timers")
    lrn.config = Config({"verbosity": -1, "tpu_numerics_stats": "off"})
    assert lrn._persist_health_mode() is False


def test_stall_baseline_reanchors_across_runs():
    """Leak regression (review finding): collective::stall is process-
    cumulative — a second run's first record must not read the first
    run's stalls as a fresh burst (and abort a healthy run under
    tpu_health_abort=stall_burst)."""
    health.configure_from_config(Config({"verbosity": -1}))
    for _ in range(health.STALL_BURST + 2):
        events.count("collective::stall", 1, category="collective")
    assert health.check_record(0) != []         # run 1 sees the burst
    # run 2 arms (abort enabled): the carryover must not fire
    health.configure_from_config(Config({
        "verbosity": -1, "tpu_health_abort": "stall_burst"}))
    assert health.check_record(0) == []


# ---------------------------------------------------------------------------
# profile --merge --run
# ---------------------------------------------------------------------------

def _mini_trace(tmp_path, base, rank):
    evs = [{"name": "collective::Allgather(binning,DCN)",
            "cat": "collective", "ph": "X", "ts": 1000.0 + rank,
            "dur": 400.0, "pid": rank, "tid": 1}]
    path = str(tmp_path / ("%s.r%d.json" % (base, rank)))
    with open(path, "w") as f:
        json.dump({"traceEvents": evs, "displayTimeUnit": "ms",
                   "otherData": {"process_index": rank}}, f)
    return path


def test_merge_run_selects_one_run(tmp_path):
    from lightgbm_tpu.telemetry import merge as trace_merge
    for base in ("runA", "runB"):
        for r in range(2):
            _mini_trace(tmp_path, base, r)
    # no flag: still refuses a mixed directory, names both runs
    with pytest.raises(trace_merge.MergeError) as ei:
        trace_merge.merge_dir(str(tmp_path))
    assert "runA" in str(ei.value) and "runB" in str(ei.value)
    assert "--run" in str(ei.value)
    out = trace_merge.merge_dir(str(tmp_path), run="runA")
    assert out["ranks"] == [0, 1]
    # unknown fingerprint: loud, lists what exists
    with pytest.raises(trace_merge.MergeError) as ei:
        trace_merge.merge_dir(str(tmp_path), run="runC")
    assert "runC" in str(ei.value) and "runA" in str(ei.value)


def test_merge_run_cli(tmp_path, capsys):
    from lightgbm_tpu.profile import main
    for base in ("runA", "runB"):
        for r in range(2):
            _mini_trace(tmp_path, base, r)
    assert main(["--merge", str(tmp_path), "--run", "runB",
                 "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["ranks"] == [0, 1]
    assert main(["--merge", str(tmp_path), "--json"]) == 2  # still refuses


# ---------------------------------------------------------------------------
# health_covered audit: inheritance-aware coverage
# ---------------------------------------------------------------------------

def test_health_audit_inheritance_coverage():
    from lightgbm_tpu.analysis import health_audit
    inherited = '''
from lightgbm_tpu.ops.grow_persist import make_scan_driver

class Base:
    def flush(self, stats):
        from lightgbm_tpu.telemetry.health import flush_device_stats
        flush_device_stats(stats[2:])

class Sharded(Base):
    def build(self, gr, gc, k, fn):
        return make_scan_driver(gr, gc, k, fn)
'''
    assert health_audit.check_fixture(inherited) == []
    orphan = '''
from lightgbm_tpu.ops.grow_persist import make_scan_driver

class Base:
    pass

class Sharded(Base):
    def build(self, gr, gc, k, fn):
        return make_scan_driver(gr, gc, k, fn)
'''
    hits = health_audit.check_fixture(orphan)
    assert len(hits) == 1 and "numerics::*" in hits[0]


def test_health_audit_green_on_repo_with_sites():
    from lightgbm_tpu.analysis import health_audit
    art = health_audit.compute_artifact()
    assert art["driver_sites"] >= 3 and art["findings"] == []


# ---------------------------------------------------------------------------
# slow sibling: REAL two-process corrupt_hist detection
# ---------------------------------------------------------------------------

DIVERGE_WORKER = r"""
import json, os, sys
import numpy as np
sys.path.insert(0, %(repo)r)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=2")
import jax
jax.config.update("jax_num_cpu_devices", 2)
jax.config.update("jax_cpu_collectives_implementation", "gloo")
from lightgbm_tpu.config import Config
from lightgbm_tpu.parallel.fingerprint import DivergenceError
from lightgbm_tpu.parallel.multihost import shard_rows, train_multihost
from lightgbm_tpu.resilience import faults
from lightgbm_tpu.telemetry import flight

rank = int(sys.argv[1])
port = sys.argv[2]
out = sys.argv[3]
dump_dir = sys.argv[4]

rng = np.random.default_rng(7)
n, nf = 2000, 6
X = rng.normal(size=(n, nf))
y = (X[:, 0] - 0.7 * X[:, 3] + rng.normal(size=n) * 0.3 > 0).astype(float)

cfg = Config({"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "num_machines": 2,
              "machines": "127.0.0.1:%%s,127.0.0.1:0" %% port,
              "min_data_in_leaf": 5, "tree_learner": "data",
              "tpu_fault_plan": "corrupt_hist@round=5;rank=1"})
faults.configure_from_config(cfg)
flight.arm(dump_dir=dump_dir)
idx = shard_rows(n, rank, 2, False)
try:
    train_multihost(cfg, X[idx], y[idx], num_rounds=12,
                    process_id=rank)
except DivergenceError as exc:
    with open(out, "w") as fh:
        json.dump({"rank": rank, "iteration": exc.iteration,
                   "component": exc.component, "ranks": exc.ranks,
                   "dump": flight.last_dump_path()}, fh)
    sys.exit(0)
with open(out, "w") as fh:
    json.dump({"rank": rank, "iteration": None}, fh)
sys.exit(1)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


@pytest.mark.slow
def test_two_process_corrupt_hist_detected(tmp_path):
    """End to end: rank 1's histogram fingerprint is corrupted at round
    5; BOTH ranks raise DivergenceError at exactly iteration 5 naming
    the hist component, and each rank leaves its own flight dump."""
    port = _free_port()
    script = tmp_path / "worker.py"
    script.write_text(DIVERGE_WORKER % {"repo": REPO})
    dump_dir = tmp_path / "dumps"
    dump_dir.mkdir()
    outs = [str(tmp_path / ("rank%d.json" % r)) for r in range(2)]
    procs = []
    for r in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.pop("JAX_PLATFORMS", None)
        procs.append(subprocess.Popen(
            [sys.executable, str(script), str(r), str(port), outs[r],
             str(dump_dir)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE))
    for p in procs:
        try:
            _, err = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("divergence worker timed out")
        assert p.returncode == 0, err.decode()[-2000:]
    for r in range(2):
        res = json.load(open(outs[r]))
        assert res["iteration"] == 5, res
        assert res["component"] == "hist"
        assert res["ranks"] == [0, 1]
        dump_path = str(dump_dir / ("flight.r%d.json" % r))
        assert os.path.exists(dump_path), \
            "rank %d left no flight dump" % r
        dump = json.load(open(dump_path))
        assert dump["reason"] == "divergence:hist@iter=5"
        assert dump["rank"] == r
