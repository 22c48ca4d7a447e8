"""Benchmark: HIGGS-like GBDT training throughput vs the reference CPU anchor.

Reference anchor (BASELINE.md / docs/Experiments.rst:103-117): LightGBM
trains HIGGS (10.5M rows x 28 features, binary, 500 iterations, 255 leaves,
max_bin=255 defaults) in 238.5 s on 2x E5-2670v3 => 22.01M row-iterations/s.

This bench trains the same shape of problem (synthetic HIGGS-like data —
the real set needs a download; zero egress here) on whatever accelerator
jax exposes and reports row-iterations/s relative to that anchor.
Rows/iters scale via BENCH_ROWS / BENCH_ITERS env vars; the metric is
throughput so partial runs compare fairly.

Prints ONE json line: {"metric", "value", "unit", "vs_baseline"}.
"""
import json
import os
import sys
import time

REF_ROWS = 10_500_000
REF_ITERS = 500
REF_SECONDS = 238.5
REF_THROUGHPUT = REF_ROWS * REF_ITERS / REF_SECONDS   # 22.01M row-iters/s


# canonical generator lives in the package (shared with the profiling CLI
# and tests); re-exported here for bench_full / prof_* imports
from lightgbm_tpu.data.synth import make_higgs_like  # noqa: E402,F401


BENCH_SCHEMA_VERSION = 1


def _phase_stats(telemetry, work=None):
    """One phase's telemetry snapshot + the archived roofline card —
    the shared layout lives in telemetry/perfmodel.phase_snapshot (the
    profile CLI archives the identical structure)."""
    from lightgbm_tpu.telemetry import perfmodel
    return perfmodel.phase_snapshot(work=work)


def _git_sha():
    import subprocess
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        ).stdout.strip()
    except Exception:
        return ""


def build_meta(repeats=1, spread=None):
    """The self-describing ``meta`` block every recorded round carries:
    schema version, git SHA, device profile, jax version, the active
    BENCH_* knobs, and the median-of-k repeat count + per-key relative
    spread. Rounds become comparable ARTIFACTS instead of bare numbers —
    the perf sentinel (analysis/perf_gate.py) keys its comparability
    lineages and noise bands off exactly this block."""
    import platform

    import jax
    from lightgbm_tpu.telemetry.devices import detect_profile
    try:
        devs = jax.devices()
        kind, plat, count = devs[0].device_kind, devs[0].platform, len(devs)
    except Exception:
        kind, plat, count = "unknown", "unknown", 0
    return {
        "schema": BENCH_SCHEMA_VERSION,
        "git_sha": _git_sha(),
        "device": {"kind": kind, "platform": plat, "count": count,
                   "profile": detect_profile().to_dict()},
        "jax": jax.__version__,
        "python": platform.python_version(),
        "knobs": {k: v for k, v in sorted(os.environ.items())
                  if k.startswith("BENCH")},
        "repeats": int(repeats),
        "spread": {k: round(float(v), 4)
                   for k, v in sorted((spread or {}).items())},
    }


def _median_merge(runs):
    """Element-wise median of repeated phase dicts + per-key relative
    spread ((max-min)/|median|) for the numeric keys present in every
    run. Non-numeric / unstable keys keep the first run's value."""
    import statistics
    merged = dict(runs[0])
    spread = {}
    for k, v0 in runs[0].items():
        if isinstance(v0, bool) or not isinstance(v0, (int, float)):
            continue
        vals = [r[k] for r in runs
                if isinstance(r.get(k), (int, float))
                and not isinstance(r.get(k), bool)]
        if len(vals) != len(runs):
            continue
        med = statistics.median(vals)
        merged[k] = med if isinstance(v0, int) and med == int(med) \
            else round(float(med), 6)
        spread[k] = (max(vals) - min(vals)) / max(abs(med), 1e-12)
    return merged, spread


def _repeat_phase(fn, repeats, reset=None):
    """(median-merged phase dict, per-key spread) over `repeats` runs.

    ``reset`` (telemetry.reset when telemetry is on) runs before EVERY
    repeat so the phase snapshot taken afterwards covers the LAST run
    only — without it, repeated phases would archive k runs' accumulated
    wall against a single run's work geometry, and the roofline card
    would divide a 1-run model by a k-run denominator."""
    runs = []
    for _ in range(max(repeats, 1)):
        if reset is not None:
            reset()
        runs.append(fn())
    if len(runs) == 1:
        return runs[0], {}
    return _median_merge(runs)


def _copy_spread(spread_out, phase_spread, mapping=None, **kw):
    """Record a phase's per-key spread under the BENCH result key names
    (``meta.spread`` speaks the same vocabulary as ``parsed``).
    ``mapping`` takes src keys that are not identifiers (the predict
    phase's dotted ``poisson.p99`` style)."""
    for src, dst in dict(mapping or {}, **kw).items():
        if src in phase_spread:
            spread_out[dst] = phase_spread[src]


def _median_merge_nested(runs, subkeys):
    """Median-merge for phases returning nested dicts (predict): each
    named sub-dict medians element-wise; spreads come back keyed
    ``sub.key``. Top-level non-dict values keep the first run's."""
    merged = dict(runs[0])
    spread = {}
    for sub in subkeys:
        subruns = [r[sub] for r in runs if isinstance(r.get(sub), dict)]
        if len(subruns) != len(runs):
            continue
        m, s = _median_merge(subruns)
        merged[sub] = m
        for k, v in s.items():
            spread["%s.%s" % (sub, k)] = v
    return merged, spread


def _extra_params():
    """BENCH_PARAMS="k=v,k=v": extra training params merged into EVERY
    bench phase (e.g. ``tpu_persist_scan=force,num_leaves=63`` records
    a comparable round on a box without the default fast-path gates —
    the knob lands in meta.knobs, so such rounds open their own
    comparability lineage instead of polluting the default one)."""
    raw = os.environ.get("BENCH_PARAMS", "")
    out = {}
    for tok in raw.split(","):
        tok = tok.strip()
        if not tok:
            continue
        k, _, v = tok.partition("=")
        out[k.strip()] = v.strip()
    return out


def _phase_params(base):
    """One phase's params: the phase defaults + the BENCH_PARAMS knob."""
    p = dict(base)
    p.update(_extra_params())
    return p


def main():
    n_rows = int(os.environ.get("BENCH_ROWS", 10_500_000))
    n_iters = int(os.environ.get("BENCH_ITERS", 500))
    num_leaves = int(os.environ.get("BENCH_LEAVES", 255))
    max_bin = int(os.environ.get("BENCH_MAX_BIN", 255))

    import lightgbm_tpu as lgb
    from lightgbm_tpu import telemetry

    # phase attribution rides the telemetry registry (timers mode): the
    # snapshot records WHERE the time went, next to the throughput metric.
    # BENCH_TELEMETRY=0 opts out, measuring the headline number with zero
    # telemetry overhead inside the timed window (comparable with BENCH
    # rounds archived before the telemetry subsystem existed).
    bench_telemetry = os.environ.get("BENCH_TELEMETRY", "1") != "0"
    if bench_telemetry:
        telemetry.enable("timers")
    phase_snaps = {}
    # BENCH_REPEATS=k: run every timed phase k times, report the per-key
    # MEDIAN, and record the relative spread into meta.spread — the perf
    # sentinel widens its noise band to the recorded spread
    repeats = int(os.environ.get("BENCH_REPEATS", 1))
    spread_out = {}
    # phases after HIGGS keep going past a failure so one crash cannot
    # lose the rest of the round, but the failure is recorded in the last
    # line and the process exits non-zero
    failed = []

    X, y = make_higgs_like(n_rows)
    t_bin0 = time.time()
    ds = lgb.Dataset(X, y)
    ds.construct()
    t_bin = time.time() - t_bin0

    params = _phase_params({"objective": "binary",
                            "num_leaves": num_leaves,
                            "max_bin": max_bin, "verbosity": -1,
                            "metric": "none"})
    num_leaves = int(params["num_leaves"])

    # warmup: compile the grower AND the fused 16-iteration scan on the
    # full-size problem (compiles are one-time costs; steady state is what
    # the throughput metric compares against the anchor)
    warm = lgb.train(dict(params), ds, 17, verbose_eval=False)
    warm._booster._materialize_pending()
    del warm

    def _timed_higgs():
        if bench_telemetry:   # opted out: never touch the global registry
            telemetry.reset()   # steady state: drop binning/warmup compiles
        t0 = time.time()
        booster = lgb.train(dict(params), ds, n_iters, verbose_eval=False)
        # force the async pipeline to finish: materialize every pending
        # device tree and block on the score buffer
        booster._booster._materialize_pending()
        import jax
        jax.block_until_ready(booster._booster.train_score.score_device(0))
        train_s = time.time() - t0
        throughput = n_rows * n_iters / train_s
        return {"train_s": train_s,
                "value": round(throughput / 1e6, 3),
                "vs_baseline": round(throughput / REF_THROUGHPUT, 4)}

    reset_fn = telemetry.reset if bench_telemetry else None
    higgs, higgs_spread = _repeat_phase(_timed_higgs, repeats,
                                        reset=reset_fn)
    train_s = higgs["train_s"]
    if bench_telemetry:
        phase_snaps["higgs"] = _phase_stats(
            telemetry, work={"phase": "higgs", "rows": n_rows,
                             "iters": n_iters, "num_leaves": num_leaves})
    _copy_spread(spread_out, higgs_spread, value="value",
                 vs_baseline="vs_baseline")

    result = {
        "metric": "higgs_like_train_throughput",
        "value": higgs["value"],
        "unit": "Mrow_iters_per_sec",
        "vs_baseline": higgs["vs_baseline"],
    }
    if bench_telemetry:
        result["phases"] = phase_snaps["higgs"]["categories"]
        # runtime numerics sentinel: the higgs phase's split-margin p01
        # (numerics::split_margin flushes when the persist path runs —
        # on a gate-less box use BENCH_PARAMS="tpu_persist_scan=force").
        # HIGHER_BETTER in the --perf sentinel: a quantization change
        # that collapses decision margins gates even at equal throughput
        mh = telemetry.histo.get("numerics::split_margin")
        if mh is not None and mh.count:
            # significant figures, not decimal places: the margin layout
            # reaches down to 1e-9 and a round(., 6) would flatten any
            # sub-5e-7 p01 to 0.0 — invisible to the HIGHER_BETTER gate
            result["margin_p01"] = float("%.4g" % mh.percentile(0.01))
    # print the primary metric BEFORE the MS-LTR phase so a hard crash
    # there (OOM kill, TPU fault) can't lose it; the combined line with
    # the ranking keys is re-printed last and shadows this one for
    # last-JSON-line parsers
    print(json.dumps(result), flush=True)
    print("# rows=%d iters=%d leaves=%d bins=%d train=%.1fs binning=%.1fs "
          "(ref anchor: %.1fM row-iters/s from HIGGS 238.5s)"
          % (n_rows, n_iters, num_leaves, max_bin, train_s, t_bin,
             REF_THROUGHPUT / 1e6), file=sys.stderr)
    ltr = None
    if os.environ.get("BENCH_SKIP_LTR", "") != "1":
        try:
            if bench_telemetry:
                telemetry.reset()
            ltr, ltr_spread = _repeat_phase(run_ltr, repeats, reset=reset_fn)
            if bench_telemetry:
                phase_snaps["ltr"] = _phase_stats(
                    telemetry, work={"phase": "ltr", "rows": ltr["rows"],
                                     "iters": ltr["iters"],
                                     "num_leaves":
                                         ltr.get("num_leaves", 255)})
            _copy_spread(spread_out, ltr_spread, value="ranking_value",
                         vs_baseline="ranking_vs_baseline")
        except Exception as exc:
            failed.append("MS-LTR")
            print("# MS-LTR phase failed: %r" % exc, file=sys.stderr)
    if ltr is not None:
        result["ranking_value"] = ltr["value"]
        result["ranking_vs_baseline"] = ltr["vs_baseline"]
        print(json.dumps(result), flush=True)
        print("# MS-LTR lambdarank: rows=%d iters=%d train=%.1fs -> "
              "%.2fM row-iters/s, vs anchor (2.27M*500/215.3s = 5.27M): "
              "%.4f" % (ltr["rows"], ltr["iters"], ltr["train_s"],
                        ltr["value"], ltr["vs_baseline"]), file=sys.stderr)
    expo = None
    if os.environ.get("BENCH_SKIP_EXPO", "") != "1":
        try:
            if bench_telemetry:
                telemetry.reset()
            expo, expo_spread = _repeat_phase(run_expo, repeats, reset=reset_fn)
            if bench_telemetry:
                phase_snaps["expo"] = _phase_stats(
                    telemetry, work={"phase": "expo",
                                     "rows": expo["rows"],
                                     "iters": expo["iters"],
                                     "num_leaves":
                                         expo.get("num_leaves", 255)})
            _copy_spread(spread_out, expo_spread, value="expo_value",
                         vs_baseline="expo_vs_baseline",
                         level_value="expo_level_value",
                         level_vs_baseline="expo_level_vs_baseline")
        except Exception as exc:
            failed.append("expo")
            print("# expo phase failed: %r" % exc, file=sys.stderr)
    if expo is not None:
        result["expo_value"] = expo["value"]
        result["expo_vs_baseline"] = expo["vs_baseline"]
        if "level_value" in expo:
            # level-program phase keys (PR 7): before/after for the
            # launch-overhead elimination, plus the measured launch count
            result["expo_level_value"] = expo["level_value"]
            result["expo_level_vs_baseline"] = expo["level_vs_baseline"]
            result["expo_level_programs"] = expo["level_programs"]
            result["expo_level_fallback_splits"] = \
                expo["level_fallback_splits"]
            result["expo_level_launches_per_tree"] = \
                expo["level_launches_per_tree"]
        if "launches_per_iter" in expo:
            # fused-iteration phase key (PR 17): device launches per
            # boosting iteration — the whole-iteration fusion target
            result["launches_per_iter"] = expo["launches_per_iter"]
        print(json.dumps(result), flush=True)
        print("# Expo-like EFB-bundled (%d groups for %d features): rows=%d "
              "iters=%d train=%.1fs -> %.2fM row-iters/s, vs anchor "
              "(11M*500/138.5s = 39.7M): %.4f"
              % (expo["groups"], expo["features"], expo["rows"],
                 expo["iters"], expo["train_s"], expo["value"],
                 expo["vs_baseline"]), file=sys.stderr)
        if "level_value" in expo:
            print("# Expo-like LEVEL-PROGRAM growth (num_leaves=2^d, "
                  "max_depth=d): train=%.1fs -> %.2fM row-iters/s, vs "
                  "anchor: %.4f; %.2f device launches/tree "
                  "(level_programs=%d fallback_splits=%d)"
                  % (expo["level_train_s"], expo["level_value"],
                     expo["level_vs_baseline"],
                     expo["level_launches_per_tree"],
                     expo["level_programs"],
                     expo["level_fallback_splits"]), file=sys.stderr)
    allst = None
    if os.environ.get("BENCH_SKIP_ALLSTATE", "") != "1":
        try:
            if bench_telemetry:
                telemetry.reset()
            allst, allst_spread = _repeat_phase(run_allstate, repeats, reset=reset_fn)
            if bench_telemetry:
                phase_snaps["allstate"] = _phase_stats(
                    telemetry, work={"phase": "allstate",
                                     "rows": allst["rows"],
                                     "iters": allst["iters"],
                                     "num_leaves":
                                         allst.get("num_leaves", 255)})
            _copy_spread(spread_out, allst_spread,
                         value="allstate_value",
                         vs_baseline="allstate_vs_baseline")
        except Exception as exc:
            failed.append("allstate")
            print("# allstate phase failed: %r" % exc, file=sys.stderr)
    if allst is not None:
        result["allstate_value"] = allst["value"]
        result["allstate_vs_baseline"] = allst["vs_baseline"]
        print(json.dumps(result), flush=True)
        print("# Allstate-like sparse one-hot (%d groups for %d features): "
              "rows=%d iters=%d train=%.1fs -> %.2fM row-iters/s, vs anchor"
              " (13.18M*500/348.1s = 18.94M): %.4f"
              % (allst["groups"], allst["features"], allst["rows"],
                 allst["iters"], allst["train_s"], allst["value"],
                 allst["vs_baseline"]), file=sys.stderr)
    yah = None
    if os.environ.get("BENCH_SKIP_YAHOO", "") != "1":
        try:
            if bench_telemetry:
                telemetry.reset()
            yah, yah_spread = _repeat_phase(run_yahoo, repeats, reset=reset_fn)
            if bench_telemetry:
                phase_snaps["yahoo_ltr"] = _phase_stats(
                    telemetry, work={"phase": "yahoo_ltr",
                                     "rows": yah["rows"],
                                     "iters": yah["iters"],
                                     "num_leaves":
                                         yah.get("num_leaves", 255)})
            _copy_spread(spread_out, yah_spread, value="yahoo_value",
                         vs_baseline="yahoo_vs_baseline")
        except Exception as exc:
            failed.append("yahoo")
            print("# yahoo phase failed: %r" % exc, file=sys.stderr)
    if yah is not None:
        result["yahoo_value"] = yah["value"]
        result["yahoo_vs_baseline"] = yah["vs_baseline"]
        print(json.dumps(result), flush=True)
        print("# Yahoo-LTR-like lambdarank: rows=%d iters=%d train=%.1fs "
              "-> %.2fM row-iters/s, vs anchor (473k*500/150.2s = 1.58M): "
              "%.4f" % (yah["rows"], yah["iters"], yah["train_s"],
                        yah["value"], yah["vs_baseline"]), file=sys.stderr)
    vote = None
    if os.environ.get("BENCH_SKIP_VOTING", "") != "1":
        try:
            if bench_telemetry:
                telemetry.reset()
            vote, vote_spread = _repeat_phase(run_voting, repeats, reset=reset_fn)
            if bench_telemetry:
                phase_snaps["voting"] = _phase_stats(
                    telemetry, work={"phase": "voting",
                                     "rows": vote["rows"],
                                     "iters": vote["iters"]})
            _copy_spread(spread_out, vote_spread, value="voting_value",
                         vs_baseline="voting_vs_baseline")
        except Exception as exc:
            failed.append("voting")
            print("# voting phase failed: %r" % exc, file=sys.stderr)
    if vote is not None:
        result["voting_value"] = vote["value"]
        result["voting_vs_baseline"] = vote["vs_baseline"]
        for key in ("reduced_feature_frac", "dcn_hist_bytes",
                    "hist_compress_ratio"):
            if key in vote:
                result[key] = vote[key]
        print(json.dumps(result), flush=True)
        print("# voting-parallel (PV-tree persist, %d-device mesh): rows=%d "
              "iters=%d train=%.1fs -> %.2fM row-iters/s (vs the same CPU "
              "anchor: %.4f)" % (vote["devices"], vote["rows"],
                                 vote["iters"], vote["train_s"],
                                 vote["value"], vote["vs_baseline"]),
              file=sys.stderr)
    ckpt = None
    if os.environ.get("BENCH_SKIP_CHECKPOINT", "") != "1":
        try:
            if bench_telemetry:
                telemetry.reset()
            ckpt, ckpt_spread = _repeat_phase(run_checkpoint, repeats,
                                              reset=reset_fn)
            if bench_telemetry:
                phase_snaps["checkpoint"] = _phase_stats(
                    telemetry, work={"phase": "checkpoint",
                                     "rows": ckpt["rows"],
                                     "iters": ckpt["iters"]})
            _copy_spread(spread_out, ckpt_spread,
                         overhead_frac="checkpoint_overhead_frac",
                         write_s="checkpoint_write_s")
        except Exception as exc:
            failed.append("checkpoint")
            print("# checkpoint phase failed: %r" % exc, file=sys.stderr)
    if ckpt is not None:
        result["checkpoint_overhead_frac"] = ckpt["overhead_frac"]
        result["checkpoint_write_s"] = ckpt["write_s"]
        result["checkpoint_writes"] = ckpt["writes"]
        result["checkpoint_mb"] = ckpt["mb"]
        print(json.dumps(result), flush=True)
        print("# checkpoint[higgs-like]: rows=%d iters=%d freq=%d -> %d "
              "snapshots (%.1f MB) in %.2fs write time; train %.1fs with "
              "vs %.1fs without = %.2f%% overhead (budget 3%%)"
              % (ckpt["rows"], ckpt["iters"], ckpt["freq"], ckpt["writes"],
                 ckpt["mb"], ckpt["write_s"], ckpt["train_on_s"],
                 ckpt["train_off_s"], 100.0 * ckpt["overhead_frac"]),
              file=sys.stderr)
    pred = None
    if os.environ.get("BENCH_SKIP_PREDICT", "") != "1":
        try:
            if bench_telemetry:
                telemetry.reset()
            # predict returns nested per-shape dicts: repeat by hand and
            # median-merge each sub-dict (spread keys come back dotted)
            runs = []
            for _ in range(max(repeats, 1)):
                if reset_fn is not None:
                    reset_fn()
                runs.append(run_predict())
            if len(runs) == 1:
                pred, pred_spread = runs[0], {}
            else:
                pred, pred_spread = _median_merge_nested(
                    runs, ("higgs", "expo", "poisson"))
            if bench_telemetry:
                phase_snaps["predict"] = _phase_stats(
                    telemetry, work={"phase": "predict",
                                     "rows": pred["higgs"]["rows"]})
            _copy_spread(spread_out, pred_spread, {
                "higgs.value": "predict_value",
                "expo.value": "predict_expo_value",
                "poisson.p50": "predict_p50",
                "poisson.p99": "predict_p99",
                "poisson.qdepth_mean": "predict_qdepth"})
        except Exception as exc:
            failed.append("predict")
            print("# predict phase failed: %r" % exc, file=sys.stderr)
    if pred is not None:
        result["predict_value"] = pred["higgs"]["value"]
        result["predict_compiles"] = pred["higgs"]["compiles"]
        result["predict_expo_value"] = pred["expo"]["value"]
        result["predict_expo_compiles"] = pred["expo"]["compiles"]
        slo = pred.get("poisson")
        if slo is not None:
            # serving SLO under the open-loop Poisson load (latency
            # measured from ARRIVAL, so queueing shows up in the tail)
            result["predict_p50"] = slo["p50"]
            result["predict_p99"] = slo["p99"]
            result["predict_qdepth"] = slo["qdepth_mean"]
        print(json.dumps(result), flush=True)
        for shape in ("higgs", "expo"):
            r = pred[shape]
            print("# predict[%s]: %d trees, rows=%d served in %.2fs -> "
                  "%.2fM rows/s, %d serve compiles (bound %d)"
                  % (shape, r["trees"], r["rows"], r["serve_s"], r["value"],
                     r["compiles"], r["compile_bound"]), file=sys.stderr)
        if slo is not None:
            print("# predict[poisson open-loop]: %d requests at %.0f rps "
                  "-> p50=%.1fms p99=%.1fms queue-wait p99=%.1fms, mean "
                  "qdepth %.2f (max %d)"
                  % (slo["requests"], slo["rps"], slo["p50"] * 1e3,
                     slo["p99"] * 1e3, slo["queue_wait_p99"] * 1e3,
                     slo["qdepth_mean"], slo["qdepth_max"]),
                  file=sys.stderr)
    serv = None
    if os.environ.get("BENCH_SKIP_SERVING", "") != "1":
        try:
            if bench_telemetry:
                telemetry.reset()
            serv, serv_spread = _repeat_phase(run_serving, repeats,
                                              reset=reset_fn)
            if bench_telemetry:
                phase_snaps["serving"] = _phase_stats(
                    telemetry, work={"phase": "serving",
                                     "requests": serv["requests"]})
            _copy_spread(spread_out, serv_spread,
                         rps="serving_rps",
                         vs_sync="serving_vs_sync",
                         deadline_miss_frac="serving_deadline_miss_frac")
        except Exception as exc:
            failed.append("serving")
            print("# serving phase failed: %r" % exc, file=sys.stderr)
    if serv is not None:
        result["serving_rps"] = serv["rps"]
        result["serving_vs_sync"] = serv["vs_sync"]
        result["serving_deadline_miss_frac"] = serv["deadline_miss_frac"]
        print(json.dumps(result), flush=True)
        print("# serving[async vs sync]: %d reqs x %d clients, %d trees "
              "-> %.0f rps async (%.2fx sync), p50=%.1fms p99=%.1fms, "
              "deadline>%.0fms miss %.1f%%; %d batches (coalesce %.2f "
              "reqs/batch, qdepth max %d)"
              % (serv["requests"], serv["clients"], serv["trees"],
                 serv["rps"], serv["vs_sync"], serv["p50"] * 1e3,
                 serv["p99"] * 1e3, serv["slo_ms"],
                 100.0 * serv["deadline_miss_frac"], serv["batches"],
                 serv["coalesce_ratio"], serv["qdepth_max"]),
              file=sys.stderr)
    swp = None
    if os.environ.get("BENCH_SKIP_SWEEP", "") != "1":
        try:
            if bench_telemetry:
                telemetry.reset()
            swp, swp_spread = _repeat_phase(run_sweep, repeats,
                                            reset=reset_fn)
            if bench_telemetry:
                phase_snaps["sweep"] = _phase_stats(
                    telemetry, work={"phase": "sweep",
                                     "rows": swp["rows"],
                                     "iters": swp["iters"],
                                     "models": swp["models"]})
            _copy_spread(spread_out, swp_spread,
                         models_per_sec="models_per_sec")
        except Exception as exc:
            failed.append("sweep")
            print("# sweep phase failed: %r" % exc, file=sys.stderr)
    if swp is not None:
        result["models_per_sec"] = swp["models_per_sec"]
        if "sweep_compiles" in swp:
            result["sweep_compiles"] = swp["sweep_compiles"]
        print(json.dumps(result), flush=True)
        print("# sweep[multimodel]: %d models (grid: %s) x %d iters on "
              "rows=%d -> warm %.2fs = %.2f models/s (cold %.2fs%s)"
              % (swp["models"], swp["grid"], swp["iters"], swp["rows"],
                 swp["warm_s"], swp["models_per_sec"], swp["cold_s"],
                 ", %d warm compiles" % swp["sweep_compiles"]
                 if "sweep_compiles" in swp else ""), file=sys.stderr)
    # the self-describing meta block rides the LAST printed json line —
    # the one last-JSON-line parsers archive as `parsed` — so every
    # recorded round is a comparable artifact (schema version, git SHA,
    # device profile, jax version, BENCH_* knobs, repeat count + spread)
    # instead of bare numbers; the perf sentinel keys its lineages and
    # noise bands off this block
    result["meta"] = build_meta(repeats=repeats, spread=spread_out)
    if failed:
        result["failed_phases"] = failed
    print(json.dumps(result), flush=True)
    # full per-phase telemetry snapshot (category totals + per-scope table)
    # so BENCH_*.json rounds can archive WHERE the time went
    if bench_telemetry:
        phases_out = os.environ.get("BENCH_PHASES_OUT", "BENCH_phases.json")
        try:
            with open(phases_out, "w") as f:
                json.dump(phase_snaps, f, indent=1, sort_keys=True)
            print("# telemetry phase snapshot written to %s" % phases_out,
                  file=sys.stderr)
        except OSError as exc:
            print("# could not write %s: %r" % (phases_out, exc),
                  file=sys.stderr)
    if failed:
        print("# failed phases: %s" % ", ".join(failed), file=sys.stderr)
        return 1
    return 0


# MS-LTR anchor: 2.27M rows x 137 features, lambdarank, 500 iters in
# 215.3 s on the reference box (docs/Experiments.rst:110,143)
LTR_ROWS = 2_270_000
LTR_THROUGHPUT = LTR_ROWS * 500 / 215.3


def run_ltr():
    """MS-LTR-shaped lambdarank throughput (second north-star metric)."""
    import lightgbm_tpu as lgb
    from bench_full import make_ltr_like
    n_iters = int(os.environ.get("BENCH_LTR_ITERS", 160))
    X, y, group = make_ltr_like(
        n_rows=int(os.environ.get("BENCH_LTR_ROWS", LTR_ROWS)))
    n_rows = len(y)
    ds = lgb.Dataset(X, y, group=group)
    ds.construct()
    params = _phase_params({"objective": "lambdarank", "num_leaves": 255,
                            "max_bin": 255, "verbosity": -1,
                            "metric": "none"})
    warm = lgb.train(dict(params), ds, 17, verbose_eval=False)
    warm._booster._materialize_pending()
    del warm
    t0 = time.time()
    booster = lgb.train(dict(params), ds, n_iters, verbose_eval=False)
    booster._booster._materialize_pending()
    import jax
    jax.block_until_ready(booster._booster.train_score.score_device(0))
    train_s = time.time() - t0
    throughput = n_rows * n_iters / train_s
    return {"rows": n_rows, "iters": n_iters, "train_s": train_s,
            "num_leaves": int(params["num_leaves"]),
            "value": round(throughput / 1e6, 3),
            "vs_baseline": round(throughput / LTR_THROUGHPUT, 4)}


def run_expo():
    """Expo-shaped EFB-bundled throughput (one-hot blocks packed into a
    handful of byte groups; persist path with in-kernel bundle decode).

    Two trainings over the same binned dataset:

      * the historical per-split config (num_leaves=255, unbounded
        depth) — keys ``value``/``vs_baseline``, comparable with every
        archived BENCH round;
      * the LEVEL-PROGRAM config (num_leaves=2^d >= the frontier, so
        the no-bind certificate holds at the root and a tree costs
        <= max_depth fused level launches instead of ~num_leaves-1
        split_pass launches — the PR 7 Expo-gap fix) — keys
        ``level_*``, including the counter-measured launches per tree.

    BENCH_EXPO_LEVEL=0 skips the second training; BENCH_EXPO_DEPTH
    picks d (default 8: 256-leaf trees, the 255-leaf class).
    """
    import jax
    import lightgbm_tpu as lgb
    from bench_full import EXPO_SECONDS, make_expo_like
    from lightgbm_tpu.telemetry import events
    n_rows = int(os.environ.get("BENCH_EXPO_ROWS", 2_000_000))
    n_iters = int(os.environ.get("BENCH_EXPO_ITERS", 96))
    X, y = make_expo_like(n_rows)
    ds = lgb.Dataset(X, y)
    ds.construct()
    inner = ds._inner
    anchor = 11_000_000 * 500 / EXPO_SECONDS

    def timed_train(params):
        warm = lgb.train(dict(params), ds, 17, verbose_eval=False)
        warm._booster._materialize_pending()
        del warm
        c0 = events.counts_snapshot()
        t0 = time.time()
        bst = lgb.train(dict(params), ds, n_iters, verbose_eval=False)
        bst._booster._materialize_pending()
        jax.block_until_ready(bst._booster.train_score.score_device(0))
        train_s = time.time() - t0
        c1 = events.counts_snapshot()
        counts = {k: v - c0.get(k, 0) for k, v in c1.items()}
        return bst, train_s, counts

    params = _phase_params({"objective": "binary", "num_leaves": 255,
                            "max_bin": 255, "verbosity": -1,
                            "metric": "none"})
    _, train_s, _ = timed_train(params)
    throughput = n_rows * n_iters / train_s
    out = {"rows": n_rows, "iters": n_iters, "train_s": train_s,
           "groups": len(inner.groups), "features": inner.num_features,
           "num_leaves": int(params["num_leaves"]),
           "value": round(throughput / 1e6, 3),
           "vs_baseline": round(throughput / anchor, 4)}
    if os.environ.get("BENCH_EXPO_LEVEL", "1") != "0":
        d = int(os.environ.get("BENCH_EXPO_DEPTH", 8))
        params_lv = dict(params, num_leaves=1 << d, max_depth=d)
        counting = not events.enabled()   # BENCH_TELEMETRY=0 runs: the
        if counting:                      # launch counters still matter
            events.enable("timers")
        _, lv_s, counts = timed_train(params_lv)
        if counting:
            events.disable()
        lv_tp = n_rows * n_iters / lv_s
        trees = counts.get("tree_learner::persist_scan_trees", 0) \
            or counts.get("tree_learner::v1_grow_trees", 0) or n_iters
        out["level_train_s"] = lv_s
        out["level_value"] = round(lv_tp / 1e6, 3)
        out["level_vs_baseline"] = round(lv_tp / anchor, 4)
        out["level_programs"] = counts.get(
            "tree_learner::level_programs", 0)
        out["level_fallback_splits"] = counts.get(
            "tree_learner::level_fallback_splits", 0)
        out["level_launches_per_tree"] = round(
            (out["level_programs"] + out["level_fallback_splits"])
            / max(trees, 1), 2)
        # fused-iteration pin: compiled-program launches the training
        # loop dispatched per boosting iteration (scan-driver programs +
        # score-delta applies; k-batched gbdt amortizes to ~1/k). LOWER
        # is better — the whole-iteration fusion headline
        out["launches_per_iter"] = round(
            counts.get("tree_learner::iter_launches", 0)
            / max(n_iters, 1), 3)
    return out


# Allstate anchor: 13,184,290 rows x 4228 one-hot columns, 500 iters in
# 348.084s (docs/Experiments.rst) => 18.94M row-iters/s
ALLSTATE_THROUGHPUT = 13_184_290 * 500 / 348.084
# Yahoo LTR anchor: 473,134 rows x 700 features, 500 iters in 150.186s
# (docs/Experiments.rst) => 1.575M row-iters/s
YAHOO_THROUGHPUT = 473_134 * 500 / 150.186


def run_allstate():
    """Allstate-shaped sparse one-hot throughput: ~4.1k binary features
    EFB-bundled into byte groups, ingested as CSR (never densified)."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.data.synth import make_allstate_like
    n_rows = int(os.environ.get("BENCH_ALLSTATE_ROWS", 1_000_000))
    n_iters = int(os.environ.get("BENCH_ALLSTATE_ITERS", 64))
    X, y = make_allstate_like(n_rows)
    ds = lgb.Dataset(X, y)
    ds.construct()
    inner = ds._inner
    params = _phase_params({"objective": "binary", "num_leaves": 255,
                            "max_bin": 255, "verbosity": -1,
                            "metric": "none"})
    warm = lgb.train(dict(params), ds, 17, verbose_eval=False)
    warm._booster._materialize_pending()
    del warm
    t0 = time.time()
    bst = lgb.train(dict(params), ds, n_iters, verbose_eval=False)
    bst._booster._materialize_pending()
    jax.block_until_ready(bst._booster.train_score.score_device(0))
    train_s = time.time() - t0
    throughput = n_rows * n_iters / train_s
    return {"rows": n_rows, "iters": n_iters, "train_s": train_s,
            "groups": len(inner.groups), "features": inner.num_features,
            "num_leaves": int(params["num_leaves"]),
            "value": round(throughput / 1e6, 3),
            "vs_baseline": round(throughput / ALLSTATE_THROUGHPUT, 4)}


def run_yahoo():
    """Yahoo-LTR-shaped lambdarank throughput (700 dense features)."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.data.synth import make_yahoo_like
    n_rows = int(os.environ.get("BENCH_YAHOO_ROWS", 473_134))
    n_iters = int(os.environ.get("BENCH_YAHOO_ITERS", 120))
    X, y, group = make_yahoo_like(n_rows)
    ds = lgb.Dataset(X, y, group=group)
    ds.construct()
    params = _phase_params({"objective": "lambdarank", "num_leaves": 255,
                            "max_bin": 255, "verbosity": -1,
                            "metric": "none"})
    warm = lgb.train(dict(params), ds, 17, verbose_eval=False)
    warm._booster._materialize_pending()
    del warm
    t0 = time.time()
    bst = lgb.train(dict(params), ds, n_iters, verbose_eval=False)
    bst._booster._materialize_pending()
    jax.block_until_ready(bst._booster.train_score.score_device(0))
    train_s = time.time() - t0
    n = len(y)
    throughput = n * n_iters / train_s
    return {"rows": n, "iters": n_iters, "train_s": train_s,
            "num_leaves": int(params["num_leaves"]),
            "value": round(throughput / 1e6, 3),
            "vs_baseline": round(throughput / YAHOO_THROUGHPUT, 4)}


def _predict_one_shape(X, y, params, n_trees, serve_rows, tag):
    """Train a model on the shape, then serve `serve_rows` ragged batches
    through the bucketed device runtime; rows/sec + compile count.
    Returns (stats dict, trained booster) — the Poisson SLO phase reuses
    the booster instead of paying a second full training."""
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.predict import BatchServer

    ds = lgb.Dataset(X, y)
    ds.construct()
    bst = lgb.train(dict(params), ds, n_trees, verbose_eval=False)
    bst._booster._materialize_pending()
    server = BatchServer(bst._booster.device_predictor(),
                         min_batch=4096, max_batch=1 << 17)
    rng = np.random.default_rng(0)
    n = len(X)
    # warmup: compile EVERY ladder bucket once so the timed loop measures
    # steady-state serving (the training phases' warmup convention)
    b = server.min_batch
    while b <= server.max_batch:
        server.predict(X[:min(b, n)])
        b <<= 1
    served = 0
    t0 = time.time()
    while served < serve_rows:
        # ragged batch sizes exercise the bucket ladder like real traffic
        k = int(rng.integers(server.min_batch // 2, server.max_batch))
        idx0 = int(rng.integers(0, max(n - k, 1)))
        server.predict(X[idx0:idx0 + min(k, n - idx0)])
        served += min(k, n - idx0)
    serve_s = time.time() - t0
    stats = server.stats()   # per-server: correct with telemetry off AND
    #                        # uncontaminated by the other shape's counters
    return ({"rows": served, "serve_s": serve_s, "trees": bst.num_trees(),
             "value": round(served / serve_s / 1e6, 3),
             "compiles": int(stats["compiles"]),
             "compile_bound": server.max_compiles(), "tag": tag}, bst)


def poisson_open_loop(server, X, rps, n_requests, rng,
                      batch_lo=None, batch_hi=None):
    """Open-loop Poisson load over a warmed BatchServer: SLO percentiles.

    OPEN loop means the arrival schedule is drawn up front (exponential
    inter-arrivals at `rps`) and does NOT slow down when the server
    falls behind — the honest regime for user-facing latency, where a
    stalled server accumulates queue instead of throttling its users
    (the closed-loop rows/sec phases above hide exactly that). Requests
    are served in arrival order on this thread; a request's latency is
    measured from its SCHEDULED ARRIVAL (service start minus arrival is
    its queue wait, recorded by the server), and the queue depth sampled
    at each service start is how many arrived requests were waiting.

    Returns p50/p99 end-to-end seconds, queue-wait p99, and queue-depth
    stats — the BENCH json's predict_p50 / predict_p99 / predict_qdepth.
    """
    import numpy as np
    n = len(X)
    lo = batch_lo if batch_lo is not None else server.min_batch // 2
    hi = batch_hi if batch_hi is not None else server.min_batch * 4
    arrivals = np.cumsum(rng.exponential(1.0 / rps, n_requests))
    sizes = rng.integers(max(lo, 1), max(hi, 2), n_requests)
    starts = rng.integers(0, max(n - int(sizes.max()), 1), n_requests)
    lat = np.empty(n_requests)
    qdepth = np.empty(n_requests, np.int64)
    t0 = time.perf_counter()
    for i in range(n_requests):
        now = time.perf_counter() - t0
        if now < arrivals[i]:
            time.sleep(arrivals[i] - now)
            now = arrivals[i]
        # arrived-but-unstarted requests, this one included
        qdepth[i] = int(np.searchsorted(arrivals, now, side="right")) - i
        k = int(sizes[i])
        i0 = int(starts[i])
        server.predict(X[i0:i0 + min(k, n - i0)],
                       arrival_t=t0 + float(arrivals[i]))
        lat[i] = (time.perf_counter() - t0) - arrivals[i]
    stats = server.stats()
    return {"requests": n_requests, "rps": float(rps),
            "p50": round(float(np.percentile(lat, 50)), 6),
            "p99": round(float(np.percentile(lat, 99)), 6),
            "queue_wait_p99": round(float(stats["queue_wait_p99"]), 6),
            "qdepth_mean": round(float(qdepth.mean()), 3),
            "qdepth_max": int(qdepth.max())}


def run_predict():
    """Inference-subsystem phase: HIGGS-like dense and Expo-like bundled
    shapes served through predict/ (rows/sec + compile counts in the
    BENCH json), plus the open-loop Poisson SLO phase on the HIGGS
    model (predict_p50/p99/qdepth keys)."""
    import numpy as np

    from bench_full import make_expo_like
    from lightgbm_tpu.predict import BatchServer
    n_rows = int(os.environ.get("BENCH_PREDICT_ROWS", 2_000_000))
    n_trees = int(os.environ.get("BENCH_PREDICT_TREES", 100))
    n_leaves = int(os.environ.get("BENCH_PREDICT_LEAVES", 255))
    serve_rows = int(os.environ.get("BENCH_PREDICT_SERVE_ROWS", 8_000_000))
    params = _phase_params({"objective": "binary", "num_leaves": n_leaves,
                            "max_bin": 255, "verbosity": -1,
                            "metric": "none"})
    Xh, yh = make_higgs_like(n_rows)
    higgs, bst_h = _predict_one_shape(Xh, yh, params, n_trees, serve_rows,
                                      "higgs")
    out = {"higgs": higgs}
    if os.environ.get("BENCH_PREDICT_POISSON", "1") != "0":
        # SAME trained model, fresh small-bucket server: SLO traffic is
        # single-user-sized requests, not the throughput phase's 64k-row
        # slabs (the compiled ensemble tensors are cached on the
        # booster; only the small ladder buckets compile here)
        server = BatchServer(bst_h._booster.device_predictor(),
                             min_batch=256, max_batch=4096)
        b = server.min_batch
        while b <= server.max_batch:     # warm every ladder bucket
            server.predict(Xh[:b])
            b <<= 1
        rng = np.random.default_rng(7)
        out["poisson"] = poisson_open_loop(
            server, Xh,
            rps=float(os.environ.get("BENCH_PREDICT_RPS", 50.0)),
            n_requests=int(os.environ.get("BENCH_PREDICT_POISSON_REQS",
                                          400)),
            rng=rng)
    del Xh, yh, bst_h
    Xe, ye = make_expo_like(min(n_rows, 1_000_000))
    out["expo"] = _predict_one_shape(Xe, ye, params, n_trees,
                                     serve_rows // 2, "expo")[0]
    return out


def run_serving():
    """Serving-subsystem phase: the IDENTICAL request mix (sizes, row
    offsets, client concurrency) driven through the synchronous
    BatchServer and the continuous-batching AsyncBatchServer sharing one
    compiled predictor (so the jit ladder is warm for both and the delta
    is pure serving architecture). Clients are a thread pool — the sync
    server serializes a device round-trip per request, the async server
    coalesces concurrent sub-bucket requests into shared batches.

    BENCH keys: serving_rps (sustained async requests/s), serving_vs_sync
    (async speedup over sync on the same mix; acceptance floor 2x on a
    coalescable mix), serving_deadline_miss_frac (fraction of async
    requests over BENCH_SERVING_SLO_MS end-to-end)."""
    import numpy as np
    from concurrent.futures import ThreadPoolExecutor

    import lightgbm_tpu as lgb
    from lightgbm_tpu.predict import BatchServer
    from lightgbm_tpu.serving import AsyncBatchServer

    n_rows = int(os.environ.get("BENCH_SERVING_ROWS", 500_000))
    n_trees = int(os.environ.get("BENCH_SERVING_TREES", 100))
    n_leaves = int(os.environ.get("BENCH_SERVING_LEAVES", 255))
    n_requests = int(os.environ.get("BENCH_SERVING_REQS", 400))
    n_clients = int(os.environ.get("BENCH_SERVING_CLIENTS", 8))
    slo_ms = float(os.environ.get("BENCH_SERVING_SLO_MS", 50.0))
    max_wait_ms = float(os.environ.get("BENCH_SERVING_MAX_WAIT_MS", 5.0))
    # single-user-sized requests: each pads to the 256-row min bucket on
    # the sync path, so coalescing them is where continuous batching
    # earns its keep (a 256-row mix would measure pure dispatch overlap)
    req_lo = int(os.environ.get("BENCH_SERVING_REQ_LO", 1))
    req_hi = int(os.environ.get("BENCH_SERVING_REQ_HI", 64))
    params = _phase_params({"objective": "binary", "num_leaves": n_leaves,
                            "max_bin": 255, "verbosity": -1,
                            "metric": "none"})
    X, y = make_higgs_like(n_rows)
    ds = lgb.Dataset(X, y)
    ds.construct()
    bst = lgb.train(dict(params), ds, n_trees, verbose_eval=False)
    pred = bst._booster.device_predictor()
    # the request mix: single-user-sized slices, drawn ONCE and replayed
    # verbatim through both servers
    rng = np.random.default_rng(11)
    sizes = rng.integers(req_lo, req_hi + 1, n_requests)
    starts = rng.integers(0, max(n_rows - req_hi - 1, 1), n_requests)
    reqs = [X[int(starts[i]):int(starts[i]) + int(sizes[i])]
            for i in range(n_requests)]

    def drive(predict_fn):
        lat = np.empty(n_requests)

        def one(i):
            t0 = time.perf_counter()
            predict_fn(reqs[i])
            lat[i] = time.perf_counter() - t0

        t0 = time.time()
        with ThreadPoolExecutor(n_clients) as pool:
            list(pool.map(one, range(n_requests)))
        return time.time() - t0, lat

    sync = BatchServer(pred, min_batch=256, max_batch=4096)
    b = sync.min_batch
    while b <= sync.max_batch:        # warm the shared ladder once
        sync.predict(X[:b])
        b <<= 1
    t_sync, _lat_sync = drive(sync.predict)
    with AsyncBatchServer(pred, min_batch=256, max_batch=4096,
                          max_wait_ms=max_wait_ms) as server:
        t_async, lat_async = drive(server.predict)
        stats = server.stats()
    return {
        "rows": n_rows, "trees": bst.num_trees(),
        "requests": n_requests, "clients": n_clients,
        "slo_ms": slo_ms, "max_wait_ms": max_wait_ms,
        "sync_s": round(t_sync, 4), "async_s": round(t_async, 4),
        "rps": round(n_requests / t_async, 2),
        "vs_sync": round(t_sync / t_async, 3),
        "deadline_miss_frac": round(
            float((lat_async > slo_ms / 1e3).mean()), 4),
        "p50": round(float(np.percentile(lat_async, 50)), 6),
        "p99": round(float(np.percentile(lat_async, 99)), 6),
        "batches": int(stats["batches"]),
        "coalesce_ratio": float(stats["coalesce_ratio"]),
        "qdepth_max": int(stats["qdepth_max"]),
    }


def run_sweep():
    """Multi-model sweep phase (multimodel/): B boosters trained over ONE
    shared binned Dataset through the model-axis vmap of the fused
    iteration, per-model knobs riding as traced [B] inputs.

    BENCH keys: models_per_sec (B over the post-warm sweep wall — the
    number the model-axis batching exists to scale) and sweep_compiles
    (tree_learner::mm_programs counter delta around the WARM sweep; the
    power-of-two bucket ladder exists so this is 0 — telemetry-on rounds
    only). BENCH_SWEEP_MODELS sets B; BENCH_SWEEP_GRID names the swept
    knob(s) (comma list from the traced set, so every grid stays ONE
    static group / one program chain regardless of B)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu import multimodel
    from lightgbm_tpu.telemetry import events as tel_events

    # defaults sized to the recorded CPU-lineage rounds (the other
    # phases' 20k x 20 scale); TPU rounds crank the knobs — they enter
    # the lineage fingerprint, defaults do not
    n_rows = int(os.environ.get("BENCH_SWEEP_ROWS", 20_000))
    n_iters = int(os.environ.get("BENCH_SWEEP_ITERS", 20))
    n_models = int(os.environ.get("BENCH_SWEEP_MODELS", 4))
    grid_keys = [s.strip() for s in os.environ.get(
        "BENCH_SWEEP_GRID", "learning_rate").split(",") if s.strip()]
    X, y = make_higgs_like(n_rows)
    ds = lgb.Dataset(X, y)
    ds.construct()
    base = _phase_params({"objective": "binary", "num_leaves": 63,
                          "max_bin": 255, "verbosity": -1,
                          "metric": "none"})
    # the driver batches the fused-scan program family; persist-eligible
    # members fall back to their own serial loop (batching the persist
    # family is future work), so a BENCH_PARAMS tpu_persist_scan=force
    # would silently measure B serial loops here. Pin the batched path.
    base["tpu_persist_scan"] = "off"
    # spans for the per-model (traced) knobs; anything else would split
    # the grid into several static groups and measure chaining, not
    # batching
    spans = {"learning_rate": (0.05, 0.2), "lambda_l1": (0.0, 1.0),
             "lambda_l2": (0.0, 2.0), "min_gain_to_split": (0.0, 0.1),
             "min_data_in_leaf": (20, 80)}
    grid = []
    for i in range(n_models):
        p = dict(base)
        for key in grid_keys:
            lo, hi = spans.get(key, (0.05, 0.2))
            v = lo + (hi - lo) * i / max(n_models - 1, 1)
            p[key] = (int(round(v)) if key == "min_data_in_leaf"
                      else round(v, 6))
        grid.append(p)

    def one_sweep():
        # sweep materializes every model's trees before returning, so the
        # wall includes the full async pipeline drain
        t0 = time.time()
        multimodel.sweep(grid, ds, num_boost_round=n_iters)
        return time.time() - t0

    cold_s = one_sweep()          # compiles the bucket-ladder programs
    c0 = tel_events.counts_snapshot().get("tree_learner::mm_programs", 0.0)
    warm_s = one_sweep()
    c1 = tel_events.counts_snapshot().get("tree_learner::mm_programs", 0.0)
    out = {"rows": n_rows, "iters": n_iters, "models": n_models,
           "grid": ",".join(grid_keys),
           "cold_s": round(cold_s, 3), "warm_s": round(warm_s, 3),
           "models_per_sec": round(n_models / warm_s, 4)}
    if tel_events.enabled():
        out["sweep_compiles"] = int(c1 - c0)
    return out


def run_checkpoint():
    """Resilience-subsystem phase: HIGGS-like training with
    snapshot_freq=10 full-state checkpoints vs the same run with them off.
    Reports the wall overhead fraction (acceptance budget: < 3%) plus the
    write time / count / bytes from the checkpoint::* telemetry."""
    import shutil
    import tempfile

    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import telemetry

    n_rows = int(os.environ.get("BENCH_CHECKPOINT_ROWS", 2_000_000))
    n_iters = int(os.environ.get("BENCH_CHECKPOINT_ITERS", 60))
    freq = int(os.environ.get("BENCH_CHECKPOINT_FREQ", 10))
    n_leaves = int(os.environ.get("BENCH_CHECKPOINT_LEAVES", 255))
    X, y = make_higgs_like(n_rows)
    ds = lgb.Dataset(X, y)
    ds.construct()
    base = _phase_params({"objective": "binary", "num_leaves": n_leaves,
                          "max_bin": 255, "verbosity": -1,
                          "metric": "none"})

    def _timed_train(params, wipe_dir=None):
        warm = lgb.train(dict(params), ds, 17, verbose_eval=False)
        warm._booster._materialize_pending()
        del warm
        if wipe_dir is not None:
            # the warmup wrote snapshots; the timed run must train the
            # full n_iters (not resume from them) and the checkpoint::*
            # telemetry must count only the timed run's writes
            for name in os.listdir(wipe_dir):
                os.remove(os.path.join(wipe_dir, name))
            telemetry.reset()
        t0 = time.time()
        bst = lgb.train(dict(params), ds, n_iters, verbose_eval=False)
        bst._booster._materialize_pending()
        jax.block_until_ready(bst._booster.train_score.score_device(0))
        return time.time() - t0

    t_off = _timed_train(base)
    ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        on = dict(base)
        on.update({"snapshot_freq": freq, "checkpoint_dir": ckpt_dir,
                   "checkpoint_keep": 2})
        t_on = _timed_train(on, wipe_dir=ckpt_dir)
        if t_on - t_off > 0.03 * t_off:
            # A shared-CPU steal burst landing in one arm of the A/B
            # masquerades as snapshot overhead (the writes themselves
            # are milliseconds — see write_s). Re-measure each arm once
            # and keep the per-arm minimum: the burst-rejecting
            # estimator, paid only when the first pair blew the budget.
            t_off = min(t_off, _timed_train(base))
            t_on = min(t_on, _timed_train(on, wipe_dir=ckpt_dir))
        counts = telemetry.events.counts_snapshot()
        scopes = telemetry.events.snapshot_full()
        write_s = scopes.get("checkpoint::write", (0.0, 0, ""))[0]
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    return {"rows": n_rows, "iters": n_iters, "freq": freq,
            "train_on_s": t_on, "train_off_s": t_off,
            "overhead_frac": round(max(t_on - t_off, 0.0)
                                   / max(t_off, 1e-9), 4),
            "write_s": round(float(write_s), 3),
            "writes": int(counts.get("checkpoint::write", 0)),
            "mb": round(counts.get("checkpoint::bytes", 0) / 1e6, 2)}


def run_voting():
    """Voting-parallel throughput on the available mesh (PV-tree on the
    sharded persist driver). On a 1-chip box the mesh is degenerate but the
    full voting program (local scan, vote psum, selective reduce) runs —
    the line tracks its overhead vs the plain persist path."""
    import jax
    import lightgbm_tpu as lgb
    n_rows = int(os.environ.get("BENCH_VOTING_ROWS", 4_000_000))
    n_iters = int(os.environ.get("BENCH_VOTING_ITERS", 120))
    X, y = make_higgs_like(n_rows)
    ds = lgb.Dataset(X, y)
    ds.construct()
    params = _phase_params({"objective": "binary", "num_leaves": 255,
                            "max_bin": 255, "verbosity": -1,
                            "metric": "none", "tree_learner": "voting",
                            "top_k": 14})
    warm = lgb.train(dict(params), ds, 17, verbose_eval=False)
    warm._booster._materialize_pending()
    del warm
    t0 = time.time()
    bst = lgb.train(dict(params), ds, n_iters, verbose_eval=False)
    bst._booster._materialize_pending()
    jax.block_until_ready(bst._booster.train_score.score_device(0))
    train_s = time.time() - t0
    throughput = n_rows * n_iters / train_s
    out = {"rows": n_rows, "iters": n_iters, "train_s": train_s,
           "devices": len(jax.devices()),
           "value": round(throughput / 1e6, 3),
           "vs_baseline": round(throughput / REF_THROUGHPUT, 4)}
    # communication-efficiency keys (ROADMAP item 2): the PV-Tree
    # pre-selection ratio and the flush-time wire-byte model — present
    # when the persist path ran with telemetry on; BENCH_PARAMS=
    # "tpu_hist_quant=int16" records a quantized round (its own
    # comparability lineage via meta.knobs)
    tl = bst._booster.tree_learner
    gr = getattr(tl, "_persist_gr", None)
    if gr is not None:
        tl.flush_level_stats()
        out["reduced_feature_frac"] = round(
            float(getattr(gr, "reduced_feature_frac", 1.0)), 4)
        from lightgbm_tpu.telemetry import events as tel_events
        counts = tel_events.counts_snapshot()
        dcn = counts.get("collective::dcn_hist_bytes", 0)
        fullb = counts.get("collective::dcn_hist_bytes_fullwidth", 0)
        if dcn:
            out["dcn_hist_bytes"] = int(dcn)
        if dcn and fullb:
            out["hist_compress_ratio"] = round(fullb / dcn, 3)
    return out


if __name__ == "__main__":
    sys.exit(main())
