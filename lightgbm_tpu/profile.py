"""`python -m lightgbm_tpu.profile` — op-level device profile of training.

Traces N boosting iterations on the real chip with the jax profiler, then
prints device time per XLA op name, the device's idle gaps by program span
and the program's ``lgbm:`` spans as the profiler saw them, via the reusable
xplane reader (:mod:`lightgbm_tpu.telemetry.xplane`, on
``jax.profiler.ProfileData``: nothing else has to be installed).

Usage: python -m lightgbm_tpu.profile [--shape NAME] [rows] [iters]
                                      [key=value ...]
       python -m lightgbm_tpu.profile --merge DIR [--run NAME]
                                      [--out PATH] [--json]

``--merge DIR`` does no training: it merges the rank-suffixed Chrome
traces a multihost run left in DIR (``telemetry_out=`` writes
``out.rN.json`` per rank) into ONE Perfetto-loadable
``merged.trace.json`` with rank-tagged pids, aligning the per-rank host
clocks via the recorded collective barrier spans
(:mod:`lightgbm_tpu.telemetry.merge`). ``--json`` prints the merge
summary as JSON instead of text.

``--shape`` (or ``shape=NAME``) picks one of the ``data/synth.py``
generators: ``higgs`` (default), ``expo`` (EFB-bundled one-hot),
``allstate`` (sparse wide one-hot), ``yahoo`` / ``msltr`` (lambdarank).
Extra ``key=value`` tokens are passed through as training params (e.g.
``tree_learner=data num_leaves=511``), except:

  * ``phases_out=PATH`` — write the traced run's telemetry snapshot
    (:func:`lightgbm_tpu.telemetry.export.phase_snapshot`: category
    totals, scope table, histograms, path counters), keyed by the shape
    name;
  * ``xplane=0`` — skip the device xplane trace (host spans + phase
    snapshot only; the CI smoke test runs this on CPU).

The host-side span registry runs in TRACE mode alongside, so
``telemetry_out=<path>`` also writes the Chrome-trace + metrics files.
"""
from __future__ import annotations

import json
import sys
import time

SHAPE_DEFAULT_ROWS = {"higgs": 2_000_000, "expo": 2_000_000,
                      "allstate": 500_000, "yahoo": 473_134,
                      "msltr": 1_000_000}


def _make_shape(shape: str, rows: int):
    """(X, y, group_or_None, objective) for one bench shape."""
    from lightgbm_tpu.data.synth import (make_allstate_like,
                                         make_expo_like, make_higgs_like,
                                         make_ltr_like, make_yahoo_like)
    if shape == "higgs":
        X, y = make_higgs_like(rows)
        return X, y, None, "binary"
    if shape == "expo":
        X, y = make_expo_like(rows)
        return X, y, None, "binary"
    if shape == "allstate":
        X, y = make_allstate_like(rows)
        return X, y, None, "binary"
    if shape == "yahoo":
        X, y, g = make_yahoo_like(rows)
        return X, y, g, "lambdarank"
    if shape == "msltr":
        X, y, g = make_ltr_like(rows)
        return X, y, g, "lambdarank"
    raise SystemExit("unknown --shape %r (expected higgs|expo|allstate|"
                     "yahoo|msltr)" % shape)


def _main_merge(argv) -> int:
    """--merge DIR [--run NAME] [--out PATH] [--json]: no jax import,
    no training. ``--run`` picks one run's rank files by their trace
    basename when the directory mixes several runs (the no-flag default
    still refuses a mixed directory loudly)."""
    from lightgbm_tpu.telemetry import merge as trace_merge
    i = argv.index("--merge")
    if i + 1 >= len(argv):
        print("--merge needs a directory of rank traces", file=sys.stderr)
        return 2
    directory = argv[i + 1]
    out = None
    if "--out" in argv:
        j = argv.index("--out")
        if j + 1 >= len(argv):
            print("--out needs a path", file=sys.stderr)
            return 2
        out = argv[j + 1]
    run = None
    if "--run" in argv:
        j = argv.index("--run")
        if j + 1 >= len(argv):
            print("--run needs a trace basename (run fingerprint)",
                  file=sys.stderr)
            return 2
        run = argv[j + 1]
    try:
        summary = trace_merge.merge_dir(directory, out, run=run)
    except (trace_merge.MergeError, OSError) as exc:
        print("merge failed: %s" % exc, file=sys.stderr)
        return 2
    if "--json" in argv:
        print(json.dumps(summary, sort_keys=True))
    else:
        print("merged %d rank(s) -> %s (%d events)"
              % (len(summary["ranks"]), summary["out"],
                 summary["events"]))
        for r in summary["ranks"]:
            print("  rank %d: clock offset %+.1fus, %d barrier span(s)"
                  % (r, summary["clock_offsets_us"][str(r)],
                     summary["barrier_spans"][r]))
        if summary["dropped_events"]:
            print("  !! %d trace event(s) were dropped at record time "
                  "across ranks (timelines truncated)"
                  % summary["dropped_events"])
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if any(a in ("-h", "--help") for a in argv):
        print(__doc__)
        return 0
    if "--merge" in argv:
        return _main_merge(argv)
    shape = "higgs"
    if "--shape" in argv:
        i = argv.index("--shape")
        if i + 1 >= len(argv):
            print("--shape needs a value (higgs|expo|allstate|yahoo|"
                  "msltr)", file=sys.stderr)
            return 2
        shape = argv[i + 1]
        del argv[i:i + 2]
    pos = [a for a in argv if "=" not in a]
    kv = [a for a in argv if "=" in a]

    import jax

    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import kv2map
    from lightgbm_tpu.telemetry import events, maybe_export, xplane
    from lightgbm_tpu.telemetry.export import phase_snapshot

    # objective comes from the SHAPE (lambdarank for the LTR ones) unless
    # the caller overrides it via key=value
    params = {"num_leaves": 255, "max_bin": 255,
              "verbosity": -1, "metric": "none"}
    params.update(kv2map(kv))
    shape = str(params.pop("shape", shape)).lower()
    rows = int(pos[0]) if len(pos) > 0 else SHAPE_DEFAULT_ROWS.get(
        shape, 2_000_000)
    iters = int(pos[1]) if len(pos) > 1 else 16
    out = params.pop("telemetry_out", None)
    phases_out = params.pop("phases_out", None)
    use_xplane = str(params.pop("xplane", "1")).lower() not in ("0",
                                                                "false")
    # api-source enable, not configure(): config-driven enablement is scoped
    # to the train that asked for it, so the default-params warmup/traced
    # trains below would flip a configure("trace") back off
    events.enable("trace")
    if out:
        events.set_out_path(out)

    X, y, group, obj = _make_shape(shape, rows)
    params.setdefault("objective", obj)
    ds = lgb.Dataset(X, y, group=group) if group is not None \
        else lgb.Dataset(X, y)
    ds.construct()
    n_rows = ds._inner.num_data
    # warmup/compile outside the trace window (compiles are one-time costs)
    warm = lgb.train(dict(params), ds, 17, verbose_eval=False)
    warm._booster._materialize_pending()
    del warm

    events.reset()
    import contextlib
    tracer = xplane.collect_trace() if use_xplane else None
    with (tracer if tracer is not None else contextlib.nullcontext()) \
            as tdir:
        t0 = time.time()
        booster = lgb.train(dict(params), ds, iters, verbose_eval=False)
        booster._booster._materialize_pending()
        jax.block_until_ready(booster._booster.train_score.score_device(0))
        wall = time.time() - t0
    print("shape=%s wall=%.3fs rows=%d iters=%d -> %.2f Mri/s"
          % (shape, wall, n_rows, iters, n_rows * iters / wall / 1e6))

    if phases_out:
        # the path counters (persist_scan_trees vs v1_grow_trees) ride
        # along so fast-path engagement is visible next to the attribution
        with open(phases_out, "w") as f:
            json.dump({shape: phase_snapshot()}, f, indent=1,
                      sort_keys=True)
        print("telemetry phase snapshot written to %s" % phases_out,
              file=sys.stderr)

    if use_xplane:
        print(xplane.format_device_report(xplane.parse_xplane_dir(tdir),
                                          iters=iters))
    written = maybe_export(out) if out else None
    if written:
        print("host-side spans: %s ; metrics: %s" % written, file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
