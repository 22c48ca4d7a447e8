"""chip_smoke.py — the quickest proof that the training path still starts on
the chip.

One process, no child that needs the chip. With no arguments (one TPU chip):

  1. device facts — fails at once unless ``jax.devices()[0].platform`` is
     ``tpu``; prints versions, the compile-cache directory in force, whether
     the native helpers loaded, and the device profile that matched;
  2. the HIGGS configuration at published widths (28 features, 255 leaves,
     255 bins, binary) on ``make_higgs_like`` data from a fixed seed:
     ``lgb.train`` for 32 iterations with default ``tpu_*`` settings, which
     must land on the Mosaic persist path by itself (counters, kernel mode,
     every tree split, held-out AUC above a floor);
  3. the same configuration through the CLI in-process (``task=train`` from a
     TSV slice, ``task=predict`` on the host walk and with
     ``predict_device=tpu``), the two predictions equal.

With ``--four-chips`` it runs ONLY ``tree_learner=data`` over a mesh of all
four local devices and the serial one-chip run it is compared with.

Any failed check raises: no phase is wrapped in try/except. The last line of
stdout is ``{"ok": true, "device": {...}}`` as JAX reports the device.
"""
import glob
import json
import os
import sys
import tempfile
import time

import numpy as np

# Sizes and expectations are constants, not options: a CPU rehearsal imports
# this module and overrides them as attributes (and stubs check_device).
SEED = 7
ROWS = 10_500_000          # the reference's HIGGS row count; a multiple of 4
HOLDOUT_ROWS = 100_000     # held-out AUC + the predict comparison
CLI_ROWS = 100_000         # TSV slice the CLI leg trains on (> 65536)
ITERS = 32                 # two fused k=16 dispatches
PARAMS = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
          "metric": "none", "verbosity": -1, "tpu_telemetry": "timers"}
# A CPU rehearsal of this configuration and seed at 1M rows (the smallest
# allowed row count; f64 v1 grower) scored 0.797868 on the held-out rows, and
# more rows only raise it. The floor sits a margin below and far from 0.5.
AUC_FLOOR = 0.79
KERNEL_MODE = ("pallas", False)   # Mosaic kernels, not interpret, not XLA
# the predictor's own tests pin device-vs-host to this (tests/test_predict_tpu)
PREDICT_ATOL = 1e-12


def say(msg):
    print(msg, flush=True)


def check_device(want_count):
    """Fail before any work unless this process is on the TPU."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        sys.stderr.write("chip_smoke: needs a TPU, JAX found platform %r\n"
                         % dev.platform)
        sys.exit(2)
    if len(devs) < want_count:
        sys.stderr.write("chip_smoke: needs %d chips, JAX found %d\n"
                         % (want_count, len(devs)))
        sys.exit(2)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def cache_entries():
    import jax
    d = jax.config.jax_compilation_cache_dir
    return d, len(glob.glob(os.path.join(d, "*-cache"))) if d else 0


def print_facts(device):
    import importlib.metadata as md

    import jax
    from lightgbm_tpu import native
    from lightgbm_tpu.telemetry.devices import detect_profile
    say("device: platform=%s kind=%r count=%d" % (
        device["platform"], device["kind"], device["count"]))
    say("versions: jax=%s jaxlib=%s libtpu=%s numpy=%s" % (
        jax.__version__, md.version("jaxlib"), md.version("libtpu"),
        np.__version__))
    d, n = cache_entries()
    say("compile cache: dir=%s (JAX_COMPILATION_CACHE_DIR %s) entries=%d"
        % (d, "set" if os.environ.get("JAX_COMPILATION_CACHE_DIR")
           else "unset", n))
    loaded = {name: native.load(name) is not None
              for name in ("binrows", "treeshap")}
    say("native helpers loaded: %s" % loaded)
    profile = detect_profile()
    say("device profile: %s (matched device_kind %r)"
        % (profile.name, device["kind"]))
    if device["platform"] == "tpu":
        assert profile.name == "v5e", profile.name


def make_data():
    from lightgbm_tpu.data.synth import make_higgs_like
    t0 = time.time()
    X, y = make_higgs_like(ROWS + HOLDOUT_ROWS, seed=SEED)
    assert X.shape[1] == 28
    say("data: make_higgs_like rows=%d (+%d held out) features=%d seed=%d "
        "in %.1fs" % (ROWS, HOLDOUT_ROWS, X.shape[1], SEED, time.time() - t0))
    if ROWS != 10_500_000:
        say("data: ROWS CUT from the reference's 10,500,000 to %d" % ROWS)
    return X[:ROWS], y[:ROWS], X[ROWS:], y[ROWS:]


def auc(y, p):
    order = np.argsort(p, kind="mergesort")
    y = np.asarray(y)[order]
    n_pos = y.sum()
    n_neg = len(y) - n_pos
    ranks = np.arange(1, len(y) + 1)
    return float((ranks[y > 0].sum() - n_pos * (n_pos + 1) / 2)
                 / (n_pos * n_neg))


def counters_delta(before):
    from lightgbm_tpu.telemetry import events
    now = events.counts_snapshot()
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v - before.get(k, 0)}


def train_timed(params, ds, label):
    """lgb.train for ITERS iterations; a post-iteration callback blocks on
    the learner's device carry at the end of each fused k=16 batch, so the
    two walls below end in block_until_ready. Returns (booster, counters)."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.telemetry import events
    marks = {}

    def mark(env):
        if (env.iteration + 1) % 16 == 0:
            learner = env.model._booster.tree_learner
            jax.block_until_ready(
                getattr(learner, "_persist_carry", None))
            marks[env.iteration + 1] = time.time()

    before = events.counts_snapshot()
    t0 = time.time()
    bst = lgb.train(dict(params), ds, ITERS, verbose_eval=False,
                    callbacks=[mark])
    jax.block_until_ready(bst._booster.train_score.score_device(0))
    t_end = time.time()
    counts = counters_delta(before)
    say("%s: first call (payload build + H2D + compile + 16 iterations) "
        "%.2fs, second call (16 iterations, compiled) %.2fs, whole train "
        "%.2fs" % (label, marks[16] - t0, marks[ITERS] - marks[16],
                   t_end - t0))
    return bst, counts


def assert_fast_path(bst, counts, label):
    learner = bst._booster.tree_learner
    gc = learner.grow_config
    mode = learner._persist_kernel_effective()[:2]
    say("%s: counters persist_scan_trees=%d v1_grow_trees=%d "
        "iter_launches=%d; scan_impl=%s hist_impl=%s kernel_mode=%s"
        % (label, counts.get("tree_learner::persist_scan_trees", 0),
           counts.get("tree_learner::v1_grow_trees", 0),
           counts.get("tree_learner::iter_launches", 0),
           gc.scan_impl, gc.hist_impl, mode))
    assert counts.get("tree_learner::persist_scan_trees", 0) == ITERS, counts
    assert counts.get("tree_learner::v1_grow_trees", 0) == 0, counts
    assert counts.get("tree_learner::iter_launches", 0) == ITERS // 16, \
        counts
    assert gc.scan_impl == "pallas", gc.scan_impl
    assert gc.hist_impl == "pallas", gc.hist_impl
    assert mode == KERNEL_MODE, mode
    leaves = [t.num_leaves for t in bst._booster.models]
    say("%s: %d trees, leaves min=%d max=%d"
        % (label, len(leaves), min(leaves), max(leaves)))
    assert len(leaves) == ITERS and min(leaves) > 1, leaves


def held_out_auc(bst, Xh, yh, label):
    p = bst.predict(Xh)
    assert p.shape == (len(yh),) and np.all(np.isfinite(p)), p.shape
    a = auc(yh, p)
    say("%s: held-out AUC %.6f on %d rows (floor %.2f)"
        % (label, a, len(yh), AUC_FLOOR))
    assert a > AUC_FLOOR, a
    return a


def memory_line(label):
    import jax
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    say("%s: device memory bytes_in_use=%s peak_bytes_in_use=%s"
        % (label, [s.get("bytes_in_use") for s in stats],
           [s.get("peak_bytes_in_use") for s in stats]))
    return stats


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_train(X, y, Xh, yh):
    import lightgbm_tpu as lgb
    t0 = time.time()
    ds = lgb.Dataset(X, y, params=dict(PARAMS))
    ds.construct()
    say("binning: %.2fs (host)" % (time.time() - t0))
    bst, counts = train_timed(PARAMS, ds, "train")
    assert_fast_path(bst, counts, "train")
    held_out_auc(bst, Xh, yh, "train")
    memory_line("train")
    return bst


def phase_cli(X, y, Xh):
    """task=train from a TSV slice, then task=predict on the host walk and
    with predict_device=tpu — in-process, the CLI's own entry point."""
    from lightgbm_tpu.main import main as cli_main
    from lightgbm_tpu.telemetry import events
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        train_tsv = os.path.join(tmp, "train.tsv")
        test_tsv = os.path.join(tmp, "test.tsv")
        model = os.path.join(tmp, "model.txt")
        t0 = time.time()
        np.savetxt(train_tsv, np.column_stack([y[:CLI_ROWS], X[:CLI_ROWS]]),
                   fmt="%.9g", delimiter="\t")
        np.savetxt(test_tsv, np.column_stack([np.zeros(len(Xh)), Xh]),
                   fmt="%.9g", delimiter="\t")
        say("cli: wrote %d-row train and %d-row predict TSV in %.1fs"
            % (CLI_ROWS, len(Xh), time.time() - t0))
        common = ["%s=%s" % kv for kv in PARAMS.items()]
        before = events.counts_snapshot()
        t0 = time.time()
        rc = cli_main(["task=train", "data=" + train_tsv,
                       "num_iterations=%d" % ITERS,
                       "output_model=" + model] + common)
        assert rc == 0, rc
        counts = counters_delta(before)
        say("cli: task=train %.2fs persist_scan_trees=%d v1_grow_trees=%d"
            % (time.time() - t0,
               counts.get("tree_learner::persist_scan_trees", 0),
               counts.get("tree_learner::v1_grow_trees", 0)))
        assert counts.get("tree_learner::persist_scan_trees", 0) == ITERS, \
            counts
        assert counts.get("tree_learner::v1_grow_trees", 0) == 0, counts
        outs = {}
        for name, extra in (("host", []), ("tpu", ["predict_device=tpu"])):
            outs[name] = os.path.join(tmp, "pred_%s.txt" % name)
            before = events.counts_snapshot()
            rc = cli_main(["task=predict", "data=" + test_tsv,
                           "input_model=" + model,
                           "output_result=" + outs[name]] + common + extra)
            assert rc == 0, rc
            counts = counters_delta(before)
        fallbacks = {k: v for k, v in counts.items()
                     if k.startswith("predict::fallback")}
        p_host = np.loadtxt(outs["host"])
        p_tpu = np.loadtxt(outs["tpu"])
        diff = float(np.max(np.abs(p_host - p_tpu)))
        say("cli: task=predict host walk vs predict_device=tpu on %d rows: "
            "max |diff| %.3g (atol %.0e), tpu_batches=%d, fallbacks=%s"
            % (len(p_host), diff, PREDICT_ATOL,
               counts.get("predict::tpu_batches", 0), fallbacks))
        assert p_host.shape == p_tpu.shape == (len(Xh),)
        assert np.all(np.isfinite(p_tpu))
        assert counts.get("predict::tpu_batches", 0) > 0, counts
        assert not fallbacks, fallbacks
        np.testing.assert_allclose(p_tpu, p_host, rtol=0, atol=PREDICT_ATOL)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def tree_tuples(bst):
    """(structure, leaf values) exactly as tests/test_persist_sharded.py
    compares a sharded run with the serial one."""
    structure, values = [], []
    for t in bst.dump_model()["tree_info"]:
        def walk(node):
            if "split_feature" in node:
                structure.append((node["split_feature"],
                                  round(float(node["threshold"]), 9),
                                  node["internal_count"]))
                walk(node["left_child"])
                walk(node["right_child"])
            else:
                structure.append(("leaf", node["leaf_count"]))
                values.append(float(node["leaf_value"]))
        walk(t["tree_structure"])
    return structure, np.asarray(values)


def phase_four_chips(X, y, Xh, yh):
    import jax
    import lightgbm_tpu as lgb
    assert len(X) % 4 == 0, len(X)
    t0 = time.time()
    ds = lgb.Dataset(X, y, params=dict(PARAMS))
    ds.construct()
    say("binning: %.2fs (host)" % (time.time() - t0))

    bst4, counts = train_timed(dict(PARAMS, tree_learner="data"), ds,
                               "data x4")
    assert_fast_path(bst4, counts, "data x4")
    learner = bst4._booster.tree_learner
    assert learner.mesh.devices.size == 4, learner.mesh
    assert set(learner.mesh.devices.flat) == set(jax.local_devices())
    assets = [v for k, v in learner.dataset._persist_cache.items()
              if k[0] == "assets_sharded"][0]
    carry = learner._persist_carry
    score = learner.persist_finalize_scores()
    for name, arr in (("assets.pay0", assets.pay0), ("payload carry", carry),
                      ("scores", score)):
        shards = arr.addressable_shards
        say("data x4: %s shape=%s in %d addressable shards of %s on %s"
            % (name, arr.shape, len(shards), shards[0].data.shape,
               sorted(s.device.id for s in shards)))
        assert len(shards) == 4, (name, len(shards))
        assert len({s.device for s in shards}) == 4, name
    stats = memory_line("data x4")
    in_use = [s["bytes_in_use"] for s in stats]
    assert len(in_use) == 4 and max(in_use) < 4 * min(in_use), in_use
    auc4 = held_out_auc(bst4, Xh, yh, "data x4")

    bst1, counts1 = train_timed(PARAMS, ds, "serial x1")
    assert_fast_path(bst1, counts1, "serial x1")
    auc1 = held_out_auc(bst1, Xh, yh, "serial x1")

    s4, v4 = tree_tuples(bst4)
    s1, v1 = tree_tuples(bst1)
    if s4 == s1:
        np.testing.assert_allclose(v4, v1, rtol=1e-4, atol=1e-6)
        say("compare: all %d nodes equal in split feature, threshold and "
            "count; leaf values within rtol 1e-4" % len(s1))
    else:
        first = next(i for i, (a, b) in enumerate(zip(s4, s1)) if a != b)
        say("compare: trees differ from node %d of %d (pre-order over all "
            "trees): data x4 %s vs serial x1 %s — a near-tie under a "
            "different reduction order; falling back to held-out AUC"
            % (first, len(s1), s4[first], s1[first]))
        say("compare: held-out AUC data x4 %.6f vs serial x1 %.6f "
            "(|diff| %.2g, bound 1e-3)" % (auc4, auc1, abs(auc4 - auc1)))
        assert abs(auc4 - auc1) < 1e-3, (auc4, auc1)


def main(argv):
    four = argv == ["--four-chips"]
    if argv and not four:
        sys.stderr.write("usage: python chip_smoke.py [--four-chips]\n")
        return 2
    t_start = time.time()
    device = check_device(4 if four else 1)
    import lightgbm_tpu  # noqa: F401  (x64 + the compile-cache rule)
    print_facts(device)
    X, y, Xh, yh = make_data()
    if four:
        phase_four_chips(X, y, Xh, yh)
    else:
        phase_train(X, y, Xh, yh)
        phase_cli(X, y, Xh)
    d, n = cache_entries()
    say("compile cache: dir=%s entries=%d (at exit)" % (d, n))
    say("total %.1fs" % (time.time() - t_start))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
