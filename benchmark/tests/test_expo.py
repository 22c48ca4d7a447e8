"""Rehearsal of ``expo.train_steady`` on the CPU at a tiny size, through the
harness's own entry point: the result line of a ``--trace 0`` and of a
``--trace 1`` run (the latter reduced from the recorded trace), the control
and every planted fault failing where they must, and the named reader on a
hand-made run record."""
import json
import os

import pytest

from conftest import HERE

CELL = "expo.train_steady"
TINY = {"rows": 60000, "block_rows": 20000, "heldout_rows": 30000}
RECORDED = os.path.join(HERE, "tests", "data", "higgs_launch_head.xplane.pb")
# Limits of this file's tiny CPU runs (float64 program, 15 leaves), as
# test_correct.py sets them for its own
CPU_LIMITS = {"count_mismatch": 0, "leaf_value_gap": 1e-4,
              "split_gain_gap": 1e-4, "median_leaf_gap": 1e-5}


def tiny_expo():
    with open(os.path.join(HERE, "configs", "expo.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg["params"].update(num_leaves=15, min_sum_hessian_in_leaf=1.0,
                         tpu_persist_scan="force")
    return cfg


@pytest.fixture
def expo(monkeypatch):
    """The harness patched for a CPU run of the cell: no chip look, the tiny
    configuration, and the kernel mode the CPU has."""
    import run as bench_run
    from harness import device

    peaks = device.load_peaks()["TPU v5 lite"]
    monkeypatch.setattr(
        device, "check_device",
        lambda chips: ({"platform": "cpu", "kind": "rehearsal",
                        "count": chips}, peaks))
    real = bench_run.load_json

    def load(*parts):
        d = real(*parts)
        if parts[-1].endswith("configs/expo.json"):
            d = tiny_expo()
        if parts[-1] == "train_steady_sparse.json":
            d["kernel_mode"] = ["xla", True]
        return d
    monkeypatch.setattr(bench_run, "load_json", load)
    return bench_run


def last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def judged(result):
    return all(result["checks"][k]["value"] <= CPU_LIMITS[k]
               for k in CPU_LIMITS)


def test_sound_run_is_correct_and_never_dense(expo, capsys, monkeypatch):
    import numpy as np
    from drivers import train
    seen = {}
    real_call = train.train_call

    def spy(lgb, params, X, y, *rest):
        seen["X"] = type(X).__name__, X.shape, X.nnz
        return real_call(lgb, params, X, y, *rest)
    monkeypatch.setattr(train, "train_call", spy)
    real_empty = np.empty
    monkeypatch.setattr(
        np, "empty", lambda shape, *a, **k: pytest.fail("dense rows")
        if np.shape(shape) == (2,) and shape[1] == 700
        else real_empty(shape, *a, **k))
    rc = expo.main(["--workload", CELL, "--seed", "3200000019",
                    "--seconds", "0.5", "--trace", "0"])
    result = last_line(capsys)
    assert rc == 0
    assert seen["X"] == ("csr_matrix", (60000, 700), 8 * 60000)
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert set(result["metrics"]) == {"train_throughput", "peak_hbm",
                                      "heldout_score", "setup_s"}
    assert result["attempted"] >= 16 and result["failed"] == 0
    assert result["metrics"]["heldout_score"]["value"] > 0.6
    assert judged(result), result["checks"]
    lost = result["checks"]["bundle_lost_share"]
    assert 0 <= lost["value"] <= lost["limit"] == 0.0035, lost
    assert result["correct"] is True
    for seam in (train.inputs, train.learner_of, train.check):
        assert seam.__module__ == "drivers.train"         # the seams are back


def test_traced_run_reports_every_metric_of_the_cell(expo, monkeypatch,
                                                     capsys):
    from harness import xtrace
    monkeypatch.setattr(xtrace, "find_xplane", lambda logdir: RECORDED)
    rc = expo.main(["--workload", CELL, "--seed", "43", "--seconds", "0.5",
                    "--trace", "1"])
    out = capsys.readouterr().out
    result = json.loads(out.strip().splitlines()[-1])
    assert rc == 0
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = {m["name"] for m in bench["per_layer"]
             if "workloads" not in m or CELL in m["workloads"]}
    assert names == set(result["metrics"])
    # the emulation the CPU runs scans the flat layout: no scan_blocks trees,
    # and the share reads a number, not nothing (0 in a process of its own;
    # the counters are the process's, so other tests' trees count too)
    assert 0.0 <= result["metrics"]["blockscan_tree_pct"]["value"] < 100.0
    for name in ("grow_roofline", "scan_roofline", "sparse_binning_s",
                 "efb_group_s", "train_step_mfu"):
        assert result["metrics"][name]["value"] > 0, name
    # (the path shares are pinned in tests/test_expo_config.py, not here; the
    # nine run-record metrics list higgs.train_steady alone, PERF.md section 7)
    assert result["failed"] == 0
    assert "work model, scan and grow" in out
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_control_and_model_faults_are_not_correct():
    import lightgbm_tpu as lgb
    from drivers import train, train_sparse
    from harness import faults, reference
    cfg = tiny_expo()
    rows, X, y, _, _, group = train_sparse.inputs(cfg, 17)
    bst = train.train_call(lgb, cfg["params"], X, y, group, 16, [], {})
    trees = reference.parse_model(bst.model_to_string(num_iteration=-1))
    init = train.init_score(cfg["params"], y)
    sound = train_sparse.check(rows, trees, cfg, init)[0]
    low = train_sparse.control(rows, trees, cfg, init)[0]
    assert all(sound[k] <= CPU_LIMITS[k] for k in CPU_LIMITS), sound
    assert low["count_mismatch"] == 0
    assert low["median_leaf_gap"] > 3 * CPU_LIMITS["median_leaf_gap"], low
    for name, plant in faults.MODEL.items():
        bad = train_sparse.check(rows, plant(trees), cfg, init)[0]
        assert not all(bad[k] <= CPU_LIMITS[k] for k in CPU_LIMITS), \
            (name, bad)


def test_reference_reads_the_rows_as_the_bundles_hold_them():
    """A seed on which a few rows have two columns of one bundle set: on the
    raw values the reference puts them in other leaves than the model; told
    the program's bundles, it agrees to the last row, and it masks exactly
    the rows a walk over the Dataset's own groups finds."""
    import numpy as np
    import lightgbm_tpu as lgb
    from drivers import train, train_sparse
    from harness import reference
    cfg = tiny_expo()
    cfg["params"]["num_leaves"] = 63    # deep enough to split on rare levels
    rows, X, y, _, _, group = train_sparse.inputs(cfg, 14)
    bst = train.train_call(lgb, cfg["params"], X, y, group, 48, [], {})
    trees = reference.parse_model(bst.model_to_string(num_iteration=-1))
    init = train.init_score(cfg["params"], y)
    raw = train_sparse.check(rows, trees, cfg, init)[0]
    assert raw["count_mismatch"] > 0 and rows.lost_rows == 0
    ds = bst._booster.tree_learner.dataset
    rows.note_bundles(ds)
    held = train_sparse.check(rows, trees, cfg, init)[0]
    assert all(held[k] <= CPU_LIMITS[k] for k in CPU_LIMITS), held
    dense, lost = X.toarray() != 0, np.zeros(len(y), bool)
    for feats in ds.groups:
        cols = [ds.used_features[i] for i in feats]
        lost |= dense[:, cols].sum(axis=1) > 1
    assert rows.lost_rows == lost.sum() > 0
    assert held["bundle_lost_share"] == lost.sum() / len(y)
    # a table that is not what the binned rows hold does not pass: told the
    # bundles' columns in the opposite order, the reference masks the value
    # the program kept
    dataset, ident, place = rows.bundle_of
    rows.bundle_of = (dataset, ident, -place)
    assert train_sparse.check(rows, trees, cfg, init)[0]["count_mismatch"] > 0


def test_a_bundling_that_gives_up_too_many_rows_is_not_correct(
        expo, monkeypatch, capsys):
    """The program's conflict budget raised underneath the harness: the
    model still follows its own bundles to the last row, and the run fails
    by the share of the rows that lost a value."""
    from drivers import train
    real_call = train.train_call
    monkeypatch.setattr(
        train, "train_call",
        lambda lgb, params, *rest: real_call(
            lgb, dict(params, max_conflict_rate=0.01), *rest))
    expo.main(["--workload", CELL, "--seed", "3200000019", "--seconds",
               "0.5", "--trace", "0"])
    result = last_line(capsys)
    assert judged(result), result["checks"]
    lost = result["checks"]["bundle_lost_share"]
    assert lost["value"] > 2 * lost["limit"], lost
    assert result["correct"] is False


def test_half_batch_under_the_harness_is_not_correct(expo, monkeypatch,
                                                     capsys):
    from drivers import train
    from harness import faults
    real_call = train.train_call
    monkeypatch.setattr(
        train, "train_call",
        lambda lgb, params, X, y, group, *rest: real_call(
            lgb, params, *faults.FEED["half_batch"](X, y, group), *rest))
    expo.main(["--workload", CELL, "--seed", "23", "--seconds", "0.5",
               "--trace", "0"])
    result = last_line(capsys)
    assert not judged(result), result["checks"]


def test_named_reader_on_a_hand_made_record(monkeypatch):
    from lightgbm_tpu import telemetry
    from readers import program_named
    ring = [
        {"name": "io::FindGroups(EFB)", "self": 9.0, "dur": 9.0, "ts": 1.0,
         "train": 0},                              # an older job's Dataset
        {"name": "engine::train", "self": 0.1, "dur": 5.0, "ts": 11.0,
         "train": 1},
        {"name": "io::PushSparse(binning)", "self": 1.5, "dur": 1.5,
         "ts": 21.0, "train": 0},
        {"name": "io::FindBinAndGroup", "self": 0.25, "dur": 1.0,
         "ts": 20.0, "train": 0},
        {"name": "io::FindGroups(EFB)", "self": 0.75, "dur": 0.75,
         "ts": 20.1, "train": 0},
        {"name": "engine::train", "self": 0.1, "dur": 5.0, "ts": 30.0,
         "train": 2},
        {"name": "ops::BuildBlockScanMeta", "self": 0.125, "dur": 0.125,
         "ts": 31.0, "train": 2},
        {"name": "ops::BuildBlockScanMeta", "self": 4.0, "dur": 4.0,
         "ts": 33.0, "train": 2, "launch": 1},
    ]
    counts = {"tree_learner::persist_scan_trees": 64.0,
              "tree_learner::blockscan_trees": 48.0}
    monkeypatch.setattr(telemetry, "ring_snapshot", lambda: list(ring))
    monkeypatch.setattr(telemetry, "counts_snapshot", lambda: dict(counts))
    read = program_named.read
    assert read({"spans": ["io::PushSparse(binning)"]}, {}) == 1.5
    # a span of no train counts from the end of the train before
    assert read({"spans": ["io::FindGroups(EFB)"]}, {}) == 0.75
    # set-up only: launch 1 and later is the window
    assert read({"spans": ["ops::BuildBlockScanMeta"]}, {}) == 0.125
    assert read({"spans": ["io::NoSuchSpan"]}, {}) is None
    assert read({"counter": "tree_learner::blockscan_trees",
                 "of": "tree_learner::persist_scan_trees"}, {}) == 75.0
    # a run off the path the counter marks reads 0; a program without the
    # counter it is a share of reads nothing
    assert read({"counter": "tree_learner::no_such",
                 "of": "tree_learner::persist_scan_trees"}, {}) == 0.0
    assert read({"counter": "tree_learner::blockscan_trees",
                 "of": "tree_learner::no_such"}, {}) is None
    monkeypatch.setattr(telemetry, "ring_snapshot", lambda: [])
    assert read({"spans": ["io::PushSparse(binning)"]}, {}) is None
