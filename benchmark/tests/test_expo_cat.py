"""Rehearsal of ``expo_cat.train_steady`` on the CPU at a tiny size: the
configuration's shape, the generator (expo_like's rows with the six categories
as level ids), the guard that refuses a program without the persist path's
categorical arm, the reference's walk through a categorical split and the
planted fault it must catch, and, through the harness's own entry point, the
result line of a ``--trace 1`` run (reduced from the recorded trace) on the
forced persist path, where every tree's eval finds categorical splits."""
import copy
import json
import os

import numpy as np
import pytest

from conftest import HERE

CELL = "expo_cat.train_steady"
TINY = {"rows": 24000, "block_rows": 8000, "heldout_rows": 10000}
RECORDED = os.path.join(HERE, "tests", "data", "higgs_launch_head.xplane.pb")
NEW = ("cattree_pct", "cat_split_pct")


def tiny_expo_cat():
    with open(os.path.join(HERE, "configs", "expo_cat.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg["params"].update(num_leaves=15, min_sum_hessian_in_leaf=1.0,
                         tpu_persist_scan="force")
    return cfg


@pytest.fixture
def expo_cat(monkeypatch):
    """The harness patched for a CPU run of the cell: no chip look, the tiny
    configuration, the kernel mode the CPU has, and the tree and split
    counters of the run record started from 0 (they are the process's)."""
    import run as bench_run
    from harness import device
    from lightgbm_tpu.telemetry import events as telemetry
    peaks = device.load_peaks()["TPU v5 lite"]
    monkeypatch.setattr(
        device, "check_device",
        lambda chips: ({"platform": "cpu", "kind": "rehearsal",
                        "count": chips}, peaks))
    real = bench_run.load_json

    def load(*parts):
        d = real(*parts)
        if parts[-1].endswith("configs/expo_cat.json"):
            d = tiny_expo_cat()
        if parts[-1] == "train_steady_cat.json":
            d["kernel_mode"] = ["xla", True]
        return d
    monkeypatch.setattr(bench_run, "load_json", load)
    telemetry.clear_counts_prefix(("tree_learner::persist_scan_trees",
                                   "tree_learner::cat_persist_trees",
                                   "tree_learner::splits",
                                   "tree_learner::cat_splits"))
    return bench_run


def test_configuration_is_the_sources_shape():
    from generators import expo_cat_like, expo_like
    with open(os.path.join(HERE, "configs", "expo_cat.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    params = cfg["params"]
    assert expo_cat_like.CARDS == expo_like.CARDS == (12, 31, 7, 22, 313,
                                                      313)
    assert expo_cat_like.FEATURES == 8 == cfg["published"]["columns"]
    assert tuple(params["categorical_feature"]) == expo_cat_like.CATEGORICAL
    assert expo_cat_like.WORK_FEATURES == expo_like.WORK_FEATURES == 10
    assert (params["num_leaves"], params["max_bin"]) == (255, 255)
    assert (params["learning_rate"], params["min_data_in_leaf"],
            params["min_sum_hessian_in_leaf"]) == (0.1, 0, 100.0)
    # v2.3.2's categorical defaults, written out: every column sorted
    assert (params["max_cat_to_onehot"], params["max_cat_threshold"],
            params["cat_smooth"], params["cat_l2"],
            params["min_data_per_group"]) == (4, 32, 10.0, 10.0, 100)
    assert min(expo_cat_like.CARDS) > params["max_cat_to_onehot"]
    assert not [k for k in params if k.startswith("tpu_")]
    assert cfg["rows"] == 36000000 > 1 << 24
    assert cfg["heldout_rows"] == cfg["published"]["test_rows"] == 1000000
    assert cfg["reduced"] == ["rows", "num_trees", "enable_bundle"]
    assert len(cfg["source"]) <= 200
    entry = [c for c in bench["configs"] if c["name"] == "expo_cat"][0]
    assert (entry["source"], entry["reduced"], entry["file"]) == (
        cfg["source"], cfg["reduced"], "benchmark/configs/expo_cat.json")
    cell = [c for c in bench["workloads"] if c["name"] == CELL][0]
    assert (cell["chips"], cell["traffic"]) == (1, "train_steady_cat")
    for name in NEW:
        metric = [m for m in bench["per_layer"] if m["name"] == name][0]
        assert metric["workloads"] == [CELL]
    for name in cfg["limits"]:
        assert name in cfg["limits_why"], name
    assert cfg["limits"]["count_mismatch"] == 0


def test_generator_is_expo_like_with_level_ids():
    import jax
    from drivers import train
    from generators import expo_cat_like as gen
    from generators import expo_like
    key = train.seed_key(4400000011)
    x, y = (np.asarray(a) for a in jax.jit(
        gen.make_block, static_argnums=(2,))(key, 3, 20000))
    cat, num, y0 = (np.asarray(a) for a in jax.jit(
        expo_like.make_block, static_argnums=(2,))(key, 3, 20000))
    assert x.shape == (20000, 8) and x.dtype == np.float32
    np.testing.assert_array_equal(x[:, :6], cat.astype(np.float32))
    np.testing.assert_array_equal(x[:, 6:], num)
    np.testing.assert_array_equal(y, y0)
    for j, card in enumerate(gen.CARDS):
        assert x[:, j].min() == 0 and x[:, j].max() < card
        assert (x[:, j] == np.floor(x[:, j])).all()
    # Zipf(1): level 0 the most frequent of every column
    assert all(np.argmax(np.bincount(x[:, j].astype(int))) == 0
               for j in range(6))


def test_the_guard_refuses_a_program_without_the_categorical_arm(
        monkeypatch):
    from generators import expo_cat_like
    from lightgbm_tpu.ops import pallas_grow
    expo_cat_like._refuse_a_program_without_categorical_persist()
    monkeypatch.delattr(pallas_grow, "S_CAT")
    with pytest.raises(SystemExit) as e:
        expo_cat_like._refuse_a_program_without_categorical_persist()
    assert e.value.code == 2


def test_reference_walks_a_categorical_split():
    """A stump on a categorical column: the left set's levels go left,
    every other level, a negative code and a NaN (the node's missing type is
    NaN) go right."""
    import jax.numpy as jnp
    from harness import reference_cat
    reference_cat.configure(0.0, 10.0, ())
    tree = {"num_leaves": 2, "num_cat": 1, "split_feature": np.array([0]),
            "threshold": np.array([0.0]), "decision_type": np.array([9]),
            "left_child": np.array([-1]), "right_child": np.array([-2]),
            "leaf_value": np.array([0.5, -0.5]),
            "cat_boundaries": np.array([0, 11]),
            "cat_threshold": np.zeros(11, np.uint32)}
    for level in (1, 40, 312):                       # 312: the 10th word
        tree["cat_threshold"][level // 32] |= np.uint32(1 << (level % 32))
    x = np.array([[1], [40], [312], [0], [2], [39], [-3], [np.nan], [313],
                  [1000]], np.float32)
    sums = reference_cat.follow(
        [(jnp.asarray(x), jnp.zeros(len(x)), jnp.ones(len(x), bool))],
        [tree], 0.0, "binary", 1, 2)
    np.testing.assert_array_equal(sums[0, :2, 0], [3, 7])
    # cat_l2 in the outputs and the gain of a many-vs-many split
    exp = reference_cat.expected(tree, np.array([[3, -3.0, 2.0],
                                                 [7, 1.0, 4.0]]), 1.0, 0.0)
    np.testing.assert_allclose(exp["leaf_value"], [3.0 / 12.0, -1.0 / 14.0])
    np.testing.assert_allclose(exp["split_gain"],
                               [9.0 / 12.0 + 1.0 / 14.0 - 4.0 / 6.0])
    # the planted fault takes the lowest level out of the left set
    moved = reference_cat.moved_category([tree, copy.deepcopy(tree)])
    assert moved[1]["cat_threshold"][0] == 0
    assert (moved[1]["cat_threshold"][1:] == tree["cat_threshold"][1:]).all()
    assert (moved[0]["cat_threshold"] == tree["cat_threshold"]).all()


def test_traced_run_carries_the_new_metrics(expo_cat, monkeypatch, capsys):
    from harness import xtrace
    monkeypatch.setattr(xtrace, "find_xplane", lambda logdir: RECORDED)
    rc = expo_cat.main(["--workload", CELL, "--seed", "4400000043",
                        "--seconds", "0.5", "--trace", "1"])
    out = capsys.readouterr().out
    result = json.loads(out.strip().splitlines()[-1])
    assert rc == 0
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = {m["name"] for m in bench["per_layer"]
             if "workloads" not in m or CELL in m["workloads"]}
    assert set(NEW) <= names == set(result["metrics"])
    # every persist tree of the run over the categorical layout, and most
    # of its splits categorical
    assert result["metrics"]["cattree_pct"]["value"] == 100.0
    assert 50.0 < result["metrics"]["cat_split_pct"]["value"] <= 100.0
    for name in ("train_step_mfu", "hist_roofline", "partition_roofline",
                 "grow_roofline", "dense_binning_s", "grower_build_s"):
        assert result["metrics"][name]["value"] > 0, name
    assert "persist carry live at every mark=True" in out
    assert result["failed"] == 0 and result["attempted"] >= 16
    assert result["correct"] is True
    assert result["checks"]["count_mismatch"]["value"] == 0


def test_untraced_run_and_the_faults(expo_cat, capsys):
    """The end-to-end metrics of a run, then the comparison on the run's
    own trees: the planted faults of harness/faults.py and the moved
    category each fail a limit, and the bfloat16 control reads wider than
    the program."""
    import argparse
    import time
    from drivers import train, train_cat
    from harness import device, faults, reference_cat
    rc = expo_cat.main(["--workload", CELL, "--seed", "4400000044",
                        "--seconds", "0.5", "--trace", "0"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True
    assert set(result["metrics"]) == {"train_throughput", "peak_hbm",
                                      "heldout_score", "setup_s"}
    assert 0.5 < result["metrics"]["heldout_score"]["value"] <= 1.0

    _, cell, cfg, traffic = expo_cat.load_cell(CELL)
    args = argparse.Namespace(seed=4400000045, seconds=0.5, trace=0,
                              keep_trace=False, workdir=os.path.join(
                                  os.path.dirname(HERE), ".cache",
                                  "benchmark"))
    with train_cat.cat_inputs(cfg):
        res = train.run(cell, cfg, traffic, args,
                        *device.check_device(1), time.time())
        rows, trees, init = res["rows"], res["trees"], res["init"]
        limits = cfg["limits"]
        planted = dict(faults.MODEL,
                       moved_category=reference_cat.moved_category)
        for name, plant in planted.items():
            numbers = train.check(rows, plant(trees), cfg, init)[0]
            assert any(numbers[k] > limits[k] for k in limits), (name,
                                                                numbers)
        sound = train.check(rows, trees, cfg, init)[0]
        low = train.control(rows, trees, cfg, init)[0]
    assert sound["count_mismatch"] == 0
    assert low["median_leaf_gap"] > sound["median_leaf_gap"]
