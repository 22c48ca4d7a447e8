"""Configuration ``expo`` (benchmark/configs/expo.json) at a tiny size: the
source's 700 one-hot columns handed over as CSR, bundled by EFB, grown on the
persist path with the kernels the chip runs (Pallas interpreter), and judged
by the benchmark's own plain reference.

The XLA emulation the CPU normally takes scans the flat per-feature layout;
``scan_blocks`` and the 4-bit payload slots only run with the Pallas kernels,
so the kernel mode is patched here, in the test, as tests/test_persist_sharded
does. The fused driver forms a batch at 16 iterations, so 16 trees are grown.
"""
import json
import os
import sys

import numpy as np
import pytest

scipy_sparse = pytest.importorskip("scipy.sparse")

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
ROWS, BLOCK, TREES, LEAVES = 20000, 5000, 16, 31
SEED = 3200000077


def _bench_modules():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from drivers import train_sparse
    from harness import reference
    return train_sparse, reference


def _config():
    with open(os.path.join(BENCH, "configs", "expo.json")) as f:
        cfg = json.load(f)
    cfg.update(rows=ROWS, block_rows=BLOCK, heldout_rows=BLOCK)
    cfg["params"].update(num_leaves=LEAVES, min_sum_hessian_in_leaf=1.0,
                         tpu_persist_scan="force")
    return cfg


@pytest.fixture(scope="module")
def expo_run():
    """One 16-tree run on the CSR through the Pallas kernels (interpreter):
    (cfg, rows, X, y, booster, counters of that run)."""
    from lightgbm_tpu.treelearner.serial import SerialTreeLearner
    train_sparse, _ = _bench_modules()
    cfg = _config()
    rows, X, y, _, _, _ = train_sparse.inputs(cfg, SEED)
    assert scipy_sparse.issparse(X) and X.shape == (ROWS, 700)
    assert X.nnz == 8 * ROWS
    before = telemetry.counts_snapshot()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(SerialTreeLearner, "_persist_kernel_mode",
                   staticmethod(lambda: ("pallas", True)))
        bst = lgb.train(dict(cfg["params"]), lgb.Dataset(X, y), TREES,
                        verbose_eval=False)
    # the last batch's host trees are built when somebody asks for them
    assert bst.model_to_string(num_iteration=-1).count("Tree=") == TREES
    after = telemetry.counts_snapshot()
    grew = {k: v - before.get(k, 0.0) for k, v in after.items()
            if k.startswith("tree_learner::")}
    grew.update({k: v for k, v in after.items() if k.startswith("io::")})
    return cfg, rows, X, y, bst, grew


def test_config_file_is_the_sources_shape():
    cfg = _config()
    train_sparse, _ = _bench_modules()
    gen = train_sparse.SparseRows(cfg, 1).gen
    assert gen.FEATURES == 700 == cfg["published"]["features"]
    assert sum(gen.CARDS) + len(gen.NUMERIC) == 700
    assert gen.WORK_FEATURES == 10
    assert "enable_bundle" not in cfg["params"]
    assert not [k for k in cfg["params"] if k.startswith("tpu_")
                and k != "tpu_persist_scan"]     # the one this file adds
    assert cfg["reduced"] == ["num_trees", "rows"]
    assert json.load(open(os.path.join(BENCH, "configs", "expo.json")))[
        "rows"] < 1 << 24


def test_reference_accepts_the_bundled_persist_trees(expo_run):
    """Counts equal, leaf values and split gains inside the configuration's
    own limits: the plain reference walks the dense expansion of the same
    compact blocks and recomputes every leaf's rows, G and H."""
    train_sparse, reference = _bench_modules()
    cfg, rows, _, y, bst, _ = expo_run
    trees = reference.parse_model(bst.model_to_string(num_iteration=-1))
    assert len(trees) == TREES
    assert min(t["num_leaves"] for t in trees) > 1
    init = reference.binary_init(float(np.mean(y, dtype=np.float64)))
    # the reference reads the rows as the program's bundles hold them
    rows.note_bundles(bst._booster.tree_learner.dataset)
    numbers, _ = train_sparse.check(rows, trees, cfg, init)
    assert numbers["count_mismatch"] == 0, numbers
    for name, limit in cfg["limits"].items():
        assert numbers[name] <= limit, numbers


def test_counters_say_which_mechanisms_grew_the_trees(expo_run):
    cfg, _, _, _, bst, grew = expo_run
    assert grew.get("tree_learner::persist_scan_trees") == TREES, grew
    assert grew.get("tree_learner::blockscan_trees") == TREES, grew
    # since PR 35 the Pallas path builds the smaller child's histogram
    # after the pass (seg_hist) at every group count: on the chip that
    # route read 2-3% faster than the histogram inside the pass (PERF.md,
    # section 6), so no tree of this run is an in-pass tree
    assert grew.get("tree_learner::inpass_hist_trees", 0) == 0, grew
    assert grew.get("tree_learner::v1_grow_trees", 0) == 0, grew
    # what a smaller-child histogram has to cost: a split's smaller child
    # holds at most half its parent's rows; both sums come from the host
    # trees' own counts and are kept with telemetry off, as this run has it
    assert lgb.Config(dict(cfg["params"])).tpu_telemetry == "off"
    assert 0 < grew["tree_learner::split_small_rows"] <= (
        grew["tree_learner::split_parent_rows"] / 2), grew
    assert grew["tree_learner::split_parent_rows"] >= ROWS * TREES, grew
    inner = bst._booster.tree_learner.dataset
    # at 20k rows the rarest levels drop out of the sample, so the count is
    # the Dataset's own and not pinned to the 16 of the full size
    assert grew["io::efb_groups"] == len(inner.groups) <= 20, grew
    assert grew["io::efb_bundled_features"] == sum(
        len(g) for g in inner.groups if len(g) > 1)
    names = {e["name"] for e in telemetry.ring_snapshot()}
    assert {"io::FindGroups(EFB)", "io::PushSparse(binning)",
            "ops::BuildBlockScanMeta"} <= names


def test_csr_and_dense_input_write_the_same_model(expo_run):
    cfg, _, X, y, _, _ = expo_run
    params = dict(cfg["params"])
    texts = []
    for data in (X, X.toarray()):
        bst = lgb.train(dict(params), lgb.Dataset(data, y), TREES,
                        verbose_eval=False)
        texts.append(bst.model_to_string(num_iteration=-1)
                     .split("\nparameters:")[0])
    assert texts[0] == texts[1]
    assert texts[0].count("Tree=") == TREES
