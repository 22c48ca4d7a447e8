"""The split scan is handed the children's histogram rows (ops/
grow_persist.py: eval_batch, eval_batch_wide), so the trees must be the
ones grown when it read them back out of the per-leaf planes: the v1
grower's, bit for bit, in the widened XLA mode on bundled data and under
the data-parallel and voting learners of the 8-device CPU mesh; and under
the Mosaic pair scan (interpreter) the voting learner's full vote still
grows the data-parallel learner's trees. Small and seeded; the structural
side (no gather of the planes is left) is tests/test_wide_dense.py's.
"""
import json

import numpy as np
import pytest

import jax

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.data.synth import make_expo_like
from lightgbm_tpu.treelearner.serial import SerialTreeLearner

N, F, ROUNDS = 2048, 12, 16      # 8 shards x 256 rows; one fused batch
BASE = {"objective": "binary", "verbosity": -1, "min_data_in_leaf": 10,
        "max_bin": 63, "learning_rate": 0.2}


def _data():
    rng = np.random.default_rng(37)
    X = rng.normal(size=(N, F))
    y = (X[:, 0] - 0.7 * X[:, 2] + 0.4 * X[:, 4] * X[:, 7]
         + rng.normal(size=N) * 0.5 > 0).astype(float)
    return X, y


def _train(X, y, **params):
    bst = lgb.train({**BASE, **params}, lgb.Dataset(X, y), ROUNDS,
                    verbose_eval=False)
    if params.get("tpu_persist_scan") == "force":
        learner = bst._booster.tree_learner
        assert getattr(learner, "_persist_carry", None) is not None, params
    return bst


def _trees(bst):
    """The model text but for the parameter block, which bakes the
    learner and tpu_persist_scan."""
    return bst.model_to_string(num_iteration=-1).split("\nparameters:")[0]


def test_bundled_persist_trees_are_the_v1_growers():
    X, y = make_expo_like(n_rows=4096, seed=3)
    persist = _train(X, y, num_leaves=15, tpu_persist_scan="force")
    inner = persist._booster.tree_learner.dataset
    assert len(inner.groups) < inner.num_features     # EFB bundled it
    assert persist._booster.tree_learner._persist_gr.wide
    v1 = _train(X, y, num_leaves=15, tpu_persist_scan="off")
    assert _trees(persist).count("Tree=") == ROUNDS
    assert _trees(persist) == _trees(v1)


@pytest.fixture(scope="module")
def v1_trees():
    X, y = _data()
    return _trees(_train(X, y, num_leaves=7, tpu_persist_scan="off"))


@pytest.mark.parametrize("learner,extra", [
    ("serial", {}), ("data", {}), ("voting", {"top_k": F}),
], ids=["serial", "data", "voting_full_vote"])
def test_sharded_persist_trees_are_the_v1_growers(v1_trees, learner, extra):
    """Widened mode: f64 planes and the v1 f64 find, so the psum of the
    shards' planes and the full vote's window exchange give the serial
    histograms to the bit and the trees are v1's."""
    assert len(jax.devices()) >= 8, "conftest provides 8 virtual devices"
    X, y = _data()
    before = telemetry.counts_snapshot()
    bst = _train(X, y, num_leaves=7, tpu_persist_scan="force",
                 tree_learner=learner, **extra)
    gr = bst._booster.tree_learner._persist_gr
    assert gr.voting == (learner == "voting")
    assert _trees(bst) == v1_trees
    grew = (telemetry.counts_snapshot()["tree_learner::persist_scan_trees"]
            - before.get("tree_learner::persist_scan_trees", 0.0))
    assert grew == ROUNDS


def test_a_small_vote_still_learns():
    X, y = _data()
    bst = _train(X, y, num_leaves=7, tpu_persist_scan="force",
                 tree_learner="voting", top_k=2)
    gr = bst._booster.tree_learner._persist_gr
    assert gr.voting and 0.0 < gr.reduced_feature_frac < 1.0
    assert ((bst.predict(X) > 0.5) == y).mean() > 0.8


def _structure(bst):
    model = bst.dump_model()
    if isinstance(model, str):
        model = json.loads(model)
    shape, values = [], []

    def walk(node):
        if "split_feature" in node:
            shape.append((node["split_feature"],
                          round(float(node["threshold"]), 9),
                          node["internal_count"]))
            walk(node["left_child"])
            walk(node["right_child"])
        else:
            shape.append(("leaf", node["leaf_count"]))
            values.append(float(node["leaf_value"]))
    for tree in model["tree_info"]:
        walk(tree["tree_structure"])
    return shape, np.asarray(values)


def test_the_mosaic_scan_under_a_full_vote_grows_the_data_parallel_trees():
    """eval_batch's voting branch (scan_pair on the local rows, the top-k
    index allgather, the winners' window exchange, scan_pair again) on
    rows it is handed: with 2 * top_k >= F every feature wins, so the
    trees are the data-parallel learner's (f32 sums in another order:
    values to f32 rounding)."""
    X, y = _data()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(SerialTreeLearner, "_persist_kernel_mode",
                   staticmethod(lambda: ("pallas", True)))
        data = _train(X, y, num_leaves=7, tpu_persist_scan="force",
                      tree_learner="data")
        vote = _train(X, y, num_leaves=7, tpu_persist_scan="force",
                      tree_learner="voting", top_k=F)
    gr = vote._booster.tree_learner._persist_gr
    assert gr.voting and not gr.inner.wide
    shape_d, values_d = _structure(data)
    shape_v, values_v = _structure(vote)
    assert shape_d == shape_v
    np.testing.assert_allclose(values_d, values_v, rtol=2e-4, atol=2e-5)
