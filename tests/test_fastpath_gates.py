"""What the device fast path admits and what it refuses, one case a line of
ROADMAP's "What the code says about coverage".

Static: a 200-row Dataset, a learner built as on the chip (`jax.default_backend`
patched to "tpu", the row floor of the partitioned growers lowered), no
training. The gates are `treelearner/serial.py:resolve_scan_impl` (the fused
Pallas scan), `SerialTreeLearner.can_persist_scan` and, under a mesh,
`parallel/learners.py:_persist_axis_ok` / `_persist_obj_ok`. A configuration
that falls to the v1 grower is otherwise seen only in a chip run's counters
(`tree_learner::v1_grow_trees`); a `model_config` PR that widens the fast
path turns a refusal of this table into an admission.
"""
import json

import numpy as np
import pytest

import jax

import lightgbm_tpu as lgb
from lightgbm_tpu.basic import LightGBMError
from lightgbm_tpu.data.dataset import BinnedDataset
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.treelearner import serial
from lightgbm_tpu.treelearner.serial import create_tree_learner

ROWS, FEATURES = 200, 6


def _dense(rng, features=FEATURES, rows=ROWS):
    return rng.normal(size=(rows, features))


def _one_hot(rng):
    """Two dense columns and two one-hot blocks: EFB bundles the blocks."""
    X = np.zeros((ROWS, 2 + 5 + 7))
    X[:, :2] = rng.normal(size=(ROWS, 2))
    X[np.arange(ROWS), 2 + rng.integers(0, 5, ROWS)] = 1.0
    X[np.arange(ROWS), 7 + rng.integers(0, 7, ROWS)] = 1.0
    return X


def _forced_splits(tmp_path):
    path = tmp_path / "forced.json"
    path.write_text(json.dumps({"feature": 0, "threshold": 0.0}))
    return {"forcedsplits_filename": str(path)}


# property -> (params, data options, persist admitted?, Pallas scan?)
# data options: bundled, weight, group, categorical, features
GATES = {
    "dense_binary": ({}, {}, True, True),
    "efb_bundled": ({}, {"bundled": True}, True, True),
    "weighted": ({}, {"weight": True}, True, True),
    "lambdarank_one_device": ({"objective": "lambdarank"}, {"group": True},
                              True, True),
    "multiclass_one_device": ({"objective": "multiclass", "num_class": 3},
                              {}, True, True),
    "tpu_use_dp": ({"tpu_use_dp": True}, {}, False, False),
    "monotone_constraints": ({"monotone_constraints": [1, 0, 0, 0, 0, -1]},
                             {}, False, False),
    "lambda_l1": ({"lambda_l1": 0.5}, {}, False, False),
    "max_delta_step": ({"max_delta_step": 1.0}, {}, False, False),
    "extra_trees": ({"extra_trees": True}, {}, False, False),
    "feature_fraction_bynode": ({"feature_fraction_bynode": 0.5}, {},
                                False, False),
    "cegb": ({"cegb_penalty_split": 0.1}, {}, False, False),
    "cegb_lazy": ({"cegb_penalty_feature_lazy": [0.1] * FEATURES}, {},
                  False, False),
    "forced_splits": (_forced_splits, {}, False, True),
    "categorical_feature": ({}, {"categorical": [1]}, True, True),
    "categorical_under_mesh": ({"tree_learner": "data"},
                               {"categorical": [1]}, False, True),
    "categorical_bundled": ({}, {"bundled": True, "categorical": [1]},
                            False, True),
    "numerical_group_past_256_bins": ({"max_bin": 300}, {"rows": 600},
                                      False, True),
    "ell_layout": ({"tpu_multival": "force"}, {"bundled": True},
                   False, True),
    "bundled_voting": ({"tree_learner": "voting"}, {"bundled": True},
                       False, True),
    "wide_past_256k_lanes": ({"enable_bundle": False},
                             {"features": 1030}, False, False),
    "persist_scan_off": ({"tpu_persist_scan": "off"}, {}, False, True),
    "feature_parallel": ({"tree_learner": "feature"}, {}, False, True),
    "lambdarank_under_mesh": ({"objective": "lambdarank",
                               "tree_learner": "data"}, {"group": True},
                              False, True),
    "multiclass_under_mesh": ({"objective": "multiclass", "num_class": 3,
                               "tree_learner": "data"}, {}, False, True),
    "uneven_shards": ({"tree_learner": "data", "tpu_num_devices": 3}, {},
                      False, True),
    "dense_binary_under_mesh": ({"tree_learner": "data"}, {}, True, True),
}


# a refusal is for the reason its name says: what the learner holds then
WHY = {
    "tpu_use_dp": lambda l: l.grow_config.use_dp,
    "monotone_constraints": lambda l: l.grow_config.use_mc,
    "lambda_l1": lambda l: l.grow_config.use_l1,
    "max_delta_step": lambda l: l.grow_config.use_mds,
    "extra_trees": lambda l: l.grow_config.extra_trees,
    "feature_fraction_bynode": lambda l: l.grow_config.bynode_k > 0,
    "cegb": lambda l: l.grow_config.use_cegb,
    "cegb_lazy": lambda l: l.grow_config.use_cegb_lazy,
    "forced_splits": lambda l: l.grow_config.n_forced == 1,
    "categorical_feature": lambda l: l.cat_layout.cat_feature.shape[0] == 1,
    # the categorical eval runs on one device over one group a feature
    "categorical_under_mesh": lambda l: (
        l.cat_layout.cat_feature.shape[0] == 1
        and l.grow_config.parallel_mode == "data" and l.num_shards > 1),
    "categorical_bundled": lambda l: (
        l.cat_layout.cat_feature.shape[0] == 1
        and len(l.dataset.groups) < l.dataset.num_features),
    # a numerical threshold scan over two histogram rows: not built
    "numerical_group_past_256_bins": lambda l: (
        int(l.dataset.group_widths().max()) > 256
        and l.cat_layout.cat_feature.shape[0] == 0),
    "ell_layout": lambda l: l.grow_config.multival,
    "bundled_voting": lambda l: l.grow_config.parallel_mode == "voting",
    "wide_past_256k_lanes": lambda l: l.grow_config.num_features == 1030,
    "feature_parallel": lambda l: l.grow_config.parallel_mode == "feature",
    "uneven_shards": lambda l: l.dataset.num_data % l.num_shards != 0,
}


def _build(params, opts):
    rng = np.random.default_rng(7)
    rows = opts.get("rows", ROWS)
    X = (_one_hot(rng) if opts.get("bundled")
         else _dense(rng, opts.get("features", FEATURES), rows))
    if opts.get("categorical"):
        X[:, opts["categorical"]] = rng.integers(0, 4, (rows, 1))
    params = dict({"objective": "binary", "verbosity": -1,
                   "min_data_in_bin": 1}, **params)
    if params["objective"] == "multiclass":
        y = rng.integers(0, 3, rows).astype(np.float64)
    elif opts.get("group"):
        y = rng.integers(0, 4, rows).astype(np.float64)
    else:
        y = (X[:, 0] > 0).astype(np.float64)
    cfg = lgb.Config(params)
    ds = BinnedDataset.from_matrix(
        X, cfg, label=y,
        categorical_features=opts.get("categorical", ()),
        weight=rng.uniform(0.5, 2.0, rows) if opts.get("weight") else None,
        group=[20] * (rows // 20) if opts.get("group") else None)
    learner = create_tree_learner(str(cfg.tree_learner), "cpu", cfg, ds)
    objective = create_objective(params["objective"], cfg)
    objective.init(ds.metadata, ds.num_data)
    return ds, learner, objective


@pytest.fixture
def as_on_the_chip(monkeypatch):
    """The learner reads the backend once, through `on_tpu()`; the row
    floor keeps toy data off the partitioned growers."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(serial, "PARTITION_MIN_ROWS", 100)


@pytest.mark.parametrize("prop", list(GATES))
def test_gate(prop, as_on_the_chip, tmp_path):
    params, opts, admitted, pallas_scan = GATES[prop]
    if callable(params):
        params = params(tmp_path)
    ds, learner, objective = _build(params, opts)
    if opts.get("bundled"):
        assert len(ds.groups) < ds.num_features
    gc = learner.grow_config
    assert WHY.get(prop, lambda l: True)(learner), gc
    assert (gc.scan_impl == "pallas") is pallas_scan, gc
    assert learner.can_persist_scan(objective) is admitted


def test_gate_host_only_objective_under_force_is_fatal(as_on_the_chip):
    """`tpu_persist_scan=force` asks for the fused path by name: an
    objective without a device gradient kernel is refused loudly, where
    `auto` takes the per-iteration host path in silence."""
    _, learner, objective = _build({"objective": "rank_xendcg"},
                                   {"group": True})
    assert learner.can_persist_scan(objective) is False
    _, learner, objective = _build(
        {"objective": "rank_xendcg", "tpu_persist_scan": "force"},
        {"group": True})
    with pytest.raises(LightGBMError, match="no device gradient kernel"):
        learner.can_persist_scan(objective)


def test_gate_off_the_chip_nothing_is_admitted(monkeypatch):
    """Without a TPU `auto` never claims the fast path."""
    monkeypatch.setattr(serial, "PARTITION_MIN_ROWS", 100)
    _, learner, objective = _build({}, {})
    assert learner.grow_config.scan_impl == "xla"
    assert learner.can_persist_scan(objective) is False
