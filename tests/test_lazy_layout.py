"""The binned rows go on the device at the first read of ``learner.layout``.

The constructor keeps the host part of the layout (the multi-value and
nibble-packing decisions the GrowConfig reads, the per-feature tables) under
``tree_learner::ToDevice(layout H2D)``; the ``[N, G]`` binned matrix is
placed under ``tree_learner::ToDevice(bins H2D)`` by the first v1 grower that
reads it, and counted by ``tree_learner::layout_placements``. The persist
path, serial or sharded, never reads it.
"""
import numpy as np
import pytest

import jax

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.treelearner.serial import SerialTreeLearner

N, F, ROUNDS = 2048, 8, 16        # divisible by 4 shards; one fused batch
BASE = {"objective": "binary", "verbosity": -1, "min_data_in_leaf": 10,
        "max_bin": 63, "num_leaves": 7, "metric": "none"}
PERSIST = {"tpu_persist_scan": "force"}
SHARDED = {"tpu_persist_scan": "force", "tree_learner": "data",
           "tpu_num_devices": 4}
PLACED = "tree_learner::layout_placements"


@pytest.fixture(autouse=True)
def _telemetry_off():
    telemetry.disable()
    yield
    telemetry.disable()


def _data(seed=41):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(N, F))
    y = (X[:, 0] - 0.6 * X[:, 3] + 0.3 * X[:, 1] * X[:, 5]
         + rng.normal(size=N) * 0.5 > 0).astype(float)
    return X, y


def _placements():
    return telemetry.counts_snapshot().get(PLACED, 0.0)


def _train(params, ds=None):
    X, y = _data()
    ds = ds if ds is not None else lgb.Dataset(X, y)
    return lgb.train({**BASE, **params}, ds, ROUNDS, verbose_eval=False), ds


def _newest_train_spans():
    ring = telemetry.ring_snapshot()
    root = [e for e in ring if e["name"] == "engine::train"][-1]
    return [e["name"] for e in ring if e.get("train") == root["train"]]


def _trees(bst):
    return bst.model_to_string(num_iteration=-1)


def _place_when_built(monkeypatch):
    """Every learner reads its ``layout`` as the last step of its
    constructor: the eager placement the property replaced."""
    init = SerialTreeLearner.__init__

    def eager(self, config, dataset):
        init(self, config, dataset)
        assert self.layout.bins.shape[0] == dataset.num_data

    monkeypatch.setattr(SerialTreeLearner, "__init__", eager)


@pytest.mark.parametrize("params", [PERSIST, SHARDED],
                         ids=["serial", "data4"])
def test_persist_train_never_places_the_bins(params):
    assert telemetry.mode() == telemetry.OFF
    if params is SHARDED:
        assert len(jax.devices()) >= 4, "conftest provides 8 virtual devices"
    before = _placements()
    bst, ds = _train(params)
    learner = bst._booster.tree_learner
    assert getattr(learner, "_persist_carry", None) is not None
    counts = telemetry.counts_snapshot()
    assert counts["tree_learner::persist_scan_trees"] >= ROUNDS
    assert _placements() == before
    assert getattr(ds._inner, "_device_layout_cache", {}) == {}
    assert learner._layout is None
    spans = _newest_train_spans()
    assert spans.count("tree_learner::ToDevice(layout H2D)") == 1
    assert "tree_learner::ToDevice(bins H2D)" not in spans


@pytest.mark.parametrize("params", [{}, {"monotone_constraints": "1,0,0,-1"}],
                         ids=["default", "monotone"])
def test_v1_train_places_the_bins_once(params, monkeypatch):
    before = _placements()
    lazy, ds = _train(params)
    learner = lazy._booster.tree_learner
    assert getattr(learner, "_persist_carry", None) is None
    assert _placements() - before == 1
    assert len(ds._inner._device_layout_cache) == 1
    spans = _newest_train_spans()
    assert spans.count("tree_learner::ToDevice(bins H2D)") == 1
    assert spans.count("tree_learner::ToDevice(layout H2D)") == 1
    # the same trees as a learner that placed its bins when it was built
    _place_when_built(monkeypatch)
    eager, _ = _train(params)
    assert _trees(lazy) == _trees(eager)


def test_persist_model_does_not_depend_on_placed_bins(monkeypatch):
    never, ds = _train(PERSIST)
    assert getattr(ds._inner, "_device_layout_cache", {}) == {}
    _place_when_built(monkeypatch)
    before = _placements()
    placed, _ = _train(PERSIST)
    assert _placements() - before == 1
    assert placed._booster.tree_learner._persist_carry is not None
    assert _trees(placed) == _trees(never)


def test_boosters_on_one_dataset_share_one_layout():
    before = _placements()
    first, ds = _train({})
    second, _ = _train({"learning_rate": 0.05}, ds=ds)
    a, b = first._booster.tree_learner, second._booster.tree_learner
    assert a is not b
    assert a.layout is b.layout
    assert len(ds._inner._device_layout_cache) == 1
    assert _placements() - before == 1
