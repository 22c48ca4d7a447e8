"""Level-parallel persist growth: parity, admission semantics, launch count.

The PR 7 grower refactor runs an ENTIRE tree level as one compiled
program (batched multi-leaf partition + batched split-find, driven by a
bounded loop over depths) whenever `can_level_grow` holds, with leaf-wise
semantics preserved by gain-ordered admission plus an in-program no-bind
certificate that hands the tree to the historical per-split tail the
moment best-first admission could be budget-truncated. These tests pin:

  * raw-score parity: `tpu_level_grow=auto` vs `off` is BIT-EXACT on the
    persist driver (gbdt + goss, bundled Expo-like and unbundled
    HIGGS-like shapes) — the level batch is a scheduling change, not a
    numerics change;
  * the frontier edge cases — leaves dropping out at min_data_in_leaf,
    and `num_leaves` budgets under which best-first admission could be
    truncated, which the certificate refuses to the per-split tail —
    keep that parity;
  * the launch-count regression the Expo gap was about: on a level-wide
    budget (num_leaves >= 2^max_depth) a tree costs <= max_depth level
    programs and ZERO per-split fallback launches, counter-pinned via
    tree_learner::level_programs / level_fallback_splits;
  * DART and RF ride the persist driver too (PR 17: per-tree weight
    vectors traced into the fused iteration program) — device vs host
    paths are BIT-EXACT, pinned on bundled and unbundled shapes along
    with the iter-launch counter the fusion exists to shrink.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.data.synth import make_expo_like, make_higgs_like
from lightgbm_tpu.telemetry import events


def _train_counted(params, X, y, rounds=16):
    events.enable("timers")
    events.reset()
    try:
        bst = lgb.train(params, lgb.Dataset(X, y), rounds,
                        verbose_eval=False)
        counts = events.counts_snapshot()
    finally:
        events.reset()
        events.disable()
    return bst, counts


def _raw(bst, X):
    return bst.predict(X[:1500], raw_score=True)


def _higgs_small(n=5000):
    X, y = make_higgs_like(n_rows=n, seed=11)
    return X, y


def _expo_small(n=4096):
    X, y = make_expo_like(n_rows=n, seed=3)
    return X, y


# ---------------------------------------------------------------------------
# static gate
# ---------------------------------------------------------------------------

def test_can_level_grow_gate():
    from collections import namedtuple
    from lightgbm_tpu.ops.grow_persist import (LEVEL_MAX_DEPTH,
                                               can_level_grow)
    GC = namedtuple("GC", "max_depth num_leaves parallel_mode n_forced")
    ok = GC(6, 64, "data", 0)
    assert can_level_grow(ok)
    assert not can_level_grow(ok._replace(max_depth=0))      # unbounded
    assert not can_level_grow(ok._replace(max_depth=-1))
    assert not can_level_grow(ok._replace(
        max_depth=LEVEL_MAX_DEPTH + 1))                      # slot blowup
    assert can_level_grow(ok._replace(max_depth=LEVEL_MAX_DEPTH))
    assert not can_level_grow(ok._replace(num_leaves=3))     # trivial trees
    assert not can_level_grow(ok._replace(parallel_mode="voting"))
    assert not can_level_grow(ok._replace(n_forced=2))       # ordered splits


# ---------------------------------------------------------------------------
# raw-score parity: level program vs per-split persist path
# ---------------------------------------------------------------------------

@pytest.mark.slow  # persist-driver compile x2 (XLA kernel emulation)
@pytest.mark.parametrize("objective_extra", [
    {},                                                       # gbdt
    {"boosting": "goss", "top_rate": 0.3, "other_rate": 0.15},
], ids=["gbdt", "goss"])
def test_level_parity_higgs_unbundled(objective_extra):
    X, y = _higgs_small()
    base = {"objective": "binary", "num_leaves": 16, "max_depth": 4,
            "verbosity": -1, "min_data_in_leaf": 10, "max_bin": 63,
            "learning_rate": 0.2, "tpu_persist_scan": "force",
            **objective_extra}
    bst_lvl, c_lvl = _train_counted(base, X, y)
    bst_off, c_off = _train_counted({**base, "tpu_level_grow": "off"},
                                    X, y)
    assert c_lvl.get("tree_learner::persist_scan_trees", 0) >= 16, c_lvl
    assert c_lvl.get("tree_learner::level_programs", 0) >= 16, c_lvl
    assert c_off.get("tree_learner::level_programs", 0) == 0, c_off
    np.testing.assert_array_equal(_raw(bst_lvl, X), _raw(bst_off, X))


@pytest.mark.slow
def test_level_parity_expo_bundled():
    X, y = _expo_small()
    base = {"objective": "binary", "num_leaves": 32, "max_depth": 5,
            "verbosity": -1, "min_data_in_leaf": 10, "max_bin": 63,
            "learning_rate": 0.2, "tpu_persist_scan": "force"}
    bst_lvl, c_lvl = _train_counted(base, X, y)
    bst_off, c_off = _train_counted({**base, "tpu_level_grow": "off"},
                                    X, y)
    inner = bst_lvl._booster.tree_learner.dataset
    assert len(inner.groups) < inner.num_features, \
        "expected EFB bundles in the Expo shape"
    assert c_lvl.get("tree_learner::level_programs", 0) >= 16, c_lvl
    assert c_off.get("tree_learner::level_fallback_splits", 0) >= 16, c_off
    np.testing.assert_array_equal(_raw(bst_lvl, X), _raw(bst_off, X))


# ---------------------------------------------------------------------------
# frontier-mask edge cases
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_level_frontier_min_data_dropout():
    """min_data_in_leaf large enough that frontier leaves stop splitting
    mid-tree: the frontier mask shrinks level over level and the parity
    with best-first growth must survive the dropouts."""
    X, y = _higgs_small()
    base = {"objective": "binary", "num_leaves": 32, "max_depth": 5,
            "verbosity": -1, "min_data_in_leaf": len(y) // 12,
            "max_bin": 63, "learning_rate": 0.2,
            "tpu_persist_scan": "force"}
    bst_lvl, c_lvl = _train_counted(base, X, y)
    bst_off, _ = _train_counted({**base, "tpu_level_grow": "off"}, X, y)
    assert c_lvl.get("tree_learner::level_programs", 0) > 0, c_lvl
    np.testing.assert_array_equal(_raw(bst_lvl, X), _raw(bst_off, X))


@pytest.mark.slow
def test_level_admission_budget_truncation_refuses():
    """num_leaves strictly between 2^(md-1) and 2^md: best-first
    admission COULD be budget-truncated mid-level, so the no-bind
    certificate must refuse at the root (remaining budget 11 < the
    positive-gain frontier's completion capacity 2^4-1 = 15) and hand
    the whole tree to the per-split tail — zero level programs, every
    split counted as a fallback, and the scores still match best-first
    exactly. (A mid-tree handoff the other way is impossible by design:
    the certificate margin (budget - capacity) is non-decreasing level
    over level, so once it holds at the root it holds to the leaves.)"""
    X, y = _higgs_small()
    base = {"objective": "binary", "num_leaves": 12, "max_depth": 4,
            "verbosity": -1, "min_data_in_leaf": 10, "max_bin": 63,
            "learning_rate": 0.2, "tpu_persist_scan": "force"}
    bst_lvl, c_lvl = _train_counted(base, X, y)
    bst_off, _ = _train_counted({**base, "tpu_level_grow": "off"}, X, y)
    assert c_lvl.get("tree_learner::level_programs", 0) == 0, c_lvl
    assert c_lvl.get("tree_learner::level_fallback_splits", 0) > 0, c_lvl
    np.testing.assert_array_equal(_raw(bst_lvl, X), _raw(bst_off, X))


# ---------------------------------------------------------------------------
# launch-count regression (the Expo gap)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_expo_level_launches_per_tree_bounded():
    """On a level-wide budget (num_leaves >= 2^max_depth) an Expo-shaped
    tree must cost <= max_depth level programs and ZERO per-split
    fallback launches — the ~num_leaves-1 small-kernel launches per tree
    that made Expo 0.23x the anchor are gone."""
    X, y = _expo_small()
    rounds, md = 16, 6
    base = {"objective": "binary", "num_leaves": 1 << md, "max_depth": md,
            "verbosity": -1, "min_data_in_leaf": 10, "max_bin": 63,
            "learning_rate": 0.2, "tpu_persist_scan": "force"}
    bst, c = _train_counted(base, X, y, rounds=rounds)
    assert bst.num_trees() == rounds
    lv = c.get("tree_learner::level_programs", 0)
    fb = c.get("tree_learner::level_fallback_splits", 0)
    assert 0 < lv <= rounds * md, c
    assert fb == 0, c
    # per-split growth of the same trees would launch one split_pass per
    # split; the level path replaces them all with <= md programs/tree
    n_splits = sum(
        bst._booster.models[t].num_leaves - 1 for t in range(rounds))
    assert lv < n_splits, (lv, n_splits)
    # the whole-iteration fusion (PR 17): 16 gbdt iterations batch into
    # ceil(16/16) = 1 driver invocation — the iter-launch counter must
    # show the amortization, not one launch per tree
    il = c.get("tree_learner::iter_launches", 0)
    assert 0 < il <= (rounds + 15) // 16 + 1, c
    assert il < rounds, c


# ---------------------------------------------------------------------------
# Mosaic level kernels (interpreter) vs the XLA emulation
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_level_mosaic_kernels_interpret_match_emulation(monkeypatch):
    """The production TPU level path (make_level_pass multi-leaf
    partition + in-pass histograms) in Pallas INTERPRETER mode must
    reproduce the XLA-emulation trees."""
    from lightgbm_tpu.treelearner.serial import SerialTreeLearner
    X, y = _higgs_small(2048)
    base = {"objective": "binary", "num_leaves": 16, "max_depth": 4,
            "verbosity": -1, "min_data_in_leaf": 10, "max_bin": 31,
            "learning_rate": 0.2, "tpu_persist_scan": "force"}
    bst_emu, _ = _train_counted(base, X, y)
    monkeypatch.setattr(SerialTreeLearner, "_persist_kernel_mode",
                        staticmethod(lambda: ("pallas", True)))
    bst_mos, c_mos = _train_counted(base, X, y)
    assert c_mos.get("tree_learner::level_programs", 0) > 0, c_mos
    np.testing.assert_allclose(_raw(bst_mos, X), _raw(bst_emu, X),
                               rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# DART / RF on the fused persist driver (PR 17)
# ---------------------------------------------------------------------------

def _trees_only(bst):
    """Model string minus the parameters block (the two runs differ in
    tpu_persist_scan by construction; the TREES must not)."""
    return bst.model_to_string().split("\nparameters:")[0]


@pytest.mark.slow
@pytest.mark.parametrize("shape", ["higgs_unbundled", "expo_bundled"])
@pytest.mark.parametrize("extra", [
    {"boosting": "dart", "drop_rate": 0.3},
    {"boosting": "rf", "bagging_freq": 1, "bagging_fraction": 0.7},
], ids=["dart", "rf"])
def test_dart_rf_device_host_parity(extra, shape):
    """Pre-PR-17 these modes pinned the persist driver INERT
    (supports_batch=False). Now DART's drop/normalize deltas and RF's
    bagged-average iterations run inside the fused iteration program —
    per-tree weight vectors computed host-side, applied as traced
    vectors — and the device path must match the host path BIT-EXACTLY:
    same trees (model string minus params) and same raw scores, on both
    the EFB-bundled Expo shape and the unbundled HIGGS shape."""
    if shape == "higgs_unbundled":
        X, y = _higgs_small(3000)
    else:
        X, y = _expo_small(2048)
    base = {"objective": "binary", "num_leaves": 15, "max_depth": 4,
            "verbosity": -1, "min_data_in_leaf": 10, "max_bin": 63,
            "learning_rate": 0.2, **extra}
    bst_dev, c_dev = _train_counted(
        {**base, "tpu_persist_scan": "force"}, X, y, rounds=8)
    bst_host, c_host = _train_counted(
        {**base, "tpu_persist_scan": "off"}, X, y, rounds=8)
    # positive device-path pins (replacing the old inert assertions)
    assert c_dev.get("tree_learner::persist_scan_trees", 0) >= 8, c_dev
    assert c_dev.get("tree_learner::iter_launches", 0) > 0, c_dev
    assert c_host.get("tree_learner::persist_scan_trees", 0) == 0, c_host
    assert _trees_only(bst_dev) == _trees_only(bst_host)
    np.testing.assert_array_equal(_raw(bst_dev, X), _raw(bst_host, X))
