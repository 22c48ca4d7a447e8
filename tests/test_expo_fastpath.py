"""Expo-shaped EFB regression: the bundle fast path must ENGAGE and match.

The bundle-native path (block scan + in-pass smaller-child histogram +
cached window masks) only pays if the fast path actually takes these
datasets. The regression test pins, via telemetry counters, that a small
Expo-shaped training runs ENTIRELY on the persist driver (zero v1 trees,
the block-scan grower built) while predictions still match the v1 grower.
The profile-CLI smoke test keeps `python -m lightgbm_tpu.profile --shape
expo` working on CPU.
"""
import json

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.data.synth import make_expo_like
from lightgbm_tpu.telemetry import events


def _expo_small(n=6144):
    X, y = make_expo_like(n_rows=n, seed=3)
    return X, y


@pytest.mark.slow  # persist-driver compile (Mosaic kernels, interpreted)
def test_expo_bundle_fast_path_engages_and_matches_v1(monkeypatch):
    # off the TPU the persist path's XLA emulation scans the flat layout;
    # the block scan is the Mosaic kernels' (tests/test_expo_config.py)
    from lightgbm_tpu.treelearner.serial import SerialTreeLearner
    monkeypatch.setattr(SerialTreeLearner, "_persist_kernel_mode",
                        staticmethod(lambda: ("pallas", True)))
    X, y = _expo_small()
    base = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
            "min_data_in_leaf": 10, "max_bin": 63, "learning_rate": 0.2}
    events.enable("timers")
    events.reset()
    try:
        bst_p = lgb.train({**base, "tpu_persist_scan": "force"},
                          lgb.Dataset(X, y), 16, verbose_eval=False)
        counts = events.counts_snapshot()
    finally:
        events.reset()
        events.disable()
    inner = bst_p._booster.tree_learner.dataset
    assert len(inner.groups) < inner.num_features, \
        "expected EFB bundles in the Expo shape"
    assert bool(np.any(inner.needs_fix))
    # the telemetry counters prove WHICH path trained: all 16 trees on the
    # persist driver, the bundle block-scan grower built, zero v1 trees
    assert counts.get("tree_learner::persist_scan_trees", 0) >= 16, counts
    assert counts.get("tree_learner::blockscan_trees", 0) >= 16, counts
    assert counts.get("tree_learner::v1_grow_trees", 0) == 0, counts

    bst_v1 = lgb.train({**base, "tpu_persist_scan": "off"},
                       lgb.Dataset(X, y), 16, verbose_eval=False)
    # early iterations match exactly; past that the f32 fix residual can
    # flip a near-tie the f64 v1 fix resolves the other way (same trade
    # the EFB persist test documents) — full models compare by quality
    p = bst_p.predict(X[:1024], num_iteration=4)
    v = bst_v1.predict(X[:1024], num_iteration=4)
    np.testing.assert_allclose(p, v, rtol=1e-4, atol=1e-6)
    acc_p = ((bst_p.predict(X) > 0.5) == y).mean()
    acc_v = ((bst_v1.predict(X) > 0.5) == y).mean()
    assert abs(acc_p - acc_v) < 0.02, (acc_p, acc_v)


@pytest.mark.slow  # tier-1 870s budget: profile --merge --run is covered in tier-1
def test_profile_cli_expo_smoke(tmp_path):
    """`python -m lightgbm_tpu.profile --shape expo` runs tier-1-safe on
    CPU (xplane off) and writes the telemetry snapshot with the
    per-category attribution + path counters."""
    from lightgbm_tpu.profile import main
    out = tmp_path / "phases.json"
    try:
        rc = main(["--shape", "expo", "4096", "2", "xplane=0",
                   "num_leaves=15", "max_bin=63",
                   # keep the engine's TRACE-mode auto-export out of CWD
                   "telemetry_out=%s" % (tmp_path / "trace.json"),
                   "phases_out=%s" % out])
    finally:
        events.reset()
        events.disable()
    assert rc == 0
    snap = json.loads(out.read_text())
    assert "expo" in snap
    cats = snap["expo"]["categories"]
    assert "tree_learner" in cats or "ops" in cats, cats
    # the path counters ride the snapshot so fast-path engagement is
    # visible next to the attribution
    assert "counters" in snap["expo"]


def test_allstate_yahoo_generators_shape():
    """The two never-benched reference shapes produce what their bench
    runs assume: sparse one-hot CSR with ~4.1k columns, and 700-feature
    LTR groups that tile the row count."""
    from lightgbm_tpu.data.synth import make_allstate_like, make_yahoo_like
    X, y = make_allstate_like(n_rows=2000)
    assert X.shape[0] == 2000 and X.shape[1] > 4000
    assert hasattr(X, "tocsr")                     # stays sparse
    assert set(np.unique(np.asarray(X[:100].todense()))) >= {0.0, 1.0}
    assert y.shape == (2000,)
    Xy, yy, g = make_yahoo_like(n_rows=2400, docs_per_query=24)
    assert Xy.shape == (2400, 700)
    assert g.sum() == len(yy) == 2400
