"""Fused Pallas split-scan kernel vs the XLA scan: same trees.

The analog of the reference's GPU_DEBUG_COMPARE self-check
(src/treelearner/gpu_tree_learner.cpp:993-1030) for the split-scan kernel
(ops/pallas_scan.py): grow whole trees with scan_impl="pallas" (interpreter
mode on CPU) and scan_impl="xla" at identical f32 settings and require the
same structure (features, thresholds, default directions) and matching
leaf values/gains to f32 reassociation tolerance.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.data.dataset import BinnedDataset
from lightgbm_tpu.ops.grow import grow_tree, grow_tree_partitioned
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.treelearner.serial import (build_cat_layout,
                                             build_gw_global)


def _problem(n=4000, f=7, seed=3, missing=True):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    if missing:
        X[rng.random((n, f)) < 0.08] = np.nan       # NaN missing type
        X[:, 2] = np.where(rng.random(n) < 0.6, 0.0, X[:, 2])  # zero-heavy
    y = (np.nan_to_num(X[:, 0]) + 0.5 * np.nan_to_num(X[:, 1]) > 0.2)
    cfg = lgb.Config({"num_leaves": 31, "max_bin": 63,
                      "min_data_in_leaf": 20, "zero_as_missing": False})
    ds = BinnedDataset.from_matrix(X, cfg, label=y.astype(np.float32))
    grad = jnp.asarray((0.5 - y).astype(np.float32))
    hess = jnp.full(n, 0.25, jnp.float32)
    return cfg, ds, grad, hess


def _grow(ds, cfg, grad, hess, scan_impl, partitioned):
    from lightgbm_tpu.ops.grow import GrowConfig
    n = ds.num_data
    layout, meta = ds.to_device(cfg)
    widths = ds.bin_end - ds.bin_start
    gc = GrowConfig(
        num_leaves=31, total_bins=ds.total_bins,
        num_features=ds.num_features, use_mc=False, max_depth=-1,
        rows_per_chunk=0, cat_width=1, hist_impl="scatter",
        scan_width=int(widths.max()), use_dp=False, window_chunk=512,
        hist_dtype="f32", use_l1=False, use_mds=False,
        scan_impl=scan_impl)
    params = SplitParams.from_config(cfg)
    fmask = jnp.ones(ds.num_features, bool)
    bag = jnp.ones(n, bool)
    cat = build_cat_layout(ds, 1)
    if partitioned:
        arrays, _ = grow_tree_partitioned(
            layout, grad, hess, bag, meta, params, fmask, ds.fix_info(),
            gc, gw_global=build_gw_global(ds), cat=cat)
    else:
        arrays, _ = grow_tree(layout, grad, hess, bag, meta, params,
                              fmask, ds.fix_info(), gc, cat=cat)
    import jax
    return jax.device_get(arrays)


@pytest.mark.parametrize("partitioned", [False, True])
@pytest.mark.parametrize("missing", [False, True])
def test_pallas_scan_matches_xla(partitioned, missing):
    cfg, ds, grad, hess = _problem(missing=missing)
    a = _grow(ds, cfg, grad, hess, "xla", partitioned)
    b = _grow(ds, cfg, grad, hess, "pallas", partitioned)
    assert a.num_leaves == b.num_leaves
    k = int(a.num_leaves) - 1
    np.testing.assert_array_equal(a.split_feature[:k], b.split_feature[:k])
    np.testing.assert_array_equal(a.threshold[:k], b.threshold[:k])
    np.testing.assert_array_equal(a.default_left[:k], b.default_left[:k])
    np.testing.assert_array_equal(a.split_leaf[:k], b.split_leaf[:k])
    np.testing.assert_allclose(a.gain[:k], b.gain[:k], rtol=2e-4, atol=1e-5)
    nl = int(a.num_leaves)
    np.testing.assert_array_equal(a.leaf_count[:nl], b.leaf_count[:nl])
    np.testing.assert_allclose(a.leaf_value[:nl], b.leaf_value[:nl],
                               rtol=2e-4, atol=1e-7)
    np.testing.assert_array_equal(a.row_leaf, b.row_leaf)


def test_pallas_scan_used_on_default_config_shapes():
    """resolve_scan_impl must pick the kernel exactly for the fast path."""
    from lightgbm_tpu.treelearner.serial import resolve_scan_impl
    base = dict(use_dp=False, use_mc=False, use_l1=False, use_mds=False,
                extra_trees=False, bynode_k=0, use_cegb=False,
                num_features=28, scan_width=256)
    cfg = lgb.Config({})
    # CPU backend in tests -> xla even for the fast path
    assert resolve_scan_impl(cfg, dict(base)) == "xla"
    cfg2 = lgb.Config({"tpu_scan_impl": "pallas"})
    # explicit pallas on a non-fast config warns and falls back
    assert resolve_scan_impl(cfg2, dict(base, use_mc=True)) == "xla"
