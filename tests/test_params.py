"""Formerly-dead parameters: extra_trees, feature_fraction_bynode, CEGB,
refit, pred_early_stop — each works (or errors loudly) per the reference
semantics it mirrors."""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.utils.log import LightGBMError


def _data(n=1500, f=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = X[:, 0] + 0.5 * X[:, 1] - 0.3 * X[:, 2] + 0.1 * rng.normal(size=n)
    return X, y


def _trees_of(bst):
    bst._booster._materialize_pending()
    return bst._booster.models


def test_extra_trees_changes_model_and_is_seeded():
    X, y = _data()
    base = {"objective": "regression", "num_leaves": 15, "verbosity": -1}
    b0 = lgb.train(dict(base), lgb.Dataset(X, y), 5, verbose_eval=False)
    b1 = lgb.train({**base, "extra_trees": True}, lgb.Dataset(X, y), 5,
                   verbose_eval=False)
    b2 = lgb.train({**base, "extra_trees": True}, lgb.Dataset(X, y), 5,
                   verbose_eval=False)
    t0, t1, t2 = _trees_of(b0), _trees_of(b1), _trees_of(b2)
    # random thresholds differ from the exhaustive scan...
    assert not np.array_equal(t0[0].threshold, t1[0].threshold)
    # ...but are deterministic under the same extra_seed
    for a, b in zip(t1, t2):
        np.testing.assert_array_equal(a.threshold, b.threshold)
    # and still learn something
    r2 = 1 - np.var(y - b1.predict(X)) / np.var(y)
    assert r2 > 0.5


def test_feature_fraction_bynode():
    X, y = _data()
    base = {"objective": "regression", "num_leaves": 31, "verbosity": -1,
            "feature_fraction_bynode": 0.5}
    b = lgb.train(base, lgb.Dataset(X, y), 5, verbose_eval=False)
    # per-node sampling: every feature should still appear somewhere
    used = set()
    for t in _trees_of(b):
        used.update(t.split_feature[:t.num_leaves - 1].tolist())
    assert len(used) > 3
    r2 = 1 - np.var(y - b.predict(X)) / np.var(y)
    assert r2 > 0.5


def test_cegb_split_penalty_prunes():
    X, y = _data()
    base = {"objective": "regression", "num_leaves": 63, "verbosity": -1,
            "min_gain_to_split": 0.0}
    b0 = lgb.train(dict(base), lgb.Dataset(X, y), 3, verbose_eval=False)
    b1 = lgb.train({**base, "cegb_penalty_split": 0.05},
                   lgb.Dataset(X, y), 3, verbose_eval=False)
    n0 = sum(t.num_leaves for t in _trees_of(b0))
    n1 = sum(t.num_leaves for t in _trees_of(b1))
    assert n1 < n0  # splitting now costs tradeoff*penalty*count


def test_cegb_coupled_penalty_limits_features():
    X, y = _data(f=8)
    pen = [10.0] * 8  # high cost to introduce each new feature
    base = {"objective": "regression", "num_leaves": 31, "verbosity": -1}
    b0 = lgb.train(dict(base), lgb.Dataset(X, y), 3, verbose_eval=False)
    b1 = lgb.train({**base, "cegb_tradeoff": 1.0,
                    "cegb_penalty_feature_coupled": pen},
                   lgb.Dataset(X, y), 3, verbose_eval=False)
    used0 = set()
    for t in _trees_of(b0):
        used0.update(t.split_feature[:t.num_leaves - 1].tolist())
    used1 = set()
    for t in _trees_of(b1):
        used1.update(t.split_feature[:t.num_leaves - 1].tolist())
    assert len(used1) <= len(used0)


def _cegb_lazy_data(tmp_path):
    """Deterministic binary problem round-tripped through CSV the way the
    reference golden below was generated (values %.9g-rounded)."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(2000, 8))
    y = (X[:, 0] + 0.5 * X[:, 1]
         + 0.2 * rng.normal(size=2000) > 0).astype(float)
    path = str(tmp_path / "cegb_train.csv")
    np.savetxt(path, np.column_stack([y, X]), delimiter=",", fmt="%.9g")
    return path


# Per-iteration training logloss of the REAL LightGBM binary (built from
# /root/reference) on the dataset above with the params below — pins the
# lazy on-demand penalty semantics (CalculateOndemandCosts + the
# feature_used_in_data_ bitset, cost_effective_gradient_boosting.hpp:47-114).
CEGB_LAZY_GOLDEN = [
    0.616674, 0.553374, 0.501297, 0.456948, 0.418483, 0.385537, 0.357251,
    0.332104, 0.309686, 0.289829, 0.272757, 0.256913, 0.24323, 0.230598,
    0.219254, 0.209052, 0.199477, 0.191094, 0.18345, 0.17599]


def test_cegb_lazy_reference_parity(tmp_path):
    path = _cegb_lazy_data(tmp_path)
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 20, "learning_rate": 0.1,
              "metric": "binary_logloss", "verbosity": -1,
              "label_column": 0, "header": False,
              "cegb_penalty_feature_lazy": [0.001] * 8,
              "cegb_tradeoff": 1.0}
    ds = lgb.Dataset(path, params=dict(params))
    evals = {}
    lgb.train(params, ds, num_boost_round=20, valid_sets=[ds],
              valid_names=["training"],
              callbacks=[lgb.record_evaluation(evals)], verbose_eval=False)
    ours = evals["training"]["binary_logloss"]
    for it, (got, ref) in enumerate(zip(ours, CEGB_LAZY_GOLDEN), 1):
        assert abs(got - ref) <= 1e-3 * abs(ref) + 1e-6, (
            "iteration %d: ours=%.6f ref=%.6f" % (it, got, ref))


@pytest.mark.slow  # tier-1 870s budget: cheaper sibling tests cover this area
def test_cegb_lazy_zero_matches_coupled_zero():
    # a zero lazy penalty vector must reproduce the zero-coupled CEGB
    # model exactly (identical gain path, bitset contributes nothing)
    X, y = _data(f=8)
    base = {"objective": "regression", "num_leaves": 31, "verbosity": -1}
    bz = lgb.train({**base, "cegb_penalty_feature_lazy": [0.0] * 8},
                   lgb.Dataset(X, y), 5, verbose_eval=False)
    bc = lgb.train({**base, "cegb_penalty_feature_coupled": [0.0] * 8},
                   lgb.Dataset(X, y), 5, verbose_eval=False)
    np.testing.assert_allclose(bz.predict(X), bc.predict(X), atol=1e-12)


@pytest.mark.slow  # tier-1 870s budget: cheaper sibling tests cover this area
def test_cegb_lazy_heavy_penalty_suppresses_splits():
    # a per-row acquisition cost far above any gain: no split clears it
    X, y = _data(n=500, f=8)
    b = lgb.train({"objective": "regression", "verbosity": -1,
                   "num_leaves": 15,
                   "cegb_penalty_feature_lazy": [1e6] * 8},
                  lgb.Dataset(X, y), 3, verbose_eval=False)
    assert all(t.num_leaves == 1 for t in _trees_of(b))


def test_cegb_lazy_parallel_raises():
    X, y = _data(n=300)
    with pytest.raises(LightGBMError):
        lgb.train({"objective": "regression", "verbosity": -1,
                   "tree_learner": "data",
                   "cegb_penalty_feature_lazy": [1.0] * 8},
                  lgb.Dataset(X, y), 1, verbose_eval=False)


def test_forcedsplits_missing_file_raises():
    # forced splits are implemented (tests/test_forced_splits.py); a
    # nonexistent spec file must still fail loudly, not silently no-op
    X, y = _data(n=300)
    with pytest.raises((LightGBMError, OSError)):
        lgb.train({"objective": "regression", "verbosity": -1,
                   "forcedsplits_filename": "foo.json"},
                  lgb.Dataset(X, y), 1, verbose_eval=False)


def test_refit_keeps_structure_updates_leaves():
    X, y = _data(seed=1)
    X2, y2 = _data(seed=2)
    bst = lgb.train({"objective": "regression", "num_leaves": 15,
                     "verbosity": -1}, lgb.Dataset(X, y), 10,
                    verbose_eval=False)
    new = bst.refit(X2, y2, decay_rate=0.5)
    t_old, t_new = _trees_of(bst), _trees_of(new)
    assert len(t_old) == len(t_new)
    for a, b in zip(t_old, t_new):
        np.testing.assert_array_equal(
            a.split_feature[:a.num_leaves - 1],
            b.split_feature[:b.num_leaves - 1])       # same structure
    changed = any(
        not np.allclose(a.leaf_value[:a.num_leaves],
                        b.leaf_value[:b.num_leaves])
        for a, b in zip(t_old, t_new))
    assert changed                                     # new leaf values
    # refitted model fits the new data better than the old model does
    mse_old = np.mean((bst.predict(X2) - y2) ** 2)
    mse_new = np.mean((new.predict(X2) - y2) ** 2)
    assert mse_new < mse_old


def test_batched_scan_respects_changed_hyperparams():
    """Two trainings on the SAME Dataset with different regularization must
    differ (the fused-scan cache must not bake hyperparameters in)."""
    X, y = _data(n=1200)
    ds = lgb.Dataset(X, y)
    base = {"objective": "regression", "num_leaves": 31, "verbosity": -1}
    b1 = lgb.train(dict(base), ds, 8, verbose_eval=False)
    b2 = lgb.train({**base, "lambda_l2": 1000.0}, ds, 8, verbose_eval=False)
    p1, p2 = b1.predict(X), b2.predict(X)
    assert not np.allclose(p1, p2)
    assert np.abs(p2).mean() < np.abs(p1).mean()  # heavy L2 shrinks outputs


def test_batched_scan_respects_objective_hyperparams_and_new_labels():
    """The fused-scan cache must also honor (a) scalars baked into the
    objective's grad closure (scale_pos_weight) and (b) replaced dataset
    fields — both bypass the traced SplitParams."""
    rng = np.random.default_rng(11)
    X = rng.normal(size=(1500, 5))
    y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(float)
    base = {"objective": "binary", "num_leaves": 15, "verbosity": -1}
    ds = lgb.Dataset(X, y, free_raw_data=False)
    b1 = lgb.train(dict(base), ds, 20, verbose_eval=False)
    b2 = lgb.train({**base, "scale_pos_weight": 25.0}, ds, 20,
                   verbose_eval=False)
    p1, p2 = b1.predict(X), b2.predict(X)
    assert not np.allclose(p1, p2)
    assert p2.mean() > p1.mean()   # up-weighted positives push probs up
    # replaced labels on the SAME Dataset retrain against the new targets
    ds.set_label(1.0 - y)
    b3 = lgb.train(dict(base), ds, 20, verbose_eval=False)
    p3 = b3.predict(X)
    assert np.corrcoef(p1, p3)[0, 1] < -0.5


def test_bagging_not_silently_dropped():
    """bagging_fraction < 1 must keep bagging active every iteration (the
    fused batch path must not engage and train full-data)."""
    X, y = _data(n=3000)
    base = {"objective": "regression", "num_leaves": 31, "verbosity": -1,
            "bagging_freq": 1, "bagging_seed": 7}
    b_full = lgb.train(dict(base), lgb.Dataset(X, y), 6, verbose_eval=False)
    b_bag = lgb.train({**base, "bagging_fraction": 0.5},
                      lgb.Dataset(X, y), 6, verbose_eval=False)
    t_full, t_bag = _trees_of(b_full), _trees_of(b_bag)
    # bagged trees must see ~half the rows at their roots, every iteration
    for t in t_bag[1:]:
        assert t.internal_count[0] < 0.7 * X.shape[0]
    for t in t_full[1:]:
        assert t.internal_count[0] == X.shape[0]


# -- reset_parameter / ResetConfig (gbdt.cpp:704) -------------------------

def test_reset_parameter_learning_rate_schedule():
    X, y = _data()
    ds = lgb.Dataset(X, y)
    base = {"objective": "regression", "num_leaves": 15, "verbosity": -1,
            "learning_rate": 0.3}
    # decaying schedule vs constant: both must train, schedules differ
    b0 = lgb.train(dict(base), ds, 10, verbose_eval=False)
    b1 = lgb.train(dict(base), lgb.Dataset(X, y), 10, verbose_eval=False,
                   callbacks=[lgb.reset_parameter(
                       learning_rate=[0.3 * (0.9 ** i) for i in range(10)])])
    assert np.abs(b0.predict(X) - b1.predict(X)).max() > 1e-8


def test_reset_parameter_num_leaves_schedule():
    # static grower knob: later trees must respect the smaller cap
    X, y = _data()
    b = lgb.train({"objective": "regression", "num_leaves": 31,
                   "verbosity": -1}, lgb.Dataset(X, y), 6,
                  verbose_eval=False,
                  callbacks=[lgb.reset_parameter(
                      num_leaves=[31, 31, 31, 4, 4, 4])])
    trees = _trees_of(b)
    assert max(t.num_leaves for t in trees[:3]) > 4
    assert all(t.num_leaves <= 4 for t in trees[3:])


def test_reset_parameter_bagging_schedule():
    # bagging switched ON mid-training: later trees see fewer in-bag rows
    X, y = _data(n=2000)
    b = lgb.train({"objective": "regression", "num_leaves": 15,
                   "verbosity": -1, "bagging_seed": 7},
                  lgb.Dataset(X, y), 6, verbose_eval=False,
                  callbacks=[lgb.reset_parameter(
                      bagging_fraction=[1.0, 1.0, 1.0, 0.5, 0.5, 0.5],
                      bagging_freq=[0, 0, 0, 1, 1, 1])])
    trees = _trees_of(b)
    counts = [int(t.leaf_count[:t.num_leaves].sum()) for t in trees]
    assert counts[0] == 2000 and counts[1] == 2000 and counts[2] == 2000
    assert all(800 < c < 1200 for c in counts[3:])


def test_reset_parameter_bagging_masks_differ_across_iterations():
    # a CONSTANT bagging schedule must not reseed the bag RNG every
    # iteration (that would redraw the identical mask each time)
    X, y = _data(n=2000)
    masks = []

    class _Spy:
        order = 99
        before_iteration = False

        def __call__(self, env):
            masks.append(np.asarray(env.model._booster._bag_mask_dev))

    lgb.train({"objective": "regression", "num_leaves": 15,
               "verbosity": -1}, lgb.Dataset(X, y), 4, verbose_eval=False,
              callbacks=[lgb.reset_parameter(bagging_fraction=[0.5] * 4,
                                             bagging_freq=[1] * 4),
                         _Spy()])
    assert len(masks) == 4
    assert any(not np.array_equal(masks[0], m) for m in masks[1:])


def test_reset_parameter_constant_schedule_is_noop():
    # scheduling the param at its constant value must not change the model
    X, y = _data()
    base = {"objective": "regression", "num_leaves": 15, "verbosity": -1,
            "lambda_l2": 0.5}
    b0 = lgb.train(dict(base), lgb.Dataset(X, y), 8, verbose_eval=False)
    b1 = lgb.train(dict(base), lgb.Dataset(X, y), 8, verbose_eval=False,
                   callbacks=[lgb.reset_parameter(lambda_l2=[0.5] * 8)])
    np.testing.assert_allclose(b0.predict(X), b1.predict(X), atol=1e-12)


def test_reset_parameter_fixed_key_warns_not_crashes():
    X, y = _data()
    b = lgb.train({"objective": "regression", "num_leaves": 15,
                   "verbosity": -1}, lgb.Dataset(X, y), 3,
                  verbose_eval=False,
                  callbacks=[lgb.reset_parameter(max_bin=[64, 64, 64])])
    assert len(_trees_of(b)) == 3   # trained through, key ignored loudly


def test_booster_reset_parameter_api():
    X, y = _data()
    b = lgb.train({"objective": "regression", "num_leaves": 15,
                   "verbosity": -1}, lgb.Dataset(X, y), 3,
                  verbose_eval=False)
    b._booster  # Booster facade wraps the inner GBDT
    b.reset_parameter({"learning_rate": 0.01})
    assert b._booster.shrinkage_rate == 0.01


def test_reset_parameter_mixed_schedule_bagging_still_varies():
    # a changing lr + CONSTANT bagging keys: the constant keys must not
    # be re-applied (re-seeding the bag RNG) just because lr changed
    X, y = _data(n=2000)
    masks = []

    class _Spy:
        order = 99
        before_iteration = False

        def __call__(self, env):
            masks.append(np.asarray(env.model._booster._bag_mask_dev))

    lgb.train({"objective": "regression", "num_leaves": 15,
               "verbosity": -1}, lgb.Dataset(X, y), 4, verbose_eval=False,
              callbacks=[lgb.reset_parameter(
                  learning_rate=[0.3 * 0.9 ** i for i in range(4)],
                  bagging_fraction=[0.5] * 4, bagging_freq=[1] * 4),
                  _Spy()])
    assert any(not np.array_equal(masks[0], m) for m in masks[1:])


def test_reset_parameter_on_loaded_model():
    # prediction-only booster (no training state): config-level updates
    # apply, nothing crashes (LGBM_BoosterResetParameter contract)
    X, y = _data(n=500)
    b = lgb.train({"objective": "regression", "num_leaves": 15,
                   "verbosity": -1}, lgb.Dataset(X, y), 3,
                  verbose_eval=False)
    loaded = lgb.Booster(model_str=b.model_to_string())
    loaded.reset_parameter({"learning_rate": 0.05, "bagging_fraction": 0.5})
    assert loaded._booster.shrinkage_rate == 0.05
    np.testing.assert_allclose(loaded.predict(X), b.predict(X), atol=1e-12)


# -- every tpu_* knob has a reader -----------------------------------------

def _tpu_knobs():
    from lightgbm_tpu.config import PARAMS
    return [p.name for p in PARAMS if p.name.startswith("tpu_")]


@pytest.fixture(scope="module")
def names_read_by_the_package():
    """Every identifier, and every string literal that is one bare name
    (`getattr(config, "tpu_x", ...)`, `params.get("tpu_x")`), in package
    code outside config.py. Comments and docstrings are neither."""
    import ast
    import os
    import tokenize
    import lightgbm_tpu
    root = os.path.dirname(lightgbm_tpu.__file__)
    seen = set()
    for dirpath, _, files in os.walk(root):
        for name in files:
            path = os.path.join(dirpath, name)
            if not name.endswith(".py") or path == os.path.join(
                    root, "config.py"):
                continue
            with tokenize.open(path) as f:
                tokens = list(tokenize.generate_tokens(f.readline))
            for tok in tokens:
                if tok.type == tokenize.NAME:
                    seen.add(tok.string)
                elif tok.type == tokenize.STRING and len(tok.string) < 64:
                    try:
                        seen.add(ast.literal_eval(tok.string))
                    except (ValueError, SyntaxError):
                        pass             # f-strings
    return seen


@pytest.mark.parametrize("knob", _tpu_knobs())
def test_every_tpu_knob_is_read(knob, names_read_by_the_package):
    """A parameter that config.py declares and nothing reads is a
    configuration the tests and the benchmark would have to cover for
    nothing: delete it, or wire it."""
    assert knob in names_read_by_the_package
