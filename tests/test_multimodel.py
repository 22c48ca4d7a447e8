"""Multi-model training subsystem (multimodel/) pins.

The contract under test: B boosters trained over ONE shared binned
Dataset through a model-axis vmap of the fused iteration are BIT-EXACT
vs the serial outer loop (one lgb.train per grid point), per-model knobs
riding as traced [B] inputs so the program count is independent of B.

  * B=1 vmapped-vs-scalar parity (model text + raw scores) on the
    unbundled HIGGS-like shape and the EFB-bundled Expo-like shape,
    across gbdt and goss;
  * B=4 sweep vs the serial loop with distinct learning rates AND
    bagging seeds (per-model bag masks as batched inputs);
  * active-mask inertness: an early-stopped lane freezes without
    perturbing its batchmates, and its truncated model matches serial;
  * engine.cv's device fast path (folds as lanes, per-fold bag masks
    over the full layout) reproduces the host fold loop bit-for-bit;
  * the compile-surface ladder (bucket_for / mm_ladder_bound) and the
    perf-gate registration of models_per_sec / sweep_compiles.

Batched-path assertions go through the tree_learner::mm_models counter:
parity would be trivially true if eligibility silently fell back to
serial, so every parity test first proves the vmapped path actually ran.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import multimodel
from lightgbm_tpu.data.synth import make_expo_like, make_higgs_like
from lightgbm_tpu.multimodel import batch, driver
from lightgbm_tpu.telemetry import events as telemetry

BASE = {"objective": "binary", "num_leaves": 15, "max_bin": 255,
        "verbosity": -1, "metric": "none", "learning_rate": 0.1}


@pytest.fixture(scope="module")
def higgs():
    X, y = make_higgs_like(2500)
    ds = lgb.Dataset(X, y, free_raw_data=False)
    ds.construct()
    return np.asarray(X), np.asarray(y), ds


@pytest.fixture(scope="module")
def expo():
    X, y = make_expo_like(2000, seed=3)
    ds = lgb.Dataset(X, y, free_raw_data=False)
    ds.construct()
    return np.asarray(X), np.asarray(y), ds


def _counted(fn, key="tree_learner::mm_models"):
    """Run ``fn`` with counters on; return (result, counter delta)."""
    was = telemetry.enabled()
    if not was:
        telemetry.enable("timers")
    c0 = telemetry.counts_snapshot().get(key, 0.0)
    try:
        out = fn()
        c1 = telemetry.counts_snapshot().get(key, 0.0)
    finally:
        if not was:
            telemetry.disable()
    return out, c1 - c0


def _assert_twin(swept, X, params, ds, rounds):
    """The swept booster must be bit-identical to its own serial loop."""
    ref = lgb.train(dict(params), ds, rounds, verbose_eval=False)
    assert swept.model_to_string() == ref.model_to_string()
    a = swept.predict(X, raw_score=True)
    b = ref.predict(X, raw_score=True)
    assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# B=1: the vmapped program vs the scalar one
# ---------------------------------------------------------------------------

def test_b1_parity_higgs_gbdt(higgs):
    X, y, ds = higgs
    out, d = _counted(
        lambda: multimodel.sweep([dict(BASE)], ds, num_boost_round=10))
    assert d == 1.0, "batched path did not run"
    _assert_twin(out[0], X, BASE, ds, 10)


@pytest.mark.slow  # extra goss step/grad program compiles
def test_b1_parity_higgs_goss(higgs):
    X, y, ds = higgs
    p = dict(BASE, boosting="goss")
    out, d = _counted(
        lambda: multimodel.sweep([p], ds, num_boost_round=10))
    assert d == 1.0, "batched path did not run"
    _assert_twin(out[0], X, p, ds, 10)


@pytest.mark.slow  # EFB-bundled layout compiles its own program family
@pytest.mark.parametrize("boosting", ["gbdt", "goss"])
def test_b1_parity_expo_bundled(expo, boosting):
    X, y, ds = expo
    p = dict(BASE, boosting=boosting)
    out, d = _counted(
        lambda: multimodel.sweep([p], ds, num_boost_round=8))
    assert d == 1.0, "batched path did not run"
    _assert_twin(out[0], X, p, ds, 8)


# ---------------------------------------------------------------------------
# B=4 sweep: distinct learning rates AND bagging seeds in one program
# ---------------------------------------------------------------------------

def test_sweep_b4_vs_serial_loop(higgs):
    X, y, ds = higgs
    grid = [dict(BASE, learning_rate=lr, bagging_fraction=0.7,
                 bagging_freq=1, bagging_seed=seed)
            for lr, seed in [(0.05, 1), (0.1, 2), (0.2, 3), (0.3, 4)]]
    out, d = _counted(
        lambda: multimodel.sweep(grid, ds, num_boost_round=10))
    assert d == 4.0, "batched path did not run for all 4 models"
    assert len(out) == 4
    texts = set()
    for bst, p in zip(out, grid):
        _assert_twin(bst, X, p, ds, 10)
        texts.add(bst.model_to_string())
    # the knobs really were per-model: four distinct models came back
    assert len(texts) == 4


@pytest.mark.slow  # compiles the fused 16-iteration block (k=16 + k=1 tail)
def test_sweep_b2_fused_block_vs_serial(higgs):
    X, y, ds = higgs
    grid = [dict(BASE, learning_rate=lr) for lr in (0.1, 0.25)]
    out, d = _counted(
        lambda: multimodel.sweep(grid, ds, num_boost_round=20))
    assert d == 2.0
    for bst, p in zip(out, grid):
        _assert_twin(bst, X, p, ds, 20)


def test_grid_expansion_and_group_identity(higgs):
    X, y, ds = higgs
    grid = multimodel.expand_grid(
        dict(BASE, learning_rate=[0.1, 0.2], min_gain_to_split=[0.0, 0.5]))
    assert len(grid) == 4
    assert sorted((g["learning_rate"], g["min_gain_to_split"])
                  for g in grid) == [(0.1, 0.0), (0.1, 0.5),
                                     (0.2, 0.0), (0.2, 0.5)]
    # traced knobs (learning_rate, min_gain_to_split) must NOT split the
    # static group: all four grid points share one compiled program chain
    members = [batch.Member(lgb.Booster(dict(p), ds), dict(p))
               for p in grid]
    kinds = [batch.eligibility(m) for m in members]
    assert all(k == ("scan", "") for k in kinds), kinds
    keys = {batch.group_key(m, "scan") for m in members}
    assert len(keys) == 1, "traced knobs leaked into the static group key"


# ---------------------------------------------------------------------------
# active-mask inertness: a stopped lane cannot perturb its batchmates
# ---------------------------------------------------------------------------

def test_active_mask_inertness(higgs):
    X, y, ds = higgs
    stopper = dict(BASE, min_gain_to_split=1e9)   # no split past iter 0
    normal = dict(BASE)
    out, d = _counted(
        lambda: multimodel.sweep([stopper, normal], ds,
                                 num_boost_round=10))
    assert d == 2.0
    # the stopper really stopped: constant tree 0, truncated at the
    # first round>=1 stub (same place the serial loop stops)
    assert out[0].model_to_string().count("Tree=") < 10
    _assert_twin(out[0], X, stopper, ds, 10)
    # ... and its frozen lane left the live batchmate untouched
    _assert_twin(out[1], X, normal, ds, 10)


# ---------------------------------------------------------------------------
# engine.cv device fast path: folds as lanes over the shared layout
# ---------------------------------------------------------------------------

def _run_cv(higgs, tpu_cv, nfold=3, rounds=8, **kw):
    X, y, ds_ = higgs
    ds = lgb.Dataset(X, label=kw.pop("label", y), free_raw_data=False)
    p = dict(BASE, seed=7, tpu_cv=tpu_cv)
    p.update(kw.pop("params", {}))
    return lgb.cv(p, ds, num_boost_round=rounds, nfold=nfold,
                  stratified=False, shuffle=True, seed=3, **kw)


def test_cv_device_parity(higgs):
    dev, d = _counted(lambda: _run_cv(
        higgs, "device", params={"metric": "auc"}))
    assert d == 3.0, "cv did not take the device fold-as-lane path"
    host = _run_cv(higgs, "off", params={"metric": "auc"})
    assert dev == host      # bitwise: same keys, same float lists


def test_cv_device_parity_bagged(higgs):
    bag = {"metric": "binary_logloss", "bagging_fraction": 0.6,
           "bagging_freq": 2, "bagging_seed": 11}
    dev, d = _counted(lambda: _run_cv(higgs, "device", params=bag))
    assert d == 3.0
    assert dev == _run_cv(higgs, "off", params=bag)


@pytest.mark.slow  # regression program family + three metric sets
def test_cv_device_parity_eval_train_metric(higgs):
    X, y, _ = higgs
    label = X[:, 0] * 2.0 + y
    p = {"objective": "regression", "metric": "l2"}
    dev, d = _counted(lambda: _run_cv(
        higgs, "device", nfold=4, label=label, params=p,
        eval_train_metric=True))
    assert d == 4.0
    host = _run_cv(higgs, "off", nfold=4, label=label, params=p,
                   eval_train_metric=True)
    assert dev == host
    assert any(k.startswith("train ") for k in dev)
    assert any(k.startswith("valid ") for k in dev)


@pytest.mark.slow  # trains to the early-stop point on both paths
def test_cv_device_early_stop_and_cvbooster(higgs):
    kw = dict(params={"metric": "binary_logloss", "learning_rate": 0.5,
                      "num_leaves": 7},
              rounds=30, early_stopping_rounds=3, return_cvbooster=True)
    dev, d = _counted(lambda: _run_cv(higgs, "device", **kw))
    assert d == 3.0
    host = _run_cv(higgs, "off", **kw)
    cbd, cbh = dev.pop("cvbooster"), host.pop("cvbooster")
    assert dev == host
    assert cbd.best_iteration == cbh.best_iteration
    assert len(cbd.boosters) == len(cbh.boosters) == 3
    for bd, bh in zip(cbd.boosters, cbh.boosters):
        # lane boosters ride the full train_set and carry tpu_cv in the
        # parameters dump, so header and tail differ; the trees
        # themselves must be bit-identical
        def trees(s):
            return s[s.index("Tree=0"):s.index("end of trees")]
        assert trees(bd.model_to_string()) == trees(bh.model_to_string())


def test_cv_off_never_touches_device_path(higgs):
    _, d = _counted(lambda: _run_cv(higgs, "off",
                                    params={"metric": "auc"}))
    assert d == 0.0


# ---------------------------------------------------------------------------
# compile-surface ladder + perf-gate registration
# ---------------------------------------------------------------------------

def test_bucket_ladder():
    assert [driver.bucket_for(b) for b in (1, 2, 3, 4, 5, 8, 9, 33, 64)] \
        == [1, 2, 4, 4, 8, 8, 16, 64, 64]
    with pytest.raises(ValueError):
        driver.bucket_for(0)
    with pytest.raises(ValueError):
        driver.bucket_for(driver.MM_MAX_BUCKET + 1)


def test_mm_ladder_bound_matches_bucket_count():
    from lightgbm_tpu.analysis import compile_audit
    buckets = {driver.bucket_for(b)
               for b in range(1, driver.MM_MAX_BUCKET + 1)}
    assert compile_audit.mm_ladder_bound() == len(buckets) == 7


def test_program_cache_is_bucket_keyed_not_width_keyed(higgs):
    """The program family is cached on the Dataset by compile-time key
    (never by B): a second sweep — even a wider one inside the same pow2
    bucket — registers zero new program families."""
    X, y, _ = higgs
    ds = lgb.Dataset(X, y, free_raw_data=False)   # fresh: empty cache
    ds.construct()
    grid3 = [dict(BASE, learning_rate=lr) for lr in (0.1, 0.15, 0.2)]
    _, d_cold = _counted(
        lambda: multimodel.sweep(grid3, ds, num_boost_round=4),
        key="tree_learner::mm_programs")
    _, d_warm = _counted(
        lambda: multimodel.sweep(grid3[:2] + [dict(BASE,
                                                   learning_rate=0.3),
                                              dict(BASE,
                                                   learning_rate=0.4)],
                                 ds, num_boost_round=4),
        key="tree_learner::mm_programs")
    assert d_cold >= 1.0          # the cold call built the program
    assert d_warm == 0.0          # B=3 and B=4 share the bucket-4 program
