"""Persistent-payload fast path under sharding: the K-iteration persist
scan on an 8-device CPU mesh (data-parallel learner, histogram-plane psum
inside the grow loop) must reproduce the single-payload persist scan tree
for tree (reference contract: data_parallel_tree_learner.cpp:163-250 —
reduce-scattered histograms give every rank identical split decisions).

tpu_persist_scan=force engages the XLA kernel emulation
(ops/grow_persist.make_xla_split_pass) off-TPU; both sides run the same
emulated kernels, so differences can only come from the sharding wiring
under test (per-shard payloads, shard-local geometry, psum'd stats).
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb

# the persist grower compiles large multi-stage programs (and most tests
# here shard them over the 8-virtual-device mesh): 7-140s each on the
# 2-core CPU CI host, ~14 min for the file — slow tier, not tier-1, but
# for the four fastest cases of the data-parallel and voting learners
# (17 s together), which stay unmarked so that tier-1 holds the sharded
# persist path whatever the benchmark's cells run, and the ranking fill's
# pos mode against row mode (8 s)
slow = pytest.mark.slow


N = 6144          # 8 shards x 768 rows
F = 6
ROUNDS = 16       # exactly one fused persist batch


def _data(seed=7):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(N, F))
    y = (X[:, 0] - 0.7 * X[:, 2] + 0.4 * X[:, 4]
         + rng.normal(size=N) * 0.25 > 0).astype(float)
    return X, y


def _train(X, y, learner, extra=None):
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 10, "max_bin": 63, "learning_rate": 0.2,
              "tpu_persist_scan": "force", "tree_learner": learner}
    if extra:
        params.update(extra)
    bst = lgb.train(params, lgb.Dataset(X, y), ROUNDS, verbose_eval=False)
    tl = bst._booster.tree_learner
    assert getattr(tl, "_persist_carry", None) is not None, \
        "persist fast path did not engage for tree_learner=%s" % learner
    return bst


def _tree_tuples(bst):
    """(structure, values): split features/thresholds/counts pinned exactly;
    leaf/internal values compared with f32 tolerance (psum of per-shard f32
    histogram partials rounds differently than a whole-data sum)."""
    model = bst.dump_model()
    if isinstance(model, str):
        model = json.loads(model)
    structure, values = [], []
    for t in model["tree_info"]:
        def walk(node):
            if "split_feature" in node:
                structure.append((node["split_feature"],
                                  round(float(node["threshold"]), 9),
                                  node["internal_count"]))
                walk(node["left_child"])
                walk(node["right_child"])
            else:
                structure.append(("leaf", node["leaf_count"]))
                values.append(float(node["leaf_value"]))
        walk(t["tree_structure"])
    return structure, np.asarray(values)


def test_persist_sharded_matches_persist_serial():
    assert len(jax.devices()) >= 8, "conftest provides 8 virtual devices"
    X, y = _data()
    bst_serial = _train(X, y, "serial")
    bst_sharded = _train(X, y, "data")
    s_serial, v_serial = _tree_tuples(bst_serial)
    s_sharded, v_sharded = _tree_tuples(bst_sharded)
    assert s_serial == s_sharded
    np.testing.assert_allclose(v_serial, v_sharded, rtol=2e-5, atol=2e-6)
    p1 = bst_serial.predict(X[:512])
    p2 = bst_sharded.predict(X[:512])
    np.testing.assert_allclose(p1, p2, rtol=1e-4, atol=1e-6)


@slow
def test_persist_matches_v1_grower():
    """The persist fast path (XLA kernel emulation) reproduces the v1
    masked/partitioned grower's trees: same splits and counts; values to
    f32 tolerance (v1 accumulates in f64 on CPU)."""
    X, y = _data(seed=23)
    base = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
            "min_data_in_leaf": 10, "max_bin": 63, "learning_rate": 0.2}
    bst_p = lgb.train({**base, "tpu_persist_scan": "force"},
                      lgb.Dataset(X, y), ROUNDS, verbose_eval=False)
    assert getattr(bst_p._booster.tree_learner, "_persist_carry",
                   None) is not None
    bst_v1 = lgb.train({**base, "tpu_persist_scan": "off"},
                       lgb.Dataset(X, y), ROUNDS, verbose_eval=False)
    s_p, v_p = _tree_tuples(bst_p)
    s_v1, v_v1 = _tree_tuples(bst_v1)
    assert s_p == s_v1
    np.testing.assert_allclose(v_p, v_v1, rtol=1e-3, atol=1e-5)


def _root_counts(bst):
    model = bst.dump_model()
    if isinstance(model, str):
        model = json.loads(model)
    out = []
    for t in model["tree_info"]:
        node = t["tree_structure"]
        out.append(node.get("internal_count", node.get("leaf_count", 0)))
    return np.asarray(out)


BAG = {"bagging_fraction": 0.8, "bagging_freq": 5}


@slow
def test_persist_bagging_counts_and_quality():
    """Device-side bagging on the persist path: root counts track the
    bagging fraction (exact in-bag count feeds the root statistics) and
    the model still learns."""
    X, y = _data(seed=31)
    bst = _train(X, y, "serial", extra=BAG)
    rc = _root_counts(bst)
    assert np.all(np.abs(rc / N - 0.8) < 0.05), rc / N
    acc = ((bst.predict(X) > 0.5) == y).mean()
    assert acc > 0.85, acc


@slow
def test_persist_bagging_sharded_matches_serial():
    """Bag masks hash GLOBAL row ids, so the sharded persist run redraws
    the identical bag and reproduces the serial persist trees."""
    X, y = _data(seed=37)
    bst_serial = _train(X, y, "serial", extra=BAG)
    bst_sharded = _train(X, y, "data", extra=BAG)
    s1, v1 = _tree_tuples(bst_serial)
    s2, v2 = _tree_tuples(bst_sharded)
    assert s1 == s2
    np.testing.assert_allclose(v1, v2, rtol=2e-5, atol=2e-6)


@slow
def test_persist_goss():
    """Device-side GOSS: warmup iterations keep every row
    (goss.hpp:126-131), sampled iterations keep ~(top_rate+other_rate) with
    the amplification preserving learning quality."""
    X, y = _data(seed=41)
    # learning_rate 0.2 -> 5 warmup iterations of the 16
    bst = _train(X, y, "serial",
                 extra={"boosting": "goss", "top_rate": 0.2,
                        "other_rate": 0.1})
    rc = _root_counts(bst)
    assert np.all(rc[:5] == N), rc[:5]
    frac = rc[5:] / N
    assert np.all(np.abs(frac - 0.3) < 0.05), frac
    acc = ((bst.predict(X) > 0.5) == y).mean()
    assert acc > 0.85, acc


def _data_mc(seed=51, k=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(N, F))
    y = ((X[:, 0] > 0.4).astype(int) + (X[:, 2] > -0.2).astype(int))
    return X, np.clip(y, 0, k - 1).astype(float)


@slow
@pytest.mark.parametrize("obj", ["multiclass", "multiclassova"])
def test_persist_multiclass_matches_v1(obj):
    """K-trees-per-iteration on the persist path (per-class snapshot
    gradients) reproduces the v1 grower's trees."""
    X, y = _data_mc()
    base = {"objective": obj, "num_class": 3, "num_leaves": 8,
            "verbosity": -1, "min_data_in_leaf": 10, "max_bin": 63,
            "learning_rate": 0.2}
    bst_p = lgb.train({**base, "tpu_persist_scan": "force"},
                      lgb.Dataset(X, y), ROUNDS, verbose_eval=False)
    assert getattr(bst_p._booster.tree_learner, "_persist_carry",
                   None) is not None, "persist did not engage for %s" % obj
    bst_v1 = lgb.train({**base, "tpu_persist_scan": "off"},
                       lgb.Dataset(X, y), ROUNDS, verbose_eval=False)
    assert bst_p.num_trees() == bst_v1.num_trees() == ROUNDS * 3
    # the first iteration matches to f32 precision; past that, the f32
    # persist scan's hessian-derived count recovery (multiclass hessians
    # 2p(1-p) sit near zero) can flip a min_data gate the f64 v1 scan
    # accepts — the reference GPU learner's gpu_use_dp=false trade — so
    # the full models compare by quality
    p_early = bst_p.predict(X[:512], num_iteration=1)
    v_early = bst_v1.predict(X[:512], num_iteration=1)
    np.testing.assert_allclose(p_early, v_early, rtol=1e-4, atol=1e-6)
    p1 = bst_p.predict(X)
    p2 = bst_v1.predict(X)
    assert p1.shape == (N, 3)
    yi = y.astype(int)
    ll_p = -np.mean(np.log(np.clip(p1[np.arange(N), yi], 1e-12, 1)))
    ll_v = -np.mean(np.log(np.clip(p2[np.arange(N), yi], 1e-12, 1)))
    assert abs(ll_p - ll_v) < 5e-3, (ll_p, ll_v)
    acc = (np.argmax(p1, axis=1) == yi).mean()
    assert acc > 0.8, acc


def test_persist_sharded_scores_row_ordered():
    """finalize_scores under shard_map returns globally row-ordered scores
    (global row ids with the shard offset subtracted; contiguous row
    shards)."""
    X, y = _data(seed=11)
    bst = _train(X, y, "data")
    inner = bst._booster
    inner._materialize_pending()
    # staged score == sum of tree outputs in row order
    staged = np.asarray(inner.train_score.score_device(0))
    pred_raw = bst.predict(X, raw_score=True)
    # order is the point here: a misplaced shard/rid would be off by O(1);
    # the payload carries scores in f32, predict sums trees in f64
    np.testing.assert_allclose(staged, pred_raw, rtol=1e-4, atol=1e-5)


@slow
def test_persist_f64_state_matches_f32(monkeypatch):
    """From EXACT_F32_ROWS rows on the persist leaf state keeps its counts
    and positions in i32 beside the f32 lanes (the f64 state this once
    forced went with PR 38); at small n the two must agree. In the widened
    XLA mode this file runs, the state is f64 either way, so this holds
    the threshold's plumbing; the Mosaic path's own case is
    tests/test_data_parallel_persist.py's."""
    import lightgbm_tpu.ops.grow_persist as GP
    X, y = _data(seed=61)
    base = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
            "min_data_in_leaf": 10, "max_bin": 63,
            "tpu_persist_scan": "force"}
    bst32 = lgb.train(dict(base), lgb.Dataset(X, y), ROUNDS,
                      verbose_eval=False)
    monkeypatch.setattr(GP, "EXACT_F32_ROWS", 1024)   # large_counts
    bst64 = lgb.train(dict(base), lgb.Dataset(X, y), ROUNDS,
                      verbose_eval=False)
    assert getattr(bst64._booster.tree_learner, "_persist_carry",
                   None) is not None
    s32, v32 = _tree_tuples(bst32)
    s64, v64 = _tree_tuples(bst64)
    assert s32 == s64
    np.testing.assert_allclose(v32, v64, rtol=1e-5, atol=1e-7)


def _data_rank(seed=71, docs=48):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(N, F))
    sig = X[:, 0] - 0.6 * X[:, 2] + rng.normal(size=N) * 0.5
    nq = N // docs
    s = sig.reshape(nq, docs)
    q = np.quantile(s, [0.5, 0.8, 0.95], axis=1)
    lab = ((s > q[0][:, None]).astype(int) + (s > q[1][:, None])
           + (s > q[2][:, None]))
    group = np.full(nq, docs, np.int32)
    return X, lab.reshape(-1).astype(float), group


def test_persist_lambdarank_pos_mode_matches_row_mode(monkeypatch):
    """Payload-position lambdarank gradients (one scatter through the
    row-id map, ops/grow_persist.fill_grad_pos) see exactly the score
    values the row-order round-trip mode sees, so the trees must match
    bit-for-bit on CPU."""
    X, y, group = _data_rank()
    base = {"objective": "lambdarank", "num_leaves": 15, "verbosity": -1,
            "min_data_in_leaf": 10, "max_bin": 63, "learning_rate": 0.2,
            "tpu_persist_scan": "force"}

    def run():
        bst = lgb.train(dict(base), lgb.Dataset(X, y, group=group),
                        ROUNDS, verbose_eval=False)
        assert getattr(bst._booster.tree_learner, "_persist_carry",
                       None) is not None, "persist did not engage"
        return bst

    bst_pos = run()
    obj = bst_pos._booster.objective
    assert obj.persist_grad_mode() == "pos"
    from lightgbm_tpu.objectives.rank import LambdarankNDCG
    monkeypatch.setattr(LambdarankNDCG, "payload_pos_fn",
                        lambda self: None)
    bst_row = run()
    assert bst_row._booster.objective.persist_grad_mode() == "row"
    s_pos, v_pos = _tree_tuples(bst_pos)
    s_row, v_row = _tree_tuples(bst_row)
    assert s_pos == s_row
    np.testing.assert_allclose(v_pos, v_row, rtol=1e-6, atol=1e-9)
    # and the model actually ranks: training NDCG@5 beats random order
    from lightgbm_tpu.metrics.dcg import (cal_dcg_at_k, cal_max_dcg_at_k,
                                          default_label_gain)
    lg = default_label_gain()
    pred = bst_pos.predict(X)
    nd = []
    off = 0
    for g in group:
        lab = y[off:off + g]
        sc = pred[off:off + g]
        off += g
        mx = cal_max_dcg_at_k(5, lab, lg)
        if mx > 0:
            nd.append(cal_dcg_at_k(5, lab, sc, lg) / mx)
    assert np.mean(nd) > 0.75, np.mean(nd)


@slow
def test_persist_mosaic_kernels_interpret_match_emulation(monkeypatch):
    """The production TPU kernel path (split_pass with _skip_hist +
    make_seg_hist post-partition histogram) run in Pallas INTERPRETER mode
    must reproduce the XLA-emulation trees — covers the Mosaic wiring
    (chunk DMA alignment rolls, lane masks, FIFO drains, seg_hist
    start/len) that the emulation-only tests never touch."""
    from lightgbm_tpu.treelearner.serial import SerialTreeLearner
    X, y = _data(seed=97)
    n_small, rounds = 2048, ROUNDS   # >= the fused batch size, so the
    Xs, ys = X[:n_small], y[:n_small]   # persist driver engages
    base = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
            "min_data_in_leaf": 10, "max_bin": 31, "learning_rate": 0.2,
            "tpu_persist_scan": "force"}
    bst_emu = lgb.train(dict(base), lgb.Dataset(Xs, ys), rounds,
                        verbose_eval=False)
    monkeypatch.setattr(SerialTreeLearner, "_persist_kernel_mode",
                        staticmethod(lambda: ("pallas", True)))
    bst_mos = lgb.train(dict(base), lgb.Dataset(Xs, ys), rounds,
                        verbose_eval=False)
    assert getattr(bst_mos._booster.tree_learner, "_persist_carry",
                   None) is not None
    s_e, v_e = _tree_tuples(bst_emu)
    s_m, v_m = _tree_tuples(bst_mos)
    assert s_e == s_m
    np.testing.assert_allclose(v_e, v_m, rtol=1e-4, atol=1e-6)
    # early-stopping trees (min_data exhausts the splits before num_leaves)
    # exercise the ZERO-GRID split_pass: the payload must pass through
    # unharmed even though no chunk steps run (interpret has no aliasing)
    stop = {**base, "num_leaves": 31, "min_data_in_leaf": 600}
    bst_s = lgb.train(dict(stop), lgb.Dataset(Xs, ys), rounds,
                      verbose_eval=False)
    s_s, _ = _tree_tuples(bst_s)
    nl = sum(1 for e in s_s if e[0] == "leaf")
    assert nl < rounds * 31, "expected early-stopped trees"
    monkeypatch.undo()
    bst_se = lgb.train(dict(stop), lgb.Dataset(Xs, ys), rounds,
                       verbose_eval=False)
    s_se, _ = _tree_tuples(bst_se)
    assert s_s == s_se


def test_persist_voting_full_vote_matches_data_parallel():
    """Voting-parallel on the sharded persist driver: with 2*top_k >= F
    every feature wins the vote, the selective psum covers the whole
    histogram, and the trees must match the data-parallel persist run
    (PV-tree exactness condition, voting_parallel_tree_learner.cpp:153)."""
    X, y = _data(seed=43)
    bst_data = _train(X, y, "data")
    bst_vote = _train(X, y, "voting", extra={"top_k": F})
    s_d, v_d = _tree_tuples(bst_data)
    s_v, v_v = _tree_tuples(bst_vote)
    assert s_d == s_v
    np.testing.assert_allclose(v_d, v_v, rtol=2e-5, atol=2e-6)


def test_persist_voting_small_vote_learns():
    """top_k below F engages the real PV-tree approximation: the model
    still learns (the reference makes the same accuracy trade)."""
    X, y = _data(seed=47)
    bst = _train(X, y, "voting", extra={"top_k": 2})
    acc = ((bst.predict(X) > 0.5) == y).mean()
    assert acc > 0.85, acc


@slow
def test_persist_weighted_matches_v1():
    """Sample weights ride the payload as one extra row and multiply into
    the gradients after the objective (grow_persist._apply_weight): the
    persist trees must reproduce the v1 weighted grower's."""
    X, y = _data(seed=53)
    rng = np.random.default_rng(8)
    w = rng.uniform(0.25, 4.0, N)
    base = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
            "min_data_in_leaf": 10, "max_bin": 63, "learning_rate": 0.2}
    ds_p = lgb.Dataset(X, y, weight=w)
    bst_p = lgb.train({**base, "tpu_persist_scan": "force"}, ds_p,
                      ROUNDS, verbose_eval=False)
    assert getattr(bst_p._booster.tree_learner, "_persist_carry",
                   None) is not None, "weighted persist did not engage"
    bst_v1 = lgb.train({**base, "tpu_persist_scan": "off"},
                       lgb.Dataset(X, y, weight=w), ROUNDS,
                       verbose_eval=False)
    s_p, v_p = _tree_tuples(bst_p)
    s_v1, v_v1 = _tree_tuples(bst_v1)
    assert s_p == s_v1
    np.testing.assert_allclose(v_p, v_v1, rtol=1e-3, atol=1e-5)


@slow
def test_persist_weighted_sharded_and_lambdarank():
    """Weighted runs on the sharded persist path and weighted lambdarank
    through the payload-position mode (weights multiply the lambdas,
    rank_objective.hpp:165-170)."""
    X, y = _data(seed=59)
    rng = np.random.default_rng(9)
    w = rng.uniform(0.5, 2.0, N)
    bst_s = _train_weighted(X, y, w, "serial")
    bst_d = _train_weighted(X, y, w, "data")
    s1, v1 = _tree_tuples(bst_s)
    s2, v2 = _tree_tuples(bst_d)
    assert s1 == s2
    # varied weights widen the f32 psum-vs-whole-sum rounding slightly
    np.testing.assert_allclose(v1, v2, rtol=1e-4, atol=2e-6)
    # weighted lambdarank: pos mode == row-order mode bit for bit (both
    # multiply weights in f64 before the f32 cast; the payload weight
    # row is NOT applied in pos mode, so weights act exactly once)
    Xr, yr, group = _data_rank(seed=61)
    wr = np.repeat(rng.uniform(0.5, 2.0, len(group)), group)
    base = {"objective": "lambdarank", "num_leaves": 15, "verbosity": -1,
            "min_data_in_leaf": 10, "max_bin": 63, "learning_rate": 0.2,
            "tpu_persist_scan": "force"}

    def run_rank():
        bst = lgb.train(dict(base),
                        lgb.Dataset(Xr, yr, group=group, weight=wr),
                        ROUNDS, verbose_eval=False)
        assert getattr(bst._booster.tree_learner, "_persist_carry",
                       None) is not None
        return bst

    bst_pos = run_rank()
    assert bst_pos._booster.objective.persist_grad_mode() == "pos"
    from lightgbm_tpu.objectives.rank import LambdarankNDCG
    import pytest as _pytest
    mp = _pytest.MonkeyPatch()
    try:
        mp.setattr(LambdarankNDCG, "payload_pos_fn", lambda self: None)
        bst_row = run_rank()
        assert bst_row._booster.objective.persist_grad_mode() == "row"
    finally:
        mp.undo()
    s_p, v_p = _tree_tuples(bst_pos)
    s_r, v_r = _tree_tuples(bst_row)
    assert s_p == s_r
    np.testing.assert_allclose(v_p, v_r, rtol=1e-6, atol=1e-9)


def _train_weighted(X, y, w, learner):
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 10, "max_bin": 63, "learning_rate": 0.2,
              "tpu_persist_scan": "force", "tree_learner": learner}
    bst = lgb.train(params, lgb.Dataset(X, y, weight=w), ROUNDS,
                    verbose_eval=False)
    assert getattr(bst._booster.tree_learner, "_persist_carry",
                   None) is not None
    return bst


def _data_sparse_bundled(seed=67, n=N, f_dense=3, f_sparse=9):
    """Mostly-zero indicator features that EFB greedily bundles into
    shared byte columns (multi-feature groups with the bin-0 sentinel)."""
    rng = np.random.default_rng(seed)
    Xd = rng.normal(size=(n, f_dense))
    # mutually exclusive indicators (a one-hot-encoded categorical):
    # zero conflicts, so greedy bundling packs them into one group
    Xs = np.zeros((n, f_sparse))
    owner = rng.integers(0, f_sparse * 3, n)     # most rows all-zero
    for j in range(f_sparse):
        hit = owner == j
        Xs[hit, j] = rng.uniform(1.0, 4.0, hit.sum())
    X = np.concatenate([Xd, Xs], axis=1)
    y = (X[:, 0] - 0.5 * X[:, 1] + 0.8 * (X[:, f_dense] > 0)
         + 0.6 * (X[:, f_dense + 1] > 0)
         + rng.normal(size=n) * 0.3 > 0.4).astype(float)
    return X, y


@slow
def test_persist_efb_bundled_matches_v1():
    """EFB-bundled datasets ride the persist path: the split kernel
    decodes the group byte through the feature's [LS, LE) range, the scan
    reads windowed group blocks, and the in-eval FixHistogram repairs the
    most_freq bins — trees must match the v1 grower's."""
    X, y = _data_sparse_bundled()
    base = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
            "min_data_in_leaf": 10, "max_bin": 63, "learning_rate": 0.2}
    ds = lgb.Dataset(X, y)
    bst_p = lgb.train({**base, "tpu_persist_scan": "force"}, ds,
                      ROUNDS, verbose_eval=False)
    inner = bst_p._booster.tree_learner.dataset
    assert len(inner.groups) < inner.num_features, \
        "expected EFB bundles in this synthetic"
    assert bool(np.any(inner.needs_fix))
    assert getattr(bst_p._booster.tree_learner, "_persist_carry",
                   None) is not None, "bundled persist did not engage"
    bst_v1 = lgb.train({**base, "tpu_persist_scan": "off"},
                       lgb.Dataset(X, y), ROUNDS, verbose_eval=False)
    # early iterations match exactly; past that the f32 FixHistogram
    # residual (child_total - window_sum, cancellation-prone) can flip a
    # near-tie the f64 v1 fix resolves the other way — the same
    # gpu_use_dp=false trade the multiclass test documents. Full models
    # compare by fit quality.
    p_early = bst_p.predict(X[:1024], num_iteration=4)
    v_early = bst_v1.predict(X[:1024], num_iteration=4)
    np.testing.assert_allclose(p_early, v_early, rtol=1e-4, atol=1e-6)
    acc_p = ((bst_p.predict(X) > 0.5) == y).mean()
    acc_v = ((bst_v1.predict(X) > 0.5) == y).mean()
    assert abs(acc_p - acc_v) < 0.01, (acc_p, acc_v)
    assert acc_p > 0.8, acc_p


@slow
def test_persist_efb_sharded_matches_serial():
    """Bundled persist under the 8-device mesh reproduces serial persist."""
    X, y = _data_sparse_bundled(seed=71)
    bst_s = _train(X, y, "serial")
    bst_d = _train(X, y, "data")
    s1, v1 = _tree_tuples(bst_s)
    s2, v2 = _tree_tuples(bst_d)
    assert s1 == s2
    np.testing.assert_allclose(v1, v2, rtol=1e-4, atol=2e-6)


@slow
def test_persist_goss_sharded_matches_serial():
    """Sharded GOSS redraws the serial bag exactly: the top-rate threshold
    is the GLOBAL k-th largest |g*h| via radix select on psum'd counts,
    and the keep/amplify draws hash global row ids."""
    X, y = _data(seed=73)
    extra = {"boosting": "goss", "top_rate": 0.2, "other_rate": 0.1}
    bst_s = _train(X, y, "serial", extra=extra)
    bst_d = _train(X, y, "data", extra=extra)
    # early predictions match exactly (identical threshold + draws); deep
    # into the run a row whose |g*h| sits at the threshold can flip on
    # the f32 psum-vs-whole-sum score drift, so full models compare by
    # quality
    p_s = bst_s.predict(X[:1024], num_iteration=8)
    p_d = bst_d.predict(X[:1024], num_iteration=8)
    np.testing.assert_allclose(p_s, p_d, rtol=1e-4, atol=1e-6)
    acc_s = ((bst_s.predict(X) > 0.5) == y).mean()
    acc_d = ((bst_d.predict(X) > 0.5) == y).mean()
    assert abs(acc_s - acc_d) < 0.01, (acc_s, acc_d)
    assert acc_s > 0.85, acc_s
