"""Categorical columns on the persistent-payload path (ops/grow_persist.py,
ops/pallas_grow.py): against the v1 grower on the same seeded data, at the
kernels and in the plain reference the benchmark's categorical cell uses.

* the pack plan: a categorical group of more than 256 bins takes a 16-bit
  slot and two histogram rows; a numerical one stays refused;
* the forced persist path (``tpu_persist_scan=force``, the XLA kernel
  emulation: the v1 grower's own f64 split-find on the emulated planes)
  grows the v1 grower's trees, split for split and set for set, on a
  one-hot column (3 levels), many-vs-many columns, a 300-level column (the
  16-bit slot), a Zipf column whose rare levels the 99% rule cuts, and a
  mix with numerical columns; a level cut by the rule or never seen goes
  right at predict;
* the Mosaic kernels in interpret mode (f32 planes, the fused eval's
  ``cat_scan``) grow the v1 grower's first tree;
* ``split_pass``'s categorical arm against a direct reading of the bitset;
* ``benchmark/harness/reference_cat.py`` against the model the program
  returns;
* a layout with no categorical and no wide group builds the pack plan, the
  split_pass scalars and the lowered fused program it built before the
  categorical arm existed, its groups in feature order or not.
"""
import hashlib
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.data.dataset import BinnedDataset
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.ops import grow_persist as gp
from lightgbm_tpu.ops import pallas_grow as pg
from lightgbm_tpu.telemetry import events as telemetry
from lightgbm_tpu.treelearner.serial import SerialTreeLearner

ROUNDS = 16          # one fused batch: fewer rounds take the per-tree path
BASE = dict(objective="binary", num_leaves=15, min_data_in_leaf=5,
            min_data_per_group=5, cat_smooth=1.0, max_bin=255,
            min_data_in_bin=1, verbosity=-1)


def _columns(case, n, rng):
    """(X, y, categorical columns) of one case, level effects drawn per
    level (no two levels tie on purpose)."""
    eff = rng.normal(size=400)
    num = rng.normal(size=(n, 2))
    if case == "onehot":
        cat = rng.integers(0, 3, (n, 1))
    elif case == "many_vs_many":
        cat = np.stack([rng.integers(0, 20, n), rng.integers(0, 9, n)], 1)
    elif case == "wide_300":
        cat = rng.integers(0, 300, (n, 1))
    elif case == "cut_by_rule":
        # Zipf popularity over 400 levels: the rule keeps the levels that
        # cover 99% of the sample, the rest share the other bin
        cat = np.minimum(rng.zipf(1.2, (n, 1)) - 1, 399)
    else:                                          # mixed
        cat = np.stack([rng.integers(0, 3, n), rng.integers(0, 20, n),
                        rng.integers(0, 300, n)], 1)
    logit = num[:, 0] + sum(eff[(cat[:, j] * (7 + j)) % 400]
                            for j in range(cat.shape[1]))
    y = (logit + rng.normal(size=n) > 0).astype(np.float64)
    X = np.concatenate([cat.astype(np.float64), num], axis=1)
    return X, y, list(range(cat.shape[1]))


def _train(X, y, cats, persist, **more):
    params = dict(BASE, tpu_persist_scan="force" if persist else "off",
                  **more)
    before = telemetry.counts_snapshot()
    bst = lgb.train(params, lgb.Dataset(X, y, categorical_feature=cats),
                    ROUNDS)
    text = bst.model_to_string()
    after = telemetry.counts_snapshot()
    grew = {k: v - before.get(k, 0.0) for k, v in after.items()}
    return bst, text, grew


def _trees(text):
    return text.split("\nTree=")[1:]


def _field(tree, key):
    m = re.search("^" + key + "=(.*)$", tree, re.M)
    return m.group(1) if m else ""


def test_pack_plan_gives_a_wide_categorical_group_a_16bit_slot():
    widths = [12, 31, 7, 22, 293, 293, 255, 255]
    plan, nbw = gp._payload_plan(widths)
    # 16-bit slots first (half a word each), then bytes, then nibble pairs
    assert plan == ((2, 0, 15), (1, 0, 255), (2, 4, 15), (1, 8, 255),
                    (0, 0, 0xFFFF), (0, 16, 0xFFFF), (1, 16, 255),
                    (1, 24, 255))
    assert nbw == 3
    rows = pg.hist_plan(plan)
    assert len(rows) == 10
    assert rows[4:7] == ((0, 0, 0xFFFF), (0, 0, 0xFFFF, 256),
                         (0, 16, 0xFFFF))
    # no wide group: the plan is its own histogram plan
    narrow = gp._payload_plan([12, 31, 255])[0]
    assert pg.hist_plan(narrow) == narrow


@pytest.mark.parametrize("kind,bins,ok", [
    ("categorical", 300, True), ("numerical", 300, False),
    ("categorical", 600, False)])
def test_pack_gate_by_group_kind(kind, bins, ok):
    rng = np.random.default_rng(3)
    n = 20 * bins
    X = np.stack([rng.permutation(np.arange(n) % bins).astype(np.float64),
                  rng.normal(size=n)], 1)
    cfg = lgb.Config(dict(max_bin=bins if kind == "numerical" else 255,
                          min_data_in_bin=1, verbosity=-1, max_cat_to_onehot=4,
                          max_cat_threshold=32))
    ds = BinnedDataset.from_matrix(
        X, cfg, label=(X[:, 1] > 0).astype(float),
        categorical_features=[0] if kind == "categorical" else [])
    assert int(ds.group_widths().max()) > 256
    got, why = gp.persist_pack_ok(ds)
    assert got == ok, why
    if not ok:
        assert kind in why


CASES = ("onehot", "many_vs_many", "wide_300", "cut_by_rule", "mixed",
         "mixed_depth3")


@pytest.mark.parametrize("case", CASES)
def test_forced_persist_grows_the_v1_trees(case):
    rng = np.random.default_rng(sorted(CASES).index(case) + 17)
    X, y, cats = _columns(case.replace("_depth3", ""), 6000, rng)
    # a depth limit: the categorical candidate is gated by depth too
    more = {"max_depth": 3} if case.endswith("_depth3") else {}
    v1, v1_text, v1_counts = _train(X, y, cats, persist=False, **more)
    per, per_text, counts = _train(X, y, cats, persist=True, **more)
    assert v1_counts.get("tree_learner::persist_scan_trees", 0) == 0
    assert counts["tree_learner::persist_scan_trees"] == ROUNDS
    assert counts["tree_learner::cat_persist_trees"] == ROUNDS
    assert counts.get("tree_learner::v1_grow_trees", 0) == 0
    assert counts["tree_learner::cat_splits"] > 0
    assert counts["tree_learner::cat_splits"] <= counts["tree_learner::splits"]
    if case in ("wide_300", "mixed", "mixed_depth3"):
        assert telemetry.counts_snapshot()["ops::wide_groups"] == 1
    t1, t0 = _trees(per_text), _trees(v1_text)
    assert len(t1) == len(t0) == ROUNDS
    for a, b in zip(t0, t1):
        for key in ("split_feature", "decision_type", "threshold",
                    "cat_boundaries", "cat_threshold", "leaf_count",
                    "internal_count"):
            assert _field(a, key) == _field(b, key), key
        np.testing.assert_allclose(
            np.asarray(_field(b, "leaf_value").split(), float),
            np.asarray(_field(a, "leaf_value").split(), float),
            rtol=1e-9, atol=1e-12)
    # rows the training never saw: unseen levels, negative codes, NaN
    Xt = X[:200].copy()
    Xt[:50, 0], Xt[50:100, 0], Xt[100:150, 0] = 1000, -2, np.nan
    np.testing.assert_array_equal(per.predict(Xt), v1.predict(Xt))


def test_cut_and_unseen_levels_go_right():
    """A stump on a Zipf column: its left set holds kept levels only, and a
    level the 99% rule cut, a level never seen, a negative code and NaN all
    land in the right leaf."""
    rng = np.random.default_rng(11)
    X, y, cats = _columns("cut_by_rule", 6000, rng)
    bst, text, _ = _train(X[:, :1], y, cats, persist=True, num_leaves=2)
    mapper = bst._booster.train_data.bin_mappers[0]
    kept = {c for c in mapper.bin_2_categorical if c >= 0}
    cut = sorted(set(range(400)) - kept)
    assert cut, "the rule cut no level"
    tree = _trees(text)[1]
    words = np.asarray(_field(tree, "cat_threshold").split(), np.uint64)
    left = {32 * w + b for w in range(len(words)) for b in range(32)
            if (int(words[w]) >> b) & 1}
    assert left and left <= kept
    right = sorted(kept - left)
    probe = np.asarray([[right[0]], [cut[0]], [cut[-1]], [100000], [-1],
                        [np.nan], [min(left)]], np.float64)
    raw = bst.predict(probe, raw_score=True, num_iteration=2)
    # tree 0 is the constant init; tree 1 the stump: all but the last right
    assert np.all(raw[:-1] == raw[0]) and raw[-1] != raw[0]


def test_pallas_kernels_grow_the_v1_first_tree(monkeypatch):
    """The Mosaic kernels (interpret mode): f32 planes, 16-bit and nibble
    slots, the fused eval's cat_scan and split_pass's categorical arm. The
    first tree matches the v1 grower's split for split (f32 sums decide
    near-ties otherwise only later, on trees grown from f32 scores)."""
    rng = np.random.default_rng(29)
    X, y, cats = _columns("mixed", 4000, rng)
    _, v1_text, _ = _train(X, y, cats, persist=False)
    monkeypatch.setattr(SerialTreeLearner, "_persist_kernel_mode",
                        staticmethod(lambda: ("pallas", True)))
    _, text, counts = _train(X, y, cats, persist=True)
    assert counts["tree_learner::cat_persist_trees"] == ROUNDS
    a, b = _trees(v1_text)[0], _trees(text)[0]
    for key in ("split_feature", "decision_type", "cat_threshold",
                "leaf_count", "internal_count"):
        assert _field(a, key) == _field(b, key), key
    np.testing.assert_allclose(
        np.asarray(_field(b, "leaf_value").split(), float),
        np.asarray(_field(a, "leaf_value").split(), float), rtol=1e-4)


# -- split_pass: the categorical arm ------------------------------------------

NP, C, WPA, WP_LIVE = 2048, 256, 16, 12
NBW = WP_LIVE - 5
PLAN = ((0, 0, 0xFFFF), (0, 16, 255))     # a 16-bit group, then a byte


def _pass_payload(rng):
    pay = rng.integers(0, 2 ** 32, (WPA, NP), dtype=np.uint32)
    bins = rng.integers(0, 300, NP).astype(np.uint32)
    pay[0] = (pay[0] & np.uint32(0xFFFF0000)) | bins
    pay[NBW + 1] = rng.permutation(NP).astype(np.uint32)    # row ids
    pay[NBW + 2:NBW + 4] = rng.normal(size=(2, NP)).astype(
        np.float32).view(np.uint32)
    return pay, bins.astype(np.int64)


def _pass_scalars(s0, n, words, cat=1, thr=0):
    v = np.zeros(pg.S_CW0 + len(words), np.int32)
    v[pg.S_NCH], v[pg.S_S0], v[pg.S_NL] = -(-n // C), s0, n
    v[pg.S_MASK], v[pg.S_NB], v[pg.S_LE] = 0xFFFF, 300, 300
    v[pg.S_MT], v[pg.S_THR], v[pg.S_SMALL_L] = 2, thr, 1
    v[pg.S_CAT] = cat
    v[pg.S_CW0:] = np.asarray(words, np.uint32).view(np.int32)
    return v


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("cat", [1, 0], ids=["bitset", "threshold"])
@pytest.mark.parametrize("s0,n", [(133, 3 * C + 37), (256, 90), (0, 0)])
def test_split_pass_reads_the_bitset(impl, cat, s0, n):
    rng = np.random.default_rng(s0 + n)
    pay, bins = _pass_payload(rng)
    left_set = rng.random(320) < 0.4
    left_set[300:] = False
    words = np.zeros(10, np.uint32)
    for b in np.nonzero(left_set)[0]:
        words[b // 32] |= np.uint32(1 << (b % 32))
    thr = 150
    if impl == "pallas":
        fn = pg.make_split_pass(WPA, NP, len(pg.hist_plan(PLAN)),
                                pg.hist_plan(PLAN), NBW, C=C, interpret=True,
                                wp_live=WP_LIVE, _skip_hist=True,
                                cat_words=10)
    else:
        fn = gp.make_xla_split_pass(WPA, NP, 3, pg.hist_plan(PLAN), NBW,
                                    cat_words=10)
    pay2, _, n_left = fn(jnp.asarray(pay),
                         jnp.asarray(_pass_scalars(s0, n, words, cat, thr)))
    seg = slice(s0, s0 + n)
    go = left_set[bins[seg]] if cat else bins[seg] <= thr
    assert int(n_left) == int(go.sum())
    rid = pay[NBW + 1, seg]
    out = np.asarray(pay2)[NBW + 1]
    k = int(go.sum())
    assert sorted(out[s0:s0 + k]) == sorted(rid[go])
    assert sorted(out[s0 + k:s0 + n]) == sorted(rid[~go])
    # outside the segment nothing moved
    np.testing.assert_array_equal(out[:s0], pay[NBW + 1, :s0])
    np.testing.assert_array_equal(out[s0 + n:], pay[NBW + 1, s0 + n:])


# -- the benchmark's plain reference ------------------------------------------

def test_reference_cat_follows_the_program_model(monkeypatch):
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    monkeypatch.syspath_prepend(bench)
    from harness import reference_cat
    rng = np.random.default_rng(41)
    X, y, cats = _columns("mixed", 6000, rng)
    bst, text, _ = _train(X, y, cats, persist=True, cat_l2=10.0,
                          cat_smooth=10.0)
    trees = reference_cat.parse_model(text)
    assert sum(t["num_cat"] for t in trees) > 0
    init = float(np.log(y.mean() / (1 - y.mean())))
    reference_cat.configure(0.0, 10.0, onehot=[0])     # 3 levels: one-hot
    block = (jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.float32),
             jnp.ones(len(y), bool))
    sums = reference_cat.follow([block], trees, init, "binary", 1, 15)
    numbers, _ = reference_cat.compare(trees, sums, 0.1, init)
    assert numbers["count_mismatch"] == 0
    assert numbers["leaf_value_gap"] < 1e-3, numbers
    assert numbers["split_gain_gap"] < 1e-3, numbers
    # a category moved out of a left set puts rows elsewhere
    moved = reference_cat.moved_category(trees)
    sums2 = reference_cat.follow([block], moved, init, "binary", 1, 15)
    assert reference_cat.compare(moved, sums2, 0.1, init)[0][
        "count_mismatch"] > 0
    # cat_l2 left out of the outputs reads as a gap
    reference_cat.configure(0.0, 0.0, onehot=[0])
    assert reference_cat.compare(trees, sums, 0.1, init)[0][
        "leaf_value_gap"] > 1e-2
    reference_cat.configure(0.0, 10.0, ())


# -- cat-free layouts: the programs of before ---------------------------------

# sha256 of the lowered text of make_scan_driver's k=2 program for the
# cat-free dataset below, as the parent of the categorical arm lowered it
# (XLA emulation; Mosaic kernels in interpret mode). A change to the fused
# program of numerical layouts changes these: re-pin them in the PR that
# makes it, from this test's own numbers.
LOWERED = {
    "xla": "9ca9bb4b97a2f4f29335dcc875266e026b08f5117ac37c913696d4a84a70e64c",
    "pallas":
        "f45ac47707a5728560cead7058257b5c6733ef3c78f9abc61ebb6f95bf54130c",
}


@pytest.mark.parametrize("kernel_impl", ["xla", "pallas"])
def test_cat_free_layout_builds_the_programs_of_before(kernel_impl,
                                                       monkeypatch):
    rng = np.random.default_rng(5)
    X = rng.normal(size=(3000, 6))
    X[:, 5] = rng.integers(0, 9, 3000)
    y = (X[:, 0] + 0.3 * X[:, 5] > 0).astype(float)
    cfg = lgb.Config(dict(objective="binary", num_leaves=15, max_bin=63,
                          enable_bundle=False, verbosity=-1))
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    obj = create_objective("binary", cfg)
    obj.init(ds.metadata, ds.num_data)
    learner = SerialTreeLearner(cfg, ds)
    assets = gp.build_assets(ds, ds.metadata.label,
                             score64=kernel_impl == "xla")
    plan = assets.geometry[3]
    # five byte slots in group order, then the one nibble
    assert plan == tuple((g // 4, (g % 4) * 8, 255) for g in range(5)) + (
        (1, 8, 15),)
    assert pg.hist_plan(plan) == plan
    text = _lowered_driver(learner, obj, assets, kernel_impl, monkeypatch)
    assert hashlib.sha256(text.encode()).hexdigest() == LOWERED[kernel_impl]


# the same, for a cat-free layout whose storage groups are not in feature
# order (enable_bundle at its default: the sparse column sorts last)
LOWERED_PERMUTED = {
    "xla": "f579ad73014a60dc61a1241c429d358e8d916a268a05734ee40e54a1596a52ef",
    "pallas":
        "cb9d8993063c5708f3e583fc567d9edbf647e2e1681516fc475e4389795aab45",
}


@pytest.mark.parametrize("kernel_impl", ["xla", "pallas"])
def test_cat_free_permuted_groups_build_the_programs_of_before(
        kernel_impl, monkeypatch):
    rng = np.random.default_rng(5)
    X = rng.normal(size=(3000, 6))
    X[:, 5] = rng.integers(0, 9, 3000)
    X[rng.random(3000) < 0.6, 0] = 0.0
    y = (X[:, 0] + 0.3 * X[:, 5] > 0).astype(float)
    cfg = lgb.Config(dict(objective="binary", num_leaves=15, max_bin=63,
                          verbosity=-1))
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    assert ds.groups == [[1], [2], [3], [4], [5], [0]]
    obj = create_objective("binary", cfg)
    obj.init(ds.metadata, ds.num_data)
    learner = SerialTreeLearner(cfg, ds)
    assets = gp.build_assets(ds, ds.metadata.label,
                             score64=kernel_impl == "xla")
    assert list(assets.efb[0]) == [5, 0, 1, 2, 3, 4]
    text = _lowered_driver(learner, obj, assets, kernel_impl, monkeypatch)
    assert hashlib.sha256(text.encode()).hexdigest() == \
        LOWERED_PERMUTED[kernel_impl]


def _lowered_driver(learner, obj, assets, kernel_impl, monkeypatch):
    """Lowered text of make_scan_driver's k=2 program over a cat-free
    layout, checking on the way that no categorical arm is built."""
    built = {}
    real = pg.make_split_pass

    def spy(*a, **k):
        built["cat_words"] = k.get("cat_words", 0)
        return real(*a, **k)
    monkeypatch.setattr(gp, "make_split_pass", spy)
    gr = gp.make_persist_grower(assets, learner.meta, learner.grow_config,
                                interpret=True, kernel_impl=kernel_impl,
                                fix=learner.fix)
    assert not gr.has_cat
    if kernel_impl == "pallas":
        assert built["cat_words"] == 0      # N_SCALARS scalars, no arm
    mode, gfn = obj.device_gradients()
    run = gp.make_scan_driver(gr, learner.grow_config, 2, gfn,
                              grad_mode=mode, wrap_jit=False)
    S = jax.ShapeDtypeStruct

    def like(tree):
        return jax.tree.map(
            lambda a: S(np.shape(a), jnp.asarray(a).dtype), tree)
    args = (S(assets.pay0.shape, jnp.uint32), S((2, 6), jnp.bool_),
            S((2, 2), jnp.uint32), S((2,), jnp.int32),
            like(learner.params), S((), jnp.float64),
            like(obj.persist_grad_args()))
    text = jax.jit(run).lower(*args).as_text()
    assert "cat_scan" not in text
    return text
