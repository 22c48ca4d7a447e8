"""Streaming CSR ingest (BinnedDataset.from_sparse): bounded host memory,
parity with the dense path, wide-sparse training end to end.

Reference behavior: DatasetLoader streams sparse rows through PushOneRow
(src/io/dataset_loader.cpp:714-1004) without a dense staging matrix; EFB
bundles sparse features (dataset.cpp:97-234)."""
import numpy as np
import pytest

scipy_sparse = pytest.importorskip("scipy.sparse")

import lightgbm_tpu as lgb


def _sparse_data(n=5000, nf=300, density=0.02, seed=11):
    rng = np.random.default_rng(seed)
    X = scipy_sparse.random(n, nf, density=density, format="csr",
                            random_state=np.random.RandomState(seed),
                            data_rvs=lambda k: rng.normal(size=k))
    w = rng.normal(size=nf) * (rng.random(nf) < 0.1)
    y = (np.asarray(X @ w).ravel() + rng.normal(size=n) * 0.2 > 0).astype(
        np.float64)
    return X.tocsr(), y


def test_sparse_matches_dense_binning():
    # binning parity on the dense [N, G] layout — the ELL layout the
    # sparse path now auto-picks is covered by tests/test_multival.py
    X, y = _sparse_data()
    params = {"objective": "binary", "verbosity": -1, "num_leaves": 15,
              "min_data_in_leaf": 5, "tpu_multival": "off"}
    ds_sp = lgb.Dataset(X, y, params=dict(params))
    ds_sp.construct()
    ds_dn = lgb.Dataset(np.asarray(X.todense()), y, params=dict(params))
    ds_dn.construct()
    a, b = ds_sp._inner, ds_dn._inner
    assert a.num_data == b.num_data
    assert a.used_features == b.used_features
    assert [m.num_bin for m in a.bin_mappers] == \
        [m.num_bin for m in b.bin_mappers]
    assert a.groups == b.groups
    assert np.array_equal(a.binned, b.binned)


def test_sparse_train_and_predict():
    X, y = _sparse_data()
    params = {"objective": "binary", "verbosity": -1, "num_leaves": 15,
              "min_data_in_leaf": 5, "metric": "none"}
    bst = lgb.train(dict(params), lgb.Dataset(X, y), 10, verbose_eval=False)
    pred = bst.predict(np.asarray(X.todense()))
    from sklearn.metrics import roc_auc_score
    assert roc_auc_score(y, pred) > 0.7


def test_sparse_never_densifies(monkeypatch):
    """The full todense() must never be called on the whole matrix — only
    row chunks (bounded memory)."""
    X, y = _sparse_data(n=4000, nf=20000, density=0.002)
    max_rows = [0]
    orig = scipy_sparse.csr_matrix.todense

    def spy(self, *a, **k):
        max_rows[0] = max(max_rows[0], self.shape[0])
        return orig(self, *a, **k)
    monkeypatch.setattr(scipy_sparse.csr_matrix, "todense", spy)
    ds = lgb.Dataset(X, y, params={"verbosity": -1})
    ds.construct()
    assert max_rows[0] < 4000, "full matrix was densified"


def test_sparse_reference_alignment():
    X, y = _sparse_data()
    Xv, yv = _sparse_data(n=1000, seed=12)
    params = {"objective": "binary", "verbosity": -1, "num_leaves": 15,
              "min_data_in_leaf": 5}
    ds = lgb.Dataset(X, y, params=dict(params))
    ds.construct()
    dv = lgb.Dataset(Xv, yv, params=dict(params), reference=ds)
    dv.construct()
    assert dv._inner.total_bins == ds._inner.total_bins
    assert dv._inner.groups == ds._inner.groups


@pytest.mark.slow  # tier-1 870s budget: cheaper sibling tests cover this area
def test_sparse_predict_chunked_matches_dense():
    """Booster.predict on scipy CSR streams row blocks (no whole-matrix
    densify; reference PredictForCSR analog) and matches dense predict."""
    sp = pytest.importorskip("scipy.sparse")
    rng = np.random.default_rng(5)
    n, f = 70_000, 400
    X = sp.random(n, f, density=0.01, format="csr", random_state=3,
                  data_rvs=lambda k: rng.normal(size=k))
    y = (np.asarray(X[:, 0].todense()).ravel()
         + np.asarray(X[:, 3].todense()).ravel() > 0.01).astype(float)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1, "max_bin": 63},
                    lgb.Dataset(X, y), 5, verbose_eval=False)
    # chunking engages: 32MB / (400*8B) ~ 10k rows per block < n
    p_sparse = bst.predict(X)
    p_dense = bst.predict(np.asarray(X[:20_000].todense(), np.float64))
    assert p_sparse.shape == (n,)
    np.testing.assert_allclose(p_sparse[:20_000], p_dense, rtol=1e-12)
    c = bst.predict(X[:15_000], pred_contrib=True)
    assert c.shape == (15_000, f + 1)


# ---------------------------------------------------------------------------
# binning by stored values (PR 32): `from_sparse` writes only the rows that
# have a stored value in a group, and must still equal the dense binning of
# the same matrix cell for cell
# ---------------------------------------------------------------------------

def _onehot_blocks(rng, n, cards):
    cols, base = [], 0
    for card in cards:
        cols.append(base + rng.integers(0, card, n))
        base += card
    cols = np.stack(cols, axis=1)
    return scipy_sparse.csr_matrix(
        (np.ones(cols.size), cols.ravel(),
         np.arange(n + 1) * len(cards)), shape=(n, base))


def _case_onehot(rng, n):
    return _onehot_blocks(rng, n, [12, 31, 7, 60]), {}


def _case_explicit_zeros_and_negatives(rng, n):
    X = scipy_sparse.random(n, 40, density=0.05, format="csr",
                            random_state=np.random.RandomState(5),
                            data_rvs=lambda k: rng.normal(size=k))
    X.data[::3] = 0.0                       # stored zeros stay stored
    assert (X.data == 0).any() and (X.data < 0).any()
    return X, {}


def _case_nan(rng, n):
    X = scipy_sparse.random(n, 30, density=0.08, format="csr",
                            random_state=np.random.RandomState(6),
                            data_rvs=lambda k: rng.normal(size=k))
    X.data[::5] = np.nan
    return X.astype(np.float32), {}         # float32 stored values too


def _case_empty_columns(rng, n):
    X = _onehot_blocks(rng, n, [9, 20]).tolil()
    X[:, 3] = 0
    X[:, 11] = 0
    return scipy_sparse.hstack(
        [X.tocsr(), scipy_sparse.csr_matrix((n, 4))]).tocsr(), {}


def _case_most_frequent_bin_is_not_zero(rng, n):
    # columns 0-2 are 1.0 on four rows of five: the most frequent bin holds
    # the ones, and the rows WITHOUT a stored value are the ones that move
    dense = np.zeros((n, 8))
    for f in range(3):
        dense[rng.random(n) < 0.8, f] = 1.0
    dense[:, 3:] = _onehot_blocks(rng, n, [5]).toarray()
    return scipy_sparse.csr_matrix(dense), {}


def _case_conflicts_inside_a_bundle(rng, n):
    # two one-hot blocks that overlap on a few rows in a hundred: with
    # max_conflict_rate > 0 they share bundles and the later feature wins
    X = _onehot_blocks(rng, n, [40]).tolil()
    extra = rng.integers(0, 40, n)
    for r in np.nonzero(rng.random(n) < 0.03)[0]:
        X[r, extra[r]] = 1.0
    return X.tocsr(), {"max_conflict_rate": 0.1}


def _case_duplicates_not_canonical(rng, n):
    rows = rng.integers(0, n, 6 * n)
    cols = rng.integers(0, 25, 6 * n)
    coo = scipy_sparse.coo_matrix((rng.normal(size=6 * n), (rows, cols)),
                                  shape=(n, 25))
    X = scipy_sparse.csr_matrix((coo.data, coo.col,
                                 np.searchsorted(np.sort(coo.row),
                                                 np.arange(n + 1))),
                                shape=(n, 25))
    assert not X.has_canonical_format
    return X, {}


_CSR_CASES = {f.__name__[6:]: f for f in (
    _case_onehot, _case_explicit_zeros_and_negatives, _case_nan,
    _case_empty_columns, _case_most_frequent_bin_is_not_zero,
    _case_conflicts_inside_a_bundle, _case_duplicates_not_canonical)}


@pytest.mark.parametrize("route", ["native", "numpy"])
@pytest.mark.parametrize("case", sorted(_CSR_CASES))
def test_binning_by_stored_values_equals_dense_binning(case, route,
                                                       monkeypatch):
    from lightgbm_tpu.data.dataset import BinnedDataset
    took = []
    real = BinnedDataset._push_sparse_native
    monkeypatch.setattr(
        BinnedDataset, "_push_sparse_native",
        lambda self, X, out: took.append(
            route == "native" and real(self, X, out)) or took[-1])
    rng = np.random.default_rng(7)
    X, extra = _CSR_CASES[case](rng, 3000)
    before = X.copy()
    monkeypatch.setattr(
        scipy_sparse.csr_matrix, "todense",
        lambda self, *a, **k: pytest.fail("from_sparse made a dense chunk"))
    params = {"verbosity": -1, "min_data_in_leaf": 1, "min_data_in_bin": 1,
              "tpu_multival": "off", **extra}
    sp_ds = lgb.Dataset(X, params=dict(params)).construct()._inner
    dn_ds = lgb.Dataset(before.toarray(), params=dict(params)) \
        .construct()._inner
    assert took == [route == "native"], "the native kernel did not build"
    assert sp_ds.groups == dn_ds.groups
    if case != "nan":
        assert any(len(g) > 1 for g in sp_ds.groups) or \
            case == "duplicates_not_canonical"
    assert sp_ds.binned.dtype == dn_ds.binned.dtype
    assert np.array_equal(sp_ds.binned, dn_ds.binned)
    # the caller's matrix is left as it was handed over
    assert np.array_equal(X.data, before.data, equal_nan=True)
    assert np.array_equal(X.indices, before.indices)
