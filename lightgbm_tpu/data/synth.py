"""Synthetic datasets in the reference's experiment shapes.

The shapes mirror the reference's experiment sets (docs/Experiments.rst):
HIGGS-like continuous kinematics for the throughput north star, the
MS-LTR and Yahoo-LTR ranking shapes, the Expo EFB-bundled one-hot shape,
and the Allstate sparse wide-one-hot shape. Kept inside the package so
the profiling CLI (``python -m lightgbm_tpu.profile``), ``chip_smoke.py``
and the tests all draw the same data. The benchmark has generators of its
own (``benchmark/generators/``).
"""
from __future__ import annotations

import numpy as np


def make_higgs_like(n_rows: int, n_features: int = 28, seed: int = 7):
    """Synthetic stand-in for HIGGS: continuous kinematic-like features,
    nonlinear decision boundary, ~53/47 class balance like the real set."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n_rows, n_features)).astype(np.float32)
    # a few derived-feature couplings like HIGGS's high-level features
    X[:, 21] = np.abs(X[:, 0] * X[:, 1]) + 0.3 * X[:, 21]
    X[:, 22] = X[:, 2] ** 2 + X[:, 3] ** 2 + 0.3 * X[:, 22]
    logit = (0.8 * X[:, 0] - 0.5 * X[:, 1] + 0.4 * X[:, 21]
             - 0.3 * X[:, 22] + 0.5 * np.tanh(X[:, 4] * X[:, 5]))
    y = (logit + rng.logistic(size=n_rows).astype(np.float32) * 0.8 > 0.0)
    return X.astype(np.float64), y.astype(np.float64)


def make_ltr_like(n_rows=2_270_000, n_feat=137, docs_per_query=73, seed=3):
    """MSLR-WEB30K-shaped synthetic LTR set: graded 0-4 relevance driven by
    a sparse linear + nonlinear signal, fixed-size query groups."""
    rng = np.random.default_rng(seed)
    n_q = n_rows // docs_per_query
    n_rows = n_q * docs_per_query
    X = rng.normal(size=(n_rows, n_feat)).astype(np.float32)
    w = np.zeros(n_feat)
    w[:20] = rng.normal(size=20)
    sig = X @ w + 0.7 * np.tanh(X[:, 20] * X[:, 21]) \
        + rng.logistic(size=n_rows) * 1.2
    # per-query grading to 0..4 by quantile
    sig = sig.reshape(n_q, docs_per_query)
    q = np.quantile(sig, [0.55, 0.75, 0.90, 0.97], axis=1)
    lab = (sig > q[0][:, None]).astype(np.int32)
    for k in range(1, 4):
        lab += sig > q[k][:, None]
    group = np.full(n_q, docs_per_query, dtype=np.int32)
    return X.astype(np.float64), lab.reshape(-1).astype(np.float64), group


def make_yahoo_like(n_rows=473_134, n_feat=700, docs_per_query=24, seed=11):
    """Yahoo LTR set1-shaped synthetic: 473k docs x 700 dense features in
    ~24-doc queries (docs/Experiments.rst lists 473,134 x 700)."""
    return make_ltr_like(n_rows, n_feat=n_feat,
                         docs_per_query=docs_per_query, seed=seed)


# The reference's Expo experiment (docs/Experiments.rst: 11M x 700, the
# airline on-time data one-hot coded): Month, DayofMonth, DayOfWeek,
# UniqueCarrier, Origin, Dest levels + DepTime + Distance = 700 columns.
# benchmark/generators/expo_like.py makes the same columns on the device.
EXPO_CARDS = (12, 31, 7, 22, 313, 313)
EXPO_NUMERICS = 2


def make_expo_like(n_rows=2_000_000, seed=0):
    """Expo-shaped synthetic, dense: two numerics, then the source's six
    one-hot blocks (698 columns), which EFB bundles into 14 storage groups
    at full size (16 with the numerics). 700 columns."""
    rng = np.random.default_rng(seed)
    nd = EXPO_NUMERICS
    blocks = list(EXPO_CARDS)
    Xd = rng.normal(size=(n_rows, nd)).astype(np.float32)
    cols = [Xd]
    sig = Xd[:, 0] * 0.5
    for card in blocks:
        # level popularity Zipf(1), as airports and carriers are skewed
        p = 1.0 / np.arange(1, card + 1)
        ids = rng.choice(card, size=n_rows, p=p / p.sum())
        oh = np.zeros((n_rows, card), np.float32)
        oh[np.arange(n_rows), ids] = 1.0
        cols.append(oh)
        sig = sig + (ids % 7 == 0) * 0.4
    X = np.concatenate(cols, axis=1)
    y = (sig + rng.logistic(size=n_rows) * 0.7 > 0.3)
    # f32 halves the ~10GB peak a dense f64 one-hot matrix would cost;
    # the binner accepts any float input
    return X, y.astype(np.float64)


def make_allstate_like(n_rows=1_000_000, seed=5):
    """Allstate-shaped synthetic (docs/Experiments.rst: 13.18M x 4228
    mostly one-hot columns): ~55 categorical blocks one-hot-expanded to
    ~4.1k binary features plus a few numerics, returned as a scipy CSR so
    the dense matrix is never materialized (the sparse-ingest path bins it
    chunk-wise; EFB re-bundles each block into byte groups)."""
    import scipy.sparse as sp
    rng = np.random.default_rng(seed)
    # cardinalities roughly log-spaced like an insurance schema: a few
    # huge blocks, many small ones — ~4.1k one-hot columns total
    # 4218 one-hot columns + 8 numerics ~= the 4228 reference columns
    cards = ([900, 600, 500, 350, 300, 250, 180, 120, 100, 80, 60, 50]
             + [40] * 6 + [25] * 8 + [12] * 12 + [7] * 12 + [4] * 15)
    nd = 8                       # leading dense numeric columns
    n_feat = nd + sum(cards)
    dense = rng.normal(size=(n_rows, nd)).astype(np.float32)
    rows = np.repeat(np.arange(n_rows, dtype=np.int64), nd)
    cols = np.tile(np.arange(nd, dtype=np.int64), n_rows)
    data = [dense.reshape(-1)]
    col_blocks = [cols]
    row_blocks = [rows]
    sig = dense[:, 0] * 0.4 - 0.3 * dense[:, 1]
    base = nd
    ar = np.arange(n_rows, dtype=np.int64)
    for card in cards:
        ids = rng.integers(0, card, n_rows)
        row_blocks.append(ar)
        col_blocks.append(base + ids.astype(np.int64))
        data.append(np.ones(n_rows, np.float32))
        sig = sig + (ids % 5 == 0) * (0.5 if card >= 100 else 0.15)
        base += card
    X = sp.csr_matrix(
        (np.concatenate(data),
         (np.concatenate(row_blocks), np.concatenate(col_blocks))),
        shape=(n_rows, n_feat))
    y = (sig + rng.logistic(size=n_rows).astype(np.float32) * 0.8 > 0.6)
    return X, y.astype(np.float64)
