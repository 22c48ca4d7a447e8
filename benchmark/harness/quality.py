"""Held-out quality of a model, by the benchmark's own arithmetic."""
import numpy as np


def auc(label, pred, group=None):
    """Area under the ROC curve (rank statistic; ties keep input order)."""
    del group
    order = np.argsort(pred, kind="mergesort")
    y = np.asarray(label)[order] > 0
    n_pos = float(y.sum())
    n_neg = float(len(y) - n_pos)
    ranks = np.arange(1, len(y) + 1, dtype=np.float64)
    return float((ranks[y].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg))


def ndcg10(label, pred, group):
    """Mean NDCG@10 over queries of ``group`` documents, gains 2^grade - 1;
    a query with no relevant document counts 1, as LightGBM's metric does."""
    lab = np.asarray(label, np.float64).reshape(-1, group)
    s = np.asarray(pred, np.float64).reshape(-1, group)
    k = min(10, group)
    disc = 1.0 / np.log2(2.0 + np.arange(k))
    gain = 2.0 ** lab - 1.0
    top = np.take_along_axis(gain, np.argsort(-s, axis=1, kind="mergesort"),
                             axis=1)[:, :k]
    best = -np.sort(-gain, axis=1)[:, :k]
    dcg, ideal = top @ disc, best @ disc
    return float(np.mean(np.where(ideal > 0, dcg / np.where(ideal > 0, ideal, 1),
                                  1.0)))


METRICS = {"auc": auc, "ndcg10": ndcg10}
