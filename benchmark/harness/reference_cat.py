"""The plain reference for models with categorical splits: ``reference.py``'s
comparison, extended to what a categorical split decides and computes,
importing nothing of the program.

``reference.py`` refuses a model with a categorical split (``parse_model``)
and walks ``x <= threshold`` alone; this module is the same follower with
three things added:

* ``parse_model`` reads ``num_cat``, ``cat_boundaries``, ``cat_threshold`` and
  ``decision_type``: a node whose decision type has bit 0 set is categorical,
  its ``threshold`` is the index of its bitset among the tree's;
* the walk reads the RAW codes: at a categorical node a row goes left when
  ``int(x)`` is in the node's bitset (LightGBM's bitsets: bit ``c % 32`` of
  word ``c // 32``), and right when it is not, when it is negative, or when
  ``x`` is NaN and the node's missing type is NaN; a NaN at a node of another
  missing type reads as category 0 (``Tree::CategoricalDecision``,
  include/LightGBM/tree.h of v2.3.2);
* the leaf outputs and split gains carry ``cat_l2`` where
  ``FeatureHistogram::FindBestThresholdCategorical`` adds it
  (src/treelearner/feature_histogram.hpp of v2.3.2): a many-vs-many split (a
  feature of more than ``max_cat_to_onehot`` bins) scores its two children
  with ``lambda_l2 + cat_l2`` (``l2`` in the sorted scan) against the parent
  with ``lambda_l2`` alone (``min_gain_shift``), and the two leaves it makes
  take ``lambda_l2 + cat_l2`` in their outputs (``CalculateSplittedLeafOutput``
  with that ``l2``); a one-hot split uses ``lambda_l2`` throughout.

``configure`` is told, once per run, the configuration's ``lambda_l2``,
``cat_l2`` and which features split one-hot: those are the configuration's,
not the program's. Everything else is ``reference.py``'s, imported.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness.reference import (OBJECTIVES, binary_init, f32_floor, gaps,
                               lambdarank_grad, to_bfloat16)

__all__ = ["binary_init", "lambdarank_grad", "to_bfloat16", "configure",
           "parse_model", "follow", "expected", "compare"]

HIGHEST = jax.lax.Precision.HIGHEST
K_CATEGORICAL = 1          # decision_type bit 0

SETTINGS = {"lambda_l2": 0.0, "cat_l2": 10.0, "onehot": frozenset()}


def configure(lambda_l2, cat_l2, onehot=()):
    """The configuration's regularisation, and the features (model indices)
    whose categorical splits are one-hot."""
    SETTINGS.update(lambda_l2=float(lambda_l2), cat_l2=float(cat_l2),
                    onehot=frozenset(int(f) for f in onehot))


def parse_model(text):
    """``reference.parse_model``'s fields, plus ``num_cat``, ``cat_boundaries``
    and ``cat_threshold`` (uint32 words) of each tree."""
    kinds = {"split_feature": np.int64, "threshold": np.float64,
             "left_child": np.int64, "right_child": np.int64,
             "leaf_value": np.float64, "leaf_count": np.int64,
             "internal_count": np.int64, "split_gain": np.float64,
             "decision_type": np.int64, "cat_boundaries": np.int64,
             "cat_threshold": np.uint64}
    trees = []
    for block in text.split("\nTree=")[1:]:
        block = block.split("\nend of trees")[0]
        tree = {"num_cat": 0}
        for line in block.splitlines()[1:]:
            key, _, val = line.partition("=")
            if key in ("num_leaves", "num_cat"):
                tree[key] = int(val)
            elif key in kinds:
                tree[key] = np.asarray(val.split(), dtype=kinds[key])
        if "cat_threshold" in tree:
            tree["cat_threshold"] = tree["cat_threshold"].astype(np.uint32)
        trees.append(tree)
    return trees


def is_cat_node(tree):
    """[nodes] bool: which splits of ``tree`` are categorical."""
    n = tree["num_leaves"] - 1
    return (tree["decision_type"][:n] & K_CATEGORICAL) != 0 if n else \
        np.zeros(0, bool)


def node_bitset(tree, node):
    """The uint32 words of categorical node ``node``'s left set."""
    k = int(tree["threshold"][node])
    lo, hi = tree["cat_boundaries"][k], tree["cat_boundaries"][k + 1]
    return tree["cat_threshold"][lo:hi]


def tables(trees, max_leaves):
    """``reference.tables`` plus, per internal node, whether it is
    categorical, its missing type and its bitset (``words`` uint32 words,
    zero-padded; ``words`` the widest of the model, at least 1)."""
    T, L, N = len(trees), max_leaves, max_leaves - 1
    words = max([len(node_bitset(t, k)) for t in trees
                 for k in np.nonzero(is_cat_node(t))[0]] or [1])
    feat = np.zeros((T, N), np.int32)
    thr = np.full((T, N), np.inf, np.float32)
    cat = np.zeros((T, N), bool)
    nan_right = np.zeros((T, N), bool)
    bits = np.zeros((T, N, words), np.uint32)
    path = np.zeros((T, L, N), np.int8)
    plen = np.full((T, L), -1, np.int32)
    value = np.zeros((T, L), np.float32)
    for t, tree in enumerate(trees):
        n = tree["num_leaves"] - 1
        if n == 0:
            plen[t, 0] = 0
            value[t, 0] = tree["leaf_value"][0]
            continue
        feat[t, :n] = tree["split_feature"]
        c = is_cat_node(tree)
        cat[t, :n] = c
        thr[t, :n] = np.where(c, np.inf, f32_floor(
            np.where(c, 0.0, tree["threshold"][:n])))
        nan_right[t, :n] = ((tree["decision_type"][:n] >> 2) & 3) == 2
        for k in np.nonzero(c)[0]:
            b = node_bitset(tree, k)
            bits[t, k, :len(b)] = b
        value[t, :n + 1] = tree["leaf_value"]
        stack = [(0, [])]
        while stack:
            node, trail = stack.pop()
            for child, sign in ((tree["left_child"][node], 1),
                                (tree["right_child"][node], -1)):
                step = trail + [(node, sign)]
                if child < 0:
                    leaf = ~child
                    for k, s in step:
                        path[t, leaf, k] = s
                    plen[t, leaf] = len(step)
                else:
                    stack.append((int(child), step))
    return feat, thr, cat, nan_right, bits, path, plen, value


@functools.partial(jax.jit, static_argnames=("objective", "group", "low"))
def follow_block(x, label, valid, init, feat, thr, cat, nan_right, bits, path,
                 plen, value, objective, group, low):
    """``reference.follow_block`` with categorical nodes: per tree, per leaf
    (rows, gradient sum, hessian sum) over the block, [T, L, 3] f32."""
    xt = x.T
    grad = OBJECTIVES[objective]
    ok = valid.astype(jnp.float32)
    words = bits.shape[-1]

    def one_tree(score, tab):
        f, th, ca, nr, bw, pa, pl, val = tab
        g, h = grad(score, label, group)
        if low:
            g, h = (jax.lax.reduce_precision(v, exponent_bits=8,
                                             mantissa_bits=7) for v in (g, h))
        xv = xt[f]                                       # [N, B] raw values
        num_left = xv <= th[:, None]
        nan = jnp.isnan(xv)
        code = jnp.where(nan, 0.0, xv)
        neg = code < 0
        ci = jnp.where(neg, 0.0, jnp.floor(code)).astype(jnp.int32)
        wi = ci >> 5
        word = jnp.zeros(ci.shape, jnp.uint32)
        for k in range(words):                           # a select a word
            word = jnp.where(wi == k, bw[:, k:k + 1], word)
        in_set = ((word >> (ci & 31).astype(jnp.uint32)) & 1) == 1
        cat_left = in_set & ~neg & ~(nan & nr[:, None])
        left = jnp.where(ca[:, None], cat_left, num_left)
        sign = jnp.where(left, 1.0, -1.0).astype(jnp.bfloat16)
        met = jnp.dot(pa.astype(jnp.bfloat16), sign,
                      preferred_element_type=jnp.float32)
        leaf = met == pl[:, None].astype(jnp.float32)
        onehot = leaf.astype(jnp.float32)
        sums = jnp.dot(onehot, jnp.stack([ok, g * ok, h * ok], axis=1),
                       precision=HIGHEST)
        out = jnp.sum(jnp.where(leaf, val[:, None], 0.0), axis=0)
        return score + out, sums

    score0 = jnp.full(label.shape, init, jnp.float32)
    _, sums = jax.lax.scan(one_tree, score0, (feat, thr, cat, nan_right, bits,
                                              path, plen, value))
    return sums


def follow(blocks, trees, init, objective, group, max_leaves, low=False):
    """``reference.follow`` over a model with categorical splits: [T, L, 3]
    float64 (rows, G, H)."""
    tabs = tables(trees, max_leaves)
    value = tabs[-1]
    plen = tabs[-2]
    value[0] = np.where(plen[0] >= 0, value[0] - np.float32(init), 0.0)
    tabs = [jnp.asarray(a) for a in tabs]
    total = None
    pending = None
    for x, label, valid in blocks:
        sums = follow_block(x, label, valid, jnp.float32(init), *tabs,
                            objective=objective, group=group, low=low)
        if pending is not None:
            got = np.asarray(pending, np.float64)
            total = got if total is None else total + got
        pending = sums
    got = np.asarray(pending, np.float64)
    return got if total is None else total + got


def split_l2(tree):
    """[nodes] the l2 a node's split scores its children with: lambda_l2,
    and cat_l2 besides for a many-vs-many categorical split."""
    sorted_cat = is_cat_node(tree) & ~np.isin(
        tree["split_feature"][:tree["num_leaves"] - 1],
        list(SETTINGS["onehot"]))
    return SETTINGS["lambda_l2"] + np.where(sorted_cat, SETTINGS["cat_l2"],
                                            0.0)


def expected(tree, sums, learning_rate, bias):
    """``reference.expected`` with each split's own l2: leaf outputs
    -G / (H + l2) of the split that made the leaf, and each node's gain
    G_l^2 / (H_l + l2) + G_r^2 / (H_r + l2) - G^2 / (H + lambda_l2)."""
    nl = tree["num_leaves"]
    rows, G, H = sums[:nl, 0], sums[:nl, 1], sums[:nl, 2]
    l2p = SETTINGS["lambda_l2"]
    if nl == 1:
        with np.errstate(divide="ignore", invalid="ignore"):
            value = -G / (H + l2p) * learning_rate + bias
        return {"leaf_count": rows, "leaf_value": value,
                "internal_count": np.zeros(0), "split_gain": np.zeros(0)}
    n = nl - 1
    l2 = split_l2(tree)
    leaf_l2 = np.full(nl, l2p)
    for node in range(n):
        for child in (tree["left_child"][node], tree["right_child"][node]):
            if child < 0:
                leaf_l2[~child] = l2[node]
    with np.errstate(divide="ignore", invalid="ignore"):
        value = -G / (H + leaf_l2) * learning_rate + bias
    agg = np.zeros((n, 3))

    def below(child):
        return sums[~child] if child < 0 else agg[child]

    gain = np.zeros(n)
    for node in range(n - 1, -1, -1):
        lo = below(tree["left_child"][node])
        hi = below(tree["right_child"][node])
        agg[node] = lo + hi
        gain[node] = (lo[1] ** 2 / (lo[2] + l2[node])
                      + hi[1] ** 2 / (hi[2] + l2[node])
                      - agg[node][1] ** 2 / (agg[node][2] + l2p))
    return {"leaf_count": rows, "leaf_value": value,
            "internal_count": agg[:, 0], "split_gain": gain}


def compare(trees, sums, learning_rate, init, answers=None):
    """``reference.compare``'s numbers (count_mismatch, leaf_value_gap,
    split_gain_gap, median_leaf_gap) and per-tree rows, with ``expected`` of
    this module."""
    answers = trees if answers is None else answers
    numbers = {"count_mismatch": 0, "leaf_value_gap": 0.0,
               "split_gain_gap": 0.0}
    per_tree, all_leaves = [], []
    for t, (tree, ans) in enumerate(zip(trees, answers)):
        exp = expected(tree, sums[t], learning_rate, init if t == 0 else 0.0)
        m = int(np.sum(np.abs(ans["leaf_count"] - exp["leaf_count"]))
                + np.sum(np.abs(ans["internal_count"]
                                - exp["internal_count"])))
        lg = gaps(ans["leaf_value"], exp["leaf_value"])
        gg = gaps(ans["split_gain"], exp["split_gain"])
        all_leaves.append(lg)
        leaf = int(np.argmax(lg))
        where = {"leaf": leaf, "rows_G_H": [float(v) for v in sums[t][leaf]],
                 "value": float(ans["leaf_value"][leaf]),
                 "reference": float(exp["leaf_value"][leaf])}
        if gg.size:
            node = int(np.argmax(gg))
            where.update(node=node, gain=float(ans["split_gain"][node]),
                         reference_gain=float(exp["split_gain"][node]),
                         node_rows=float(exp["internal_count"][node]),
                         categorical=bool(is_cat_node(tree)[node]))
        per_tree.append((m, float(lg.max()), float(gg.max()) if gg.size
                         else 0.0, where))
        numbers["count_mismatch"] += m
        numbers["leaf_value_gap"] = max(numbers["leaf_value_gap"],
                                        per_tree[-1][1])
        numbers["split_gain_gap"] = max(numbers["split_gain_gap"],
                                        per_tree[-1][2])
    numbers["median_leaf_gap"] = float(np.median(np.concatenate(all_leaves)))
    return numbers, per_tree


def moved_category(trees):
    """A planted fault: one category moved from the left set to the right in
    one split (the middle tree's first categorical split, its most popular
    category on the left), the rest of the model as the run returned it."""
    import copy
    out = copy.deepcopy(trees)
    for t in range(len(out) // 2, len(out)):
        tree = out[t]
        nodes = np.nonzero(is_cat_node(tree))[0]
        if not len(nodes):
            continue
        k = int(tree["threshold"][nodes[0]])
        lo, hi = tree["cat_boundaries"][k], tree["cat_boundaries"][k + 1]
        for i in range(lo, hi):
            word = int(tree["cat_threshold"][i])
            if word:
                low_bit = word & -word
                tree["cat_threshold"][i] = np.uint32(word ^ low_bit)
                return out
    raise ValueError("no categorical split to move a category in")
