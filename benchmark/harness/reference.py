"""The plain reference: what a gradient-boosted tree trainer has to compute,
in straightforward jax.numpy, importing nothing of the program.

It does not grow trees of its own (two growers that each take the best of
thousands of near-tied candidates part ways on rounding, and agree on nothing
after the first such split). It follows the trees the timed path produced and
recomputes, from the raw rows and labels alone, every quantity the program
derived while growing them:

* which leaf each training row falls in, by walking the returned model on the
  raw feature values (binning, partition, the host model's thresholds);
* the score of every row before each tree (the score update), starting from
  the objective's own initial score;
* the gradient and hessian of every row before each tree (the gradient fill:
  binary log-loss, or LambdaRank-NDCG as rank_objective.hpp states it);
* per leaf the row count, gradient sum and hessian sum (the histograms, from
  which the program takes them), hence the leaf outputs and the gain of every
  split the program chose (the split scan's arithmetic).

``follow`` returns those per-tree sums; ``compare`` turns them into the numbers
that decide ``correct``. ``low=True`` with ``to_bfloat16`` on the sums computes
the control: the same reference with gradients, hessians and their per-leaf
sums held in the next lower precision.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# the model as the program returned it (LightGBM's text format)
# ---------------------------------------------------------------------------

def parse_model(text):
    """[{num_leaves, split_feature, threshold, left_child, right_child,
    leaf_value, leaf_count, internal_count, split_gain}] from model text."""
    kinds = {"split_feature": np.int64, "threshold": np.float64,
             "left_child": np.int64, "right_child": np.int64,
             "leaf_value": np.float64, "leaf_count": np.int64,
             "internal_count": np.int64, "split_gain": np.float64,
             "decision_type": np.int64}
    trees = []
    for block in text.split("\nTree=")[1:]:
        block = block.split("\nend of trees")[0]
        tree = {}
        for line in block.splitlines()[1:]:
            key, _, val = line.partition("=")
            if key == "num_leaves":
                tree[key] = int(val)
            elif key == "num_cat":
                assert int(val) == 0, "categorical splits are not covered"
            elif key in kinds:
                tree[key] = np.asarray(val.split(), dtype=kinds[key])
        trees.append(tree)
    return trees


def f32_floor(t):
    """Largest float32 not above each float64 t: for a float32 x,
    x <= t (as the model decides, in float64) iff x <= f32_floor(t)."""
    t = np.asarray(t, np.float64)
    t32 = t.astype(np.float32)
    up = t32.astype(np.float64) > t
    return np.where(up, np.nextafter(t32, np.float32(-np.inf)), t32)


def tables(trees, max_leaves):
    """Stacked per-tree arrays for the walk: split feature and threshold per
    internal node, and per leaf its path as +1 (left of that node), -1
    (right), 0 (not on the path), with the path's length."""
    T, L, N = len(trees), max_leaves, max_leaves - 1
    feat = np.zeros((T, N), np.int32)
    thr = np.full((T, N), np.inf, np.float32)
    path = np.zeros((T, L, N), np.int8)
    plen = np.full((T, L), -1, np.int32)       # -1: no such leaf, never met
    value = np.zeros((T, L), np.float32)
    for t, tree in enumerate(trees):
        n = tree["num_leaves"] - 1
        if n == 0:                              # a stump: every row in leaf 0
            plen[t, 0] = 0
            value[t, 0] = tree["leaf_value"][0]
            continue
        assert np.all(tree["decision_type"] & 1 == 0), "categorical split"
        feat[t, :n] = tree["split_feature"]
        thr[t, :n] = f32_floor(tree["threshold"])
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
    return feat, thr, path, plen, value


# ---------------------------------------------------------------------------
# objectives: initial score and gradient fill
# ---------------------------------------------------------------------------

def binary_init(label_mean):
    return float(np.log(label_mean / (1.0 - label_mean)))


def binary_grad(score, label, group):
    del group
    p = jax.nn.sigmoid(score)
    return p - label, p * (1.0 - p)


def lambdarank_grad(score, label, group, truncation=20, sigmoid=1.0):
    """LambdaRank-NDCG with normalisation, per query of ``group`` documents
    (rank_objective.hpp: every pair of a higher and a lower grade; the NDCG
    swap cost over the query's best DCG@truncation; the 0.01 + |gap| and
    log2(1 + sum)/sum normalisations)."""
    s = score.reshape(-1, group)
    lab = label.reshape(-1, group)
    gain = 2.0 ** lab - 1.0
    order = jnp.argsort(-s, axis=1, stable=True)
    rank = jnp.argsort(order, axis=1, stable=True)
    disc = 1.0 / jnp.log2(2.0 + rank.astype(jnp.float32))
    top = -jnp.sort(-gain, axis=1)[:, :truncation]
    max_dcg = jnp.sum(top / jnp.log2(2.0 + jnp.arange(top.shape[1],
                                                      dtype=jnp.float32)),
                      axis=1)
    inv = jnp.where(max_dcg > 0, 1.0 / max_dcg, 0.0)[:, None, None]
    ds = s[:, :, None] - s[:, None, :]
    pair = lab[:, :, None] > lab[:, None, :]
    delta = ((gain[:, :, None] - gain[:, None, :])
             * jnp.abs(disc[:, :, None] - disc[:, None, :]) * inv)
    spread = (jnp.max(s, axis=1) != jnp.min(s, axis=1))[:, None, None]
    delta = jnp.where(spread, delta / (0.01 + jnp.abs(ds)), delta)
    p = 1.0 / (1.0 + jnp.exp(sigmoid * ds))
    lam = jnp.where(pair, -sigmoid * delta * p, 0.0)
    hes = jnp.where(pair, sigmoid * sigmoid * delta * p * (1.0 - p), 0.0)
    g = jnp.sum(lam, axis=2) - jnp.sum(lam, axis=1)
    h = jnp.sum(hes, axis=2) + jnp.sum(hes, axis=1)
    total = -2.0 * jnp.sum(lam, axis=(1, 2))
    norm = jnp.where(total > 0, jnp.log2(1.0 + total) / total, 1.0)[:, None]
    return (g * norm).reshape(-1), (h * norm).reshape(-1)


OBJECTIVES = {"binary": binary_grad, "lambdarank": lambdarank_grad}


# ---------------------------------------------------------------------------
# following the trees over one block of rows
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("objective", "group", "low"))
def follow_block(x, label, valid, init, feat, thr, path, plen, value,
                 objective, group, low):
    """Per tree, per leaf: (rows, gradient sum, hessian sum) over the block.

    x [B, F] f32, label [B], valid [B] bool; the tables of ``tables``.
    ``low``: round every gradient and hessian to bfloat16 (the control).
    Returns sums [T, L, 3] f32."""
    xt = x.T                                             # [F, B]
    grad = OBJECTIVES[objective]
    ok = valid.astype(jnp.float32)

    def one_tree(score, tab):
        f, th, pa, pl, val = tab
        g, h = grad(score, label, group)
        if low:
            # not astype(bfloat16).astype(float32): the TPU's compiler may
            # keep the excess precision of such a pair and round nothing
            g, h = (jax.lax.reduce_precision(v, exponent_bits=8,
                                             mantissa_bits=7) for v in (g, h))
        left = xt[f] <= th[:, None]                      # [N, B]
        sign = jnp.where(left, 1.0, -1.0).astype(jnp.bfloat16)
        met = jnp.dot(pa.astype(jnp.bfloat16), sign,
                      preferred_element_type=jnp.float32)  # [L, B], exact
        leaf = met == pl[:, None].astype(jnp.float32)    # one-hot over L
        onehot = leaf.astype(jnp.float32)
        sums = jnp.dot(onehot, jnp.stack([ok, g * ok, h * ok], axis=1),
                       precision=HIGHEST)                # [L, 3]
        out = jnp.sum(jnp.where(leaf, val[:, None], 0.0), axis=0)
        return score + out, sums

    score0 = jnp.full(label.shape, init, jnp.float32)
    _, sums = jax.lax.scan(one_tree, score0, (feat, thr, path, plen, value))
    return sums


def follow(blocks, trees, init, objective, group, max_leaves, low=False):
    """Sum ``follow_block`` over ``blocks``, an iterable of (x, label, valid)
    device arrays of one shape. Returns [T, L, 3] float64 (rows, G, H)."""
    feat, thr, path, plen, value = tables(trees, max_leaves)
    # LightGBM folds the initial score into the first tree's outputs, and the
    # walk starts from that score: take it out of the table again
    value[0] = np.where(plen[0] >= 0, value[0] - np.float32(init), 0.0)
    tabs = [jnp.asarray(a) for a in (feat, thr, path, plen, value)]
    total = None
    pending = None
    for x, label, valid in blocks:
        sums = follow_block(x, label, valid, jnp.float32(init), *tabs,
                            objective=objective, group=group, low=low)
        if pending is not None:                 # fetch one behind: overlap
            got = np.asarray(pending, np.float64)
            total = got if total is None else total + got
        pending = sums
    got = np.asarray(pending, np.float64)
    return got if total is None else total + got


# ---------------------------------------------------------------------------
# what the sums say of a model: leaf outputs, split gains, counts
# ---------------------------------------------------------------------------

def to_bfloat16(a):
    """float64 values rounded to the nearest bfloat16 (ties to even)."""
    bits = np.asarray(a, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7fff + ((bits >> 16) & 1)) & 0xffff0000
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


def expected(tree, sums, learning_rate, bias):
    """From one tree's per-leaf (rows, G, H): the leaf outputs and, bottom
    up, each internal node's row count and split gain."""
    nl = tree["num_leaves"]
    rows, G, H = sums[:nl, 0], sums[:nl, 1], sums[:nl, 2]
    with np.errstate(divide="ignore", invalid="ignore"):
        value = -G / H * learning_rate + bias
    if nl == 1:
        return {"leaf_count": rows, "leaf_value": value,
                "internal_count": np.zeros(0), "split_gain": np.zeros(0)}
    n = nl - 1
    agg = np.zeros((n, 3))

    def below(child):
        return sums[~child] if child < 0 else agg[child]

    # children have larger indices than their parent (nodes are numbered in
    # the order they were split), so a reverse sweep sees children first
    gain = np.zeros(n)
    for node in range(n - 1, -1, -1):
        lo = below(tree["left_child"][node])
        hi = below(tree["right_child"][node])
        agg[node] = lo + hi
        gain[node] = (lo[1] ** 2 / lo[2] + hi[1] ** 2 / hi[2]
                      - agg[node][1] ** 2 / agg[node][2])
    return {"leaf_count": rows, "leaf_value": value,
            "internal_count": agg[:, 0], "split_gain": gain}


def gaps(got, want):
    """|got - want| per entry, each measured against the larger of |want|
    there and the median |want|; an entry that is not finite reads inf."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if want.size == 0:
        return np.zeros(0)
    scale = np.maximum(np.abs(want), np.median(np.abs(want)))
    gap = np.abs(got - want) / np.where(scale > 0, scale, 1.0)
    return np.where(np.isfinite(gap), gap, np.inf)


def compare(trees, sums, learning_rate, init, answers=None):
    """The numbers that decide ``correct``. ``answers`` (default: the model's
    own fields) is what is judged: [{leaf_value, leaf_count, internal_count,
    split_gain}] per tree; ``sums`` is the reference's [T, L, 3].

    count_mismatch  rows that the reference puts in another leaf or node than
                    the model reports, summed over all leaves and nodes
    leaf_value_gap  widest gap of a leaf output, over all trees
    split_gain_gap  widest gap of a chosen split's gain, over all trees
    median_leaf_gap median gap of a leaf output, over all leaves of all trees:
                    the widest gap is one leaf's noise, the median is what
                    a lower precision everywhere moves

    Also returns, per tree, (mismatch, leaf gap, gain gap, where): ``where``
    names the leaf and the node that read widest, with both sides' numbers.
    """
    answers = trees if answers is None else answers
    numbers = {"count_mismatch": 0, "leaf_value_gap": 0.0,
               "split_gain_gap": 0.0}
    per_tree, all_leaves = [], []
    for t, (tree, ans) in enumerate(zip(trees, answers)):
        # LightGBM folds the initial score into the first tree's outputs
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
                         node_rows=float(exp["internal_count"][node]))
        per_tree.append((m, float(lg.max()), float(gg.max()) if gg.size
                         else 0.0, where))
        numbers["count_mismatch"] += m
        numbers["leaf_value_gap"] = max(numbers["leaf_value_gap"],
                                        per_tree[-1][1])
        numbers["split_gain_gap"] = max(numbers["split_gain_gap"],
                                        per_tree[-1][2])
    numbers["median_leaf_gap"] = float(np.median(np.concatenate(all_leaves)))
    return numbers, per_tree
