"""Ranking on the persist path: the per-query gradient fill inside the fused
driver (objectives/rank.py:payload_pos_fn, ops/grow_persist.fill_grad_pos).

The fill sorts each lane's score into its query's padded slot with the lane
number beside it, and sorts the lambdas back to the lanes by that
slot-to-lane map: no scatter and no gather. The lane numbers must stay
integers: an int32 under 2^23 read as a float32 is a denormal, which XLA
flushes to zero on the CPU and the TPU, and every lambda of such a lane then
lands on lane 0 (a model of stumps, every row's gradient 0). These tests hold
the fill to the row-order gradients lane for lane and, bit for bit, to a fill
that scatters, and the trees it grows to a plain LambdaRank-NDCG
written here from rank_objective.hpp; XE-NDCG, which has no device fill (its
draws are fresh host inputs every iteration), to a plain XE-NDCG on the
grower it takes. They also read the run record the fill leaves: the trees it
filled, the padded query layout, and the fused program's instructions under
the ``fill_grad`` scope.
"""
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.telemetry import events as telemetry

DOCS, QUERIES, FEATURES = 73, 1400, 12       # 102,200 rows
ROUNDS = 16                                  # one fused launch


def _objective(name, label, counts, weight=None):
    qb = np.concatenate([[0], np.cumsum(counts)])
    meta = types.SimpleNamespace(label=label, weight=weight,
                                 num_queries=len(counts),
                                 query_boundaries=qb)
    obj = create_objective(name, Config({"objective": name}))
    obj.init(meta, len(label))
    return obj


@pytest.mark.parametrize("lengths", ["equal", "unequal"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64],
                         ids=["f32", "f64"])
def test_pos_fill_puts_every_lambda_on_its_own_lane(dtype, lengths):
    """The fill's lambdas in a shuffled lane order, with dead lanes after
    the rows as the payload has them, equal the row-order gradients of the
    same scores through the same row-id map. Every row id here is under
    2^23. float64 is the score the CPU's payload carries, float32 the
    chip's."""
    rng = np.random.default_rng(5)
    counts = (np.full(40, 30) if lengths == "equal"
              else rng.integers(5, 60, 40))
    n = int(counts.sum())
    label = rng.integers(0, 5, n).astype(np.float64)
    obj = _objective("lambdarank", label, counts)
    mode, fn = obj.device_gradients()
    assert mode == "pos"
    score = rng.normal(size=n)
    lanes = n + 300
    rid = np.concatenate([rng.permutation(n), np.full(lanes - n, n)])
    live = np.arange(lanes) < n
    g, h = jax.jit(fn)(jnp.asarray(score[np.minimum(rid, n - 1)], dtype),
                       jnp.asarray(rid, jnp.int32), jnp.asarray(live),
                       *obj.persist_grad_args())
    want_g, want_h = obj.get_gradients(jnp.asarray(score, dtype))
    g, h = np.asarray(g), np.asarray(h)
    assert np.abs(np.asarray(want_g)).max() > 0.01
    np.testing.assert_allclose(g[live], np.asarray(want_g)[rid[live]],
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(h[live], np.asarray(want_h)[rid[live]],
                               rtol=1e-5, atol=1e-7)
    assert not g[~live].any() and not h[~live].any()


def _two_scatter_fill(obj, score, rid, live, *gargs):
    """The fill by scatters: each live lane's score scattered to its
    query slot (q * P + offset, from the query boundaries) with the lane
    beside it, the pairwise core run on the slots, and the lambdas
    scattered back to the lanes through that slot-to-lane map."""
    lab_pad, qvalid, inv_max, gains, disc, _fill, w_pad = gargs
    Q, P = lab_pad.shape
    NP, n = score.shape[0], obj.num_data
    qb = obj.query_boundaries
    q = np.repeat(np.arange(len(qb) - 1), np.diff(qb))
    slot_of_row = q * P + np.arange(n) - qb[q]
    pos = jnp.where(live, jnp.asarray(slot_of_row)[jnp.minimum(rid, n - 1)],
                    Q * P)
    sp = jnp.zeros(Q * P, score.dtype).at[pos].set(score, mode="drop")
    lane = jnp.full(Q * P, NP, jnp.int32).at[pos].set(
        jnp.arange(NP, dtype=jnp.int32), mode="drop")
    lam, hes = obj._pairwise_flat()(sp.reshape(Q, P), lab_pad, qvalid,
                                    inv_max, gains, disc)
    lam, hes = lam[:Q * P], hes[:Q * P]
    if w_pad is not None:
        lam, hes = lam * w_pad.reshape(-1), hes * w_pad.reshape(-1)
    out = jnp.zeros((2, NP), jnp.float32).at[:, lane].set(
        jnp.stack([lam.astype(jnp.float32), hes.astype(jnp.float32)]),
        mode="drop")
    return out[0], out[1]


@pytest.mark.parametrize("weighted", [False, True], ids=["plain", "weighted"])
@pytest.mark.parametrize("lengths", ["equal", "unequal"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64],
                         ids=["f32", "f64"])
def test_pos_fill_moves_rows_by_sorts_as_the_scatters_did(dtype, lengths,
                                                          weighted):
    """The fill carries rows between lane order and query-slot order by
    sorts: its program holds no scatter and no gather, and on shuffled
    lanes with dead lanes at the tail it gives, bit for bit on every lane,
    the (g, h) of a fill that scatters the scores into the slots and the
    lambdas back."""
    from lightgbm_tpu.analysis.dataflow import iter_eqns
    rng = np.random.default_rng(17)
    counts = (np.full(36, 25) if lengths == "equal"
              else rng.integers(3, 50, 36))
    n = int(counts.sum())
    label = rng.integers(0, 5, n).astype(np.float64)
    weight = (np.repeat(rng.uniform(0.5, 2.0, len(counts)), counts)
              if weighted else None)
    obj = _objective("lambdarank", label, counts, weight)
    _, fn = obj.device_gradients()
    lanes = n + 200
    rid = np.concatenate([rng.permutation(n), np.full(lanes - n, n)])
    score = np.where(rid < n, rng.normal(size=lanes), 0.0)
    args = (jnp.asarray(score, dtype), jnp.asarray(rid, jnp.int32),
            jnp.asarray(np.arange(lanes) < n), *obj.persist_grad_args())
    prims = {e.primitive.name
             for e, _ in iter_eqns(jax.make_jaxpr(fn)(*args).jaxpr)}
    assert "sort" in prims
    assert not prims & {"scatter", "scatter-add", "gather"}, prims
    got = jax.jit(fn)(*args)
    want = jax.jit(lambda *a: _two_scatter_fill(obj, *a))(*args)
    assert np.abs(np.asarray(want[0])).max() > 0.01
    for a, b in zip(got, want):
        assert a.dtype == b.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(a).view(np.int32),
                                      np.asarray(b).view(np.int32))


def _rank_data():
    rng = np.random.default_rng(42)
    n = DOCS * QUERIES
    X = rng.normal(size=(n, FEATURES)).astype(np.float32)
    sig = (X[:, :5] @ np.asarray([1.0, -0.8, 0.6, 0.5, -0.3])
           + 0.5 * np.tanh(X[:, 5] * X[:, 6]) + rng.logistic(size=n))
    s = sig.reshape(QUERIES, DOCS)
    cuts = np.quantile(s, [0.55, 0.75, 0.9, 0.97], axis=1)
    y = sum((s > c[:, None]) for c in cuts).reshape(-1).astype(np.float64)
    return X, y, np.full(QUERIES, DOCS)


def _train(X, y, group, **params):
    base = {"num_leaves": 31, "verbosity": -1, "metric": "none",
            "min_data_in_leaf": 20}
    return lgb.train(dict(base, **params), lgb.Dataset(X, y, group=group),
                     ROUNDS, verbose_eval=False)


def _trees(bst):
    """[(num_leaves, split features, thresholds, leaf values)] per tree."""
    out = []
    for block in bst.model_to_string().split("\nTree=")[1:]:
        kv = dict(line.partition("=")[::2] for line in block.splitlines())
        out.append((int(kv["num_leaves"]),
                    kv.get("split_feature", ""), kv.get("threshold", ""),
                    np.asarray(kv["leaf_value"].split(), np.float64)))
    return out


def _plain_lambdarank(score, label, group):
    """LambdaRank-NDCG with its normalisations (rank_objective.hpp: every
    pair of a higher and a lower grade, the NDCG swap cost over the query's
    best DCG@20, the 0.01 + |gap| and log2(1 + sum) / sum factors), float64,
    queries of ``group`` documents."""
    s = score.reshape(-1, group)
    lab = label.reshape(-1, group)
    gain = 2.0 ** lab - 1.0
    rank = np.argsort(np.argsort(-s, axis=1, kind="stable"), axis=1,
                      kind="stable")
    disc = 1.0 / np.log2(2.0 + rank)
    top = -np.sort(-gain, axis=1)[:, :20]
    best = top @ (1.0 / np.log2(2.0 + np.arange(top.shape[1])))
    inv = np.where(best > 0, 1.0 / np.where(best > 0, best, 1), 0.0)
    ds = s[:, :, None] - s[:, None, :]
    delta = ((gain[:, :, None] - gain[:, None, :])
             * np.abs(disc[:, :, None] - disc[:, None, :])
             * inv[:, None, None])
    spread = (s.max(axis=1) != s.min(axis=1))[:, None, None]
    delta = np.where(spread, delta / (0.01 + np.abs(ds)), delta)
    p = 1.0 / (1.0 + np.exp(ds))
    pair = lab[:, :, None] > lab[:, None, :]
    lam = np.where(pair, -delta * p, 0.0)
    hes = np.where(pair, delta * p * (1.0 - p), 0.0)
    g = lam.sum(axis=2) - lam.sum(axis=1)
    h = hes.sum(axis=2) + hes.sum(axis=1)
    total = -2.0 * lam.sum(axis=(1, 2))
    norm = np.where(total > 0, np.log2(1.0 + total)
                    / np.where(total > 0, total, 1), 1.0)[:, None]
    return (g * norm).reshape(-1), (h * norm).reshape(-1)


def _plain_xendcg(score, label, group, draws):
    """XE-NDCG (rank_objective.hpp:288-352), float64, with the iteration's
    draws."""
    s = score.reshape(-1, group)
    e = np.exp(s - s.max(axis=1, keepdims=True))
    rho = e / e.sum(axis=1, keepdims=True)
    phi = 2.0 ** np.floor(label.reshape(-1, group)) - draws.reshape(-1, group)
    l1 = -phi / np.maximum(1e-15, phi.sum(axis=1, keepdims=True)) + rho
    l2 = (l1.sum(axis=1, keepdims=True) - l1) / (1.0 - rho)
    l3 = (l2.sum(axis=1, keepdims=True) - l2) / (1.0 - rho)
    return ((l1 + rho * l2 + rho * rho * l3).reshape(-1),
            (rho * (1.0 - rho)).reshape(-1))


def _follow(bst, X, y, group, grad):
    """Per tree, the leaf values the plain gradients give the model's own
    leaves: -G / H x learning rate over the rows each leaf holds."""
    leaves = bst.predict(X, pred_leaf=True).astype(np.int64)
    out = []
    for t in range(leaves.shape[1]):
        score = (bst.predict(X, num_iteration=t, raw_score=True) if t
                 else np.zeros(len(y)))
        g, h = grad(score, y, group[0], t)
        L = leaves[:, t].max() + 1
        G = np.bincount(leaves[:, t], g, L)
        H = np.bincount(leaves[:, t], h, L)
        out.append(-G / H * 0.1)
    return out


def test_lambdarank_persist_grows_the_v1_trees_and_the_plain_sums():
    """~100k rows in queries of 73: the fused driver with the ranking fill
    (tpu_persist_scan=force) grows the same trees as the per-iteration
    grower (off), every one of them with its 31 leaves, and each leaf's
    value is what a plain LambdaRank-NDCG's sums over its rows give."""
    X, y, group = _rank_data()
    fused = _train(X, y, group, objective="lambdarank",
                   tpu_persist_scan="force")
    v1 = _train(X, y, group, objective="lambdarank", tpu_persist_scan="off")
    assert fused._booster.objective.persist_grad_mode() == "pos"
    assert getattr(fused._booster.tree_learner, "_persist_carry",
                   None) is not None
    got, want = _trees(fused), _trees(v1)
    assert len(got) == len(want) == ROUNDS
    for a, b in zip(got, want):
        assert a[0] == b[0] == 31
        assert a[1:3] == b[1:3]
        np.testing.assert_allclose(a[3], b[3], rtol=1e-4, atol=1e-7)
    plain = _follow(fused, X, y, group,
                    lambda s, lab, g, t: _plain_lambdarank(s, lab, g))
    for (_, _, _, values), ref in zip(got, plain):
        np.testing.assert_allclose(values, ref, rtol=1e-3, atol=1e-6)


def test_f32_scores_are_the_walk_of_the_model_bit_for_bit(monkeypatch):
    """The chip's payload carries f32 scores (the Mosaic kernels, run in
    the interpreter here, take that layout). After a launch with the
    ranking fill each row's score is, bit for bit, the f32 sum tree by
    tree of the leaf values the model text holds, as a walk of the model
    adds them, so a near-tie of a query is ordered as the model orders
    it. Carried down the segments as f32 deltas, scores were ulps off."""
    from lightgbm_tpu.treelearner.serial import SerialTreeLearner
    monkeypatch.setattr(SerialTreeLearner, "_persist_kernel_mode",
                        staticmethod(lambda: ("pallas", True)))
    rng = np.random.default_rng(11)
    group = np.full(100, 24)
    n = int(group.sum())
    X = rng.normal(size=(n, 5)).astype(np.float32)
    y = rng.integers(0, 5, n).astype(np.float64)
    bst = _train(X, y, group, objective="lambdarank", num_leaves=15,
                 min_data_in_leaf=5, tpu_persist_scan="force")
    learner = bst._booster.tree_learner
    assert learner._persist_kernel_effective()[2] is False    # f32 scores
    assert getattr(learner, "_persist_carry", None) is not None
    got = np.asarray(learner.persist_finalize_scores()).reshape(-1)
    leaves = bst.predict(X, pred_leaf=True).astype(np.int64)
    trees = _trees(bst)
    assert len(trees) == ROUNDS and min(t[0] for t in trees) > 1
    walk = np.zeros(n, np.float32)
    for t, (_, _, _, values) in enumerate(trees):
        walk = walk + values.astype(np.float32)[leaves[:, t]]
    np.testing.assert_array_equal(got.astype(np.float32).view(np.int32),
                                  walk.view(np.int32))


def test_xendcg_trees_follow_a_plain_xendcg():
    """XE-NDCG shares RankingObjective but has no device fill: under
    tpu_persist_scan=force it is refused by name, and on the grower it
    takes its trees are the same as off's and each leaf's value is what a
    plain XE-NDCG's sums give, with the draws of the reference's per-query
    random streams replayed."""
    X, y, group = _rank_data()
    with pytest.raises(lgb.basic.LightGBMError,
                       match="no device gradient kernel"):
        _train(X, y, group, objective="rank_xendcg",
               tpu_persist_scan="force")
    auto = _train(X, y, group, objective="rank_xendcg")
    off = _train(X, y, group, objective="rank_xendcg",
                 tpu_persist_scan="off")
    got, want = _trees(auto), _trees(off)
    assert len(got) == len(want) == ROUNDS
    for a, b in zip(got, want):
        assert a[0] == b[0] == 31 and a[1:3] == b[1:3]
        np.testing.assert_array_equal(a[3], b[3])
    stream = _objective("rank_xendcg", y, group)
    draws = [stream._next_floats() for _ in range(ROUNDS)]
    plain = _follow(auto, X, y, group,
                    lambda s, lab, g, t: _plain_xendcg(s, lab, g, draws[t]))
    for (_, _, _, values), ref in zip(got, plain):
        np.testing.assert_allclose(values, ref, rtol=1e-3, atol=1e-6)


def test_the_run_record_names_the_ranking_fill(monkeypatch, tmp_path):
    """A fused launch with the ranking fill counts its trees under
    tree_learner::rank_pos_trees and sets the padded layout's gauges in
    every mode. In trace mode alone it keeps a handle on the fused program,
    whose instructions under the ``fill_grad`` scope it lists on request,
    compiling nothing; keeping it traces and lowers nothing at launch 0
    that the launch itself does not."""
    rng = np.random.default_rng(3)
    counts = np.asarray([20, 35] * 40)
    n = int(counts.sum())
    X = rng.normal(size=(n, 6))
    y = rng.integers(0, 4, n).astype(np.float64)
    params = {"objective": "lambdarank", "num_leaves": 7, "verbosity": -1,
              "metric": "none", "tpu_persist_scan": "force"}
    launch = "ops::persist_scan(launch)"
    telemetry.reset()
    before = telemetry.counts_snapshot()
    bst = lgb.train(params, lgb.Dataset(X, y, group=counts), ROUNDS)
    after = telemetry.counts_snapshot()

    def grew(name):
        return after.get(name, 0.0) - before.get(name, 0.0)
    assert grew("tree_learner::rank_pos_trees") == ROUNDS
    assert grew("tree_learner::persist_scan_trees") == ROUNDS
    assert after["objective::rank_queries"] == len(counts)
    assert after["objective::rank_query_slots"] == len(counts) * 35
    bst.model_to_string()
    assert telemetry.program_scopes(launch, ("fill_grad",)) == {}

    def first_launch_lowerings():
        """(traces of other functions, lowerings, traces of the fused
        driver ``run`` past a hundredth of its longest) at launch 0 of the
        last train. A call that finds the driver in the jit's cache still
        reports a trace of a few microseconds."""
        ring = telemetry.ring_snapshot()
        train = max(e["train"] for e in ring)
        at0 = [e for e in ring if e["train"] == train
               and e.get("launch") == 0]
        runs = [e["dur"] for e in at0 if e["name"] == "jax::jaxpr_trace"
                and (e.get("args") or {}).get("fun") == "run"]
        return (sum(1 for e in at0 if e["name"] == "jax::jaxpr_trace"
                    and (e.get("args") or {}).get("fun") != "run"),
                sum(1 for e in at0 if e["name"] == "jax::lower"),
                sum(1 for d in runs if d > max(runs) / 100))

    def compiles():
        return sum(1 for e in telemetry.ring_snapshot() if e["name"] in
                   ("jax::jaxpr_trace", "jax::lower", "jax::backend_compile",
                    "jax::cache_load"))
    traced = dict(params, tpu_telemetry="trace",
                  telemetry_out=str(tmp_path / "rank_trace.json"))
    try:
        with monkeypatch.context() as mp:
            # every trace and lowering recorded, however short: which of
            # them pass the run record's 1 ms floor moves with the load
            mp.setattr(telemetry, "_JAX_MIN_S", 0.0)
            with monkeypatch.context() as off:
                off.setattr(telemetry, "keep_program", lambda *a: None)
                # the first train in trace mode also traces the helpers its
                # annotations change; the second is the one to compare with
                for _ in range(2):
                    lgb.train(traced, lgb.Dataset(X, y, group=counts),
                              ROUNDS)
            without = first_launch_lowerings()
            lgb.train(traced, lgb.Dataset(X, y, group=counts), ROUNDS)
            assert without[1] > 0 and without[2] == 1
            assert first_launch_lowerings() == without
            was = compiles()
            scopes = telemetry.program_scopes(launch, ("fill_grad", "grow"))
            assert compiles() == was
    finally:
        telemetry.configure("off", None)
    assert set(scopes.values()) == {"fill_grad", "grow"}
    fill = [k for k, v in scopes.items() if v == "fill_grad"]
    # the fill moves its rows by sorts: no scatter is left under the scope
    assert any(k.startswith("sort") for k in fill), fill
    assert not any(k.startswith("scatter") for k in fill), fill
    assert telemetry.program_scopes("no such span", ("fill_grad",)) == {}
    # a binary objective's launch fills elementwise: no ranking trees
    was = telemetry.counts_snapshot().get("tree_learner::rank_pos_trees")
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
               "metric": "none", "tpu_persist_scan": "force"},
              lgb.Dataset(X, (y > 1).astype(np.float64)), ROUNDS)
    assert telemetry.counts_snapshot()["tree_learner::rank_pos_trees"] == was
