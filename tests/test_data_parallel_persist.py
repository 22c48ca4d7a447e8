"""The sharded persist path (``tree_learner=data`` over the CPU mesh, forced
persist scan) against a plain reference of the same semantics, and the
state it takes from 2^24 rows on (``large_counts``) forced at a small size.

The reference here is plain numpy on the raw rows: it walks the trees the
program returned (dump_model's thresholds, ``<=`` goes left), carries the
score from the objective's own initial score, takes binary log-loss
gradients and hessians before each tree, and sums them per leaf. Per tree
it then holds the program to: the leaf each row lands in (the program's own
``pred_leaf``), every leaf's row count, gradient and hessian sums, and
output. It does not know how many shards grew the trees.

Tolerances, with their reasons. Counts and leaf assignments are integers:
equal exactly, on every path. Leaf outputs and sums: in the widened XLA
mode (what the CPU runs by default) histograms and sums are float64, but
each row's gradient and hessian is rounded to float32 once on its way
into the payload (6e-8 relative a row), so 2e-6 relative; with the Mosaic
kernels (interpreter) gradients enter the histograms as two bfloat16
halves (2^-17 relative a row) and are summed in float32, so 3e-4 relative
on a leaf of tens of rows; trees need not equal the serial learner's bit
for bit there, because float32 sums all-reduced over shards round in
another order than one chip's.
"""
import json

import numpy as np
import pytest

import jax

import lightgbm_tpu as lgb
import lightgbm_tpu.ops.grow_persist as GP
from lightgbm_tpu import telemetry
from lightgbm_tpu.treelearner.serial import SerialTreeLearner

N, F, ROUNDS = 2048, 12, 16      # 8 shards x 256 rows; one fused batch
LR = 0.2
BASE = {"objective": "binary", "verbosity": -1, "min_data_in_leaf": 10,
        "max_bin": 63, "learning_rate": LR, "num_leaves": 7,
        "tpu_persist_scan": "force"}


def _data(seed=38):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(N, F))
    y = (X[:, 0] - 0.7 * X[:, 2] + 0.4 * X[:, 4] * X[:, 7]
         + rng.normal(size=N) * 0.5 > 0).astype(float)
    return X, y


def _train(X, y, **params):
    bst = lgb.train({**BASE, **params}, lgb.Dataset(X, y), ROUNDS,
                    verbose_eval=False)
    learner = bst._booster.tree_learner
    assert getattr(learner, "_persist_carry", None) is not None, params
    return bst


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _flatten(tree):
    """(nodes, leaves) of one dump_model tree: a node is (feature,
    threshold, left, right) with a child >= 0 a node and ~child a leaf;
    a leaf is (index, count, value)."""
    nodes, leaves = {}, {}

    def walk(node):
        if "split_index" not in node:
            leaves[node["leaf_index"]] = (node["leaf_count"],
                                          node["leaf_value"])
            return ~node["leaf_index"]
        k = node["split_index"]
        assert node["decision_type"] == "<=", node["decision_type"]
        nodes[k] = (node["split_feature"], node["threshold"],
                    walk(node["left_child"]), walk(node["right_child"]),
                    node["internal_count"])
        return k
    walk(tree["tree_structure"])
    return nodes, leaves


def _leaf_of(nodes, X):
    at = np.zeros(len(X), np.int64)           # node >= 0, leaf ~at
    for _ in range(len(nodes) + 1):
        inner = at >= 0
        if not inner.any():
            break
        for k, (f, thr, left, right, _) in nodes.items():
            here = inner & (at == k)
            at[here] = np.where(X[here, f] <= thr, left, right)
    assert (at < 0).all()
    return ~at


def _follow(bst, X, y):
    """Per tree: (leaf of each row [N], per-leaf (count, G, H, output),
    the model's (count, value) per leaf, the nodes)."""
    model = bst.dump_model()
    if isinstance(model, str):
        model = json.loads(model)
    p0 = y.mean()
    init = np.log(p0 / (1.0 - p0))
    score = np.full(len(y), init)
    out = []
    for t, tree in enumerate(model["tree_info"]):
        nodes, leaves = _flatten(tree)
        leaf = _leaf_of(nodes, X)
        p = 1.0 / (1.0 + np.exp(-score))
        g, h = p - y, p * (1.0 - p)
        stats = {}
        for i in leaves:
            rows = leaf == i
            G, H = g[rows].sum(), h[rows].sum()
            stats[i] = (int(rows.sum()), G, H,
                        -G / H * LR + (init if t == 0 else 0.0))
        out.append((leaf, stats, leaves, nodes))
        value = np.asarray([leaves[i][1] for i in range(len(leaves))])
        score = score + value[leaf] - (init if t == 0 else 0.0)
    return out


def _hold_to_the_reference(bst, X, y, rtol):
    followed = _follow(bst, X, y)
    assert len(followed) == ROUNDS
    pred_leaf = np.asarray(bst.predict(X, pred_leaf=True))
    for t, (leaf, stats, leaves, nodes) in enumerate(followed):
        # the leaf each row lands in
        np.testing.assert_array_equal(leaf, pred_leaf[:, t])
        # counts: exactly, leaves and nodes (a node's is its leaves')
        for i, (count, _, _, _) in stats.items():
            assert count == leaves[i][0], (t, i, count, leaves[i])

        def below(child):
            return (stats[~child][0] if child < 0
                    else below(nodes[child][2]) + below(nodes[child][3]))
        for k, (_, _, left, right, internal) in nodes.items():
            assert below(left) + below(right) == internal, (t, k)
        assert sum(s[0] for s in stats.values()) == len(y)
        # outputs: sums enter through -G/H
        want = np.asarray([stats[i][3] for i in sorted(stats)])
        got = np.asarray([leaves[i][1] for i in sorted(stats)])
        np.testing.assert_allclose(got, want, rtol=rtol,
                                   atol=rtol * np.abs(want).max())


# ---------------------------------------------------------------------------
# the sharded path, widened XLA mode (float64): 2, 4 and 8 shards
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shards", [2, 4, 8])
def test_sharded_persist_agrees_with_the_plain_reference(shards):
    assert len(jax.devices()) >= 8, "conftest provides 8 virtual devices"
    X, y = _data()
    before = telemetry.counts_snapshot()
    bst = _train(X, y, tree_learner="data", tpu_num_devices=shards)
    learner = bst._booster.tree_learner
    assert type(learner).__name__ == "DataParallelTreeLearner"
    assert learner.num_shards == shards
    assert learner._persist_gr.num_shards == shards
    _hold_to_the_reference(bst, X, y, rtol=2e-6)
    after = telemetry.counts_snapshot()

    def grew(name):
        return after.get(name, 0.0) - before.get(name, 0.0)
    assert grew("tree_learner::persist_scan_trees") == ROUNDS
    assert grew("tree_learner::sharded_persist_trees") == ROUNDS
    assert grew("tree_learner::large_count_trees") == 0
    assert after["tree_learner::shards"] == shards
    # the modelled wire bytes of the planes' exchange, counted at the flush
    assert grew("collective::dcn_hist_bytes") > 0
    spans = [e for e in telemetry.ring_snapshot()
             if e["name"] == "tree_learner::ShardPayload(device_put)"]
    assert spans and spans[-1]["dur"] > 0


def test_run_record_of_a_sharded_train():
    """The sharded path's set-up under the serial path's span names, as
    tests/test_telemetry.py::test_run_record_of_an_off_mode_train holds
    the serial path to: the learner and the layout before any launch, the
    grower, the shard_map wrappers and the driver built inside launch 0;
    the dispatch keeps its own name; parents form one tree."""
    X, y = _data()
    _train(X, y, tree_learner="data", tpu_num_devices=4)
    ring = telemetry.ring_snapshot()
    root = [e for e in ring if e["name"] == "engine::train"][-1]
    ours = [e for e in ring if e["train"] == root["train"]]
    by_name = {}
    for e in ours:
        by_name.setdefault(e["name"], []).append(e)
    container = "boosting::TrainMultiIterFast(launch)"
    for name in ("boosting::Init", "tree_learner::ToDevice(layout H2D)",
                 "tree_learner::PersistBuild(trace)",
                 "tree_learner::ShardPayload(device_put)",
                 "collective::persist_scan(launch)", container):
        assert len(by_name[name]) == 1, (name, sorted(by_name))
    assert "ops::persist_scan(launch)" not in by_name
    init, put, build, launch = (by_name[n][0] for n in (
        "boosting::Init", "tree_learner::ToDevice(layout H2D)",
        "tree_learner::PersistBuild(trace)",
        "collective::persist_scan(launch)"))
    assert "launch" not in init and "launch" not in put
    assert init["parent"] == "engine::train"
    assert put["parent"] == "boosting::Init"
    assert build["launch"] == 0 and build["parent"] == container
    assert launch["launch"] == 0 and launch["parent"] == container
    assert by_name[container][0]["parent"] == "engine::train"
    assert {init["cat"], put["cat"], build["cat"]} == {"setup"}
    # the build closes before the dispatch opens, and both lie in launch 0
    assert build["ts"] + build["dur"] <= launch["ts"] + 1e-3
    names = set(by_name)
    for e in ours:
        if e["name"] != "engine::train":
            assert e["parent"] in names, e
            assert e["ts"] >= root["ts"] - 1e-3
            assert e["ts"] + e["dur"] <= root["ts"] + root["dur"] + 1e-3
    assert not [e for e in ours if "hbm" in e]      # the CPU keeps none


# ---------------------------------------------------------------------------
# the state past 2^24 rows, forced small: Mosaic kernels in the interpreter
# ---------------------------------------------------------------------------

@pytest.fixture
def mosaic(monkeypatch):
    monkeypatch.setattr(SerialTreeLearner, "_persist_kernel_mode",
                        staticmethod(lambda: ("pallas", True)))


@pytest.mark.parametrize("learner,shards", [("serial", 1), ("data", 4)],
                         ids=["serial", "data4"])
def test_large_count_state_agrees_with_the_plain_reference(
        mosaic, monkeypatch, learner, shards):
    """From EXACT_F32_ROWS rows on the counts and positions ride i32
    beside the float32 state. Lowered to 1,024 rows here, so that these
    2,048 take that state: the trees must be the ones the float lanes
    give (every count is under 2^24 here, so both are exact), and must
    agree with the reference, counts exactly."""
    X, y = _data()
    params = ({"tree_learner": "data", "tpu_num_devices": shards}
              if learner == "data" else {})
    small = _train(X, y, **params)
    assert not small._booster.tree_learner._persist_gr.large_counts
    before = telemetry.counts_snapshot()
    monkeypatch.setattr(GP, "EXACT_F32_ROWS", 1024)
    big = _train(X, y, **params)
    gr = big._booster.tree_learner._persist_gr
    assert gr.large_counts and not getattr(gr, "inner", gr).wide
    grew = (telemetry.counts_snapshot()["tree_learner::large_count_trees"]
            - before.get("tree_learner::large_count_trees", 0.0))
    assert grew == ROUNDS

    def trees(bst):
        return bst.model_to_string(num_iteration=-1).split(
            "\nparameters:")[0]
    assert trees(big) == trees(small)
    _hold_to_the_reference(big, X, y, rtol=3e-4)


def test_root_totals_are_the_root_histograms():
    """The root's sums are read off the root histogram (the first group's
    plane), not kept as a running float32 total beside it: on the first
    tree every hessian is one number, the running total's rounding then
    has one sign at every chunk, and the scan leaves the drift with the
    leftmost child of every split (PERF.md section 7 row 0b). Chunks of
    128 lanes make 400 of them here."""
    import jax.numpy as jnp
    from lightgbm_tpu.data.dataset import BinnedDataset
    rng = np.random.default_rng(5)
    n = 51_200
    X = rng.normal(size=(n, 3))
    y = (rng.uniform(size=n) < 0.419035).astype(np.float64)
    cfg = lgb.Config({"objective": "binary", "max_bin": 63,
                      "num_leaves": 7})
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    assets = GP.build_assets(ds, ds.metadata.label, C=1024, CR=128)
    learner = SerialTreeLearner(cfg, ds)
    gr = GP.make_persist_grower(assets, learner.meta, learner.grow_config,
                                interpret=True, kernel_impl="pallas")
    pay = gr.init_carry(jnp.asarray(assets.pay0),
                        jnp.full((n,), np.log(0.419035 / 0.580965)))
    pay = gr.fill_grad(pay, lambda s, l: (jax.nn.sigmoid(s) - l,
                                          jax.nn.sigmoid(s)
                                          * (1.0 - jax.nn.sigmoid(s))))
    rhist = gr._root_hist(pay)
    totals = np.asarray(gr._root_totals(pay, rhist), np.float64)
    G = len(ds.groups)
    planes = np.asarray(rhist, np.float64).reshape(2, G, 256).sum(axis=2)
    # the totals are every group's plane's, to float32 rounding of a sum
    np.testing.assert_allclose(planes[1], totals[1], rtol=3e-7)
    np.testing.assert_allclose(planes[0], totals[0],
                               atol=3e-7 * np.abs(planes[1]).max())
    # what root_hist once kept beside the planes: a float32 total, one add
    # a chunk of 128 rows. It drifts further from the planes than the
    # planes lie from each other
    hess = jax.lax.bitcast_convert_type(pay[gr.nbw + 3, :n], jnp.float32)
    running = np.float32(0.0)
    for chunk in np.asarray(hess).reshape(-1, 128):
        running = np.float32(running + chunk.sum(dtype=np.float32))
    drift = abs(float(running) - totals[1])
    assert drift > 4 * np.abs(planes[1] - totals[1]).max(), (drift, planes)


# ---------------------------------------------------------------------------
# rows that do not divide into the shards: said, and counted
# ---------------------------------------------------------------------------

def test_uneven_shards_fall_back_to_the_v1_grower_and_say_so(capsys):
    X, y = _data()
    X, y = X[:N - 3], y[:N - 3]              # 2,045 rows over 4 shards
    before = telemetry.counts_snapshot()
    bst = lgb.train({**BASE, "verbosity": 0, "tree_learner": "data",
                     "tpu_num_devices": 4},
                    lgb.Dataset(X, y), ROUNDS, verbose_eval=False)
    learner = bst._booster.tree_learner
    assert getattr(learner, "_persist_carry", None) is None
    after = telemetry.counts_snapshot()

    def grew(name):
        return after.get(name, 0.0) - before.get(name, 0.0)
    assert grew("tree_learner::sharded_v1_fallback") == 1
    assert grew("tree_learner::v1_grow_trees") == ROUNDS
    assert grew("tree_learner::sharded_persist_trees") == 0
    said = capsys.readouterr()
    text = said.out + said.err
    assert text.count("2045 rows do not divide into 4 equal shards") == 1
    assert bst.num_trees() == ROUNDS
