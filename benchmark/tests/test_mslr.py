"""Rehearsal of ``mslr.train_steady`` on the CPU at a tiny size: the
configuration's shape, the generator's law (137 columns in three kinds, the
grades' shares, whole queries of 120, any block made again bit for bit, the
population's tables and test fold the same under every seed), the guard that refuses a
program whose ranking fill bitcasts its lane numbers to floats, and, through
the harness's own entry point, the result line of a ``--trace 1`` run
(reduced from the recorded trace) on the forced persist path, where every
tree's gradients come from the per-query fill inside the fused driver.

The configuration is named ``mslr``, apart from the rehearsal's stand-in
``msltr`` that ``conftest.py`` adds to every other rehearsal."""
import json
import os

import numpy as np
import pytest

from conftest import EXTRA_CONFIG, HERE

CELL = "mslr.train_steady"
TINY = {"rows": 9600, "block_rows": 4800, "heldout_rows": 2400}
RECORDED = os.path.join(HERE, "tests", "data", "higgs_launch_head.xplane.pb")
NEW = ("ranktree_pct",)


def tiny_mslr():
    with open(os.path.join(HERE, "configs", "mslr.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg["params"].update(num_leaves=15, min_sum_hessian_in_leaf=1.0,
                         tpu_persist_scan="force")
    return cfg


@pytest.fixture
def mslr(monkeypatch):
    """The harness patched for a CPU run of the cell: no chip look, the tiny
    configuration, the kernel mode the CPU has, and the two tree counters
    of the run record started from 0 (they are the process's)."""
    import run as bench_run
    from harness import device
    from lightgbm_tpu.telemetry import events as telemetry
    peaks = device.load_peaks()["TPU v5 lite"]
    monkeypatch.setattr(
        device, "check_device",
        lambda chips: ({"platform": "cpu", "kind": "rehearsal",
                        "count": chips}, peaks))
    real = bench_run.load_json

    def load(*parts):
        d = real(*parts)
        if parts[-1].endswith("configs/mslr.json"):
            d = tiny_mslr()
        if parts[-1] == "train_steady.json":
            d["kernel_mode"] = ["xla", True]
        return d
    monkeypatch.setattr(bench_run, "load_json", load)
    telemetry.clear_counts_prefix(("tree_learner::persist_scan_trees",
                                   "tree_learner::rank_pos_trees"))
    return bench_run


def test_configuration_is_the_sources_shape():
    from generators import mslr_like
    with open(os.path.join(HERE, "configs", "mslr.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    pub, params = cfg["published"], cfg["params"]
    assert mslr_like.FEATURES == 137 == pub["features"]
    assert (mslr_like.COUNTS + mslr_like.SCORES + mslr_like.RATIOS
            == mslr_like.FEATURES)
    assert mslr_like.GROUP == 120
    assert cfg["rows"] == 96000 * 120 and cfg["rows"] < 1 << 24
    # whole queries a block, whole blocks, 1,000 held-out queries of a block
    assert cfg["block_rows"] % 120 == 0 and cfg["rows"] % cfg["block_rows"] == 0
    assert cfg["heldout_rows"] == 1000 * 120 <= cfg["block_rows"]
    # the held-out queries are the test fold's, the block after the last
    # training block
    assert cfg["rows"] // cfg["block_rows"] == mslr_like.TEST_FOLD
    assert params["objective"] == "lambdarank"
    assert (params["num_leaves"], params["max_bin"]) == (255, 255)
    assert (params["learning_rate"], params["min_data_in_leaf"],
            params["min_sum_hessian_in_leaf"]) == (0.1, 0, 100.0)
    assert not [k for k in params if k.startswith("tpu_")]
    assert cfg["reduced"] == ["rows", "num_trees", "enable_bundle"]
    assert len(cfg["source"]) <= 200
    assert cfg["name"] != EXTRA_CONFIG["name"]
    entry = [c for c in bench["configs"] if c["name"] == "mslr"][0]
    assert (entry["source"], entry["reduced"]) == (cfg["source"],
                                                   cfg["reduced"])
    cell = [c for c in bench["workloads"] if c["name"] == CELL][0]
    assert (cell["chips"], cell["traffic"]) == (1, "train_steady")
    for name in NEW:
        metric = [m for m in bench["per_layer"] if m["name"] == name][0]
        assert metric["workloads"] == [CELL]
    for name in cfg["limits"]:
        assert name in cfg["limits_why"], name
    assert cfg["limits"]["count_mismatch"] == 0


def test_generator_law():
    import jax
    from drivers import train
    from generators import mslr_like as gen
    from harness import quality
    make = jax.jit(gen.make_block, static_argnums=(2,))
    key = train.seed_key(4200000011)
    rows = 36000
    x, y = (np.asarray(a) for a in make(key, 3, rows))
    assert x.shape == (rows, 137) and x.dtype == np.float32
    assert np.isfinite(x).all()
    shares = np.bincount(y.astype(int), minlength=5) / rows
    assert set(np.unique(y)) == {0.0, 1.0, 2.0, 3.0, 4.0}
    np.testing.assert_allclose(shares, gen.GRADE_SHARES, atol=0.02)
    counts = x[:, :gen.COUNTS]
    scores = x[:, gen.COUNTS:gen.COUNTS + gen.SCORES]
    ratios = x[:, gen.COUNTS + gen.SCORES:]
    # whole counts, 30% to 70% zeros a column, some past a thousand
    assert (counts == np.floor(counts)).all() and counts.min() == 0
    zeros = (counts == 0).mean(axis=0)
    assert zeros.min() > 0.25 and zeros.max() < 0.75, zeros
    assert counts.max() > 1000
    # scores positive and heavy-tailed
    assert scores.min() > 0
    assert np.median(np.percentile(scores, 99.9, axis=0)
                     / np.median(scores, axis=0)) > 10
    # ratios bounded, 5% to 40% zeros a column
    assert ratios.min() == 0 and ratios.max() < 1
    rz = (ratios == 0).mean(axis=0)
    assert rz.min() > 0.03 and rz.max() < 0.42, rz
    # any block again, bit for bit; another block differs
    again = make(key, 3, rows)
    assert np.asarray(again[0]).tobytes() == x.tobytes()
    assert np.asarray(again[1]).tobytes() == y.tobytes()
    assert np.asarray(make(key, 4, rows)[0]).tobytes() != x.tobytes()
    # a seed draws the training blocks; the test fold is the same under all
    other = train.seed_key(4200000012)
    assert np.asarray(make(other, 3, rows)[0]).tobytes() != x.tobytes()
    fold = gen.TEST_FOLD
    for a, b in zip(make(key, fold, rows), make(other, fold, rows)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert (np.asarray(make(key, fold + 1, rows)[0]).tobytes()
            != np.asarray(make(key, fold, rows)[0]).tobytes())
    assert (np.asarray(make(key, fold - 1, rows)[0]).tobytes()
            != np.asarray(make(other, fold - 1, rows)[0]).tobytes())
    # the tables are the population's: a seed draws documents, not weights;
    # the columns that load on relevance rank a query's documents
    w = np.asarray(gen.tables()[0])
    proxy = np.log1p(np.abs(x)) @ w
    random = np.random.default_rng(0).normal(size=rows)
    assert (quality.ndcg10(y, proxy, gen.GROUP)
            > quality.ndcg10(y, random, gen.GROUP) + 0.2)


def test_the_guard_refuses_a_fill_that_bitcasts_its_lane_numbers(
        monkeypatch):
    """The guard passes the program as it is and refuses one whose ranking
    fill sends its lane numbers through a float32, as the fill before the
    mend did."""
    import jax
    import jax.numpy as jnp
    from generators import mslr_like
    from lightgbm_tpu.objectives.rank import LambdarankNDCG
    mslr_like._refuse_a_program_whose_ranking_fill_drops_its_lambdas()

    def float_lanes(self):
        def fn(score, rid, live, *gargs):
            lane = jnp.arange(score.shape[0], dtype=jnp.int32)
            lane_f = jax.lax.bitcast_convert_type(lane, jnp.float32)
            return score * 0 + lane_f, score * 0
        return fn
    monkeypatch.setattr(LambdarankNDCG, "payload_pos_fn", float_lanes)
    with pytest.raises(SystemExit) as e:
        mslr_like._refuse_a_program_whose_ranking_fill_drops_its_lambdas()
    assert e.value.code == 2


def test_traced_run_carries_the_new_metric(mslr, monkeypatch, capsys):
    from harness import xtrace
    monkeypatch.setattr(xtrace, "find_xplane", lambda logdir: RECORDED)
    rc = mslr.main(["--workload", CELL, "--seed", "4200000043",
                    "--seconds", "0.5", "--trace", "1"])
    out = capsys.readouterr().out
    result = json.loads(out.strip().splitlines()[-1])
    assert rc == 0
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = {m["name"] for m in bench["per_layer"]
             if "workloads" not in m or CELL in m["workloads"]}
    assert set(NEW) <= names == set(result["metrics"])
    # every persist tree of the run from the ranking fill
    assert result["metrics"]["ranktree_pct"]["value"] == 100.0
    for name in ("train_step_mfu", "hist_roofline", "partition_roofline"):
        assert result["metrics"][name]["value"] > 0, name
    assert "persist carry live at every mark=True" in out
    # as many trees in the model text as trained, none of one leaf
    model = [line for line in out.splitlines() if line.startswith("model: ")]
    trees, trained, least = (int(v) for v in np.asarray(
        model[0].split())[[1, 7, 13]])
    assert trees == trained >= 48 and least > 1, model
    assert result["failed"] == 0 and result["attempted"] >= 16
    assert result["correct"] is True
    assert result["checks"]["count_mismatch"]["value"] == 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_untraced_run_reports_the_end_to_end_metrics(mslr, capsys):
    rc = mslr.main(["--workload", CELL, "--seed", "4200000044",
                    "--seconds", "0.5", "--trace", "0"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True
    assert set(result["metrics"]) == {"train_throughput", "peak_hbm",
                                      "heldout_score", "setup_s"}
    # NDCG@10 of the first 16 trees on 20 held-out queries
    assert 0.0 < result["metrics"]["heldout_score"]["value"] <= 1.0
