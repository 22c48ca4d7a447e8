"""Rehearsal of ``criteo.train_data4`` on the CPU at a tiny size: the
configuration's shape, the generator's law (67 columns, the zero shares, the
label's rate, any block made again bit for bit), ``drivers/train_mesh.py``
(one chip's share of the work; a run off the sharded path fails) and,
through the harness's own entry point, the result line of a ``--trace 1``
run (reduced from the recorded trace), which has to carry the three metrics
the cell brought. The mesh is four virtual CPU devices: this module asks for
them as it is imported, which is before any test of the session starts the
backend (``rehearse_criteo.sh`` asks for them too); where another backend
is up already, the runs are skipped."""
import json
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from conftest import HERE  # noqa: E402

CELL = "criteo.train_data4"
TINY = {"rows": 8192, "block_rows": 4096, "heldout_rows": 4096}
RECORDED = os.path.join(HERE, "tests", "data", "higgs_launch_head.xplane.pb")
NEW = ("collective_pct", "sharded_tree_pct", "shard_put_s")


def tiny_criteo(**params):
    with open(os.path.join(HERE, "configs", "criteo.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg["params"].update(num_leaves=15, tpu_persist_scan="force", **params)
    return cfg


@pytest.fixture
def criteo(monkeypatch):
    """The harness patched for a CPU run of the cell: no chip look, the tiny
    configuration, and the kernel mode the CPU has. ``criteo.params`` holds
    overrides of the configuration's parameters."""
    import jax
    import run as bench_run
    from harness import device
    if len(jax.devices()) < 4:
        pytest.skip("the backend came up with %d devices, the cell's mesh "
                    "needs 4" % len(jax.devices()))
    peaks = device.load_peaks()["TPU v5 lite"]
    monkeypatch.setattr(
        device, "check_device",
        lambda chips: ({"platform": "cpu", "kind": "rehearsal",
                        "count": chips}, peaks))
    real = bench_run.load_json
    bench_run.params = {}

    def load(*parts):
        d = real(*parts)
        if parts[-1].endswith("configs/criteo.json"):
            d = tiny_criteo(**bench_run.params)
        if parts[-1] == "train_steady_data.json":
            d["kernel_mode"] = ["xla", True]
        return d
    monkeypatch.setattr(bench_run, "load_json", load)
    return bench_run


def test_configuration_is_the_sources_shape():
    from generators import criteo_like
    with open(os.path.join(HERE, "configs", "criteo.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    pub, params = cfg["published"], cfg["params"]
    assert criteo_like.FEATURES == 67 == pub["features"]
    assert criteo_like.INTS + 2 * criteo_like.CATS + 2 == 67
    assert (params["num_leaves"], params["max_bin"]) == (255, 255)
    assert (params["tree_learner"], params["tpu_num_devices"]) == ("data", 4)
    assert (params["min_data_in_leaf"],
            params["min_sum_hessian_in_leaf"]) == (20, 1e-3)
    assert cfg["machines"] == 4 and 4 in pub["machines"]
    assert pub["seconds_per_tree"][pub["machines"].index(4)] == 156
    assert cfg["rows"] > 1 << 24 and cfg["rows"] % 4 == 0
    assert cfg["heldout_rows"] <= cfg["block_rows"]
    assert cfg["reduced"] == ["rows", "num_trees", "enable_bundle"]
    assert len(cfg["source"]) <= 200
    entry = [c for c in bench["configs"] if c["name"] == "criteo"][0]
    assert (entry["source"], entry["reduced"]) == (cfg["source"],
                                                   cfg["reduced"])
    cell = [c for c in bench["workloads"] if c["name"] == CELL][0]
    assert (cell["chips"], cell["traffic"]) == (4, "train_steady_data")
    for name in NEW:
        metric = [m for m in bench["per_layer"] if m["name"] == name][0]
        assert metric["workloads"] == [CELL]
    for name, limit in cfg["limits"].items():
        assert name in cfg["limits_why"], name
    assert cfg["limits"]["count_mismatch"] == 0


def test_generator_law():
    import jax
    from drivers import train
    from generators import criteo_like as gen
    make = jax.jit(gen.make_block, static_argnums=(2,))
    key = train.seed_key(3800000011)
    rows = 65536
    x, y = (np.asarray(a) for a in make(key, 3, rows))
    assert x.shape == (rows, 67) and x.dtype == np.float32
    assert np.isfinite(x).all()
    assert set(np.unique(y)) == {0.0, 1.0} and 0.02 < y.mean() < 0.04
    ints, rate, count = x[:, :13], x[:, 13:39], x[:, 39:65]
    # the integer fields: whole, heavy-tailed, 10% to 50% zeros a column
    assert (ints == np.floor(ints)).all() and ints.min() == 0
    zeros = (ints == 0).mean(axis=0)
    assert zeros.min() > 0.10 and zeros.max() < 0.50, zeros
    assert (np.percentile(ints, 99.9, axis=0)
            > 20 * np.median(ints, axis=0).clip(1)).all()
    # no column anywhere near sparse_threshold (0.8)
    assert (x == 0).mean(axis=0).max() < 0.5
    # rates in [0, 1] about the base rate; counts whole and heavy-tailed
    assert rate.min() >= 0.0 and rate.max() <= 1.0
    assert (np.abs(np.median(rate, axis=0) - gen.BASE_RATE) < 0.02).all()
    assert (count == np.floor(count)).all() and count.min() >= 1
    assert (count.max(axis=0) > 100 * np.median(count, axis=0)).any()
    # a level's rate and count are the level's, not the row's: the field of
    # 10 levels shows at most 10 (rate, count) pairs, however many rows
    assert gen.CAT_LEVELS[0] == 10
    assert 8 <= len(set(zip(rate[:, 0], count[:, 0]))) <= 10
    hour = x[:, 65]
    assert hour.min() == 0 and hour.max() == 23 and x[:, 66].min() > 0
    # any block again, bit for bit; another block differs
    again = make(key, 3, rows)
    assert np.asarray(again[0]).tobytes() == x.tobytes()
    assert np.asarray(again[1]).tobytes() == y.tobytes()
    assert np.asarray(make(key, 4, rows)[0]).tobytes() != x.tobytes()
    # the label is learnable from the columns (not noise)
    from harness import quality
    odds = np.log((rate + 1e-4) / (1 - rate + 1e-4)).sum(axis=1)
    assert quality.METRICS["auc"](y, odds, 1) > 0.6


def test_train_mesh_gives_one_chips_share_of_the_work():
    from drivers import train_mesh
    from harness import workmodel
    tree = {"num_leaves": 2, "left_child": np.asarray([-1]),
            "right_child": np.asarray([-2]),
            "leaf_count": np.asarray([300, 700]),
            "internal_count": np.asarray([1000])}
    whole = workmodel.launch_work([tree, tree], 1000, 67)
    share = train_mesh.per_chip(whole, 4)
    assert set(share) == set(whole) == {"hist", "partition", "fill", "step"}
    for part in whole:
        assert share[part] == tuple(v / 4 for v in whole[part]), part
    peak = {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    assert (workmodel.least_seconds(share["step"], peak)
            == workmodel.least_seconds(whole["step"], peak) / 4)


def test_collective_share_reads_the_names_the_chip_gave():
    """The traced launch of PR 38's first four-chip run, as its result line's
    breakdown had it (seconds, mean over the four planes): the grow loop's
    all-reduces are psum.N there."""
    from readers import opshare
    with open(os.path.join(HERE, "layer_metrics", "collective_pct.json")) as f:
        spec = json.load(f)
    ops = {"split_pass": 2.14271394075, "seg_hist": 2.13584372300,
           "root_hist": 0.635651316, "psum": 0.0538425955,
           "dynamic_update_slice": 0.041029949, "scan_pair": 0.02041342125}
    trace = {"busy_s": 5.251608444, "window_s": 5.2934474945,
             "op_seconds": ops}
    got = opshare.read(spec, {"trace": trace})
    assert got == pytest.approx(100 * 0.0538425955 / 5.251608444)
    for name in ("all-reduce-start", "all-reduce-done", "all_gather",
                 "reduce-scatter", "collective-permute-done"):
        more = dict(ops, **{name: 0.1})
        assert opshare.read(spec, {"trace": dict(trace, op_seconds=more)}) \
            == pytest.approx(100 * (0.0538425955 + 0.1) / 5.251608444), name
    assert opshare.read(spec, {}) is None
    assert opshare.read(spec, {"trace": dict(trace, op_seconds={
        "split_pass": 1.0})}) == 0.0


def test_traced_run_carries_the_three_new_metrics(criteo, monkeypatch,
                                                  capsys):
    from harness import xtrace
    monkeypatch.setattr(xtrace, "find_xplane", lambda logdir: RECORDED)
    rc = criteo.main(["--workload", CELL, "--seed", "3800000043",
                      "--seconds", "0.5", "--trace", "1"])
    out = capsys.readouterr().out
    result = json.loads(out.strip().splitlines()[-1])
    assert rc == 0
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = {m["name"] for m in bench["per_layer"]
             if "workloads" not in m or CELL in m["workloads"]}
    assert set(NEW) <= names == set(result["metrics"])
    # the counters are the process's, so other tests' trees count too
    assert 0.0 < result["metrics"]["sharded_tree_pct"]["value"] <= 100.0
    assert result["metrics"]["shard_put_s"]["value"] > 0
    # the recorded trace is one chip's: it holds no collective
    assert result["metrics"]["collective_pct"]["value"] == 0.0
    for name in ("train_step_mfu", "hist_roofline", "partition_roofline"):
        assert result["metrics"][name]["value"] > 0, name
    assert "one chip's share of 4" in out
    assert "sharded persist grower at every mark=True" in out
    assert result["device"]["count"] == 4
    assert result["failed"] == 0 and result["attempted"] >= 16
    assert result["correct"] is True
    assert result["checks"]["count_mismatch"]["value"] == 0


def test_a_run_off_the_sharded_path_fails(criteo, capsys):
    """Two shards where the cell says four chips: every tree is on the
    persist path with the right kernels, and the run still counts as
    failed, all iterations of it."""
    criteo.params = {"tpu_num_devices": 2}
    rc = criteo.main(["--workload", CELL, "--seed", "3800000044",
                      "--seconds", "0.5", "--trace", "0"])
    out = capsys.readouterr().out
    result = json.loads(out.strip().splitlines()[-1])
    assert rc == 0
    assert "at every mark=False" in out
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] >= 16
