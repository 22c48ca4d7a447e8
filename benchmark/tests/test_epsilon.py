"""Rehearsal of ``epsilon.train_steady`` on the CPU at a tiny size: the
generator's law (unit-length rows, a balanced label, any block made again bit
for bit, the population's tables the same under every seed) and, through the
harness's own entry point, the result line of a ``--trace 1`` run (reduced
from the recorded trace), which has to carry the three metrics the cell
brought. The rows are cut to 256 of the 2,000 columns for the run: the XLA
emulation the CPU takes pays for every group, and 256 byte columns are still
a payload wide enough (69 words) for its width to size the chunks."""
import json
import os

import numpy as np
import pytest

from conftest import HERE

CELL = "epsilon.train_steady"
TINY = {"rows": 8192, "block_rows": 4096, "heldout_rows": 4096}
COLUMNS = 256
RECORDED = os.path.join(HERE, "tests", "data", "higgs_launch_head.xplane.pb")
NEW = ("widepath_tree_pct", "grower_build_s", "dense_binning_s")


def tiny_epsilon():
    with open(os.path.join(HERE, "configs", "epsilon.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg["params"].update(num_leaves=15, min_sum_hessian_in_leaf=1.0,
                         tpu_persist_scan="force")
    return cfg


@pytest.fixture
def epsilon(monkeypatch):
    """The harness patched for a CPU run of the cell: no chip look, the tiny
    configuration on 256 columns, and the kernel mode the CPU has."""
    import run as bench_run
    from generators import epsilon_like
    from harness import device

    monkeypatch.setattr(epsilon_like, "FEATURES", COLUMNS)
    peaks = device.load_peaks()["TPU v5 lite"]
    monkeypatch.setattr(
        device, "check_device",
        lambda chips: ({"platform": "cpu", "kind": "rehearsal",
                        "count": chips}, peaks))
    real = bench_run.load_json

    def load(*parts):
        d = real(*parts)
        if parts[-1].endswith("configs/epsilon.json"):
            d = tiny_epsilon()
        if parts[-1] == "train_steady.json":
            d["kernel_mode"] = ["xla", True]
        return d
    monkeypatch.setattr(bench_run, "load_json", load)
    return bench_run


def test_configuration_is_the_sources_shape():
    from generators import epsilon_like
    with open(os.path.join(HERE, "configs", "epsilon.json")) as f:
        cfg = json.load(f)
    pub = cfg["published"]
    assert epsilon_like.FEATURES == 2000 == pub["features"]
    assert cfg["rows"] == pub["rows"] == 400000
    assert cfg["heldout_rows"] == pub["test_rows"] == 100000
    assert (cfg["params"]["max_bin"], cfg["params"]["num_leaves"]) == (63, 255)
    assert not [k for k in cfg["params"] if k.startswith("tpu_")]
    assert cfg["reduced"] == ["num_trees", "enable_bundle"]
    assert len(cfg["source"]) <= 200


def test_generator_law():
    import jax
    from drivers import train
    from generators import epsilon_like as gen
    make = jax.jit(gen.make_block, static_argnums=(2,))
    key = train.seed_key(3600000011)
    # 2,048 rows: a trace of make_block is kept by its shapes, and the run
    # below makes blocks of 4,096 rows on 256 columns
    x, y = (np.asarray(a) for a in make(key, 3, 2048))
    assert x.shape == (2048, 2000) and x.dtype == np.float32
    np.testing.assert_allclose(np.linalg.norm(x.astype(np.float64), axis=1),
                               1.0, atol=1e-6)
    assert set(np.unique(y)) == {0.0, 1.0} and 0.45 < y.mean() < 0.55
    # dense: no column is constant, none stands out
    sd = x.std(axis=0)
    assert sd.min() > 0.5 * sd.max() > 0
    again = make(key, 3, 2048)
    assert np.asarray(again[0]).tobytes() == x.tobytes()
    assert np.asarray(again[1]).tobytes() == y.tobytes()
    other = np.asarray(make(key, 4, 2048)[0])
    assert other.tobytes() != x.tobytes()
    # the tables are the population's: a seed draws rows, not weights
    mix, w = (np.asarray(a) for a in gen.tables())
    assert mix.shape == (gen.FACTORS, 2000) and w.shape == (2000,)
    assert np.count_nonzero(np.abs(w) > 0.1 * np.abs(w).max()) > 500


def test_traced_run_carries_the_three_new_metrics(epsilon, monkeypatch,
                                                  capsys):
    from harness import xtrace
    monkeypatch.setattr(xtrace, "find_xplane", lambda logdir: RECORDED)
    rc = epsilon.main(["--workload", CELL, "--seed", "3600000043",
                       "--seconds", "0.5", "--trace", "1"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = {m["name"] for m in bench["per_layer"]
             if "workloads" not in m or CELL in m["workloads"]}
    assert set(NEW) <= names == set(result["metrics"])
    # the counters are the process's, so other tests' trees count too
    assert 0.0 < result["metrics"]["widepath_tree_pct"]["value"] <= 100.0
    for name in ("grower_build_s", "dense_binning_s", "train_step_mfu",
                 "hist_roofline", "partition_roofline"):
        assert result["metrics"][name]["value"] > 0, name
    assert result["failed"] == 0 and result["attempted"] >= 16
    assert result["checks"]["count_mismatch"]["value"] == 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
