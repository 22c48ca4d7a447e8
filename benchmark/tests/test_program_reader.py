"""The per-layer metrics that read the program's own run record
(``readers/program.py``), rehearsed on a tiny forced-persist train on the
CPU: every one of them reads something, the set-up parts are no larger than
the job they are parts of, and each reads nothing (None, no error) from a
program that keeps no such record."""
import glob
import json
import os

import numpy as np
import pytest

from readers import program

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPECS = {}
for path in sorted(glob.glob(os.path.join(HERE, "layer_metrics", "*.json"))):
    with open(path) as f:
        spec = json.load(f)
    if spec["reader"] == "program":
        SPECS[spec["name"]] = spec
SETUP_PARTS = ("payload_pack_s", "carry_init_s", "compile_s", "cache_load_s",
               "train_host_setup_s")


@pytest.fixture(scope="module")
def trained():
    """48 iterations = three fused launches of 16, telemetry off, then the
    model asked for (which builds the host trees), as the driver does."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu import telemetry
    telemetry.disable()
    telemetry.reset()
    rng = np.random.default_rng(11)
    X = rng.normal(size=(2000, 8)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "metric": "none", "tpu_persist_scan": "force"}
    bst = lgb.train(params, lgb.Dataset(X, y), 48, verbose_eval=False)
    bst.model_to_string(num_iteration=-1)
    return bst


def test_the_nine_metrics_are_declared():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    assert len(SPECS) == 9
    for name, spec in SPECS.items():
        entry = declared[name]
        assert entry["workloads"] == ["higgs.train_steady"]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert entry[key] == spec[key], (name, key)


@pytest.mark.parametrize("name", sorted(SPECS))
def test_metric_reads_the_run_record(trained, name):
    value = program.read(SPECS[name], {})
    assert value is not None
    assert value >= 0
    if name == "fastpath_tree_pct":
        assert value == 100.0
    if name == "materialize_ms":
        assert value > 0


def test_setup_parts_fit_inside_the_job(trained):
    root, spans, _ = program.record()
    parts = {n: program.read(SPECS[n], {}) for n in SETUP_PARTS}
    assert all(v >= 0 for v in parts.values()), parts
    assert parts["compile_s"] > 0 and parts["payload_pack_s"] > 0
    assert sum(parts.values()) <= root["dur"]
    # the five add up to the stretch they were cut from
    first = [e for e in spans if e["name"] == "ops::persist_scan(launch)"
             and e["launch"] == 0][0]
    assert sum(parts.values()) == pytest.approx(
        first["ts"] + first["dur"] - root["ts"], abs=1e-6)
    launches = {e["launch"] for e in spans if "launch" in e}
    assert launches == {0, 1, 2}


@pytest.mark.parametrize("name", sorted(SPECS))
def test_metric_reads_nothing_without_the_api(trained, monkeypatch, name):
    from lightgbm_tpu import telemetry
    monkeypatch.delattr(telemetry, "ring_snapshot")
    assert program.read(SPECS[name], {}) is None


def test_metric_reads_nothing_before_any_train(trained, monkeypatch):
    from lightgbm_tpu import telemetry
    monkeypatch.setattr(telemetry, "ring_snapshot", lambda: [])
    assert program.read(SPECS["compile_s"], {}) is None
