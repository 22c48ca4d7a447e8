"""The benchmark's own tests: ``python3 -m pytest benchmark/tests -q`` on the
CPU. They rehearse the harness at a tiny size; the chip check is stubbed here,
in the test, never by an option of the harness."""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

TINY = {"higgs": {"rows": 60000, "block_rows": 20000, "heldout_rows": 5000},
        "msltr": {"rows": 73000, "block_rows": 36500, "heldout_rows": 7300}}


# The rehearsal's second configuration and cell: MS-LTR, which is no cell of
# BENCHMARK.json until the program's ranking gradient fill is repaired
# (PERF.md, Open questions). Added here as a later PR would add it: one
# configuration file, one entry each in ``configs`` and ``workloads``.
EXTRA_CONFIG = {"name": "msltr", "file": "benchmark/tests/data/msltr.json"}
EXTRA_CELL = {"name": "msltr.train_steady", "config": "msltr",
              "traffic": "train_steady", "chips": 1, "why": "rehearsal"}


def config_path(name):
    if name == EXTRA_CONFIG["name"]:
        return os.path.join(ROOT, EXTRA_CONFIG["file"])
    return os.path.join(HERE, "configs", name + ".json")


def tiny_config(name):
    """The configuration's own file at a size a test run can hold. Off the
    TPU the persist path is its XLA emulation and has to be forced; ranking
    stays on the per-iteration grower, whose emulated pos-mode fill grows
    stumps (PERF.md, Open questions)."""
    with open(config_path(name)) as f:
        cfg = json.load(f)
    cfg.update(TINY[name])
    cfg["params"].update(num_leaves=15, min_sum_hessian_in_leaf=1.0)
    if cfg["params"]["objective"] == "binary":
        cfg["params"]["tpu_persist_scan"] = "force"
    return cfg


@pytest.fixture
def rehearsal(monkeypatch):
    """Patch the harness for a CPU run: no chip look, tiny configurations,
    and the kernel mode and path the CPU has."""
    import run as bench_run
    from drivers import train
    from harness import device

    peaks = device.load_peaks()["TPU v5 lite"]
    monkeypatch.setattr(
        device, "check_device",
        lambda chips: ({"platform": "cpu", "kind": "rehearsal",
                        "count": chips}, peaks))
    real = bench_run.load_json

    def load(*parts):
        d = real(*parts)
        if parts[-1] == "BENCHMARK.json":
            d["configs"].append(dict(EXTRA_CONFIG))
            d["workloads"].append(dict(EXTRA_CELL))
        elif "generator" in d:                 # a configuration's file
            d = tiny_config(d["name"])
        if parts[-1] == "train_steady.json":
            d["kernel_mode"] = ["xla", True]
        return d
    monkeypatch.setattr(bench_run, "load_json", load)
    # ranking trains off the persist path here, so no carry is live
    real_run = train.run

    def run(cell, cfg, *rest):
        if cfg["params"]["objective"] != "binary":
            monkeypatch.setattr(train, "learner_of", _FakeLearner)
        return real_run(cell, cfg, *rest)
    monkeypatch.setattr(train, "run", run)
    return bench_run


class _FakeLearner:
    """Stands in for the learner where the CPU run has no persist carry."""
    _persist_carry = ()

    def __init__(self, bst):
        self._real = bst._booster.tree_learner
        self.grow_config = self._real.grow_config

    def _persist_kernel_effective(self):
        return ("xla", True, True)
