"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data found by name: the cell in
``BENCHMARK.json``, its configuration in ``benchmark/configs/``, its traffic
mix in ``benchmark/traffic/`` (which names a driver in ``benchmark/drivers/``
and a generator in ``benchmark/generators/``), and each per-layer metric in
``benchmark/layer_metrics/`` (which names a reader in ``benchmark/readers/``).
The last line of standard output is the result object; everything else goes
on earlier lines. Fails, printing no result, unless JAX finds the TPU chips
the cell asks for and ``benchmark/peaks.json`` knows their kind.
"""
import time

T_START = time.time()

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)

from harness import device as device_mod  # noqa: E402


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name):
    """(BENCHMARK.json, the cell's entry, its configuration, its traffic mix)."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError("no workload %r in BENCHMARK.json (%s)"
                       % (name, ", ".join(sorted(cells))))
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(ROOT, configs[cell["config"]]["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return bench, cell, cfg, traffic


def applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the raw trace under .cache/benchmark/trace")
    args = ap.parse_args(argv)

    try:
        bench, cell, cfg, traffic = load_cell(args.workload)
    except KeyError as e:
        sys.stderr.write("benchmark: %s\n" % e.args[0])
        return 2

    device, peak = device_mod.check_device(cell["chips"])
    import lightgbm_tpu  # noqa: F401  (x64 and the compile-cache rule)
    args.workdir = os.path.join(ROOT, ".cache", "benchmark")
    os.makedirs(args.workdir, exist_ok=True)

    driver = importlib.import_module("drivers." + traffic["driver"])
    out = driver.run(cell, cfg, traffic, args, device, peak, T_START)

    metrics = {}
    if args.trace:
        for entry in bench["per_layer"]:
            if not applies(entry, cell["name"]):
                continue
            spec = load_json(HERE, "layer_metrics", entry["name"] + ".json")
            reader = importlib.import_module("readers." + spec["reader"])
            value = reader.read(spec, out["ctx"])
            if value is not None:
                metrics[entry["name"]] = {"value": value,
                                          "unit": entry["unit"]}
    else:
        for entry in bench["end_to_end"]:
            if applies(entry, cell["name"]) and \
                    entry["name"] in out["end_to_end"]:
                metrics[entry["name"]] = {
                    "value": out["end_to_end"][entry["name"]],
                    "unit": entry["unit"]}

    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    trace = out["ctx"].get("trace")
    if args.trace and trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if args.trace and out["breakdown"]:
        result["breakdown"] = out["breakdown"]
    result["checks"] = out["checks"]
    for name, c in out["checks"].items():
        sys.stderr.write("check %s: value %r limit %r\n"
                         % (name, c["value"], c["limit"]))
    sys.stderr.write("correct: %s\n" % out["correct"])
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
