"""Readings from which a configuration's limits are set (steps 4 and 5 of how
``correct`` is decided): over several seeds in one process, at the cell's own
size, the numbers of the program, of the control, and of each planted fault.

    python3 benchmark/tools/limits.py --workload <cell> --seeds 11,12,13 \
        [--faults 2] [--feed-faults 1] [--out chiprun_out/limits.jsonl]

Each seed trains through the cell's own driver (warm-up launch and one window
launch), so the trees are the timed path's. Prints one JSON line per reading.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)

import run as bench_run  # noqa: E402
from drivers import train  # noqa: E402
from harness import device as device_mod  # noqa: E402
from harness import faults  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--faults", type=int, default=3,
                    help="seeds on which the model faults are read")
    ap.add_argument("--feed-faults", type=int, default=0,
                    help="seeds on which the feed faults are read (each "
                         "trains once more)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    _, cell, cfg, traffic = bench_run.load_cell(args.workload)
    device, peak = device_mod.check_device(cell["chips"])
    import lightgbm_tpu  # noqa: F401
    workdir = os.path.join(ROOT, ".cache", "benchmark")
    os.makedirs(workdir, exist_ok=True)
    out = open(args.out, "a") if args.out else None

    def emit(seed, what, numbers, seconds):
        line = json.dumps({"workload": args.workload, "seed": seed,
                           "what": what, "numbers": numbers,
                           "seconds": round(seconds, 1),
                           "device": device["kind"]})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    def one_run(seed):
        run_args = argparse.Namespace(seed=seed, seconds=args.seconds,
                                      trace=0, keep_trace=False,
                                      workdir=workdir)
        return train.run(cell, cfg, traffic, run_args, device, peak,
                         time.time())

    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        res = one_run(seed)
        rows, trees, init = res["rows"], res["trees"], res["init"]
        numbers = {k: v["value"] for k, v in res["checks"].items()}
        numbers["correct"] = res["correct"]
        numbers["e2e"] = res["end_to_end"]
        emit(seed, "program", numbers, time.time() - t0)
        t0 = time.time()
        sums = train.follow(rows, trees, cfg, init)
        emit(seed, "control_bf16",
             train.control(rows, trees, cfg, init, sums)[0],
             time.time() - t0)
        if i < args.faults:
            for name, plant in faults.MODEL.items():
                t0 = time.time()
                emit(seed, "fault_" + name,
                     train.check(rows, plant(trees), cfg, init)[0],
                     time.time() - t0)
        if i < args.feed_faults:
            for name, plant in faults.FEED.items():
                t0 = time.time()
                real = train.train_call

                def broken(lgb, params, X, y, group, *rest):
                    return real(lgb, params, *plant(X, y, group), *rest)
                train.train_call = broken
                try:
                    res = one_run(seed)
                finally:
                    train.train_call = real
                emit(seed, "fault_" + name,
                     {k: v["value"] for k, v in res["checks"].items()},
                     time.time() - t0)
        del res, rows, trees, sums
    return 0


if __name__ == "__main__":
    sys.exit(main())
