"""``tools/limits.py`` for a cell whose driver is ``train_sparse``: the same
readings (program, bfloat16 control, planted faults, over several seeds in one
process at the cell's own size), with the rows handed over as CSR, and then
the fault only bundled rows can have: a bundling that gives up more rows than
the configuration states. On the first seed the program's Dataset is built
again with its conflict budget raised (``max_conflict_rate``, which the
configuration leaves at its default of 0) and ``bundle_lost_share`` is read
from the raw blocks, as the run's own check reads it.

    python3 benchmark/tools/limits_sparse.py --workload expo.train_steady \
        --seeds 11,12,13 [--faults 2] [--feed-faults 1] [--out <file>]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import limits  # noqa: E402  (puts benchmark/ and the repo on sys.path)
import run as bench_run  # noqa: E402
from drivers import train_sparse  # noqa: E402

# conflict budgets planted, as shares of the binning sample, beside the
# program's own 1 in 10,000: eleven and a hundred and one times it
OVER_LOSSY = (0.001, 0.01)


def over_lossy(workload, seed, out):
    import lightgbm_tpu as lgb
    _, _, cfg, _ = bench_run.load_cell(workload)
    rows, X, y = train_sparse.inputs(cfg, seed)[:3]
    for rate in OVER_LOSSY:
        t0 = time.time()
        ds = lgb.Dataset(X, y, params=dict(cfg["params"],
                                           max_conflict_rate=rate))
        ds.construct()
        rows.note_bundles(ds._inner)
        for b in range(rows.blocks):
            rows.device_block(b)
        line = json.dumps({
            "workload": workload, "seed": seed,
            "what": "fault_conflict_budget_%g" % rate,
            "numbers": {"bundle_lost_share": rows.lost_rows / rows.rows,
                        "groups": len(ds._inner.groups)},
            "seconds": round(time.time() - t0, 1)})
        print(line, flush=True)
        if out:
            with open(out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_known_args()[0]
    with train_sparse.sparse_inputs():
        rc = limits.main()
    over_lossy(args.workload, int(args.seeds.split(",")[0]), args.out)
    sys.exit(rc)
