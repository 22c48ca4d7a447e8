"""Does the program's first launch grow sound trees? Trains one launch (16
iterations) of a configuration on each seed, with overrides, in one process,
and has the reference follow those trees.

    python3 benchmark/tools/tree0_probe.py --config higgs --seeds 5,7 \
        [--rows N] [--param min_data_in_leaf=1 ...] [--dump 6]

Prints one line a seed: leaves a tree, the first tree whose widest leaf gap is
over 0.1, and tree 0's numbers; with --dump the first nodes of the worst tree.
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


from drivers import train  # noqa: E402
from harness import reference  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--param", action="append", default=[])
    ap.add_argument("--rounds", type=int, default=16)
    ap.add_argument("--dump", type=int, default=0)
    args = ap.parse_args(argv)
    import lightgbm_tpu as lgb
    with open(os.path.join(HERE, "configs", args.config + ".json")) as f:
        cfg = json.load(f)
    if args.rows:
        cfg["rows"] = args.rows
    for kv in args.param:
        k, _, v = kv.partition("=")
        cfg["params"][k] = json.loads(v)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        rows, X, y, _, _, group = train.inputs(cfg, seed)
        bst = train.train_call(lgb, cfg["params"], X, y, group, args.rounds,
                               [], {})
        n_models = len(bst._booster.models)
        trees = reference.parse_model(bst.model_to_string(num_iteration=-1))
        init = train.init_score(cfg["params"], y)
        del bst, X
        numbers, per_tree = train.check(rows, trees, cfg, init)
        bad = next((t for t, p in enumerate(per_tree) if not p[1] <= 0.1),
                   None)
        print(json.dumps({
            "rows": rows.rows, "seed": seed, "overrides": args.param,
            "trained": n_models, "in_text": len(trees),
            "leaves": [t["num_leaves"] for t in trees],
            "first_bad_tree": bad, "tree0": per_tree[0][:3],
            "numbers": numbers, "seconds": round(time.time() - t0, 1)}),
            flush=True)
        if args.dump and bad is not None:
            tree = trees[bad]
            for node in range(min(args.dump, tree["num_leaves"] - 1)):
                print("  tree %d node %d: feature %d threshold %.6g gain %.6g "
                      "rows %d left %d right %d"
                      % (bad, node, tree["split_feature"][node],
                         tree["threshold"][node], tree["split_gain"][node],
                         tree["internal_count"][node],
                         tree["left_child"][node], tree["right_child"][node]),
                      flush=True)
            print("  where: %s" % (per_tree[bad][3],), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
