"""``tools/limits.py`` for a cell whose driver is ``train_cat``: the same
readings (program, bfloat16 control, planted faults, over several seeds in one
process at the cell's own size), with the category columns handed over as
``categorical_feature`` and the reference that walks categorical splits
(``harness/reference_cat.py``), and one fault more among the model faults:
one category moved from a split's left set to its right
(``reference_cat.moved_category``).

    python3 benchmark/tools/limits_cat.py --workload expo_cat.train_steady \
        --seeds 11,12,13 [--faults 2] [--feed-faults 1] [--out <file>]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import limits  # noqa: E402  (puts benchmark/ and the repo on sys.path)
import run as bench_run  # noqa: E402
from drivers import train_cat  # noqa: E402
from harness import faults, reference_cat  # noqa: E402

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    workload = ap.parse_known_args()[0].workload
    cfg = bench_run.load_cell(workload)[2]
    faults.MODEL["moved_category"] = reference_cat.moved_category
    with train_cat.cat_inputs(cfg):
        sys.exit(limits.main())
