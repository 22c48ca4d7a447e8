"""The least work a boosting iteration needs, counted from the trees it grew.

The count depends on the trees and the data's shape alone, so it reads the same
whatever kernel does the work. Per row a learner must hold the binned features
(one byte each at max_bin <= 255) and a float32 gradient and hessian:
``row_bytes = features + 8``.

* histogram of a node: read each of its rows once and add its gradient and
  hessian into one bin per feature (2 adds a feature). With the subtraction
  trick only the root and the smaller child of every split are built.
* partition of a split: read every row of the parent once and write it once.
* per tree, over all rows: the gradient fill (read score and label, write
  gradient and hessian: 16 B, 8 operations) and the score update (read and
  write the score: 8 B, 1 add).
"""


def tree_work(tree, rows, features):
    """{"hist": (ops, bytes), "partition": (ops, bytes), "fill": (ops, bytes)}
    for one parsed tree over ``rows`` training rows."""
    row_bytes = features + 8
    hist_rows = rows                                   # the root
    part_rows = 0
    for node in range(tree["num_leaves"] - 1):
        kids = []
        for child in (tree["left_child"][node], tree["right_child"][node]):
            kids.append(int(tree["leaf_count"][~child]) if child < 0
                        else int(tree["internal_count"][child]))
        hist_rows += min(kids)
        part_rows += int(tree["internal_count"][node])
    return {"hist": (2 * features * hist_rows, row_bytes * hist_rows),
            "partition": (0, 2 * row_bytes * part_rows),
            "fill": (9 * rows, 24 * rows)}


def launch_work(trees, rows, features):
    """Summed ``tree_work`` of the trees of one launch."""
    total = {"hist": [0, 0], "partition": [0, 0], "fill": [0, 0]}
    for tree in trees:
        for part, (ops, nbytes) in tree_work(tree, rows, features).items():
            total[part][0] += ops
            total[part][1] += nbytes
    total["step"] = [sum(v[0] for v in total.values()),
                     sum(v[1] for v in total.values())]
    return {k: tuple(v) for k, v in total.items()}


def least_seconds(work, peak):
    """Roofline: the larger of operations over peak and bytes over peak.
    The adds are float32 vector work, so they are held to the chip's
    matrix peak only as a bound that cannot be beaten."""
    ops, nbytes = work
    return max(ops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
