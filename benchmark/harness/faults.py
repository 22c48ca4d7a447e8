"""Faults a training run can have, planted so that the comparison can be
shown to fail on each. ``MODEL`` faults rewrite the model the run returned,
as the fault would have left it; ``FEED`` faults change what the program is
given to train on, underneath the harness."""
import copy

import numpy as np


def state_unchanged(trees):
    """A step that returns its state unchanged: the scores are not updated, so
    the next iteration sees the same gradients and grows the same tree."""
    out = copy.deepcopy(trees)
    t = len(out) // 2
    out[t + 1] = copy.deepcopy(out[t])
    return out


def answer_altered(trees):
    """An answer altered where it is produced: one leaf output a quarter too
    large (an alteration under the program's own widest gap cannot show)."""
    out = copy.deepcopy(trees)
    tree = out[len(out) // 2]
    leaf = int(np.argmax(np.abs(tree["leaf_value"])))
    tree["leaf_value"][leaf] *= 1.25
    return out


def half_batch(X, y, group):
    """Half of the batch left out, the statistics taken over the rest."""
    half = len(y) // 2
    if group is not None:
        group = group[:len(group) // 2]
        half = int(np.sum(group))
    return X[:half], y[:half], group


MODEL = {"state_unchanged": state_unchanged, "answer_altered": answer_altered}
FEED = {"half_batch": half_batch}
