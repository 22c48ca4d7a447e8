"""Expo-shaped synthetic rows with the six airline categories as categorical
columns, not one-hot blocks: the raw columns of the reference's Expo
experiment (Month, DayofMonth, DayOfWeek, UniqueCarrier, Origin, Dest,
DepTime, Distance), as a LightGBM user hands them over with
``categorical_feature=[0, 1, 2, 3, 4, 5]``.

The rows are ``expo_like``'s, imported: the same level counts (12, 31, 7, 22,
313, 313), the same Zipf(1) popularity within a block (level 0 the most
frequent), the same fixed label tables, in which only the ``HUBS`` (30)
busiest levels of a block carry an effect of their own. A seed draws the
same rows here as there; the columns differ only in how the categories are
coded, so this cell and ``expo.train_steady`` are the reference's own
one-hot-versus-categorical comparison on one population.

``make_block`` returns the dense [rows, 8] f32 block: the six level ids
(non-negative whole numbers), then DepTime and Distance.
"""
import sys

import jax.numpy as jnp

from generators.expo_like import CARDS  # noqa: F401  (the driver's)
from generators.expo_like import make_block as _compact_block


def _refuse_a_program_without_categorical_persist():
    """A program from before the persist path's categorical arm trains these
    columns on the per-tree v1 grower (its gate refuses any categorical
    feature), which at this cell's 36M rows is a different system at a
    fraction of the speed, for as long as the window lasts. Such a program
    cannot run this configuration; say so at once, with run.py's own exit
    code for a cell it cannot run."""
    from lightgbm_tpu.ops import pallas_grow
    if not hasattr(pallas_grow, "S_CAT"):
        sys.stderr.write(
            "benchmark: configuration expo_cat needs a program whose persist "
            "path splits categorical columns (lightgbm_tpu/ops/pallas_grow."
            "py:S_CAT); this one cannot run it\n")
        raise SystemExit(2)


_refuse_a_program_without_categorical_persist()

FEATURES = 8
CATEGORICAL = (0, 1, 2, 3, 4, 5)
GROUP = 1            # rows that must stay together in a block
# the least bytes that hold one binned row: four categories of <= 255 levels
# at one byte, the two of 313 at two, two numerics at one (expo_like's count)
WORK_FEATURES = 10


def make_block(key, index, rows):
    """(x [rows, 8] f32, y [rows] f32) of block ``index``: expo_like's block
    of the same key and index, its category ids as values."""
    cat, num, y = _compact_block(key, index, rows)
    return jnp.concatenate([cat.astype(jnp.float32), num], axis=1), y
