"""Traffic driver ``train_cat``: the ``train`` driver's job, window, path
check, trace reduction and comparison, on dense rows whose category columns
reach the program as ``categorical_feature``.

One ``lgb.train(params, Dataset(X, y, categorical_feature=...), rounds)``:
the columns the configuration's ``params["categorical_feature"]`` names are
non-negative level ids, binned and split as categories (a left set of levels
a split). Everything else is ``drivers/train.py``, imported: its ``run`` is
called with this module's ``inputs`` and ``train_call`` and with
``harness/reference_cat.py`` in the place of ``harness/reference.py`` (which
refuses a categorical split and walks thresholds alone), for the length of a
run, as ``drivers/train_sparse.py`` swaps its seams.
"""
import contextlib
import importlib
import time

import numpy as np

from drivers import train
from harness import reference_cat


class CatRows:
    """``train.Rows`` for the categorical generator: the same blocks of one
    shape from (seed key, block index), any block made again bit for bit;
    the held-out rows are the blocks after the last training block, as many
    as they take. ``features`` is the width the work model counts a binned
    row at (the generator's ``WORK_FEATURES``), not the number of columns."""

    def __init__(self, cfg, seed):
        import jax
        self.gen = importlib.import_module("generators." + cfg["generator"])
        self.group = self.gen.GROUP
        self.columns = self.gen.FEATURES
        self.features = self.gen.WORK_FEATURES
        self.rows = cfg["rows"]
        self.block = cfg["block_rows"]
        self.blocks = -(-self.rows // self.block)
        self.heldout = cfg["heldout_rows"]
        self.key = train.seed_key(seed)
        self._make = jax.jit(self.gen.make_block, static_argnums=(2,))

    def device_block(self, index):
        import jax.numpy as jnp
        x, y = self._make(self.key, index, self.block)
        live = min(self.block, self.rows - index * self.block)
        return x, y, jnp.arange(self.block) < live

    def _host(self, first, rows):
        """(X [rows, columns] f32, y [rows] f32) of the blocks from ``first``
        on, the copy of a block overlapping the next one's making."""
        X = np.empty((rows, self.columns), np.float32)
        y = np.empty((rows,), np.float32)
        pending = self._make(self.key, first, self.block)
        for lo in range(0, rows, self.block):
            x_d, y_d = pending
            first += 1
            pending = self._make(self.key, first, self.block)
            hi = min(lo + self.block, rows)
            X[lo:hi] = np.asarray(x_d)[:hi - lo]
            y[lo:hi] = np.asarray(y_d)[:hi - lo]
        return X, y

    def to_host(self):
        X, y = self._host(0, self.rows)
        Xh, yh = self._host(self.blocks, self.heldout)
        return X, y, Xh, yh


def inputs(cfg, seed):
    """``train.inputs`` for the categorical generator."""
    rows = CatRows(cfg, seed)
    X, y, Xh, yh = rows.to_host()
    return rows, X, y, Xh, yh, None


def train_call(lgb, params, X, y, group, rounds, callbacks, spans):
    """``train.train_call`` with the configuration's category columns handed
    to the Dataset as ``categorical_feature``."""
    t0 = time.time()
    ds = lgb.Dataset(X, y, group=group, params=dict(params),
                     categorical_feature=list(params["categorical_feature"]))
    ds.construct()
    spans["binning_s"] = time.time() - t0
    spans["train_entry"] = time.time()
    return lgb.train(dict(params), ds, rounds, verbose_eval=False,
                     callbacks=callbacks)


def configure(cfg):
    """Tell the reference the configuration's regularisation and which
    category columns split one-hot (at most ``max_cat_to_onehot`` levels,
    v2.3.2's rule, by the generator's level counts)."""
    p = cfg["params"]
    gen = importlib.import_module("generators." + cfg["generator"])
    onehot = [f for f, card in zip(p["categorical_feature"], gen.CARDS)
              if card <= int(p["max_cat_to_onehot"])]
    reference_cat.configure(p.get("lambda_l2", 0.0), p["cat_l2"], onehot)


@contextlib.contextmanager
def cat_inputs(cfg):
    """``train.run`` (and the tools that call it) on categorical rows."""
    configure(cfg)
    real = train.inputs, train.train_call, train.reference
    train.inputs, train.train_call, train.reference = (inputs, train_call,
                                                       reference_cat)
    try:
        yield
    finally:
        train.inputs, train.train_call, train.reference = real


def run(cell, cfg, traffic, args, device, peak, t_start):
    with cat_inputs(cfg):
        out = train.run(cell, cfg, traffic, args, device, peak, t_start)
    work = out["ctx"].get("work")
    if work:
        # grow_roofline's part: histograms and partition together, held
        # against root_hist + seg_hist + split_pass, as train_sparse.py has it
        work["grow"] = tuple(a + b for a, b in zip(work["hist"],
                                                   work["partition"]))
    trees = out["trees"]
    cat = sum(int(np.count_nonzero(reference_cat.is_cat_node(t)))
              for t in trees)
    train.say("categorical splits of the first %d trees: %d of %d"
              % (len(trees), cat, sum(t["num_leaves"] - 1 for t in trees)))
    return out
