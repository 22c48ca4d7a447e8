"""Traffic driver ``train_sparse``: the ``train`` driver's job, window, path
check, trace reduction and comparison, on rows that reach the program as a
``scipy.sparse.csr_matrix`` and are never dense on the host.

The generator makes COMPACT blocks on the device (``make_block``), names the
CSR entries of a compact block (``stored``) and expands one to the dense block
the plain reference walks (``expand``); the dense block exists on the device
only, one block at a time, after the program's state is freed. Everything
else is ``drivers/train.py``, imported: its ``run`` is called with this
module's ``inputs``, ``learner_of`` and ``check`` in the place of its own (the
seams it lacks; PERF.md, Open questions).

What the comparison takes from the program, and what holds it. A bundle
stores one value a row, so the reference has to be told which of two columns
of one bundle set in a row the program kept: ``note_bundles`` takes the
bundles from the program's Dataset. Nothing else is taken on trust. The rows
are made again from the seed and the masking is done on them here; a table
that is not what the binned rows hold puts rows in other leaves than the
reference (``count_mismatch``, limit 0); and a bundling that gives up more
rows than the configuration states fails ``bundle_lost_share``, the share of
the rows that reached the reference with a value masked, counted here from
the raw blocks and held to a limit of the configuration like the rest.
"""
import contextlib
import importlib

import numpy as np

from drivers import train
from drivers.train import control, follow  # noqa: F401  (the tools')


class SparseRows:
    """``train.Rows`` for a generator of compact blocks: the same blocks of
    one shape from (seed key, block index), ``device_block`` dense for the
    reference, ``to_host`` CSR for the program. ``features`` is the width the
    work model counts a binned row at, not the number of columns.

    Exclusive feature bundling stores ONE value a bundle a row: where a row
    has two of a bundle's columns set (a conflict the program's sampled
    conflict budget let through), the later column of the bundle overwrites
    the earlier. That is the configuration's stated semantics, not a fault,
    so once ``note_bundles`` has been told which columns the program bundled,
    ``device_block`` hands the reference the rows as the bundles hold them:
    an overwritten one-hot value reads 0. Everything else the reference
    recomputes from the raw values."""

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
        self._expand = jax.jit(self.gen.expand)
        self._lost = jax.jit(self.gen.overwritten)
        self.bundle_of = None           # (bundle id, place in it) a column
        self._masked = {}               # block index -> its masked rows

    def note_bundles(self, dataset):
        """Take from the program's constructed Dataset which columns share a
        bundle and in which order (once per Dataset)."""
        import jax.numpy as jnp
        if self.bundle_of is not None and self.bundle_of[0] is dataset:
            return
        ident = -1 - np.arange(self.columns, dtype=np.int32)  # alone: unique
        place = np.zeros(self.columns, np.int32)
        for g, feats in enumerate(dataset.groups):
            for at, inner in enumerate(feats if len(feats) > 1 else ()):
                col = dataset.used_features[inner]
                mapper = dataset.bin_mappers[col]
                # the masking below takes "set" for "outside the most
                # frequent bin", which holds while zero is the most frequent
                assert mapper.default_bin == mapper.most_freq_bin, col
                ident[col], place[col] = g, at
        self.bundle_of = (dataset, jnp.asarray(ident), jnp.asarray(place))
        self._masked = {}

    @property
    def lost_rows(self):
        """Rows handed to the reference with a value masked: one pass's, over
        the blocks made since the bundles were noted."""
        return sum(int(c) for c in self._masked.values())

    def device_block(self, index):
        import jax.numpy as jnp
        cat, num, y = self._make(self.key, index, self.block)
        live = min(self.block, self.rows - index * self.block)
        valid = jnp.arange(self.block) < live
        if self.bundle_of is not None:
            lost = self._lost(cat, *self.bundle_of[1:])
            cat = jnp.where(lost, -1, cat)
            # no host sync here: the reference fetches one block behind
            self._masked[index] = jnp.sum(jnp.any(lost, axis=1) & valid)
        return self._expand(cat, num), y, valid

    def _csr(self, first, rows):
        """(CSR [rows, columns] f32, y [rows] f32) of the blocks from
        ``first`` on, the copy of a block overlapping the next one's making."""
        import scipy.sparse as sp
        k = self.gen.STORED
        cols = np.empty((rows, k), np.int32)
        vals = np.empty((rows, k), np.float32)
        y = np.empty((rows,), np.float32)
        pending = self._make(self.key, first, self.block)
        for lo in range(0, rows, self.block):
            cat, num, y_d = pending
            first += 1
            pending = self._make(self.key, first, self.block)
            hi = min(lo + self.block, rows)
            cols[lo:hi], vals[lo:hi] = (
                a[:hi - lo] for a in self.gen.stored(cat, num))
            y[lo:hi] = np.asarray(y_d)[:hi - lo]
        indptr = np.arange(rows + 1, dtype=np.int64) * k
        X = sp.csr_matrix((vals.reshape(-1), cols.reshape(-1), indptr),
                          shape=(rows, self.columns))
        return X, y

    def to_host(self):
        """(X, y) of the training rows and of the held-out rows, which are
        the blocks after the last training block."""
        X, y = self._csr(0, self.rows)
        Xh, yh = self._csr(self.blocks, self.heldout)
        return X, y, Xh, yh


def inputs(cfg, seed):
    """``train.inputs`` for compact generators: X and the held-out X are CSR."""
    rows = SparseRows(cfg, seed)
    X, y, Xh, yh = rows.to_host()
    train.say("sparse rows: %d columns, %d stored values a row (CSR %s, "
              "%.2f GB); the work model counts a binned row at %d bytes"
              % (rows.columns, rows.gen.STORED, X.dtype,
                 (X.data.nbytes + X.indices.nbytes + X.indptr.nbytes) / 1e9,
                 rows.features))
    return rows, X, y, Xh, yh, None


_check = train.check


def check(rows, trees, cfg, init):
    """``train.check``'s numbers, and ``bundle_lost_share``: the share of the
    rows that reached the reference with a value masked."""
    numbers, per_tree = _check(rows, trees, cfg, init)
    numbers["bundle_lost_share"] = rows.lost_rows / rows.rows
    return numbers, per_tree


@contextlib.contextmanager
def sparse_inputs():
    """``train.run`` (and the tools that call it) on CSR rows: its ``inputs``
    makes them, its ``learner_of`` tells them the program's bundles, and its
    ``check`` holds what the bundles lost to the configuration's limit."""
    newest = []                       # the rows of the run under way
    real = train.inputs, train.learner_of, train.check

    def inputs_(cfg, seed):
        made = inputs(cfg, seed)
        newest[:] = made[:1]
        return made

    def learner_of(bst):
        learner = real[1](bst)
        newest[0].note_bundles(learner.dataset)
        return learner
    train.inputs, train.learner_of, train.check = inputs_, learner_of, check
    try:
        yield
    finally:
        train.inputs, train.learner_of, train.check = real


def scan_work(trees, bins):
    """(operations, bytes) of the split scans of ``trees``: for every split
    both children's gradient and hessian planes over ``bins`` histogram bins
    read once as f32, and per bin and child two running sums and the gain
    arithmetic of a threshold (16 operations)."""
    splits = sum(t["num_leaves"] - 1 for t in trees)
    return (16 * 2 * bins * splits, 2 * 2 * 4 * bins * splits)


def run(cell, cfg, traffic, args, device, peak, t_start):
    with sparse_inputs():
        out = train.run(cell, cfg, traffic, args, device, peak, t_start)
    work = out["ctx"].get("work")
    if work:
        # the traced launch is launch `trace_launch`; its trees are among the
        # first `reference_trees` that `out` carries
        k = int(traffic["launch_iterations"])
        at = int(traffic["trace_launch"]) * k
        traced = out["trees"][at:at + k]
        bins = out["rows"].gen.total_bins(cfg["params"]["max_bin"])
        work["scan"] = scan_work(traced, bins)
        work["grow"] = tuple(a + b for a, b in zip(work["hist"],
                                                   work["partition"]))
        train.say("work model, scan and grow (ops, bytes): %s %s"
                  % (work["scan"], work["grow"]))
    train.say("rows with a one-hot value overwritten in its bundle: %d of %d"
              % (out["rows"].lost_rows, out["rows"].rows))
    return out
