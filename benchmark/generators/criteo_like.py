"""Criteo-shaped synthetic rows (the reference's Parallel Experiment: the
Criteo click logs as 67 dense numeric columns, binary label), made on the
device in fixed-size blocks.

The source's rows are the 13 integer fields of a display-advertising log,
and its 26 categorical fields each replaced by two numbers taken over the
first ten days: the click-through rate of the row's level and how often
that level was seen. Here:

* columns 0..12, the integer fields: heavy-tailed counts, a log-normal
  of a latent shared by all of them and one of the column's own, rounded
  down to an integer; 0 where the log has no value (the source's fields
  have missing values; this generator writes 0 and no NaN) and where the
  count rounds to it: between 10% and 50% zeros a column;
* columns 13..38, the rate columns: each of 26 categorical fields draws a
  level under a Zipf law over the field's own number of levels (10 to
  10,000,000); the level's click rate is the base rate moved by an effect
  that is a fixed function of (field, level), observed over the level's
  count, so rare levels read 0, 1/count, 2/count, ... and frequent ones
  sit close to the base rate, all in [0, 1];
* columns 39..64, the count columns: that level's count over the ten
  days, a heavy-tailed integer (the Zipf law again);
* columns 65 and 66, two further numeric columns to make the 67 the
  source states (it lists 13 + 2 x 26 = 65): the hour of the day, 0..23
  on a diurnal curve, and a positive log-normal (seconds since the user's
  last event).

The label is a fixed logistic of the rate columns' log-odds against the
base rate, the logs of some counts and integer fields, three products and
logistic noise, with an offset set so that about 3% of the rows are
clicks, the logs' own rate. The tables (levels a field, effects a level,
the label's weights) are the population's, from fixed constants, the same
under every seed: a seed draws the rows (as ``higgs_like``, ``expo_like``
and ``epsilon_like``). They are made up, not fitted to Criteo.

One jitted function of (key, block index): f32 from the start, and any
block can be made again later, bit for bit, by calling the same compiled
function, which is how the reference gets the rows without the program's
copy.
"""
import sys

import jax
import jax.numpy as jnp
import numpy as np


def _refuse_a_program_that_miscounts_past_2_24():
    """The cell holds 40,000,000 rows. A program from before PR 38 cannot
    run that to a result that means anything: past 2^24 rows it keeps the
    leaves' row counts in an emulated float64 state that comes out a few
    rows wrong in every tree, and it takes the root's sums from a running
    float32 total that drifts on the first tree, where every hessian is
    the same number, far enough to write a wrong or an empty first leaf
    (PERF.md section 7 row 0b). It would spend ten minutes of four chips
    to say so. Refuse at once, with run.py's own exit code for a cell it
    cannot run."""
    from lightgbm_tpu.ops import grow_persist
    if not hasattr(grow_persist, "LI_CNT"):
        sys.stderr.write(
            "benchmark: configuration criteo needs a program whose persist "
            "path keeps row counts past 2^24 exact and reads the root's "
            "sums off the root histogram (lightgbm_tpu/ops/grow_persist.py: "
            "LI_CNT, root_totals); this one cannot run it\n")
        raise SystemExit(2)


_refuse_a_program_that_miscounts_past_2_24()

FEATURES = 67
GROUP = 1            # rows that must stay together in a block
INTS = 13            # integer fields
CATS = 26            # categorical fields, a rate and a count column each
BASE_RATE = 0.03     # the logs' click rate
DAYS_ROWS = 7.0e8    # rows of the ten days the rates and counts are over

# per integer field: (mean, spread) of the log of the count, the share of
# the spread that is the shared latent, and the share of rows without a value
_j = np.arange(INTS)
INT_MU = (0.9 + 0.35 * (_j % 7)).astype(np.float32)           # 0.9 .. 3.0
INT_SIGMA = (1.0 + 0.12 * (_j % 5)).astype(np.float32)        # 1.0 .. 1.48
INT_SHARED = (0.3 + 0.05 * (_j % 4)).astype(np.float32)
INT_MISSING = (0.12 + 0.04 * (_j % 6)).astype(np.float32)     # 0.12 .. 0.32

# per categorical field: how many levels, and how far a level's click rate
# strays from the base rate (in log-odds)
_c = np.arange(CATS)
CAT_LEVELS = np.asarray(
    [10.0 ** (1.0 + 6.0 * ((7 * c) % CATS) / (CATS - 1)) for c in _c],
    np.float32)                                               # 10 .. 1e7
CAT_EFFECT = (0.25 + 0.05 * (_c % 6)).astype(np.float32)      # 0.25 .. 0.5

# the label: weights on the rate columns' log-odds, on log counts, on the
# log integer fields; the offset puts the click rate at about 3%
W_RATE = (0.55 * (0.4 + 0.6 * ((3 * _c) % 5) / 4.0)).astype(np.float32)
W_COUNT = (0.05 * np.where(_c % 3 == 0, 1.0, -0.5)).astype(np.float32)
W_INT = (0.12 * np.where(_j % 2 == 0, 1.0, -0.7)).astype(np.float32)
OFFSET = -3.27
NOISE = 0.6          # scale of the label's logistic noise


def _hash_unit(field, level, salt):
    """A fixed uniform in [0, 1) of (field, level): a murmur3-style integer
    finalizer, so that a level's click rate is a function of the level and
    not of the row or the seed."""
    x = (level.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         + field.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
         + jnp.uint32(salt))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return (x >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def make_block(key, index, rows):
    """(X [rows, 67] f32, y [rows] f32) of block ``index``."""
    ks, ki, km, kl, kh, kd, kn = jax.random.split(
        jax.random.fold_in(key, index), 7)
    logit_base = jnp.log(BASE_RATE / (1.0 - BASE_RATE))

    # ---- the 13 integer fields ------------------------------------------
    shared = jax.random.normal(ks, (rows, 1), jnp.float32)
    own = jax.random.normal(ki, (rows, INTS), jnp.float32)
    rho = jnp.asarray(INT_SHARED)
    z = rho * shared + jnp.sqrt(1.0 - rho * rho) * own
    ints = jnp.floor(jnp.exp(jnp.asarray(INT_MU) + jnp.asarray(INT_SIGMA) * z))
    ints = jnp.minimum(ints, 65535.0)
    has = jax.random.uniform(km, (rows, INTS)) >= jnp.asarray(INT_MISSING)
    ints = jnp.where(has, ints, 0.0)

    # ---- the 26 categorical fields: a level, its count, its rate --------
    levels = jnp.asarray(CAT_LEVELS)
    u = jax.random.uniform(kl, (rows, CATS), jnp.float32)
    # Zipf(1) over L levels by its continuous inverse: rank = L^u
    rank = jnp.minimum(jnp.floor(jnp.exp(u * jnp.log(levels))), levels)
    harmonic = jnp.log(levels) + 0.5772
    count = jnp.maximum(jnp.floor(DAYS_ROWS / (rank * harmonic)), 1.0)
    field = jnp.arange(CATS, dtype=jnp.uint32)[None, :]
    effect = jnp.asarray(CAT_EFFECT) * jnp.sqrt(3.0) * (
        2.0 * _hash_unit(field, rank, 0x2014) - 1.0)
    p_level = jax.nn.sigmoid(logit_base + effect)
    # the rate as observed over the level's count: binomial noise of that
    # many trials (normal approximation), a fixed function of the level
    # like the effect, so that every row of a level reads the same rate;
    # rounded to whole clicks
    seen = jax.scipy.special.ndtri(jnp.clip(
        _hash_unit(field, rank, 0x10DA), 1e-6, 1.0 - 1e-6))
    clicks = jnp.round(count * p_level
                       + jnp.sqrt(count * p_level * (1.0 - p_level)) * seen)
    rate = jnp.clip(clicks, 0.0, count) / count

    # ---- two further numeric columns -------------------------------------
    uh = jax.random.uniform(kh, (rows,), jnp.float32)
    # a diurnal curve: the inverse of t + 0.35 sin(2 pi t) / (2 pi) to
    # first order is enough for an uneven spread over the 24 hours
    hour = jnp.floor(24.0 * jnp.mod(
        uh + 0.35 / (2.0 * jnp.pi) * jnp.sin(2.0 * jnp.pi * uh), 1.0))
    since = jnp.exp(4.0 + 1.6 * jax.random.normal(kd, (rows,), jnp.float32)
                    - 0.5 * shared[:, 0])

    # ---- the label --------------------------------------------------------
    eps = 0.5 / count
    odds = jnp.log((rate + eps) / (1.0 - rate + eps)) - logit_base
    lcount = jnp.log(count) - 10.0
    lint = jnp.log1p(ints)
    logit = (OFFSET
             + jnp.sum(jnp.asarray(W_RATE) * odds, axis=1)
             + jnp.sum(jnp.asarray(W_COUNT) * lcount, axis=1)
             + jnp.sum(jnp.asarray(W_INT) * (lint - 1.5), axis=1)
             + 0.25 * odds[:, 0] * odds[:, 1]
             - 0.10 * lint[:, 0] * lint[:, 2]
             + 0.08 * lcount[:, 3] * (hour > 17.0)
             - 0.15 * jnp.log(since / 55.0) * (lint[:, 1] > 1.0))
    noise = jax.random.logistic(kn, (rows,), jnp.float32) * NOISE
    x = jnp.concatenate([ints, rate, count, hour[:, None], since[:, None]],
                        axis=1)
    return x, (logit + noise > 0.0).astype(jnp.float32)
