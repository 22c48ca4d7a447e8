"""Expo-shaped synthetic rows (the reference's one-hot coded airline on-time
experiment: 700 columns), made on the device in fixed-size blocks and never
dense on the way to the program.

Six categorical columns, one-hot coded as the source codes them (Month 12,
DayofMonth 31, DayOfWeek 7, UniqueCarrier 22, Origin 313, Dest 313 = 698
columns), then DepTime and Distance. Category popularity is Zipf(1) within
each block, level 0 the most frequent. The label is a logistic of per-level
effects of carrier, origin, destination, month and weekday, of one carrier x
origin interaction (the top carrier at its hub), and of the two numerics,
whose weight differs by month, carrier, origin and destination (the hour of
departure is the strongest predictor of a delay, and it tells more at some
airports than at others); about 20% positives, as the real
``dep_delayed_15min``. The tables are fixed: one population, from which a
seed draws its rows. They are made up, not fitted to the airline data, and
only the HUBS busiest levels of a block have an entry of their own: of the
forms of the label tried on the chip this is the one whose throughput and
AUC move least from seed to seed, and it was chosen for that (PERF.md
section 6 gives every form's readings). What it costs: trees here chase
levels of a few thousand rows less than they would on the real data.

``make_block`` returns the COMPACT block: six category ids and two numerics a
row. ``stored`` turns a compact block into the CSR entries of its rows on the
host (eight stored values a row), ``expand`` into the dense [rows, 700] f32
the plain reference walks, and ``overwritten`` marks the values a program that
bundles columns does not hold.
"""
import jax
import jax.numpy as jnp
import numpy as np

CARDS = (12, 31, 7, 22, 313, 313)   # Month DayofMonth DayOfWeek Carrier Origin Dest
OFFSETS = (0, 12, 43, 50, 72, 385)  # first column of each one-hot block
NUMERIC = (698, 699)                # DepTime (hours), Distance (miles)
FEATURES = 700
STORED = 8           # stored values a row: six ones and the two numerics
GROUP = 1            # rows that must stay together in a block
# the least bytes that hold one binned row, whatever bundles it: four one-hot
# blocks of <= 255 levels at one byte, two of 313 at two, two numerics at one
WORK_FEATURES = 10
HUBS = 30            # levels of a block that carry an effect of their own


def total_bins(max_bin):
    """Histogram bins of the source's columns: two a one-hot column, up to
    ``max_bin`` a numeric."""
    return 2 * sum(CARDS) + len(NUMERIC) * int(max_bin)


def zipf(card):
    """Level k with probability proportional to 1 / (k + 1)."""
    w = 1.0 / jnp.arange(1, card + 1, dtype=jnp.float32)
    return w / jnp.sum(w)


def effects():
    """The label's tables: the population's, the same under every seed (a
    seed draws the rows, as it does for HIGGS, whose label function is fixed
    too). ``level``: an additive effect per level of each block, mean 0 under
    the levels' popularity, so that the positive rate does not hang on what
    the popular levels drew. ``slope``: how much more or less the hour of
    departure (by month, carrier, origin) and the distance (by destination)
    tell at that level. Only the HUBS most popular levels of a block have
    entries of their own (30 airports hold 63% of the flights)."""
    ks = jax.random.split(jax.random.PRNGKey(2008), 10)
    scale = (0.25, 0.0, 0.2, 0.5, 0.45, 0.3)     # the day of the month: none

    def table(k, s, c):
        # an airport past the HUBS busiest has too few flights to tell
        v = s * jax.random.normal(k, (c,), jnp.float32)
        return jnp.where(jnp.arange(c) < HUBS, v, 0.0)
    level = [table(k, s, c) for k, s, c in zip(ks, scale, CARDS)]
    level = [v - jnp.sum(v * zipf(c)) for v, c in zip(level, CARDS)]
    slope = {j: table(ks[6 + i], 0.35, CARDS[j])
             for i, j in enumerate((0, 3, 4, 5))}
    return level, slope


def zipf_ids(key, card, rows):
    cdf = jnp.cumsum(zipf(card))
    u = jax.random.uniform(key, (rows,), jnp.float32)
    return jnp.sum(u[:, None] >= cdf[None, :-1], axis=1).astype(jnp.int32)


def make_block(key, index, rows):
    """(category ids [rows, 6] i32, numerics [rows, 2] f32, y [rows] f32) of
    block ``index``."""
    level, slope = effects()
    ks = jax.random.split(jax.random.fold_in(key, index), 9)
    ids = [zipf_ids(ks[j], CARDS[j], rows) for j in range(6)]
    dep = 5.0 + 9.5 * jnp.sum(                     # triangular on 5h..24h
        jax.random.uniform(ks[6], (rows, 2), jnp.float32), axis=1)
    dist = jnp.exp(6.3 + 0.7 * jax.random.normal(ks[7], (rows,), jnp.float32))
    # delays build up over the day, in waves; longer flights make up time
    by_hour = (0.11 * (dep - 14.0) + 0.4 * jnp.tanh((dep - 17.0) / 2.0)
               + 0.15 * jnp.sin(dep * (2.0 * jnp.pi / 5.0)))
    by_dist = (-0.25 * jnp.log(dist / 540.0)
               + 0.12 * jnp.sin(3.0 * jnp.log(dist)))
    hub = (ids[3] == 0) & (ids[4] == 0)     # the top carrier at its hub
    logit = (-1.31 + sum(level[j][ids[j]] for j in range(6)) + 0.6 * hub
             + by_hour * (1.0 + slope[0][ids[0]] + slope[3][ids[3]]
                          + slope[4][ids[4]])
             + by_dist * (1.0 + slope[5][ids[5]]))
    noise = jax.random.logistic(ks[8], (rows,), jnp.float32) * 0.8
    return (jnp.stack(ids, axis=1), jnp.stack([dep, dist], axis=1),
            (logit + noise > 0.0).astype(jnp.float32))


def expand(cat, num):
    """The dense [rows, 700] f32 block of a compact one (a category id of -1
    sets no column of its block)."""
    return jnp.concatenate(
        [jax.nn.one_hot(cat[:, j], CARDS[j], dtype=jnp.float32)
         for j in range(6)] + [num], axis=1)


def overwritten(cat, bundle, place):
    """[rows, 6] bool: the one-hot values a bundling program does not hold.
    ``bundle`` and ``place`` say, per column, which bundle stores it (a
    value of its own for a column stored alone) and where in the bundle's
    order: of two columns of one bundle set in a row, the later is kept."""
    cols = cat + jnp.asarray(OFFSETS, jnp.int32)
    b, at = bundle[cols], place[cols]
    return jnp.any((b[:, :, None] == b[:, None, :])
                   & (at[:, :, None] < at[:, None, :]), axis=2)


def stored(cat, num):
    """(column indices [rows, 8] i32, values [rows, 8] f32) of the CSR
    entries of a compact block (host arrays), columns ascending in a row."""
    cat, num = np.asarray(cat), np.asarray(num)
    cols = np.empty((len(cat), STORED), np.int32)
    cols[:, :6] = cat + np.asarray(OFFSETS, np.int32)
    cols[:, 6:] = NUMERIC
    vals = np.ones((len(cat), STORED), np.float32)
    vals[:, 6:] = num
    return cols, vals
