"""Epsilon-shaped synthetic rows (the reference's dense GPU experiment: the
PASCAL Large Scale Learning Challenge 2008 set, 2,000 dense numeric
columns, balanced binary label), made on the device in fixed-size blocks.

The source's rows are dense, standardised a column and scaled to unit
length a row. Here a row is FACTORS latent normals mixed into all 2,000
columns by a fixed matrix, plus independent noise of the same variance in
every column, then scaled to unit length. The label is the sign of a fixed
logistic: a dense linear form of the (unscaled) columns, so that its signal
is spread over hundreds of columns and no one column says much, three
products of factors, and logistic noise; about 50% positives. The mixing
matrix and the label's weights are the population's, from a fixed key, the
same under every seed: a seed draws the rows (as ``higgs_like``, whose label
function is fixed, and ``expo_like``). They are made up, not fitted to
Epsilon.

One jitted function of (key, block index): f32 from the start, and any
block can be made again later, bit for bit, by calling the same compiled
function, which is how the reference gets the rows without the program's
copy.
"""
import sys

import jax
import jax.numpy as jnp


def _refuse_a_program_that_cannot_hold_the_row():
    """A program from before PR 36 sizes its kernels' chunks for narrow rows
    and unrolls its histogram kernels over the groups: handed these 2,000
    columns it asks the compiler for 2,000-fold unrolled kernels at chunks
    that cannot fit VMEM, and works on them for tens of minutes before it
    is refused, if it is (chip-less compile of the parent of PR 36's fused
    program at this geometry: no answer after 45 minutes, then stopped).
    Such a program cannot run this configuration; say so at once, with
    run.py's own exit code for a cell it cannot run."""
    from lightgbm_tpu.ops import pallas_grow
    if not hasattr(pallas_grow, "hist_loops_groups"):
        sys.stderr.write(
            "benchmark: configuration epsilon needs a program whose persist "
            "path sizes its chunks from the row's width and loops its "
            "histogram kernels over word rows (lightgbm_tpu/ops/pallas_grow."
            "py:hist_loops_groups); this one cannot run it\n")
        raise SystemExit(2)


_refuse_a_program_that_cannot_hold_the_row()

FEATURES = 2000
GROUP = 1            # rows that must stay together in a block
FACTORS = 32         # latent factors behind the columns
TABLES = 2008        # key of the population's tables (the challenge's year)
LINEAR = 1.6         # weight of the dense linear form in the logit
PRODUCTS = 0.6       # weight of the factor products
NOISE = 0.5          # scale of the label's logistic noise


def tables():
    """(mix [FACTORS, FEATURES], w [FEATURES]): the fixed mixing matrix,
    every column's factor part of unit variance, and the label's dense
    weights over the columns, scaled so that the linear form has unit
    variance."""
    km, kw = jax.random.split(jax.random.key(TABLES))
    mix = jax.random.normal(km, (FACTORS, FEATURES), jnp.float32)
    mix = mix / jnp.sqrt(jnp.sum(mix * mix, axis=0, keepdims=True))
    # a direction in factor space read through every column that loads on
    # it: dense over the columns, each a noisy proxy of the whole
    w = jax.random.normal(kw, (FACTORS,), jnp.float32) @ mix
    # var(x @ w) = |mix @ w|^2 (factors) + |w|^2 (noise)
    w = w / jnp.sqrt(jnp.sum((mix @ w) ** 2) + jnp.sum(w * w))
    return mix, w


def make_block(key, index, rows):
    """(X [rows, 2000] f32 of unit-length rows, y [rows] f32) of block
    ``index``."""
    mix, w = tables()
    kz, ke, kn = jax.random.split(jax.random.fold_in(key, index), 3)
    z = jax.random.normal(kz, (rows, FACTORS), jnp.float32)
    x = z @ mix + jax.random.normal(ke, (rows, FEATURES), jnp.float32)
    logit = (LINEAR * (x @ w)
             + PRODUCTS * (z[:, 0] * z[:, 1] + z[:, 2] * z[:, 3]
                           - z[:, 4] * z[:, 5]))
    noise = jax.random.logistic(kn, (rows,), jnp.float32) * NOISE
    x = x / jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True))
    return x, (logit + noise > 0.0).astype(jnp.float32)
