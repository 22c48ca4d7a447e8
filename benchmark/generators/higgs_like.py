"""HIGGS-shaped synthetic rows, made on the device in fixed-size blocks.

A copy of ``lightgbm_tpu/data/synth.py:make_higgs_like`` (continuous
kinematic-like features, two derived couplings, a nonlinear logistic label,
about 47% positives) rewritten as one jitted function of (key, block index):
f32 from the start, no whole-array intermediate, and any block can be made
again later, bit for bit, by calling the same compiled function — which is how
the reference gets the rows without the program's copy.
"""
import jax
import jax.numpy as jnp

FEATURES = 28
GROUP = 1            # rows that must stay together in a block


def make_block(key, index, rows):
    """(X [rows, 28] f32, y [rows] f32) of block ``index``."""
    kx, kn = jax.random.split(jax.random.fold_in(key, index))
    x = jax.random.normal(kx, (rows, FEATURES), jnp.float32)
    x21 = jnp.abs(x[:, 0] * x[:, 1]) + 0.3 * x[:, 21]
    x22 = x[:, 2] ** 2 + x[:, 3] ** 2 + 0.3 * x[:, 22]
    x = x.at[:, 21].set(x21).at[:, 22].set(x22)
    logit = (0.8 * x[:, 0] - 0.5 * x[:, 1] + 0.4 * x21 - 0.3 * x22
             + 0.5 * jnp.tanh(x[:, 4] * x[:, 5]))
    noise = jax.random.logistic(kn, (rows,), jnp.float32) * 0.8
    return x, (logit + noise > 0.0).astype(jnp.float32)
