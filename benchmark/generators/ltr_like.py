"""MSLR-WEB30K-shaped synthetic ranking rows, made on the device in blocks.

A copy of ``lightgbm_tpu/data/synth.py:make_ltr_like`` (137 dense features,
fixed 73-document queries, grades 0-4 cut per query at the 55/75/90/97%
quantiles of a sparse linear + nonlinear signal) as one jitted function of
(key, block index). A block holds whole queries. The 20 signal weights come
from the key alone, so every block shares them.
"""
import jax
import jax.numpy as jnp

FEATURES = 137
GROUP = 73           # documents a query; a block is a whole number of them


def make_block(key, index, rows):
    """(X [rows, 137] f32, grade [rows] f32) of block ``index``."""
    w = jax.random.normal(jax.random.fold_in(key, 0x7fffffff), (20,),
                          jnp.float32)
    kx, kn = jax.random.split(jax.random.fold_in(key, index))
    x = jax.random.normal(kx, (rows, FEATURES), jnp.float32)
    sig = (jnp.sum(x[:, :20] * w, axis=1)
           + 0.7 * jnp.tanh(x[:, 20] * x[:, 21])
           + jax.random.logistic(kn, (rows,), jnp.float32) * 1.2)
    sig = sig.reshape(rows // GROUP, GROUP)
    cuts = jnp.quantile(sig, jnp.asarray([0.55, 0.75, 0.90, 0.97]), axis=1)
    grade = sum((sig > cuts[k][:, None]).astype(jnp.float32)
                for k in range(4))
    return x, grade.reshape(-1)
