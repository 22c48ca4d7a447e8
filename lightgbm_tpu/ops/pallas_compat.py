"""Single import point for the Pallas TPU API.

Lint rule JG006 keeps every other module off the raw
``jax.experimental.pallas`` import, so a move in that API is followed in
this one file. The Mosaic kernels trace under ``enable_x64(False)`` so
reference-parity f64 host math can stay on without weak-int promotion
leaking i64 into the kernels.
"""
from jax import enable_x64  # noqa: F401
from jax.experimental import pallas as pl  # noqa: F401
from jax.experimental.pallas import tpu as pltpu  # noqa: F401

CompilerParams = pltpu.CompilerParams
