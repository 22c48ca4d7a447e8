"""The in-VMEM partition of one split_pass chunk (ops/pallas_grow.
_partition_chunk), alone, in the Pallas interpreter: for any mask the left
rows must land packed from lane base_l of the left slot and the right rows
from lane base_r of the right slot, each side in row order, every word bit
for bit — the numpy stable partition. Trees are bit-identical only because
this permutation is."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops import pallas_grow as pg
from lightgbm_tpu.ops.pallas_compat import pl, pltpu

I32, U32 = jnp.int32, jnp.uint32

# (live payload rows, lane tiles of the chunk buffer): 12 rows leave spare
# sublanes in their second tile (HIGGS), 16 and 40 none (MS-LTR is 40);
# 3, 9 and 18 tiles are not powers of two; 18 runs the grouped loop and
# its tail (16 + 1 tiles of rows), the others the tail alone
GEOMETRIES = [(12, 3), (16, 9), (40, 3), (12, 18)]
# (base_l, base_r): where each side's block starts in its slot — lane 0,
# and the sub-tile offsets a drain at an unaligned payload lane asks for
BASES = [(0, 0), (37, 101)]


def _masks(E, rng):
    C = E - 128                                   # a full chunk's rows
    rnd = lambda p: rng.random(E) < p             # noqa: E731
    one = lambda i: np.arange(E) == i             # noqa: E731
    return {
        "all_keep": (C, np.ones(E, bool)),
        "none_keep": (C, np.zeros(E, bool)),
        "alternating": (C, np.arange(E) % 2 == 0),
        "random_03": (C, rnd(0.03)),
        "random_50": (C, rnd(0.50)),
        "random_97": (C, rnd(0.97)),
        "single_lane_0": (C, one(0)),
        "single_lane_last": (C, one(C - 1)),
        "short_chunk_tail": (C // 2 + 37, rnd(0.5)),
    }


MASKS = sorted(_masks(3 * 128, np.random.default_rng(0)))


@functools.lru_cache(maxsize=None)
def _call(R, T):
    E = T * 128
    TP = -(-T // 8) * 8

    def kernel(ms, w_ref, keep_ref, out_l, out_r, ctl, cnt):
        m = ms[0]
        lane = jax.lax.broadcasted_iota(I32, (1, E), 1)[0]
        gl = (lane < m) & (keep_ref[0, :] > 0)
        out_l[...] = jnp.zeros_like(out_l)
        out_r[...] = jnp.zeros_like(out_r)
        pg._partition_chunk(w_ref, R, gl, m, ms[1], ms[2], out_l, out_r,
                            ctl, cnt)

    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return jax.jit(lambda m, w, keep: pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[vmem, vmem], out_specs=[vmem, vmem],
            scratch_shapes=[pltpu.VMEM((TP, 128), I32),
                            pltpu.VMEM((TP, 128), I32)]),
        out_shape=[jax.ShapeDtypeStruct((R, E + 128), U32)] * 2,
        interpret=True)(m, w, keep))


@functools.lru_cache(maxsize=None)
def _partitioned(R, T, mask, bases):
    E = T * 128
    rng = np.random.default_rng(1000 * R + T)
    m, keep = _masks(E, rng)[mask]
    w = rng.integers(0, 2 ** 32, (R, E), dtype=np.uint32)
    out_l, out_r = _call(R, T)(jnp.array((m,) + bases, I32), jnp.asarray(w),
                               jnp.asarray(keep[None, :].astype(np.int32)))
    return m, keep, w, np.asarray(out_l), np.asarray(out_r)


@pytest.mark.parametrize("side", ["left", "right"])
@pytest.mark.parametrize("bases", BASES, ids=lambda b: "bases%d_%d" % b)
@pytest.mark.parametrize("mask", MASKS)
@pytest.mark.parametrize("geometry", GEOMETRIES,
                         ids=lambda g: "rows%d_tiles%d" % g)
def test_chunk_partition_is_the_stable_partition(geometry, mask, bases,
                                                 side):
    R, T = geometry
    E = T * 128
    m, keep, w, out_l, out_r = _partitioned(R, T, mask, bases)
    valid = np.arange(E) < m
    goes = valid & (keep if side == "left" else ~keep)
    out, base = (out_l, bases[0]) if side == "left" else (out_r, bases[1])
    np.testing.assert_array_equal(out[:, base:base + goes.sum()], w[:, goes])
