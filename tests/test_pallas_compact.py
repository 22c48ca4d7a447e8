"""The in-VMEM partition of one split_pass chunk (ops/pallas_grow.
_partition_chunk), alone, in the Pallas interpreter: for any mask the left
rows must land packed from lane base_l of the left slot and the right rows
from lane base_r of the right slot, each side in row order, every word bit
for bit — the numpy stable partition. Trees are bit-identical only because
this permutation is. The tile a side has open is carried from chunk to
chunk: the left block goes on in the tile handed in and hands on the one
it leaves open, the right block's last tile ends in the tile handed in
and its first tile is handed on (tests/test_split_drain.py runs the whole
writeback)."""
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

    def kernel(ms, w_ref, keep_ref, open_ref, out_l, out_r, carry, ctl,
               cnt):
        m = ms[0]
        lane = jax.lax.broadcasted_iota(I32, (1, E), 1)[0]
        gl = (lane < m) & (keep_ref[0, :] > 0)
        out_l[...] = jnp.zeros_like(out_l)
        out_r[...] = jnp.zeros_like(out_r)
        carry[...] = open_ref[...]
        pg._partition_chunk(w_ref, R, gl, m, ms[1], ms[2], out_l, out_r,
                            carry, ctl, cnt)

    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return jax.jit(lambda m, w, keep, tiles: pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[vmem, vmem, vmem], out_specs=[vmem, vmem, vmem],
            scratch_shapes=[pltpu.VMEM((TP, 128), I32),
                            pltpu.VMEM((TP, 128), I32)]),
        out_shape=[jax.ShapeDtypeStruct((R, E + 128), U32)] * 2
        + [jax.ShapeDtypeStruct((2, R, 128), U32)],
        interpret=True)(m, w, keep, tiles))


@functools.lru_cache(maxsize=None)
def _partitioned(R, T, mask, bases):
    E = T * 128
    rng = np.random.default_rng(1000 * R + T)
    m, keep = _masks(E, rng)[mask]
    w = rng.integers(0, 2 ** 32, (R, E), dtype=np.uint32)
    tiles = rng.integers(0, 2 ** 32, (2, R, 128), dtype=np.uint32)
    out_l, out_r, carry = map(np.asarray, _call(R, T)(
        jnp.array((m,) + bases, I32), jnp.asarray(w),
        jnp.asarray(keep[None, :].astype(np.int32)), jnp.asarray(tiles)))
    return m, keep, w, tiles, out_l, out_r, carry


@pytest.mark.parametrize("side", ["left", "right"])
@pytest.mark.parametrize("bases", BASES, ids=lambda b: "bases%d_%d" % b)
@pytest.mark.parametrize("mask", MASKS)
@pytest.mark.parametrize("geometry", GEOMETRIES,
                         ids=lambda g: "rows%d_tiles%d" % g)
def test_chunk_partition_is_the_stable_partition(geometry, mask, bases,
                                                 side):
    R, T = geometry
    E = T * 128
    m, keep, w, tiles, out_l, out_r, carry = _partitioned(R, T, mask, bases)
    valid = np.arange(E) < m
    goes = valid & (keep if side == "left" else ~keep)
    n = int(goes.sum())
    if side == "left":
        base, end = bases[0], bases[0] + n
        q = end // 128 * 128
        # the slot's closed tiles, then the tile handed on
        out = np.concatenate([out_l[:, :q], carry[0]], axis=1)
        # the tile handed in goes on below the block
        np.testing.assert_array_equal(out[:, :base], tiles[0][:, :base])
    else:
        base, end = bases[1], bases[1] + n
        out = out_r
        # the block's last tile ends in the tile handed in
        np.testing.assert_array_equal(
            out[:, end:end // 128 * 128 + 128], tiles[1][:, end % 128:])
        # and its first tile is handed on
        np.testing.assert_array_equal(carry[1][:, base:],
                                      out[:, base:128])
    np.testing.assert_array_equal(out[:, base:end], w[:, goes])
