"""The whole in-place writeback of split_pass / level_pass (ops/pallas_grow.
_make_segment_step) at a small chunk size, against a numpy model of the
same chunk schedule: every chunk partitioned stably, left blocks appended
bottom-up and right blocks top-down in the order the FIFO reads them. The
payload is compared bit for bit over ALL rows and ALL lanes, so the
neighbouring leaves' rows in the segment's two edge tiles, the lanes past
the segment and the payload rows past the last live sublane tile are held
too.

Where the pass builds the smaller child's histogram (9 live rows: the
Expo cell's kernel) it builds it from the rows the partition has just
compacted into the FIFO slot (pallas_grow._slot_hist), in blocks of
min(HIST_BLOCK, C) lanes. The planes are compared with an f64 model for
either smaller side, with byte slots and with a 4-bit slot, at a chunk
size whose last block is pulled back to the slot's end (C = 384), and for
a smaller side of a chunk that is empty, under one tile, ends on a tile
edge (start 256, 128 rows, all kept), runs past a block (a whole chunk
behind a sub-tile offset) and is the whole chunk; start 133 leaves a
neighbouring leaf's rows in the first tile below the block's offset, which
must not be counted.

Every case runs twice. The ordinary Pallas interpreter copies at a DMA's
start(); under ``InterpretParams(dma_execution_mode="on_wait")`` a copy
happens at its wait(), so a slot refilled, or a carry tile changed, under a
drain still in flight gives a wrong payload here on the CPU (moving the
wait behind the partition fails 3 cases in 5 of a spot check).
"""
import functools

import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.ops import pallas_grow as pg
from lightgbm_tpu.ops.pallas_compat import pltpu

C = 256                      # 2 lane tiles a chunk, E = 3: quick to interpret
NP = 2048
# live payload rows: Expo (nibble slot, histogram in the pass), HIGGS,
# whole sublane tiles, MS-LTR widths
WP_LIVES = [9, 12, 16, 40]
PLANS = {"bytes": ((0, 0, 255), (0, 8, 255)),     # two byte groups in word 0
         "nibble": ((0, 0, 255), (0, 8, 255), (0, 16, 15))}   # + a 4-bit slot
# (live rows, S_SMALL_L, plan, chunk): the four heights as they were, then
# the histogram's own cases
VARIANTS = [(w, 1, "bytes", C) for w in WP_LIVES] + [
    (9, 0, "bytes", C), (9, 1, "nibble", 384), (9, 0, "nibble", 384)]
# segment start: on a tile boundary, and inside a tile
STARTS = [256, 133]
# rows: none; one; inside one tile (both edge tiles the same tile when the
# start is inside it); around one tile; around one chunk; 4 chunks, so the
# FIFO reads from both ends and refills both slot pairs
LENGTHS = [0, 1, 90, 127, 128, 129, C - 1, C, C + 1, 3 * C + 37]
# percent of rows that go left -> threshold on the low byte of word row 0
KEEPS = {0: -1, 3: 7, 50: 127, 97: 247, 100: 255}
MODES = ["copy_at_start", "copy_at_wait"]


def _variant_id(v):
    return "live%d" % v[0] + ("" if v[1:] == (1, "bytes", C) else
                              "-small%s-%s-C%d" % ("RL"[v[1]], v[2], v[3]))


def _interpret(mode):
    if mode == "copy_at_start":
        return True
    if not hasattr(pltpu, "InterpretParams"):
        pytest.skip("this JAX has no TPU interpret mode")
    return pltpu.InterpretParams(dma_execution_mode="on_wait",
                                 detect_races=True)


def _no_race_reported():
    try:
        from jax._src.pallas.mosaic.interpret import (
            interpret_pallas_call as ipc)
    except ImportError:
        return True
    return ipc.races is None or not ipc.races.races_found


def _geometry(wp_live):
    R = -(-wp_live // 8) * 8
    # one sublane tile of payload rows past the live ones: never touched
    return R + 8, R, wp_live - 5


@functools.lru_cache(maxsize=None)
def _split_pass(wp_live, plan, chunk, mode):
    WPA, _, nbw = _geometry(wp_live)
    # the histogram rides the pass where the cell's does (Expo)
    return pg.make_split_pass(WPA, NP, len(PLANS[plan]), PLANS[plan], nbw,
                              C=chunk, interpret=_interpret(mode),
                              wp_live=wp_live, _skip_hist=wp_live != 9)


@functools.lru_cache(maxsize=None)
def _level_pass(wp_live, plan, mode):
    WPA, _, nbw = _geometry(wp_live)
    return pg.make_level_pass(WPA, NP, len(PLANS[plan]), PLANS[plan], nbw,
                              4, 32, C=C, interpret=_interpret(mode),
                              wp_live=wp_live)


def _payload(wp_live, seed):
    WPA, _, nbw = _geometry(wp_live)
    rng = np.random.default_rng(seed)
    pay = rng.integers(0, 2 ** 32, (WPA, NP), dtype=np.uint32)
    pay[nbw + 2:nbw + 4] = rng.normal(size=(2, NP)).astype(
        np.float32).view(np.uint32)
    return pay


def _scalars(s0, n, thr, small_l=1, chunk=C):
    v = np.zeros(16, np.int32)
    v[pg.S_NCH], v[pg.S_S0], v[pg.S_NL] = -(-n // chunk), s0, n
    v[pg.S_MASK], v[pg.S_NB], v[pg.S_LE] = 255, 256, 256
    v[pg.S_THR], v[pg.S_SMALL_L] = thr, small_l
    return v


def _model(pay, R, nbw, s0, n, thr, small_l=1, plan="bytes", C=C):
    """(payload, left rows, [2, G * 256] histogram of the child S_SMALL_L
    names, in f64) after one segment's pass."""
    out = pay.copy()
    seg = pay[:R, s0:s0 + n]
    gl = (seg[0] & 255).astype(np.int64) <= thr
    vl, vr, fr, br = 0, n, 0, n
    while fr < br:
        m = min(C, br - fr)
        if fr - vl <= vr - br:          # the end with the smaller gap
            idx = np.arange(fr, fr + m)
            fr += m
        else:
            idx = np.arange(br - m, br)
            br -= m
        left, right = seg[:, idx[gl[idx]]], seg[:, idx[~gl[idx]]]
        out[:R, s0 + vl:s0 + vl + left.shape[1]] = left
        vl += left.shape[1]
        out[:R, s0 + vr - right.shape[1]:s0 + vr] = right
        vr -= right.shape[1]
    hist = np.zeros((2, len(PLANS[plan]) * 256))
    small = gl if small_l else ~gl
    gh = seg[nbw + 2:nbw + 4, small].view(np.float32).astype(np.float64)
    for g, (w, sh, mk) in enumerate(PLANS[plan]):
        b = ((seg[w, small] >> sh) & mk).astype(np.int64) + g * 256
        np.add.at(hist[0], b, gh[0])
        np.add.at(hist[1], b, gh[1])
    return out, int(gl.sum()), hist


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("keep", sorted(KEEPS), ids=lambda k: "keep%d" % k)
@pytest.mark.parametrize("n", LENGTHS, ids=lambda n: "rows%d" % n)
@pytest.mark.parametrize("s0", STARTS, ids=lambda s: "start%d" % s)
@pytest.mark.parametrize("variant", VARIANTS, ids=_variant_id)
def test_split_pass_writeback(variant, s0, n, keep, mode):
    wp_live, small_l, plan, chunk = variant
    _, R, nbw = _geometry(wp_live)
    pay = _payload(wp_live, 1000 * wp_live + n)
    pay2, (gh, hh), n_left = _split_pass(wp_live, plan, chunk, mode)(
        jnp.asarray(pay),
        jnp.asarray(_scalars(s0, n, KEEPS[keep], small_l, chunk)[:15]))
    want, want_left, want_hist = _model(pay, R, nbw, s0, n, KEEPS[keep],
                                        small_l, plan, chunk)
    assert int(n_left) == want_left
    np.testing.assert_array_equal(np.asarray(pay2), want)
    if wp_live == 9:
        np.testing.assert_allclose(np.stack([gh, hh]), want_hist,
                                   rtol=1e-4, atol=1e-4)
    assert _no_race_reported()


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("keeps", [(50, 3), (97, 50), (0, 100)],
                         ids=lambda k: "keep%d_%d" % k)
@pytest.mark.parametrize("lengths", [(C + 50, 2 * C + 9), (70, 3 * C + 1)],
                         ids=lambda n: "rows%d_%d" % n)
@pytest.mark.parametrize("variant", [
    (9, (1, 1), "bytes"), (12, (1, 1), "bytes"), (9, (0, 0), "bytes"),
    (9, (0, 1), "nibble"), (9, (1, 0), "nibble")],
    ids=lambda v: "live%d" % v[0] + ("" if v[1:] == ((1, 1), "bytes") else
                                     "-small%s%s-%s" % ("RL"[v[1][0]],
                                                        "RL"[v[1][1]], v[2])))
def test_level_pass_two_adjacent_segments(variant, lengths, keeps, mode):
    """The second segment starts in the tile the first one ends in: its
    edge read must see the first one's closed tile."""
    wp_live, smalls, plan = variant
    _, R, nbw = _geometry(wp_live)
    pay = _payload(wp_live, 77 * wp_live + lengths[0])
    starts = (133, 133 + lengths[0])
    scal = np.zeros((4, 16), np.int32)
    for j in range(2):
        scal[j] = _scalars(starts[j], lengths[j], KEEPS[keeps[j]],
                           smalls[j])
    steps = np.where(scal[:, pg.S_NL] > 0, scal[:, pg.S_NCH] + 2, 0)
    ends = np.cumsum(steps)
    slot_of_step = np.minimum(
        np.searchsorted(ends, np.arange(32), side="right"), 3)
    pay2, hist, n_left = _level_pass(wp_live, plan, mode)(
        jnp.asarray(pay), jnp.asarray(scal),
        jnp.asarray(slot_of_step.astype(np.int32)),
        jnp.asarray((ends - steps).astype(np.int32)), jnp.int32(ends[-1]))
    want = pay
    for j in range(2):
        want, want_left, want_hist = _model(
            want, R, nbw, starts[j], lengths[j], KEEPS[keeps[j]],
            smalls[j], plan)
        assert int(n_left[j]) == want_left
        np.testing.assert_allclose(
            np.stack(pg._unpack_hist(hist[j])), want_hist,
            rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(pay2), want)
    assert _no_race_reported()
