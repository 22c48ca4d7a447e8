"""Fused Pallas TPU kernels for the persistent-payload tree grower.

TPU-native re-design of the reference's per-split hot loop — the
DataPartition::Split row shuffle (src/treelearner/data_partition.hpp:101),
the OrderedBin leaf-sorted histogram walk (include/LightGBM/bin.h:229) and
the ConstructHistograms inner loops (src/io/dense_bin.hpp:74-110) — as TWO
Mosaic kernels over a single transposed payload matrix:

  payload: u32 [WP, NP]   (rows on lanes; one matrix, one DMA per window)
     rows 0..nbw-1   bit-packed bin slots — byte per group, or 4-bit
                     nibble pairs for <=16-bin groups (the Dense4bitsBin
                     trade applied to the payload; grow_persist._payload_plan)
     row  nbw        label     (f32 bitcast; objective input)
     row  nbw+1      row id    (u32; positions -> original rows at the end)
     row  nbw+2      gradient  (f32 bitcast; rewritten every iteration)
     row  nbw+3      hessian   (f32 bitcast)
     row  nbw+4      score     (f32 bitcast; permutes WITH the rows, so the
                                boosting state follows the partition)
     optional tail rows (grow_persist.payload_weight_row is the index
     authority): u32-pair f64 scores in score64 mode, a per-class score +
     snapshot block for multiclass (K > 1), and a sample-weight row.
     The fused boosting iteration (PR 17) also multiplies per-tree
     RF bagging weights into the grad/hess rows between the gradient
     fill and the grow (traced [n] vectors gathered through the rid
     row, grow_persist.apply_row_weights) — so bagged iterations ride
     these SAME kernels with zero extra launches
     (tree_learner::iter_launches counts whole-driver dispatches,
     not trees).

  * split_pass (one call per split, DYNAMIC grid over chunks): streams the
    splitting leaf's contiguous payload segment once, and per chunk
      - decides go_left per row (DenseBin::Split semantics at the bin
        level, src/io/dense_bin.hpp:112-207; numerical features),
      - partitions the chunk tile by tile: one prefix sum gives both sides'
        control words, 7 hole-shift lane stages compact each 128-lane tile
        in registers, and a dynamic roll appends the kept lanes to the
        FIFO slot at the offset the drain will write them (word moves only,
        bit-exact, no sort, no scratch matmul),
      - unless ``_skip_hist``, accumulates the SMALLER child's histogram
        as radix-16 MXU contractions (the GPU histogram kernel analog,
        src/treelearner/ocl/histogram256.cl, re-derived for the MXU: a
        bin is hi * 16 + lo, the [16, E] one-hot of hi is contracted over
        the lanes with a [64, E] operand that holds the four bf16 value
        rows where the lo mask is on and 0 elsewhere, SELECTED under the
        mask and not multiplied by a one-hot, _hist_group) over the rows
        just compacted into that child's slot, block by block, so its
        cost follows the smaller child and not the chunk (_slot_hist).
        The persist grower skips it and runs seg_hist after the pass
        instead, which read 2-3% faster on the chip at 16.5M rows
        (PERF.md, PR 35),
      - partitions the payload IN PLACE: a two-ended writeback with a
        2-chunk FIFO. Chunks are read from whichever end has the smaller
        write-space gap and drained two steps later, so reads always lead
        writes on both ends (left blocks fill bottom-up, right blocks
        top-down) with no scratch buffer and no second pass — this replaces
        v1's scratch + copy-back design (ops/grow.py pass A + pass B).
    Chunk windows are DMAed at 128-aligned lane offsets and re-aligned in
    VMEM with one dynamic roll. The drain writes WHOLE 128-lane tiles,
    each once, and reads nothing back: a block lies in its slot at the
    sub-tile offset it is written at, the tile it shares with the block
    before it is carried in VMEM from one chunk's partition to the next,
    and the old payload is read in two tiles only, the segment's own ends
    (where a neighbouring leaf's rows live), once, before any write. The
    writes stay in flight under the next chunk's read and decision
    (_make_segment_step has the hazard argument).

  * root_hist (static grid): one streaming pass building the root histogram
    and the gradient/hessian totals.

Both kernels keep the histogram in the PADDED [G, 256] per-group layout
(group g's bins at flat offset g*256), so the flat [TB, 2] view used by the
split scan is a reshape — no gather, no scatter (v1's _hist_acc_finish
scatter and dense-scan gather cost ~80us per split).

Gated to the fast path: numerical features only, <= 256 bins per feature,
f32 accumulation; EFB-bundled groups decode in the split kernel via the
[LS, LE) group-local range scalars. Everything else falls back to
ops/grow.py. Equivalence is tested on CPU against the XLA kernel
emulation and the v1 growers (tests/test_persist_sharded.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .pallas_compat import CompilerParams, enable_x64, pl, pltpu

I32 = jnp.int32
U32 = jnp.uint32
F32 = jnp.float32

# scalar-prefetch slot indices for split_pass
S_NCH = 0         # number of payload chunks of the segment
S_S0 = 1          # segment start lane
S_NL = 2          # segment length (rows)
S_WG = 3          # payload word row of the split feature's storage byte
S_SH = 4          # shift of the feature's bits inside the word
S_MASK = 5        # value mask after shift (15 nibble / 255 byte)
S_NB = 6          # feature bin count
S_MT = 7          # missing type (0 none / 1 zero / 2 nan)
S_DB = 8          # default (zero) bin
S_THR = 9         # threshold (local bin)
S_DL = 10        # default_left flag
S_SMALL_L = 11    # smaller child is the left one
S_LS = 12         # feature's group-local byte range start (EFB bundles)
S_LE = 13         # range end; bytes outside [LS, LE) read as most_freq
S_MF = 14         # most_freq (feature-local) bin
N_SCALARS = 15


def _ceil8(x: int) -> int:
    """x rounded up to whole sublane tiles."""
    return -(-x // 8) * 8


# -- scoped-vmem requests ----------------------------------------------------
# One formula per kernel family, and the only place a kernel's footprint
# is written down: the helper is the `vmem_limit_bytes` the kernel runs
# with, and the proof that it suffices is the compiler's, in the
# described-topology compiles of tests/test_chip_compile.py (a request
# too small is refused there, without a chip; a geometry that test does
# not hold is unproven). The default 16MB scoped-VMEM limit forces
# small chunks whose cost is DMA latency and per-step scalar work (every
# chunk waits for its read); v5e cores carry 128MB of VMEM, so the limits
# are sized to each kernel's actual footprint (buffers + Mosaic
# temporaries scale with E) and C grows instead.

VMEM_CAP = 96 << 20


def split_pass_vmem_bytes(WPA: int, E: int, G: int, cap=VMEM_CAP) -> int:
    """split_pass / level_pass: 2 chunk-sized u32 buffers (the DMA's
    landing buffer and the re-aligned chunk's tile-addressable home), 4
    FIFO slots one lane tile wider, the two open tiles the drain carries
    from block to block, the radix hist accumulator, and ~3 buffers of
    Mosaic's own staging for the re-aligned chunk value and the dynamic
    rolls around it (the compiler's stack reads 44.1 MB at WPA 40,
    E 16512). The partition adds two [E / 128, 128] control planes and
    otherwise works tile by tile in registers; the drain holds nothing:
    it DMAs whole tiles from the slots and reads no payload back.

    ``cap=None`` gives the footprint unclipped: what
    grow_persist._payload_geometry sizes the chunk by, so that no kernel
    runs with a request the cap has cut."""
    need = (2 * WPA * E * 4 + 4 * WPA * (E + 128) * 4
            + 2 * WPA * 128 * 4
            + G * 16 * 64 * 4 + (20 << 20)
            + 3 * WPA * E * 4 + 2 * _ceil8(E // 128) * 128 * 4)
    return int(need if cap is None else min(cap, need))


def seg_hist_vmem_bytes(WPA: int, E: int, G: int, looped: bool = False,
                        cap=VMEM_CAP) -> int:
    """seg_hist / level_seg_hist / root_hist: one streaming chunk buffer
    (+1 working copy) + the radix hist accumulator + what the group loop
    holds at once. Unrolled, that is the [G, E] decoded group-bin planes
    `_hist_accum` is handed and one group's [64, E] operand, a float32
    value (_hist_group selects it in f32 and casts it once): at G=700,
    E=8320 (a 700-group unbundled shape) they are 25MB. ``looped``
    (hist_loops_groups) the kernels decode one sublane tile of word rows
    at a time (_hist_accum_words) and the [G, E] plane never exists, but
    the accumulator is counted as VMEM holds it: [16, 64] tiles padded to
    128 lanes, and twice, for the output window's two buffers (16.4 MB
    each at 2,000 groups). ``cap`` as in split_pass_vmem_bytes."""
    if looped:
        need = (2 * WPA * E * 4 + 2 * G * 16 * 128 * 4
                + 8 * E * 4 + 64 * E * 4 + (20 << 20))
    else:
        need = (2 * WPA * E * 4 + G * 16 * 64 * 4
                + G * E * 4 + 64 * E * 4 + (20 << 20))
    return int(need if cap is None else min(cap, need))


def grow_input_contract(NP: int, w: int = 256) -> dict:
    """Value-range contract for the persist/level kernel inputs (read
    by the analysis/dataflow seeder): payload words are packed u32
    (bins are group-local indices below ``w`` once unpacked), plan rows
    address payload columns in ``[-1, NP)`` (-1 = inactive slot), and
    every leaf/segment count is bounded by the padded payload width."""
    return {
        "payload": (0.0, float(2 ** 32 - 1)),
        "bins": (0.0, float(w - 1)),
        "plan_rows": (-1.0, float(NP)),
        "counts": (0.0, float(NP)),
    }


# the grow kernels reuse the histogram kernel's exact bf16 hi/lo trick
# for their in-payload radix contractions (_hist_values; _hist_group's one
# cast of the selected [64, E] operand) — same blessing
NARROW_OK = (("float32", "bfloat16"),)


def _lane_iota(E: int):
    return jax.lax.broadcasted_iota(I32, (1, E), 1)


def _tile_prefix_sum(g):
    """Inclusive prefix sum along the 128 lanes of every row of [T, 128]
    i32 (Kogge-Stone; a row is one lane tile, so 7 stages whatever T)."""
    lane = jax.lax.broadcasted_iota(I32, g.shape, 1)
    for b in range(7):
        sh = 1 << b
        g = g + jnp.where(lane >= sh, pltpu.roll(g, sh, 1), 0)
    return g


def _compact_tiles(xs, cs):
    """Hole-shift compaction inside 128-lane tiles, both sides at once.

    xs: tiles [R, 128] u32; cs: their control words [1, 128] i32 — bits
    0-6 hold how far a left row moves toward lane 0 (its holes before it
    in the tile), bits 8-14 the same for a right row, 0 = a hole or a row
    already home. Stage b moves every lane whose bit b is set by 2^b; the
    word is never decremented (clearing lower bits changes nothing above
    them) and travels with its row, a vacated lane becomes a hole.
    Low-to-high is collision-free: two kept lanes closer than 2^b have
    equal remaining shifts, so where one arrives the other has left; a
    row never leaves its tile, so the cyclic wrap of the roll carries no
    set bit. Word moves and selects only: bit-exact for any payload.
    Stage-major over the group so the rolls of different tiles overlap.
    Returns [(left-compacted, right-compacted)] per tile, both toward
    lane 0 in row order.
    """
    xl, xr, cs = list(xs), list(xs), list(cs)
    for b in range(7):
        sh = 1 << b
        for i, c in enumerate(cs):
            c_s = pltpu.roll(c, 128 - sh, 1)
            arr_l = (c_s & sh) != 0
            arr_r = (c_s & (sh << 8)) != 0
            xl[i] = jnp.where(arr_l, pltpu.roll(xl[i], 128 - sh, 1), xl[i])
            xr[i] = jnp.where(arr_r, pltpu.roll(xr[i], 128 - sh, 1), xr[i])
            if b < 6:
                lo = jnp.where(arr_l, c_s,
                               jnp.where((c & sh) != 0, 0, c)) & 0xFF
                hi = jnp.where(arr_r, c_s,
                               jnp.where((c & (sh << 8)) != 0, 0, c)) & 0xFF00
                cs[i] = lo | hi
    return list(zip(xl, xr))


def _partition_chunk(src, R: int, gl, m, base_l, base_r, dst_l, dst_r,
                     carry, ctl, cnt):
    """Stable two-sided partition of one chunk into a FIFO slot pair.

    src: VMEM ref [>= R, E] u32, chunk rows at lanes [0, m), m <= E - 128
    (the last lane tile is the DMA's alignment slack and holds no row);
    gl: [E] bool, the valid lanes that go left (the other valid lanes go
    right). dst_l / dst_r: VMEM refs [>= R, E + 128]. The caller passes as
    bases the sub-tile lane offsets the drain will write the blocks at, so
    a slot's tiles ARE payload tiles and the drain copies them whole:
    slot lane 0 stands for the payload lane tile that holds the block's
    first row. ctl / cnt: VMEM scratch [>= E / 128, 128] i32.

    carry: VMEM ref [2, >= R, 128] u32, the tile each side has open.
    carry[0] holds, in its lanes below base_l, what lies before the left
    block in its first tile: the left rows of the blocks before it, and
    below those the neighbouring leaf's rows. carry[1] holds, from lane
    (base_r + nR) & 127 on, what lies behind the right block in its last
    tile: the right blocks before it (they fill top-down) and the
    neighbour above. Afterwards
      - dst_l tiles [0, (base_l + nL) >> 7) are final payload tiles (the
        first one continued from carry[0]); the tile still open is NOT in
        the slot: it is the new carry[0], rows in lanes
        [0, (base_l + nL) & 127);
      - dst_r lanes [base_r, ...) hold the right rows in order and the
        block's last tile ends in the old carry[1]: tiles
        [1, (base_r + nR + 127) >> 7) are final, and tile 0 too where
        base_r is 0; tile 0, whose lanes below base_r the next right
        block fills, is the new carry[1].
    Every other lane is undefined.

    One prefix sum serves both sides: with P the inclusive count of left
    rows inside the tile, a left row at tile lane j has j + 1 - P holes
    before it and a valid right row has P. The chunk is then walked tile
    by tile: 7 lane stages compact the tile for each side
    (_compact_tiles), and the kept prefix is appended to its side's
    running output by one dynamic roll and a merge with the tile being
    filled, which is stored each time and carried in registers — no
    read-modify-write of the slot. A group of tiles goes through the
    stages together so that their rolls overlap: the XLU's round trip,
    not its throughput, bounds a single tile.
    """
    E = src.shape[1]
    T = E // 128
    # the select keeps the two reshapes apart: Mosaic has no [E] -> [T, 128]
    g = jnp.where(gl[None, :], 1, 0).astype(I32).reshape(T, 128)
    P = _tile_prefix_sum(g)
    j = jax.lax.broadcasted_iota(I32, (T, 128), 1)
    pos = jax.lax.broadcasted_iota(I32, (T, 128), 0) * 128 + j
    ctl[0:T, :] = jnp.where(g > 0, j + 1 - P,
                            jnp.where(pos < m, P << 8, 0))
    cnt[0:T, :] = jnp.broadcast_to(P[:, 127:128], (T, 128))

    lane = jax.lax.broadcasted_iota(I32, (R, 128), 1)

    def place(x, off, acc, n, dst):
        q = off >> 7
        d = off & 127
        rot = pltpu.roll(x, d, 1)
        merged = jnp.where(lane >= d, rot, acc)
        dst[0:R, pl.ds(pl.multiple_of(q * 128, 128), 128)] = merged
        # vector compare: a scalar-bool select does not lower (see dlv)
        full = (jnp.zeros_like(lane) + (d + n)) >= 128
        return off + n, jnp.where(full, rot, merged)

    def group(t0, k, state):
        """Tiles t0 .. t0 + k - 1 (t0 traced, k static)."""
        off_l, acc_l, off_r, acc_r = state
        tile = [pl.ds(pl.multiple_of((t0 + s) * 128, 128), 128)
                for s in range(k)]
        packed = _compact_tiles([src[0:R, tile[s]] for s in range(k)],
                                [ctl[pl.ds(t0 + s, 1), :] for s in range(k)])
        for s, (x_l, x_r) in enumerate(packed):
            n_l = cnt[pl.ds(t0 + s, 1), :][0, 0]
            n_r = jnp.clip(m - (t0 + s) * 128, 0, 128) - n_l
            off_l, acc_l = place(x_l, off_l, acc_l, n_l, dst_l)
            off_r, acc_r = place(x_r, off_r, acc_r, n_r, dst_r)
        return off_l, acc_l, off_r, acc_r

    # chip readings (PERF.md, PR 29): 8 tiles a group leave the loop bound
    # by the roll's latency, 16 by its throughput, 32 gain nothing; wide
    # payloads spill at 16 and read the same at 8
    G = 16 if R <= 16 else 8
    tiles = T - 1
    # the left side goes on in the tile the block before left open; what
    # the right side's first tile holds below base_r is the next block's
    state = (base_l, carry[0, 0:R, :], base_r, jnp.zeros((R, 128), U32))
    if tiles // G:
        state = jax.lax.fori_loop(
            0, tiles // G, lambda gi, c: group(gi * G, G, c), state)
    if tiles % G:
        state = group(jnp.int32(tiles // G * G), tiles % G, state)
    off_l, acc_l, off_r, acc_r = state
    carry[0, 0:R, :] = acc_l
    dst_r[0:R, pl.ds(pl.multiple_of((off_r >> 7) * 128, 128), 128)] = (
        jnp.where(lane < (off_r & 127), acc_r, carry[1, 0:R, :]))
    carry[1, 0:R, :] = dst_r[0:R, 0:128]


def _unpack_group_bins(pay_block, plan):
    """[G, E] i32 group-local bins from the packed word rows of [WP, E].

    plan: static tuple of (word_row, shift, mask) per logical group —
    byte slots (mask 255) or 4-bit nibble slots (mask 15) as produced by
    grow_persist._payload_plan; the decode is slot-width agnostic, so the
    same kernels serve byte and nibble-packed payloads.
    """
    rows = []
    for (w, sh, mk) in plan:
        rows.append(((pay_block[w, :] >> U32(sh)) & U32(mk)).astype(I32))
    return jnp.stack(rows, axis=0)


def _hist_values(grad, hess):
    """The four value rows of the radix contraction, (grad_hi, hess_hi,
    grad_lo, hess_lo), as f32 rows that hold bf16-representable values:
    hi + lo exact to f32, and the one cast to bf16 that _hist_group makes
    of them loses nothing."""
    g_hi = grad.astype(jnp.bfloat16).astype(F32)
    h_hi = hess.astype(jnp.bfloat16).astype(F32)
    g_lo = (grad - g_hi).astype(jnp.bfloat16).astype(F32)
    h_lo = (hess - h_hi).astype(jnp.bfloat16).astype(F32)
    return (g_hi, h_hi, g_lo, h_lo)


def _hist_group(hist_ref, g, b, n16, vt):
    """hist_ref[g] += one group's contraction; b: [E] i32 group-local
    bins, g static or traced, vt: _hist_values' rows."""
    oh_hi = (n16 == (b >> 4)[None, :]).astype(jnp.bfloat16)   # [16, E]
    lo = n16 == (b & 15)[None, :]
    # the value-carrying operand is SELECTED, not multiplied: the v5e has
    # no bf16 VALU, so a [16, E] bf16 product is two unpacks, two f32
    # multiplies and a pack a vreg; a select of f32 rows under the [16, E]
    # mask, cast once at [64, E], Mosaic folds into the MXU push
    # (vmatpush...msk). A value or 0 where the product had 1 x value or
    # 0 x value: the same operand but for the sign of a zero
    bv = jnp.concatenate([jnp.where(lo, v[None, :], 0.0) for v in vt],
                         axis=0).astype(jnp.bfloat16)         # [64, E]
    # lanes [0, 64) only: the level kernels' accumulators carry
    # HIST_LANES_PAD lanes (see below), the others exactly 64
    hist_ref[g, :, 0:64] = hist_ref[g, :, 0:64] + jax.lax.dot_general(
        oh_hi, bv, (((1,), (1,)), ((), ())),
        preferred_element_type=F32)                           # [16, 64]


def _hist_accum(hist_ref, bins_g, grad, hess, G: int):
    """hist_ref[g] += radix-16 MXU contraction of one chunk, group by
    group (_hist_group): the [16, E] one-hot of a bin's high nibble
    against a [64, E] operand that holds, in row v*16+lo, value row v
    where the bin's low nibble is lo and 0 elsewhere.

    bins_g: [G, E] i32; grad/hess: [E] f32 already masked to valid rows.
    hist_ref: [G, 16, >=64] f32 VMEM ref holding RAW accumulator columns
    v*16+lo for v in (grad_hi, hess_hi, grad_lo, hess_lo) — the bf16 hi/lo
    pairs that make the contraction exact to f32 (ops/pallas_histogram
    docs). The 4 value rows ride ONE [64, E] rhs so each group costs one
    [16,E]x[E,64] MXU issue instead of four [16,E]x[E,16]: same FLOPs, 4x
    the N-utilization. Callers unpack hi/lo planes OUTSIDE the kernel
    (_unpack_hist).
    """
    E = bins_g.shape[1]
    n16 = jax.lax.broadcasted_iota(I32, (16, E), 0)
    vt = _hist_values(grad, hess)
    for g in range(G):
        _hist_group(hist_ref, g, bins_g[g, :], n16, vt)


# Up to this many groups the histogram kernels unroll their group loop
# (_hist_accum over a [G, E] decoded plane); past it, on a payload of byte
# slots in group order, they loop over sublane tiles of word rows
# (_hist_accum_words), so kernel size and compile time stop growing with
# G. Chip-less compile readings for the v5e (CHANGES.md, PR 36): unrolled,
# seg_hist takes 72-75 s at 137 groups and 283 s at 2,000 groups with a
# chunk of 2,048 lanes; looped, 13 s and 4 s (11.9k bundles: the 32
# groups of one tile at that chunk, whatever G). The benchmark's narrow
# cells (16 and 28 groups) stay unrolled, program for program; two tiles'
# worth of groups is where the loop starts to have iterations to save.
HIST_UNROLL_MAX_GROUPS = 64


def hist_loops_groups(G: int, plan, forced=None) -> bool:
    """Do the histogram kernels loop over word rows for this payload?
    They do past HIST_UNROLL_MAX_GROUPS when every group has a byte slot
    and the slots are in group order (what _payload_plan gives when no
    group fits a nibble): group g is byte g % 4 of word row g // 4.
    ``forced``: the kernels' ``_loop_groups``, by which a test takes
    either loop to hold the two bit-equal."""
    if forced is not None:
        return bool(forced)
    return G > HIST_UNROLL_MAX_GROUPS and all(
        tuple(p) == (g // 4, (g % 4) * 8, 255) for g, p in enumerate(plan))


def _hist_accum_words(hist_ref, wbuf, back, grad, hess, G: int):
    """_hist_accum with the group loop as a loop, for a payload of byte
    slots in group order (hist_loops_groups): a sublane tile of eight
    word rows an iteration, its 32 groups unrolled; the tile that holds
    the last bin words (and the label and row id beside them) is static.

    wbuf: VMEM ref [WPA, E] u32, the chunk as DMAed; ``back``: the lane
    rotation that brings its rows to lane 0 (None where it is aligned
    already). A tile is rotated as the unrolled kernels rotate the whole
    chunk, so each group's operands, and hence its sums, are theirs bit
    for bit.
    """
    E = wbuf.shape[1]
    n16 = jax.lax.broadcasted_iota(I32, (16, E), 0)
    vt = _hist_values(grad, hess)

    def tile(r0, g0, groups):
        x = wbuf[pl.ds(r0, 8), :]
        if back is not None:
            x = pltpu.roll(x, back, 1)
        for k in range(groups):
            b = ((x[k // 4, :] >> U32((k % 4) * 8)) & U32(255)).astype(I32)
            _hist_group(hist_ref, g0 + k, b, n16, vt)

    def full(i, c):
        tile(pl.multiple_of(i * 8, 8), i * 32, 32)
        return c

    jax.lax.fori_loop(0, G // 32, full, 0)
    if G % 32:
        tile(G // 32 * 8, G // 32 * 32, G % 32)


def _chunk_hist(hist_ref, wbuf, d, m, plan, nbw: int, G: int,
                looped: bool):
    """hist_ref += histogram of the chunk in ``wbuf``, whose rows lie in
    lanes [d, d + m()) (d None: 0, no rotation needed). Returns the
    chunk's masked (grad, hess) rows. The one body seg_hist,
    level_seg_hist and root_hist share; ``m`` is a thunk so that the
    unrolled kernels trace op for op as they did before they shared it.
    """
    E = wbuf.shape[1]
    grad_row = nbw + 2
    if looped:
        w = wbuf[grad_row:grad_row + 2, :]
        grad_row = 0
    else:
        w = wbuf[...]
    back = None
    if d is not None:
        back = jax.lax.sub(jnp.int32(E), d)
        w = pltpu.roll(w, back, 1)   # chunk rows at lanes 0..m
    lane = _lane_iota(E)[0]
    valid = (lane < m()).astype(F32)
    grad = _f32r(w[grad_row, :]) * valid
    hess = _f32r(w[grad_row + 1, :]) * valid
    if looped:
        _hist_accum_words(hist_ref, wbuf, back, grad, hess, G)
    else:
        _hist_accum(hist_ref, _unpack_group_bins(w, plan), grad, hess, G)
    return grad, hess


# lanes one block of _slot_hist histograms at a time: a block is the least
# a chunk's smaller child costs. Chip readings, kernel alone at the Expo
# cell's geometry (PERF.md, PR 35): 256 and 512 cost a child of 3% of the
# chunk 0.4-0.5 us a step, 1,024 to 4,096 1-2.5 us more; at half a chunk
# 512 to 2,048 read alike and 256 loses 2 us
HIST_BLOCK = 512


def _slot_hist(hist_ref, slot, plan, grad_row: int, G: int, B: int, off, n):
    """hist_ref += histogram of the rows in lanes [off, off + n) of one
    FIFO slot, the smaller child's rows of the chunk _partition_chunk has
    just compacted there: B lanes a block, ceil((off + n) / B) blocks, so
    the cost follows n and not the chunk. slot: VMEM ref [>= grad_row + 2,
    W]; off < 128 (the block's sub-tile offset); lanes outside the range
    hold other blocks' rows, already counted, or nothing, and are masked
    by select (an undefined lane may hold any bit pattern). The last
    block that can occur is pulled back to end at W and skips the lanes
    the block before it took.
    """
    W = slot.shape[1]
    end = off + n
    lane = jax.lax.broadcasted_iota(I32, (1, B), 1)[0]

    def block(k, carry):
        a = jnp.minimum(k * B, W - B)
        x = slot[0:grad_row + 2, pl.ds(pl.multiple_of(a, 128), B)]
        pos = a + lane
        keep = (pos >= jnp.maximum(off, k * B)) & (pos < end)
        grad = jnp.where(keep, _f32r(x[grad_row, :]), 0.0)
        hess = jnp.where(keep, _f32r(x[grad_row + 1, :]), 0.0)
        _hist_accum(hist_ref, _unpack_group_bins(x, plan), grad, hess, G)
        return carry

    jax.lax.fori_loop(0, jnp.where(n > 0, (end + B - 1) // B, 0), block, 0)


def plane_health(g_plane, h_plane):
    """i32 count of non-finite entries across a (grad, hess) histogram
    plane pair — the ``numerics::inf_hist`` device probe the persist
    grower folds into its scan-carried health vector right after each
    plane lands (post-psum, so sharded ranks count the identical global
    plane). Any float width, any leading batch dims; pure jnp, so it
    fuses into the compiled program with zero host syncs."""
    bad_g = jnp.sum(~jnp.isfinite(g_plane), dtype=I32)
    bad_h = jnp.sum(~jnp.isfinite(h_plane), dtype=I32)
    return bad_g + bad_h


# The level kernels DMA each slot's finished accumulator into row j of an
# HBM [S_max, ...] output. Mosaic refuses a leading-dim slice of an HBM
# ref whose minor dim is not a multiple of the 128-lane tile, so their
# accumulator scratch and output carry HIST_LANES_PAD lanes, of which the
# first 64 hold the [16, 64] radix columns; the wrappers slice the pad
# off outside the kernel.
HIST_LANES_PAD = 128


def _unpack_hist(hist):
    """[G, 16, 64] raw accumulator -> ([G*256] grad, [G*256] hess) f32
    planes (hi*16+lo bin order); runs OUTSIDE the kernel where XLA
    reshapes freely."""
    G = hist.shape[0]
    h4 = hist.reshape(G, 16, 4, 16)
    gh = (h4[:, :, 0] + h4[:, :, 2]).reshape(G * 256)
    hh = (h4[:, :, 1] + h4[:, :, 3]).reshape(G * 256)
    return gh, hh


def _f32r(row):
    return jax.lax.bitcast_convert_type(row, F32)


def _align128(ptr):
    c128 = jnp.int32(128)
    al = jax.lax.mul(jax.lax.div(ptr, c128), c128)
    return pl.multiple_of(al, 128)


# ---------------------------------------------------------------------------
# split_pass
# ---------------------------------------------------------------------------

def _make_segment_step(C: int, G: int, plan, nbw: int, WP_LIVE: int,
                       skip_hist: bool, skip_pack: bool):
    """One grid step of the in-place partition of one payload segment:
    the body split_pass and level_pass share.

    Returns step(lo, sc, pay, hist, wbuf, obuf, slots, carry, ctl, tcnt,
    st, sem_r, sem_w): ``lo`` is the step's number within the segment
    (0 .. nch + 1), ``sc(k)`` its S_* scalar k, ``pay`` the payload in HBM
    (read and written in place), ``hist`` the [G, 16, >= 64] accumulator.
    Scratch: wbuf / obuf [WPA, E] u32, slots [4, R, E + 128] (2 x L / R),
    carry [2, R, 128], ctl / tcnt [>= E / 128, 128] i32, st SMEM [11] i32,
    sem_r one DMA semaphore, sem_w two (one a side); E = C + 128,
    R = ceil8(WP_LIVE). The R - WP_LIVE pad rows of the last live sublane
    tile ride the partition (the same vregs; nothing reads them), payload
    rows from R on are never touched.

    st: 0 fr, 1 br (read frontiers), 2 lf, 3 rf (write frontiers),
        4 vl, 5 vr (the write frontiers with the blocks waiting in the
        FIFO counted in: where the next left block starts and the next
        right block ends), 6 nleft,
        7+2p nL(slot pair p), 8+2p nR(slot pair p)

    Step lo reads chunk lo (lo < nch) into slot pair lo % 2 and drains
    block lo - 2 (2 <= lo < nch + 2) out of the same pair first. Unless
    ``skip_hist``, it then adds the rows of the child S_SMALL_L names to
    ``hist`` from that child's slot, where the partition has just laid
    them in order (_slot_hist: as many blocks as the rows fill; reads of
    VMEM no DMA writes). A left
    block covers payload lanes [lf, lf + nL), a right block
    [rf - nR, rf); _partition_chunk has laid each in its slot at the
    sub-tile offset it has in the payload and has closed the tile it
    shares with the block before it, so the drain is DMAs of whole tiles
    from the slot to the payload and nothing else: the left side writes
    the tiles below floor128(lf + nL), the right side those from
    ceil128(rf - nR) up to ceil128(rf), each tile once. The tile a side
    still has open (it holds the segment's edge and a neighbouring
    leaf's rows at first: both edge tiles are read at lo == 0, before
    any write, and nothing else of the old payload is ever read back)
    stays in VMEM, in ``carry``, for the next block of that side. When
    the last block has drained the frontiers meet inside one payload
    tile, whose two parts are the two open tiles: merged and written
    once, at lo == nch + 1. A run of 0 .. C / 128 + 1 tiles goes out as
    the binary digits of its length, one DMA of 128 << k lanes a set bit.

    The writes are started and left in flight; they are waited (the same
    descriptors under the same conditions) just before this step's
    partition refills the slot pair they read, or at the step's end when
    there is no chunk left to read, so no DMA outlives its step. Hazards:
      - payload lanes: a completed left tile lies wholly below lf + nL
        <= fr and a right one at or above rf - nR >= br, so no write
        covers a row not yet read (reads lead writes: the FIFO's
        invariant); the open tiles are written only after the last read.
        The chunk read's aligned E-wide window can overlap tiles being
        written, but only in lanes outside [ptr, ptr + m), which
        ``valid`` masks (old or new, a word there is some row's). The
        two sides' runs are disjoint (lf + nL <= rf - nR), and the
        closing tile is written after every other write has been
        waited;
      - VMEM: a slot pair is refilled only after the waits; the carry
        tiles are read by no DMA but the closing one, which is waited
        at once; the edge reads are waited before the first partition.
      - between segments of one level_pass launch: every step has
        waited its own DMAs, so the next segment's edge reads see the
        neighbour's closed tile.
    The Pallas interpreter copies at start(); tests/test_split_drain.py
    also runs under InterpretParams(dma_execution_mode="on_wait"), where
    a copy happens at its wait.
    """
    E = C + 128
    R = _ceil8(WP_LIVE)
    grad_row = nbw + 2
    # a side completes at most C / 128 + 1 tiles a block
    run_bits = range((C // 128 + 1).bit_length())

    def step(lo, sc, pay, hist, wbuf, obuf, slots, carry, ctl, tcnt, st,
             sem_r, sem_w):
        nch = sc(S_NCH)
        lane = _lane_iota(E)[0]

        @pl.when(lo == 0)
        def _init():
            s0 = sc(S_S0)
            s1 = s0 + sc(S_NL)
            for k in (0, 2, 4):
                st[k] = s0
                st[k + 1] = s1
            for k in range(6, 11):
                st[k] = 0
            hist[...] = jnp.zeros_like(hist)

            @pl.when(nch > 0)
            def _edges():
                # the two tiles the segment shares with its neighbours
                # (one tile twice where the segment is that short)
                edges = [pltpu.make_async_copy(
                    pay.at[0:R, pl.ds(_align128(x), 128)], carry.at[k],
                    sem_r) for k, x in enumerate((s0, s1))]
                for cp in edges:
                    cp.start()
                for cp in edges:
                    cp.wait()

        fr, br, lf, rf, vl, vr = (st[k] for k in range(6))
        reading = lo < nch
        draining = (lo >= 2) & (lo < nch + 2)
        p = jax.lax.rem(lo, jnp.int32(2))   # this step's slot pair

        # the chunk to read: from the end with the smaller write-space gap
        m = jnp.minimum(jnp.int32(C), jax.lax.sub(br, fr))
        use_front = (fr - vl) <= (vr - br)
        ptr = jnp.where(use_front, fr, br - m)
        al = _align128(ptr)
        chunk = pltpu.make_async_copy(pay.at[:, pl.ds(al, E)], wbuf, sem_r)

        # block lo - 2, waiting in slot pair p: its whole tiles, as
        # (first slot tile, payload lane of slot lane 0, tiles) a side
        nL_ = st[7 + 2 * p]
        nR_ = st[8 + 2 * p]
        dL = lf & 127
        rs = rf - nR_
        dR = rs & 127
        t0R = (dR + 127) >> 7   # the first tile is the next block's too
        sides = ((0, lf - dL, (dL + nL_) >> 7),
                 (t0R, rs - dR, ((dR + nR_ + 127) >> 7) - t0R))
        runs = []
        for side, (t0, a0, cnt) in enumerate(sides):
            for k in run_bits:
                t = (t0 + ((cnt >> (k + 1)) << (k + 1))) * 128
                runs.append((((cnt >> k) & 1) == 1, pltpu.make_async_copy(
                    slots.at[2 * p + side, :,
                             pl.ds(pl.multiple_of(t, 128), 128 << k)],
                    pay.at[0:R, pl.ds(pl.multiple_of(a0 + t, 128),
                                      128 << k)],
                    sem_w.at[side])))

        def wait_writes():
            for on, cp in runs:
                pl.when(on)(cp.wait)

        @pl.when(reading)
        def _start_read():
            st[0] = jnp.where(use_front, fr + m, fr)
            st[1] = jnp.where(use_front, br, br - m)
            chunk.start()

        @pl.when(draining)
        def _drain():
            for on, cp in runs:
                pl.when(on)(cp.start)
            st[2] = lf + nL_
            st[3] = rs

        @pl.when(reading)
        def _read():
            chunk.wait()
            d = ptr - al
            w = pltpu.roll(wbuf[...], jax.lax.sub(jnp.int32(E), d), 1)   # chunk rows at lanes 0..m
            valid = lane < m

            # decision (numerical; dense_bin.hpp:112 semantics). Bundled
            # (EFB) features read the group byte: values outside the
            # feature's [LS, LE) range belong to another bundle member or
            # the sentinel — the row is at this feature's most_freq bin
            word = w[0, :] * U32(0)
            for r_ in range(nbw):
                word = jnp.where(sc(S_WG) == r_, w[r_, :], word)
            b_raw = ((word >> sc(S_SH).astype(U32))
                     & sc(S_MASK).astype(U32)).astype(I32)
            in_r = (b_raw >= sc(S_LS)) & (b_raw < sc(S_LE))
            b = jnp.where(in_r, b_raw - sc(S_LS), sc(S_MF))
            cmp_left = b <= sc(S_THR)
            is_na = (sc(S_MT) == 2) & (b == sc(S_NB) - 1)
            is_zero = (sc(S_MT) == 1) & (b == sc(S_DB))
            # dl as a VECTOR compare: a scalar-bool broadcast lowers to an
            # unsupported i8->i1 truncation in Mosaic
            dlv = (jnp.zeros_like(b) + sc(S_DL)) > 0
            gd = is_na | is_zero
            go_left = (gd & dlv) | ((~gd) & cmp_left)

            gl = valid & go_left
            nL = jnp.sum(gl.astype(F32), dtype=F32).astype(I32)
            nR = m - nL
            st[6] = st[6] + nL

            # slot pair p is refilled below: its drain has to be out
            pl.when(draining)(wait_writes)
            if skip_pack:
                slots[2 * p, :, 0:E] = w[:R]
                slots[2 * p + 1, :, 0:E] = w[:R]
            else:
                # obuf lends the chunk a tile-addressable home; the
                # blocks land where the drain two steps on will write
                # them: after the blocks still pending
                obuf[...] = w
                _partition_chunk(
                    obuf, R, gl, m, vl & 127, (vr - nR) & 127,
                    slots.at[2 * p], slots.at[2 * p + 1], carry, ctl, tcnt)
            if not skip_hist:
                # the smaller child's histogram, from the rows the
                # partition has just laid in its slot and from no other
                # lane. The left side's open tile is not in the slot
                # (_partition_chunk): put it there
                base_l = vl & 127
                slots[2 * p, :, pl.ds(pl.multiple_of(
                    ((base_l + nL) >> 7) * 128, 128), 128)] = carry[0]
                small_l = sc(S_SMALL_L) > 0
                _slot_hist(hist, slots.at[2 * p + jnp.where(small_l, 0, 1)],
                           plan, grad_row, G, min(HIST_BLOCK, C),
                           jnp.where(small_l, base_l, (vr - nR) & 127),
                           jnp.where(small_l, nL, nR))
            st[7 + 2 * p] = nL
            st[8 + 2 * p] = nR
            st[4] = vl + nL
            st[5] = vr - nR

        pl.when(draining & ~reading)(wait_writes)

        @pl.when((lo == nch + 1) & (nch > 0))
        def _close():
            # lf == rf: the two open tiles are two parts of one
            end = st[2]
            l128 = jax.lax.broadcasted_iota(I32, (R, 128), 1)
            carry[0] = jnp.where(l128 < (end & 127), carry[0], carry[1])
            cp = pltpu.make_async_copy(
                carry.at[0], pay.at[0:R, pl.ds(_align128(end), 128)],
                sem_w.at[0])
            cp.start()
            cp.wait()

    return step


def _segment_scratch(WPA: int, E: int, R: int):
    """scratch_shapes of _make_segment_step, in its argument order."""
    TP = _ceil8(E // 128)
    return [
        pltpu.VMEM((WPA, E), U32),          # wbuf: the chunk as DMAed
        pltpu.VMEM((WPA, E), U32),          # obuf: the chunk re-aligned
        # FIFO slots (2 x L/R): whole sublane tiles, and one lane tile
        # past E for the placement's last store
        pltpu.VMEM((4, R, E + 128), U32),
        pltpu.VMEM((2, R, 128), U32),       # carry: each side's open tile
        pltpu.VMEM((TP, 128), I32),         # ctl: control words
        pltpu.VMEM((TP, 128), I32),         # tcnt: rows left a tile
        pltpu.SMEM((11,), I32),             # st
        pltpu.SemaphoreType.DMA,            # sem_r
        pltpu.SemaphoreType.DMA((2,)),      # sem_w: one a side
    ]


def make_split_pass(WPA: int, NP: int, G: int, plan, nbw: int,
                    C: int = 8192, interpret: bool = False,
                    wp_live: int = 0,
                    _skip_hist: bool = False, _skip_pack: bool = False):
    """Build the fused per-split kernel for one payload geometry.

    plan: tuple of (word_row, shift, mask) per group; rows nbw..nbw+3 are
    label/rowid/grad/hess (nbw = WP - 4).

    wp_live: how many leading payload rows carry per-row state that must
    PERMUTE with the partition (bins + label/rid/grad/hess + all score and
    snapshot rows — everything multiclass adds); defaults to the
    single-score layout nbw + 5. Rows past wp_live are padding: those of
    the last live sublane tile ride along, the others are never touched.

    Returns fn(pay, scalars_i32) -> (pay', hist [G*256, 2] f32, n_left).
    """
    assert WPA % 8 == 0, "payload row count must be padded to 8"
    E = C + 128
    WP_LIVE = wp_live or (nbw + 5)
    assert WP_LIVE <= WPA
    step = _make_segment_step(C, G, plan, nbw, WP_LIVE, _skip_hist,
                              _skip_pack)

    def kernel(ns, pay_in, pay_out, hist_ref, cnt_ref, *scratch):
        st, sem_r = scratch[-3:-1]
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _seed():
            if interpret:
                # on hardware pay_out IS pay_in (input_output_aliases) and
                # every read below goes through pay_out; interpreter mode
                # does not alias, so seed the output with the input once
                cpi = pltpu.make_async_copy(pay_in, pay_out, sem_r)
                cpi.start()
                cpi.wait()

        step(i, lambda k: ns[k], pay_out, hist_ref, *scratch)

        @pl.when(i == jax.lax.add(ns[S_NCH], jnp.int32(1)))
        def _fin():
            cnt_ref[0] = st[6]

    _cparams = CompilerParams(
        vmem_limit_bytes=split_pass_vmem_bytes(WPA, E, G))

    @jax.jit
    def split_pass(pay, scalars):
        # ALWAYS run the init/fin steps even for an empty segment (grid 2,
        # no read/drain work): a zero grid would skip the interpreter-mode
        # pay_in -> pay_out seed and return an uninitialized payload
        grid = (scalars[S_NCH] + 2).astype(jnp.int32)
        # trace the kernel with 32-bit default dtypes: under jax_enable_x64
        # (on for reference-parity f64 host math) weak-int promotion inside
        # Mosaic recurses/lowers to unsupported i64
        with enable_x64(False):
            pay2, hist, cnt = _call(pay, scalars, grid)
        # separate grad/hess planes: downstream keeps per-plane [L, TBp]
        # histograms (no strided channel slices on the hot path)
        return pay2, _unpack_hist(hist), cnt[0]

    def _call(pay, scalars, grid):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(grid,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=[
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec((G, 16, 64),
                                 lambda i, s: (i * 0, i * 0, i * 0)),
                    pl.BlockSpec((1,), lambda i, s: (i * 0,),
                                 memory_space=pltpu.SMEM),
                ],
                scratch_shapes=_segment_scratch(WPA, E, _ceil8(WP_LIVE)),
            ),
            out_shape=[
                jax.ShapeDtypeStruct((WPA, NP), U32),
                jax.ShapeDtypeStruct((G, 16, 64), F32),
                jax.ShapeDtypeStruct((1,), I32),
            ],
            input_output_aliases={1: 0},
            compiler_params=_cparams,
            interpret=interpret,
        )(scalars, pay)

    return split_pass


# ---------------------------------------------------------------------------
# level_pass: one launch partitions EVERY splitting leaf of a tree level
# ---------------------------------------------------------------------------

def make_level_pass(WPA: int, NP: int, G: int, plan, nbw: int,
                    S_max: int, T_max: int, C: int = 8192,
                    interpret: bool = False, wp_live: int = 0,
                    _skip_hist: bool = False):
    """Multi-leaf split_pass: the level-parallel grower's fused partition.

    One pallas_call partitions the payload segments of up to ``S_max``
    splitting leaves (slots) and, unless ``_skip_hist`` (the grower
    skips it, as in make_split_pass), accumulates each slot's
    smaller-child histogram — the per-split kernel's steps
    (_make_segment_step) with the slot id derived per grid step from
    prefetched step tables, so a whole tree level costs ONE
    device-program launch instead of one per split.

    Per-slot scalars arrive as one [S_max, 16] i32 matrix in S_* column
    order (columns 15 unused); ``slot_of_step`` [T_max] and
    ``base_of_slot`` [S_max] map the flat dynamic grid onto (slot,
    local step): slot j owns steps [base[j], base[j] + nch_j + 2) and
    runs init / read / 2-deep-FIFO drain / fin exactly like
    make_split_pass. Slots' segments are disjoint, the grid is
    sequential and a step leaves no DMA in flight, so the in-place
    two-ended writeback stays safe; the payload keeps its
    input_output_aliases (in-place contract).

    Returns fn(pay, scal_mat, slot_of_step, base_of_slot, grid) ->
    (pay', hist [S_max, G, 16, 64] raw accumulator, n_left [S_max]).
    Slots with zero steps leave their hist/count outputs UNDEFINED —
    callers mask by activity.
    """
    assert WPA % 8 == 0, "payload row count must be padded to 8"
    E = C + 128
    WP_LIVE = wp_live or (nbw + 5)
    assert WP_LIVE <= WPA
    step = _make_segment_step(C, G, plan, nbw, WP_LIVE, _skip_hist, False)

    def kernel(sm, so, bo, pay_in, pay_out, hist_out, cnt_ref,
               hacc, sem_h, *scratch):
        st, sem_r = scratch[-3:-1]
        i = pl.program_id(0)
        j = so[i]                       # slot of this step
        lo = i - bo[j]                  # local step within the slot

        @pl.when(i == 0)
        def _seed():
            if interpret:
                # on hardware pay_out IS pay_in (input_output_aliases);
                # the interpreter does not alias, so seed the output once
                cpi = pltpu.make_async_copy(pay_in, pay_out, sem_r)
                cpi.start()
                cpi.wait()

        step(lo, lambda k: sm[j, k], pay_out, hacc, *scratch)

        @pl.when(lo == jax.lax.add(sm[j, S_NCH], jnp.int32(1)))
        def _fin():
            cnt_ref[j] = st[6]
            cph = pltpu.make_async_copy(hacc, hist_out.at[j], sem_h)
            cph.start()
            cph.wait()

    _cparams = CompilerParams(
        vmem_limit_bytes=split_pass_vmem_bytes(WPA, E, G))

    @jax.jit
    def level_pass(pay, scal_mat, slot_of_step, base_of_slot, grid):
        with enable_x64(False):
            pay2, hist, cnt = _call(pay, scal_mat, slot_of_step,
                                    base_of_slot,
                                    jnp.maximum(grid, 1).astype(jnp.int32))
        return pay2, hist[..., :64], cnt

    def _call(pay, scal_mat, slot_of_step, base_of_slot, grid):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(grid,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=[
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec((S_max,), lambda i, *s: (i * 0,),
                                 memory_space=pltpu.SMEM),
                ],
                scratch_shapes=[
                    pltpu.VMEM((G, 16, HIST_LANES_PAD), F32),  # hist acc
                    pltpu.SemaphoreType.DMA,                   # sem_h
                ] + _segment_scratch(WPA, E, _ceil8(WP_LIVE)),
            ),
            out_shape=[
                jax.ShapeDtypeStruct((WPA, NP), U32),
                jax.ShapeDtypeStruct((S_max, G, 16, HIST_LANES_PAD), F32),
                jax.ShapeDtypeStruct((S_max,), I32),
            ],
            input_output_aliases={3: 0},
            compiler_params=_cparams,
            interpret=interpret,
        )(scal_mat, slot_of_step, base_of_slot, pay)

    return level_pass


def make_level_seg_hist(WPA: int, NP: int, G: int, plan, nbw: int,
                        S_max: int, T_max: int, C: int = 16384,
                        interpret: bool = False):
    """Batched seg_hist: smaller-child histograms of up to ``S_max``
    contiguous payload segments in ONE launch (the level-parallel
    companion of make_seg_hist).

    Per-slot scalars: [S_max, 4] i32 (nch, start, length, pad); step
    tables as in make_level_pass. Returns fn(pay, scal_mat,
    slot_of_step, base_of_slot, grid) -> hist [S_max, G, 16, 64] raw
    accumulator; zero-length slots leave their plane UNDEFINED.
    """
    assert WPA % 8 == 0
    E = C + 128
    looped = hist_loops_groups(G, plan)

    def kernel(sm, so, bo, pay_hbm, hist_out, hacc, wbuf, sem_r, sem_h):
        i = pl.program_id(0)
        j = so[i]
        lo = i - bo[j]

        @pl.when(lo == 0)
        def _init():
            hacc[...] = jnp.zeros_like(hacc)

        ptr = sm[j, 1] + lo * C
        m = jnp.minimum(jnp.int32(C), sm[j, 2] - lo * C)
        al = _align128(ptr)
        cp = pltpu.make_async_copy(
            pay_hbm.at[:, pl.ds(al, E)], wbuf, sem_r)
        cp.start()
        cp.wait()
        d = ptr - al
        _chunk_hist(hacc, wbuf, d, lambda: m, plan, nbw, G, looped)

        @pl.when(lo == sm[j, 0] - 1)
        def _fin():
            cph = pltpu.make_async_copy(hacc, hist_out.at[j], sem_h)
            cph.start()
            cph.wait()

    _cparams = CompilerParams(
        vmem_limit_bytes=seg_hist_vmem_bytes(WPA, E, G, looped))

    @jax.jit
    def level_seg_hist(pay, scal_mat, slot_of_step, base_of_slot, grid):
        with enable_x64(False):
            hist = pl.pallas_call(
                kernel,
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=3,
                    grid=(jnp.maximum(grid, 1).astype(jnp.int32),),
                    in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                    out_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                    scratch_shapes=[
                        pltpu.VMEM((G, 16, HIST_LANES_PAD), F32),
                        pltpu.VMEM((WPA, E), U32),
                        pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.DMA,
                    ],
                ),
                out_shape=[jax.ShapeDtypeStruct(
                    (S_max, G, 16, HIST_LANES_PAD), F32)],
                compiler_params=_cparams,
                interpret=interpret,
            )(scal_mat, slot_of_step, base_of_slot, pay)[0]
        return hist[..., :64]

    return level_seg_hist


# ---------------------------------------------------------------------------
# seg_hist
# ---------------------------------------------------------------------------

def make_seg_hist(WPA: int, NP: int, G: int, plan, nbw: int,
                  C: int = 16384, interpret: bool = False,
                  _loop_groups=None):
    """Histogram of one contiguous payload segment (dynamic start/length).

    Runs AFTER split_pass has partitioned a leaf: the smaller child's rows
    are contiguous, so the histogram streams exactly those rows — the
    leaf-wise subtraction trick then charges each tree level ~n/2 histogram
    rows (the reference's ordered-bin smaller-leaf walk,
    include/LightGBM/bin.h:229, achieves the same economy row-wise on CPU;
    split_pass's own in-slot histogram does too, without this launch or a
    second read of the rows, and yet read slower on the chip: PERF.md,
    PR 35).

    Returns fn(pay, start, length) -> (gh [G*256], hh [G*256]) f32; outputs
    are UNDEFINED when length == 0 (zero grid steps) — callers mask.

    _loop_groups: hist_loops_groups' ``forced``.
    """
    assert WPA % 8 == 0
    E = C + 128
    looped = hist_loops_groups(G, plan, _loop_groups)

    def kernel(ns, pay_hbm, hist_ref, wbuf, sem_r):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            hist_ref[...] = jnp.zeros_like(hist_ref)

        ptr = ns[1] + i * C
        m = jnp.minimum(jnp.int32(C), ns[2] - i * C)
        al = _align128(ptr)
        cp = pltpu.make_async_copy(
            pay_hbm.at[:, pl.ds(al, E)], wbuf, sem_r)
        cp.start()
        cp.wait()
        d = ptr - al
        _chunk_hist(hist_ref, wbuf, d, lambda: m, plan, nbw, G, looped)

    _cparams = CompilerParams(
        vmem_limit_bytes=seg_hist_vmem_bytes(WPA, E, G, looped))

    @jax.jit
    def seg_hist(pay, start, length):
        nch = (length + C - 1) // C
        grid = jnp.where(length > 0, nch, 0).astype(jnp.int32)
        scalars = jnp.stack([nch, start, length]).astype(jnp.int32)
        with enable_x64(False):
            hist = pl.pallas_call(
                kernel,
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=(grid,),
                    in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                    out_specs=[
                        pl.BlockSpec((G, 16, 64),
                                     lambda i, s: (i * 0, i * 0, i * 0)),
                    ],
                    scratch_shapes=[
                        pltpu.VMEM((WPA, E), U32),
                        pltpu.SemaphoreType.DMA,
                    ],
                ),
                out_shape=[jax.ShapeDtypeStruct((G, 16, 64), F32)],
                compiler_params=_cparams,
                interpret=interpret,
            )(scalars, pay)[0]
        return _unpack_hist(hist)

    return seg_hist


# ---------------------------------------------------------------------------
# root_hist
# ---------------------------------------------------------------------------

def make_root_hist(WPA: int, NP: int, G: int, plan, nbw: int, n: int,
                   C: int = 16384, interpret: bool = False,
                   _loop_groups=None):
    """One streaming pass: the padded root histogram.

    Returns fn(pay) -> (gh [G*256], hh [G*256]) f32. The root's sums are
    the caller's to read off a group's plane (grow_persist.root_totals): a
    running f32 total kept here, one add a chunk, drifts with one sign
    where every hessian is the same number (PERF.md section 7 row 0b).
    """
    assert WPA % 8 == 0
    looped = hist_loops_groups(G, plan, _loop_groups)
    nch = (n + C - 1) // C
    assert NP >= nch * C, "payload lanes must cover whole root chunks"

    def kernel(pay_hbm, hist_ref, wbuf, sem_r):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            hist_ref[...] = jnp.zeros_like(hist_ref)

        cp = pltpu.make_async_copy(
            pay_hbm.at[:, pl.ds(i * C, C)], wbuf, sem_r)
        cp.start()
        cp.wait()
        _chunk_hist(hist_ref, wbuf, None, lambda: n - i * C, plan, nbw, G,
                    looped)

    @jax.jit
    def root_hist(pay):
        with enable_x64(False):
            hist = _call(pay)[0]
        return _unpack_hist(hist)

    # the streaming chunk buffer alone (WPA*C u32) outgrows the 16MB
    # Mosaic default on wide unbundled payloads (~180 words at C=16384)
    _cparams = CompilerParams(
        vmem_limit_bytes=seg_hist_vmem_bytes(WPA, C, G, looped))

    def _call(pay):
        return pl.pallas_call(
            kernel,
            compiler_params=_cparams,
            grid=(nch,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[
                pl.BlockSpec((G, 16, 64),
                             lambda i: (i * 0, i * 0, i * 0)),
            ],
            out_shape=[jax.ShapeDtypeStruct((G, 16, 64), F32)],
            scratch_shapes=[
                pltpu.VMEM((WPA, C), U32),
                pltpu.SemaphoreType.DMA,
            ],
            interpret=interpret,
        )(pay)

    return root_hist
