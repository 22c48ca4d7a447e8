"""Pallas TPU kernel: fused best-split scan for a (left, right) child pair.

The XLA formulation of the per-leaf scan (ops/split.py,
find_best_split_numerical — the rebuild of the reference's
FeatureHistogram::FindBestThresholdSequentially,
src/treelearner/feature_histogram.hpp:770-948) is ~150 small HLO ops on
[F, W] tiles; at [28, 256] every op is latency-bound and the pair of child
scans costs ~0.5 ms of pure per-op overhead per split — the dominant fixed
cost of tree growth. This kernel fuses the whole computation (both missing-
direction scans, gain math, validity masks, per-feature argmax with the
reference's tie-breaking) into ONE Mosaic program:

  * the six masked cumulative sums become a single [6·F, W] x [W, W]
    lower-triangular matmul on the MXU (f32 HIGHEST precision);
  * everything else is elementwise VPU work on [F, W] tiles plus lane
    reductions — no per-op dispatch.

Fast-path semantics only (the defaults): no monotone constraints, no L1, no
max_delta_step, f32 accumulation (use_dp=false), no extra_trees/by-node/
CEGB. Anything else falls back to the XLA path — see
treelearner/serial.resolve_scan_impl. Numerics match the XLA f32 path up to
f32 summation-order (cumsum reassociation); the equivalence test
(tests/test_pallas_scan.py) pins thresholds/choices exactly and gains to
float tolerance.

Outputs per (child, feature): penalized gain (-inf when invalid), chosen
local threshold, direction flag, and the left-side (grad, hess, count) sums
at that threshold — the host-side assembly (ops/grow._eval_children_fused)
does the tiny cross-feature argmax and builds the SplitCandidate pair.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .pallas_compat import CompilerParams, enable_x64, pl, pltpu

NEG_INF = float("-inf")


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def scan_pair_vmem_bytes(Fp: int, Wp: int) -> int:
    """Scoped-vmem limit :func:`scan_pair` requests at padded geometry
    (Fp, Wp): ~12 staged [Fp, Wp] f32 blocks + the cumsum stack + Mosaic
    temporaries. The kernel runs with this number; the described-topology
    compiles of tests/test_chip_compile.py prove it (28, 137 and 2,000
    features). Past some 290k lanes the blocks outgrow the fixed 20 MB:
    at Fp 2000, Wp 256 (the persist grower's padded group planes of 2,000
    features) the compiler's stack is 60.7 MB, 31 blocks, where 16 blocks
    + 20 MB are 51; 34 blocks cover it. The default
    scoped-vmem budget OOMs past ~450 features at Wp=256 (v5e carries
    128MB of VMEM, so size the limit to the footprint)."""
    block = Fp * Wp * 4
    return int(min(100 << 20, max(16 * block + (20 << 20), 34 * block)))


def scan_blocks_vmem_bytes(Gp: int, Wp: int) -> int:
    """Scoped-vmem limit :func:`scan_blocks` requests: ~14 [Gp, Wp]
    staging planes + the [Wp, Wp] triangle + fill temporaries (small
    next to the per-feature kernel's footprint). Requested and proven
    like :func:`scan_pair_vmem_bytes`."""
    return int(min(100 << 20, 48 * Gp * Wp * 4 + Wp * Wp * 4 + (20 << 20)))


def scan_input_contract(rows: int, g_max: float = 1.0,
                        h_max: float = 0.25) -> dict:
    """Value-range contract for the split-find scan inputs, seeded into
    the analysis/dataflow interpreter: ``gb``/``hb`` are per-bin
    (grad, hess) histogram sums, so any entry (and any prefix sum of
    entries — every row contributes once) is bounded by the per-row
    caps times ``rows``; hessians are nonnegative; the scalar row
    carries counts in ``[0, rows]`` and the parent aggregates."""
    g = float(rows) * float(g_max)
    h = float(rows) * float(h_max)
    return {
        "gb": (-g, g), "hb": (0.0, h),
        "counts": (0.0, float(rows)),
        "parent_grad": (-g, g), "parent_hess": (0.0, h),
    }


# the split-find scan stages everything in f32 and never narrows on
# purpose; an empty blessing table means every narrowing the
# precision-flow auditor finds here must prove its range
NARROW_OK = ()


def margin_bucket_index(margin):
    """Device-side split-margin bucketing at the ``numerics::split_margin``
    layout (telemetry/health MARGIN_LO/GROWTH/NB — the single source of
    truth shared with the host registry histogram).

    The margin — best gain minus runner-up at a split decision, the
    quantity quantized-histogram noise must not collapse — is the scan
    kernels' output domain, so its device bucketing lives here next to
    the gain contract. Same rule as ``histo.Histogram.bucket_index``:
    ``floor(log(m/lo)/log(growth))``, sub-``lo`` values clamp into
    bucket 0, the top bucket saturates. All-f32 (the persist fast path
    is f64-free; the 2x bucket growth dwarfs f32 log roundoff)."""
    from ..telemetry.health import MARGIN_GROWTH, MARGIN_LO, MARGIN_NB
    f32 = jnp.float32
    m = jnp.maximum(margin.astype(f32), jnp.asarray(MARGIN_LO, f32))
    idx = jnp.floor(jnp.log(m * jnp.asarray(1.0 / MARGIN_LO, f32))
                    * jnp.asarray(1.0 / math.log(MARGIN_GROWTH), f32))
    return jnp.clip(idx.astype(jnp.int32), 0, MARGIN_NB - 1)


def topk_vote_indices(gains, k: int, num_features: int, neg):
    """Per-rank PV-Tree vote proposal from a local gain scan: the top-k
    feature ids of ``gains`` ([..., F], batched over leading axes), with
    non-splitting proposals (gain <= ``neg``) replaced by the
    ``num_features`` sentinel so the vote-count scatter drops them.

    Shared by the v1 voting eval (ops/grow._voting_reduce_hist) and both
    persist voting evals (ops/grow_persist) so the proposal ordering —
    ``lax.top_k``'s stable smaller-index-on-ties rule, the reference's
    GlobalVoting tie semantics — can never drift between growers. The
    result is the ``vote_allgather`` wire payload: k i32 words per rank
    per leaf instead of the historical [F]-plane vote psum."""
    top_vals, top_idx = jax.lax.top_k(gains, k)
    return jnp.where(top_vals > neg, top_idx.astype(jnp.int32),
                     jnp.asarray(num_features, jnp.int32))


def _scan_kernel(scal_ref, gb_ref, hb_ref, keepr_ref, keepf_ref,
                 validr_ref, validf_ref, aux_ref, out_ref):
    # validr/validf arrive as [1, F, W] child blocks
    """One grid step = one child.

    scal_ref:  [1, 1, 128] f32 (sum_grad, sum_hess, num_data, cnt_factor,
                                min_data, min_hess, min_gain_shift,
                                lambda_l2, 0...)
    gb/hb:     [1, F, W] f32 dense per-feature bin grad/hess
    keepr/keepf: [F, W] f32 cumsum masks (1 - excluded bins) per direction
    validr/validf: [F, W] f32 positional validity (in-feat, range, fmask)
    aux_ref:   [8, F] f32  (row 0: penalty; rows 1+: reserved)
    out_ref:   [1, 8, F] f32 (gain, t, use_f, lg, lh, lc, has, pad)
    """
    F, W = keepr_ref.shape
    sg = scal_ref[0, 0, 0]
    sh = scal_ref[0, 0, 1]       # sum_hess + 2*kEpsilon (caller adds it)
    nd = scal_ref[0, 0, 2]
    cf = scal_ref[0, 0, 3]
    min_data = scal_ref[0, 0, 4]
    min_hess = scal_ref[0, 0, 5]
    min_gain_shift = scal_ref[0, 0, 6]
    l2 = scal_ref[0, 0, 7]

    gb = gb_ref[0]
    hb = hb_ref[0]
    keep_r = keepr_ref[:]
    keep_f = keepf_ref[:]
    valid_r0 = validr_ref[0]
    valid_f0 = validf_ref[0]
    pen = aux_ref[0, :]

    cnt_b = jnp.floor(hb * cf + jnp.float32(0.5))

    # ---- six cumulative sums as one triangular MXU contraction ----------
    # tri[w, w'] = 1 when w' <= w  (inclusive prefix along lanes)
    iw = jax.lax.broadcasted_iota(jnp.int32, (W, W), 0)
    jw = jax.lax.broadcasted_iota(jnp.int32, (W, W), 1)
    tri = (iw >= jw).astype(jnp.float32)                     # [W, W] lower
    stack = jnp.concatenate([gb * keep_r, hb * keep_r, cnt_b * keep_r,
                             gb * keep_f, hb * keep_f, cnt_b * keep_f],
                            axis=0)                          # [6F, W]
    cums = jax.lax.dot_general(
        stack, tri, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                  # [6F, W]
    gr_c = cums[0 * F:1 * F]
    hr_c = cums[1 * F:2 * F]
    cr_c = cums[2 * F:3 * F]
    gl_c = cums[3 * F:4 * F]
    hl_c = cums[4 * F:5 * F]
    cl_c = cums[5 * F:6 * F]

    # ---- REVERSE direction (right side accumulates from high bins) ------
    gr_tot = gr_c[:, W - 1:W]
    hr_tot = hr_c[:, W - 1:W]
    cr_tot = cr_c[:, W - 1:W]
    r_grad = gr_tot - gr_c
    r_hess = hr_tot - hr_c                                   # (+eps no-op)
    r_cnt = cr_tot - cr_c
    l_cnt = nd - r_cnt
    l_grad = sg - r_grad
    l_hess = sh - r_hess

    ok_r = (valid_r0 > jnp.float32(0.0)) \
        & (r_cnt >= min_data) & (r_hess >= min_hess) \
        & (l_cnt >= min_data) & (l_hess >= min_hess)
    gains_r = (l_grad * l_grad) / (l_hess + l2) \
        + (r_grad * r_grad) / (r_hess + l2)
    ok_r &= gains_r > min_gain_shift
    gains_r = jnp.where(ok_r, gains_r, NEG_INF)

    wrow = jax.lax.broadcasted_iota(jnp.int32, (F, W), 1).astype(jnp.float32)
    best_gain_r = jnp.max(gains_r, axis=1)                   # [F]
    at_max_r = ok_r & (gains_r == best_gain_r[:, None])
    best_t_r = jnp.max(jnp.where(at_max_r, wrow, -1.0), axis=1)

    # ---- forward direction (left accumulates from low bins) -------------
    f_l_grad = gl_c
    f_l_hess = hl_c
    f_l_cnt = cl_c
    f_r_cnt = nd - f_l_cnt
    f_r_grad = sg - f_l_grad
    f_r_hess = sh - f_l_hess

    ok_f = (valid_f0 > jnp.float32(0.0)) \
        & (f_l_cnt >= min_data) & (f_l_hess >= min_hess) \
        & (f_r_cnt >= min_data) & (f_r_hess >= min_hess)
    gains_f = (f_l_grad * f_l_grad) / (f_l_hess + l2) \
        + (f_r_grad * f_r_grad) / (f_r_hess + l2)
    ok_f &= gains_f > min_gain_shift
    gains_f = jnp.where(ok_f, gains_f, NEG_INF)

    best_gain_f = jnp.max(gains_f, axis=1)
    big = jnp.float32(2.0 ** 30)
    at_max_f = ok_f & (gains_f == best_gain_f[:, None])
    best_t_f = jnp.min(jnp.where(at_max_f, wrow, big), axis=1)

    # ---- combine directions (forward wins only on strictly more gain) ---
    has_r = best_t_r >= jnp.float32(0.0)
    has_f = best_t_f < big
    best_gain_r = jnp.where(has_r, best_gain_r, NEG_INF)
    best_gain_f = jnp.where(has_f, best_gain_f, NEG_INF)
    use_f = best_gain_f > best_gain_r
    feat_gain = jnp.where(use_f, best_gain_f, best_gain_r)
    feat_t = jnp.where(use_f, best_t_f, best_t_r)
    has_any = has_r | has_f

    # left sums at the chosen threshold (masked lane reduction)
    sel = (wrow == feat_t[:, None]).astype(jnp.float32)
    lg_f = jnp.sum(gl_c * sel, axis=1)
    lh_f = jnp.sum(hl_c * sel, axis=1)
    lc_f = jnp.sum(cl_c * sel, axis=1)
    lg_r = sg - (gr_tot[:, 0] - jnp.sum(gr_c * sel, axis=1))
    lh_r = sh - (hr_tot[:, 0] - jnp.sum(hr_c * sel, axis=1))
    lc_r = nd - (cr_tot[:, 0] - jnp.sum(cr_c * sel, axis=1))
    lg = jnp.where(use_f, lg_f, lg_r)
    lh = jnp.where(use_f, lh_f, lh_r)
    lc = jnp.where(use_f, lc_f, lc_r)

    gain_out = jnp.where(has_any,
                         (feat_gain - min_gain_shift) * pen, NEG_INF)

    out_ref[0, 0, :] = gain_out
    out_ref[0, 1, :] = feat_t
    out_ref[0, 2, :] = use_f.astype(jnp.float32)
    out_ref[0, 3, :] = lg
    out_ref[0, 4, :] = lh
    out_ref[0, 5, :] = lc
    out_ref[0, 6, :] = has_any.astype(jnp.float32)
    out_ref[0, 7, :] = jnp.zeros((F,), jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def scan_pair(scal, gb, hb, keep_r, keep_f, valid_r, valid_f, aux,
              interpret: bool = False):
    """Run the fused scan for a batch of children (one grid step each).

    Historically the batch was exactly the (left, right) pair of one
    split; the level-parallel grower feeds ALL frontier children of a
    tree level at once — the kernel body is per-child either way, so the
    batch size is simply the leading dim B.

    scal: [B, 8] f32; gb/hb: [B, Fp, Wp] f32; valid masks: [Fp, Wp] f32
    shared, or [B, Fp, Wp] per child (the voting-parallel win masks);
    keep masks: [Fp, Wp] f32; aux: [8, Fp] f32 (row 0 = penalty).
    Returns [B, 8, Fp] f32.
    """
    B, Fp, Wp = gb.shape
    if valid_r.ndim == 2:
        valid_r = jnp.broadcast_to(valid_r, (B, Fp, Wp))
    if valid_f.ndim == 2:
        valid_f = jnp.broadcast_to(valid_f, (B, Fp, Wp))
    scal = jnp.zeros((B, 1, 128), jnp.float32).at[:, 0, :8].set(scal)
    _vmem = scan_pair_vmem_bytes(Fp, Wp)
    return pl.pallas_call(
        _scan_kernel,
        compiler_params=CompilerParams(vmem_limit_bytes=_vmem),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, 1, 128), lambda c: (c, c * 0, c * 0)),
            pl.BlockSpec((1, Fp, Wp), lambda c: (c, c * 0, c * 0)),
            pl.BlockSpec((1, Fp, Wp), lambda c: (c, c * 0, c * 0)),
            pl.BlockSpec((Fp, Wp), lambda c: (c * 0, c * 0)),
            pl.BlockSpec((Fp, Wp), lambda c: (c * 0, c * 0)),
            pl.BlockSpec((1, Fp, Wp), lambda c: (c, c * 0, c * 0)),
            pl.BlockSpec((1, Fp, Wp), lambda c: (c, c * 0, c * 0)),
            pl.BlockSpec((8, Fp), lambda c: (c * 0, c * 0)),
        ],
        out_specs=pl.BlockSpec((1, 8, Fp), lambda c: (c, c * 0, c * 0)),
        out_shape=jax.ShapeDtypeStruct((B, 8, Fp), jnp.float32),
        interpret=interpret,
    )(scal, gb, hb, keep_r, keep_f, valid_r, valid_f, aux)


# ---------------------------------------------------------------------------
# bundle-native block scan
# ---------------------------------------------------------------------------
#
# For EFB-bundled datasets the per-feature formulation above is wasteful:
# every bundled feature's row holds a COPY of its whole [W] group block
# (Expo: 700 feature rows from 16 groups — a 44x duplication re-gathered
# per split). The block kernel below scans the [G, W] group planes
# DIRECTLY: each lane belongs to exactly one feature's bin window, the six
# cumulative sums run per group block, and per-lane window quantities
# (windowed prefix, window total) are recovered with segmented fills —
# log2(W) stages of static lane rolls seeded at the (static) window
# boundary lanes. The FixHistogram repair for bundled features
# (src/io/dataset.cpp:1410) also moves INSIDE the kernel: the residual
# child_total - window_sum lands on each needs-fix feature's most_freq
# lane before any cumsum reads it, so the caller no longer materializes
# [2, F, W] fix tensors per split.
#
# Tie-break note: within a feature the threshold choice is identical to the
# per-feature kernel (REVERSE keeps the highest lane = highest threshold,
# forward the lowest). ACROSS features the per-group argmax compares
# penalized gains lane-wise, so an exact cross-feature gain tie resolves by
# lane position inside the block instead of by smaller feature index — an
# f32-exact-tie corner the fast path accepts (the v1/XLA paths keep the
# reference order).


def _fill_fwd(v, has, W: int):
    """Per-lane value of the NEAREST seed at-or-before the lane.

    v: [R, W] f32, zero off-seed; has: [R, W] f32 0/1 seed mask. Hillis-
    Steele doubling of the 'rightmost defined' operator — log2(W) static
    rolls, associative, so every lane converges to its closest seed."""
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    n = 0
    while (1 << n) < W:
        n += 1
    for b in range(n):
        sh = 1 << b
        v2 = pltpu.roll(v, sh, 1)
        h2 = pltpu.roll(has, sh, 1)
        take = (lane >= sh) & (has < jnp.float32(0.5)) & (h2 > jnp.float32(0.5))
        v = jnp.where(take, v2, v)
        has = jnp.where(take, 1.0, has)
    return v


def _fill_bwd(v, has, W: int):
    """Nearest seed at-or-after each lane (the backward _fill_fwd)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    n = 0
    while (1 << n) < W:
        n += 1
    for b in range(n):
        sh = 1 << b
        v2 = pltpu.roll(v, W - sh, 1)
        h2 = pltpu.roll(has, W - sh, 1)
        take = (lane < W - sh) & (has < jnp.float32(0.5)) & (h2 > jnp.float32(0.5))
        v = jnp.where(take, v2, v)
        has = jnp.where(take, 1.0, has)
    return v


# rows of the static mask stack consumed by _scan_blocks_kernel
(BM_KEEP_R, BM_KEEP_F, BM_VALID_R, BM_VALID_F,
 BM_SEED_S, BM_SEED_E, BM_FIX, BM_PEN) = range(8)
BM_ROWS = 8


def _scan_blocks_kernel(do_fix, scal_ref, gb_ref, hb_ref, mk_ref, out_ref):
    """One grid step = one child, scanning [G, W] group blocks.

    scal_ref: [1, 1, 128] f32 (sum_grad, sum_hess(+eps), num_data,
              cnt_factor, min_data, min_hess, min_gain_shift, lambda_l2,
              sum_hess_raw, 0...)
    gb/hb:    [1, G, W] f32 per-GROUP bin grad/hess planes
    mk_ref:   [8, G, W] f32 static per-lane masks (BM_* rows): cumsum
              keeps, positional validity (feature mask folded per tree),
              window start / end-1 seeds, fix-target lanes, penalty
    out_ref:  [1, 8, G] f32 per-group (gain, t_abs, use_f, lg, lh, lc,
              has, pad) — t_abs is the ABSOLUTE block lane; the caller
              recovers the feature from the owner map and subtracts its
              window offset
    """
    G, W = mk_ref.shape[1], mk_ref.shape[2]
    sg = scal_ref[0, 0, 0]
    sh = scal_ref[0, 0, 1]
    nd = scal_ref[0, 0, 2]
    cf = scal_ref[0, 0, 3]
    min_data = scal_ref[0, 0, 4]
    min_hess = scal_ref[0, 0, 5]
    min_gain_shift = scal_ref[0, 0, 6]
    l2 = scal_ref[0, 0, 7]
    sh_raw = scal_ref[0, 0, 8]

    gb = gb_ref[0]
    hb = hb_ref[0]
    keep_r = mk_ref[BM_KEEP_R]
    keep_f = mk_ref[BM_KEEP_F]
    valid_r = mk_ref[BM_VALID_R]
    valid_f = mk_ref[BM_VALID_F]
    seed_s = mk_ref[BM_SEED_S]
    seed_e = mk_ref[BM_SEED_E]
    pen = mk_ref[BM_PEN]

    iw = jax.lax.broadcasted_iota(jnp.int32, (W, W), 0)
    jw = jax.lax.broadcasted_iota(jnp.int32, (W, W), 1)
    tri = (iw >= jw).astype(jnp.float32)
    dn = (((1,), (1,)), ((), ()))

    def cumsum(x):
        return jax.lax.dot_general(x, tri, dn,
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)

    if do_fix:
        # FixHistogram in place: each needs-fix feature's most_freq lane
        # receives child_total - window_sum BEFORE any cumsum reads it
        fixm = mk_ref[BM_FIX]
        raw = jnp.concatenate([gb, hb], axis=0)              # [2G, W]
        cum = cumsum(raw)
        ecum = cum - raw
        ss2 = jnp.concatenate([seed_s, seed_s], axis=0)
        se2 = jnp.concatenate([seed_e, seed_e], axis=0)
        cs = _fill_fwd(ecum * ss2, ss2, W)                   # cum at ws-1
        ce = _fill_bwd(cum * se2, se2, W)                    # cum at we-1
        wsum = ce - cs
        tgt = jnp.concatenate([jnp.zeros_like(gb) + sg,
                               jnp.zeros_like(hb) + sh_raw], axis=0)
        res = (tgt - wsum) * jnp.concatenate([fixm, fixm], axis=0)
        gb = gb + res[:G]
        hb = hb + res[G:]

    cnt_b = jnp.floor(hb * cf + jnp.float32(0.5))
    stack = jnp.concatenate([gb * keep_r, hb * keep_r, cnt_b * keep_r,
                             gb * keep_f, hb * keep_f, cnt_b * keep_f],
                            axis=0)                          # [6G, W]
    cums = cumsum(stack)

    # ---- REVERSE: r_x(lane) = window_total_x - windowed_cum_x(lane)
    #             = cum_x(we-1) - cum_x(lane)  (per-lane end fill) --------
    cr = cums[:3 * G]
    se3 = jnp.concatenate([seed_e, seed_e, seed_e], axis=0)
    ce3 = _fill_bwd(cr * se3, se3, W)
    r_grad = ce3[:G] - cr[:G]
    r_hess = ce3[G:2 * G] - cr[G:2 * G]
    r_cnt = ce3[2 * G:] - cr[2 * G:]
    l_cnt = nd - r_cnt
    l_grad = sg - r_grad
    l_hess = sh - r_hess

    ok_r = (valid_r > jnp.float32(0.0)) \
        & (r_cnt >= min_data) & (r_hess >= min_hess) \
        & (l_cnt >= min_data) & (l_hess >= min_hess)
    gains_r = (l_grad * l_grad) / (l_hess + l2) \
        + (r_grad * r_grad) / (r_hess + l2)
    ok_r &= gains_r > min_gain_shift
    # penalized per-lane gains: constant within a feature's window (so
    # threshold/direction choices match the per-feature kernel) and the
    # cross-feature comparison quantity everywhere else
    pg_r = jnp.where(ok_r, (gains_r - min_gain_shift) * pen, NEG_INF)

    wrow = jax.lax.broadcasted_iota(jnp.int32, (G, W), 1).astype(jnp.float32)
    best_gain_r = jnp.max(pg_r, axis=1)                      # [G]
    at_max_r = ok_r & (pg_r == best_gain_r[:, None])
    best_t_r = jnp.max(jnp.where(at_max_r, wrow, -1.0), axis=1)

    # ---- forward: windowed cum = cum - ecum(ws) (per-lane start fill) ---
    cfw = cums[3 * G:]
    sfw = stack[3 * G:]
    ss3 = jnp.concatenate([seed_s, seed_s, seed_s], axis=0)
    ecw = cfw - sfw
    cs3 = _fill_fwd(ecw * ss3, ss3, W)
    f_l_grad = cfw[:G] - cs3[:G]
    f_l_hess = cfw[G:2 * G] - cs3[G:2 * G]
    f_l_cnt = cfw[2 * G:] - cs3[2 * G:]
    f_r_cnt = nd - f_l_cnt
    f_r_grad = sg - f_l_grad
    f_r_hess = sh - f_l_hess

    ok_f = (valid_f > jnp.float32(0.0)) \
        & (f_l_cnt >= min_data) & (f_l_hess >= min_hess) \
        & (f_r_cnt >= min_data) & (f_r_hess >= min_hess)
    gains_f = (f_l_grad * f_l_grad) / (f_l_hess + l2) \
        + (f_r_grad * f_r_grad) / (f_r_hess + l2)
    ok_f &= gains_f > min_gain_shift
    pg_f = jnp.where(ok_f, (gains_f - min_gain_shift) * pen, NEG_INF)

    best_gain_f = jnp.max(pg_f, axis=1)
    big = jnp.float32(2.0 ** 30)
    at_max_f = ok_f & (pg_f == best_gain_f[:, None])
    best_t_f = jnp.min(jnp.where(at_max_f, wrow, big), axis=1)

    # ---- combine (forward wins only on strictly more penalized gain) ----
    has_r = best_t_r >= jnp.float32(0.0)
    has_f = best_t_f < big
    bg_r = jnp.where(has_r, best_gain_r, NEG_INF)
    bg_f = jnp.where(has_f, best_gain_f, NEG_INF)
    use_f = bg_f > bg_r
    group_gain = jnp.where(use_f, bg_f, bg_r)
    group_t = jnp.where(use_f, best_t_f, best_t_r)
    has_any = has_r | has_f

    sel = (wrow == group_t[:, None]).astype(jnp.float32)
    lg = jnp.where(use_f, jnp.sum(f_l_grad * sel, axis=1),
                   jnp.sum(l_grad * sel, axis=1))
    lh = jnp.where(use_f, jnp.sum(f_l_hess * sel, axis=1),
                   jnp.sum(l_hess * sel, axis=1))
    lc = jnp.where(use_f, jnp.sum(f_l_cnt * sel, axis=1),
                   jnp.sum(l_cnt * sel, axis=1))

    out_ref[0, 0, :] = jnp.where(has_any, group_gain, NEG_INF)
    out_ref[0, 1, :] = group_t
    out_ref[0, 2, :] = use_f.astype(jnp.float32)
    out_ref[0, 3, :] = lg
    out_ref[0, 4, :] = lh
    out_ref[0, 5, :] = lc
    out_ref[0, 6, :] = has_any.astype(jnp.float32)
    out_ref[0, 7, :] = jnp.zeros((G,), jnp.float32)


@functools.partial(jax.jit, static_argnames=("do_fix", "interpret"))
def scan_blocks(scal, gb, hb, masks, do_fix: bool = False,
                interpret: bool = False):
    """Fused bundle-native scan for a BATCH of children over [G, W]
    group planes (one grid step per child — historically the (left,
    right) pair of one split; the level-parallel grower feeds every
    frontier child of a tree level in one call).

    scal: [B, 9] f32 (scan_pair's 8 scalars + the raw hessian sum for the
    in-kernel fix residual); gb/hb: [B, Gp, Wp] f32 group-block planes;
    masks: [8, Gp, Wp] f32 static stack (BM_* rows) with the per-tree
    feature mask already folded into the valid rows.
    Returns [B, 8, Gp] f32 per-group results (t in ABSOLUTE block lanes).
    """
    B, Gp, Wp = gb.shape
    scal_p = jnp.zeros((B, 1, 128), jnp.float32).at[:, 0, :9].set(
        scal.astype(jnp.float32))
    _vmem = scan_blocks_vmem_bytes(Gp, Wp)
    kern = functools.partial(_scan_blocks_kernel, do_fix)
    # trace with 32-bit default dtypes (as ops/pallas_grow does): under
    # jax_enable_x64 the static lane-roll shifts of _fill_fwd/_fill_bwd
    # trace as i64, which Mosaic's rotate refuses
    with enable_x64(False):
        return pl.pallas_call(
            kern,
            compiler_params=CompilerParams(vmem_limit_bytes=_vmem),
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, 1, 128), lambda c: (c, c * 0, c * 0)),
                pl.BlockSpec((1, Gp, Wp), lambda c: (c, c * 0, c * 0)),
                pl.BlockSpec((1, Gp, Wp), lambda c: (c, c * 0, c * 0)),
                pl.BlockSpec((BM_ROWS, Gp, Wp),
                             lambda c: (c * 0, c * 0, c * 0)),
            ],
            out_specs=pl.BlockSpec((1, 8, Gp),
                                   lambda c: (c, c * 0, c * 0)),
            out_shape=jax.ShapeDtypeStruct((B, 8, Gp), jnp.float32),
            interpret=interpret,
        )(scal_p, gb, hb, masks)


def build_block_scan_meta(group_of, ls, nb, mt, db, mf, needs_fix,
                          penalty, G: int, W: int = 256):
    """Static per-lane mask stack for :func:`scan_blocks` (host numpy).

    Derived ONCE per payload geometry and cached across levels and trees
    (the per-feature ScanLayout re-derives its masks per tree; these are
    tree-invariant — only the feature-mask fold is per-tree). All inputs
    are host arrays in FEATURE order; `group_of`/`ls`/`nb` place feature
    f's bins at lanes [ls, ls+nb) of block group_of[f].

    Returns dict with:
      masks     [BM_ROWS, Gp, Wp] f32 — the kernel's static stack
      owner     [Gp, Wp] i32 — owning feature per lane (-1 = none)
      has_owner [Gp, Wp] bool
    """
    import numpy as np
    Gp = _round_up(max(G, 8), 8)
    Wp = _round_up(max(W, 128), 128)
    owner = np.full((Gp, Wp), -1, dtype=np.int32)
    F = len(group_of)
    for f in range(F):
        owner[group_of[f], ls[f]:ls[f] + nb[f]] = f
    has_owner = owner >= 0
    o = np.where(has_owner, owner, 0)
    lane = np.arange(Wp, dtype=np.int64)[None, :]
    w_loc = lane - ls[o]
    nb_l = nb[o]
    mt_l = mt[o]
    db_l = db[o]

    two_scan = (nb_l > 2) & (mt_l != 0)
    skip_default = two_scan & (mt_l == 1)
    na_as_missing = two_scan & (mt_l == 2)
    is_na_bin = w_loc == nb_l - 1
    is_default_bin = w_loc == db_l

    excl_r = (na_as_missing & is_na_bin) | (skip_default & is_default_bin)
    excl_f = skip_default & is_default_bin
    keep_r = has_owner & ~excl_r
    keep_f = has_owner & ~excl_f

    valid_r = has_owner & (w_loc <= nb_l - 2 - na_as_missing.astype(np.int64))
    valid_r &= ~(skip_default & (w_loc == db_l - 1))
    valid_f = two_scan & has_owner & (w_loc <= nb_l - 2)
    valid_f &= ~(skip_default & is_default_bin)

    seed_s = has_owner & (w_loc == 0)
    seed_e = has_owner & is_na_bin          # w_loc == nb-1: window end
    fixm = has_owner & needs_fix[o] & (w_loc == mf[o])
    pen_l = np.where(has_owner, penalty[o], 0.0)

    masks = np.zeros((BM_ROWS, Gp, Wp), np.float32)
    masks[BM_KEEP_R] = keep_r
    masks[BM_KEEP_F] = keep_f
    masks[BM_VALID_R] = valid_r
    masks[BM_VALID_F] = valid_f
    masks[BM_SEED_S] = seed_s
    masks[BM_SEED_E] = seed_e
    masks[BM_FIX] = fixm
    masks[BM_PEN] = pen_l
    return {"masks": masks, "owner": owner, "has_owner": has_owner}


class ScanLayout:
    """Per-tree precomputed dense layout + masks for the fused scan.

    Built ONCE per tree (inside jit; ~15 ops) from FeatureMeta + the tree's
    feature mask; every split then pays only the gather + kernel + a tiny
    assembly. Mirrors the mask derivations in
    ops/split.find_best_split_numerical.
    """

    def __init__(self, meta, feature_mask, F: int, W: int, tb: int,
                 win_off=None):
        I32 = jnp.int32
        self.F = F
        self.W = W
        self.Fp = _round_up(max(F, 8), 8)
        self.Wp = _round_up(max(W, 128), 128)
        Fp, Wp = self.Fp, self.Wp

        pad_f = Fp - F
        start = jnp.pad(meta.bin_start, (0, pad_f))[:, None]
        nb = jnp.pad(meta.bin_end - meta.bin_start, (0, pad_f))[:, None]
        mt = jnp.pad(meta.missing_type, (0, pad_f))[:, None]
        d_local = jnp.pad(meta.default_bin, (0, pad_f))[:, None]
        fmask = jnp.pad(feature_mask & ~meta.is_categorical, (0, pad_f))
        pen = jnp.pad(meta.penalty.astype(jnp.float32), (0, pad_f))

        w = jnp.arange(Wp, dtype=I32)[None, :]
        if win_off is not None:
            # feature f's window starts at lane win_off[f] of its row
            # (EFB rows hold whole group blocks; the scan masks shift and
            # thresholds come out ABSOLUTE — callers subtract win_off).
            # Lanes before the offset have every mask zero, so the
            # bidirectional accumulations see only the window. gidx has
            # no meaning for block-row layouts — None so misuse is loud.
            w = w - jnp.pad(win_off, (0, pad_f))[:, None]
            self.gidx = None
        else:
            self.gidx = jnp.clip(
                start + jnp.arange(Wp, dtype=I32)[None, :],
                0, tb - 1)                                   # [Fp, Wp]
        in_feat = (w >= 0) & (w < nb)

        two_scan = (nb > 2) & (mt != 0)
        skip_default = two_scan & (mt == 1)
        na_as_missing = two_scan & (mt == 2)
        is_na_bin = w == (nb - 1)
        is_default_bin = w == d_local

        excl_r = (na_as_missing & is_na_bin) | (skip_default & is_default_bin)
        excl_f = skip_default & is_default_bin
        keep_r = (in_feat & ~excl_r)
        keep_f = (in_feat & ~excl_f)

        valid_r = in_feat & (w <= nb - 2 - na_as_missing.astype(I32))
        valid_r &= ~(skip_default & (w == d_local - 1))
        valid_r &= fmask[:, None]
        valid_f = two_scan & in_feat & (w <= nb - 2)
        valid_f &= ~(skip_default & is_default_bin)
        valid_f &= fmask[:, None]

        self.keep_r = keep_r.astype(jnp.float32)
        self.keep_f = keep_f.astype(jnp.float32)
        self.valid_r = valid_r.astype(jnp.float32)
        self.valid_f = valid_f.astype(jnp.float32)
        self.aux = jnp.zeros((8, Fp), jnp.float32).at[0].set(pen)
        self.forced_right = jnp.pad(
            (meta.missing_type == 2) & ((meta.bin_end - meta.bin_start) <= 2),
            (0, pad_f))
