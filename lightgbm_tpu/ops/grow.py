"""Leaf-wise (best-first) tree growing as a single jitted device loop.

TPU-native equivalent of SerialTreeLearner::Train
(src/treelearner/serial_tree_learner.cpp:149-196): repeat {pick leaf with max
cached split gain -> partition its rows -> build smaller-child histogram ->
larger child = parent - smaller (the subtraction trick, :290-298,:380-388) ->
scan both children for their best splits} until num_leaves-1 splits or no
positive gain.

Key TPU design decisions (vs the reference's pointer-chasing structures):
  * two row-management strategies: grow_tree (small data) keeps a flat [N]
    leaf-id vector and masks — no reordering, O(N) per split; grow_tree_
    partitioned (large data) keeps the row PAYLOADS physically leaf-sorted
    (the OrderedBin/DataPartition analog, src/io/bin.h:229 +
    src/treelearner/data_partition.hpp:21) so every pass is a contiguous
    slice — TPU gathers run on the scalar path and would dominate;
  * per-leaf histograms live in one [num_leaves, total_bins, 2] HBM tensor
    (replacing HistogramPool, feature_histogram.hpp:960) updated with
    dynamic_update_slice inside a lax.while_loop;
  * the loop body is BRANCH-FREE: instead of lax.cond around the split, every
    state update is masked by a `do` predicate. A cond keeps both the old and
    new leaf-histogram tensors alive, forcing XLA to copy the full [L, TB, 2]
    buffer every iteration (~2x14MB per split at 255 leaves); masked
    dynamic-update-slices keep the updates in place;
  * the partition decision reproduces DenseBin::Split semantics
    (src/io/dense_bin.hpp:112-207): missing NaN bin / zero bin travel in the
    default_left direction, everything else compares local_bin <= threshold;
    rows whose bundled (EFB) group value belongs to another feature fall back
    to this feature's most_freq_bin;
  * monotone constraint propagation follows
    src/treelearner/monotone_constraints.hpp:15-64 (children inherit the
    parent's range; the split midpoint tightens one side);
  * gc.use_dp selects f64 vs f32 leaf/gain state (f32 is the TPU default,
    mirroring the reference GPU learner's gpu_use_dp=false).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..telemetry import events as telemetry
from ..telemetry.devices import on_tpu
from .quantize import plane_psum, quant_tag, vote_allgather
from .split import (CatLayout, F64, I32, K_EPSILON, K_MIN_SCORE, FeatureMeta,
                    SplitCandidate, SplitParams, _leaf_gain,
                    _leaf_output_unconstrained, acc_dtype,
                    find_best_split_categorical, find_best_split_numerical,
                    fix_histogram, merge_candidates)


def empty_cat_layout(cat_width: int = 1) -> CatLayout:
    z = jnp.zeros((0,), I32)
    return CatLayout(cat_feature=z,
                     gather_idx=jnp.zeros((0, cat_width), I32),
                     bin_valid=jnp.zeros((0, cat_width), bool),
                     used_bin=z, num_bin=z)

BOOL = jnp.bool_


class GrowConfig(NamedTuple):
    """Static knobs that shape the compiled program."""
    num_leaves: int
    total_bins: int
    num_features: int
    use_mc: bool
    max_depth: int          # <=0: unlimited
    rows_per_chunk: int     # histogram chunking; 0 = one shot
    cat_width: int          # width of categorical bitmask (1 if no cat feats)
    hist_impl: str = "scatter"   # "scatter" (CPU) | "onehot" (XLA einsum)
    #                            # | "pallas" (VMEM one-hot MXU kernel)
    scan_width: int = 0     # dense scan width (0 = min(total_bins, 256))
    use_dp: bool = True     # f64 (CPU default) vs f32 (TPU default) math
    window_chunk: int = 2048  # streaming chunk of the partitioned grower
    use_l1: bool = True     # lambda_l1 > 0 (USE_L1 template analog)
    use_mds: bool = True    # max_delta_step > 0 (USE_MAX_OUTPUT analog)
    hist_dtype: str = "f32"  # "f32" | "bf16x2" (hi/lo split bf16 MXU)
    extra_trees: bool = False   # USE_RAND: one random threshold per feature
    bynode_k: int = 0           # >0: feature_fraction_bynode sample size
    use_cegb: bool = False      # CEGB split/coupled gain penalties
    use_cegb_lazy: bool = False  # CEGB per-row lazy feature penalty
    #                            # (masked grower only; [N, F] bookkeeping)
    parallel_mode: str = "data"  # "data" | "feature" | "voting" (see
    #                            # parallel/learners.py for the mapping to
    #                            # the reference's three learners)
    top_k: int = 20              # voting-parallel per-shard vote size
    scan_impl: str = "xla"       # "xla" | "pallas" fused split-scan kernel
    #                            # (fast path only; resolve_scan_impl gates)
    packed_4bit: bool = False    # layout.bins nibble-packs <=16-bin groups
    n_forced: int = 0            # forcedsplits_filename node count
    multival: bool = False       # layout is ELL row-sparse (masked grower)
    max_cat_threshold: int = 32  # bound of the sorted categorical scan


class GrowExtras(NamedTuple):
    """Per-tree inputs for the optional split policies (zeros when off)."""
    key: jnp.ndarray            # [2] u32 PRNG key (extra_trees / bynode)
    cegb_coupled: jnp.ndarray   # [F] f64 per-feature coupled penalty
    cegb_split_pen: jnp.ndarray  # scalar f64 penalty_split
    cegb_tradeoff: jnp.ndarray   # scalar f64
    cegb_lazy: jnp.ndarray       # [F] f64 per-feature lazy (on-demand)
    #                            # penalty charged per row that has not yet
    #                            # seen the feature used on its path
    feature_used: jnp.ndarray    # [F] bool: features already split on in
    #                            # EARLIER trees (CEGB coupled penalty is
    #                            # charged once per model, not per tree —
    #                            # is_feature_used_in_split_ lives on the
    #                            # learner in the reference)


def default_extras(num_features: int) -> GrowExtras:
    return GrowExtras(
        key=jnp.zeros((2,), jnp.uint32),
        cegb_coupled=jnp.zeros((max(num_features, 1),), F64),
        cegb_split_pen=jnp.asarray(0.0, F64),
        cegb_tradeoff=jnp.asarray(1.0, F64),
        cegb_lazy=jnp.zeros((max(num_features, 1),), F64),
        feature_used=jnp.zeros((max(num_features, 1),), jnp.bool_))


class FixInfo(NamedTuple):
    """Bundled-feature histogram repair indices (empty when no EFB bundles)."""
    mf_global: jnp.ndarray   # [K] i32 global bin of each bundled feature's most_freq
    start: jnp.ndarray       # [K] i32 feature global bin range start
    end: jnp.ndarray         # [K] i32 exclusive end


class DataLayout(NamedTuple):
    """Device-resident binned dataset layout (built once by Dataset).

    When gc.packed_4bit is set, `bins` holds STORAGE columns where pairs of
    <=16-bin logical groups share one byte (the Dense4bitsBin analog,
    src/io/dense_nbits_bin.hpp — half the HBM footprint/bandwidth for
    narrow-feature datasets); unpack_col/unpack_shift map each LOGICAL
    group to (storage column, nibble shift). Without packing they are the
    identity and unused.
    """
    bins: jnp.ndarray           # [N, G_storage] uint8/16/32 bins
    group_offset: jnp.ndarray   # [G_logical] i32 global bin offset per group
    group_of: jnp.ndarray       # [F] i32 feature -> logical group
    most_freq_bin: jnp.ndarray  # [F] i32 local most_freq bin (EFB fallback)
    unpack_col: jnp.ndarray = None    # [G_logical] i32 storage column
    unpack_shift: jnp.ndarray = None  # [G_logical] i32 shift (0 or 4)
    unpack_mask: jnp.ndarray = None   # [G_logical] i32 (15 packed, else wide)
    # multi-value (ELL) row-sparse storage — the MultiValBin/SparseBin
    # analog (ref src/io/multi_val_sparse_bin.hpp, sparse_bin.hpp): when
    # gc.multival is set, `bins` is an empty placeholder and each row
    # stores up to K (group, local bin) pairs for the groups whose bin
    # differs from that group's default; every feature's default-bin mass
    # is reconstructed from leaf totals by ops.split.fix_histogram.
    ell_grp: jnp.ndarray = None       # [N, K] i32 logical group (G = pad)
    ell_bin: jnp.ndarray = None       # [N, K] i32 group-local bin
    group_default: jnp.ndarray = None  # [G] i32 omitted bin per group (the
    #                                  # single feature's most_freq, or the
    #                                  # 0 sentinel for EFB bundles)


def _logical_bins(bw, layout: DataLayout, packed: bool):
    """[rows, G_storage] storage window -> [rows, G_logical] i32 bins."""
    if not packed:
        return bw.astype(I32)
    u = jnp.take(bw.astype(I32), layout.unpack_col, axis=1)
    return (u >> layout.unpack_shift[None, :]) & layout.unpack_mask[None, :]


def _logical_col(bins, g, layout: DataLayout, packed: bool):
    """One logical group's [rows] column from the storage matrix."""
    if not packed:
        return bins[:, g].astype(I32)
    sc = layout.unpack_col[g]
    return ((bins[:, sc].astype(I32) >> layout.unpack_shift[g])
            & layout.unpack_mask[g])


class TreeArrays(NamedTuple):
    """Split records + leaf state: everything the host needs to build a Tree."""
    num_leaves: jnp.ndarray     # scalar i32 (final)
    split_leaf: jnp.ndarray     # [L-1] i32 leaf index that was split
    split_feature: jnp.ndarray  # [L-1] i32 inner feature index
    threshold: jnp.ndarray      # [L-1] i32 local bin threshold
    default_left: jnp.ndarray   # [L-1] bool
    gain: jnp.ndarray           # [L-1] ft
    is_cat: jnp.ndarray         # [L-1] bool
    cat_mask: jnp.ndarray       # [L-1, CAT_W] bool
    internal_value: jnp.ndarray  # [L-1] ft (parent leaf output at split time)
    internal_count: jnp.ndarray  # [L-1] i32
    leaf_value: jnp.ndarray     # [L] ft
    leaf_count: jnp.ndarray     # [L] i32
    leaf_weight: jnp.ndarray    # [L] ft (sum_hessian)
    row_leaf: jnp.ndarray       # [N] i32 final leaf id per row


class _LoopState(NamedTuple):
    s: jnp.ndarray              # next split index (== current num_leaves)
    done: jnp.ndarray           # bool
    fidx: jnp.ndarray           # i32 next forced-split index
    row_leaf: jnp.ndarray       # [N] i32
    leaf_hist: jnp.ndarray      # [L, TB, 2] f32
    leaf_sum_grad: jnp.ndarray  # [L] ft
    leaf_sum_hess: jnp.ndarray  # [L] ft
    leaf_count: jnp.ndarray     # [L] i32 (in-bag rows)
    leaf_value: jnp.ndarray     # [L] ft
    leaf_depth: jnp.ndarray     # [L] i32
    leaf_cmin: jnp.ndarray      # [L] ft monotone lower bound
    leaf_cmax: jnp.ndarray      # [L] ft monotone upper bound
    feature_used: jnp.ndarray   # [F] bool (CEGB coupled-penalty bookkeeping)
    row_feat_used: jnp.ndarray  # [N, F] bool CEGB lazy bookkeeping
    #                           # (feature_used_in_data_ bitset analog;
    #                           # [0, 0] when gc.use_cegb_lazy is off)
    best: SplitCandidate        # [L] pytree of per-leaf best splits
    tree: TreeArrays


def hist_ft(gc: "GrowConfig"):
    """Histogram ACCUMULATION dtype: f64 bins when hist_dtype says so
    (the CPU default — the reference CPU learner's double hist_t), f32
    otherwise (the accelerator gpu_use_dp=false trade). f64 sums of f32
    per-row gradients are exact at histogram scales, so f64 bins are
    summation-order-independent — which is what lets two different
    growers (v1 and the widened persist emulation) agree bit for bit."""
    return jnp.float64 if gc.hist_dtype == "f64" else jnp.float32


def _hist_masked(layout: DataLayout, grad, hess, mask, total_bins,
                 rows_per_chunk, packed: bool, axis_name=None,
                 multival: bool = False, dtype=jnp.float32):
    from .histogram import build_histogram
    m = mask.astype(grad.dtype)
    if multival:
        # row-sparse scatter (ConstructHistogramsMultiVal analog,
        # src/io/dataset.cpp:1198): K entries per row, padding entries
        # land in a scratch bin that is sliced away
        g = layout.ell_grp
        pad = g >= layout.group_offset.shape[0]
        gsafe = jnp.where(pad, 0, g)
        idx = jnp.where(pad, total_bins,
                        layout.group_offset[gsafe] + layout.ell_bin)
        h = build_histogram(idx, grad * m, hess * m,
                            total_bins=total_bins + 1,
                            rows_per_chunk=rows_per_chunk,
                            dtype=dtype)[:total_bins]
    else:
        idx = (_logical_bins(layout.bins, layout, packed)
               + layout.group_offset[None, :])
        h = build_histogram(idx, grad * m, hess * m, total_bins=total_bins,
                            rows_per_chunk=rows_per_chunk, dtype=dtype)
    if axis_name is not None:
        h = jax.lax.psum(h, axis_name)
    return h


def _multival_col(layout: DataLayout, g):
    """One logical group's [rows] local-bin column from the ELL storage:
    rows without an entry for group g sit at the group's default bin."""
    match = layout.ell_grp == g
    found = jnp.any(match, axis=1)
    raw = jnp.sum(jnp.where(match, layout.ell_bin, 0), axis=1)
    return jnp.where(found, raw, layout.group_default[g]).astype(I32)


def _root_candidate_dummy(cat_width: int, ft) -> SplitCandidate:
    z = jnp.asarray(0.0, ft)
    return SplitCandidate(
        gain=jnp.asarray(K_MIN_SCORE, ft), feature=jnp.asarray(-1, I32),
        threshold=jnp.asarray(0, I32), default_left=jnp.asarray(True),
        left_output=z, right_output=z, left_sum_grad=z,
        left_sum_hess=z, right_sum_grad=z, right_sum_hess=z,
        left_count=jnp.asarray(0, I32), right_count=jnp.asarray(0, I32),
        is_cat=jnp.asarray(False), cat_mask=jnp.zeros((cat_width,), BOOL))


def _go_left_decision(local_bin, in_range, feat_meta_row, cand, cat_width):
    """DenseBin::Split decision at the logical-bin level (dense_bin.hpp:112)."""
    nb, missing_type, default_bin, most_freq = feat_meta_row
    b = jnp.where(in_range, local_bin, most_freq)
    cmp_left = b <= cand.threshold
    is_na = (missing_type == 2) & (b == nb - 1)
    is_zero = (missing_type == 1) & (b == default_bin)
    go_default = is_na | is_zero
    num_left = jnp.where(go_default, cand.default_left, cmp_left)
    if cat_width > 1:
        bc = jnp.clip(b, 0, cat_width - 1)
        cat_left = cand.cat_mask[bc] & (b < cat_width)
        return jnp.where(cand.is_cat, cat_left, num_left)
    return num_left


def _single_leaf_tree(n, L, cat_width, grad, hess, bag_mask, params, axis_name,
                      ft):
    def psum(x):
        return jax.lax.psum(x, axis_name) if axis_name is not None else x
    sum_grad = psum(jnp.sum(grad.astype(jnp.float32), dtype=ft))
    sum_hess = psum(jnp.sum(hess.astype(jnp.float32), dtype=ft))
    count = psum(jnp.sum(bag_mask, dtype=I32))
    params = params.cast(ft)
    root_out = _leaf_output_unconstrained(
        sum_grad, sum_hess, params.lambda_l1, params.lambda_l2,
        params.max_delta_step)   # generic flags: one-off, not hot
    return TreeArrays(
        num_leaves=jnp.asarray(1, I32),
        split_leaf=jnp.zeros((L - 1,), I32),
        split_feature=jnp.full((L - 1,), -1, I32),
        threshold=jnp.zeros((L - 1,), I32),
        default_left=jnp.zeros((L - 1,), BOOL),
        gain=jnp.zeros((L - 1,), ft),
        is_cat=jnp.zeros((L - 1,), BOOL),
        cat_mask=jnp.zeros((L - 1, cat_width), BOOL),
        internal_value=jnp.zeros((L - 1,), ft),
        internal_count=jnp.zeros((L - 1,), I32),
        leaf_value=jnp.zeros((L,), ft).at[0].set(root_out),
        leaf_count=jnp.zeros((L,), I32).at[0].set(count),
        leaf_weight=jnp.zeros((L,), ft).at[0].set(sum_hess),
        row_leaf=jnp.zeros((n,), I32),
    )


def _empty_tree_arrays(n, L, cat_width, ft) -> TreeArrays:
    return TreeArrays(
        num_leaves=jnp.asarray(1, I32),
        split_leaf=jnp.zeros((L - 1,), I32),
        split_feature=jnp.full((L - 1,), -1, I32),
        threshold=jnp.zeros((L - 1,), I32),
        default_left=jnp.zeros((L - 1,), BOOL),
        gain=jnp.zeros((L - 1,), ft),
        is_cat=jnp.zeros((L - 1,), BOOL),
        cat_mask=jnp.zeros((L - 1, cat_width), BOOL),
        internal_value=jnp.zeros((L - 1,), ft),
        internal_count=jnp.zeros((L - 1,), I32),
        leaf_value=jnp.zeros((L,), ft),
        leaf_count=jnp.zeros((L,), I32),
        leaf_weight=jnp.zeros((L,), ft),
        row_leaf=jnp.zeros((n,), I32),
    )


def _merge_cands_over_shards(cand, axis_name):
    """SyncUpGlobalBestSplit (parallel_tree_learner.h:190) as an
    all_gather + sequential merge: every shard sees every shard's local
    best candidate and deterministically agrees on the global one."""
    gathered = jax.lax.all_gather(cand, axis_name)   # leaves: [S, ...]
    S = gathered.gain.shape[0]
    best = jax.tree.map(lambda a: a[0], gathered)
    for i in range(1, S):
        best = merge_candidates(best, jax.tree.map(lambda a: a[i], gathered))
    return best


def _voting_reduce_hist(hist, feat_gains, meta, gc: GrowConfig, axis_name,
                        feat_nb, always_mask, quant=None, tag=None):
    """The PV-tree communication step (voting_parallel_tree_learner.cpp):
    per-shard top-k proposals cross the wire as a SMALL INDEX ALLGATHER
    (:321's LightSplitInfo exchange — k i32 words per rank, not an
    [F]-plane vote psum), GlobalVoting ranks by vote count (:153-184),
    then ONLY the winning features' histogram bins are reduced
    (CopyLocalHistogram + ReduceScatter, :186-243, :344) — int16
    stochastic-rounded codes under ``quant``. Returns (hist with winner
    bins globally summed, winner feature mask) — identical on every
    shard."""
    from .pallas_scan import topk_vote_indices
    F = gc.num_features
    k = min(max(gc.top_k, 1), F)
    prop = topk_vote_indices(feat_gains, k,
                             F, jnp.asarray(K_MIN_SCORE,
                                            feat_gains.dtype))   # [k]
    gathered = vote_allgather("allgather:vote_topk", prop,
                              axis_name)                      # [S, k]
    votes = jnp.zeros((F,), I32).at[gathered.reshape(-1)].add(
        1, mode="drop")              # F-sentinel proposals drop out
    n_win = min(2 * k, F)
    # stable vote ranking: ties keep the smaller feature id; the 2k quota
    # is always filled (zero-vote features pad it, as in GlobalVoting)
    rank_key = votes * F - jnp.arange(F, dtype=I32)
    _, winners = jax.lax.top_k(rank_key, n_win)                 # [n_win]
    win_mask = jnp.zeros((F,), BOOL).at[winners].set(True)
    win_mask = win_mask | always_mask        # categorical: always reduced
    # reduce only the winning features' bin ranges: mask the flat
    # histogram by bin ownership (bin_to_feat from meta.feat_id); the
    # masked-out lanes are exact zeros, which quantize to exact zeros
    bin_win = win_mask[jnp.clip(meta.feat_id, 0, F - 1)] \
        & (meta.feat_id >= 0)
    masked = hist * bin_win[:, None].astype(hist.dtype)
    red_g, red_h = plane_psum("psum:vote_planes", masked[:, 0],
                              masked[:, 1], axis_name, quant, tag)
    reduced = jnp.stack([red_g, red_h], axis=-1)
    hist_out = jnp.where(bin_win[:, None], reduced, hist)
    return hist_out, win_mask


def _make_eval_leaf(meta, params, feature_mask, cat, gc: GrowConfig,
                    extras: GrowExtras, feat_nb, axis_name=None, fix=None,
                    quant=None):
    """Per-leaf best-split evaluator over a [TB, 2] histogram.

    `key` seeds the per-node randomness (extra_trees random thresholds,
    feature_fraction_bynode column sample); `feature_used` feeds the CEGB
    coupled penalty. Both are ignored unless the matching gc flag is set.

    The three reference parallel learners dispatch here:
      * "data": hist arrives globally psum-reduced — plain scan;
      * "feature" (feature_parallel_tree_learner.cpp): data replicated,
        each shard scans its round-robin-owned features, candidates merged
        by SyncUpGlobalBestSplit (all_gather + deterministic merge);
      * "voting" (voting_parallel_tree_learner.cpp): hist arrives LOCAL;
        a per-shard scan with 1/S-scaled thresholds proposes top_k
        features, the global vote picks 2k winners, only their bins are
        psum-reduced, then the real scan runs on those features with the
        global leaf sums.
    """
    F = gc.num_features

    def eval_leaf(hist, sg, sh, cnt, depth, cmin, cmax, key, feature_used,
                  lazy_unused=None):
        fmask = feature_mask
        win_mask = None
        if gc.parallel_mode == "voting" and axis_name is not None:
            # exact LOCAL leaf sums: every row lands in exactly one bin of
            # every group (EFB sentinel included), so the flat-hist total
            # is num_groups * local_leaf_sum
            S = jax.lax.psum(jnp.asarray(1.0, jnp.float32), axis_name)
            local_sg = jnp.sum(hist[:, 0]) / _NG[0]
            local_sh = jnp.sum(hist[:, 1]) / _NG[0]
            sh_f = jnp.maximum(sh.astype(jnp.float32), 1e-12)
            local_cnt = jnp.round(
                local_sh * cnt.astype(jnp.float32) / sh_f).astype(I32)
            pv = params._replace(
                min_data_in_leaf=jnp.maximum(
                    (params.min_data_in_leaf.astype(jnp.float32) / S)
                    .astype(I32), 1),
                min_sum_hessian_in_leaf=params.min_sum_hessian_in_leaf / S)
            local_gains = find_best_split_numerical(
                hist, local_sg, local_sh, local_cnt, meta, pv, cmin, cmax,
                fmask & (~meta.is_categorical), num_features=F,
                use_mc=gc.use_mc, max_w=gc.scan_width, use_dp=gc.use_dp,
                use_l1=gc.use_l1, use_mds=gc.use_mds, feat_gains_only=True)
            # the per-node PRNG key is rank-uniform (folded from the
            # shared tree key by split index), so it doubles as the
            # quantization rounding seed — unique per eval, identical
            # on every shard
            hist, win_mask = _voting_reduce_hist(
                hist, local_gains, meta, gc, axis_name, feat_nb,
                meta.is_categorical, quant=quant,
                tag=jnp.asarray(key, jnp.uint32)[0])
            if fix is not None:
                hist = fix_histogram(hist, sg, sh, fix.mf_global, fix.start,
                                     fix.end, max_w=gc.scan_width,
                                     use_dp=gc.use_dp)
            fmask = fmask & win_mask
        if gc.parallel_mode == "feature" and axis_name is not None:
            shard = jax.lax.axis_index(axis_name)
            owned = (jnp.arange(F, dtype=I32)
                     % jax.lax.psum(1, axis_name)) == shard
            fmask = fmask & owned
        if gc.bynode_k > 0:
            # per-node column sample of exactly k features
            # (ColSampler by-node, col_sampler.hpp:90-140)
            r = jax.random.uniform(jax.random.fold_in(
                jax.random.wrap_key_data(key), 1), (F,))
            r = jnp.where(feature_mask, r, jnp.inf)
            order = jnp.argsort(r)
            node_mask = jnp.zeros((F,), BOOL).at[order[:gc.bynode_k]].set(True)
            fmask = fmask & node_mask
        rand_bins = None
        if gc.extra_trees:
            # USE_RAND: one uniform threshold in each feature's scan range
            rand_bins = jax.random.randint(
                jax.random.fold_in(jax.random.wrap_key_data(key), 2),
                (F,), 0, jnp.maximum(feat_nb - 1, 1))
        gain_penalty = None
        if gc.use_cegb:
            ft_ = acc_dtype(gc.use_dp)
            gain_penalty = (
                extras.cegb_tradeoff.astype(ft_)
                * (extras.cegb_split_pen.astype(ft_) * cnt.astype(ft_)
                   + jnp.where(feature_used, 0.0,
                               extras.cegb_coupled.astype(ft_))))
            if gc.use_cegb_lazy and lazy_unused is not None:
                # on-demand data-acquisition cost: penalty_lazy[f] per
                # in-leaf row whose path never used feature f
                # (CalculateOndemandCosts,
                # cost_effective_gradient_boosting.hpp:94-114)
                gain_penalty = gain_penalty + (
                    extras.cegb_tradeoff.astype(ft_)
                    * extras.cegb_lazy.astype(ft_)
                    * lazy_unused.astype(ft_))
        cand = find_best_split_numerical(
            hist, sg, sh, cnt, meta, params, cmin, cmax, fmask,
            num_features=F, use_mc=gc.use_mc, max_w=gc.scan_width,
            use_dp=gc.use_dp, use_l1=gc.use_l1, use_mds=gc.use_mds,
            rand_bins=rand_bins, gain_penalty=gain_penalty)
        cand = cand._replace(cat_mask=jnp.zeros((gc.cat_width,), BOOL))
        if cat.cat_feature.shape[0] > 0:
            cat_cand = find_best_split_categorical(
                hist, sg, sh, cnt, cat, meta, params, cmin, cmax,
                fmask, use_mc=gc.use_mc, use_dp=gc.use_dp,
                gain_penalty=gain_penalty, steps=gc.max_cat_threshold)
            cand = merge_candidates(cand, cat_cand)
        if gc.max_depth > 0:
            blocked = depth >= gc.max_depth
            cand = cand._replace(
                gain=jnp.where(blocked, K_MIN_SCORE, cand.gain))
        if gc.parallel_mode == "feature" and axis_name is not None:
            cand = _merge_cands_over_shards(cand, axis_name)
        return cand

    # static group count for the voting local-sum recovery
    _NG = [1]

    def set_num_groups(ng):
        _NG[0] = max(int(ng), 1)
    eval_leaf.set_num_groups = set_num_groups
    return eval_leaf


def _eval_children(eval_leaf, leaf_hist, l, s, cand, left_cnt, right_cnt,
                   depth_child, l_cmin, l_cmax, r_cmin, r_cmax, keys,
                   feature_used, lazy_pair=None):
    """Evaluate both children in ONE vectorized scan pass (vmap over a
    [2, TB, 2] stack) — halves the per-split fixed cost of the dense scan."""
    pair_hist = jnp.stack([leaf_hist[l], leaf_hist[s]])
    sgs = jnp.stack([cand.left_sum_grad, cand.right_sum_grad])
    shs = jnp.stack([cand.left_sum_hess, cand.right_sum_hess])
    cnts = jnp.stack([left_cnt, right_cnt])
    cmins = jnp.stack([l_cmin, r_cmin])
    cmaxs = jnp.stack([l_cmax, r_cmax])
    if lazy_pair is None:
        pair = jax.vmap(eval_leaf, in_axes=(0, 0, 0, 0, None, 0, 0, 0, None))(
            pair_hist, sgs, shs, cnts, depth_child, cmins, cmaxs, keys,
            feature_used)
    else:
        pair = jax.vmap(eval_leaf,
                        in_axes=(0, 0, 0, 0, None, 0, 0, 0, None, 0))(
            pair_hist, sgs, shs, cnts, depth_child, cmins, cmaxs, keys,
            feature_used, lazy_pair)
    cand_l = jax.tree.map(lambda a: a[0], pair)
    cand_r = jax.tree.map(lambda a: a[1], pair)
    return cand_l, cand_r


def _make_eval_pair_fused(meta, params, feature_mask, cat, gc: GrowConfig,
                          axis_name=None, feat_nb=None, num_groups: int = 1,
                          quant=None, extras: GrowExtras = None):
    """Fused Pallas scan-pair evaluator (fast path; see ops/pallas_scan.py).

    Built once per tree: dense gather layout + direction masks precompute
    (~15 ops), then every split pays one gather + one kernel + a ~25-op
    scalar assembly instead of the ~300-op XLA pair scan. Falls back never
    — the CALLER gates on gc.scan_impl (resolve_scan_impl checks every
    semantic knob this kernel does not implement).

    Parallel modes (the reference's three learners):
      * "data": hist arrives psum-reduced — plain kernel scan;
      * "feature": the shard scans only its round-robin-owned features
        (ownership folded into the layout masks) and the per-shard winners
        merge via SyncUpGlobalBestSplit (all_gather + deterministic merge,
        parallel_tree_learner.h:190);
      * "voting": the kernel runs TWICE per child — a local scan with
        1/S-scaled thresholds proposes top_k features, the global vote
        picks 2k winners, only their bins psum, then the real scan runs
        with win-masked validity (voting_parallel_tree_learner.cpp:153-344;
        EFB-bundled datasets fall back to the XLA path — the fix-up runs
        inside the voting eval there).
    """
    from .pallas_scan import ScanLayout, scan_pair
    F = gc.num_features
    if gc.parallel_mode == "feature" and axis_name is not None:
        shard = jax.lax.axis_index(axis_name)
        owned = (jnp.arange(F, dtype=I32)
                 % jax.lax.psum(1, axis_name)) == shard
        feature_mask = feature_mask & owned
    layout = ScanLayout(meta, feature_mask, F, gc.scan_width, gc.total_bins)
    # rank-uniform per-TREE seed base for the voting-window rounding:
    # without the tree key, the same (split, child) would reuse its
    # noise every boosting iteration and the zero-mean errors the
    # quant_certify envelope assumes would turn into a systematic bias
    _qkey = (jnp.asarray(extras.key, jnp.uint32)[0].astype(I32)
             if extras is not None else jnp.asarray(0, I32))
    p32 = params.cast(jnp.float32)
    f32 = jnp.float32
    # CPU (tests) runs the kernel in interpreter mode — the equivalence
    # suite compares it against the XLA scan there
    interpret = not on_tpu()
    voting = gc.parallel_mode == "voting" and axis_name is not None

    def _scan(gb, hb, scal, valid_r, valid_f):
        return scan_pair(scal, gb, hb, layout.keep_r, layout.keep_f,
                         valid_r, valid_f, layout.aux, interpret=interpret)

    def _build_scal(sg, sh, cnt, md, mh):
        l2 = p32.lambda_l2.astype(f32)
        cf = cnt / sh
        gain_shift = sg * sg / (sh + l2)
        mgs = gain_shift + p32.min_gain_to_split.astype(f32)
        return jnp.stack([
            sg, sh, cnt, cf,
            jnp.broadcast_to(md, (2,)), jnp.broadcast_to(mh, (2,)),
            mgs, jnp.broadcast_to(l2, (2,))], axis=1)  # [2, 8]

    def eval_pair(leaf_hist, l, s, cand, left_cnt, right_cnt, depth_child):
        rows2 = jnp.stack([l, s])
        hist2 = leaf_hist[rows2]                      # [2, TB, 2]
        sg = jnp.stack([cand.left_sum_grad,
                        cand.right_sum_grad]).astype(f32)
        # the XLA scan's sum_hess_adj = sum_hess + 2*kEpsilon: NOT a no-op
        # when a child's hessians are all zero (keeps cnt_factor finite)
        sh = jnp.stack([cand.left_sum_hess,
                        cand.right_sum_hess]).astype(f32) + f32(2e-15)
        cnt = jnp.stack([left_cnt, right_cnt]).astype(f32)
        md = p32.min_data_in_leaf.astype(f32)
        mh = p32.min_sum_hessian_in_leaf.astype(f32)
        l2 = p32.lambda_l2.astype(f32)
        valid_r, valid_f = layout.valid_r, layout.valid_f
        if voting:
            # ---- PV-tree: local scan -> vote -> selective psum ----------
            S = jax.lax.psum(jnp.asarray(1.0, f32), axis_name)
            ng = f32(max(num_groups, 1))
            local_sg = jnp.sum(hist2[:, :, 0], axis=1) / ng        # [2]
            local_sh = jnp.sum(hist2[:, :, 1], axis=1) / ng + f32(2e-15)
            local_cnt = jnp.round(local_sh * cnt
                                  / jnp.maximum(sh, f32(1e-12)))
            gb_l = leaf_hist[..., 0][rows2][:, layout.gidx]
            hb_l = leaf_hist[..., 1][rows2][:, layout.gidx]
            scal_l = _build_scal(local_sg, local_sh, local_cnt,
                                 jnp.maximum(jnp.floor(md / S), 1.0),
                                 mh / S)
            out_l = _scan(gb_l, hb_l, scal_l, valid_r, valid_f)
            hist_new = []
            win_masks = []
            for c in range(2):
                hist_c, win = _voting_reduce_hist(
                    hist2[c], out_l[c, 0, :F], meta, gc, axis_name,
                    feat_nb, meta.is_categorical, quant=quant,
                    tag=quant_tag(_qkey, 2 * s + c))
                hist_new.append(hist_c)
                win_masks.append(win)
            hist2 = jnp.stack(hist_new)
            winp = jnp.pad(jnp.stack(win_masks),
                           ((0, 0), (0, layout.Fp - F)))    # [2, Fp]
            valid_r = valid_r[None] * winp[:, :, None].astype(f32)
            valid_f = valid_f[None] * winp[:, :, None].astype(f32)

        # channel planes sliced BEFORE the dense gather: a [..., 0] slice
        # of the fused gather output miscompiles on TPU at large F
        gb = leaf_hist[..., 0][rows2][:, layout.gidx]  # [2, Fp, Wp]
        hb = leaf_hist[..., 1][rows2][:, layout.gidx]
        scal = _build_scal(sg, sh, cnt, md, mh)
        out = _scan(gb, hb, scal, valid_r, valid_f)
        gains = out[:, 0, :]                          # [2, Fp]
        best_f = jnp.argmax(gains, axis=1)            # [2] first max

        def take(row):
            return jnp.take_along_axis(out[:, row, :], best_f[:, None],
                                       axis=1)[:, 0]
        gain_b = take(0)
        t_b = take(1).astype(I32)
        use_f_b = take(2) > 0.5
        lg = take(3)
        lh = take(4)
        lc = take(5)
        best_valid = jnp.isfinite(gain_b)
        if gc.max_depth > 0:
            best_valid &= depth_child < gc.max_depth
        rg = sg - lg
        rh = sh - lh
        rc = cnt - lc
        lo = -lg / (lh + l2)
        ro = -rg / (rh + l2)
        default_left = (~use_f_b) & (~layout.forced_right[best_f])
        neg = jnp.asarray(K_MIN_SCORE, f32)
        pair = SplitCandidate(
            gain=jnp.where(best_valid, gain_b, neg),
            feature=jnp.where(best_valid, best_f.astype(I32), -1),
            threshold=jnp.where(best_valid, t_b, 0),
            default_left=jnp.where(best_valid, default_left, True),
            left_output=lo, right_output=ro,
            left_sum_grad=lg, left_sum_hess=lh,
            right_sum_grad=rg, right_sum_hess=rh,
            left_count=jnp.floor(lc + 0.5).astype(I32),
            right_count=jnp.floor(rc + 0.5).astype(I32),
            is_cat=jnp.zeros((2,), BOOL),
            cat_mask=jnp.zeros((2, gc.cat_width), BOOL),
        )
        if cat.cat_feature.shape[0] > 0:
            cat_pair = jax.vmap(
                lambda h, a, b, c: find_best_split_categorical(
                    h, a, b, c, cat, meta, params,
                    jnp.asarray(-jnp.inf, f32), jnp.asarray(jnp.inf, f32),
                    feature_mask, use_mc=False, use_dp=gc.use_dp,
                    steps=gc.max_cat_threshold))(
                hist2, sg, sh, jnp.stack([left_cnt, right_cnt]))
            if gc.max_depth > 0:
                cat_pair = cat_pair._replace(gain=jnp.where(
                    depth_child < gc.max_depth, cat_pair.gain, neg))
            pair = merge_candidates(pair, cat_pair)
        if gc.parallel_mode == "feature" and axis_name is not None:
            # SyncUpGlobalBestSplit (parallel_tree_learner.h:190)
            pair = _merge_cands_over_shards(pair, axis_name)
        cand_l = jax.tree.map(lambda a: a[0], pair)
        cand_r = jax.tree.map(lambda a: a[1], pair)
        return cand_l, cand_r

    return eval_pair


def _hist_chunk_contract(bv, vc, W, hist_dtype):
    """One chunk's one-hot MXU contraction -> [G, W, 2] f32.

    hist_dtype "bf16x2" splits (grad, hess) into bf16 hi + lo halves and
    contracts one [C, 4]-wide bf16 matmul (the one-hot is exact in bf16, so
    accuracy is f32-grade while the MXU runs at its bf16 rate — the padded-N
    cost of 4 vs 2 columns is zero).
    """
    if hist_dtype == "bf16x2":
        oh = (bv[:, :, None] == jnp.arange(W, dtype=I32)[None, None, :]
              ).astype(jnp.bfloat16)
        v_hi = vc.astype(jnp.bfloat16)
        v_lo = (vc - v_hi.astype(jnp.float32)).astype(jnp.bfloat16)
        vq = jnp.concatenate([v_hi, v_lo], -1)                  # [C, 4]
        out = jnp.einsum("rgw,rc->gwc", oh, vq,
                         preferred_element_type=jnp.float32)    # [G, W, 4]
        return out[..., :2] + out[..., 2:]
    if hist_dtype == "f64":
        oh = (bv[:, :, None] == jnp.arange(W, dtype=I32)[None, None, :]
              ).astype(jnp.float64)
        return jnp.einsum("rgw,rc->gwc", oh, vc.astype(jnp.float64),
                          preferred_element_type=jnp.float64)
    oh = (bv[:, :, None] == jnp.arange(W, dtype=I32)[None, None, :]
          ).astype(jnp.float32)
    return jnp.einsum("rgw,rc->gwc", oh, vc,
                      preferred_element_type=jnp.float32)


class ForcedInfo(NamedTuple):
    """forcedsplits_filename JSON flattened to application order (BFS).

    thr holds the kernel-convention threshold (bins <= thr go left), which
    is the reference threshold bin T = ValueToBin(value) unchanged: the
    reference partition sends bin <= T left and records RealThreshold(T)
    (DenseBin::Split, src/io/dense_bin.hpp:112;
    GatherInfoForThresholdNumerical, feature_histogram.hpp:488-571).
    """
    leaf: jnp.ndarray       # [K] i32 leaf the forced split applies to
    feature: jnp.ndarray    # [K] i32 inner feature
    thr: jnp.ndarray        # [K] i32 local-bin threshold (ours)


def empty_forced() -> ForcedInfo:
    z = jnp.zeros((1,), I32)
    return ForcedInfo(leaf=z, feature=z, thr=z)


def _forced_candidate(hist, sum_grad, sum_hess, cnt, f, thr, meta,
                      params, gc: GrowConfig, ft):
    """SplitCandidate for a FORCED (feature, threshold) on one leaf.

    The reference walks the histogram top-down summing bins >= T into the
    right side, skipping the zero bin (MissingType::Zero) and starting
    below the NaN bin (MissingType::NaN), always default_left
    (GatherInfoForThresholdNumerical, feature_histogram.hpp:488-571);
    invalid forced splits (gain <= min_gain_shift) come back with
    K_MIN_SCORE gain and the caller aborts further forcing.
    """
    p = params.cast(ft)
    sum_grad = sum_grad.astype(ft)
    sum_hess = sum_hess.astype(ft)
    W = gc.scan_width if gc.scan_width > 0 else 256
    start = meta.bin_start[f]
    nb = meta.bin_end[f] - start
    mt = meta.missing_type[f]
    db = meta.default_bin[f]
    # pad W trailing zero rows: a feature narrower than scan_width near the
    # end of the histogram would otherwise make dynamic_slice clamp `start`
    # and silently misalign the window with the local-bin iota below
    hist_p = jnp.pad(hist, ((0, W), (0, 0)))
    win = jax.lax.dynamic_slice(
        hist_p, (start, jnp.asarray(0, I32)), (W, 2)).astype(ft)
    w = jnp.arange(W, dtype=I32)
    T = thr + 1
    right = (w >= jnp.maximum(T, 1)) & (w < nb)
    right &= ~((mt == 1) & (w == db))           # zero bin rides left
    right &= ~((mt == 2) & (w == nb - 1))       # NaN bin rides left
    m = right.astype(ft)
    rg = jnp.sum(win[:, 0] * m)
    rh = jnp.sum(win[:, 1] * m) + ft(K_EPSILON)
    cf = cnt.astype(ft) / sum_hess
    rc = jnp.floor(jnp.sum(win[:, 1] * m) * cf + 0.5).astype(I32)
    lg = sum_grad - rg
    lh = sum_hess - rh
    lc = cnt - rc
    l1, l2, mds = p.lambda_l1, p.lambda_l2, p.max_delta_step
    gain_shift = _leaf_gain(sum_grad, sum_hess, l1, l2, mds)
    min_gain_shift = gain_shift + p.min_gain_to_split
    cur = _leaf_gain(lg, lh, l1, l2, mds) + _leaf_gain(rg, rh, l1, l2, mds)
    ok = jnp.isfinite(cur) & (cur > min_gain_shift)
    neg = jnp.asarray(K_MIN_SCORE, ft)
    return SplitCandidate(
        gain=jnp.where(ok, cur - min_gain_shift, neg),
        feature=f.astype(I32),
        threshold=thr.astype(I32),
        default_left=jnp.asarray(True),
        left_output=_leaf_output_unconstrained(lg, lh, l1, l2, mds),
        right_output=_leaf_output_unconstrained(
            sum_grad - lg, sum_hess - lh, l1, l2, mds),
        left_sum_grad=lg, left_sum_hess=lh - ft(K_EPSILON),
        right_sum_grad=sum_grad - lg,
        right_sum_hess=sum_hess - lh - ft(K_EPSILON),
        left_count=lc, right_count=cnt - lc,
        is_cat=jnp.asarray(False),
        cat_mask=jnp.zeros((gc.cat_width,), BOOL))


def _select_with_forced(st_fidx, best, leaf_hist, leaf_sum_grad,
                        leaf_sum_hess, leaf_count, forced: ForcedInfo,
                        meta, params, gc: GrowConfig, ft):
    """(l, cand, do, done, fidx') honoring the forced-split phase.

    While fidx < n_forced the forced entry overrides leaf choice and
    candidate; a failed forced split aborts the remaining forced list
    (reference abort_last_forced_split) and growth continues normally.
    """
    l_best = jnp.argmax(best.gain).astype(I32)
    cand_best = jax.tree.map(lambda a: a[l_best], best)
    if gc.n_forced == 0:
        do = cand_best.gain > 0.0
        return l_best, cand_best, do, ~do, st_fidx
    in_forced = st_fidx < gc.n_forced
    fi = jnp.clip(st_fidx, 0, gc.n_forced - 1)
    l = jnp.where(in_forced, forced.leaf[fi], l_best)
    fc = _forced_candidate(
        leaf_hist[l], leaf_sum_grad[l], leaf_sum_hess[l], leaf_count[l],
        forced.feature[fi], forced.thr[fi], meta, params, gc, ft)
    cand = jax.tree.map(
        lambda a, b: jnp.where(in_forced, a, b), fc,
        jax.tree.map(lambda a: a[l], best))
    do = cand.gain > 0.0
    done = jnp.where(in_forced, False, ~do)
    fidx = jnp.where(in_forced,
                     jnp.where(do, st_fidx + 1, gc.n_forced), st_fidx)
    return l, cand, do, done, fidx


def _split_keys(extras: GrowExtras, s):
    """Raw [2, 2]u32 child keys for split s (root uses tag 0; children use
    2s / 2s+1, disjoint because s >= 1)."""
    base = jax.random.wrap_key_data(extras.key)
    kl = jax.random.key_data(jax.random.fold_in(base, s * 2))
    kr = jax.random.key_data(jax.random.fold_in(base, s * 2 + 1))
    return jnp.stack([kl, kr])


def _root_key(extras: GrowExtras):
    return jax.random.key_data(
        jax.random.fold_in(jax.random.wrap_key_data(extras.key), 0))


def _mono_bounds(st_cmin, st_cmax, mono, left_out, right_out, ft):
    """Monotone bound propagation (monotone_constraints.hpp:15-64)."""
    mid = ((left_out + right_out) / 2.0).astype(ft)
    l_cmax = jnp.where(mono > 0, jnp.minimum(st_cmax, mid), st_cmax)
    r_cmin = jnp.where(mono > 0, jnp.maximum(st_cmin, mid), st_cmin)
    l_cmin = jnp.where(mono < 0, jnp.maximum(st_cmin, mid), st_cmin)
    r_cmax = jnp.where(mono < 0, jnp.minimum(st_cmax, mid), st_cmax)
    return l_cmin, l_cmax, r_cmin, r_cmax


def _record_split(tree: TreeArrays, k, do, l, cand, parent_value,
                  parent_count, s):
    """Masked write of split record k (identity when ~do)."""
    def m(a, new, idx):
        return a.at[idx].set(jnp.where(do, new, a[idx]))
    return tree._replace(
        num_leaves=jnp.where(do, s + 1, tree.num_leaves),
        split_leaf=m(tree.split_leaf, l, k),
        split_feature=m(tree.split_feature, cand.feature, k),
        threshold=m(tree.threshold, cand.threshold, k),
        default_left=m(tree.default_left, cand.default_left, k),
        gain=m(tree.gain, cand.gain, k),
        is_cat=m(tree.is_cat, cand.is_cat, k),
        cat_mask=tree.cat_mask.at[k].set(
            jnp.where(do, cand.cat_mask, tree.cat_mask[k])),
        internal_value=m(tree.internal_value, parent_value, k),
        internal_count=m(tree.internal_count, parent_count, k),
    )


@functools.partial(
    jax.jit,
    static_argnames=("gc", "axis_name", "quant"),
    donate_argnums=(),
)
def _grow_tree_jit(layout: DataLayout, grad: jnp.ndarray, hess: jnp.ndarray,
              bag_mask: jnp.ndarray, meta: FeatureMeta, params: SplitParams,
              feature_mask: jnp.ndarray, fix: FixInfo, gc: GrowConfig,
              axis_name=None, cat: CatLayout = None,
              extras: GrowExtras = None,
              forced: ForcedInfo = None,
              row_feat_used=None, quant=None) -> TreeArrays:
    """Grow one tree. grad/hess must already include bagging/GOSS weighting
    and be zero on padded/out-of-bag rows; bag_mask marks in-bag valid rows.

    When axis_name is set, rows are sharded across that mesh axis and
    histograms / counts are psum-reduced — this IS the data-parallel learner
    (reference src/treelearner/data_parallel_tree_learner.cpp) expressed as
    sharding + one collective.

    When gc.use_cegb_lazy is set, `row_feat_used` carries the [N, F] bool
    per-row feature-acquisition bitset across trees (the reference's
    feature_used_in_data_, cost_effective_gradient_boosting.hpp:47) and the
    return value grows a third element with its updated state. Lazy CEGB is
    single-device masked-grower only (gated in treelearner/serial.py).
    """
    if cat is None:
        cat = empty_cat_layout(gc.cat_width)
    if extras is None:
        extras = default_extras(gc.num_features)
    if forced is None:
        forced = empty_forced()
    ft = acc_dtype(gc.use_dp)
    n = (layout.ell_grp if gc.multival else layout.bins).shape[0]
    L = gc.num_leaves
    TB = gc.total_bins
    F = gc.num_features
    if F == 0 or TB == 0:
        # no usable features: a single-leaf tree (reference warns and trains
        # constant trees when all features are trivial)
        one = _single_leaf_tree(n, L, gc.cat_width, grad, hess, bag_mask,
                                params, axis_name, ft)
        if gc.use_cegb_lazy:
            return one, extras.feature_used, row_feat_used
        return one, extras.feature_used

    grad = grad.astype(jnp.float32)
    hess = hess.astype(jnp.float32)

    # collectives per mode: "data" reduces hists+counts; "voting" reduces
    # counts/sums only (hists reduce selectively inside eval); "feature"
    # replicates data so nothing reduces
    def psum(x):
        if axis_name is None or gc.parallel_mode == "feature":
            return x
        return jax.lax.psum(x, axis_name)

    # quantization-seed base: the per-tree PRNG key is rank-uniform, so
    # (key, split index) seeds identical stochastic rounding on every
    # shard while varying across trees and splits
    _qkey = jnp.asarray(extras.key, jnp.uint32)[0].astype(I32)

    def hist_psum(x, stage):
        """Histogram-plane reduction over the mesh — int16 codes on the
        wire under ``quant`` (ops/quantize.plane_psum)."""
        if axis_name is None or gc.parallel_mode != "data":
            return x
        g_r, h_r = plane_psum("psum:hist_plane", x[..., 0], x[..., 1],
                              axis_name, quant, quant_tag(_qkey, stage))
        return jnp.stack([g_r, h_r], axis=-1)

    # ---- root ----------------------------------------------------------
    hft = hist_ft(gc)
    root_hist = hist_psum(_hist_masked(
        layout, grad, hess, bag_mask, TB, gc.rows_per_chunk,
        gc.packed_4bit, None, multival=gc.multival, dtype=hft),
        jnp.asarray(0, I32))
    sum_grad = psum(jnp.sum(grad, dtype=ft))
    sum_hess = psum(jnp.sum(hess, dtype=ft))
    root_count = psum(jnp.sum(bag_mask, dtype=I32))
    if gc.parallel_mode != "voting":
        root_hist = fix_histogram(root_hist, sum_grad, sum_hess,
                                  fix.mf_global, fix.start, fix.end,
                                  max_w=gc.scan_width, use_dp=gc.use_dp)

    pcast = params.cast(ft)
    feat_nb_e = meta.bin_end - meta.bin_start
    eval_leaf = _make_eval_leaf(meta, params, feature_mask, cat, gc,
                                extras, feat_nb_e, axis_name=axis_name,
                                fix=fix, quant=quant)
    eval_leaf.set_num_groups(layout.group_offset.shape[0])
    eval_pair_fused = (_make_eval_pair_fused(
        meta, params, feature_mask, cat, gc, axis_name=axis_name,
        feat_nb=feat_nb_e, num_groups=layout.group_offset.shape[0],
        quant=quant, extras=extras)
        if gc.scan_impl == "pallas" else None)
    root_out = _leaf_output_unconstrained(
        sum_grad, sum_hess, pcast.lambda_l1, pcast.lambda_l2,
        pcast.max_delta_step)

    if gc.use_cegb_lazy:
        assert eval_pair_fused is None, \
            "CEGB excludes the fused Pallas pair scan (resolve_scan_impl)"
        rfu0 = (row_feat_used if row_feat_used is not None
                else jnp.zeros((n, F), jnp.bool_))
    else:
        rfu0 = jnp.zeros((0, 0), jnp.bool_)

    def _lazy_unused(mask, rfu):
        # per-feature count of rows in `mask` whose acquisition bit is
        # still unset: one [N]x[N,F] matvec (counts exact in f32 — lazy
        # CEGB rides the masked grower, bounded well under 2^24 rows)
        return jnp.matmul(mask.astype(jnp.float32),
                          (~rfu).astype(jnp.float32))

    state = _LoopState(
        s=jnp.asarray(1, I32),
        done=jnp.asarray(False),
        fidx=jnp.asarray(0, I32),
        row_leaf=jnp.zeros((n,), I32),
        leaf_hist=jnp.zeros((L, TB, 2), hft).at[0].set(root_hist),
        leaf_sum_grad=jnp.zeros((L,), ft).at[0].set(sum_grad),
        leaf_sum_hess=jnp.zeros((L,), ft).at[0].set(sum_hess),
        leaf_count=jnp.zeros((L,), I32).at[0].set(root_count),
        leaf_value=jnp.zeros((L,), ft).at[0].set(root_out),
        leaf_depth=jnp.zeros((L,), I32),
        leaf_cmin=jnp.full((L,), -jnp.inf, ft),
        leaf_cmax=jnp.full((L,), jnp.inf, ft),
        feature_used=extras.feature_used,
        row_feat_used=rfu0,
        best=jax.tree.map(
            lambda x: jnp.broadcast_to(x, (L,) + x.shape),
            _root_candidate_dummy(gc.cat_width, ft)),
        tree=_empty_tree_arrays(n, L, gc.cat_width, ft),
    )

    # root best split
    root_lazy = (_lazy_unused(bag_mask, rfu0) if gc.use_cegb_lazy else None)
    root_cand = eval_leaf(root_hist, sum_grad, sum_hess, root_count,
                          jnp.asarray(0, I32), state.leaf_cmin[0],
                          state.leaf_cmax[0], _root_key(extras),
                          state.feature_used, root_lazy)
    state = state._replace(
        best=jax.tree.map(lambda a, v: a.at[0].set(v), state.best, root_cand))

    feat_nb = meta.bin_end - meta.bin_start

    def cond(st: _LoopState):
        return (~st.done) & (st.s < L)

    def body(st: _LoopState) -> _LoopState:
        l, cand, do, done_new, fidx = _select_with_forced(
            st.fidx, st.best, st.leaf_hist, st.leaf_sum_grad,
            st.leaf_sum_hess, st.leaf_count, forced, meta, params, gc, ft)
        s = st.s
        f = jnp.maximum(cand.feature, 0)
        g = layout.group_of[f]
        # per-row local bin of feature f (EFB fallback to most_freq)
        if gc.multival:
            col = _multival_col(layout, g) + layout.group_offset[g]
        else:
            col = (_logical_col(layout.bins, g, layout, gc.packed_4bit)
                   + layout.group_offset[g])
        in_range = (col >= meta.bin_start[f]) & (col < meta.bin_end[f])
        local_bin = col - meta.bin_start[f]
        go_left = _go_left_decision(
            local_bin, in_range,
            (feat_nb[f], meta.missing_type[f], meta.default_bin[f],
             layout.most_freq_bin[f]),
            cand, gc.cat_width)
        in_leaf = (st.row_leaf == l) & do
        row_leaf = jnp.where(in_leaf & ~go_left, s, st.row_leaf)

        in_bag = in_leaf & bag_mask
        left_cnt = psum(jnp.sum(in_bag & go_left, dtype=I32))
        right_cnt = psum(jnp.sum(in_bag, dtype=I32)) - left_cnt

        smaller_is_left = left_cnt <= right_cnt
        smaller_mask = in_leaf & (go_left == smaller_is_left)
        hist_smaller = hist_psum(_hist_masked(
            layout, grad, hess, smaller_mask, TB, gc.rows_per_chunk,
            gc.packed_4bit, None, multival=gc.multival, dtype=hft), s)
        sm_sum_grad = jnp.where(smaller_is_left, cand.left_sum_grad,
                                cand.right_sum_grad)
        sm_sum_hess = jnp.where(smaller_is_left, cand.left_sum_hess,
                                cand.right_sum_hess)
        if gc.parallel_mode != "voting":
            hist_smaller = fix_histogram(
                hist_smaller, sm_sum_grad, sm_sum_hess, fix.mf_global,
                fix.start, fix.end, max_w=gc.scan_width, use_dp=gc.use_dp)
        parent_hist = st.leaf_hist[l]
        hist_larger = parent_hist - hist_smaller
        hist_left = jnp.where(smaller_is_left, hist_smaller, hist_larger)
        hist_right = jnp.where(smaller_is_left, hist_larger, hist_smaller)

        depth_child = st.leaf_depth[l] + 1
        mono = meta.monotone[f]
        l_cmin, l_cmax, r_cmin, r_cmax = _mono_bounds(
            st.leaf_cmin[l], st.leaf_cmax[l], mono, cand.left_output,
            cand.right_output, ft)

        # masked in-place updates: left keeps id l, right gets id s.
        # Fallback values avoid re-reading the big buffer: slot l's old value
        # is parent_hist (already sliced), slot s is untouched initial zeros
        # by construction — so the original buffer's liveness ends at the
        # first update and XLA keeps the DUS chain in place.
        def upd(a, new_l, new_s):
            a = a.at[l].set(jnp.where(do, new_l, a[l]))
            return a.at[s].set(jnp.where(do, new_s, a[s]))

        # materialize both write values behind an optimization barrier so
        # XLA cannot re-fuse the parent_hist slice into the DUS fusions
        # (that would keep the carried buffer alive and force a full copy)
        val_l, val_r = jax.lax.optimization_barrier(
            (jnp.where(do, hist_left, parent_hist),
             jnp.where(do, hist_right, jnp.zeros_like(hist_right))))
        leaf_hist = st.leaf_hist.at[l].set(val_l).at[s].set(val_r)
        leaf_sum_grad = upd(st.leaf_sum_grad, cand.left_sum_grad,
                            cand.right_sum_grad)
        leaf_sum_hess = upd(st.leaf_sum_hess, cand.left_sum_hess,
                            cand.right_sum_hess)
        leaf_count = upd(st.leaf_count, left_cnt, right_cnt)
        leaf_value = upd(st.leaf_value, cand.left_output, cand.right_output)
        leaf_depth = upd(st.leaf_depth, depth_child, depth_child)
        leaf_cmin = upd(st.leaf_cmin, l_cmin, r_cmin)
        leaf_cmax = upd(st.leaf_cmax, l_cmax, r_cmax)

        feature_used = st.feature_used
        if gc.use_cegb:
            feature_used = feature_used.at[f].set(feature_used[f] | do)

        row_feat_used = st.row_feat_used
        lazy_pair = None
        if gc.use_cegb_lazy:
            # the split leaf's rows acquire feature f BEFORE the children
            # are evaluated (UpdateLeafBestSplits marks, then the children's
            # FindBestSplits see the updated bitset)
            row_feat_used = row_feat_used.at[:, f].set(
                row_feat_used[:, f] | (in_bag & do))
            nrfu = (~row_feat_used).astype(jnp.float32)
            lazy_pair = jnp.stack([
                jnp.matmul((in_bag & go_left).astype(jnp.float32), nrfu),
                jnp.matmul((in_bag & ~go_left).astype(jnp.float32), nrfu)])

        # evaluate children FROM THE UPDATED BUFFER: slicing leaf_hist (not
        # the hist_left/right expressions) ends the old buffer's liveness at
        # the update, letting XLA do the dynamic-update-slice in place
        # instead of copying the whole [L, TB, 2] tensor twice per split
        if eval_pair_fused is not None:
            cand_l, cand_r = eval_pair_fused(
                leaf_hist, l, s, cand, left_cnt, right_cnt, depth_child)
        else:
            cand_l, cand_r = _eval_children(
                eval_leaf, leaf_hist, l, s, cand, left_cnt, right_cnt,
                depth_child, l_cmin, l_cmax, r_cmin, r_cmax,
                _split_keys(extras, s), feature_used, lazy_pair=lazy_pair)
        best = jax.tree.map(
            lambda a, vl, vr: a.at[l].set(jnp.where(do, vl, a[l]))
                               .at[s].set(jnp.where(do, vr, a[s])),
            st.best, cand_l, cand_r)

        tree = _record_split(st.tree, s - 1, do, l, cand, st.leaf_value[l],
                             st.leaf_count[l], s)
        return st._replace(
            s=s + do.astype(I32), done=done_new, fidx=fidx,
            row_leaf=row_leaf,
            leaf_hist=leaf_hist, leaf_sum_grad=leaf_sum_grad,
            leaf_sum_hess=leaf_sum_hess, leaf_count=leaf_count,
            leaf_value=leaf_value, leaf_depth=leaf_depth,
            leaf_cmin=leaf_cmin, leaf_cmax=leaf_cmax,
            feature_used=feature_used, row_feat_used=row_feat_used,
            best=best, tree=tree)

    final = jax.lax.while_loop(cond, body, state)
    out = final.tree._replace(
        num_leaves=final.s,
        leaf_value=final.leaf_value,
        leaf_count=final.leaf_count,
        leaf_weight=final.leaf_sum_hess,
        row_leaf=final.row_leaf,
    )
    if gc.use_cegb_lazy:
        return out, final.feature_used, final.row_feat_used
    return out, final.feature_used


# ---------------------------------------------------------------------------
# Partitioned grower: O(rows-in-child) per split with ZERO row gathers.
#
# The reference keeps rows leaf-sorted so histogram loops stream memory
# (OrderedBin, include/LightGBM/bin.h:229; DataPartition::Split,
# src/treelearner/data_partition.hpp:101). A TPU cannot afford the index
# indirection — random row gathers run on the scalar path — so instead of a
# leaf-sorted *index permutation* this grower maintains the row PAYLOADS
# (bins, grad, hess, bag flag, original row id) physically leaf-sorted in
# HBM. Every pass is then a contiguous dynamic_slice, and the reordering
# itself is done with a one-hot [C, C] pack matmul on the MXU (a permutation
# expressed as matrix multiply is exact in f32 and runs at systolic-array
# speed).
#
# Per split, two chunked passes over the leaf's segment:
#   pass A: decide go_left per row, pack rows two-ended into scratch
#           ([left block ... right block]) via the pack matmul, count in-bag
#           left rows, and accumulate the SMALLER child's histogram on the
#           fly (which side is smaller is known beforehand from the split
#           candidate's counts) — larger child = parent - smaller;
#   pass B: copy the packed blocks back into the payload buffers
#           (contiguous, masked tails so neighbouring leaves are untouched)
#           and stamp the new leaf id on the right block's positions.
# The final per-row leaf ids are recovered once per tree by scattering the
# position->leaf map through the carried row ids.
# ---------------------------------------------------------------------------

class _PartState(NamedTuple):
    s: jnp.ndarray
    done: jnp.ndarray
    fidx: jnp.ndarray
    binsP: jnp.ndarray          # [N + PAD, G]  leaf-sorted bins
    gradP: jnp.ndarray          # [N + PAD] f32
    hessP: jnp.ndarray          # [N + PAD] f32
    rbP: jnp.ndarray            # [N + PAD] u32: row id | bag_flag << 30
    posL: jnp.ndarray           # [N + PAD] i32 leaf id per position
    binsS: jnp.ndarray          # [N + 2C + CB, G] scratch (writes top out
    gradS: jnp.ndarray          # at N + 2C; the extra CB rows are read
    hessS: jnp.ndarray          # slack so the final right copy-back
    rbS: jnp.ndarray            # chunk's slice stays in range)
    leaf_start: jnp.ndarray     # [L] i32 segment starts (local rows)
    leaf_nrows: jnp.ndarray     # [L] i32 segment lengths (local rows)
    leaf_hist: jnp.ndarray
    leaf_sum_grad: jnp.ndarray
    leaf_sum_hess: jnp.ndarray
    leaf_count: jnp.ndarray     # [L] i32 in-bag (global when sharded)
    leaf_value: jnp.ndarray
    leaf_depth: jnp.ndarray
    leaf_cmin: jnp.ndarray
    leaf_cmax: jnp.ndarray
    feature_used: jnp.ndarray   # [F] bool (CEGB coupled-penalty bookkeeping)
    best: SplitCandidate
    tree: TreeArrays


U32 = jnp.uint32


def _bits_of(bdt) -> int:
    return jnp.dtype(bdt).itemsize * 8


def _bitpack_cols(bw, bits: int):
    """[C, G] narrow ints -> [C, ncol] u32, `32 // bits` values per column."""
    per = 32 // bits
    C, G = bw.shape
    ncol = (G + per - 1) // per
    pad = ncol * per - G
    w = bw.astype(U32)
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
    shifts = (jnp.arange(per, dtype=U32) * U32(bits))[None, None, :]
    return jnp.sum(w.reshape(C, ncol, per) << shifts, axis=-1, dtype=U32)


def _bitunpack_cols(packed, bits: int, G: int, bdt):
    per = 32 // bits
    C, ncol = packed.shape
    shifts = (jnp.arange(per, dtype=U32) * U32(bits))[None, None, :]
    mask = U32((1 << bits) - 1)
    vals = (packed[:, :, None] >> shifts) & mask
    return vals.reshape(C, ncol * per)[:, :G].astype(bdt)


def _pack_sort(key, bw, gw, hw, rbw, bits: int):
    """Two-way partition of a chunk's payload via one vectorized sort.

    key: [C] u32 with 0 = left, 1 = invalid, 2 = right, so the sorted chunk
    is [left block | dropped rows | right block] — the same two-ended layout
    the scratch writes expect. Payload rides as u32 columns (bins bit-packed,
    grad/hess bit-cast, row id carrying the bag flag in bit 30), so the pack
    is EXACT by construction: lax.sort moves words, it never does arithmetic.
    Returns (bins [C, G_as_input], grad, hess, ridbag).
    """
    C, G = bw.shape
    bin_cols = _bitpack_cols(bw, bits)
    g_u = jax.lax.bitcast_convert_type(gw, U32)
    h_u = jax.lax.bitcast_convert_type(hw, U32)
    ops = [key] + [bin_cols[:, i] for i in range(bin_cols.shape[1])] \
        + [g_u, h_u, rbw]
    out = jax.lax.sort(ops, num_keys=1, is_stable=False)
    nbc = bin_cols.shape[1]
    pb = _bitunpack_cols(jnp.stack(out[1:1 + nbc], axis=-1), bits, G,
                         bw.dtype)
    pg = jax.lax.bitcast_convert_type(out[1 + nbc], jnp.float32)
    ph = jax.lax.bitcast_convert_type(out[2 + nbc], jnp.float32)
    prb = out[3 + nbc]
    return pb, pg, ph, prb


def _hist_chunk_accum(acc, bw, gw, hw, gc: GrowConfig, group_offset, W):
    """Accumulate one chunk's (masked) grad/hess into the running histogram.

    The single shared chunk kernel: "pallas" (TPU default) runs the VMEM
    one-hot MXU kernel; "onehot" is the XLA einsum equivalent; both use a
    [G, W, 2] accumulator the caller scatters to global bins once at the
    end. "scatter" (CPU) adds straight into a [TB, 2] accumulator.
    """
    if gc.hist_impl == "pallas":
        from .pallas_histogram import hist_window
        return acc + hist_window(bw.T, gw, hw, W)
    vc = jnp.stack([gw, hw], -1)
    if gc.hist_impl == "onehot":
        return acc + _hist_chunk_contract(bw, vc, W, gc.hist_dtype)
    idx = bw + group_offset[None, :]
    C, G = bw.shape
    fv = jnp.broadcast_to(vc[:, None, :], (C, G, 2)).astype(acc.dtype)
    return acc.at[idx.reshape(-1)].add(fv.reshape(-1, 2))


def _hist_acc_init(gc: GrowConfig, G, W):
    if gc.hist_impl in ("onehot", "pallas"):
        return jnp.zeros((G, W, 2), hist_ft(gc))
    return jnp.zeros((gc.total_bins, 2), hist_ft(gc))


def _hist_acc_finish(acc, gc: GrowConfig, gw_global):
    if gc.hist_impl in ("onehot", "pallas"):
        return jnp.zeros((gc.total_bins, 2), acc.dtype).at[
            gw_global.reshape(-1)].add(acc.reshape(-1, 2), mode="drop")
    return acc


def _hist_contiguous(binsP, grad, hess, layout: DataLayout, start, length,
                     C, gc: GrowConfig, gw_global):
    """[TB, 2] histogram over a contiguous payload segment, chunked by C."""
    Gs = binsP.shape[1]                       # storage columns
    Gl = layout.group_offset.shape[0]         # logical groups
    W = gw_global.shape[1] if gw_global is not None else 0
    arangeC = jnp.arange(C, dtype=I32)
    nch = (length + C - 1) // C

    def body(i, acc):
        off = (start + i * C).astype(I32)
        bw = jax.lax.dynamic_slice(
            binsP, (off, jnp.asarray(0, I32)), (C, Gs))
        bwl = _logical_bins(bw, layout, gc.packed_4bit)
        m = (arangeC < (length - i * C)).astype(jnp.float32)
        gw = jax.lax.dynamic_slice(grad, (off,), (C,)) * m
        hw = jax.lax.dynamic_slice(hess, (off,), (C,)) * m
        return _hist_chunk_accum(acc, bwl, gw, hw, gc,
                                 layout.group_offset, W)

    acc = jax.lax.fori_loop(0, nch, body, _hist_acc_init(gc, Gl, W))
    return _hist_acc_finish(acc, gc, gw_global)


@functools.partial(
    jax.jit, static_argnames=("gc", "axis_name", "quant"))
def _grow_tree_partitioned_jit(layout: DataLayout, grad: jnp.ndarray,
                          hess: jnp.ndarray, bag_mask: jnp.ndarray,
                          meta: FeatureMeta, params: SplitParams,
                          feature_mask: jnp.ndarray, fix: FixInfo,
                          gc: GrowConfig, gw_global=None, axis_name=None,
                          cat: CatLayout = None,
                          extras: GrowExtras = None,
                          forced: ForcedInfo = None,
                          quant=None) -> TreeArrays:
    """Leaf-wise growth with O(rows-in-child) per-split work and no gathers.

    Same trees as grow_tree (up to f32 summation order); see the section
    comment above for the payload-sorting design. Row ids ride along as two
    f32 columns (4096*hi + lo, both < 2^23) so the pack matmul stays exact
    for any realistic per-shard row count.
    """
    if cat is None:
        cat = empty_cat_layout(gc.cat_width)
    if extras is None:
        extras = default_extras(gc.num_features)
    if forced is None:
        forced = empty_forced()
    ft = acc_dtype(gc.use_dp)
    n = layout.bins.shape[0]
    L = gc.num_leaves
    TB = gc.total_bins
    F = gc.num_features
    G = layout.bins.shape[1]
    C = max(256, int(gc.window_chunk))
    if F == 0 or TB == 0:
        return _single_leaf_tree(n, L, gc.cat_width, grad, hess, bag_mask,
                                 params, axis_name, ft), extras.feature_used
    grad = grad.astype(jnp.float32)
    hess = hess.astype(jnp.float32)
    bagf = bag_mask.astype(jnp.float32)
    bdt = layout.bins.dtype
    goff = layout.group_offset

    def psum(x):
        if axis_name is None or gc.parallel_mode == "feature":
            return x
        return jax.lax.psum(x, axis_name)

    # rank-uniform quantization-seed base (see _grow_tree_jit)
    _qkey = jnp.asarray(extras.key, jnp.uint32)[0].astype(I32)

    def hist_psum(x, stage):
        """Histogram-plane reduction over the mesh — int16 codes on the
        wire under ``quant`` (ops/quantize.plane_psum)."""
        if axis_name is None or gc.parallel_mode != "data":
            return x
        g_r, h_r = plane_psum("psum:hist_plane", x[..., 0], x[..., 1],
                              axis_name, quant, quant_tag(_qkey, stage))
        return jnp.stack([g_r, h_r], axis=-1)

    # ---- padded payload buffers ----------------------------------------
    # PAD covers the per-split C-windows, the CB copy-back windows, and the
    # root's bigger chunks (dynamic_slice clamps out-of-range starts, which
    # would silently shift a window onto the wrong rows — padding keeps
    # every slice in range)
    CB = C                       # copy-back chunk (larger hurts small leaves)
    CR = min(max(C, 65536), max(C, n))
    PAD = max(2 * C, CB, CR)
    # row ids share a u32 with the bag bit
    assert n + PAD < (1 << 30), "per-shard row count must be < 2^30"
    binsP0 = jnp.concatenate([layout.bins, jnp.zeros((PAD, G), bdt)])
    gradP0 = jnp.concatenate([grad, jnp.zeros((PAD,), jnp.float32)])
    hessP0 = jnp.concatenate([hess, jnp.zeros((PAD,), jnp.float32)])
    bagP0 = jnp.concatenate([bag_mask, jnp.zeros((PAD,), BOOL)])
    rbP0 = (jnp.arange(n + PAD, dtype=U32)
            | (bagP0.astype(U32) << U32(30)))

    # ---- root ----------------------------------------------------------
    # root histogram streams the (identity-ordered) payload in big chunks;
    # the XLA einsum path materializes a [chunk, G, W] one-hot, so cap its
    # chunk (the Pallas kernel re-tiles internally and takes the full CR)
    root_chunk = CR if gc.hist_impl != "onehot" else min(CR, 8192)
    root_hist = _hist_contiguous(binsP0, gradP0 * bagP0, hessP0 * bagP0,
                                 layout, jnp.asarray(0, I32),
                                 jnp.asarray(n, I32), root_chunk, gc,
                                 gw_global)
    root_hist = hist_psum(root_hist, jnp.asarray(0, I32))
    sum_grad = psum(jnp.sum(grad * bagf, dtype=ft))
    sum_hess = psum(jnp.sum(hess * bagf, dtype=ft))
    root_count = psum(jnp.sum(bag_mask, dtype=I32))
    if gc.parallel_mode != "voting":
        # voting keeps hists LOCAL; the repair runs on the selectively
        # reduced winner bins inside eval_leaf
        root_hist = fix_histogram(root_hist, sum_grad, sum_hess,
                                  fix.mf_global, fix.start, fix.end,
                                  max_w=gc.scan_width, use_dp=gc.use_dp)

    feat_nb = meta.bin_end - meta.bin_start
    pcast = params.cast(ft)
    eval_leaf = _make_eval_leaf(meta, params, feature_mask, cat, gc,
                                extras, feat_nb, axis_name=axis_name,
                                fix=fix, quant=quant)
    eval_leaf.set_num_groups(layout.group_offset.shape[0])
    eval_pair_fused = (_make_eval_pair_fused(
        meta, params, feature_mask, cat, gc, axis_name=axis_name,
        feat_nb=feat_nb, num_groups=layout.group_offset.shape[0],
        quant=quant, extras=extras)
        if gc.scan_impl == "pallas" else None)
    feature_used0 = extras.feature_used

    root_cand = eval_leaf(root_hist, sum_grad, sum_hess, root_count,
                          jnp.asarray(0, I32), jnp.asarray(-jnp.inf, ft),
                          jnp.asarray(jnp.inf, ft), _root_key(extras),
                          feature_used0)
    root_out = _leaf_output_unconstrained(
        sum_grad, sum_hess, pcast.lambda_l1, pcast.lambda_l2,
        pcast.max_delta_step)

    SS = n + 2 * C + CB          # scratch size (write top + read slack)
    state = _PartState(
        s=jnp.asarray(1, I32),
        done=jnp.asarray(False),
        fidx=jnp.asarray(0, I32),
        binsP=binsP0,
        gradP=gradP0,
        hessP=hessP0,
        rbP=rbP0,
        posL=jnp.zeros((n + PAD,), I32),
        binsS=jnp.zeros((SS, G), bdt),
        gradS=jnp.zeros((SS,), jnp.float32),
        hessS=jnp.zeros((SS,), jnp.float32),
        rbS=jnp.zeros((SS,), U32),
        leaf_start=jnp.zeros((L,), I32),
        leaf_nrows=jnp.zeros((L,), I32).at[0].set(n),
        leaf_hist=jnp.zeros((L, TB, 2), hist_ft(gc)).at[0].set(root_hist),
        leaf_sum_grad=jnp.zeros((L,), ft).at[0].set(sum_grad),
        leaf_sum_hess=jnp.zeros((L,), ft).at[0].set(sum_hess),
        leaf_count=jnp.zeros((L,), I32).at[0].set(root_count),
        leaf_value=jnp.zeros((L,), ft).at[0].set(root_out),
        leaf_depth=jnp.zeros((L,), I32),
        leaf_cmin=jnp.full((L,), -jnp.inf, ft),
        leaf_cmax=jnp.full((L,), jnp.inf, ft),
        feature_used=feature_used0,
        best=jax.tree.map(
            lambda a: jnp.broadcast_to(a, (L,) + a.shape),
            _root_candidate_dummy(gc.cat_width, ft)),
        tree=_empty_tree_arrays(n, L, gc.cat_width, ft),
    )
    state = state._replace(
        best=jax.tree.map(lambda a, v: a.at[0].set(v), state.best, root_cand))

    W = gw_global.shape[1] if gw_global is not None else 0
    arangeC = jnp.arange(C, dtype=I32)

    def cond(st: _PartState):
        return (~st.done) & (st.s < L)

    def body(st: _PartState) -> _PartState:
        l, cand, do, done_new, fidx = _select_with_forced(
            st.fidx, st.best, st.leaf_hist, st.leaf_sum_grad,
            st.leaf_sum_hess, st.leaf_count, forced, meta, params, gc, ft)
        s = st.s
        s0 = st.leaf_start[l]
        n_l = jnp.where(do, st.leaf_nrows[l], 0)
        f = jnp.maximum(cand.feature, 0)
        g = layout.group_of[f]
        fmeta = (feat_nb[f], meta.missing_type[f], meta.default_bin[f],
                 layout.most_freq_bin[f])
        # which child is smaller is known BEFORE partitioning from the
        # candidate's (hessian-recovered) counts; a rare mismatch with the
        # exact row counts only swaps which side takes the subtraction
        smaller_is_left = cand.left_count <= cand.right_count

        # ---- pass A: partition + pack + fused smaller-child histogram ----
        nch = (n_l + C - 1) // C

        def pa_body(i, carry):
            (binsS, gradS, hessS, rbS, lf, rf, bag_left, hacc) = carry
            off = (s0 + i * C).astype(I32)
            bw = jax.lax.dynamic_slice(st.binsP,
                                       (off, jnp.asarray(0, I32)), (C, G))
            gw = jax.lax.dynamic_slice(st.gradP, (off,), (C,))
            hw = jax.lax.dynamic_slice(st.hessP, (off,), (C,))
            rbw = jax.lax.dynamic_slice(st.rbP, (off,), (C,))
            bgw = (rbw >> U32(30)) & U32(1)
            valid = arangeC < (n_l - i * C)

            col = _logical_col(bw, g, layout, gc.packed_4bit) + goff[g]
            in_range = (col >= meta.bin_start[f]) & (col < meta.bin_end[f])
            local_bin = col - meta.bin_start[f]
            go_left = _go_left_decision(local_bin, in_range, fmeta, cand,
                                        gc.cat_width)
            gl = valid & go_left
            gr = valid & ~go_left
            nL = jnp.sum(gl, dtype=I32)
            nR = jnp.sum(gr, dtype=I32)
            # pack orders the chunk [left | dropped | right]; writing the
            # whole packed block at lf puts the left block in place, writing
            # it again at rf - C puts the right block's end exactly at rf
            key = jnp.where(gl, U32(0), jnp.where(gr, U32(2), U32(1)))
            pb, pg, ph, prb = _pack_sort(key, bw, gw, hw, rbw,
                                         _bits_of(bdt))

            # scratch layout: left blocks stack up from 0, right blocks
            # stack down from n+2C; the 2C padding keeps the two whole-[C]
            # writes inside the gap, so they never clobber packed blocks
            binsS = jax.lax.dynamic_update_slice(binsS, pb, (lf, jnp.asarray(0, I32)))
            gradS = jax.lax.dynamic_update_slice(gradS, pg, (lf,))
            hessS = jax.lax.dynamic_update_slice(hessS, ph, (lf,))
            rbS = jax.lax.dynamic_update_slice(rbS, prb, (lf,))
            binsS = jax.lax.dynamic_update_slice(binsS, pb, (rf - C, jnp.asarray(0, I32)))
            gradS = jax.lax.dynamic_update_slice(gradS, pg, (rf - C,))
            hessS = jax.lax.dynamic_update_slice(hessS, ph, (rf - C,))
            rbS = jax.lax.dynamic_update_slice(rbS, prb, (rf - C,))

            bag_left = bag_left + jnp.sum(gl & (bgw > 0), dtype=I32)
            m = (valid & (go_left == smaller_is_left)).astype(jnp.float32)
            hacc = _hist_chunk_accum(hacc,
                                     _logical_bins(bw, layout,
                                                   gc.packed_4bit),
                                     gw * m, hw * m, gc, goff, W)
            return (binsS, gradS, hessS, rbS,
                    lf + nL, rf - nR, bag_left, hacc)

        (binsS, gradS, hessS, rbS, n_left, rf_end, bag_left,
         hacc) = jax.lax.fori_loop(
            0, nch, pa_body,
            (st.binsS, st.gradS, st.hessS, st.rbS,
             jnp.asarray(0, I32), jnp.asarray(n + 2 * C, I32),
             jnp.asarray(0, I32),
             _hist_acc_init(gc, layout.group_offset.shape[0], W)))
        n_right = n_l - n_left

        hist_smaller = hist_psum(_hist_acc_finish(hacc, gc, gw_global),
                                 s)

        left_cnt = psum(bag_left)
        right_cnt = st.leaf_count[l] - left_cnt

        # ---- pass B: copy packed blocks back (contiguous, masked tails;
        # CB-wide chunks — currently CB = C, wider measured slower because
        # every split pays two whole-CB minimum passes) --
        nchL = (n_left + CB - 1) // CB
        nchR = (n_right + CB - 1) // CB
        right_src0 = jnp.asarray(n + 2 * C, I32) - n_right
        arangeCB = jnp.arange(CB, dtype=I32)

        def copy_back(j, carry, src0, dst0, count, stamp):
            binsP, gradP, hessP, rbP, posL = carry
            src = (src0 + j * CB).astype(I32)
            dst = (dst0 + j * CB).astype(I32)
            keep = arangeCB < (count - j * CB)

            def blend(P, S, is2d):
                if is2d:
                    z = jnp.asarray(0, I32)
                    new = jax.lax.dynamic_slice(S, (src, z), (CB, G))
                    old = jax.lax.dynamic_slice(P, (dst, z), (CB, G))
                    out = jnp.where(keep[:, None], new, old)
                    return jax.lax.dynamic_update_slice(P, out, (dst, z))
                new = jax.lax.dynamic_slice(S, (src,), (CB,))
                old = jax.lax.dynamic_slice(P, (dst,), (CB,))
                return jax.lax.dynamic_update_slice(
                    P, jnp.where(keep, new, old), (dst,))

            binsP = blend(binsP, binsS, True)
            gradP = blend(gradP, gradS, False)
            hessP = blend(hessP, hessS, False)
            rbP = blend(rbP, rbS, False)
            if stamp is not None:
                oldp = jax.lax.dynamic_slice(posL, (dst,), (CB,))
                posL = jax.lax.dynamic_update_slice(
                    posL, jnp.where(keep, stamp, oldp), (dst,))
            return binsP, gradP, hessP, rbP, posL

        carry0 = (st.binsP, st.gradP, st.hessP, st.rbP, st.posL)
        carry1 = jax.lax.fori_loop(
            0, nchL,
            lambda j, c: copy_back(j, c, jnp.asarray(0, I32), s0,
                                   n_left, None),
            carry0)
        binsP, gradP, hessP, rbP, posL = jax.lax.fori_loop(
            0, nchR,
            lambda j, c: copy_back(j, c, right_src0, s0 + n_left,
                                   n_right, s),
            carry1)

        # ---- histograms for both children --------------------------------
        sm_sum_grad = jnp.where(smaller_is_left, cand.left_sum_grad,
                                cand.right_sum_grad)
        sm_sum_hess = jnp.where(smaller_is_left, cand.left_sum_hess,
                                cand.right_sum_hess)
        if gc.parallel_mode != "voting":
            hist_smaller = fix_histogram(
                hist_smaller, sm_sum_grad, sm_sum_hess, fix.mf_global,
                fix.start, fix.end, max_w=gc.scan_width, use_dp=gc.use_dp)
        parent_hist = st.leaf_hist[l]
        hist_larger = parent_hist - hist_smaller
        hist_left = jnp.where(smaller_is_left, hist_smaller, hist_larger)
        hist_right = jnp.where(smaller_is_left, hist_larger, hist_smaller)

        depth_child = st.leaf_depth[l] + 1
        mono = meta.monotone[f]
        l_cmin, l_cmax, r_cmin, r_cmax = _mono_bounds(
            st.leaf_cmin[l], st.leaf_cmax[l], mono, cand.left_output,
            cand.right_output, ft)

        def upd(a, new_l, new_s):
            a = a.at[l].set(jnp.where(do, new_l, a[l]))
            return a.at[s].set(jnp.where(do, new_s, a[s]))

        # big-buffer update with liveness-safe fallbacks: materialize both
        # write values behind an optimization barrier so XLA cannot re-fuse
        # the parent_hist slice into the DUS fusions (that would keep the
        # carried buffer alive and force a full copy)
        val_l, val_r = jax.lax.optimization_barrier(
            (jnp.where(do, hist_left, parent_hist),
             jnp.where(do, hist_right, jnp.zeros_like(hist_right))))
        leaf_hist = st.leaf_hist.at[l].set(val_l).at[s].set(val_r)
        leaf_sum_grad = upd(st.leaf_sum_grad, cand.left_sum_grad,
                            cand.right_sum_grad)
        leaf_sum_hess = upd(st.leaf_sum_hess, cand.left_sum_hess,
                            cand.right_sum_hess)
        leaf_count = upd(st.leaf_count, left_cnt, right_cnt)
        leaf_value = upd(st.leaf_value, cand.left_output, cand.right_output)
        leaf_depth = upd(st.leaf_depth, depth_child, depth_child)
        leaf_cmin = upd(st.leaf_cmin, l_cmin, r_cmin)
        leaf_cmax = upd(st.leaf_cmax, l_cmax, r_cmax)
        leaf_start = st.leaf_start.at[s].set(
            jnp.where(do, s0 + n_left, st.leaf_start[s]))
        leaf_nrows = upd(st.leaf_nrows, n_left, n_right)

        feature_used = st.feature_used
        if gc.use_cegb:
            feature_used = feature_used.at[f].set(feature_used[f] | do)

        # children evaluated from the updated buffer (in-place DUS; see
        # grow_tree body comment)
        if eval_pair_fused is not None:
            cand_l, cand_r = eval_pair_fused(
                leaf_hist, l, s, cand, left_cnt, right_cnt, depth_child)
        else:
            cand_l, cand_r = _eval_children(
                eval_leaf, leaf_hist, l, s, cand, left_cnt, right_cnt,
                depth_child, l_cmin, l_cmax, r_cmin, r_cmax,
                _split_keys(extras, s), feature_used)
        best = jax.tree.map(
            lambda a, vl, vr: a.at[l].set(jnp.where(do, vl, a[l]))
                               .at[s].set(jnp.where(do, vr, a[s])),
            st.best, cand_l, cand_r)

        tree = _record_split(st.tree, s - 1, do, l, cand, st.leaf_value[l],
                             st.leaf_count[l], s)
        return st._replace(
            s=s + do.astype(I32), done=done_new, fidx=fidx,
            binsP=binsP, gradP=gradP, hessP=hessP, rbP=rbP,
            posL=posL, binsS=binsS, gradS=gradS, hessS=hessS, rbS=rbS,
            leaf_start=leaf_start, leaf_nrows=leaf_nrows,
            leaf_hist=leaf_hist, leaf_sum_grad=leaf_sum_grad,
            leaf_sum_hess=leaf_sum_hess, leaf_count=leaf_count,
            leaf_value=leaf_value, leaf_depth=leaf_depth,
            leaf_cmin=leaf_cmin, leaf_cmax=leaf_cmax,
            feature_used=feature_used, best=best,
            tree=tree)

    final = jax.lax.while_loop(cond, body, state)
    # per-row leaf ids in original row order: one scatter through the carried
    # row ids (rbP[:n] & rid-mask is a permutation of 0..n-1)
    rid = (final.rbP[:n] & U32((1 << 30) - 1)).astype(I32)
    row_leaf = jnp.zeros((n,), I32).at[rid].set(
        final.posL[:n], mode="drop", unique_indices=True)
    return final.tree._replace(
        num_leaves=final.s,
        leaf_value=final.leaf_value,
        leaf_count=final.leaf_count,
        leaf_weight=final.leaf_sum_hess,
        row_leaf=row_leaf,
    ), final.feature_used


# public entry points: telemetry-wrapped dispatch of the jitted growers
# (telemetry.events.launch_wrapper — tracer_arg=1 is `grad`, so calls traced
# into the fused K-iteration scans are tagged "(trace)" not "(launch)")
grow_tree = telemetry.launch_wrapper(
    _grow_tree_jit, "ops::grow_tree", category="ops", tracer_arg=1)
grow_tree_partitioned = telemetry.launch_wrapper(
    _grow_tree_partitioned_jit, "ops::grow_tree_partitioned",
    category="ops", tracer_arg=1)
