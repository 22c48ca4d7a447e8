"""Best-split search over histograms, vectorized over a dense [F, W] grid.

TPU-native equivalent of the reference per-feature sequential scan
(FeatureHistogram::FindBestThresholdSequentially,
src/treelearner/feature_histogram.hpp:770-948, and
FindBestThresholdCategoricalInner, :263-474). The reference walks each
feature's bins twice (REVERSE and forward) accumulating running sums; here
the flat [total_bins] histogram is gathered once into a dense
[num_features, max_w] grid (max_w = widest feature, <= max_bin+1) and both
directions become cumulative sums along the W axis — plain vectorized ops
with no segment scatters, which matters on TPU where scatter serializes.
The validity `continue`/`break` conditions become masks (all break
conditions are monotone along the scan so masking is exactly equivalent),
and the argmax tie-breaking reproduces the reference's first-maximum
semantics:
  * REVERSE scans thresholds high->low, ties keep the highest threshold;
  * forward beats REVERSE only on strictly greater gain
    (feature_histogram.hpp:924);
  * across features, equal gain keeps the smaller feature index
    (SplitInfo::operator>, src/treelearner/split_info.hpp:126-153).

Missing-value semantics (feature_histogram.hpp:141-208):
  * MissingType::None (or num_bin<=2): single REVERSE scan, default_left=true;
  * MissingType::Zero & num_bin>2: both scans SKIP the default (zero) bin —
    zeros implicitly travel with the non-accumulated side;
  * MissingType::NaN & num_bin>2: REVERSE excludes the NaN bin from the right
    side (missing goes left), forward never accumulates it (missing goes
    right);
  * MissingType::NaN & num_bin<=2: single REVERSE scan, default_left=false.

Gain/leaf-output math mirrors GetSplitGains / GetLeafGain /
CalculateSplittedLeafOutput (feature_histogram.hpp:656-768) including L1
thresholding, max_delta_step clamping, monotone-constraint clipping, the
kEpsilon hessian adjustments (:87, :786, :848) and the count-from-hessian
recovery Common::RoundInt(hess * cnt_factor) (:783).

Precision: `use_dp` selects f64 (bit-faithful to the reference CPU learner;
the CPU-backend default) or f32 accumulation/gain math (the TPU default —
the same trade the reference GPU learner makes with gpu_use_dp=false,
docs/GPU-Performance.rst:43-47; f64 is software-emulated on TPU).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

F64 = jnp.float64
F32 = jnp.float32
I32 = jnp.int32

# reference include/LightGBM/meta.h:51-55
K_EPSILON = 1e-15
K_MIN_SCORE = -jnp.inf

MISSING_NONE = 0
MISSING_ZERO = 1
MISSING_NAN = 2


def acc_dtype(use_dp: bool):
    return F64 if use_dp else F32


class FeatureMeta(NamedTuple):
    """Static per-dataset feature layout on device (analog of FeatureMetainfo,
    feature_histogram.hpp:25-42, plus the global-bin layout)."""
    feat_id: jnp.ndarray        # [TB] i32: feature owning each global bin
    bin_start: jnp.ndarray      # [F] i32 global bin range start
    bin_end: jnp.ndarray        # [F] i32 global bin range end (exclusive)
    missing_type: jnp.ndarray   # [F] i32
    default_bin: jnp.ndarray    # [F] i32 (local bin of value 0.0)
    monotone: jnp.ndarray       # [F] i32 in {-1,0,1}
    is_categorical: jnp.ndarray  # [F] bool
    penalty: jnp.ndarray        # [F] f64 (feature_contri)


class SplitParams(NamedTuple):
    """Per-config scalars (jnp 0-d arrays so value changes don't recompile)."""
    lambda_l1: jnp.ndarray
    lambda_l2: jnp.ndarray
    max_delta_step: jnp.ndarray
    min_gain_to_split: jnp.ndarray
    min_data_in_leaf: jnp.ndarray
    min_sum_hessian_in_leaf: jnp.ndarray
    # categorical
    max_cat_threshold: jnp.ndarray
    max_cat_to_onehot: jnp.ndarray
    cat_smooth: jnp.ndarray
    cat_l2: jnp.ndarray
    min_data_per_group: jnp.ndarray

    @classmethod
    def from_config(cls, cfg) -> "SplitParams":
        return cls(
            lambda_l1=jnp.asarray(cfg.lambda_l1, F64),
            lambda_l2=jnp.asarray(cfg.lambda_l2, F64),
            max_delta_step=jnp.asarray(cfg.max_delta_step, F64),
            min_gain_to_split=jnp.asarray(cfg.min_gain_to_split, F64),
            min_data_in_leaf=jnp.asarray(cfg.min_data_in_leaf, I32),
            min_sum_hessian_in_leaf=jnp.asarray(cfg.min_sum_hessian_in_leaf, F64),
            max_cat_threshold=jnp.asarray(cfg.max_cat_threshold, I32),
            max_cat_to_onehot=jnp.asarray(cfg.max_cat_to_onehot, I32),
            cat_smooth=jnp.asarray(cfg.cat_smooth, F64),
            cat_l2=jnp.asarray(cfg.cat_l2, F64),
            min_data_per_group=jnp.asarray(cfg.min_data_per_group, I32),
        )

    def cast(self, ft):
        """Float fields in the accumulation dtype (ints untouched)."""
        return self._replace(
            lambda_l1=self.lambda_l1.astype(ft),
            lambda_l2=self.lambda_l2.astype(ft),
            max_delta_step=self.max_delta_step.astype(ft),
            min_gain_to_split=self.min_gain_to_split.astype(ft),
            min_sum_hessian_in_leaf=self.min_sum_hessian_in_leaf.astype(ft),
            cat_smooth=self.cat_smooth.astype(ft),
            cat_l2=self.cat_l2.astype(ft),
        )


class SplitCandidate(NamedTuple):
    """Best split of one leaf (analog of SplitInfo, split_info.hpp)."""
    gain: jnp.ndarray           # ft; -inf when unsplittable
    feature: jnp.ndarray        # i32 inner feature id; -1 when none
    threshold: jnp.ndarray      # i32 local bin threshold (numerical)
    default_left: jnp.ndarray   # bool
    left_output: jnp.ndarray    # ft
    right_output: jnp.ndarray   # ft
    left_sum_grad: jnp.ndarray  # ft
    left_sum_hess: jnp.ndarray  # ft
    right_sum_grad: jnp.ndarray
    right_sum_hess: jnp.ndarray
    left_count: jnp.ndarray     # i32 (hessian-recovered, reference semantics)
    right_count: jnp.ndarray    # i32
    is_cat: jnp.ndarray         # bool
    cat_mask: jnp.ndarray       # [CAT_W] bool over local bins going LEFT


def _round_int(x):
    # Common::RoundInt: int(x + 0.5)
    return jnp.floor(x + 0.5).astype(I32)


def _threshold_l1(s, l1, use_l1: bool = True):
    # feature_histogram.hpp:659; the use_l1=False specialization mirrors the
    # reference's USE_L1 template parameter (identity when lambda_l1 == 0)
    if not use_l1:
        return s
    return jnp.sign(s) * jnp.maximum(0.0, jnp.abs(s) - l1)


def _leaf_output_unconstrained(g, h, l1, l2, mds, use_l1: bool = True,
                               use_mds: bool = True):
    # CalculateSplittedLeafOutput, feature_histogram.hpp:664-685
    ret = -_threshold_l1(g, l1, use_l1) / (h + l2)
    if not use_mds:
        return ret
    clipped = jnp.sign(ret) * jnp.minimum(jnp.abs(ret), mds)
    return jnp.where(mds > 0, clipped, ret)


def _leaf_output(g, h, l1, l2, mds, cmin, cmax, use_mc: bool,
                 use_l1: bool = True, use_mds: bool = True):
    ret = _leaf_output_unconstrained(g, h, l1, l2, mds, use_l1, use_mds)
    if use_mc:
        ret = jnp.clip(ret, cmin, cmax)
    return ret


def _leaf_gain_given_output(g, h, l1, l2, out, use_l1: bool = True):
    # feature_histogram.hpp:757-768
    sg = _threshold_l1(g, l1, use_l1)
    return -(2.0 * sg * out + (h + l2) * out * out)


def _leaf_gain(g, h, l1, l2, mds, use_l1: bool = True, use_mds: bool = True):
    # feature_histogram.hpp:739-755 (USE_MAX_OUTPUT specialization)
    sg = _threshold_l1(g, l1, use_l1)
    plain = sg * sg / (h + l2)
    if not use_mds:
        return plain
    out = _leaf_output_unconstrained(g, h, l1, l2, mds, use_l1, True)
    with_mds = _leaf_gain_given_output(g, h, l1, l2, out, use_l1)
    return jnp.where(mds > 0, with_mds, plain)


def _split_gains(gl, hl, gr, hr, l1, l2, mds, cmin, cmax, mono, use_mc: bool,
                 use_l1: bool = True, use_mds: bool = True):
    # GetSplitGains, feature_histogram.hpp:704-737
    if not use_mc:
        return (_leaf_gain(gl, hl, l1, l2, mds, use_l1, use_mds)
                + _leaf_gain(gr, hr, l1, l2, mds, use_l1, use_mds))
    lo = _leaf_output(gl, hl, l1, l2, mds, cmin, cmax, True, use_l1, use_mds)
    ro = _leaf_output(gr, hr, l1, l2, mds, cmin, cmax, True, use_l1, use_mds)
    bad = ((mono > 0) & (lo > ro)) | ((mono < 0) & (lo < ro))
    gain = (_leaf_gain_given_output(gl, hl, l1, l2, lo, use_l1)
            + _leaf_gain_given_output(gr, hr, l1, l2, ro, use_l1))
    return jnp.where(bad, 0.0, gain)


def _resolve_w(tb: int, max_w: int) -> int:
    """Static dense scan width: widest feature (caller-supplied) or a safe
    upper bound for small problems."""
    if max_w and max_w > 0:
        return int(max_w)
    return int(min(tb, 256))


@functools.partial(jax.jit, static_argnames=("max_w", "use_dp"))
def fix_histogram(hist, sum_grad, sum_hess, fix_mf_global, fix_start, fix_end,
                  max_w: int = 0, use_dp: bool = True):
    """Reconstruct bundled features' most_freq bins from leaf totals.

    TPU equivalent of Dataset::FixHistogram (src/io/dataset.cpp:1410): rows at
    a bundled sub-feature's most frequent bin are not materialized in the
    group column, so hist[most_freq] = leaf_total - sum(feature's other bins).
    fix_* arrays index only the features that live in multi-feature bundles.
    """
    if fix_mf_global.shape[0] == 0:
        return hist
    ft = acc_dtype(use_dp)
    tb = hist.shape[0]
    W = _resolve_w(tb, max_w)
    w = jnp.arange(W, dtype=I32)[None, :]
    gidx = jnp.clip(fix_start[:, None] + w, 0, tb - 1)          # [K, W]
    valid = w < (fix_end - fix_start)[:, None]
    vals = hist[gidx].astype(ft) * valid[..., None]             # [K, W, 2]
    tot = vals.sum(axis=1)                                      # [K, 2]
    leaf_tot = jnp.stack([sum_grad, sum_hess]).astype(ft)       # [2]
    corrected = leaf_tot[None, :] - (tot - hist[fix_mf_global].astype(ft))
    return hist.at[fix_mf_global].set(corrected.astype(hist.dtype))


@functools.partial(jax.jit,
                   static_argnames=("use_mc", "num_features", "max_w",
                                    "use_dp", "use_l1", "use_mds",
                                    "feat_gains_only"))
def find_best_split_numerical(hist, sum_grad, sum_hess, num_data,
                              meta: FeatureMeta, p: SplitParams,
                              cmin, cmax, feature_mask,
                              num_features: int, use_mc: bool = False,
                              max_w: int = 0, use_dp: bool = True,
                              use_l1: bool = True, use_mds: bool = True,
                              rand_bins=None, gain_penalty=None,
                              feat_gains_only: bool = False):
    """Best numerical split for one leaf over all features at once.

    hist: [TB, 2] f32; sums are leaf totals; num_data i32 (reference
    semantics: in-bag count). Returns a SplitCandidate of scalars (cat fields
    dummy). Mirrors the dispatch in FuncForNumricalL2
    (feature_histogram.hpp:141-208) and both scan directions.
    """
    ft = acc_dtype(use_dp)
    tb = hist.shape[0]
    F = num_features
    W = _resolve_w(tb, max_w)
    p = p.cast(ft)
    sum_grad = sum_grad.astype(ft)
    sum_hess = sum_hess.astype(ft)
    cmin = jnp.asarray(cmin).astype(ft)
    cmax = jnp.asarray(cmax).astype(ft)

    start = meta.bin_start[:, None]                       # [F, 1]
    nb = (meta.bin_end - meta.bin_start)[:, None]         # [F, 1]
    w = jnp.arange(W, dtype=I32)[None, :]                 # [1, W]
    in_feat = w < nb                                      # [F, W]
    gidx = jnp.clip(start + w, 0, tb - 1)
    mt = meta.missing_type[:, None]
    d_local = meta.default_bin[:, None]
    mono = meta.monotone.astype(ft)                       # [F]

    sum_hess_adj = sum_hess + 2 * K_EPSILON
    cnt_factor = num_data.astype(ft) / sum_hess_adj
    min_data = p.min_data_in_leaf
    min_hess = p.min_sum_hessian_in_leaf

    gain_shift = _leaf_gain(sum_grad, sum_hess_adj, p.lambda_l1, p.lambda_l2,
                            p.max_delta_step, use_l1, use_mds)
    min_gain_shift = gain_shift + p.min_gain_to_split

    grad_b = jnp.where(in_feat, hist[gidx, 0].astype(ft), 0)
    hess_b = jnp.where(in_feat, hist[gidx, 1].astype(ft), 0)
    cnt_b = jnp.where(in_feat, _round_int(hess_b * cnt_factor), 0)

    two_scan = (nb > 2) & (mt != MISSING_NONE)
    skip_default = two_scan & (mt == MISSING_ZERO)
    na_as_missing = two_scan & (mt == MISSING_NAN)
    is_na_bin = w == (nb - 1)
    is_default_bin = w == d_local

    not_cat = ~meta.is_categorical
    fmask_f = (feature_mask & not_cat)[:, None]           # [F, 1]

    # ---------------- REVERSE scan (right accumulates from high bins) ------
    excl_r = (na_as_missing & is_na_bin) | (skip_default & is_default_bin)
    keep_r = (~excl_r).astype(ft)
    gr_c = jnp.cumsum(grad_b * keep_r, axis=1)
    hr_c = jnp.cumsum(hess_b * keep_r, axis=1)
    cr_c = jnp.cumsum(cnt_b * (~excl_r), axis=1)
    gr_tot = gr_c[:, -1:]
    hr_tot = hr_c[:, -1:]
    cr_tot = cr_c[:, -1:]
    sum_right_grad = gr_tot - gr_c
    sum_right_hess = hr_tot - hr_c + K_EPSILON
    right_cnt = cr_tot - cr_c
    left_cnt = num_data - right_cnt
    sum_left_grad = sum_grad - sum_right_grad
    sum_left_hess = sum_hess_adj - sum_right_hess

    valid_r = in_feat & (w <= nb - 2 - na_as_missing.astype(I32))
    valid_r &= ~(skip_default & (w == d_local - 1))
    valid_r &= (right_cnt >= min_data) & (sum_right_hess >= min_hess)
    valid_r &= (left_cnt >= min_data) & (sum_left_hess >= min_hess)
    valid_r &= fmask_f
    if rand_bins is not None:
        # extra_trees / USE_RAND (feature_histogram.hpp template arm): only
        # one randomly drawn threshold per feature is considered
        valid_r &= w == rand_bins[:, None]

    gains_r = _split_gains(sum_left_grad, sum_left_hess, sum_right_grad,
                           sum_right_hess, p.lambda_l1, p.lambda_l2,
                           p.max_delta_step, cmin, cmax, mono[:, None],
                           use_mc, use_l1, use_mds)
    valid_r &= gains_r > min_gain_shift
    gains_r = jnp.where(valid_r, gains_r, K_MIN_SCORE)

    # per-feature best, ties -> HIGHEST threshold (reverse scans high->low)
    best_gain_r = jnp.max(gains_r, axis=1)                # [F]
    at_max_r = valid_r & (gains_r == best_gain_r[:, None])
    best_t_r = jnp.max(jnp.where(at_max_r, w, -1), axis=1)

    # ---------------- forward scan (left accumulates from low bins) --------
    excl_f = skip_default & is_default_bin
    keep_f = (~excl_f).astype(ft)
    gl_c = jnp.cumsum(grad_b * keep_f, axis=1)
    hl_c = jnp.cumsum(hess_b * keep_f, axis=1)
    cl_c = jnp.cumsum(cnt_b * (~excl_f), axis=1)
    f_left_grad = gl_c
    f_left_hess = hl_c + K_EPSILON
    f_left_cnt = cl_c
    f_right_cnt = num_data - f_left_cnt
    f_right_grad = sum_grad - f_left_grad
    f_right_hess = sum_hess_adj - f_left_hess

    valid_f = two_scan & in_feat & (w <= nb - 2)
    valid_f &= ~(skip_default & is_default_bin)
    valid_f &= (f_left_cnt >= min_data) & (f_left_hess >= min_hess)
    valid_f &= (f_right_cnt >= min_data) & (f_right_hess >= min_hess)
    valid_f &= fmask_f
    if rand_bins is not None:
        valid_f &= w == rand_bins[:, None]

    gains_f = _split_gains(f_left_grad, f_left_hess, f_right_grad,
                           f_right_hess, p.lambda_l1, p.lambda_l2,
                           p.max_delta_step, cmin, cmax, mono[:, None],
                           use_mc, use_l1, use_mds)
    valid_f &= gains_f > min_gain_shift
    gains_f = jnp.where(valid_f, gains_f, K_MIN_SCORE)

    best_gain_f = jnp.max(gains_f, axis=1)
    at_max_f = valid_f & (gains_f == best_gain_f[:, None])
    big = jnp.iinfo(jnp.int32).max
    best_t_f = jnp.min(jnp.where(at_max_f, w, big), axis=1)

    # ---------------- combine directions per feature -----------------------
    has_r = best_t_r >= 0
    has_f = best_t_f < big
    best_gain_r = jnp.where(has_r, best_gain_r, K_MIN_SCORE)
    best_gain_f = jnp.where(has_f, best_gain_f, K_MIN_SCORE)
    use_f = best_gain_f > best_gain_r       # strict: ties keep REVERSE (:924)
    feat_gain = jnp.where(use_f, best_gain_f, best_gain_r)
    feat_t = jnp.where(use_f, best_t_f, best_t_r)
    # default_left=REVERSE(:946); NaN num_bin<=2 forces false (:205)
    f_nb = meta.bin_end - meta.bin_start
    forced_right = (meta.missing_type == MISSING_NAN) & (f_nb <= 2)
    feat_default_left = (~use_f) & (~forced_right)
    feat_valid = has_r | has_f

    # gain reported = best - shift, then * penalty (:89, :945)
    feat_gain_out = jnp.where(feat_valid,
                              (feat_gain - min_gain_shift)
                              * meta.penalty.astype(ft),
                              K_MIN_SCORE)
    if gain_penalty is not None:
        # CEGB DetlaGain subtracted per feature before the cross-feature
        # argmax (cost_effective_gradient_boosting.hpp:51-62)
        feat_gain_out = jnp.where(feat_valid,
                                  feat_gain_out - gain_penalty.astype(ft),
                                  K_MIN_SCORE)

    if feat_gains_only:
        # voting-parallel local scan: per-feature best gains, no payload
        # (LightSplitInfo, split_info.hpp — gain + feature is all the vote
        # needs)
        return feat_gain_out

    # ---------------- best feature (ties -> smaller index) -----------------
    best_f = jnp.argmax(feat_gain_out)      # first max = smallest feature id
    best_valid = feat_valid[best_f] & (feat_gain_out[best_f] > K_MIN_SCORE)
    bt = feat_t[best_f]

    b_use_f = use_f[best_f]

    # recover left sums at the chosen threshold
    lg = jnp.where(b_use_f, gl_c[best_f, bt],
                   sum_grad - (gr_tot[best_f, 0] - gr_c[best_f, bt]))
    lh = jnp.where(b_use_f, hl_c[best_f, bt] + K_EPSILON,
                   sum_hess_adj - (hr_tot[best_f, 0] - hr_c[best_f, bt]
                                   + K_EPSILON))
    lc = jnp.where(b_use_f, cl_c[best_f, bt],
                   num_data - (cr_tot[best_f, 0] - cr_c[best_f, bt]))
    rg = sum_grad - lg
    rh = sum_hess_adj - lh
    rc = num_data - lc

    cm_b, cx_b = (cmin, cmax) if use_mc else (-jnp.inf, jnp.inf)
    lo = _leaf_output(lg, lh, p.lambda_l1, p.lambda_l2, p.max_delta_step,
                      cm_b, cx_b, use_mc, use_l1, use_mds)
    ro = _leaf_output(rg, rh, p.lambda_l1, p.lambda_l2, p.max_delta_step,
                      cm_b, cx_b, use_mc, use_l1, use_mds)

    neg = jnp.asarray(K_MIN_SCORE, ft)
    return SplitCandidate(
        gain=jnp.where(best_valid, feat_gain_out[best_f], neg),
        feature=jnp.where(best_valid, best_f.astype(I32), -1),
        threshold=jnp.where(best_valid, bt, 0),
        default_left=jnp.where(best_valid, feat_default_left[best_f], True),
        left_output=lo, right_output=ro,
        left_sum_grad=lg, left_sum_hess=lh - K_EPSILON,
        right_sum_grad=rg, right_sum_hess=rh - K_EPSILON,
        left_count=lc.astype(I32), right_count=rc.astype(I32),
        is_cat=jnp.asarray(False),
        cat_mask=jnp.zeros((1,), dtype=bool),
    )


class CatLayout(NamedTuple):
    """Static gather layout for categorical features, built host-side once.

    cat_feature: [C] i32 inner feature id of each categorical feature
    gather_idx: [C, W] i32 global bin index of each local bin (clipped)
    bin_valid: [C, W] bool local bin < num_bin
    used_bin: [C] i32 (num_bin - 1 + is_full_categorical, hpp:281-282)
    num_bin: [C] i32
    """
    cat_feature: jnp.ndarray
    gather_idx: jnp.ndarray
    bin_valid: jnp.ndarray
    used_bin: jnp.ndarray
    num_bin: jnp.ndarray


def _cat_onehot_scan(grad_b, hess_b, cnt_b, used_mask, sum_grad, sum_hess_adj,
                     num_data, p: SplitParams, cmin, cmax, use_mc: bool):
    """One-hot categorical: each single bin vs rest
    (feature_histogram.hpp:291-338). Vectorized over the W bins."""
    hess_adj = hess_b + K_EPSILON
    other_grad = sum_grad - grad_b
    other_hess = sum_hess_adj - hess_b - K_EPSILON
    other_cnt = num_data - cnt_b
    ok = used_mask
    ok &= (cnt_b >= p.min_data_in_leaf) & (hess_b >= p.min_sum_hessian_in_leaf)
    ok &= (other_cnt >= p.min_data_in_leaf)
    ok &= (other_hess >= p.min_sum_hessian_in_leaf)
    zero = jnp.zeros((), grad_b.dtype)
    gains = _split_gains(other_grad, other_hess, grad_b, hess_adj,
                         p.lambda_l1, p.lambda_l2, p.max_delta_step,
                         cmin, cmax, zero, use_mc)
    gains = jnp.where(ok, gains, K_MIN_SCORE)
    t = jnp.argmax(gains)
    best_gain = gains[t]
    W = grad_b.shape[0]
    cat_mask = jnp.arange(W, dtype=I32) == t
    return (best_gain, cat_mask, grad_b[t], hess_adj[t], cnt_b[t])


def _cat_sorted_scan(grad_b, hess_b, cnt_b, used_mask, sum_grad, sum_hess_adj,
                     num_data, p: SplitParams, cmin, cmax, use_mc: bool,
                     steps: int):
    """Many-vs-many categorical: bins sorted by grad/hess ratio, prefix scans
    in both directions with the reference's stateful min_data_per_group
    bookkeeping (feature_histogram.hpp:339-432) as a lax.scan.

    The scan covers the first min(steps, W) sorted positions of each
    direction, ``steps`` being the config's max_cat_threshold: a position
    past it is never a candidate (``in_range``; the reference's loop stops
    at max_num_cat), so the bound changes no result. Under the width the
    steps are unrolled: a rolled loop of a few small ops an iteration cost
    the fused persist launch 1.4% on expo_cat.train_steady (TPU v5e)."""
    ft = grad_b.dtype
    W = grad_b.shape[0]
    l2 = p.lambda_l2 + p.cat_l2
    # filter: count >= cat_smooth (hpp:340-344; count vs cat_smooth is the
    # reference's comparison, odd but faithful)
    part = used_mask & (cnt_b.astype(ft) >= p.cat_smooth)
    ratio = grad_b / (hess_b + p.cat_smooth)
    ratio = jnp.where(part, ratio, jnp.inf)    # excluded bins sort last
    order = jnp.argsort(ratio, stable=True)    # ascending
    used_bin_cnt = jnp.sum(part.astype(I32))
    max_num_cat = jnp.minimum(p.max_cat_threshold, (used_bin_cnt + 1) // 2)
    n_steps = min(int(steps), W)

    g_s = grad_b[order]
    h_s = hess_b[order]
    c_s = cnt_b[order]
    valid_s = part[order]

    def direction(reverse: bool):
        if reverse:
            gd = jnp.where(valid_s, g_s, 0.0)[::-1]
            hd = jnp.where(valid_s, h_s, 0.0)[::-1]
            cd = jnp.where(valid_s, c_s, 0)[::-1]
            vd = valid_s[::-1]
            # roll so position 0 is the last USED bin
            shift = W - used_bin_cnt
            gd = jnp.roll(gd, -shift, 0)
            hd = jnp.roll(hd, -shift, 0)
            cd = jnp.roll(cd, -shift, 0)
            vd = jnp.roll(vd, -shift, 0)
        else:
            gd = jnp.where(valid_s, g_s, 0.0)
            hd = jnp.where(valid_s, h_s, 0.0)
            cd = jnp.where(valid_s, c_s, 0)
            vd = valid_s

        def step(carry, x):
            (sum_lg, sum_lh, left_cnt, cnt_grp, stopped, i) = carry
            g, h, c, v = x
            sum_lg = sum_lg + g
            sum_lh = sum_lh + h
            left_cnt = left_cnt + c
            cnt_grp = cnt_grp + c
            in_range = v & (i < max_num_cat) & (~stopped)
            right_cnt = num_data - left_cnt
            right_hess = sum_hess_adj - sum_lh
            brk = (right_cnt < p.min_data_in_leaf) \
                | (right_cnt < p.min_data_per_group) \
                | (right_hess < p.min_sum_hessian_in_leaf)
            stopped = stopped | (in_range & brk)
            ok = in_range & (~brk)
            ok &= (left_cnt >= p.min_data_in_leaf)
            ok &= (sum_lh >= p.min_sum_hessian_in_leaf)
            ok &= (cnt_grp >= p.min_data_per_group)
            gain = _split_gains(sum_lg, sum_lh, sum_grad - sum_lg,
                                sum_hess_adj - sum_lh, p.lambda_l1, l2,
                                p.max_delta_step, cmin, cmax,
                                jnp.zeros((), ft), use_mc)
            gain = jnp.where(ok, gain, K_MIN_SCORE)
            cnt_grp = jnp.where(ok, 0, cnt_grp)
            return ((sum_lg, sum_lh, left_cnt, cnt_grp, stopped, i + 1),
                    (gain, sum_lg, sum_lh, left_cnt))

        init = (jnp.asarray(0.0, ft), jnp.asarray(K_EPSILON, ft),
                jnp.asarray(0, I32), jnp.asarray(0, I32),
                jnp.asarray(False), jnp.asarray(0, I32))
        xs = tuple(x[:n_steps] for x in (gd, hd.astype(ft), cd, vd))
        _, (gains, lgs, lhs, lcs) = jax.lax.scan(
            step, init, xs, unroll=n_steps < W)
        i_best = jnp.argmax(gains)
        return gains[i_best], i_best, lgs[i_best], lhs[i_best], lcs[i_best]

    gain_f, i_f, lg_f, lh_f, lc_f = direction(False)
    gain_r, i_r, lg_r, lh_r, lc_r = direction(True)
    use_r = gain_r > gain_f
    best_gain = jnp.where(use_r, gain_r, gain_f)
    i_best = jnp.where(use_r, i_r, i_f)
    lg = jnp.where(use_r, lg_r, lg_f)
    lh = jnp.where(use_r, lh_r, lh_f)
    lc = jnp.where(use_r, lc_r, lc_f)
    # cat_mask over local bins: first i_best+1 sorted bins (or last, reversed)
    pos_of = jnp.argsort(order, stable=True)   # local bin -> sorted position
    fwd_mask = pos_of <= i_best
    rev_mask = pos_of >= (used_bin_cnt - 1 - i_best)
    cat_mask = jnp.where(use_r, rev_mask, fwd_mask) & part
    return best_gain, cat_mask, lg, lh, lc


@functools.partial(jax.jit, static_argnames=("use_mc", "use_dp", "steps"))
def find_best_split_categorical(hist, sum_grad, sum_hess, num_data,
                                cat: CatLayout, meta: FeatureMeta,
                                p: SplitParams, cmin, cmax, feature_mask,
                                use_mc: bool = False,
                                use_dp: bool = True,
                                gain_penalty=None, *,
                                steps: int) -> SplitCandidate:
    """Best categorical split over all categorical features of one leaf.

    Mirrors FindBestThresholdCategoricalInner (feature_histogram.hpp:263-474):
    one-hot when num_bin <= max_cat_to_onehot, else the sorted two-direction
    scan; the l2 used for outputs includes cat_l2 only in sorted mode.
    Returns a scalar SplitCandidate (feature -1 when nothing splits).
    steps: the sorted scan's bound, GrowConfig.max_cat_threshold (static;
    p.max_cat_threshold is its traced copy).
    """
    ft = acc_dtype(use_dp)
    C, W = cat.gather_idx.shape
    p = p.cast(ft)
    sum_grad = sum_grad.astype(ft)
    sum_hess = sum_hess.astype(ft)
    cmin = jnp.asarray(cmin).astype(ft)
    cmax = jnp.asarray(cmax).astype(ft)
    sum_hess_adj = sum_hess + 2 * K_EPSILON
    cnt_factor = num_data.astype(ft) / sum_hess_adj
    gain_shift = _leaf_gain(sum_grad, sum_hess_adj, p.lambda_l1, p.lambda_l2,
                            p.max_delta_step)
    min_gain_shift = gain_shift + p.min_gain_to_split

    def per_feature(f_idx, g_idx, valid, used_bin, nb):
        grad_b = hist[g_idx, 0].astype(ft)
        hess_b = hist[g_idx, 1].astype(ft)
        used_mask = valid & (jnp.arange(W, dtype=I32) < used_bin)
        grad_b = jnp.where(used_mask, grad_b, 0.0)
        hess_b = jnp.where(used_mask, hess_b, 0.0)
        cnt_b = _round_int(hess_b * cnt_factor)
        onehot = nb <= p.max_cat_to_onehot
        oh = _cat_onehot_scan(grad_b, hess_b, cnt_b, used_mask, sum_grad,
                              sum_hess_adj, num_data, p, cmin, cmax, use_mc)
        so = _cat_sorted_scan(grad_b, hess_b, cnt_b, used_mask, sum_grad,
                              sum_hess_adj, num_data, p, cmin, cmax, use_mc,
                              steps)
        gain, mask, lg, lh, lc = jax.tree.map(
            lambda a, b: jnp.where(onehot, a, b), oh, so)
        l2_out = jnp.where(onehot, p.lambda_l2, p.lambda_l2 + p.cat_l2)
        ok = (gain > min_gain_shift) & feature_mask[f_idx]
        gain_out = jnp.where(ok, (gain - min_gain_shift)
                             * meta.penalty[f_idx].astype(ft), K_MIN_SCORE)
        if gain_penalty is not None:
            gain_out = jnp.where(ok, gain_out - gain_penalty[f_idx].astype(ft),
                                 K_MIN_SCORE)
        return gain_out, mask, lg, lh, lc, l2_out

    if C == 0:
        z = jnp.asarray(0.0, ft)
        return SplitCandidate(
            gain=jnp.asarray(K_MIN_SCORE, ft), feature=jnp.asarray(-1, I32),
            threshold=jnp.asarray(0, I32), default_left=jnp.asarray(False),
            left_output=z, right_output=z, left_sum_grad=z,
            left_sum_hess=z, right_sum_grad=z, right_sum_hess=z,
            left_count=jnp.asarray(0, I32), right_count=jnp.asarray(0, I32),
            is_cat=jnp.asarray(False), cat_mask=jnp.zeros((W or 1,), bool))

    gains, masks, lgs, lhs, lcs, l2s = jax.vmap(per_feature)(
        cat.cat_feature, cat.gather_idx, cat.bin_valid, cat.used_bin,
        cat.num_bin)
    c = jnp.argmax(gains)
    best_valid = gains[c] > K_MIN_SCORE
    lg, lh, lc = lgs[c], lhs[c], lcs[c]
    rg = sum_grad - lg
    rh = sum_hess_adj - lh
    rc = num_data - lc
    l2b = l2s[c]
    cm_b, cx_b = (cmin, cmax) if use_mc else (-jnp.inf, jnp.inf)
    lo = _leaf_output(lg, lh, p.lambda_l1, l2b, p.max_delta_step,
                      cm_b, cx_b, use_mc)
    ro = _leaf_output(rg, rh, p.lambda_l1, l2b, p.max_delta_step,
                      cm_b, cx_b, use_mc)
    return SplitCandidate(
        gain=jnp.where(best_valid, gains[c], K_MIN_SCORE),
        feature=jnp.where(best_valid, cat.cat_feature[c], -1),
        threshold=jnp.asarray(0, I32),
        default_left=jnp.asarray(False),
        left_output=lo, right_output=ro,
        left_sum_grad=lg, left_sum_hess=lh - K_EPSILON,
        right_sum_grad=rg, right_sum_hess=rh - K_EPSILON,
        left_count=lc.astype(I32), right_count=rc.astype(I32),
        is_cat=jnp.asarray(True),
        cat_mask=masks[c],
    )


def find_best_split_numerical_batch(hist, sum_grad, sum_hess, num_data,
                                    meta: FeatureMeta, p: SplitParams,
                                    feature_mask, num_features: int,
                                    use_dp: bool = True,
                                    use_l1: bool = True,
                                    use_mds: bool = True,
                                    max_w: int = 0):
    """Best numerical split for a BATCH of leaves — vmap of
    :func:`find_best_split_numerical` over the leading leaf axis.

    This is the widened split-find of the persist grower's XLA kernel
    mode (and the batched find of the level-parallel grower): per leaf it
    reproduces the v1 scan's f64 gain accumulation, count recovery and
    tie-break rules EXACTLY (same function), so persist-f32-payload runs
    scored through it order splits identically to the v1 f64 grower —
    the fix for the historical persist-vs-v1 tie-flip on noise-gain
    splits (tests/test_known_divergence.py).

    hist: [B, TB, 2]; sum_grad/sum_hess: [B] leaf sums; num_data: [B]
    i32. Returns a SplitCandidate pytree of [B]-shaped leaves.
    """
    one = functools.partial(
        find_best_split_numerical, meta=meta, p=p,
        cmin=-jnp.inf, cmax=jnp.inf, feature_mask=feature_mask,
        num_features=num_features, use_mc=False, max_w=max_w,
        use_dp=use_dp, use_l1=use_l1, use_mds=use_mds)
    return jax.vmap(lambda h, sg, sh, nd: one(h, sg, sh, nd))(
        hist, sum_grad, sum_hess, num_data)


def merge_candidates(a: SplitCandidate, b: SplitCandidate) -> SplitCandidate:
    """Pick the better of two candidates (SplitInfo::operator>,
    split_info.hpp:126-153: higher gain wins; equal gain keeps the smaller
    feature id — matching the reference's single-loop scan order)."""
    b_wins = (b.gain > a.gain) | ((b.gain == a.gain)
                                  & (b.feature >= 0)
                                  & ((a.feature < 0)
                                     | (b.feature < a.feature)))

    def sel(x, y):
        # leaves may carry trailing dims (cat_mask [..., CAT_W]) and the
        # candidates may be batched (the fused pair scan merges [2]-shaped
        # candidate pairs): align the predicate to each leaf's rank
        w = b_wins.reshape(b_wins.shape + (1,) * (x.ndim - b_wins.ndim))
        return jnp.where(w, y, x)
    return jax.tree.map(sel, a, b)
