"""Bundle-native block scan (ops/pallas_scan.scan_blocks) equivalence.

The block kernel scans [G, 256] group planes directly; the established
per-feature kernel (scan_pair) scans one row per feature, each holding a
copy of its group block with window-offset masks — the layout the persist
grower used before the bundle-native path. Given the same histograms and
scalars, the best candidate per GROUP from scan_blocks must match the best
per-feature candidate within that group from scan_pair: same penalized
gain, absolute threshold lane, direction and left sums. The in-kernel
FixHistogram must match the explicit residual tensors the old eval_pair
materialized per split.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.analysis import strict_numerics
from lightgbm_tpu.ops.pallas_scan import (ScanLayout, build_block_scan_meta,
                                          scan_blocks, scan_pair)
from lightgbm_tpu.ops.split import FeatureMeta

W = 256


def _geometry():
    """3 groups: two EFB bundles + one singleton; mixed missing types."""
    group_of = np.array([0, 0, 1, 2], np.int32)
    ls = np.array([1, 9, 1, 0], np.int32)          # bundles reserve lane 0
    nb = np.array([8, 23, 60, 63], np.int32)
    mt = np.array([1, 2, 0, 2], np.int32)          # zero / nan / none / nan
    db = np.array([2, 0, 0, 5], np.int32)
    mf = np.array([0, 0, 0, 5], np.int32)
    needs_fix = np.array([True, True, True, False])
    penalty = np.array([1.0, 0.8, 1.0, 1.2])
    return group_of, ls, nb, mt, db, mf, needs_fix, penalty


def _feature_rows(blocks, group_of, Fp):
    """[2, Fp, W] per-feature rows: each feature gets a COPY of its whole
    group block (the pre-block-scan eval_pair layout)."""
    rows = np.take(blocks, group_of, axis=1)
    return np.pad(rows, ((0, 0), (0, Fp - len(group_of)), (0, 0)))


def _apply_fix(rows, sg, shr, ls, nb, mf, needs_fix):
    """The old out-of-kernel FixHistogram: most_freq lane gets
    child_total - window_sum for every needs-fix feature."""
    out = rows.copy()
    tot = np.array([sg, shr])
    for c in range(2):
        for v in range(2):
            for f in np.nonzero(needs_fix)[0]:
                wsum = rows[v][c, f, ls[f]:ls[f] + nb[f]].sum()
                out[v][c, f, ls[f] + mf[f]] += tot[v][c] - wsum
    return out


@pytest.mark.parametrize("seed", [0, 5])
def test_block_scan_matches_per_feature_kernel(seed):
    group_of, ls, nb, mt, db, mf, needs_fix, penalty = _geometry()
    F, G = len(group_of), 3
    rng = np.random.default_rng(seed)
    gb = rng.normal(size=(2, G, W)).astype(np.float32)
    hb = rng.random((2, G, W)).astype(np.float32) + 0.01
    # zero the lanes no feature owns so both paths see identical data
    meta_blk = build_block_scan_meta(group_of, ls, nb, mt, db, mf,
                                     needs_fix, penalty, G, W)
    has = meta_blk["has_owner"][:G]
    gb *= has
    hb *= has

    sg = np.array([3.0, -1.5], np.float32)
    shr = np.array([150.0, 90.0], np.float32)      # raw hessian sums
    sh = shr + 2e-15
    cnt = np.array([600.0, 360.0], np.float32)
    cf = cnt / sh
    l2, min_gain, md, mh = 0.5, 0.0, 5.0, 1e-3
    mgs = sg * sg / (sh + l2) + min_gain
    scal8 = np.stack([sg, sh, cnt, cf, np.full(2, md), np.full(2, mh),
                      mgs, np.full(2, l2)], axis=1).astype(np.float32)
    scal9 = np.concatenate([scal8, shr[:, None]], axis=1)

    # ---- per-feature reference: gather rows, explicit fix, scan_pair ---
    Fp = 8
    win_start = (group_of.astype(np.int64) * W + ls).astype(np.int32)
    meta = FeatureMeta(
        feat_id=jnp.zeros((G * W,), jnp.int32),
        bin_start=jnp.asarray(win_start),
        bin_end=jnp.asarray(win_start + nb),
        missing_type=jnp.asarray(mt),
        default_bin=jnp.asarray(db),
        monotone=jnp.zeros(F, jnp.int32),
        is_categorical=jnp.zeros(F, bool),
        penalty=jnp.asarray(penalty))
    layout = ScanLayout(meta, jnp.ones(F, bool), F, W, G * W,
                        win_off=jnp.asarray(ls))
    rows_g, rows_h = _apply_fix(
        [_feature_rows(gb, group_of, Fp), _feature_rows(hb, group_of, Fp)],
        sg, shr, ls, nb, mf, needs_fix)
    # strict-numerics harness: a silent f64 leak into either kernel's
    # f32 math fails here even if the numeric outputs still agree
    with strict_numerics():
        out_pair = np.asarray(scan_pair(
            jnp.asarray(scal8), jnp.asarray(rows_g), jnp.asarray(rows_h),
            layout.keep_r, layout.keep_f, layout.valid_r, layout.valid_f,
            layout.aux, interpret=True))              # [2, 8, Fp]

        # ---- block kernel: raw blocks, in-kernel fix ------------------
        Gp = meta_blk["masks"].shape[1]
        gbB = np.pad(gb, ((0, 0), (0, Gp - G), (0, 0)))
        hbB = np.pad(hb, ((0, 0), (0, Gp - G), (0, 0)))
        out_blk = np.asarray(scan_blocks(
            jnp.asarray(scal9), jnp.asarray(gbB), jnp.asarray(hbB),
            jnp.asarray(meta_blk["masks"]), do_fix=True, interpret=True))

    for c in range(2):
        for g in range(G):
            feats = np.nonzero(group_of == g)[0]
            gains_f = out_pair[c, 0, feats]
            bf = feats[np.argmax(gains_f)]
            bg, bt = out_blk[c, 0, g], out_blk[c, 1, g]
            if not np.isfinite(gains_f.max()):
                assert not np.isfinite(bg)
                continue
            np.testing.assert_allclose(bg, gains_f.max(), rtol=1e-4,
                                       atol=1e-5)
            assert bt == out_pair[c, 1, bf], (c, g, bf)
            assert out_blk[c, 2, g] == out_pair[c, 2, bf]
            np.testing.assert_allclose(out_blk[c, 3:6, g],
                                       out_pair[c, 3:6, bf],
                                       rtol=1e-4, atol=1e-4)


def test_block_scan_feature_mask_fold():
    """Folding a feature mask into the valid rows disables exactly that
    feature's window: the group's best moves to another member."""
    group_of, ls, nb, mt, db, mf, needs_fix, penalty = _geometry()
    G = 3
    rng = np.random.default_rng(2)
    gb = rng.normal(size=(2, G, W)).astype(np.float32)
    hb = rng.random((2, G, W)).astype(np.float32) + 0.01
    meta_blk = build_block_scan_meta(group_of, ls, nb, mt, db, mf,
                                     needs_fix, penalty, G, W)
    gb *= meta_blk["has_owner"][:G]
    hb *= meta_blk["has_owner"][:G]
    Gp = meta_blk["masks"].shape[1]
    gbB = jnp.asarray(np.pad(gb, ((0, 0), (0, Gp - G), (0, 0))))
    hbB = jnp.asarray(np.pad(hb, ((0, 0), (0, Gp - G), (0, 0))))
    sg, shr = np.array([2.0, 1.0]), np.array([120.0, 80.0])
    sh = shr + 2e-15
    cnt = np.array([480.0, 320.0])
    mgs = sg * sg / (sh + 0.5)
    scal9 = jnp.asarray(np.stack(
        [sg, sh, cnt, cnt / sh, np.full(2, 3.0), np.full(2, 1e-3), mgs,
         np.full(2, 0.5), shr], axis=1).astype(np.float32))

    def run(masks):
        with strict_numerics():
            return np.asarray(scan_blocks(scal9, gbB, hbB,
                                          jnp.asarray(masks),
                                          do_fix=False, interpret=True))

    base = run(meta_blk["masks"])
    # mask out group 0's feature that currently wins it
    owner = meta_blk["owner"]
    t0 = int(base[0, 1, 0])
    win_f = int(owner[0, t0])
    fmask = np.ones(len(group_of), np.float32)
    fmask[win_f] = 0.0
    fm_lane = np.where(meta_blk["has_owner"],
                       fmask[np.where(meta_blk["has_owner"],
                                      meta_blk["owner"], 0)], 0.0)
    masked = meta_blk["masks"].copy()
    masked[2:4] *= fm_lane[None]
    out = run(masked)
    t1 = int(out[0, 1, 0])
    assert not np.isfinite(out[0, 0, 0]) or owner[0, t1] != win_f
