"""Pallas histogram kernel vs XLA reference equivalence.

The analog of the reference's opt-in GPU_DEBUG_COMPARE CPU-vs-GPU histogram
diff (src/treelearner/gpu_tree_learner.cpp:993-1030): the Pallas kernel runs
in interpreter mode on CPU and must match the plain einsum bit-for-bit in
its f32 totals. The kernel's bf16 hi/lo gradient split carries a ~1e-7
relative residual-rounding error per element (the hi half is exact, the lo
half is itself bf16-rounded), so tolerances are f32-grade, not bitwise.

Kernel invocations run under the strict-numerics harness
(analysis.strict_numerics: strict dtype promotion + debug-nans), so a
silent f64 leak into the f32 kernel math fails here even when the
numeric outputs still match.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.analysis import strict_numerics
from lightgbm_tpu.ops.pallas_histogram import hist_window, hist_window_xla


def _hist_strict(bins_t, grad, hess, w):
    with strict_numerics():
        out = hist_window(jnp.asarray(bins_t), jnp.asarray(grad),
                          jnp.asarray(hess), w, interpret=True)
        out.block_until_ready()
    return np.asarray(out)


@pytest.mark.parametrize("C,G,W", [(512, 4, 64), (1024, 7, 256), (256, 1, 128)])
def test_pallas_hist_matches_xla(C, G, W):
    rng = np.random.default_rng(0)
    bins = rng.integers(0, W, size=(C, G)).astype(np.int32)
    grad = rng.normal(size=C).astype(np.float32)
    hess = rng.random(C).astype(np.float32)
    # mask a tail like the growers do
    grad[C // 2:] = 0.0
    hess[C // 2:] = 0.0

    ref = np.asarray(hist_window_xla(jnp.asarray(bins), jnp.asarray(grad),
                                     jnp.asarray(hess), W))
    out = _hist_strict(bins.T, grad, hess, W)
    assert out.shape == (G, W, 2)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def _scatter_ref(bins, grad, hess, w):
    """CPU scatter-add oracle (the reference ConstructHistogram inner
    loop, src/io/dense_bin.hpp:74): exact f64 bincount per group."""
    C, G = bins.shape
    out = np.zeros((G, w, 2), np.float64)
    for g in range(G):
        out[g, :, 0] = np.bincount(bins[:, g], weights=grad.astype(np.float64),
                                   minlength=w)[:w]
        out[g, :, 1] = np.bincount(bins[:, g], weights=hess.astype(np.float64),
                                   minlength=w)[:w]
    return out


@pytest.mark.parametrize("C,G,W", [
    (768, 3, 256),    # byte groups -> radix-split kernel
    (768, 18, 256),   # the Expo geometry (few wide groups, radix)
    (768, 5, 16),     # nibble-width groups -> direct one-hot kernel
    (512, 2, 64),     # heuristic boundary: one-hot side
    (512, 2, 65),     # heuristic boundary: radix side
])
def test_kernel_variants_match_scatter_add(C, G, W):
    """Both kernel variants (radix-split for few wide groups, direct
    one-hot for narrow groups — ops/pallas_histogram._select_impl) must
    reproduce the CPU scatter-add path in interpreter mode, for nibble-
    width and byte-width storage alike."""
    from lightgbm_tpu.ops.pallas_histogram import _select_impl
    rng = np.random.default_rng(3 + W + G)
    bins = rng.integers(0, W, size=(C, G)).astype(np.int32)
    grad = rng.normal(size=C).astype(np.float32)
    hess = rng.random(C).astype(np.float32)
    ref = _scatter_ref(bins, grad, hess, W)
    out = _hist_strict(bins.T, grad, hess, W)
    assert out.shape == (G, W, 2)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
    # pin the heuristic: wide groups radix, narrow groups one-hot
    use_radix = _select_impl(W, G, C)[0]
    assert use_radix == (W > 64)


def test_stripe_retune_few_groups():
    """The radix stripe length grows in the few-group regime and the
    one-hot kernel keeps its VMEM-bounded stripes."""
    from lightgbm_tpu.ops.pallas_histogram import _select_impl
    assert _select_impl(256, 4, 1 << 20)[2] == 32768     # Expo-ish: long
    assert _select_impl(256, 18, 1 << 20)[2] == 16384
    assert _select_impl(256, 64, 1 << 20)[2] == 8192     # many groups
    assert _select_impl(16, 40, 1 << 20)[2] == 16384     # narrow one-hot
    assert _select_impl(300, 2, 1 << 20)[2] == 8192      # uint16-wide
    assert _select_impl(256, 4, 4096)[2] == 4096         # capped by C


def test_pallas_hist_totals_exact():
    """Per-group totals must equal the f32 sums exactly (bf16 hi/lo split)."""
    rng = np.random.default_rng(1)
    C, G, W = 2048, 3, 256
    bins = rng.integers(0, W, size=(C, G)).astype(np.int32)
    grad = (rng.normal(size=C) * 3).astype(np.float32)
    hess = rng.random(C).astype(np.float32)
    out = _hist_strict(bins.T, grad, hess, W)
    np.testing.assert_allclose(out[..., 0].sum(axis=1),
                               np.repeat(np.float64(grad.astype(np.float64).sum()), G),
                               rtol=1e-5)
    np.testing.assert_allclose(out[..., 1].sum(axis=1),
                               np.repeat(np.float64(hess.astype(np.float64).sum()), G),
                               rtol=1e-5)
