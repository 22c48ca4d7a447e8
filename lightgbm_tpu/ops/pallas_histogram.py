"""Pallas TPU histogram kernel: per-group (grad, hess) bin accumulation.

TPU-native replacement for the reference's tuned OpenCL histogram kernels
(src/treelearner/ocl/histogram16/64/256.cl): where the GPU builds per-
workgroup shared-memory sub-histograms with atomic float adds, a TPU has no
fast atomics — instead each grid step generates a one-hot [W, C] tile IN
VMEM and contracts it against the (hi, lo)-split bf16 gradient pairs on the
MXU. Materializing that one-hot in VMEM is the whole point: the equivalent
XLA einsum materializes the [C, G, W] one-hot through HBM, which costs more
bandwidth than every other part of tree growth combined.

Numerics: grad/hess are split into bf16 hi + (x - hi) lo halves outside the
kernel. The one-hot is exact in bf16, each product has a single term, and
the MXU accumulates in f32, so hi+lo recovers full f32 accuracy (the same
trade the bf16x2 einsum path makes; see ops/grow.py:_hist_chunk_contract).

The kernel is used by the growers for every chunked histogram pass (root
and per-split smaller-child) when tpu_histogram_impl resolves to "pallas"
(the accelerator default). CPU keeps the scatter-add path; the equivalence
test runs this kernel in interpreter mode against it — the analog of the
reference's GPU_DEBUG_COMPARE (src/treelearner/gpu_tree_learner.cpp:993).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pallas_compat import CompilerParams, pl


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# dataflow contracts (read by analysis/{precision,quant}_audit)
# ---------------------------------------------------------------------------

def hist_input_contract(w: int, rows: int, g_max: float = 1.0,
                        h_max: float = 0.25) -> dict:
    """Value-range contract for :func:`hist_window`'s arguments, the
    seed the analysis/dataflow abstract interpreter starts from:
    group-local bin indices live in ``[0, w)``, per-row grad/hess are
    capped by the objective (binary logloss: |g| <= 1, 0 <= h <= 1/4),
    and any bin's accumulated (grad, hess) sum over ``rows`` rows is
    therefore capped at ``rows * cap``.  The quantization certifier
    derives its plane scales from exactly these numbers."""
    return {
        "bins_t": (0.0, float(w - 1)),
        "grad": (-float(g_max), float(g_max)),
        "hess": (0.0, float(h_max)),
        "grad_plane": (-float(rows) * float(g_max),
                       float(rows) * float(g_max)),
        "hess_plane": (0.0, float(rows) * float(h_max)),
    }


# narrowings this kernel performs ON PURPOSE: the bf16 hi + (x - hi) lo
# split is exact by construction (hi+lo recovers full f32 through the
# MXU's f32 accumulation — see the module docstring), so the
# precision-flow auditor blesses f32->bf16 inside hist_window
NARROW_OK = (("float32", "bfloat16"),)


def _hist_kernel(bins_ref, vals_ref, out_ref):
    """One grid step = one row stripe, all feature groups.

    bins_ref: [G, CT] i32 group-local bins of this stripe's rows
    vals_ref: [CT, 4] bf16 (grad_hi, hess_hi, grad_lo, hess_lo)
    out_ref:  [G, W, 2] f32, accumulated across grid steps
    """
    G, ct = bins_ref.shape
    w = out_ref.shape[1]

    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    vals = vals_ref[:]
    iota_w = jax.lax.broadcasted_iota(jnp.int32, (w, ct), 0)

    for g in range(G):  # static group count: unrolled, no loop carry
        b = bins_ref[g, :]
        onehot_t = (iota_w == b[None, :]).astype(jnp.bfloat16)   # [W, CT]
        acc = jax.lax.dot(onehot_t, vals,
                          preferred_element_type=jnp.float32)     # [W, 4]
        out_ref[g] = out_ref[g] + (acc[:, :2] + acc[:, 2:])


def _hist_kernel_radix(bins_ref, vals_ref, out_ref):
    """Radix-16 variant: hist[hi*16+lo] = oh_hi @ (oh_lo * val)^T.

    Generating two [16, C] one-hots costs ~16x less VPU work than one
    [256, C] one-hot; the [16, C] x [16, C]^T contractions stay on the MXU.
    Requires W == 256 (bins < 256; pad the output width).
    """
    G, ct = bins_ref.shape
    n16 = jax.lax.broadcasted_iota(jnp.int32, (16, ct), 0)

    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    vals = vals_ref[:]                                        # [CT, 4] bf16
    vt = vals.T                                               # [4, CT]
    dn = (((1,), (1,)), ((), ()))

    for g in range(G):
        b = bins_ref[g, :]
        oh_hi = (n16 == (b >> 4)[None, :]).astype(jnp.bfloat16)   # [16, CT]
        oh_lo = (n16 == (b & 15)[None, :]).astype(jnp.bfloat16)   # [16, CT]
        hs = []
        for v in range(4):
            bv = oh_lo * vt[v][None, :]                            # [16, CT]
            h = jax.lax.dot_general(oh_hi, bv, dn,
                                    preferred_element_type=jnp.float32)
            hs.append(h)                                           # [16, 16]
        out_ref[g] = out_ref[g] + jnp.stack(
            [hs[0] + hs[2], hs[1] + hs[3]], axis=-1)           # [16, 16, 2]


def _select_impl(w: int, G: int, C: int):
    """Geometry heuristic: (use_radix, w_pad, ct stripe length).

    Few wide groups (the EFB/Expo shape: byte groups at 256 bins) take the
    radix-split kernel — two [16, ct] nibble one-hots cost ~16x less VPU
    work than one [256, ct] one-hot, the histogram256.cl workgroup-radix
    trick re-derived for the MXU. Many NARROW groups keep the direct
    one-hot kernel: at w <= 64 the [<=128, ct] one-hot is already smaller
    than the radix pair's four extra MXU issues per group. The stripe
    length ct is retuned for the few-group regime — the radix kernel's
    VMEM footprint scales with G*ct (not w_pad*ct), so few groups afford
    long stripes and amortize per-stripe grid overhead.
    """
    use_radix = 64 < w <= 256
    w_pad = 256 if use_radix else _round_up(max(w, 1), 128)
    if use_radix:
        ct = 32768 if G <= 8 else (16384 if G <= 32 else 8192)
    else:
        ct = 16384 if w_pad <= 128 else 8192
    return use_radix, w_pad, min(C, ct)


def hist_vmem_plan(w: int, G: int, C: int) -> dict:
    """Static VMEM plan for :func:`hist_window` at geometry (w, G, C).

    One place derives the impl choice, the grid stripe, and the
    scoped-vmem limit the kernel requests: the kernel runs with these
    numbers, and tests/test_chip_compile.py proves them by compiling the
    kernel for a described v5e. The limit covers the
    double-buffered in/out blocks plus the one-hot temporaries (the
    16MB slack is Mosaic's own working set); many-group shapes (a
    700-feature unbundled dataset) exceed the 16MB Mosaic default,
    which is why the kernel must size the limit explicitly.
    """
    use_radix, w_pad, ct = _select_impl(w, G, C)
    out_bytes = G * 16 * 16 * 2 * 4 if use_radix else G * w_pad * 2 * 4
    temp = 3 * 16 * ct * 2 if use_radix else w_pad * ct * 2
    request = min(100 << 20,
                  2 * (G * ct * 4 + ct * 8 + out_bytes) + temp + (16 << 20))
    return {"use_radix": use_radix, "w_pad": w_pad, "ct": ct,
            "vmem_limit": int(request)}


@functools.partial(jax.jit, static_argnames=("w", "interpret"))
def hist_window(bins_t: jnp.ndarray, grad: jnp.ndarray, hess: jnp.ndarray,
                w: int, interpret: bool = False) -> jnp.ndarray:
    """[G, W, 2] f32 histogram of one row window.

    bins_t: [G, C] i32 group-local bins (transposed window — C on lanes).
    grad/hess: [C] f32, already masked (zero for rows outside the window).
    w: static bin-width of the output (max group width).
    """
    G, C = bins_t.shape
    plan = hist_vmem_plan(w, G, C)
    use_radix, w_pad, ct = plan["use_radix"], plan["w_pad"], plan["ct"]
    _cparams = CompilerParams(vmem_limit_bytes=plan["vmem_limit"])
    kernel = _hist_kernel_radix if use_radix else _hist_kernel
    nst = (C + ct - 1) // ct
    if nst * ct != C:
        pad = nst * ct - C
        bins_t = jnp.pad(bins_t, ((0, 0), (0, pad)))
        grad = jnp.pad(grad, (0, pad))
        hess = jnp.pad(hess, (0, pad))
    g_hi = grad.astype(jnp.bfloat16)
    h_hi = hess.astype(jnp.bfloat16)
    g_lo = (grad - g_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    h_lo = (hess - h_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    vals = jnp.stack([g_hi, h_hi, g_lo, h_lo], axis=-1)       # [C, 4] bf16

    # index maps derive every component from `i`: under jax_enable_x64 (on
    # for reference-parity f64 math) a literal 0 traces as i64 and Mosaic
    # rejects the mixed (i64, i32) index tuple with a legalize error
    z = lambda i: i * 0  # noqa: E731
    if use_radix:
        out = pl.pallas_call(
            kernel,
            compiler_params=_cparams,
            grid=(nst,),
            in_specs=[
                pl.BlockSpec((G, ct), lambda i: (z(i), i)),
                pl.BlockSpec((ct, 4), lambda i: (i, z(i))),
            ],
            out_specs=pl.BlockSpec((G, 16, 16, 2),
                                   lambda i: (z(i), z(i), z(i), z(i))),
            out_shape=jax.ShapeDtypeStruct((G, 16, 16, 2), jnp.float32),
            interpret=interpret,
        )(bins_t, vals)
        return out.reshape(G, 256, 2)[:, :w, :]
    out = pl.pallas_call(
        kernel,
        compiler_params=_cparams,
        grid=(nst,),
        in_specs=[
            pl.BlockSpec((G, ct), lambda i: (z(i), i)),
            pl.BlockSpec((ct, 4), lambda i: (i, z(i))),
        ],
        out_specs=pl.BlockSpec((G, w_pad, 2),
                               lambda i: (z(i), z(i), z(i))),
        out_shape=jax.ShapeDtypeStruct((G, w_pad, 2), jnp.float32),
        interpret=interpret,
    )(bins_t, vals)
    return out[:, :w, :]


def hist_window_xla(bins: jnp.ndarray, grad: jnp.ndarray, hess: jnp.ndarray,
                    w: int) -> jnp.ndarray:
    """Reference implementation (einsum) used by the equivalence test."""
    G = bins.shape[1]
    oh = (bins[:, :, None] == jnp.arange(w, dtype=jnp.int32)[None, None, :]
          ).astype(jnp.float32)
    vc = jnp.stack([grad, hess], -1)
    return jnp.einsum("rgw,rc->gwc", oh, vc,
                      preferred_element_type=jnp.float32)
