"""Wide dense rows on the persist path: the chunk geometry that follows
from the row's width, the histogram kernels whose group loop is a loop,
the partition at 135 live word rows, and a forced-persist train of 520
dense columns at 63 bins held against the benchmark's plain reference and
the v1 grower. Small and seeded: the Epsilon configuration
(benchmark/configs/epsilon.json: 2,000 columns, a 2 KB payload row) is
compiled for the chip by tests/test_chip_compile.py and run on it by the
benchmark; here its mechanisms run in interpret mode and through the XLA
emulation at a width past the loop threshold.
"""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import grow_persist as gp
from lightgbm_tpu.ops import pallas_grow as pg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ROWS = 400_000               # Epsilon's, for the geometry cases
# (bin words, groups) -> today's (WPA, C, CR): HIGGS, the Expo cell,
# MS-LTR, a 700-group unbundled shape; and Epsilon, whose answer is held by
# the footprints
GEOMETRIES = [((7, 28), (16, 16384, 16384)), ((4, 16), (16, 16384, 16384)),
              ((35, 137), (40, 16384, 16384)),
              ((175, 700), (184, 8192, 16384)), ((500, 2000), None)]


@pytest.mark.parametrize("shape,held", GEOMETRIES,
                         ids=lambda v: "x".join(map(str, v))
                         if isinstance(v, tuple) and len(v) == 2 else None)
def test_chunk_geometry_follows_the_width(shape, held):
    nbw, G = shape
    WPA, C, CR, NP = gp._payload_geometry(ROWS, nbw, G)
    looped = G > pg.HIST_UNROLL_MAX_GROUPS
    need = (pg.split_pass_vmem_bytes(WPA, C + 128, G, cap=None),
            pg.seg_hist_vmem_bytes(WPA, C + 128, G, looped, cap=None),
            pg.seg_hist_vmem_bytes(WPA, CR, G, looped, cap=None))
    # no kernel runs with a request the cap has cut
    assert max(need) < pg.VMEM_CAP, need
    assert need == (pg.split_pass_vmem_bytes(WPA, C + 128, G),
                    pg.seg_hist_vmem_bytes(WPA, C + 128, G, looped),
                    pg.seg_hist_vmem_bytes(WPA, CR, G, looped))
    assert NP >= -(-ROWS // CR) * CR and NP >= ROWS + C + 256
    if held is not None:
        assert (WPA, C, CR) == held
    else:
        assert WPA == 512 and 1024 <= C < 8192 and 1024 <= CR <= 16384
        # the next size up does not fit: the chunk is the largest that does
        assert pg.split_pass_vmem_bytes(WPA, 2 * C + 128, G,
                                        cap=None) >= pg.VMEM_CAP


# -- kernels at a wide small geometry ----------------------------------------

N, F, BINS = 2048, 520, 63
CH = 512                     # chunk lanes: quick to interpret
PLAN, NBW = gp._payload_plan([BINS + 1] * F)
WPA, _, _, _ = gp._payload_geometry(N, NBW, F)
NPAD = 4096
GRAD = NBW + 2
LIVE = gp.payload_weight_row(NBW, 1)


def test_the_wide_geometry_is_past_the_loop_threshold():
    assert (WPA, NBW, LIVE) == (136, 130, 135)
    assert pg.hist_loops_groups(F, PLAN)
    assert not pg.hist_loops_groups(28, gp._payload_plan([256] * 28)[0])
    # a nibble slot among many groups: the unrolled kernels keep it
    assert not pg.hist_loops_groups(
        F, gp._payload_plan([BINS + 1] * (F - 2) + [8, 8])[0])


@pytest.fixture(scope="module")
def wide():
    rng = np.random.default_rng(36)
    bins = rng.integers(0, BINS, (N, F)).astype(np.uint8)
    pay = gp._pack_payload(bins, rng.integers(0, 2, N), N, WPA, NPAD, NBW,
                           0, N, plan=PLAN)
    gh = rng.normal(size=(2, N)).astype(np.float32)
    pay[GRAD:GRAD + 2, :N] = gh.view(np.uint32)
    # lanes past the rows hold anything: the kernels mask them
    pay[:, N:] = rng.integers(0, 2 ** 32, (WPA, NPAD - N), dtype=np.uint32)
    pay[NBW + 1, N:] = N
    return bins, gh, pay


def _numpy_hist(bins, gh, rows):
    G = bins.shape[1]
    out = np.zeros((2, G * 256))
    flat = bins[rows].astype(np.int64) + np.arange(G) * 256
    for k in range(2):
        np.add.at(out[k], flat.ravel(),
                  np.repeat(gh[k, rows].astype(np.float64), G))
    return out


def _close(planes, want):
    got = np.stack([np.asarray(p, np.float64) for p in planes])
    # every bin, the empty ones (63..255 of each group) included, to the
    # rounding of the kernels' bf16 hi + lo split (16 bits a value, some
    # 30 values a bin here)
    np.testing.assert_allclose(got, want, rtol=0, atol=3e-4)


def test_root_hist_is_numpys_bin_for_bin(wide):
    bins, gh, pay = wide
    fn = pg.make_root_hist(WPA, NPAD, F, PLAN, NBW, N, C=CH, interpret=True)
    planes = fn(jnp.asarray(pay))
    _close(planes, _numpy_hist(bins, gh, np.arange(N)))
    # every row lies in one bin of every group: a group's plane sums to the
    # rows' own sums (the grower reads the root's sums off the first)
    for g in (0, F - 1):
        np.testing.assert_allclose(
            [np.asarray(p)[g * 256:(g + 1) * 256].sum() for p in planes],
            gh.sum(axis=1), rtol=1e-4)


@pytest.mark.parametrize("start,length", [(0, N), (133, 700), (640, 511),
                                          (1000, 1)])
def test_seg_hist_is_numpys_bin_for_bin(wide, start, length):
    bins, gh, pay = wide
    fn = pg.make_seg_hist(WPA, NPAD, F, PLAN, NBW, C=CH, interpret=True)
    planes = fn(jnp.asarray(pay), jnp.int32(start), jnp.int32(length))
    _close(planes, _numpy_hist(bins, gh, np.arange(start, start + length)))


@pytest.mark.parametrize("kernel", ["root_hist", "seg_hist"])
def test_the_looped_group_decode_is_the_unrolled_one_bit_for_bit(wide,
                                                                 kernel):
    _, _, pay = wide
    out = []
    for loop in (True, False):
        if kernel == "root_hist":
            fn = pg.make_root_hist(WPA, NPAD, F, PLAN, NBW, N, C=CH,
                                   interpret=True, _loop_groups=loop)
            out.append([np.asarray(p) for p in fn(jnp.asarray(pay))])
        else:
            fn = pg.make_seg_hist(WPA, NPAD, F, PLAN, NBW, C=CH,
                                  interpret=True, _loop_groups=loop)
            out.append([np.asarray(p) for p in fn(
                jnp.asarray(pay), jnp.int32(133), jnp.int32(1500))])
    for a, b in zip(*out):
        assert a.tobytes() == b.tobytes()


# -- the [64, E] operand is selected, not multiplied --------------------------

BF16 = jnp.bfloat16
# histogram payloads of a few hundred rows: 28 byte groups (unrolled, as
# HIGGS), 67 (past HIST_UNROLL_MAX_GROUPS: looped over word rows, as
# Criteo), and the Expo cell's sixteen widths, one of them a 4-bit slot
HIST_WIDTHS = {"bytes28": [256] * 28, "looped67": [256] * 67,
               "nibble16": [256, 256, 8, 13, 23, 32, 128, 128,
                            28, 32, 36, 40, 44, 48, 52, 56]}
HN, HNP, HC = 900, 2048, 256


def _hist_plan(name):
    widths = HIST_WIDTHS[name]
    plan, nbw = gp._payload_plan(widths)
    wpa = gp._payload_geometry(HN, nbw, len(widths))[0]
    assert pg.hist_loops_groups(len(widths), plan) == (name == "looped67")
    assert any(mk == 15 for _, _, mk in plan) == (name == "nibble16")
    return widths, plan, nbw, wpa


def _hist_kernel(kernel, name):
    widths, plan, nbw, wpa = _hist_plan(name)
    if kernel == "root_hist":
        return pg.make_root_hist(wpa, HNP, len(widths), plan, nbw, HN, C=HC,
                                 interpret=True)
    return pg.make_seg_hist(wpa, HNP, len(widths), plan, nbw, C=HC,
                            interpret=True)


@pytest.mark.parametrize("name", list(HIST_WIDTHS))
@pytest.mark.parametrize("kernel", ["root_hist", "seg_hist"])
def test_no_bf16_multiply_builds_the_histogram_operand(kernel, name):
    """The v5e has no bf16 VALU: a [16, E] bf16 product is two unpacks, two
    f32 multiplies and a pack a vreg, 25 of the 38-46 VALU ops a (group,
    lane tile) the kernels issued (PERF.md, PR 39). Each group's [64, E]
    operand is a select of f32 rows under the lo mask, cast to bf16 once,
    which Mosaic folds into the MXU push; the hi one-hot is cast from its
    mask. Read off the kernel's own jaxpr, loop bodies included."""
    from lightgbm_tpu.analysis.dataflow import iter_eqns
    widths, plan, _, wpa = _hist_plan(name)
    G = len(widths)
    fn = _hist_kernel(kernel, name)
    S = jax.ShapeDtypeStruct
    args = [S((wpa, HNP), jnp.uint32)] + (
        [] if kernel == "root_hist" else [S((), jnp.int32)] * 2)
    calls = [e for e, _ in iter_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    E = HC if kernel == "root_hist" else HC + 128
    # group bodies in the kernel's text: every group unrolled, or one
    # sublane tile of 32 in the loop and the rest in the static tail
    bodies = 32 + G % 32 if name == "looped67" else G
    operand, one_hot, dots = [], [], []
    for eqn, _ in iter_eqns(calls[0].params["jaxpr"]):
        prim = eqn.primitive.name
        if prim not in ("mul", "convert_element_type", "dot_general"):
            continue
        out = eqn.outvars[0].aval
        if prim == "mul":
            assert out.dtype != BF16, eqn
        if prim == "convert_element_type" and out.dtype == BF16 \
                and len(out.shape) == 2:
            src = eqn.invars[0].aval.dtype
            {(64, E): operand, (16, E): one_hot}[out.shape].append(src)
        if prim == "dot_general":
            dots.append(tuple(v.aval.shape for v in eqn.invars))
    assert operand == [jnp.float32] * bodies, operand
    assert one_hot == [jnp.bool_] * bodies, one_hot
    assert dots == [((16, E), (64, E))] * bodies, dots


def _multiply_form_hist(pay, plan, nbw, chunks, E):
    """The contraction as the kernels built it before PR 39, in plain
    jax.numpy: per chunk and group a bf16 hi one-hot against four [16, E]
    bf16 PRODUCTS of the lo one-hot and a value row, f32 accumulation.
    chunks: (first lane read, lane of the first row in it, rows)."""
    n16 = jax.lax.broadcasted_iota(jnp.int32, (16, E), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, E), 1)[0]

    @jax.jit
    def chunk(hist, w, d, m):
        w = jnp.roll(w, E - d, axis=1)
        valid = (lane < m).astype(jnp.float32)
        grad = jax.lax.bitcast_convert_type(w[nbw + 2], jnp.float32) * valid
        hess = jax.lax.bitcast_convert_type(w[nbw + 3], jnp.float32) * valid
        g_hi, h_hi = grad.astype(BF16), hess.astype(BF16)
        vt = (g_hi, h_hi,
              (grad - g_hi.astype(jnp.float32)).astype(BF16),
              (hess - h_hi.astype(jnp.float32)).astype(BF16))
        out = []
        for g, (wr, sh, mk) in enumerate(plan):
            b = ((w[wr] >> jnp.uint32(sh)) & jnp.uint32(mk)).astype(jnp.int32)
            oh_hi = (n16 == (b >> 4)[None, :]).astype(BF16)
            oh_lo = (n16 == (b & 15)[None, :]).astype(BF16)
            bv = jnp.concatenate([oh_lo * v[None, :] for v in vt], axis=0)
            out.append(hist[g] + jax.lax.dot_general(
                oh_hi, bv, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))
        return jnp.stack(out), bv

    hist = jnp.zeros((len(plan), 16, 64), jnp.float32)
    for a, d, m in chunks:
        hist, bv = chunk(hist, jnp.asarray(pay[:, a:a + E]), d, m)
    planes = [np.asarray(p) for p in pg._unpack_hist(hist)]
    return planes, np.asarray(bv).astype(np.float32)


def _hist_payload(name, gh_of):
    widths, plan, nbw, wpa = _hist_plan(name)
    rng = np.random.default_rng(39)
    bins = np.stack([rng.integers(0, w, HN) for w in widths],
                    axis=1).astype(np.uint8)
    pay = gp._pack_payload(bins, rng.integers(0, 2, HN), HN, wpa, HNP, nbw,
                           0, HN, plan=plan)
    gh = gh_of(rng)
    pay[nbw + 2:nbw + 4, :HN] = gh.view(np.uint32)
    # finite everywhere: the multiply form turns a masked-out infinity
    # into NaN (0 x inf), the select into 0
    pay[nbw + 2:nbw + 4, HN:] = np.float32(7.0).view(np.uint32)
    return bins, gh, pay, plan, nbw


def _both_signs_and_zeros(rng):
    gh = rng.normal(size=(2, HN)).astype(np.float32)
    gh[1] = np.abs(gh[1])
    gh[:, rng.random(HN) < 0.2] = 0.0       # rows a bag left out: exactly 0
    return gh


def _chunks(kernel, start, length):
    if kernel == "root_hist":
        return [(i * HC, 0, HN - i * HC) for i in range(-(-HN // HC))], HC
    ptrs = range(start, start + length, HC)
    return [(p // 128 * 128, p % 128, min(HC, start + length - p))
            for p in ptrs], HC + 128


@pytest.mark.parametrize("name", list(HIST_WIDTHS))
@pytest.mark.parametrize("kernel,start,length", [
    ("root_hist", 0, HN), ("seg_hist", 133, 700), ("seg_hist", 256, 512)])
def test_the_selected_operand_gives_the_multiplied_ones_histogram(
        kernel, start, length, name):
    """Planes equal, value for value, to the multiply form's: byte and
    nibble slots, the unrolled and the looped group loop, a segment that
    starts inside a lane tile and one on a boundary, gradients of both
    signs and rows of exactly 0.0."""
    bins, gh, pay, plan, nbw = _hist_payload(name, _both_signs_and_zeros)
    fn = _hist_kernel(kernel, name)
    got = fn(jnp.asarray(pay)) if kernel == "root_hist" else fn(
        jnp.asarray(pay), jnp.int32(start), jnp.int32(length))
    chunks, E = _chunks(kernel, start, length)
    want, _ = _multiply_form_hist(pay, plan, nbw, chunks, E)
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), b)
    # and they are the rows' own sums, bin for bin
    _close(got, _numpy_hist(bins, gh, np.arange(start, start + length)))


def test_the_two_operands_differ_in_the_sign_of_zero_alone():
    """0 x -v is -0.0 and a select gives +0.0: with every gradient
    negative the multiply form's [64, E] operand holds -0.0 wherever the lo
    mask is off, the selected one +0.0, and nothing else differs. A sum
    takes no notice of either zero unless every term is one, so the planes
    can differ in bits only where no row of the segment lies (an all-zero
    bin), and there in the sign alone."""
    def negative(rng):
        return -np.abs(rng.normal(size=(2, HN))).astype(np.float32) - 0.5
    # from a tile boundary to the last row: every lane outside the segment
    # holds the filler, and the masked value there is +0.0 whether the
    # valid mask multiplies (the chip) or XLA makes a select of it (here)
    name, start, length = "bytes28", 128, HN - 128
    bins, _, pay, plan, nbw = _hist_payload(name, negative)
    got = _hist_kernel("seg_hist", name)(
        jnp.asarray(pay), jnp.int32(start), jnp.int32(length))
    chunks, E = _chunks("seg_hist", start, length)
    want, bv_mul = _multiply_form_hist(pay, plan, nbw, chunks, E)
    # the last chunk's operand of the last group, both ways
    a, d, m = chunks[-1]
    w = np.roll(pay[:, a:a + E], E - d, axis=1)
    wr, sh, mk = plan[-1]
    lo = ((w[wr] >> sh) & mk & 15)[None, :] == np.arange(16)[:, None]
    valid = (np.arange(E) < m).astype(np.float32)
    vt = pg._hist_values(*(jnp.asarray(w[r].view(np.float32) * valid)
                           for r in (nbw + 2, nbw + 3)))
    bv_sel = np.concatenate([np.where(lo, np.asarray(v)[None, :],
                                      np.float32(0.0)) for v in vt], axis=0)
    assert np.array_equal(bv_sel, bv_mul)
    differ = bv_sel.view(np.uint32) != bv_mul.view(np.uint32)
    assert differ.any() and not bv_mul[differ].any()
    assert np.signbit(bv_mul[differ]).all()
    assert not np.signbit(bv_sel[differ]).any()
    # the planes: equal values; bits differ, if anywhere, in empty bins
    occupied = np.zeros(len(plan) * 256, bool)
    occupied[(bins[start:start + length].astype(np.int64)
              + np.arange(len(plan)) * 256).ravel()] = True
    for p, q in zip(got, want):
        p = np.asarray(p)
        assert np.array_equal(p, q)
        bits = p.view(np.uint32) != q.view(np.uint32)
        assert not (bits & occupied).any()
        assert not p[bits].any() and not q[bits].any()


@pytest.mark.parametrize("feature,thr", [(0, 30), (519, 5), (261, 61)])
def test_split_pass_leaves_every_payload_row_intact(wide, feature, thr):
    """135 live word rows (17 sublane tiles): after the pass each row id
    of the segment still carries its own 135 words, the left rows first,
    and nothing outside the segment has moved."""
    bins, _, pay = wide
    s0, n = 133, 1700
    fn = pg.make_split_pass(WPA, NPAD, F, PLAN, NBW, C=CH, interpret=True,
                            wp_live=LIVE, _skip_hist=True)
    w, sh, mk = PLAN[feature]
    v = np.zeros(pg.N_SCALARS, np.int32)
    v[pg.S_NCH], v[pg.S_S0], v[pg.S_NL] = -(-n // CH), s0, n
    v[pg.S_WG], v[pg.S_SH], v[pg.S_MASK] = w, sh, mk
    v[pg.S_NB], v[pg.S_LE], v[pg.S_THR] = BINS + 1, 256, thr
    out, _, n_left = fn(jnp.asarray(pay), jnp.asarray(v))
    out = np.asarray(out)
    left = bins[s0:s0 + n, feature] <= thr
    assert int(n_left) == left.sum() and 0 < left.sum() < n
    rid = out[NBW + 1, s0:s0 + n].astype(np.int64)
    assert sorted(rid) == list(range(s0, s0 + n))
    np.testing.assert_array_equal(out[:LIVE, s0:s0 + n], pay[:LIVE, rid])
    assert left[rid - s0][:int(n_left)].all()
    assert not left[rid - s0][int(n_left):].any()
    np.testing.assert_array_equal(out[:, :s0], pay[:, :s0])
    np.testing.assert_array_equal(out[:, s0 + n:], pay[:, s0 + n:])


# -- the split scan is handed the children's rows -----------------------------

@pytest.mark.parametrize("kernel_impl", ["xla", "pallas"])
@pytest.mark.parametrize("columns", [F, 28])
def test_no_gather_reads_the_per_leaf_planes(wide, columns, kernel_impl):
    """A gather by an index vector out of the [L, TBe] per-leaf planes is
    lowered on TPU through slices of the whole planes (2 GB a split at
    2,000 columns). The per-split grower reads a parent's row by a scalar
    (a dynamic_slice) and hands the split scan the children's rows it has
    just computed: no gather equation takes an operand of the planes' shape."""
    from lightgbm_tpu.analysis.dataflow import iter_eqns
    from lightgbm_tpu.data.dataset import BinnedDataset
    from lightgbm_tpu.treelearner.serial import SerialTreeLearner
    bins = wide[0][:, :columns]
    y = (bins[:, 0] > BINS // 2).astype(np.float64)
    cfg = lgb.Config({"objective": "binary", "num_leaves": 15,
                      "max_bin": BINS, "enable_bundle": False,
                      "verbosity": -1})
    ds = BinnedDataset.from_matrix(bins.astype(np.float32), cfg, label=y)
    learner = SerialTreeLearner(cfg, ds)
    assets = gp.build_assets(ds, y, score64=kernel_impl == "xla")
    gr = gp.make_persist_grower(assets, learner.meta, learner.grow_config,
                                interpret=True, kernel_impl=kernel_impl,
                                fix=learner.fix)
    assert not gr.use_level
    S = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(
        lambda pay, fmask: gr.grow(pay, learner.params, fmask))(
            S(assets.geometry[:2], jnp.uint32), S((columns,), jnp.bool_))
    # the planes: flat [L, total_bins] f64 in the widened XLA mode, the
    # kernels' [L, G x 256] f32 otherwise
    planes = (learner.grow_config.num_leaves,
              ds.total_bins if kernel_impl == "xla" else 256 * columns)
    in_loops = set()
    for eqn, loop_depth in iter_eqns(jaxpr.jaxpr):
        if loop_depth:
            in_loops.add(eqn.primitive.name)
        if eqn.primitive.name == "gather":
            assert eqn.invars[0].aval.shape != planes, eqn
    # the walk went inside the split loop: the parent's row is read there
    assert {"dynamic_slice", "gather"} <= in_loops, in_loops


# -- end to end: a forced-persist train held by the plain reference ----------

BENCH = os.path.join(ROOT, "benchmark")
E2E_ROWS, E2E_BLOCK, TREES, LEAVES = 8192, 4096, 16, 31
SEED = 3600000077


def _bench_modules():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from drivers import train
    from harness import reference
    return train, reference


def _config(**params):
    with open(os.path.join(BENCH, "configs", "epsilon.json")) as f:
        cfg = json.load(f)
    cfg.update(rows=E2E_ROWS, block_rows=E2E_BLOCK, heldout_rows=E2E_BLOCK)
    cfg["params"].update(num_leaves=LEAVES, min_sum_hessian_in_leaf=1.0,
                         **params)
    return cfg


@pytest.fixture(scope="module")
def wide_run():
    """(cfg, rows, booster, counters) of 16 trees (the fused driver forms a
    batch at 16 iterations) on 8,192 x 520 dense
    columns at 63 bins through the persist path (on the CPU: its XLA
    emulation), rows from the configuration's own generator cut to 520
    columns."""
    from lightgbm_tpu import telemetry
    train, _ = _bench_modules()
    cfg = _config(tpu_persist_scan="force")
    with pytest.MonkeyPatch.context() as mp:
        from generators import epsilon_like
        mp.setattr(epsilon_like, "FEATURES", F)
        rows, X, y, _, _, _ = train.inputs(cfg, SEED)
        assert X.shape == (E2E_ROWS, F) and X.dtype == np.float32
        before = telemetry.counts_snapshot()
        bst = lgb.train(dict(cfg["params"]), lgb.Dataset(X, y), TREES,
                        verbose_eval=False)
        text = bst.model_to_string(num_iteration=-1)
        after = telemetry.counts_snapshot()
        v1 = lgb.train(dict(cfg["params"], tpu_persist_scan="off"),
                       lgb.Dataset(X, y), TREES, verbose_eval=False)
        # the reference makes the rows again: inside the patch
        _, reference = _bench_modules()
        trees = reference.parse_model(text)
        init = reference.binary_init(float(np.mean(y, dtype=np.float64)))
        sums = train.follow(rows, trees, cfg, init)
        numbers = reference.compare(trees, sums,
                                    cfg["params"]["learning_rate"], init)[0]
        control = train.control(rows, trees, cfg, init, sums)[0]
    grew = {k: v - before.get(k, 0.0) for k, v in after.items()
            if k.startswith("tree_learner::")}
    grew.update({k: v for k, v in after.items() if k.startswith("ops::")})
    return cfg, text, v1.model_to_string(num_iteration=-1), numbers, \
        control, grew


def test_reference_accepts_the_wide_persist_trees(wide_run):
    cfg, text, _, numbers, _, _ = wide_run
    assert text.count("Tree=") == TREES
    assert numbers["count_mismatch"] == 0, numbers
    for name, limit in cfg["limits"].items():
        assert numbers[name] <= limit, (name, numbers)


def test_the_bfloat16_control_fails_a_limit(wide_run):
    cfg, _, _, _, control, _ = wide_run
    assert any(control[name] > limit
               for name, limit in cfg["limits"].items()), control


def test_the_v1_grower_grows_the_same_trees(wide_run):
    """Model text equal but for the parameter block (which bakes
    tpu_persist_scan), as tests/test_known_divergence.py pins for narrow
    data."""
    _, text, v1_text, _, _, _ = wide_run
    strip = lambda t: t.split("\nparameters:")[0]  # noqa: E731
    assert strip(text) == strip(v1_text)


def test_counters_say_the_payload_was_wide(wide_run):
    _, _, _, _, _, grew = wide_run
    assert grew.get("tree_learner::persist_scan_trees") == TREES, grew
    assert grew.get("tree_learner::wide_payload_trees") == TREES, grew
    assert grew.get("tree_learner::v1_grow_trees", 0) == 0, grew
    assert grew["ops::payload_words"] >= 136, grew
    assert grew["ops::chunk_lanes"] == 8192, grew
    assert grew["ops::root_chunk_lanes"] == 16384, grew


def test_bin_finding_on_threads_finds_the_same_bins(monkeypatch):
    """From 64 columns on, BinMapper.find_bin runs on a thread pool
    (data/dataset.py:_construct_from_sample); a column's mapper hangs on
    that column alone, so the bins are those of the plain loop."""
    from lightgbm_tpu.data import dataset as ds_mod
    rng = np.random.default_rng(5)
    X = rng.normal(size=(3000, 96))
    X[:, 7] = np.round(X[:, 7])                  # few distinct values
    X[rng.random(3000) < 0.1, 11] = np.nan       # a NaN bin
    y = (X[:, 0] > 0).astype(np.float64)
    cfg = lgb.Config({"max_bin": 63, "enable_bundle": False})
    threaded = ds_mod.BinnedDataset.from_matrix(X, cfg, label=y)
    monkeypatch.setattr(ds_mod, "_FIND_BIN_THREADS_MIN_FEATURES", 10 ** 9)
    plain = ds_mod.BinnedDataset.from_matrix(X, cfg, label=y)
    assert len(threaded.bin_mappers) == 96
    for a, b in zip(threaded.bin_mappers, plain.bin_mappers):
        np.testing.assert_array_equal(a.bin_upper_bound, b.bin_upper_bound)
        assert (a.num_bin, a.missing_type, a.default_bin) == (
            b.num_bin, b.missing_type, b.default_bin)
    np.testing.assert_array_equal(threaded.binned, plain.binned)
