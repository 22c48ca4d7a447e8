"""Described-topology compiles: the Mosaic kernels of the training path,
compiled for a TPU v5e that is described and not attached.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: a slice not aligned to the tiling, a rotate fed an i64
shift, more VMEM than a kernel may hold. These cases hand the TPU compiler
each ``pallas_call`` site at the geometry the code itself builds for a real
configuration — the persist grower's kernels for HIGGS (10.5M x 28,
255 bins, 255 leaves), the same payload with a finite ``max_depth`` for the
level kernels, an EFB-bundled Expo-like payload (11M rows) for the
block scan, the benchmark's Expo cell's geometry (9 live rows, a nibble
slot) for the seg_hist it runs after each pass and for the split_pass
that builds the histogram inside the pass, which no grower takes since
PR 35, the MS-LTR payload (137 features:
40 live rows, whole sublane tiles, so split_pass has no spare sublane; its
histogram kernels, which loop over word rows past 64 groups, and its pair
scan at 137 groups), the Epsilon payload (benchmark/configs/epsilon.json:
400,000 x 2,000 dense columns at 63 bins — a 2 KB row of 505 live words, the
chunks `_payload_geometry` sizes from that width (C 2048, CR 8192),
split_pass at 64 sublane tiles, seg_hist / root_hist over 2,000 groups,
scan_pair over [2000, 256] planes and the fused k=16 driver with its
1.04 GB of per-leaf planes), the fused driver of benchmark/configs/mslr.json
(11.52M rows in 96,000 queries of 120, its per-query ranking fill beside
the kernels at 137 groups), and the HIGGS rows under
the other static shapes every persist configuration can take: a weight row
(13 live rows), three classes (17 live rows in 24), ``max_bin=15`` (every
group a nibble, 9 live rows in 16) — plus the whole fused k=16 scan driver.
Nothing runs, so they say nothing about results or times; ``chip_smoke.py``
does that on the chip.

These compiles are the proof of each kernel's scoped-VMEM request (the
``*_vmem_bytes`` / ``hist_vmem_plan`` helper beside the kernel): a request
too small for what Mosaic allocates is refused here. A geometry without a
case here is unproven.

All cases live in this one file: the topology is described inside a
module-scoped fixture (never at import, in a skipif or in a parametrize),
because only one process at a time may load the TPU library and each xdist
worker imports every test file. The persistent compile cache is off around
them — a described-topology entry cannot be read back without a chip.
"""
import inspect
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import lightgbm_tpu as lgb
from lightgbm_tpu.data.dataset import BinnedDataset
from lightgbm_tpu.data.synth import (make_expo_like, make_higgs_like,
                                      make_ltr_like)
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.ops import grow_persist as gp
from lightgbm_tpu.ops.pallas_grow import (N_SCALARS, hist_loops_groups,
                                          make_level_pass, make_seg_hist,
                                          make_split_pass)
from lightgbm_tpu.ops.pallas_histogram import hist_window
from lightgbm_tpu.ops.pallas_scan import (ScanLayout, build_block_scan_meta,
                                          scan_blocks, scan_pair)
from lightgbm_tpu.treelearner.serial import SerialTreeLearner

HIGGS_ROWS = 10_500_000     # docs/Experiments.rst: HIGGS
EXPO_ROWS = 11_000_000      # docs/Experiments.rst: Expo
MSLTR_ROWS = 2_270_296      # docs/Experiments.rst: MS LTR
EXPO_CELL_ROWS = 16_500_000  # benchmark/configs/expo.json: 1.5 x Expo
EPSILON_ROWS = 400_000      # docs/GPU-Performance.rst: Epsilon
MSLR_QUERIES, MSLR_DOCS = 96_000, 120   # benchmark/configs/mslr.json
EPSILON_FEATURES = 2_000
LEVEL_DEPTH = 8             # max_depth; with num_leaves = 2^8 the level
#                             phase engages
SAMPLE_ROWS = 20_000        # rows actually binned: the bin structure only


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %r" % e)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


class _Built:
    """One configuration as the code builds it for the chip: the learner's
    GrowConfig on a TPU backend, and persist growers whose payload
    geometry is that of the full row count."""

    def __init__(self, X, y, rows, max_depths, weight=None, **more):
        params = dict({"objective": "binary", "num_leaves": 255,
                       "max_bin": 255}, **more)
        cfg = lgb.Config(params)
        self.ds = BinnedDataset.from_matrix(X, cfg, label=y, weight=weight)
        self.objective = create_objective(params["objective"], cfg)
        self.objective.init(self.ds.metadata, self.ds.num_data)
        K = self.objective.num_model_per_iteration
        small = gp.build_assets(self.ds, self.ds.metadata.label,
                                num_scores=K)
        G, plan, nbw, has_w = (small.geometry[2], small.geometry[3],
                               small.geometry[4], small.geometry[9])
        assert has_w == (weight is not None)
        WPA, C, CR, NP = gp._payload_geometry(
            rows, nbw, G, 0, 0, K, has_w,
            loop_groups=hist_loops_groups(G, plan))
        self.assets = small._replace(
            pay0=None,
            geometry=(WPA, NP, G, plan, nbw, rows, C, CR, K, has_w, False))
        self.pay = (WPA, NP)
        self.wp_live = gp.payload_weight_row(nbw, K) + int(has_w)
        self.learners, self.growers = {}, {}
        # the learner reads the backend to choose f32/bf16x2 and the
        # Mosaic scan; steer it here, in the test, to what it does on TPU
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax, "default_backend", lambda: "tpu")
            for md in max_depths:
                c = lgb.Config(dict(params, max_depth=md) if md < 0 else
                               dict(params, max_depth=md,
                                    num_leaves=1 << md))
                self.learners[md] = SerialTreeLearner(c, self.ds)
        for md, learner in self.learners.items():
            gc = learner.grow_config
            assert gc.scan_impl == "pallas" and not gc.use_dp, gc
            self.growers[md] = gp.make_persist_grower(
                self.assets, learner.meta, gc, interpret=False,
                kernel_impl="pallas", fix=learner.fix)


@pytest.fixture(scope="module")
def higgs():
    X, y = make_higgs_like(SAMPLE_ROWS)
    return _Built(X, y, HIGGS_ROWS, (-1, LEVEL_DEPTH))


@pytest.fixture(scope="module")
def expo():
    X, y = make_expo_like(SAMPLE_ROWS)
    return _Built(X, y, EXPO_ROWS, (LEVEL_DEPTH,))


@pytest.fixture(scope="module")
def msltr():
    X, y, _ = make_ltr_like(SAMPLE_ROWS)
    return _Built(X, (y > 1).astype(np.float64), MSLTR_ROWS, (-1,))


@pytest.fixture(scope="module")
def higgs_weighted():
    X, y = make_higgs_like(SAMPLE_ROWS)
    w = np.random.default_rng(0).uniform(0.5, 2.0, SAMPLE_ROWS)
    return _Built(X, y, HIGGS_ROWS, (-1,), weight=w)


@pytest.fixture(scope="module")
def higgs_3class():
    X, y = make_higgs_like(SAMPLE_ROWS)
    y3 = y + (X[:, 0] > np.median(X[:, 0]))
    return _Built(X, y3, HIGGS_ROWS, (-1,), objective="multiclass",
                  num_class=3)


@pytest.fixture(scope="module")
def higgs_15bins():
    X, y = make_higgs_like(SAMPLE_ROWS)
    return _Built(X, y, HIGGS_ROWS, (-1,), max_bin=15)


@pytest.fixture(scope="module")
def epsilon():
    """benchmark/configs/epsilon.json: 2,000 dense columns at 63 bins."""
    rng = np.random.default_rng(36)
    X = rng.normal(size=(SAMPLE_ROWS, EPSILON_FEATURES)).astype(np.float32)
    y = (X[:, :8].sum(axis=1) > 0).astype(np.float64)
    built = _Built(X, y, EPSILON_ROWS, (-1,), max_bin=63,
                   enable_bundle=False)
    # a 2 KB payload row: 500 bin words, 505 live rows; the chunks follow
    # from the width (gp._payload_geometry) and the histogram kernels loop
    # over word rows
    G, plan, C, CR = (built.assets.geometry[i] for i in (2, 3, 6, 7))
    assert (built.wp_live, built.pay[0], G) == (505, 512, EPSILON_FEATURES)
    assert (C, CR) == (2048, 8192) and hist_loops_groups(G, plan)
    return built


@pytest.fixture(scope="module")
def mslr():
    """benchmark/configs/mslr.json: the cell's own generator's columns
    (some whole-count columns take a nibble: 34 bin words, 39 live rows)
    at 11.52M rows, and a lambdarank objective over its 96,000 queries of
    120 built as the chip builds it (its pair planes in float32)."""
    import importlib.util
    import types
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "generators",
        "mslr_like.py")
    spec = importlib.util.spec_from_file_location("mslr_like", path)
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    key = jax.random.wrap_key_data(np.asarray([0, 42], np.uint32))
    x, y = (np.asarray(a) for a in gen.make_block(key, 0, SAMPLE_ROWS // 120
                                                  * 120))
    rows = MSLR_QUERIES * MSLR_DOCS
    built = _Built(x.astype(np.float64), (y > 1).astype(np.float64), rows,
                   (-1,), enable_bundle=False)
    assert (built.pay[0], built.wp_live, len(built.ds.groups)) == (40, 39,
                                                                  137)
    label = np.resize(y.astype(np.float64), rows)
    built.objective = create_objective("lambdarank", lgb.Config(
        {"objective": "lambdarank"}))
    built.objective.init(types.SimpleNamespace(
        label=label, weight=None, num_queries=MSLR_QUERIES,
        query_boundaries=np.arange(MSLR_QUERIES + 1) * MSLR_DOCS), rows)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        built.grad = built.objective.device_gradients()
    return built


def _hist_window(higgs, expo, S):
    learner = higgs.learners[-1]
    G, C = len(higgs.ds.groups), learner.grow_config.window_chunk
    w = int(learner.gw_global.shape[1])
    return (lambda b, g, h: hist_window(b, g, h, w=w),
            (S((G, C), jnp.int32), S((C,), jnp.float32),
             S((C,), jnp.float32)))


def _scan_pair_of(built, S):
    gr = built.growers[-1]
    F = built.ds.num_features
    lay = ScanLayout(gr._pad_meta, jnp.ones(F, bool), F, 256,
                     len(built.ds.groups) * 256)
    f32 = jnp.float32
    plane, mask = (2, lay.Fp, lay.Wp), lay.keep_r.shape
    return scan_pair, (S((2, 8), f32), S(plane, f32), S(plane, f32),
                       S(mask, f32), S(mask, f32), S(mask, f32),
                       S(mask, f32), S(lay.aux.shape, f32))


def _scan_pair(higgs, expo, S):
    return _scan_pair_of(higgs, S)


def _scan_pair_msltr(higgs, expo, S, msltr):
    """137 features: Fp 144, the widest pair scan a published
    configuration of the reference asks for."""
    assert msltr.ds.num_features == 137 == len(msltr.ds.groups)
    return _scan_pair_of(msltr, S)


def _scan_blocks(higgs, expo, S):
    (group_of, ls, nb, mf, needs_fix, bundled, mt, db) = expo.assets.efb
    assert bundled and len(expo.ds.groups) < expo.ds.num_features
    G = len(expo.ds.groups)
    blk = build_block_scan_meta(group_of, ls, nb, mt, db, mf, needs_fix,
                                np.ones(expo.ds.num_features), G, 256)
    _, Gp, Wp = blk["masks"].shape
    do_fix = bool(needs_fix.any())
    f32 = jnp.float32
    return (lambda s, g, h, m: scan_blocks(s, g, h, m, do_fix=do_fix),
            (S((2, 9), f32), S((2, Gp, Wp), f32), S((2, Gp, Wp), f32),
             S(blk["masks"].shape, f32)))


def _split_pass_of(built, S, wp_live, wpa):
    assert (built.wp_live, built.pay[0]) == (wp_live, wpa), (
        built.wp_live, built.pay)
    return built.growers[-1]._split_pass, (
        S(built.pay, jnp.uint32), S((N_SCALARS,), jnp.int32))


def _split_pass(higgs, expo, S):
    return _split_pass_of(higgs, S, 12, 16)


def _split_pass_msltr(higgs, expo, S, msltr):
    """40 live payload rows: the partition's tiles are five whole
    sublane tiles and its group of tiles is shorter."""
    return _split_pass_of(msltr, S, 40, 40)


def _expo_cell():
    """expo.train_steady's payload, from the storage groups EFB finds on
    the cell's rows (PERF.md, section 4: 16 groups of these level counts,
    two of them narrow enough for a nibble): 9 live payload rows in 16.
    The `expo` fixture's 20,000 sampled rows bundle into fewer groups and
    a payload half as tall, so the cell's kernels are built here from the
    widths."""
    widths = [256, 256, 8, 13, 23, 32, 128, 128,
              28, 32, 36, 40, 44, 48, 52, 56]
    plan, nbw = gp._payload_plan(widths)
    WPA, C, CR, NP = gp._payload_geometry(EXPO_CELL_ROWS, nbw, len(widths))
    wp_live = gp.payload_weight_row(nbw, 1)
    assert (WPA, C, CR, nbw, wp_live) == (16, 16384, 16384, 4, 9)
    assert any(mk == 15 for _, _, mk in plan)
    return WPA, NP, len(widths), plan, nbw, C, wp_live


def _split_pass_expo(higgs, expo, S):
    """split_pass with the smaller child's histogram built inside the
    pass, from the slot the partition has just compacted (_slot_hist), at
    the cell's geometry. Since PR 35 the grower takes seg_hist after the
    pass at every group count (PERF.md, section 6), so no grower builds
    this kernel: it is made here."""
    WPA, NP, G, plan, nbw, C, wp_live = _expo_cell()
    return make_split_pass(WPA, NP, G, plan, nbw, C=C, wp_live=wp_live), (
        S((WPA, NP), jnp.uint32), S((N_SCALARS,), jnp.int32))


def _seg_hist_expo(higgs, expo, S):
    """What the cell runs after each pass since PR 35: seg_hist at its
    geometry (16 groups, one nibble slot, WPA 16); its split_pass is the
    9-live-row kernel without a histogram, as _split_pass_15bins has it."""
    WPA, NP, G, plan, nbw, C, _ = _expo_cell()
    return make_seg_hist(WPA, NP, G, plan, nbw, C=C), (
        S((WPA, NP), jnp.uint32), S((), jnp.int32), S((), jnp.int32))


def _split_pass_weighted(higgs, expo, S, higgs_weighted):
    """A weight row rides the partition: 13 live rows, two sublane tiles
    with three pad rows."""
    return _split_pass_of(higgs_weighted, S, 13, 16)


def _split_pass_3class(higgs, expo, S, higgs_3class):
    """Three score rows and their iteration-start snapshot: 17 live rows
    of 24, three sublane tiles."""
    return _split_pass_of(higgs_3class, S, 17, 24)


def _split_pass_15bins(higgs, expo, S, higgs_15bins):
    """max_bin=15: every group a nibble, 28 groups in 4 bin words, 9 live
    rows of 16 (the Expo cell's height at HIGGS's group count, with
    seg_hist after the pass)."""
    plan = higgs_15bins.assets.geometry[3]
    assert all(mk == 15 for _, _, mk in plan), plan
    return _split_pass_of(higgs_15bins, S, 9, 16)


def _seg_hist_msltr(higgs, expo, S, msltr):
    """137 groups, past HIST_UNROLL_MAX_GROUPS: four whole sublane tiles
    of word rows in the loop, the last nine groups in the static tail."""
    assert msltr.pay[0] == 40 and len(msltr.ds.groups) == 137
    return msltr.growers[-1]._seg_hist, (
        S(msltr.pay, jnp.uint32), S((), jnp.int32), S((), jnp.int32))


def _root_hist_msltr(higgs, expo, S, msltr):
    return msltr.growers[-1]._root_hist, (S(msltr.pay, jnp.uint32),)


def _seg_hist(higgs, expo, S):
    return higgs.growers[-1]._seg_hist, (
        S(higgs.pay, jnp.uint32), S((), jnp.int32), S((), jnp.int32))


def _root_hist(higgs, expo, S):
    return higgs.growers[-1]._root_hist, (S(higgs.pay, jnp.uint32),)


def _split_pass_epsilon(higgs, expo, S, epsilon):
    """505 live payload rows, 64 sublane tiles: the partition's tiles,
    the FIFO slots [4, 512, 2304] and the carry [2, 512, 128]."""
    return _split_pass_of(epsilon, S, 505, 512)


def _seg_hist_epsilon(higgs, expo, S, epsilon):
    """2,000 groups: the group loop is a loop over word rows
    (_hist_accum_words), 32 groups an iteration."""
    return epsilon.growers[-1]._seg_hist, (
        S(epsilon.pay, jnp.uint32), S((), jnp.int32), S((), jnp.int32))


def _root_hist_epsilon(higgs, expo, S, epsilon):
    return epsilon.growers[-1]._root_hist, (S(epsilon.pay, jnp.uint32),)


def _scan_pair_epsilon(higgs, expo, S, epsilon):
    """Fp 2000: resolve_scan_impl admits the fused scan by the bins a
    feature has (2,000 x 128 = 256,000 lanes of 262,144), and the persist
    grower hands it the padded [F, 256] group planes: Wp 256, 70 MB by
    scan_pair_vmem_bytes."""
    fn, args = _scan_pair_of(epsilon, S)
    assert args[1].shape == (2, 2000, 256)
    return fn, args


def _level_args(built, gr, S):
    i32 = jnp.int32
    return (S(built.pay, jnp.uint32), S((gr.S_MAXL, 16), i32),
            S((gr.T_MAXL,), i32), S((gr.S_MAXL,), i32), S((), i32))


def _level_pass(higgs, expo, S):
    gr = higgs.growers[LEVEL_DEPTH]
    assert gr.use_level
    return gr._level_pass, _level_args(higgs, gr, S)


def _level_pass_inpass(higgs, expo, S):
    """level_pass with the smaller-child histograms built inside the
    pass (_slot_hist per slot), at the bundled Expo-like payload. The
    grower builds level_pass without them (seg route, PR 35), so the
    kernel is made here from the grower's own geometry."""
    gr = expo.growers[LEVEL_DEPTH]
    assert gr.use_level and gr._level_seg is not None
    WPA, NP, G, plan, nbw = expo.assets.geometry[:5]
    C = expo.assets.geometry[6]
    return make_level_pass(WPA, NP, G, plan, nbw, gr.S_MAXL, gr.T_MAXL,
                           C=C, wp_live=expo.wp_live), _level_args(
                               expo, gr, S)


def _level_seg_hist(higgs, expo, S):
    gr = higgs.growers[LEVEL_DEPTH]
    return gr._level_seg, _level_args(higgs, gr, S)


def _fused_driver_of(built, S):
    k, F = 16, built.ds.num_features
    learner, gr = built.learners[-1], built.growers[-1]
    mode, grad_fn = getattr(built, "grad", None) or \
        built.objective.device_gradients()
    run = gp.make_scan_driver(gr, learner.grow_config, k, grad_fn,
                              grad_mode=mode, wrap_jit=False)
    like = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: S(np.shape(a), jnp.asarray(a).dtype), tree)
    return run, (S(built.pay, jnp.uint32), S((k, F), jnp.bool_),
                 S((k, 2), jnp.uint32), S((k,), jnp.int32),
                 like(learner.params), S((), jnp.float64),
                 like(built.objective.persist_grad_args()))


def _fused_driver(higgs, expo, S):
    """make_scan_driver's whole k=16 program: the kernels above plus the
    XLA around them, at the size chip_smoke.py trains."""
    return _fused_driver_of(higgs, S)


def _fused_driver_epsilon(higgs, expo, S, epsilon):
    """The same program at Epsilon's geometry: the per-leaf planes are
    [255, 2000 x 256] f32 twice over."""
    return _fused_driver_of(epsilon, S)


def _fused_driver_mslr(higgs, expo, S, mslr):
    """mslr.train_steady's launch: the fused driver with the per-query
    ranking fill (grad_mode 'pos': one sort of the rows into [96000, 120]
    slots, the pair planes in 21 chunks, one sort back to the lanes)
    beside the kernels at 137 groups."""
    assert mslr.grad[0] == "pos"
    return _fused_driver_of(mslr, S)


@pytest.mark.parametrize("case", [
    _hist_window, _scan_pair, _scan_pair_msltr, _scan_blocks, _split_pass,
    _split_pass_msltr, _split_pass_expo, _split_pass_weighted,
    _split_pass_3class, _split_pass_15bins, _level_pass, _level_pass_inpass,
    _level_seg_hist, _seg_hist, _seg_hist_msltr, _seg_hist_expo,
    _root_hist, _root_hist_msltr, _fused_driver, _split_pass_epsilon,
    _seg_hist_epsilon, _root_hist_epsilon, _scan_pair_epsilon,
    _fused_driver_epsilon, _fused_driver_mslr,
], ids=lambda f: f.__name__.lstrip("_"))
def test_compiles_for_v5e(case, one_chip, higgs, expo, request):
    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # a case names the other configurations it needs; they are built only
    # for it
    more = [request.getfixturevalue(name)
            for name in list(inspect.signature(case).parameters)[3:]]
    fn, args = case(higgs, expo, S, *more)
    assert fn is not None, "the grower built no such kernel here"
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    if case in (_fused_driver, _fused_driver_epsilon, _fused_driver_mslr):
        # the split scan is handed the children's rows: a gather of them
        # by an index vector out of the [L, G x 256] planes is lowered
        # through whole-plane slices (2 GB a split at 2,000 columns)
        assert "mini-gather" not in text


CRITEO_SHARD_ROWS = 10_000_000   # benchmark/configs/criteo.json: 40M over 4
CRITEO_FEATURES = 67


def test_sharded_fused_driver_compiles_for_four_v5e(topo, one_chip):
    """criteo.train_data4's launch: the fused k=16 driver under shard_map
    over all four devices of the described v5e:2x2, at the cell's geometry
    (67 groups, so the histogram kernels loop over word rows; 17 bin
    words, WPA 24, a 96 B payload row; 10,000,000 rows a shard) and with
    the state 40M rows in all take (row counts in i32). The planes'
    all-reduce inside the grow loop has to be in the compiled program."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from lightgbm_tpu.parallel.learners import AXIS, _tree_arrays_spec
    rng = np.random.default_rng(38)
    X = rng.normal(size=(SAMPLE_ROWS, CRITEO_FEATURES)).astype(np.float32)
    y = (X[:, :4].sum(axis=1) > 1.5).astype(np.float64)
    built = _Built(X, y, CRITEO_SHARD_ROWS, (-1,), enable_bundle=False)
    WPA, NP, G, plan = built.assets.geometry[:4]
    assert (WPA, G, built.wp_live) == (24, CRITEO_FEATURES, 22)
    assert hist_loops_groups(G, plan)
    learner = built.learners[-1]
    gc = learner.grow_config._replace(parallel_mode="data")
    gr = gp.make_persist_grower(built.assets, learner.meta, gc,
                                interpret=False, kernel_impl="pallas",
                                axis_name=AXIS, fix=learner.fix,
                                large_counts=True)
    assert gr.large_counts
    k, F = 16, built.ds.num_features
    mode, grad_fn = built.objective.device_gradients()
    assert mode == "payload"
    run = gp.make_scan_driver(gr, gc, k, grad_fn, wrap_jit=False)
    mesh = Mesh(np.asarray(topo.devices).reshape(-1), (AXIS,))
    assert mesh.devices.size == 4
    pay_spec = P(None, AXIS)
    smapped = jax.shard_map(
        run, mesh=mesh,
        in_specs=(pay_spec, P(), P(), P(), P(), P(), P()),
        out_specs=(pay_spec, _tree_arrays_spec(gc, row_sharded=False), P()),
        check_vma=False)

    def S(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))
    like = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: S(np.shape(a), jnp.asarray(a).dtype), tree)
    args = (S((WPA, 4 * NP), jnp.uint32, pay_spec), S((k, F), jnp.bool_),
            S((k, 2), jnp.uint32), S((k,), jnp.int32),
            like(learner.params), S((), jnp.float64),
            like(built.objective.persist_grad_args()))
    compiled = jax.jit(smapped, donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    assert "mini-gather" not in text
    mem = compiled.memory_analysis()
    per_device = (mem.argument_size_in_bytes + mem.temp_size_in_bytes)
    # a shard's payload (96 B x 10M rows) and its temporaries fit a chip
    assert per_device < 8 * 2 ** 30, per_device
