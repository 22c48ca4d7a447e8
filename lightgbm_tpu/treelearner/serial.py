"""Serial (single-device) tree learner: host wrapper around the device grower.

TPU-native rebuild of SerialTreeLearner (src/treelearner/serial_tree_learner.cpp).
The reference's per-split loop of histogram construction / best-split scan /
partition lives entirely on device as one jitted lax.while_loop (ops/grow.py);
this class owns the device-resident dataset layout, per-tree column sampling
(ColSampler, src/treelearner/col_sampler.hpp), and converts the device split
records into a host `Tree`.

The parallel learners (feature/data/voting, src/treelearner/*_parallel_*) are
the same grower under jax.sharding — see lightgbm_tpu/parallel/.
"""
from __future__ import annotations

import math
from typing import Tuple

import numpy as np

import jax.numpy as jnp

from ..config import Config
from ..models.tree import Tree
from ..ops.grow import (ForcedInfo, GrowConfig, GrowExtras, default_extras,
                        empty_cat_layout, empty_forced, grow_tree,
                        grow_tree_partitioned)
from ..ops.split import CatLayout, FeatureMeta, SplitParams
from ..telemetry import events as telemetry
from ..telemetry.devices import on_tpu
from ..utils.log import Log

# below this many rows the masked full-N grower compiles faster and the
# O(N)-per-split cost is irrelevant
PARTITION_MIN_ROWS = 65536


def _cegb_enabled(config: Config) -> bool:
    """CostEfficientGradientBoosting::IsEnable
    (cost_effective_gradient_boosting.hpp:25-31)."""
    return bool(float(config.cegb_penalty_split) > 0.0
                or list(config.cegb_penalty_feature_coupled)
                or list(config.cegb_penalty_feature_lazy))


def _cegb_lazy_enabled(config: Config) -> bool:
    """The per-row on-demand penalty keeps a [N, F] device bitset
    (feature_used_in_data_, cost_effective_gradient_boosting.hpp:47) —
    masked-grower, single-device only."""
    return bool(list(config.cegb_penalty_feature_lazy))


def _config_grow_kwargs(config: Config, num_features: int) -> dict:
    """Static GrowConfig knobs derived purely from Config — one source of
    truth shared by SerialTreeLearner.__init__ and refresh_config, so a
    new config-derived knob cannot be added to one site and silently
    missed by the other."""
    return dict(
        num_leaves=int(config.num_leaves),
        max_depth=int(config.max_depth),
        use_l1=float(config.lambda_l1) > 0.0,
        use_mds=float(config.max_delta_step) > 0.0,
        extra_trees=bool(config.extra_trees),
        # by-node sample scales off the by-TREE sampled feature count
        # (ColSampler::GetByNode, col_sampler.hpp:90-140)
        bynode_k=(int(math.ceil(
            float(config.feature_fraction_bynode)
            * max(1, int(num_features
                         * min(float(config.feature_fraction), 1.0)))))
                  if float(config.feature_fraction_bynode) < 1.0 else 0),
        use_cegb=_cegb_enabled(config),
        use_cegb_lazy=_cegb_lazy_enabled(config),
    )


def _build_extras(config: Config, dataset) -> GrowExtras:
    import jax
    import jax.numpy as jnp
    F = max(dataset.num_features, 1)
    coupled = np.zeros(F, dtype=np.float64)
    pen = list(config.cegb_penalty_feature_coupled)
    if pen:
        if len(pen) != dataset.num_total_features:
            Log.fatal("cegb_penalty_feature_coupled should be the same "
                      "size as feature number.")
        for inner, real in enumerate(dataset.used_features):
            coupled[inner] = pen[real]
    lazy = np.zeros(F, dtype=np.float64)
    pen_lazy = list(config.cegb_penalty_feature_lazy)
    if pen_lazy:
        if len(pen_lazy) != dataset.num_total_features:
            Log.fatal("cegb_penalty_feature_lazy should be the same "
                      "size as feature number.")
        for inner, real in enumerate(dataset.used_features):
            lazy[inner] = pen_lazy[real]
    seed = int(config.extra_seed)
    key = jax.random.key_data(jax.random.PRNGKey(seed))
    ex = default_extras(dataset.num_features)
    return ex._replace(
        key=jnp.asarray(key, jnp.uint32),
        cegb_coupled=jnp.asarray(coupled),
        cegb_split_pen=jnp.asarray(float(config.cegb_penalty_split),
                                   jnp.float64),
        cegb_tradeoff=jnp.asarray(float(config.cegb_tradeoff), jnp.float64),
        cegb_lazy=jnp.asarray(lazy))


def resolve_hist_impl(config: Config) -> str:
    """'auto' -> Pallas VMEM one-hot kernel on TPU, XLA einsum on other
    accelerators, scatter-add on CPU."""
    impl = str(config.tpu_histogram_impl).lower()
    if impl in ("xla", "scatter"):
        return "scatter"
    import jax
    backend = jax.default_backend()
    pallas_ok = on_tpu()
    if impl == "onehot":
        return impl
    f32_req = str(config.tpu_hist_dtype).lower() in ("f32", "f64")
    if impl == "pallas":
        if not pallas_ok:
            Log.warning("tpu_histogram_impl=pallas unavailable on backend "
                        "%s; falling back to onehot" % backend)
            return "onehot"
        if f32_req:
            Log.warning("tpu_hist_dtype=%s needs the XLA einsum path; "
                        "using tpu_histogram_impl=onehot (the Pallas kernel "
                        "is bf16 hi/lo only)"
                        % str(config.tpu_hist_dtype).lower())
            return "onehot"
        return impl
    if backend == "cpu":
        return "scatter"
    if f32_req:
        return "onehot"
    return "pallas" if pallas_ok else "onehot"


def resolve_scan_impl(config: Config, gc_kwargs: dict) -> str:
    """'auto' -> the fused Pallas split-scan kernel on TPU when every
    semantic knob it implements covers the run (fast path: f32, no monotone
    constraints, no L1/max_delta_step, no extra_trees/by-node/CEGB, not the
    voting/feature parallel scans); otherwise the general XLA scan."""
    impl = str(config.tpu_scan_impl).lower()
    if impl == "xla":
        return "xla"
    # the fused kernel stages ~12 [Fp, Wp] f32 blocks in VMEM at once
    # and runs under its own scoped-VMEM request (pallas_scan.
    # scan_pair_vmem_bytes: 36 MB at 2,000 features x 128 lanes, and 70 MB
    # where the persist grower hands it the same features as padded
    # [F, 256] group planes: the widest shape compiled for the chip,
    # tests/test_chip_compile.py); wider feature planes (Fp*Wp beyond
    # 256k lanes) use the XLA scan
    Fp = -(-max(gc_kwargs["num_features"], 8) // 8) * 8
    Wp = -(-max(gc_kwargs["scan_width"], 128) // 128) * 128
    vmem_ok = Fp * Wp <= 256 * 1024
    ok = (on_tpu() and vmem_ok
          and not gc_kwargs["use_dp"] and not gc_kwargs["use_mc"]
          and not gc_kwargs["use_l1"] and not gc_kwargs["use_mds"]
          and not gc_kwargs["extra_trees"] and gc_kwargs["bynode_k"] == 0
          and not gc_kwargs["use_cegb"])
    if impl == "pallas":
        if not ok:
            Log.warning("tpu_scan_impl=pallas requires the fast-path "
                        "config (f32, no monotone/L1/max_delta_step/"
                        "extra_trees/by-node/CEGB); using the XLA scan")
            return "xla"
        return "pallas"
    return "pallas" if ok else "xla"


def resolve_use_dp(config: Config) -> bool:
    """Precision of leaf sums / gain math. The CPU backend always uses f64
    (it stands in for the reference CPU learner, which is double-only); on
    accelerators the default is f32 — the same trade the reference GPU
    learner makes (gpu_use_dp, docs/GPU-Performance.rst:43-47) — unless
    tpu_use_dp=true requests emulated f64."""
    import jax
    if jax.default_backend() == "cpu":
        return True
    return bool(config.tpu_use_dp)


def build_gw_global(dataset) -> "jnp.ndarray":
    """[G, W] map from (group, group-local bin) to global bin; entries past
    a group's width point at total_bins and are dropped by the scatter."""
    offs = np.asarray(dataset.group_offset, dtype=np.int64)
    widths = np.diff(np.append(offs, dataset.total_bins))
    W = int(widths.max()) if len(widths) else 1
    G = len(offs)
    gw = np.full((G, W), dataset.total_bins, dtype=np.int32)
    for g in range(G):
        gw[g, :widths[g]] = offs[g] + np.arange(widths[g])
    return jnp.asarray(gw)


def build_cat_layout(dataset, cat_width: int) -> CatLayout:
    """Host-side gather layout for categorical features (ops.split.CatLayout).

    used_bin follows feature_histogram.hpp:281-282: num_bin - 1 +
    (missing_type == None) — the trailing other/NaN bin never splits alone.
    """
    import jax.numpy as jnp
    cat_ids = np.nonzero(dataset.is_categorical)[0].astype(np.int32)
    C = len(cat_ids)
    if C == 0:
        return empty_cat_layout(cat_width)
    W = cat_width
    gather = np.zeros((C, W), dtype=np.int32)
    valid = np.zeros((C, W), dtype=bool)
    used = np.zeros(C, dtype=np.int32)
    nbins = np.zeros(C, dtype=np.int32)
    for i, f in enumerate(cat_ids):
        nb = int(dataset.bin_end[f] - dataset.bin_start[f])
        idx = dataset.bin_start[f] + np.arange(W)
        gather[i] = np.clip(idx, 0, dataset.total_bins - 1)
        valid[i, :nb] = True
        is_full = dataset.missing_type_arr[f] == 0
        used[i] = nb - 1 + int(is_full)
        nbins[i] = nb
    return CatLayout(cat_feature=jnp.asarray(cat_ids),
                     gather_idx=jnp.asarray(gather),
                     bin_valid=jnp.asarray(valid),
                     used_bin=jnp.asarray(used),
                     num_bin=jnp.asarray(nbins))


def _parse_forced_splits(config: Config, dataset):
    """forcedsplits_filename JSON -> BFS-ordered (leaf, inner_feature,
    threshold_bin) triples (SerialTreeLearner::ForceSplits,
    src/treelearner/serial_tree_learner.cpp:411-521). The right child of
    the k-th applied split receives leaf id k+1 — the same deterministic
    numbering the device grower assigns, so leaf targets are precomputable
    host-side. Thresholds convert value -> bin via BinMapper::ValueToBin
    (dataset.h:597); the kernel's bins<=thr-left convention matches the
    reference partition (DenseBin::Split sends bin <= ValueToBin(v) left,
    src/io/dense_bin.hpp:112), so T is stored as-is."""
    fname = str(config.forcedsplits_filename)
    if not fname:
        return None
    import json as _json
    from collections import deque
    with open(fname) as fh:
        spec = _json.load(fh)
    if not isinstance(spec, dict) or "feature" not in spec:
        Log.warning("forcedsplits_filename %s has no usable root node "
                    "(expected an object with a 'feature' key); no splits "
                    "will be forced" % fname)
        return None
    inner_of = {real: i for i, real in enumerate(dataset.used_features)}
    out = []
    q = deque([(spec, 0)])
    max_splits = max(int(config.num_leaves) - 1, 0)
    while q and len(out) < max_splits:
        node, leaf = q.popleft()
        real = int(node["feature"])
        if real not in inner_of:
            Log.fatal("forcedsplits_filename: split on unused feature %d"
                      % real)
        inner = inner_of[real]
        if bool(dataset.is_categorical[inner]):
            Log.fatal("forcedsplits_filename: categorical forced splits "
                      "are not supported on device_type=tpu")
        mapper = dataset.bin_mappers[real]
        T = int(mapper.value_to_bin(
            np.asarray([float(node["threshold"])]))[0])
        out.append((leaf, inner, T))
        s = len(out)
        left = node.get("left")
        right = node.get("right")
        if isinstance(left, dict) and "feature" in left \
                and "threshold" in left:
            q.append((left, leaf))
        if isinstance(right, dict) and "feature" in right \
                and "threshold" in right:
            q.append((right, s))
    if q:
        Log.warning("forced splits dropped: the specification holds more "
                    "than num_leaves - 1 = %d splits" % max_splits)
    return out or None


class ColSampler:
    """feature_fraction by-tree sampling (col_sampler.hpp:17-160); the
    by-node sample runs inside the device grower (GrowConfig.bynode_k)."""

    def __init__(self, config: Config, num_features: int):
        self.fraction = float(config.feature_fraction)
        self.num_features = num_features
        self.rng = np.random.default_rng(config.feature_fraction_seed)

    def sample(self) -> np.ndarray:
        if self.fraction >= 1.0:
            return np.ones(self.num_features, dtype=bool)
        k = max(1, int(self.num_features * self.fraction))
        mask = np.zeros(self.num_features, dtype=bool)
        idx = self.rng.choice(self.num_features, size=k, replace=False)
        mask[idx] = True
        return mask


class SerialTreeLearner:
    """Owns device arrays for one BinnedDataset and grows trees on it."""

    def __init__(self, config: Config, dataset):
        self.config = config
        self.dataset = dataset
        # run record: the host part of the layout (the multi-value and
        # nibble-packing decisions the GrowConfig reads) and the per-feature
        # tables' copy; the binned rows go on the device at the first read
        # of self.layout, which the persist path never makes
        with telemetry.scope("tree_learner::ToDevice(layout H2D)",
                             category="setup", always=True):
            self.meta = dataset.device_meta(config)
        self._layout = None
        self._layout_config = config     # the key GrowConfig was built on
        self.fix = dataset.fix_info()
        self.params = SplitParams.from_config(config)
        cat_bins = dataset.bin_end[dataset.is_categorical] - \
            dataset.bin_start[dataset.is_categorical] \
            if dataset.num_features else np.array([], dtype=np.int32)
        cat_width = int(cat_bins.max()) if len(cat_bins) else 1
        use_mc = bool(np.any(dataset.monotone)) if dataset.num_features else False
        rows_per_chunk = int(config.tpu_rows_per_chunk)
        if rows_per_chunk <= 0:
            # bound the one-shot scatter update tensor to ~256MB
            g = max(1, len(dataset.groups))
            rows_per_chunk = max(1 << 14, int(2 ** 25 / g))
            if rows_per_chunk >= dataset.num_data:
                rows_per_chunk = 0
        widths = dataset.bin_end - dataset.bin_start \
            if dataset.num_features else np.array([1])
        window_chunk = int(config.tpu_window_chunk)
        if window_chunk <= 0:
            # measured sweet spot on v5e with the sort pack + Pallas
            # histogram kernel; overwork per split is bounded by one chunk
            window_chunk = 8192
        hist_dtype = str(config.tpu_hist_dtype).lower()
        if hist_dtype == "auto":
            import jax
            # CPU stands in for the reference CPU learner, whose hist_t is
            # double: f64 bins are exact sums of the f32 per-row gradients
            # (order-independent), which is also what lets the widened
            # persist kernel emulation match the v1 grower bit for bit
            hist_dtype = ("f64" if jax.default_backend() == "cpu"
                          else "bf16x2")
        gc_kwargs = dict(
            total_bins=int(dataset.total_bins),
            num_features=int(dataset.num_features),
            use_mc=use_mc,
            rows_per_chunk=rows_per_chunk,
            cat_width=cat_width,
            hist_impl=resolve_hist_impl(config),
            scan_width=max(1, int(widths.max())),
            use_dp=resolve_use_dp(config),
            window_chunk=window_chunk,
            hist_dtype=hist_dtype,
            packed_4bit=bool(getattr(dataset, "device_packed", False)),
            multival=bool(getattr(dataset, "is_multival", False)),
            **_config_grow_kwargs(config, dataset.num_features),
        )
        forced_list = _parse_forced_splits(config, dataset)
        if forced_list:
            gc_kwargs["n_forced"] = len(forced_list)
            self.forced = ForcedInfo(
                leaf=jnp.asarray([x[0] for x in forced_list], jnp.int32),
                feature=jnp.asarray([x[1] for x in forced_list], jnp.int32),
                thr=jnp.asarray([x[2] for x in forced_list], jnp.int32))
        else:
            self.forced = empty_forced()
        self.grow_config = GrowConfig(
            scan_impl=resolve_scan_impl(config, gc_kwargs), **gc_kwargs)
        self._extras_base = _build_extras(config, dataset)
        self._tree_counter = 0
        self._feature_used_dev = None
        self._row_feat_used_dev = None   # CEGB lazy [N, F] bitset carry
        self.col_sampler = ColSampler(config, dataset.num_features)
        self.cat_layout = build_cat_layout(dataset, cat_width)
        # lazy CEGB keeps its per-row bitset in the masked grower's full-N
        # row space; the payload-sorted grower has no stable row residency.
        # Its unused-row counts accumulate in an f32 matmul — exact only
        # below 2^24 rows, so the row count is gated loudly.
        if self.grow_config.use_cegb_lazy and dataset.num_data >= (1 << 24):
            Log.fatal("cegb_penalty_feature_lazy supports up to 2^24 rows "
                      "(per-row acquisition counts are f32-exact)")
        # the payload-sorted grower gathers dense [N, G] windows; the
        # multi-value layout stays on the masked grower (row-sparse
        # scatter histograms, the MultiValBin serial path)
        self.use_partitioned = (dataset.num_data >= PARTITION_MIN_ROWS
                                and not self.grow_config.use_cegb_lazy
                                and not self.grow_config.multival)
        self.gw_global = build_gw_global(dataset)
        self._axis_name = None   # set by parallel learners

    @property
    def layout(self):
        """The binned rows on the device (DataLayout) that the v1 growers
        read, placed at the first read and shared through the dataset's
        cache; not blocked, as the grower's launch that follows waits."""
        if self._layout is None:
            with telemetry.scope("tree_learner::ToDevice(bins H2D)",
                                 category="setup", always=True):
                self._layout, _ = self.dataset.to_device(self._layout_config)
        return self._layout

    def refresh_config(self, config: Config) -> bool:
        """SerialTreeLearner::ResetConfig
        (src/treelearner/serial_tree_learner.cpp:124-160): re-derive the
        split params and the static grower knobs from an updated Config.
        Gain/regularization params flow as traced arguments, so most
        changes take effect without recompiling; flipping a static flag
        (use_l1, num_leaves, ...) re-keys the jit caches and compiles the
        new program on next use. Returns True when the static GrowConfig
        changed (callers must then drop any persistent-payload carry)."""
        self.config = config
        self.params = SplitParams.from_config(config)
        self.col_sampler.fraction = float(config.feature_fraction)
        kwargs = self.grow_config._asdict()
        kwargs.update(_config_grow_kwargs(config, self.dataset.num_features))
        kwargs["scan_impl"] = resolve_scan_impl(config, kwargs)
        new_gc = GrowConfig(**kwargs)
        changed = new_gc != self.grow_config
        self.grow_config = new_gc
        return changed

    @telemetry.timed("tree_learner::Train(launch)", category="tree_learner")
    def train_arrays(self, grad: jnp.ndarray, hess: jnp.ndarray,
                     bag_mask: jnp.ndarray):
        """Grow one tree fully on device; returns TreeArrays WITHOUT any
        host synchronization (the async fast path — dispatch returns
        immediately, XLA pipelines successive trees)."""
        # which path trained: tests and the profiling CLIs assert the fast
        # path engaged (or deliberately fell back) via these counters
        telemetry.count("tree_learner::v1_grow_trees",
                        category="tree_learner")
        fmask = jnp.asarray(self.col_sampler.sample())
        extras = self._next_extras()
        if self.use_partitioned:
            arrays, fu = grow_tree_partitioned(
                self.layout, grad, hess, bag_mask, self.meta, self.params,
                fmask, self.fix, self.grow_config,
                gw_global=self.gw_global, axis_name=self._axis_name,
                cat=self.cat_layout, extras=extras, forced=self.forced)
        elif self.grow_config.use_cegb_lazy:
            arrays, fu, rfu = grow_tree(
                self.layout, grad, hess, bag_mask, self.meta,
                self.params, fmask, self.fix, self.grow_config,
                axis_name=self._axis_name, cat=self.cat_layout,
                extras=extras, forced=self.forced,
                row_feat_used=self._row_feat_used_dev)
            self._row_feat_used_dev = rfu
        else:
            arrays, fu = grow_tree(
                self.layout, grad, hess, bag_mask, self.meta,
                self.params, fmask, self.fix, self.grow_config,
                axis_name=self._axis_name, cat=self.cat_layout,
                extras=extras, forced=self.forced)
        self._feature_used_dev = fu
        return arrays

    def _next_extras(self) -> GrowExtras:
        """Per-tree randomness (fold the tree counter into the base key so
        extra_trees / by-node draws differ across trees) plus the model-wide
        used-feature set the previous tree returned (CEGB's
        is_feature_used_in_split_ persists across iterations)."""
        import jax
        self._tree_counter += 1
        key = jax.random.key_data(jax.random.fold_in(
            jax.random.wrap_key_data(self._extras_base.key),
            self._tree_counter))
        ex = self._extras_base._replace(key=key)
        if self._feature_used_dev is not None:
            ex = ex._replace(feature_used=self._feature_used_dev)
        return ex

    # -- persistent-payload fast path (ops/grow_persist.py) -------------
    def _persist_axis_ok(self) -> bool:
        """Overridden by DataParallelTreeLearner: the persist path runs
        sharded there (psum of histogram planes inside the grow loop)."""
        return self._axis_name is None

    def _persist_rows_ok(self) -> bool:
        """Row-count bound for one payload: lane pointers and row ids are
        32-bit. From 2^24 rows on, row counts and segment positions are
        exact in i32 beside the f32 leaf state; the scan's
        hessian-derived counts, which gate min_data_in_leaf, stay f32
        estimates (ops/grow_persist.py:make_persist_grower)."""
        return self.dataset.num_data < (1 << 31) - (1 << 16)

    def _persist_obj_ok(self, objective) -> bool:
        """ONE capability probe: the objective's device_gradients()
        surface (objectives/base.py) decides fused-scan eligibility —
        None means host-only (fresh per-iteration inputs)."""
        dg = getattr(objective, "device_gradients", None)
        return dg is not None and dg() is not None

    def persist_bag_ok(self, bag_spec) -> bool:
        """Which device-side bag transforms this learner's persist path
        supports (single-payload: all of them)."""
        return bag_spec[0] in ("none", "bagging", "goss")

    def can_persist_scan(self, objective) -> bool:
        """True when the whole K-iteration scan can run on the persistent
        transposed payload (fused split kernel, no per-row gathers).
        Requirements beyond the Pallas-scan fast path: numerical features
        only, a payload pack plan (<= 256 bins per group — narrow groups
        nibble-pack, device_packed v1 storage is fine), rows under 2^31
        less 2^16 (_persist_rows_ok); sample weights ride as a payload row and EFB bundles
        decode in the split kernel. Single device or the data/voting-
        parallel learners (sharded persist). tpu_persist_scan=force
        engages the XLA kernel emulation off-TPU (tests)."""
        from ..ops.grow_persist import persist_pack_ok
        ds = self.dataset
        gc = self.grow_config
        opt = str(getattr(self.config, "tpu_persist_scan", "auto")).lower()
        if opt in ("false", "0", "off"):
            return False
        if (opt == "force" and objective is not None
                and not self._persist_obj_ok(objective)):
            # the config REQUESTED the fused path; refuse loudly instead
            # of silently training on the v1 host path (the two would
            # diverge in launch count and, for quantized modes, in bits)
            Log.fatal(
                "tpu_persist_scan=force: objective '%s' has no device "
                "gradient kernel (device_gradients() is None — it needs "
                "fresh per-iteration host inputs); drop the force or "
                "pick a fused-scan-capable objective"
                % getattr(objective, "name", type(objective).__name__))
        if opt != "force":
            if not on_tpu():
                return False
            if gc.scan_impl != "pallas":
                return False
            if ds.num_data < PARTITION_MIN_ROWS:
                return False
        pack_ok, why = persist_pack_ok(ds)
        if not pack_ok and not getattr(ds, "_persist_pack_warned", False):
            # graceful, logged fallback instead of the historical
            # NotImplementedError hard crash on unpackable geometries
            ds._persist_pack_warned = True
            Log.info("persistent-payload fast path unavailable (%s); "
                     "using the v1 grower" % why)
        bundled = (len(ds.groups) != ds.num_features
                   or bool(np.any(ds.needs_fix)))
        return (pack_ok
                and gc.n_forced == 0
                and not gc.use_cegb_lazy
                and not gc.multival
                and self.cat_layout.cat_feature.shape[0] == 0
                and ds.num_features > 0
                # EFB bundles ride the persist path (group-byte decode in
                # split_pass + bundle-native block scan with in-kernel
                # FixHistogram); the voting eval's winner gather is
                # block-shaped, so bundled voting stays on the v1 path
                and not (bundled and gc.parallel_mode == "voting")
                and self._persist_rows_ok()
                and self._persist_axis_ok()
                and objective is not None
                and self._persist_obj_ok(objective))

    @staticmethod
    def _persist_kernel_mode():
        """(kernel_impl, interpret) by backend: Mosaic kernels on TPU, the
        XLA emulation elsewhere (tpu_persist_scan=force paths/tests)."""
        if on_tpu():
            return "pallas", False
        return "xla", True

    def _persist_level_mode(self) -> str:
        """tpu_level_grow: 'auto' engages the level-parallel phase when
        can_level_grow(grow_config) holds; 'off' forces per-split."""
        opt = str(getattr(self.config, "tpu_level_grow", "auto")).lower()
        return "off" if opt in ("off", "false", "0") else "auto"

    def _persist_health_mode(self) -> bool:
        """tpu_numerics_stats: 'auto' accumulates the device-side
        numerics health vector (NaN/Inf counters + split-margin
        histogram) in the persist scan carry WHEN telemetry is on, so
        the default run's compiled program carries no probe (the
        off-mode contract: the run record changes no program).
        'on'/'force' accumulates regardless (the flush's counters are
        recorded in every mode, its histogram with telemetry on);
        'off' zeroes it."""
        opt = str(getattr(self.config, "tpu_numerics_stats",
                          "auto")).lower()
        if opt in ("off", "false", "0"):
            return False
        if opt in ("on", "force", "1", "true"):
            return True
        return telemetry.enabled()

    def _persist_kernel_effective(self):
        """(kernel_impl, interpret, score64): the payload asset layout
        (f64 score rows in xla mode) must be decided before the grower
        is built."""
        kernel_impl, interpret = self._persist_kernel_mode()
        return kernel_impl, interpret, kernel_impl == "xla"

    def _persist_cached(self, objective, k: int, bag_spec=("none",),
                        mode: str = "gbdt"):
        from ..ops.grow_persist import (build_assets, make_bag_transform,
                                        make_persist_grower,
                                        make_scan_driver)
        cache = getattr(self.dataset, "_persist_cache", None)
        if cache is None:
            cache = self.dataset._persist_cache = {}
        K = getattr(objective, "num_model_per_iteration", 1)
        # pos/row grad modes weight through their own args — only the
        # 'payload' fill reads the payload weight row
        use_w_row = objective.persist_grad_mode() == "payload"
        kernel_impl, interpret, score64 = self._persist_kernel_effective()
        level_mode = self._persist_level_mode()
        health = self._persist_health_mode()
        akey = ("assets", K, use_w_row, score64)
        assets = cache.get(akey)
        if assets is None:
            assets = build_assets(self.dataset, self.dataset.metadata.label,
                                  num_scores=K, use_weight_row=use_w_row,
                                  score64=score64)
            cache[akey] = assets
        # RF bags through per-iteration weight vectors (apply_row_weights)
        # rather than a bag_spec, but the count semantics are the same:
        # out-of-bag rows still ride the payload segments, so leaf counts
        # must come from the hessian-derived scan recovery, not the
        # geometric partition counts
        stat_from_scan = bag_spec[0] != "none" or mode == "rf"
        gkey = ("grower", K, use_w_row, self.grow_config,
                stat_from_scan, kernel_impl, level_mode, health)
        dkey = ("driver", K, use_w_row, k, self.grow_config,
                objective.static_fingerprint(), bag_spec, kernel_impl,
                level_mode, health, mode)
        gr, driver = cache.get(gkey), cache.get(dkey)
        if gr is not None and driver is not None:
            return assets, gr, driver
        # run record: host closure building, apart from the payload pack
        with telemetry.scope("tree_learner::PersistBuild(trace)",
                             category="setup", always=True):
            if gr is None:
                gr = make_persist_grower(assets, self.meta,
                                         self.grow_config,
                                         interpret=interpret,
                                         kernel_impl=kernel_impl,
                                         stat_from_scan=stat_from_scan,
                                         fix=self.fix,
                                         level_mode=level_mode,
                                         health=health)
                cache[gkey] = gr
            if driver is None:
                bag_fn = (make_bag_transform(bag_spec, assets.geometry)
                          if stat_from_scan else None)
                # the objective's ONE capability surface hands the driver
                # both the fill contract and the kernel
                gmode, gfn = objective.device_gradients()
                if mode == "rf":
                    driver = make_scan_driver(gr, self.grow_config, k, gfn,
                                              mode="rf")
                elif K > 1:
                    driver = make_scan_driver(gr, self.grow_config, k, gfn,
                                              bag_fn=bag_fn)
                else:
                    driver = make_scan_driver(gr, self.grow_config, k, gfn,
                                              grad_mode=gmode,
                                              bag_fn=bag_fn)
                cache[dkey] = driver
        return assets, gr, driver

    @staticmethod
    def _count_persist_trees(gr, k: int, grad_mode: str = "payload"):
        """Run record: k trees on the persist path, and by which of the
        grower's mechanisms (split scan over the bundled group planes;
        smaller-child histogram built inside split_pass; a payload row
        wide enough for its width to size the kernels' chunks; row counts
        in i32 past 2^24 rows; the sharded grower) and of the fused
        scan's gradient fills (the per-query ranking fill, grad_mode
        'pos')."""
        telemetry.count("tree_learner::persist_scan_trees", float(k),
                        category="tree_learner")
        if grad_mode == "pos":
            telemetry.count("tree_learner::rank_pos_trees", float(k),
                            category="tree_learner")
        if gr.block_scan:
            telemetry.count("tree_learner::blockscan_trees", float(k),
                            category="tree_learner")
        if gr.inpass_hist:
            telemetry.count("tree_learner::inpass_hist_trees", float(k),
                            category="tree_learner")
        if gr.wide_payload:
            telemetry.count("tree_learner::wide_payload_trees", float(k),
                            category="tree_learner")
        if gr.large_counts:
            telemetry.count("tree_learner::large_count_trees", float(k),
                            category="tree_learner")
        shards = getattr(gr, "num_shards", 0)
        if shards:
            # the sharded grower (parallel/learners.py): its trees, and
            # over how many shards the newest launch ran (set, not summed)
            telemetry.count("tree_learner::sharded_persist_trees", float(k),
                            category="tree_learner")
            telemetry.clear_counts_prefix("tree_learner::shards")
            telemetry.count("tree_learner::shards", float(shards),
                            category="tree_learner")

    @staticmethod
    def _persist_init_carry(gr, assets, score0):
        """The first program that takes the host payload. The span is the
        dispatch's wall (the staging copy of ``pay0`` and that program's
        compile or cache load); without a block its end is the dispatch's
        end, not the copy's."""
        with telemetry.scope("tree_learner::InitCarry(H2D launch)",
                             category="setup", always=True):
            return gr.init_carry(assets.pay0, jnp.asarray(score0))

    @telemetry.timed("tree_learner::TrainScanPersist(launch)",
                     category="tree_learner")
    def train_arrays_scan_persist(self, objective, score0, fmasks, wkeys,
                                  iters, shrink: float, k: int,
                                  bag_spec=("none",)):
        """K iterations on the persistent payload. Keeps (pay, score_pos)
        as a device carry on this learner; scores return to row order only
        in persist_finalize_scores()."""
        assets, gr, driver = self._persist_cached(objective, k, bag_spec)
        self._count_persist_trees(gr, k, objective.persist_grad_mode())
        pay = getattr(self, "_persist_carry", None)
        first = pay is None
        if first:
            pay = self._persist_init_carry(gr, assets, score0)
        args = (pay, jnp.asarray(fmasks), jnp.asarray(wkeys, jnp.uint32),
                jnp.asarray(iters, jnp.int32), self.params,
                jnp.asarray(shrink, jnp.float64),
                objective.persist_grad_args())
        if first:
            # run record, trace mode only: the fused program's HLO, and so
            # which of its instructions each named scope holds, on request
            telemetry.keep_program("ops::persist_scan(launch)",
                                   driver.__wrapped__, args)
        pay, stacked, stats = driver(*args)
        # level-program stats stay a DEVICE array until finalize: the
        # fast path must not sync per batch just to bump a counter
        prev = getattr(self, "_level_stats_dev", None)
        self._level_stats_dev = stats if prev is None else prev + stats
        # host-side tree tally feeding the flush-time wire-byte model
        # (one root-plane exchange per tree on the sharded path)
        self._persist_pending_trees = (
            getattr(self, "_persist_pending_trees", 0)
            + k * getattr(gr, "K", 1))
        self._persist_carry = pay
        self._persist_gr = gr
        return stacked

    @telemetry.timed("tree_learner::TrainScanPersistRF(launch)",
                     category="tree_learner")
    def train_arrays_scan_persist_rf(self, objective, score0, fmasks,
                                     bagw, aux, bias: float, k: int):
        """K random-forest iterations fused into one persist-driver
        program: constant-init-score gradients, host-RNG bag masks as
        traced [k, n] weight vectors, and the running-average score
        dance all inside the scan (the RF half of the fused boosting
        iteration). aux is [k, 2] f64 = (total_iter, 1/(total_iter+1));
        bias is the objective's constant init score."""
        assets, gr, driver = self._persist_cached(objective, k,
                                                  mode="rf")
        self._count_persist_trees(gr, k)
        pay = getattr(self, "_persist_carry", None)
        if pay is None:
            pay = self._persist_init_carry(gr, assets, score0)
        pay, stacked, stats = driver(pay, jnp.asarray(fmasks),
                                     jnp.asarray(bagw, jnp.float32),
                                     jnp.asarray(aux, jnp.float64),
                                     jnp.arange(k, dtype=jnp.int32),
                                     self.params,
                                     jnp.asarray(bias, jnp.float64))
        prev = getattr(self, "_level_stats_dev", None)
        self._level_stats_dev = stats if prev is None else prev + stats
        self._persist_pending_trees = (
            getattr(self, "_persist_pending_trees", 0) + k)
        self._persist_carry = pay
        self._persist_gr = gr
        return stacked

    def persist_add_score_delta(self, values, cls: int = 0):
        """Apply a host-computed row-ordered f64 score delta to the live
        payload carry (DART's drop/normalize between fused iterations)
        WITHOUT leaving the device: one gather-add program per call,
        counted into the iter_launches stat. Caller guarantees a live
        carry (boosting/dart.py routes through train_score otherwise)."""
        import jax
        from ..ops.grow_persist import STAT_ITER_LAUNCH, STATS_LEN
        gr = self._persist_gr
        fn = getattr(gr, "_add_delta_jit", None)
        if fn is None:
            fn = gr._add_delta_jit = jax.jit(
                gr.add_score_delta, donate_argnums=(0,),
                static_argnames=("cls",))
        self._persist_carry = fn(self._persist_carry,
                                 jnp.asarray(values, jnp.float64),
                                 cls=cls)
        st = getattr(self, "_level_stats_dev", None)
        if st is None:
            st = jnp.zeros((STATS_LEN,), jnp.int32)
        self._level_stats_dev = st.at[STAT_ITER_LAUNCH].add(1)

    def flush_level_stats(self):
        """Convert the accumulated device-side stats (level-program
        counters + the numerics health vector) into telemetry counters
        and the ``numerics::split_margin`` histogram. Called at
        score-finalize time — the first natural host sync after a
        persist batch; the ONLY host-side cost of the runtime numerics
        sentinel, measured under ``numerics::flush`` (the < 2%
        overhead pin)."""
        st = getattr(self, "_level_stats_dev", None)
        if st is None:
            return
        self._level_stats_dev = None
        trees = int(getattr(self, "_persist_pending_trees", 0))
        self._persist_pending_trees = 0
        import jax
        # the device_get may drain the still-running async batch — that
        # wait is pipeline time (the callers' device_wait spans own it),
        # not sentinel cost; only the host-side conversion below is the
        # sentinel's bill, and that is what the < 2% pin measures
        with telemetry.scope("tree_learner::FlushStats(D2H+wait)",
                             category="device_wait", always=True):
            v = np.asarray(jax.device_get(st))
        with telemetry.scope("numerics::flush", category="numerics"):
            if v[0]:
                telemetry.count("tree_learner::level_programs",
                                float(v[0]), category="tree_learner")
            if v[1]:
                telemetry.count("tree_learner::level_fallback_splits",
                                float(v[1]), category="tree_learner")
            if v[2]:
                # compiled-program launches the fused path dispatched
                # (scan-driver invocations + DART score-delta applies):
                # the launches_per_iter bench numerator
                telemetry.count("tree_learner::iter_launches",
                                float(v[2]), category="tree_learner")
            from ..telemetry import health as telemetry_health
            telemetry_health.flush_device_stats(v[3:])
            gr = getattr(self, "_persist_gr", None)
            if gr is not None and getattr(gr, "axis_name", None) \
                    is not None and trees:
                # estimated per-shard histogram-exchange payload for the
                # flushed batches (mirrors the plane_psum/vote_allgather
                # sites exactly — ops/grow_persist.wire_bytes_model);
                # the full-width twin is the hist_compress_ratio
                # denominator the --perf sentinel gates
                actual, full = gr.wire_bytes_model(int(v[0]), int(v[1]),
                                                   trees)
                if actual:
                    from ..telemetry import histo as telemetry_histo
                    telemetry.count("collective::dcn_hist_bytes",
                                    float(actual), category="collective")
                    telemetry.count(
                        "collective::dcn_hist_bytes_fullwidth",
                        float(full), category="collective")
                    telemetry_histo.observe("collective::psum::bytes",
                                            float(actual), unit="bytes",
                                            category="collective")

    def persist_finalize_scores(self):
        """Row-ordered f64 scores from the live carry (None when no carry).
        Keeps the carry alive — finalize is a pure read."""
        pay = getattr(self, "_persist_carry", None)
        if pay is None:
            return None
        with telemetry.scope("tree_learner::FinalizeScores(launch)",
                             category="launch", always=True):
            self.flush_level_stats()
            gr = self._persist_gr
            return gr.finalize_scores(pay).astype(jnp.float64)

    @telemetry.timed("tree_learner::TrainScan(launch)",
                     category="tree_learner")
    def train_arrays_scan(self, objective, score0, fmasks, keys,
                          shrink: float, k: int):
        """K boosting iterations in ONE jitted lax.scan: gradients ->
        grow -> score update never leave the device, and the per-call
        dispatch cost is paid once per K iterations. Returns (final
        score, final feature_used, stacked TreeArrays with row_leaf
        dropped)."""
        import jax
        # the v1 grower inside one lax.scan is still the v1 grower: count
        # it, so a run that missed the persist path says so
        telemetry.count("tree_learner::v1_grow_trees", float(k),
                        category="tree_learner")
        # cache the compiled scan ON THE DATASET: every Booster builds a
        # fresh learner (bench warmup vs measured run, cv folds, ...), and
        # a fresh closure means a recompile — the program only depends
        # on the dataset layout + grow config + objective
        cache = getattr(self.dataset, "_scan_cache", None)
        if cache is None:
            cache = self.dataset._scan_cache = {}
        # everything config-valued (SplitParams, FeatureMeta's monotone/
        # penalty, the CEGB extras) is passed as a TRACED argument — baking
        # it into the closure would let a second training on the same
        # Dataset silently reuse the first run's hyperparameters. The
        # objective's device data (labels, weights, masks) is likewise
        # traced (gargs below); its closure-baked scalars (sigmoid, class
        # weights, ...) are captured in static_fingerprint so differing
        # hyperparameters compile separately.
        cache_key = (k, self.grow_config, objective.static_fingerprint())
        fn = cache.get(cache_key)
        if fn is None:
            grad_fn = objective.grad_fn()
            gc = self.grow_config
            use_part = self.use_partitioned
            cat, gw = self.cat_layout, self.gw_global
            n = self.dataset.num_data

            # layout is a traced ARGUMENT: closure-captured device arrays
            # embed as HLO constants, and a [N, G] constant bloats every
            # compile (hundreds of MB at HIGGS-scale row counts)
            @jax.jit
            def run(layout, score0, fu0, rfu0, fmasks, keys, base_extras,
                    shrink_t, meta, params, fix, gargs, forced):
                bag = jnp.ones(n, bool)

                def body(carry, per):
                    score, fu, rfu = carry
                    fmask, kk = per
                    g, h = grad_fn(score, *gargs)
                    ex = base_extras._replace(key=kk, feature_used=fu)
                    g = g.astype(jnp.float32)
                    h = h.astype(jnp.float32)
                    rfu2 = rfu
                    if use_part:
                        arrays, fu2 = grow_tree_partitioned(
                            layout, g, h, bag, meta, params, fmask, fix, gc,
                            gw_global=gw, cat=cat, extras=ex, forced=forced)
                    elif gc.use_cegb_lazy:
                        arrays, fu2, rfu2 = grow_tree(
                            layout, g, h, bag, meta, params, fmask, fix, gc,
                            cat=cat, extras=ex, forced=forced,
                            row_feat_used=rfu)
                    else:
                        arrays, fu2 = grow_tree(
                            layout, g, h, bag, meta, params, fmask, fix, gc,
                            cat=cat, extras=ex, forced=forced)
                    upd = arrays.leaf_value.astype(jnp.float64)[
                        arrays.row_leaf] * shrink_t
                    score2 = score + jnp.where(arrays.num_leaves > 1, upd,
                                               0.0)
                    out = arrays._replace(
                        row_leaf=jnp.zeros((0,), jnp.int32))
                    return (score2, fu2, rfu2), out

                (scoreK, fuK, rfuK), stacked = jax.lax.scan(
                    body, (score0, fu0, rfu0), (fmasks, keys), length=k)
                return scoreK, fuK, rfuK, stacked
            cache[cache_key] = run
            fn = run
        base = self._extras_base
        fu0 = (self._feature_used_dev if self._feature_used_dev is not None
               else base.feature_used)
        if self.grow_config.use_cegb_lazy:
            rfu0 = (self._row_feat_used_dev
                    if self._row_feat_used_dev is not None
                    else jnp.zeros((self.layout.bins.shape[0],
                                    self.dataset.num_features), jnp.bool_))
        else:
            rfu0 = jnp.zeros((0, 0), jnp.bool_)
        scoreK, fuK, rfuK, stacked = fn(
            self.layout, score0, fu0, rfu0, fmasks, keys, base,
            jnp.asarray(shrink, jnp.float64),
            self.meta, self.params, self.fix, objective._grad_args(),
            self.forced)
        if self.grow_config.use_cegb_lazy:
            self._row_feat_used_dev = rfuK
        return scoreK, fuK, stacked

    def train(self, grad: jnp.ndarray, hess: jnp.ndarray,
              bag_mask: jnp.ndarray) -> Tuple[Tree, jnp.ndarray]:
        """Grow one tree; returns (host Tree, device row->leaf array).

        grad/hess must be zero outside the bag (SerialTreeLearner::Train's
        contract is that the learner only sees in-bag rows; the masked design
        keeps shapes static instead).
        """
        arrays = self.train_arrays(grad, hess, bag_mask)
        import jax
        # row_leaf stays on device: the host Tree never reads it and the
        # [N] transfer would dominate the sync
        with telemetry.scope("tree_learner::SyncTree(D2H+wait)",
                             category="device_wait"):
            host = jax.device_get(
                arrays._replace(row_leaf=jnp.zeros((0,), jnp.int32)))
        tree = Tree.from_grower(host, self.dataset)
        return tree, arrays.row_leaf


def create_tree_learner(learner_type: str, device_type: str, config: Config,
                        dataset):
    """TreeLearner::CreateTreeLearner (src/treelearner/tree_learner.cpp).

    The data/feature/voting learners are sharding configurations of the same
    device grower; until the mesh wiring lands in lightgbm_tpu/parallel they
    fall back to serial with a warning.
    """
    if learner_type == "serial":
        return SerialTreeLearner(config, dataset)
    from ..parallel import create_parallel_learner
    return create_parallel_learner(learner_type, config, dataset)
