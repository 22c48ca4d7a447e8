"""Distributed tree learners: sharding configurations of the device grower.

TPU-native rebuild of the three reference parallel learners
(src/treelearner/feature_parallel_tree_learner.cpp,
data_parallel_tree_learner.cpp, voting_parallel_tree_learner.cpp) and the
collectives they run over src/network. The reference moves serialized
histograms through hand-rolled ReduceScatter/Allgather over TCP/MPI; here
the binned matrix is sharded row-wise over a `jax.sharding.Mesh` axis and
the same jitted grower runs under shard_map with `lax.psum` reducing
histograms over ICI — the ReduceScatter at data_parallel_tree_learner.cpp:163
plus SyncUpGlobalBestSplit (parallel_tree_learner.h:190) collapse into that
one collective, because after psum every device scans identical histograms
and deterministically agrees on the global best split.

All three reference strategies are real here:
  * data-parallel: rows sharded, full-histogram psum (ReduceScatter analog,
    data_parallel_tree_learner.cpp:163);
  * feature-parallel: data replicated, each shard scans its owned features,
    the global best split is agreed via all_gather + deterministic merge
    (feature_parallel_tree_learner.cpp:33-77);
  * voting-parallel: rows sharded, per-shard top-k vote, and ONLY the
    2k globally voted features' histogram bins are psum-reduced
    (PV-tree; voting_parallel_tree_learner.cpp:153-344) — the
    communication-volume compression that matters once the mesh axis
    crosses DCN.

Fault scope (resilience/): the in-program mesh collectives here
(psum/all_gather inside the jitted growers) fail via XLA's distributed
runtime — an abort with an XlaRuntimeError that the retry guard's caller
surfaces — while the HOST-side DCN collectives around them (binning
allgather, metric allreduce, resume agreement) run under
``resilience.retry.guard`` with a deadline and bounded retries, so a gone
peer never hangs the launch loop.
"""
from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.tree import Tree
from ..ops.grow import DataLayout, GrowConfig, grow_tree, grow_tree_partitioned
from ..telemetry import events as telemetry
from ..treelearner.serial import PARTITION_MIN_ROWS, SerialTreeLearner
from ..utils.log import Log

AXIS = "data"

def _make_mesh(num_devices: int = 0) -> Mesh:
    devs = jax.devices()
    n = num_devices if num_devices > 0 else len(devs)
    return Mesh(np.array(devs[:n]), (AXIS,))


class DataParallelTreeLearner(SerialTreeLearner):
    """Rows sharded over the mesh; histograms psum-reduced.

    Equivalent of DataParallelTreeLearner<T> (data_parallel_tree_learner.cpp)
    with the feature-ownership ReduceScatter replaced by a full psum: the
    reference scatters histogram blocks to per-feature owners to split scan
    work across machines, but on TPU the scan is a single fused device op and
    the psum'd histogram is already resident on every chip.
    """

    def __init__(self, config, dataset, mesh: Mesh = None):
        super().__init__(config, dataset)
        self.mesh = mesh if mesh is not None else _make_mesh(
            int(config.tpu_num_devices))
        self.num_shards = self.mesh.devices.size
        n = dataset.num_data
        self._pad = (-n) % self.num_shards
        self._axis_name = AXIS
        # communication-efficient exchange (ROADMAP item 2): int16
        # quantized histogram reductions, certified at config time
        # against the quant_certify budget (int8 is refused there), and
        # double-buffered level-program reductions. Both knobs are
        # wire-format choices — the reduced global planes are identical
        # on every shard either way (bit-exact under a fixed mesh).
        from .distributed import resolve_comm_overlap, resolve_hist_quant
        # single-process sharding sees the FULL dataset, so the max
        # sample weight is trivially rank-uniform (the contract scale
        # must be identical on every shard)
        w = dataset.metadata.weight
        w_max = float(np.max(w)) if w is not None and len(w) else 1.0
        hq = resolve_hist_quant(config, (n + self._pad) // self.num_shards,
                                self.num_shards, weight_max=w_max)
        self.hist_quant, self.hist_quant_cert = hq if hq else (None, None)
        self.comm_overlap = resolve_comm_overlap(config)
        # the v1 sharded grower's row-sharded bins, placed on first use
        # (train_arrays): a run that stays on the persist path never pays
        self._bins_padded = None
        # rebuild the sharded grow fn once per dataset
        self._sharded_grow = None

    def _build(self):
        mesh = self.mesh
        gc = self.grow_config._replace()
        meta, params, fix = self.meta, self.params, self.fix
        layout_rest = tuple(self.layout)[1:]   # all fields after bins
        #              (incl. the 4-bit unpack maps when packing is on)

        cat = self.cat_layout
        n_shard = (self.dataset.num_data + self._pad) // self.num_shards
        # the multi-value (ELL) layout always takes the masked grower
        # (row-sparse scatter histograms have no partitioned variant)
        use_part = n_shard >= PARTITION_MIN_ROWS and not gc.multival
        gw_global = self.gw_global
        mv = bool(gc.multival)
        qc = self.hist_quant
        # ELL row-sparse arrays are row-aligned: shard them WITH the rows
        # (they ride as args, not closure constants, so shard_map splits
        # them; pad rows carry the G sentinel group = contribute nothing)
        ell_specs = (P(AXIS), P(AXIS)) if mv else ()

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(), P())
            + ell_specs,
            out_specs=(_tree_arrays_spec(gc, row_sharded=True), P()),
            check_vma=False)
        def run(bins, grad, hess, bag, fmask, extras, *ell):
            layout = DataLayout(bins, *layout_rest)
            if mv:
                layout = layout._replace(ell_grp=ell[0], ell_bin=ell[1])
            if use_part:
                return grow_tree_partitioned(
                    layout, grad, hess, bag, meta, params, fmask, fix, gc,
                    gw_global=gw_global, axis_name=AXIS,
                    cat=cat, extras=extras, quant=qc)
            return grow_tree(layout, grad, hess, bag, meta, params, fmask,
                             fix, gc, axis_name=AXIS, cat=cat,
                             extras=extras, quant=qc)
        return run

    def train_arrays(self, grad: jnp.ndarray, hess: jnp.ndarray,
                     bag_mask: jnp.ndarray):
        """Sharded grow; returns TreeArrays with row_leaf sliced back to
        num_data (the async fast path used by GBDT.train_one_iter)."""
        telemetry.count("tree_learner::v1_grow_trees",
                        category="tree_learner")
        if self._sharded_grow is None:
            self._sharded_grow = self._build()
        pad = self._pad
        if self._bins_padded is None:
            # pad the HBM-resident bins ONCE (per-tree inputs pad per
            # call) and commit them to the row sharding the program runs
            # under: layout.bins is an uncommitted array on the first
            # device, and left there every call would re-scatter it
            bins = (jnp.pad(self.layout.bins, ((0, pad), (0, 0)))
                    if pad else self.layout.bins)
            self._bins_padded = jax.device_put(
                bins, NamedSharding(self.mesh, P(AXIS)))
        bins = self._bins_padded
        if pad:
            grad = jnp.pad(grad, (0, pad))
            hess = jnp.pad(hess, (0, pad))
            bag_mask = jnp.pad(bag_mask, (0, pad))
        fmask = jnp.asarray(self.col_sampler.sample())
        ell = ()
        if self.grow_config.multival:
            ell = getattr(self, "_ell_padded", None)
            if ell is None:
                eg, eb = self.layout.ell_grp, self.layout.ell_bin
                if pad:
                    G = int(self.layout.group_offset.shape[0])
                    eg = jnp.pad(eg, ((0, pad), (0, 0)), constant_values=G)
                    eb = jnp.pad(eb, ((0, pad), (0, 0)))
                ell = self._ell_padded = (eg, eb)
        # the sharded program's histogram psums / candidate gathers run over
        # the mesh axis inside this one dispatch — the ReduceScatter /
        # SyncUpGlobalBestSplit of the reference, attributed per tree
        with telemetry.scope("collective::sharded_grow(launch)",
                             category="collective",
                             shards=self.num_shards,
                             mode=self.grow_config.parallel_mode):
            arrays, fu = self._sharded_grow(bins, grad, hess, bag_mask,
                                            fmask, self._next_extras(), *ell)
        self._feature_used_dev = fu
        if pad:
            arrays = arrays._replace(
                row_leaf=arrays.row_leaf[:self.dataset.num_data])
        return arrays

    def train(self, grad: jnp.ndarray, hess: jnp.ndarray,
              bag_mask: jnp.ndarray) -> Tuple[Tree, jnp.ndarray]:
        arrays = self.train_arrays(grad, hess, bag_mask)
        host = jax.device_get(
            arrays._replace(row_leaf=jnp.zeros((0,), jnp.int32)))
        tree = Tree.from_grower(host, self.dataset)
        return tree, arrays.row_leaf

    # -- sharded persistent-payload fast path ---------------------------
    # The K-iteration persist scan (ops/grow_persist.py) under shard_map:
    # per-shard payloads carrying GLOBAL row ids (bag draws must agree
    # with serial runs; finalize subtracts the shard offset), histogram
    # planes and left counts psum'd inside the grow loop (the
    # ReduceScatter at data_parallel_tree_learner.cpp:163 fused into the
    # per-split kernel step). The base-class driver methods
    # (train_arrays_scan_persist / persist_finalize_scores) work
    # unchanged against the wrapper this _persist_cached returns.

    def _persist_axis_ok(self) -> bool:
        # data-parallel AND voting-parallel ride the sharded persist
        # driver (voting = local planes + in-eval vote, grow_persist);
        # feature-parallel replicates rows and keeps the v1 path
        if self.grow_config.parallel_mode == "feature":
            return False
        n, S = self.dataset.num_data, self.num_shards
        if n % S == 0:
            return True
        if not getattr(self, "_uneven_shards_said", False):
            # every other gate of can_persist_scan has passed when this one
            # is asked: say once that the per-tree v1 grower takes the job
            self._uneven_shards_said = True
            telemetry.count("tree_learner::sharded_v1_fallback",
                            category="tree_learner")
            Log.warning(
                "tree_learner=%s: %d rows do not divide into %d equal "
                "shards, so the sharded persistent-payload fast path is "
                "off and the per-tree v1 grower trains this run (pad or "
                "trim the rows to a multiple of %d to get it back)"
                % (self.grow_config.parallel_mode, n, S, S))
        return False

    def _persist_rows_ok(self) -> bool:
        # 32-bit row ids bound the TOTAL rows. From 2^24 rows on the
        # global counts (psum'd partition counts) and the shard's segment
        # positions are exact in i32 (large_counts below); the scan's
        # hessian-derived counts, which gate min_data_in_leaf, stay f32
        # estimates (ops/grow_persist.py:make_persist_grower)
        return self.dataset.num_data < (1 << 31) - (1 << 16)

    def _persist_obj_ok(self, objective) -> bool:
        # payload-order gradients only: row-order mode needs global row
        # structure (lambdarank query groups) that crosses shards
        return objective.payload_grad_fn() is not None

    def persist_bag_ok(self, bag_spec) -> bool:
        # bagging draws are row-local; GOSS's global order statistic is a
        # radix select on psum'd counts (grow_persist._kth_largest), so
        # sharded runs reproduce the serial threshold exactly
        return bag_spec[0] in ("none", "bagging", "goss")

    def _persist_cached(self, objective, k: int, bag_spec=("none",)):
        from ..ops.grow_persist import (EXACT_F32_ROWS, build_assets,
                                        make_bag_transform,
                                        make_persist_grower,
                                        make_scan_driver)
        cache = getattr(self.dataset, "_persist_cache", None)
        if cache is None:
            cache = self.dataset._persist_cache = {}
        S = self.num_shards
        mesh = self.mesh
        pay_spec = P(None, AXIS)
        kernel_impl, interpret, score64 = self._persist_kernel_effective()
        level_mode = self._persist_level_mode()
        akey = ("assets_sharded", S, score64)
        assets = cache.get(akey)
        if assets is None:
            assets = build_assets(self.dataset, self.dataset.metadata.label,
                                  num_shards=S, score64=score64)
            # run record: each chip's lanes of the host payload onto the
            # mesh; blocked, so that the span holds the copy and not its
            # dispatch alone
            with telemetry.scope("tree_learner::ShardPayload(device_put)",
                                 category="setup", always=True):
                assets = assets._replace(pay0=jax.block_until_ready(
                    jax.device_put(assets.pay0,
                                   NamedSharding(mesh, pay_spec))))
            cache[akey] = assets
        stat_from_scan = bag_spec[0] != "none"
        gc = self.grow_config
        health = self._persist_health_mode()
        gkey = ("grower_sharded", S, gc, stat_from_scan, kernel_impl,
                level_mode, health, self.hist_quant, self.comm_overlap)
        dkey = ("driver_sharded", S, k, gc, objective.static_fingerprint(),
                bag_spec, kernel_impl, level_mode, health,
                self.hist_quant, self.comm_overlap)
        wrapper, driver = cache.get(gkey), cache.get(dkey)
        if wrapper is not None and driver is not None:
            return assets, wrapper, driver
        # run record: the grower, the two jit(shard_map) wrappers and the
        # driver, under the serial path's name so that one metric reads both
        with telemetry.scope("tree_learner::PersistBuild(trace)",
                             category="setup", always=True):
            if wrapper is None:
                inner = make_persist_grower(
                    assets, self.meta, gc, interpret=interpret, axis_name=AXIS,
                    kernel_impl=kernel_impl, stat_from_scan=stat_from_scan,
                    fix=self.fix, level_mode=level_mode, health=health,
                    quant=self.hist_quant, comm_overlap=self.comm_overlap,
                    # GLOBAL counts live in the leaf state: the total row
                    # count decides whether they need i32, not the shard's
                    large_counts=self.dataset.num_data >= EXACT_F32_ROWS)

                class _ShardedGrower:
                    pass

                wrapper = _ShardedGrower()
                wrapper.inner = inner
                # surface the comm-accounting facts the flush-time wire-byte
                # telemetry reads (treelearner/serial.flush_level_stats);
                # K included — the pending-tree tally multiplies by it
                wrapper.K = inner.K
                wrapper.axis_name = AXIS
                wrapper.quant = inner.quant
                wrapper.voting = inner.voting
                wrapper.comm_overlap = inner.comm_overlap
                wrapper.wire_bytes_model = inner.wire_bytes_model
                wrapper.reduced_feature_frac = inner.reduced_feature_frac
                # and the mechanisms the run record counts trees by
                # (treelearner/serial._count_persist_trees)
                wrapper.block_scan = inner.block_scan
                wrapper.inpass_hist = inner.inpass_hist
                wrapper.wide_payload = inner.wide_payload
                wrapper.large_counts = inner.large_counts
                wrapper.num_shards = S
                wrapper.init_carry = jax.jit(jax.shard_map(
                    inner.init_carry, mesh=mesh,
                    in_specs=(pay_spec, P(AXIS)), out_specs=pay_spec,
                    check_vma=False))
                wrapper.finalize_scores = jax.jit(jax.shard_map(
                    inner.finalize_scores, mesh=mesh,
                    in_specs=(pay_spec,), out_specs=P(AXIS),
                    check_vma=False))
                cache[gkey] = wrapper
            if driver is None:
                bag_fn = (make_bag_transform(bag_spec, assets.geometry,
                                             axis_name=AXIS, num_shards=S)
                          if stat_from_scan else None)
                raw = make_scan_driver(wrapper.inner, gc, k,
                                       objective.payload_grad_fn(),
                                       wrap_jit=False, bag_fn=bag_fn)
                smapped = jax.shard_map(
                    raw, mesh=mesh,
                    in_specs=(pay_spec, P(), P(), P(), P(), P(), P()),
                    out_specs=(pay_spec,
                               _tree_arrays_spec(gc, row_sharded=False),
                               P()),
                    check_vma=False)
                driver = telemetry.launch_wrapper(
                    jax.jit(smapped, donate_argnums=(0,)),
                    "collective::persist_scan(launch)", category="collective",
                    always=True, shards=S, mode=gc.parallel_mode, k=k)
                cache[dkey] = driver
        return assets, wrapper, driver


def _tree_arrays_spec(gc: GrowConfig, row_sharded: bool = True):
    """A TreeArrays-shaped pytree of PartitionSpecs (replicated except
    row_leaf, which is row-sharded when the data is)."""
    from ..ops.grow import TreeArrays
    none = P()
    return TreeArrays(
        num_leaves=none, split_leaf=none, split_feature=none, threshold=none,
        default_left=none, gain=none, is_cat=none, cat_mask=none,
        internal_value=none, internal_count=none, leaf_value=none,
        leaf_count=none, leaf_weight=none,
        row_leaf=P(AXIS) if row_sharded else none)


class VotingParallelTreeLearner(DataParallelTreeLearner):
    """PV-tree voting-parallel learner: the data-parallel sharding with the
    histogram reduction compressed to the globally voted top-2k features
    (voting_parallel_tree_learner.cpp). Trees match data-parallel exactly
    whenever 2 * top_k covers every feature; with fewer votes the split
    search is the PV-tree approximation, as in the reference."""

    def __init__(self, config, dataset, mesh: Mesh = None):
        super().__init__(config, dataset, mesh=mesh)
        # the fast path: voting runs on the sharded PERSIST driver (local
        # histogram planes + in-eval vote, ops/grow_persist), which needs
        # scan_impl to stay as resolved. The V1 fused pair scan's PV-tree
        # path is still opt-in only (its vote ordering does not reproduce
        # the XLA voting eval split-for-split), so v1 builds downgrade to
        # the XLA scan in _build unless the user forces pallas.
        self._forced_pallas = (str(config.tpu_scan_impl).lower()
                               == "pallas")
        if self._forced_pallas and np.any(dataset.needs_fix):
            Log.warning("tpu_scan_impl=pallas: the fused voting scan does "
                        "not implement the EFB histogram fix-up; using the "
                        "XLA voting eval for this bundled dataset")
        self.grow_config = self.grow_config._replace(
            parallel_mode="voting", top_k=int(config.top_k))
        self._sharded_grow = None

    def _build(self):
        gc = self.grow_config
        if gc.scan_impl == "pallas" and (not self._forced_pallas
                                         or np.any(self.dataset.needs_fix)):
            saved = gc
            self.grow_config = gc._replace(scan_impl="xla")
            try:
                return super()._build()
            finally:
                self.grow_config = saved
        return super()._build()


class FeatureParallelTreeLearner(SerialTreeLearner):
    """Feature-parallel learner: every shard holds ALL rows (like the
    reference, feature_parallel_tree_learner.cpp:33-77 — no data movement),
    scans only its round-robin-owned features, and the shards agree on the
    global best split via all_gather + the SplitInfo merge order
    (SyncUpGlobalBestSplit). The reference balances feature ownership by
    bin count; round-robin is within a few percent for typical widths."""

    def __init__(self, config, dataset, mesh: Mesh = None):
        super().__init__(config, dataset)
        self.mesh = mesh if mesh is not None else _make_mesh(
            int(config.tpu_num_devices))
        self.num_shards = self.mesh.devices.size
        self._axis_name = AXIS
        # the fused pair scan folds per-shard feature ownership into its
        # layout masks and merges winners via SyncUpGlobalBestSplit
        self.grow_config = self.grow_config._replace(parallel_mode="feature")
        self._sharded_grow = None

    def _build(self):
        mesh = self.mesh
        gc = self.grow_config
        meta, params, fix = self.meta, self.params, self.fix
        layout_rest = tuple(self.layout)[1:]   # all fields after bins
        #              (incl. the 4-bit unpack maps when packing is on)
        cat = self.cat_layout
        # ELL always takes the masked grower (no partitioned variant)
        use_part = (self.dataset.num_data >= PARTITION_MIN_ROWS
                    and not gc.multival)
        gw_global = self.gw_global

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P(), P(), P(), P(), P(), P()),
            out_specs=(_tree_arrays_spec(gc, row_sharded=False), P()),
            check_vma=False)
        def run(bins, grad, hess, bag, fmask, extras):
            layout = DataLayout(bins, *layout_rest)
            if use_part:
                return grow_tree_partitioned(
                    layout, grad, hess, bag, meta, params, fmask, fix, gc,
                    gw_global=gw_global, axis_name=AXIS, cat=cat,
                    extras=extras)
            return grow_tree(layout, grad, hess, bag, meta, params, fmask,
                             fix, gc, axis_name=AXIS, cat=cat, extras=extras)
        return run

    def train_arrays(self, grad, hess, bag_mask):
        telemetry.count("tree_learner::v1_grow_trees",
                        category="tree_learner")
        if self._sharded_grow is None:
            self._sharded_grow = self._build()
        fmask = jnp.asarray(self.col_sampler.sample())
        with telemetry.scope("collective::sharded_grow(launch)",
                             category="collective",
                             shards=self.num_shards, mode="feature"):
            arrays, fu = self._sharded_grow(self.layout.bins, grad, hess,
                                            bag_mask, fmask,
                                            self._next_extras())
        self._feature_used_dev = fu
        return arrays

    def train(self, grad, hess, bag_mask):
        arrays = self.train_arrays(grad, hess, bag_mask)
        host = jax.device_get(
            arrays._replace(row_leaf=jnp.zeros((0,), jnp.int32)))
        tree = Tree.from_grower(host, self.dataset)
        return tree, arrays.row_leaf


def create_parallel_learner(learner_type: str, config, dataset):
    if list(config.cegb_penalty_feature_lazy):
        # the [N, F] acquisition bitset lives in the masked grower's
        # full-N row space; sharded rows would need a gathered bitset
        Log.fatal("cegb_penalty_feature_lazy requires tree_learner=serial")
    if learner_type == "data":
        return DataParallelTreeLearner(config, dataset)
    if learner_type == "voting":
        return VotingParallelTreeLearner(config, dataset)
    if learner_type == "feature":
        return FeatureParallelTreeLearner(config, dataset)
    Log.fatal("Unknown tree learner type %s" % learner_type)
