"""Multi-host distributed training: the end-to-end path behind
`num_machines > 1` (reference Application::Train with a socket/MPI Network,
src/application/application.cpp:164-210 + src/network/).

Flow per process (one per machine, mirroring the reference's rank flow):

  1. init_network(config)            Network::Init (jax.distributed)
  2. shard rows                      dataset_loader.cpp:714-760 — without
                                     pre_partition, row i belongs to rank
                                     (i % num_machines)
  3. distributed_bin_mappers         ConstructBinMappersFromTextData
                                     (dataset_loader.cpp:824-975): per-rank
                                     feature slices + allgather
  4. local BinnedDataset             from_matrix_with_mappers (EFB off so
                                     every rank derives an identical layout)
  5. sharded boosting                K-iteration fused lax.scan under
                                     shard_map over a GLOBAL mesh spanning
                                     every process's devices; histograms
                                     psum over ICI/DCN
                                     (data_parallel_tree_learner.cpp:163),
                                     ONE host transfer of K stacked trees
                                     per batch instead of a per-tree
                                     device_get

Scores, gradients and row ids stay row-sharded on the devices that own the
rows — only histograms, split candidates and the finished split records
cross hosts, exactly the reference's communication pattern. Every process
materializes the identical model (deterministic merge), so rank 0 saving
the model matches the reference CLI behavior.

Objective dispatch is generic: the local objective's grad_fn consumes its
own _grad_args(), each row-aligned device argument sharded over the mesh
(weights included). Bagging draws per-row bernoulli masks from a stateless
hash of the GLOBAL row id at the bagging window key (the same draw the
persist fast path uses), so every rank agrees on the bag without
communication. Validation shards evaluate locally and the metric
aggregates as a count-weighted mean across ranks (the reference's
pre-partitioned parallel eval, SURVEY §2.6), driving reference-semantics
early stopping identically on every rank.

Multiclass (K trees per iteration) computes ONE [K, N] softmax gradient
pass per iteration and grows the K class trees inside the same scan.
Ranking (lambdarank) shards WHOLE queries: ranks receive query-aligned
contiguous row blocks (shard_queries) and each local device gets its own
padded whole-query block, so per-query lambdas never cross a shard
(rank_objective.hpp:139's locality). rank_xendcg is the one loud failure
left — its per-iteration host LCG draws cannot ride the fused batch.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import Config
from ..models.tree import Tree
from ..resilience import faults as resilience_faults
from ..resilience import retry as resilience_retry
from ..telemetry import events as telemetry
from ..utils.log import Log
from .distributed import (distributed_bin_mappers, init_network,
                          resolve_hist_quant)
from .learners import AXIS, _tree_arrays_spec

__all__ = ["init_network", "shard_rows", "train_multihost"]


def _pallgather(name: str, arr: np.ndarray) -> np.ndarray:
    """process_allgather under the resilience retry guard: DCN-side host
    collectives get a deadline + bounded retries instead of hanging
    forever on a gone peer (resilience/retry.py). Single-process runs
    (the world=1 end of an elastic resume) short-circuit to the stacked
    local value — there is no peer to gather from and no distributed
    runtime to ask."""
    if jax.process_count() == 1:
        return np.asarray(arr)[None, ...]
    from jax.experimental import multihost_utils
    return resilience_retry.guard(name, multihost_utils.process_allgather,
                                  arr)


def shard_rows(n_rows: int, rank: int, world: int,
               pre_partition: bool) -> np.ndarray:
    """Row indices owned by `rank` (dataset_loader.cpp:714-760): with
    pre_partition the caller's file already holds only its shard; without,
    rows are dealt round-robin by index."""
    if pre_partition or world <= 1:
        return np.arange(n_rows)
    return np.arange(rank, n_rows, world)


def _balanced_query_cuts(sizes: np.ndarray, parts: int):
    """parts+1 monotone query indices splitting contiguous queries into
    `parts` groups with near-equal ROW counts (queries never split)."""
    sizes = np.asarray(sizes, np.int64)
    ends = np.cumsum(sizes)
    total = int(ends[-1]) if len(ends) else 0
    cuts = [0]
    for r in range(1, parts):
        q = int(np.searchsorted(ends, total * r // parts))
        cuts.append(max(cuts[-1], min(q, len(sizes))))
    cuts.append(len(sizes))
    return cuts


def shard_queries(group_sizes, rank: int, world: int):
    """(row_indices, local_query_sizes) for `rank`: contiguous whole-query
    assignment balanced by rows — ranking's pre-partitioned sharding (the
    reference requires query-aligned partitions for distributed ranking,
    docs/Parallel-Learning-Guide + rank_objective.hpp's per-query
    gradient locality)."""
    sizes = np.asarray(group_sizes, np.int64)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    cuts = _balanced_query_cuts(sizes, world)
    q0, q1 = cuts[rank], cuts[rank + 1]
    return (np.arange(int(bounds[q0]), int(bounds[q1])),
            sizes[q0:q1].copy())


def _np_grad_args(obj):
    """An objective's device gradient args materialized as host numpy.

    Setup-time shaping of host-resident metadata — the objective's
    ``_grad_args`` returns host arrays, so this never syncs a device
    (the reason it may run inside the per-device setup loop)."""
    return [None if a is None else np.asarray(a) for a in obj._grad_args()]


def _lambdarank_block_gargs(config: Config, label_local, weight_local,
                            qb, dev_cuts, B, NQB, Pmax):
    """Per-local-device lambdarank gradient inputs, padded to the global
    block geometry and stacked on axis 0 so shard_map hands each device
    its own whole-query block. Returns (arrays, in_specs) matching the
    lambdarank _grad_args contract: (label, weight, qidx, qvalid,
    inverse_max_dcgs, label_gain, discounts, inv_pos)."""
    from ..metrics.dcg import _DISCOUNT_CACHE
    from ..objectives import create_objective
    local_dev = len(dev_cuts) - 1
    lab_b, w_b, qidx_b, qval_b, inv_b, ipos_b = [], [], [], [], [], []
    label_gain = None
    # hoisted conversions: one asarray per input, sliced per device below
    label_all = np.asarray(label_local, np.float64)
    weight_all = (np.asarray(weight_local, np.float64)
                  if weight_local is not None else None)
    qb_all = np.asarray(qb, np.int64)
    for d in range(local_dev):
        qd0, qd1 = dev_cuts[d], dev_cuts[d + 1]
        r0, r1 = int(qb_all[qd0]), int(qb_all[qd1])
        nq_d, n_d = qd1 - qd0, r1 - r0

        class _BMeta:
            label = label_all[r0:r1]
            weight = (weight_all[r0:r1] if weight_all is not None
                      else None)
            query_boundaries = qb_all[qd0:qd1 + 1] - r0
            num_queries = nq_d
            init_score = None
        obj_d = create_objective(config.objective, config)
        obj_d.init(_BMeta(), n_d)
        (lab, w, qidx, qval, inv, lgain, _disc, _ipos) = \
            _np_grad_args(obj_d)
        label_gain = lgain
        P_d = qidx.shape[1] if nq_d else 0
        qidx_p = np.full((NQB, Pmax), -1, np.int64)
        qval_p = np.zeros((NQB, Pmax), bool)
        if nq_d:
            qidx_p[:nq_d, :P_d] = qidx
            qval_p[:nq_d, :P_d] = qval
        inv_p = np.zeros(NQB, np.float64)
        inv_p[:nq_d] = inv
        # row -> flat padded (query, position) slot; pad rows point at 0
        # (their gradients are discarded by the in-bag mask anyway)
        ipos = np.zeros(B, np.int64)
        qq, pp = np.nonzero(qidx_p >= 0)
        ipos[qidx_p[qq, pp]] = qq * Pmax + pp
        lab_b.append(np.pad(_BMeta.label, (0, B - n_d)))
        if _BMeta.weight is not None:
            w_b.append(np.pad(_BMeta.weight, (0, B - n_d)))
        qidx_b.append(qidx_p)
        qval_b.append(qval_p)
        inv_b.append(inv_p)
        ipos_b.append(ipos)
    arrays = (
        np.concatenate(lab_b),                                # label [D*B]
        (np.concatenate(w_b) if w_b else None),               # weight
        np.concatenate(qidx_b),                               # [D*NQB, Pmax]
        np.concatenate(qval_b),
        np.concatenate(inv_b),                                # [D*NQB]
        np.asarray(label_gain),                               # replicated
        np.asarray(_DISCOUNT_CACHE[:max(Pmax, 1)]),           # replicated
        np.concatenate(ipos_b),                               # [D*B]
    )
    specs = (P(AXIS), P(AXIS) if arrays[1] is not None else P(),
             P(AXIS, None), P(AXIS, None), P(AXIS), P(), P(), P(AXIS))
    return arrays, specs


def _global_mesh() -> Mesh:
    return Mesh(np.array(jax.devices()), (AXIS,))


def _global_array(mesh: Mesh, local_np: np.ndarray):
    """Process-local shard -> global row-sharded jax.Array."""
    sharding = NamedSharding(mesh, P(AXIS) if local_np.ndim == 1
                             else P(AXIS, None))
    return jax.make_array_from_process_local_data(sharding, local_np)


@telemetry.timed("collective::AllreduceMean(metrics,DCN)",
                 category="collective")
def _allreduce_mean_host(values, weights, extra=None):
    """Count-weighted mean across processes via host allgather (used for
    metric aggregation over unequal validation shards; zero-weight ranks
    contribute nothing but still participate in the collective).
    Returns plain Python floats so per-batch callers need no further
    host conversion (the JG002 hot-loop contract).

    ``extra`` (a flat float64 vector) PIGGYBACKS on the values gather:
    the per-batch divergence fingerprints (parallel/fingerprint.py) ride
    the same retry-guarded collective site instead of adding a new one
    (the ``collective_trace`` pin holds). With extra, returns
    ``(means, gathered_extra [world, len(extra)])``; with only extra
    (no metric values — a metric-less training loop still exchanges
    fingerprints), the weights gather is skipped on every rank alike."""
    nv = len(values)
    row = np.asarray(list(values) + list(extra if extra is not None
                                         else ()), np.float64)
    v = _pallgather(
        "allreduce:metrics_values",
        row.reshape(1, -1)).reshape(jax.process_count(), -1)
    gathered_extra = v[:, nv:]
    v = v[:, :nv]
    if nv:
        w = _pallgather(
            "allreduce:metrics_weights",
            np.asarray(weights, np.float64).reshape(1, -1)).reshape(
            jax.process_count(), -1)
        tot = np.sum(w, axis=0)
        out = [float(x) for x in
               np.sum(v * w, axis=0) / np.where(tot > 0, tot, 1.0)]
    else:
        out = []
    if extra is None:
        return out
    return out, gathered_extra


def _local_metric_value(metric, vscore, objective, n_valid):
    """(value, weight) of this rank's validation shard as host floats.

    Rank metrics average per QUERY, so the aggregation weight is the
    query count there; ``metric.eval`` returns numpy scalars — no
    device sync happens here, which is what lets the per-batch metric
    block call this helper from the training loop."""
    nv = int(n_valid)
    if nv and getattr(metric, "query_boundaries", None) is not None:
        nv = max(len(metric.query_boundaries) - 1, 0)
    val = (float(metric.eval(vscore.reshape(-1), objective)[0])
           if nv else 0.0)
    return val, float(nv)


class _EarlyStop:
    """Reference early-stopping semantics (GBDT::EvalAndCheckEarlyStopping,
    gbdt.cpp:440-543): stop when the first metric fails to improve for
    early_stopping_round consecutive evaluations."""

    def __init__(self, rounds: int, higher_better: bool,
                 start_iteration: int = 0):
        self.rounds = rounds
        self.higher = higher_better
        self.best = -np.inf if higher_better else np.inf
        self.best_iter = start_iteration

    def update(self, value: float, it: int) -> bool:
        """Patience counts ITERATIONS (not evaluations): evaluations here
        happen once per k-iteration batch."""
        improved = (value > self.best) if self.higher else (value < self.best)
        if improved:
            self.best, self.best_iter = value, it
            return False
        return self.rounds > 0 and it - self.best_iter >= self.rounds


def train_multihost(config: Config, X_local: np.ndarray,
                    y_local: np.ndarray, num_rounds: int,
                    categorical_features=(), process_id: Optional[int] = None,
                    sample_override: Optional[np.ndarray] = None,
                    weight_local: Optional[np.ndarray] = None,
                    X_valid: Optional[np.ndarray] = None,
                    y_valid: Optional[np.ndarray] = None,
                    group_local: Optional[np.ndarray] = None,
                    group_valid: Optional[np.ndarray] = None,
                    init_score_local: Optional[np.ndarray] = None,
                    init_score_valid: Optional[np.ndarray] = None,
                    start_iteration: int = 0,
                    snapshot_hook=None,
                    es_resume=None, result_info=None,
                    mappers_override=None):
    """Distributed training entry; returns the (identical-on-every-rank)
    list of host Trees plus the shared BinMappers for model IO.

    start_iteration: checkpoint resume offset — the bagging/GOSS hash
    windows, tree key stream, and early-stopping patience all run at
    ABSOLUTE iteration indices so a resumed run draws the identical
    randomness the uninterrupted run would have (`num_rounds` counts the
    NEW rounds to train). snapshot_hook(it_done, trees, ds) fires at
    every snapshot_freq boundary (engine._train_distributed writes the
    per-rank model checkpoint there).

    X_valid/y_valid: this rank's shard of a validation set; with
    valid data and early_stopping_round > 0 the loop stops when the
    aggregated first metric stalls.

    group_local: this rank's query sizes (ranking). Rows must arrive
    query-contiguous (shard_queries does this); internally each local
    DEVICE receives whole queries — rows re-block with padding so the
    per-query lambda computation stays device-local
    (GetGradientsForOneQuery, rank_objective.hpp:139 — the reference's
    pre-partitioned ranking contract).

    es_resume: {"best": float, "best_iter": int} from a resumed
    checkpoint — the early-stopping patience clock and rollback point
    survive the resume. result_info (a caller-supplied dict) reports
    "early_stop_best_iter"/"trees_per_iteration" when a resumed run's
    rollback may land inside the restored model, so the caller truncates
    the COMBINED tree list (offsetting any original init model itself).
    """
    from ..data.dataset import BinnedDataset
    from ..objectives import create_objective
    from ..ops.grow_persist import _hash_uniform
    from ..treelearner.serial import PARTITION_MIN_ROWS

    rank = init_network(config, process_id)
    world = max(int(config.num_machines), 1)

    # ---- distributed binning -----------------------------------------
    if mappers_override is not None:
        # elastic resume: binning restored from the mesh manifest — the
        # source run's bin boundaries, NOT boundaries re-derived from
        # this (differently-sharded) mesh's local samples, keep the
        # resumed model bit-exact (resilience/reshard.py)
        mappers = list(mappers_override)
    else:
        cnt = int(config.bin_construct_sample_cnt)
        if sample_override is not None:
            sample = sample_override
        else:
            # random sample over the local rows (dataset_loader.cpp:
            # 762-823 samples across the whole shard); taking the file
            # head instead biases the bin boundaries on ordered
            # (time/label-sorted) data
            rng = np.random.default_rng(int(config.data_random_seed))
            k = min(len(X_local), cnt)
            if k < len(X_local):
                idx = np.sort(rng.choice(len(X_local), size=k,
                                         replace=False))
                sample = X_local[idx]
            else:
                sample = X_local
        mappers = distributed_bin_mappers(
            np.ascontiguousarray(sample, np.float64), len(X_local), config,
            categorical_features=categorical_features,
            rank=rank, world=world)
    ds = BinnedDataset.from_matrix_with_mappers(
        X_local, config, mappers, label=y_local, weight=weight_local)
    if group_local is not None:
        ds.metadata.set_query(np.asarray(group_local, np.int64))

    objective = create_objective(config.objective, config)
    if objective is None:
        Log.fatal("num_machines > 1 needs a built-in objective")
    objective.init(ds.metadata, ds.num_data)
    # K trees per iteration (multiclass): gradients are a [K, N] matrix
    # row-shardable along N; each iteration grows K class trees from the
    # iteration-start scores (GBDT::TrainOneIter computes gradients once,
    # then trains per class — gbdt.cpp:372-411)
    K = int(getattr(objective, "num_model_per_iteration", 1))
    if list(config.cegb_penalty_feature_lazy):
        Log.fatal("cegb_penalty_feature_lazy is not supported with "
                  "num_machines > 1 (per-row bitset needs unsharded rows)")

    is_ranking = ds.metadata.query_boundaries is not None
    if is_ranking and str(config.objective) != "lambdarank":
        Log.fatal("among ranking objectives only lambdarank supports "
                  "num_machines > 1 (rank_xendcg draws per-iteration "
                  "host randomness)")

    boosting = str(config.boosting).lower()
    if boosting in ("dart", "rf", "random_forest"):
        Log.fatal("boosting=%s is not supported with num_machines > 1 yet "
                  "(per-iteration tree mutation/averaging needs the "
                  "single-process driver)" % boosting)
    use_goss = boosting == "goss"
    if use_goss and K > 1:
        Log.fatal("boosting=goss with num_class > 1 is not supported with "
                  "num_machines > 1")

    # ---- global mesh + row-sharded device state ----------------------
    from ..treelearner.serial import SerialTreeLearner
    mesh = _global_mesh()
    S = mesh.devices.size
    learner = SerialTreeLearner(config, ds)
    if int(start_iteration) > 0:
        # resume: the per-tree key stream folds the tree counter into the
        # base key; continue it where the snapshotted run left off. The
        # feature-fraction RNG is sequential (one sample() per tree when
        # fraction < 1) — fast-forward it to the resume point so resumed
        # column masks match the uninterrupted run's
        learner._tree_counter = int(start_iteration)
        if learner.col_sampler.fraction < 1.0:
            for _ in range(int(start_iteration) * K):
                learner.col_sampler.sample()
    n_local = ds.num_data
    counts = _pallgather("allgather:row_counts",
                         np.asarray([n_local], np.int64)).reshape(-1)
    local_dev = S // jax.process_count()
    # GLOBAL row ids drive the bagging hash — every rank draws the same
    # per-row bernoulli without communication (gbdt.cpp:210-244 semantics).
    # Ranking shards whole queries as CONTIGUOUS blocks (shard_queries),
    # so its global ids are the rank's row range; round-robin ids would
    # misalign under the uneven row counts query alignment produces.
    if is_ranking:
        off = int(counts[:rank].sum())
        gidx_l = np.arange(off, off + n_local)
    else:
        gidx_l = shard_rows(int(counts.sum()), rank, world,
                            bool(config.pre_partition))[:n_local]
    if is_ranking:
        # whole queries per local DEVICE: re-block this rank's rows so the
        # per-query lambda computation never crosses a shard boundary
        qb = np.asarray(ds.metadata.query_boundaries, np.int64)
        dev_cuts = _balanced_query_cuts(np.diff(qb), local_dev)
        blk_rows = [int(qb[dev_cuts[d + 1]] - qb[dev_cuts[d]])
                    for d in range(local_dev)]
        blk_nq = [dev_cuts[d + 1] - dev_cuts[d] for d in range(local_dev)]
        P_l = int(np.diff(qb).max()) if len(qb) > 1 else 1
        geom = _pallgather(
            "allgather:ranking_geometry",
            np.asarray([max(blk_rows), max(blk_nq), P_l],
                       np.int64)).reshape(-1, 3)
        B, NQB, Pmax = (int(geom[:, 0].max()), int(geom[:, 1].max()),
                        int(geom[:, 2].max()))
        pad_to = local_dev * B
        src = np.full((local_dev, B), -1, np.int64)
        for d in range(local_dev):
            src[d, :blk_rows[d]] = np.arange(int(qb[dev_cuts[d]]),
                                             int(qb[dev_cuts[d + 1]]))
        srcf = src.reshape(-1)
        valid_local = srcf >= 0

        def padded(a, fill=0.0):
            a = np.asarray(a)
            out = np.ascontiguousarray(a[np.clip(srcf, 0, None)])
            out[~valid_local] = fill
            return out
    else:
        # equal local shards: every process must contribute the same
        # number of device rows; pad the tail shard
        per_proc = int(counts.max())
        pad_to = ((per_proc + local_dev - 1) // local_dev) * local_dev
        pad = pad_to - n_local
        valid_local = np.pad(np.ones(n_local, bool), (0, pad))

        def padded(a, fill=0.0):
            a = np.asarray(a)
            if not pad:
                return a
            widths = ((0, pad),) + ((0, 0),) * (a.ndim - 1)
            return np.pad(a, widths, constant_values=fill)

    # evaluated AFTER the learner construction: to_device converts
    # tpu_multival=force datasets to the ELL layout in place
    use_mv = bool(getattr(ds, "is_multival", False))
    if use_mv:
        # ELL row-sparse: the placeholder dense matrix plus the row-aligned
        # (group, bin) pair arrays, sharded WITH the rows (pad rows carry
        # the G sentinel group and contribute nothing)
        bins_local = np.zeros((ds.num_data, 1), np.uint8)
        G_mv = len(ds.groups)
        bins_g = _global_array(mesh, padded(bins_local))
        ell_grp_g = _global_array(
            mesh, padded(ds.ell_grp, fill=G_mv).astype(np.int32))
        ell_bin_g = _global_array(mesh, padded(ds.ell_bin).astype(np.int32))
        ell_g = (ell_grp_g, ell_bin_g)
    else:
        bins_g = _global_array(mesh,
                               padded(np.ascontiguousarray(ds.binned)))
        ell_g = ()
    valid_g = _global_array(mesh, valid_local)
    gidx_g = _global_array(mesh, padded(gidx_l.astype(np.uint32)))

    # the objective's device gradient args
    grad_fn = objective.grad_fn()
    if is_ranking:
        gargs_np, garg_specs = _lambdarank_block_gargs(
            config, y_local, weight_local, qb, dev_cuts, B, NQB, Pmax)
        gargs_g = [None if a is None else
                   (_global_array(mesh, a) if sp != P() else jnp.asarray(a))
                   for a, sp in zip(gargs_np, garg_specs)]
    else:
        # row-sharded where row-aligned (args pre-converted to numpy so
        # the transfer loop itself stays sync-free)
        gargs_g = []
        garg_specs = []
        for a in _np_grad_args(objective):
            if a is None:
                gargs_g.append(None)
                garg_specs.append(P())
            elif a.ndim >= 1 and a.shape[0] == n_local:
                gargs_g.append(_global_array(mesh, padded(a)))
                garg_specs.append(P(AXIS))
            else:
                Log.fatal("objective %s has gradient inputs that are not "
                          "row-shardable; not supported with "
                          "num_machines > 1" % config.objective)

    gc = learner.grow_config
    n_shard = pad_to * jax.process_count() // S
    use_part = n_shard >= PARTITION_MIN_ROWS and not use_mv
    # int16-quantized histogram reductions over ICI/DCN (ROADMAP item
    # 2): the runtime spec is certified against the quant_certify budget
    # here, at config-application time — int8 (and any objective
    # without a static gradient cap) is refused with the certificate
    # named. The per-device shard size is rank-uniform (the padded
    # global geometry), so every rank certifies the same spec and
    # derives the same wire scales. Sample-weighted runs are refused:
    # the contract scale would need the GLOBAL weight max, and each
    # rank only sees its shard — a shard-local max would desync the
    # dequantization scales across ranks.
    if weight_local is not None \
            and str(config.tpu_hist_quant).lower() not in ("off", ""):
        Log.fatal("tpu_hist_quant with sample weights needs a rank-"
                  "uniform weight cap, which the distributed driver "
                  "does not exchange yet; drop the weights or "
                  "tpu_hist_quant=off")
    hq = resolve_hist_quant(config, n_shard, S)
    hist_quant, hist_quant_cert = hq if hq else (None, None)
    meta, params, fix = learner.meta, learner.params, learner.fix
    cat = learner.cat_layout
    gw_global = learner.gw_global
    layout_rest = tuple(learner.layout)[1:]
    base_extras = learner._extras_base

    from ..ops.grow import DataLayout, grow_tree, grow_tree_partitioned

    bag_frac = (float(config.bagging_fraction)
                if (config.bagging_freq > 0
                    and config.bagging_fraction < 1.0) else 1.0)
    goss_wfn = None
    if use_goss:
        if bag_frac < 1.0:
            Log.fatal("Cannot use bagging in GOSS")
        from ..ops.grow_persist import make_goss_weight_fn
        # global row count: the earlier per-rank counts allgather holds it
        goss_wfn = make_goss_weight_fn(
            int(counts.sum()), float(config.top_rate),
            float(config.other_rate),
            int(1.0 / float(config.learning_rate)), AXIS)

    def _grow(bins, grad, hess, bag, fmask, extras, ell=()):
        layout = DataLayout(bins, *layout_rest)
        if use_mv:
            layout = layout._replace(ell_grp=ell[0], ell_bin=ell[1])
        if use_part:
            return grow_tree_partitioned(
                layout, grad, hess, bag, meta, params, fmask, fix, gc,
                gw_global=gw_global, axis_name=AXIS, cat=cat,
                extras=extras, quant=hist_quant)
        return grow_tree(layout, grad, hess, bag, meta, params, fmask,
                         fix, gc, axis_name=AXIS, cat=cat, extras=extras,
                         quant=hist_quant)

    def _batch(k: int):
        """jitted K-iteration boosting scan under shard_map: gradients ->
        bag mask -> sharded grow (psum inside) -> on-device score update;
        K stacked tree records come back replicated, ONE transfer."""

        def body_fn(bins, gidx, valid, gargs, score0, fu0, fmasks, wkeys,
                    keys, its, *ell):
            def body(carry, per):
                score, fu = carry
                fmask, wkey, key, it_i = per
                if bag_frac < 1.0:
                    u = _hash_uniform(gidx, wkey)
                    bag = valid & (u < jnp.float32(bag_frac))
                else:
                    bag = valid
                m = bag.astype(jnp.float32)
                shrink_t = jnp.float64(config.learning_rate)
                if K == 1:
                    g, h = grad_fn(score, *gargs)
                    g = g.astype(jnp.float32) * m
                    h = h.astype(jnp.float32) * m
                    if use_goss:
                        # the shared GOSS weighting (grow_persist.
                        # make_goss_weight_fn): GLOBAL top-rate threshold
                        # via radix select on psum'd counts; keep/amplify
                        # draws hash global row ids at per-ITERATION keys
                        # (the serial persist driver redraws each
                        # iteration too — windows = iters for goss)
                        s = jnp.where(valid, jnp.abs(g * h), 0.0)
                        u = _hash_uniform(gidx, wkey)
                        w = goss_wfn(s, valid, u, it_i)
                        g = g * w
                        h = h * w
                        bag = w > 0
                    ex = base_extras._replace(key=key, feature_used=fu)
                    arrays, fu2 = _grow(bins, g, h, bag, fmask, ex, ell)
                    upd = arrays.leaf_value.astype(jnp.float64)[
                        arrays.row_leaf] * shrink_t
                    score2 = score + jnp.where(arrays.num_leaves > 1,
                                               upd, 0.0)
                    out = arrays._replace(
                        row_leaf=jnp.zeros((0,), jnp.int32))
                    return (score2, fu2), out
                # multiclass: one [K, N] gradient pass at the iteration
                # start, then K class trees (static unroll)
                G, H = grad_fn(score, *gargs)
                outs = []
                score2 = score
                fu2 = fu
                for c in range(K):
                    g = G[c].astype(jnp.float32) * m
                    h = H[c].astype(jnp.float32) * m
                    ex = base_extras._replace(
                        key=jax.random.key_data(jax.random.fold_in(
                            jax.random.wrap_key_data(key), c)),
                        feature_used=fu2)
                    arrays, fu2 = _grow(bins, g, h, bag, fmask[c], ex, ell)
                    upd = arrays.leaf_value.astype(jnp.float64)[
                        arrays.row_leaf] * shrink_t
                    score2 = score2.at[c].add(
                        jnp.where(arrays.num_leaves > 1, upd, 0.0))
                    outs.append(arrays._replace(
                        row_leaf=jnp.zeros((0,), jnp.int32)))
                stacked_c = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
                return (score2, fu2), stacked_c

            (scoreK, fuK), stacked = jax.lax.scan(
                body, (score0, fu0), (fmasks, wkeys, keys, its), length=k)
            return scoreK, fuK, stacked

        spec_gargs = tuple(garg_specs)
        score_spec = P(AXIS) if K == 1 else P(None, AXIS)
        return jax.jit(jax.shard_map(
            body_fn, mesh=mesh,
            in_specs=(P(AXIS, None), P(AXIS), P(AXIS), spec_gargs,
                      score_spec, P(), P(), P(), P(), P())
            + ((P(AXIS, None), P(AXIS, None)) if use_mv else ()),
            out_specs=(score_spec, P(), _tree_arrays_spec(gc,
                                                          row_sharded=False)),
            check_vma=False))

    # ---- init score (BoostFromAverage; GlobalSyncUpByMean) -----------
    # continued training (init_model graft): per-row raw scores from the
    # init model replace boost-from-average entirely, matching the
    # single-host _graft_init_model contract (has_init_score suppresses
    # the average seed)
    if init_score_local is not None:
        init0s = [0.0] * K
    else:
        init0s = [(objective.boost_from_score(c)
                   if config.boost_from_average else 0.0) for c in range(K)]
    if world > 1:
        # Network::GlobalSyncUpByMean (gbdt.cpp:308): UNWEIGHTED mean over
        # machines — reference parity on unequal shards
        with telemetry.scope("collective::GlobalSyncUpByMean(DCN)",
                             category="collective"):
            init0s = [float(v) for v in np.mean(
                _pallgather("allreduce:boost_from_average",
                            np.asarray(init0s,
                                       np.float64)).reshape(world, -1),
                axis=0)]
    init0 = init0s[0]
    n_glob = pad_to * jax.process_count()
    if init_score_local is not None:
        isc = np.asarray(init_score_local, np.float64)
        if K == 1:
            score = _global_array(mesh, padded(isc.reshape(-1)))
        else:
            isc_p = np.stack([padded(isc.reshape(K, -1)[c])
                              for c in range(K)])        # [K, pad_to]
            score = jax.make_array_from_process_local_data(
                NamedSharding(mesh, P(None, AXIS)), isc_p)
    elif K == 1:
        score = jax.device_put(
            jnp.full((n_glob,), float(init0), jnp.float64),
            NamedSharding(mesh, P(AXIS)))
    else:
        score = jax.device_put(
            jnp.broadcast_to(jnp.asarray(init0s, jnp.float64)[:, None],
                             (K, n_glob)),
            NamedSharding(mesh, P(None, AXIS)))

    # ---- validation + metrics ----------------------------------------
    # metrics are constructed whenever valid data was PASSED (even when
    # this rank's shard came up empty): the per-batch metric aggregation
    # is a collective, and every rank must participate — empty shards
    # contribute weight 0
    from ..metrics import create_metric
    metrics = []
    Xv = None
    if X_valid is not None and y_valid is not None:
        names = list(config.metric) or [""]
        m = create_metric(names[0] or str(config.objective), config)
        if m is not None:
            _vqb = (np.concatenate(
                [[0], np.cumsum(np.asarray(group_valid, np.int64))])
                if group_valid is not None else None)

            class _VMeta:
                label = np.asarray(y_valid, np.float64)
                weight = None
                query_boundaries = _vqb
                num_queries = (len(_vqb) - 1 if _vqb is not None else 0)
                query_weights = None
                init_score = None
            m.init(_VMeta(), len(y_valid))
            metrics.append(m)
            Xv = np.ascontiguousarray(X_valid, np.float64)
    es = (_EarlyStop(int(config.early_stopping_round),
                     metrics[0].factor_to_bigger_better > 0,
                     start_iteration=int(start_iteration))
          if metrics and int(config.early_stopping_round) > 0 else None)
    if es is not None and es_resume is not None:
        es.best = float(es_resume["best"])
        es.best_iter = int(es_resume["best_iter"])
    vscore = None
    if metrics:
        if init_score_valid is not None:
            vsc = np.asarray(init_score_valid, np.float64)
            vscore = (vsc.reshape(-1).copy() if K == 1
                      else vsc.reshape(K, -1).copy())
        else:
            vscore = (np.zeros(len(y_valid), np.float64) + init0 if K == 1
                      else np.broadcast_to(
                          np.asarray(init0s)[:, None],
                          (K, len(y_valid))).astype(np.float64).copy())

    # ---- batched boosting loop ---------------------------------------
    from . import fingerprint as divergence
    # per-iteration cross-rank divergence fingerprints: 'auto' arms the
    # probe only when there is a peer to diverge FROM — at
    # jax.process_count() == 1 (including the elastic-resume small end)
    # the compare can never fire, so auto skips the per-batch score-
    # shard D2H and tree CRCs entirely; 'on' forces the full pipeline
    # through the 1-row short-circuit (what the tier-1 tests drive)
    probe_opt = str(getattr(config, "tpu_divergence_probe",
                            "auto")).lower()
    if probe_opt in ("off", "false", "0"):
        probe_on = False
    elif probe_opt in ("on", "force", "1", "true"):
        probe_on = True
    else:
        probe_on = jax.process_count() > 1
    shrink = float(config.learning_rate)
    base_key = jax.random.PRNGKey(int(config.bagging_seed))
    freq = max(int(config.bagging_freq), 1)
    trees: List[Tree] = []
    fu = base_extras.feature_used
    runners = {}
    it = int(start_iteration)
    end_round = it + int(num_rounds)
    fault_plan = resilience_faults.active()
    # batch clamping must be IDENTICAL on every rank (the fused scan is
    # one global-mesh collective program; mismatched k desyncs psum);
    # only the raise itself is rank-filtered
    kill_clamp = (fault_plan.clamp_iter() if fault_plan is not None
                  else None)
    snap_freq = int(config.snapshot_freq)
    stopped = False
    while it < end_round and not stopped:
        if fault_plan is not None:
            fault_plan.check_kill(it, rank)
        k = min(8 if metrics else 16, end_round - it)
        if snapshot_hook is not None and snap_freq > 0:
            # batches end exactly on snapshot boundaries, so the hook
            # always sees iteration-k state (and a resumed run re-aligns
            # to the identical batch shapes)
            k = min(k, snap_freq - (it % snap_freq))
        if kill_clamp is not None and kill_clamp > it:
            # clamp so the injected kill lands on an iteration boundary
            k = min(k, kill_clamp - it)
        if k not in runners:
            runners[k] = _batch(k)
        fmasks = jnp.asarray(
            np.stack([learner.col_sampler.sample()
                      for _ in range(k * K)]))
        if K > 1:
            fmasks = fmasks.reshape(k, K, -1)
        # goss redraws its sample every iteration (windows = iters, as the
        # serial persist driver does); bagging windows follow bagging_freq.
        # One vmapped fold_in builds all k window keys on device — the
        # old per-key key_data round-trip was a device sync per iteration
        wwin = 1 if use_goss else freq
        win_ids = jnp.arange(it, it + k, dtype=jnp.int32) // wwin
        wkeys = jax.vmap(lambda wi: jax.random.key_data(
            jax.random.fold_in(base_key, wi)))(win_ids).astype(jnp.uint32)
        keys = jnp.stack([learner._next_extras().key for _ in range(k)])
        its = jnp.arange(it, it + k, dtype=jnp.int32)
        with telemetry.scope("collective::multihost_scan(launch)",
                             category="collective", k=k):
            score, fu, stacked = runners[k](
                bins_g, gidx_g, valid_g, tuple(gargs_g), score, fu, fmasks,
                wkeys, keys, its, *ell_g)
        with telemetry.scope("boosting::MaterializeBatch(D2H+wait)",
                             category="device_wait"):
            host = jax.device_get(stacked)      # ONE transfer per batch
        batch_trees = []                        # per-ITERATION tree lists
        for i in range(k):
            class_trees = []
            for c in range(K):
                ha = jax.tree.map(
                    (lambda a, i=i: a[i]) if K == 1
                    else (lambda a, i=i, c=c: a[i][c]), host)
                tree = Tree.from_grower(ha, ds)
                if tree.num_leaves > 1:
                    tree.shrink(shrink)
                    if it + i == 0 and abs(init0s[c]) > 1e-15:
                        tree.add_bias(init0s[c])
                elif it + i == 0 and tree.leaf_value[0] == 0.0:
                    # no-split first tree keeps the boost_from_average
                    # constant (gbdt.cpp:396-411)
                    tree.leaf_value[0] = init0s[c]
                class_trees.append(tree)
            if (it + i > 0
                    and all(t.num_leaves <= 1 for t in class_trees)):
                # the model stops only when NO class can split
                # (gbdt.cpp:425-435)
                Log.warning("Stopped training because there are no more "
                            "leaves that meet the split requirements")
                stopped = True
                break
            trees.extend(class_trees)
            batch_trees.append(class_trees)
            if vscore is not None and vscore.size:
                if K == 1:
                    vscore += class_trees[0].predict(Xv)
                else:
                    for c in range(K):
                        vscore[c] += class_trees[c].predict(Xv)
        it += k
        if batch_trees:
            # estimated per-shard histogram-exchange payload of this
            # batch (root + one smaller-child plane pair per split in
            # data-parallel mode) — feeds the --perf sentinel's
            # dcn_hist_bytes / hist_compress_ratio keys; int16 codes
            # under tpu_hist_quant shrink it 2-4x vs the full planes
            n_trees = sum(len(ct) for ct in batch_trees)
            n_splits = sum(t.num_leaves - 1 for ct in batch_trees
                           for t in ct)
            bpe_full = 8 if gc.hist_dtype == "f64" else 4
            bpe = (hist_quant.wire_bytes_per_value
                   if hist_quant is not None else bpe_full)
            # host-int arithmetic over already-materialized trees — no
            # device value is touched here
            units = (n_trees + n_splits) * 2 * int(gc.total_bins)
            telemetry.count("collective::dcn_hist_bytes",
                            units * bpe, category="collective")
            telemetry.count("collective::dcn_hist_bytes_fullwidth",
                            units * bpe_full, category="collective")
        fp_rows = None
        if probe_on and batch_trees and not stopped:
            # ONE deliberate batched D2H of the local score shard (the
            # Kahan-reduced sum is the per-rank diagnostic column; the
            # tree CRCs below are pure host work over already-
            # materialized arrays)
            ssum = divergence.kahan_sum(np.concatenate(
                [np.asarray(s.data).reshape(-1)   # graftlint: disable=JG002
                 for s in score.addressable_shards]))
            fp_rows = divergence.batch_records(
                it - k, batch_trees, rank=rank, score_sum=ssum,
                fault_plan=fault_plan).reshape(-1)
        gathered_fp = None
        if metrics and not stopped:
            local, nv = _local_metric_value(
                metrics[0], vscore, objective,
                len(y_valid) if y_valid is not None else 0)
            if fp_rows is not None:
                # fingerprints piggyback the metric aggregation — the
                # same guarded collective site, one payload
                aggs, gathered_fp = _allreduce_mean_host(
                    [local], [nv], extra=fp_rows)
                agg = aggs[0]
            else:
                agg = _allreduce_mean_host([local], [nv])[0]
        elif fp_rows is not None:
            # metric-less loop: the fingerprint exchange still rides the
            # metrics-values site (empty metric block; rank-uniform
            # branch — every rank takes it or none does)
            gathered_fp = _allreduce_mean_host([], [], extra=fp_rows)[1]
        if gathered_fp is not None:
            # raises DivergenceError at the exact iteration on EVERY
            # rank (identical gathered matrix), each with its own
            # flight dump
            divergence.check_gathered(gathered_fp, rank=rank)
        if metrics and not stopped:
            if rank == 0:
                Log.info("[%d] valid %s : %g"
                         % (it, metrics[0].names[0], agg))
            if es is not None and es.update(agg, it):
                Log.info("Early stopping at iteration %d, best %g at %d"
                         % (it, es.best, es.best_iter))
                # the local tree list starts at start_iteration; truncate
                # relative to it. A RESUMED patience clock may roll back
                # into the restored model itself — report the combined
                # truncation to the caller (which holds the init trees)
                if es_resume is not None:
                    trees = trees[:max(es.best_iter
                                       - int(start_iteration), 0) * K]
                    if result_info is not None:
                        # ROUND-space iterations (excludes any original
                        # init model); the caller adds its init offset
                        result_info["early_stop_best_iter"] = \
                            max(es.best_iter, 1)
                        result_info["trees_per_iteration"] = K
                else:
                    trees = trees[:max(es.best_iter
                                       - int(start_iteration), 1) * K]
                stopped = True
        if (snapshot_hook is not None and snap_freq > 0 and not stopped
                and it % snap_freq == 0):
            # after the metrics/early-stop check: a stopping boundary is
            # never snapshotted past its truncation point; the patience
            # state rides along so a resume keeps the same clock
            # es.best/best_iter are host scalars already (no device sync)
            es_state = ({"best": es.best, "best_iter": es.best_iter}
                        if es is not None else None)
            snapshot_hook(it, trees, ds, es_state)
    return trees, mappers, ds, score
