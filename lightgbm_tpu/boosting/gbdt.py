"""GBDT: the boosting driver.

TPU-native rebuild of src/boosting/gbdt.{h,cpp}. The per-iteration control
flow mirrors GBDT::TrainOneIter (gbdt.cpp:338-420): BoostFromAverage (:302) ->
objective gradients (Boosting, :152) -> Bagging (:210) -> per-class tree
growth -> leaf renewal (serial_tree_learner.cpp:628-666) -> shrinkage ->
score update (:459). The heavy steps (gradients, tree growth, train-score
update) are jitted device programs; the scalar orchestration stays host-side
Python, like the reference's C++ driver around OpenMP/GPU kernels.

Model text IO follows gbdt_model_text.cpp (SaveModelToString :301,
LoadModelFromString :385) so models interoperate with LightGBM tooling.
"""
from __future__ import annotations

import json
from typing import Dict, List, Optional

import numpy as np

import jax.numpy as jnp

from ..config import Config
from ..models.tree import Tree, kCategoricalMask
from ..objectives import parse_objective_string
from ..telemetry import events as telemetry
from ..treelearner import create_tree_learner
from ..utils.log import Log
from .score_updater import HostScoreUpdater, ScoreUpdater

K_EPSILON = 1e-15
K_MODEL_VERSION = "v3"


class GBDT:
    """Gradient Boosting Decision Tree driver (gbdt.h)."""

    sub_model_name = "tree"
    average_output = False

    def __init__(self):
        self.config: Optional[Config] = None
        self.train_data = None
        self.objective = None
        self.models: List[Tree] = []
        self.iter = 0
        self.num_init_iteration = 0
        self.num_class = 1
        self.num_tree_per_iteration = 1
        self.shrinkage_rate = 0.1
        self.max_feature_idx = 0
        self.label_idx = 0
        self.feature_names: List[str] = []
        self.feature_infos: List[str] = []
        self.monotone_constraints: List[int] = []
        self.loaded_parameter = ""
        self.train_score: Optional[ScoreUpdater] = None
        self.valid_score: List[HostScoreUpdater] = []
        self.valid_metrics: List[List] = []
        self.valid_names: List[str] = []
        self.training_metrics: List = []
        self.best_iter_by_metric: Dict[str, int] = {}
        self.best_score_by_metric: Dict[str, float] = {}
        self.evals_output: List[tuple] = []   # (iter, dataset, name, value)
        self._pending: List[tuple] = []       # async fast-path device trees
        # (start_pos, stacked, shrink, init0s, mode) — mode 'gbdt'|'rf'
        self._pending_batches: List[tuple] = []
        # run-record tags (telemetry.run_tags) of the newest fused launch,
        # and by start_pos of the launch that grew each pending batch, so
        # that a late materialize is put down to that launch
        self._run_tags: dict = {}
        self._batch_run_tags: Dict[int, dict] = {}
        # engine sets allow_batch when no before-iteration callbacks/evals
        # exist; then K iterations fuse into one jitted lax.scan dispatch
        self.allow_batch = False
        self.planned_rounds = 0
        self._rounds_done = 0
        self._batch_credit = 0
        # resilience: >0 caps fused batches so they never cross a
        # snapshot boundary (the checkpoint writer needs the exact
        # iteration-k state; a 16-iteration scan would overshoot it)
        self.snapshot_stride = 0
        # compiled device predictors keyed by (start, num, model length);
        # stale keys age out when the model grows (see device_predictor)
        self._tpu_predictors: Dict[tuple, object] = {}

    # ------------------------------------------------------------------
    # run record: the learner (the per-feature tables onto the device,
    # under a span of its own), the score updater and the bag state
    @telemetry.timed("boosting::Init", category="setup", always=True)
    def init(self, config: Config, train_data, objective,
             training_metrics=()) -> None:
        telemetry.configure_from_config(config)
        if float(config.histogram_pool_size) > 0:
            Log.warning("histogram_pool_size is ignored on device_type=tpu: "
                        "all per-leaf histograms stay HBM-resident "
                        "([num_leaves, total_bins, 2] tensor)")
        self.config = config
        self.train_data = train_data
        self.objective = objective
        self.training_metrics = list(training_metrics)
        self.iter = 0
        self.num_class = int(config.num_class)
        self.shrinkage_rate = float(config.learning_rate)
        self.num_tree_per_iteration = (
            objective.num_model_per_iteration if objective is not None
            else self.num_class)
        self.tree_learner = create_tree_learner(
            config.tree_learner, config.device_type, config, train_data)
        n = train_data.num_data
        self.num_data = n
        self.max_feature_idx = train_data.num_total_features - 1
        self.feature_names = list(train_data.feature_names)
        self.feature_infos = [self._feature_info(m)
                              for m in train_data.bin_mappers]
        self.monotone_constraints = list(config.monotone_constraints)
        init_score = (train_data.metadata.init_score
                      if train_data.metadata else None)
        self.train_score = ScoreUpdater(n, self.num_tree_per_iteration,
                                        init_score)
        self.class_need_train = [True] * self.num_tree_per_iteration
        if objective is not None:
            self.class_need_train = [
                objective.class_need_train(k)
                for k in range(self.num_tree_per_iteration)]
        # bagging state; the plan itself is derived in
        # _refresh_bagging_config (the ResetBaggingConfig analog shared
        # with reset_config)
        self._bag_mask_dev = jnp.ones(n, dtype=bool)
        self._bag_weight_dev = None   # GOSS amplification weights
        self._refresh_bagging_config()
        self._grad_rows = None
        self._pending = []

    @staticmethod
    def _feature_info(mapper) -> str:
        """Dataset::get feature_infos: [min:max] or category list."""
        if mapper.is_trivial:
            return "none"
        if mapper.is_categorical:
            return ":".join(str(c) for c in sorted(
                c for c in mapper.bin_2_categorical if c >= 0))
        return "[%s:%s]" % (repr(float(mapper.min_val)),
                            repr(float(mapper.max_val)))

    # ------------------------------------------------------------------
    def add_valid_dataset(self, valid_data, valid_metrics, name="valid") -> None:
        self._materialize_pending()
        self.valid_score.append(
            HostScoreUpdater(valid_data, self.num_tree_per_iteration))
        ms = []
        for m in valid_metrics:
            m.init(valid_data.metadata, valid_data.num_data)
            ms.append(m)
        self.valid_metrics.append(ms)
        self.valid_names.append(name)
        # replay existing model onto the new valid scores
        su = self.valid_score[-1]
        for i, tree in enumerate(self.models):
            su.add_tree(tree, i % self.num_tree_per_iteration)

    # ------------------------------------------------------------------
    def boost_from_average(self, class_id: int, update_scorer: bool) -> float:
        """gbdt.cpp:302-336."""
        cfg = self.config
        if (not self.models and not self.train_score.has_init_score
                and self.objective is not None):
            if cfg.boost_from_average or self.train_data.num_features == 0:
                init_score = self.objective.boost_from_score(class_id)
                if abs(init_score) > K_EPSILON:
                    if update_scorer:
                        self.train_score.add_score_const(init_score, class_id)
                        for su in self.valid_score:
                            su.add_score_const(init_score, class_id)
                    Log.info("Start training from score %f" % init_score)
                    return init_score
            elif self.objective.name in ("regression_l1", "quantile", "mape"):
                Log.warning("Disabling boost_from_average in %s may cause the "
                            "slow convergence" % self.objective.name)
        return 0.0

    @telemetry.timed("boosting::Boosting(gradients)", category="boosting")
    def _compute_gradients(self):
        """Boosting() (gbdt.cpp:152): objective grad/hess from cached score."""
        if self.objective is None:
            Log.fatal("No objective function provided")
        if self.num_tree_per_iteration > 1:
            score = self.train_score.score_matrix()
        else:
            score = self.train_score.score_device(0)
        g, h = self.objective.get_gradients(score)
        if self.num_tree_per_iteration == 1:
            g = g.reshape(1, -1)
            h = h.reshape(1, -1)
        return g, h

    # ------------------------------------------------------------------
    def bagging(self, it: int) -> None:
        """GBDT::Bagging (gbdt.cpp:210-244) as a boolean mask."""
        cfg = self.config
        do_bag = (self.bag_data_cnt < self.num_data or self.balanced_bagging)
        if not ((do_bag and cfg.bagging_freq > 0
                 and it % cfg.bagging_freq == 0) or self.need_re_bagging):
            return
        self.need_re_bagging = False
        n = self.num_data
        u = self._bagging_rng.random(n)
        if self.balanced_bagging:
            label = self.train_data.metadata.label
            pos = label > 0
            mask = np.where(pos, u < cfg.pos_bagging_fraction,
                            u < cfg.neg_bagging_fraction)
        else:
            mask = u < cfg.bagging_fraction
        self.bag_data_cnt = int(mask.sum())
        if self.bag_data_cnt == 0:
            mask[self._bagging_rng.integers(n)] = True
            self.bag_data_cnt = 1
        Log.debug("Re-bagging, using %d data to train" % self.bag_data_cnt)
        self._bag_mask_dev = jnp.asarray(mask)
        self._bag_weight_dev = None

    # -- ResetConfig ---------------------------------------------------
    # training-control params GBDT::ResetConfig accepts mid-training
    # (gbdt.cpp:704-760 + SerialTreeLearner::ResetConfig). Everything
    # else — objective, metric, num_class, binning/layout params — shapes
    # state built at construction and is rejected with a warning.
    _RESET_SPLIT = frozenset({
        "lambda_l1", "lambda_l2", "min_data_in_leaf",
        "min_sum_hessian_in_leaf", "min_gain_to_split", "max_delta_step",
        "num_leaves", "max_depth", "extra_trees", "feature_fraction",
        "feature_fraction_bynode", "cat_smooth", "cat_l2",
        "max_cat_threshold", "min_data_per_group", "max_cat_to_onehot"})
    _RESET_BAG = frozenset({
        "bagging_fraction", "bagging_freq", "pos_bagging_fraction",
        "neg_bagging_fraction", "bagging_seed"})

    def reset_config(self, updates: dict) -> None:
        """GBDT::ResetConfig (gbdt.cpp:704): apply new training-control
        parameters between iterations. Unsupported keys warn and are
        skipped (loudly, never silently misapplied)."""
        from ..config import _BY_NAME, alias_transform
        updates = alias_transform(dict(updates))
        cfg = self.config
        touched_split = touched_bag = False
        rejected = []
        for k, v in updates.items():
            p = _BY_NAME.get(k)
            if p is None:
                rejected.append(k)
                continue
            v = cfg._coerce(p, v)
            if k == "learning_rate":
                cfg.learning_rate = v
                self.shrinkage_rate = float(v)
            elif k in self._RESET_SPLIT:
                setattr(cfg, k, v)
                touched_split = True
            elif k in self._RESET_BAG:
                setattr(cfg, k, v)
                touched_bag = True
            else:
                rejected.append(k)
        if rejected:
            Log.warning("reset_config: parameter(s) %s cannot change "
                        "during training; ignored"
                        % ", ".join(sorted(rejected)))
        if getattr(self, "train_data", None) is None:
            # model loaded from string/file: no learner or bagging state
            # to refresh — config + shrinkage updates above are all that
            # can apply (matches LGBM_BoosterResetParameter on a
            # prediction-only booster)
            return
        if touched_split:
            # pending async trees were grown under the old static knobs;
            # materialize them while their shapes still agree
            self._materialize_pending()
        if touched_split and hasattr(self.tree_learner, "refresh_config"):
            gc_changed = self.tree_learner.refresh_config(cfg)
            if gc_changed and getattr(self.tree_learner, "_persist_carry",
                                      None) is not None:
                # static grower knobs re-key the compiled persist program;
                # sync the payload-ordered scores back to the row-ordered
                # buffer and re-enter the persist path fresh next batch
                self._sync_persist_scores()
                self.tree_learner._persist_carry = None
        if touched_bag:
            self._refresh_bagging_config()

    def _refresh_bagging_config(self) -> None:
        """GBDT::ResetBaggingConfig (gbdt.cpp:762-800): recompute the bag
        plan from the updated config and force a redraw next iteration."""
        cfg = self.config
        n = self.num_data
        self._bagging_rng = np.random.default_rng(cfg.bagging_seed)
        self.balanced_bagging = False
        self.bag_data_cnt = n
        bag_on = False
        if cfg.bagging_fraction < 1.0 and cfg.bagging_freq > 0:
            self.bag_data_cnt = max(1, int(cfg.bagging_fraction * n))
            bag_on = True
        if (cfg.pos_bagging_fraction < 1.0
                or cfg.neg_bagging_fraction < 1.0):
            if cfg.bagging_freq <= 0:
                Log.warning("pos/neg bagging needs bagging_freq > 0")
            else:
                self.balanced_bagging = True
                self.bag_data_cnt = 0
                bag_on = True
        if bag_on:
            self.need_re_bagging = True
        else:
            # bagging turned off: all rows back in the bag immediately
            self.need_re_bagging = False
            self._bag_mask_dev = jnp.ones(n, dtype=bool)
            self._bag_weight_dev = None

    # ------------------------------------------------------------------
    def _fast_path_ok(self) -> bool:
        """True when an iteration needs NO host-side work: built-in
        objective without leaf renewal, no validation/training metric
        evaluation, all classes trainable. Then trees stay on device and
        are materialized in bulk later (the whole boosting loop pipelines
        asynchronously; no per-iteration host sync)."""
        cfg = self.config
        return (self.objective is not None
                and not self.objective.is_renew_tree_output
                and not self.valid_score
                and not (cfg.is_provide_training_metric
                         and self.training_metrics)
                and self.train_data.num_features > 0
                and all(self.class_need_train))

    supports_batch = True   # DART/RF need host work per iteration

    def _persist_bag_spec(self):
        """Static description of the device-side bag transform the persist
        driver should run (ops/grow_persist.make_bag_transform); GOSS
        overrides. ("none",) = no per-row sampling configured."""
        cfg = self.config
        if cfg.bagging_freq > 0 and self.balanced_bagging:
            return ("bagging", 1.0, float(cfg.pos_bagging_fraction),
                    float(cfg.neg_bagging_fraction))
        if cfg.bagging_freq > 0 and cfg.bagging_fraction < 1.0:
            return ("bagging", float(cfg.bagging_fraction), 1.0, 1.0)
        return ("none",)

    def _batch_size(self) -> int:
        from ..parallel.learners import DataParallelTreeLearner
        from ..treelearner.serial import SerialTreeLearner
        cfg = self.config
        learner = self.tree_learner
        persist = bool(getattr(learner, "can_persist_scan", None)
                       and learner.can_persist_scan(self.objective))
        # the v1 fused scan is serial-only; the persist driver also runs
        # sharded under the data-parallel learner (in-loop histogram psum)
        learner_ok = (type(learner) is SerialTreeLearner
                      or (persist
                          and isinstance(learner, DataParallelTreeLearner)))
        bag_spec = self._persist_bag_spec()
        if bag_spec[0] == "none":
            # no sampling configured for the driver; any leftover host
            # bagging state (reset_parameter re-bag, GOSS weights from a
            # single-iteration fallback) forces the per-iteration path
            bag_ok = (not (cfg.bagging_fraction < 1.0
                           and cfg.bagging_freq > 0)
                      and not self.balanced_bagging
                      and not self.need_re_bagging
                      and self._bag_weight_dev is None)
        else:
            # bagging/GOSS run INSIDE the persist driver as payload
            # transforms (masks re-derived from row ids per window)
            bag_ok = persist and learner.persist_bag_ok(bag_spec)
        if not (self.allow_batch and self.supports_batch
                and (self.objective is None
                     or self.objective.supports_fused_scan)
                # K trees/iteration (multiclass) batch only through the
                # persist driver's per-class snapshot loop; GOSS needs the
                # cross-class |g*h| sum it doesn't compute yet
                and (self.num_tree_per_iteration == 1
                     or (persist and bag_spec[0] != "goss"))
                and bag_ok
                and self.train_data.num_features > 0
                and learner_ok):
            return 1
        remaining = self.planned_rounds - self._rounds_done + 1
        # the v1 fused scan exists to amortize dispatch latency; when a
        # single tree is already seconds of device work the batch buys
        # nothing and one 16-iteration program runs for minutes without
        # a host-visible boundary. The persistent-payload path has its
        # own driver and keeps batching at any size.
        if not persist and self.num_data * max(
                self.train_data.num_features, 1) > 150_000_000:
            return 1
        # fixed batch size: every distinct k compiles its own scan program,
        # so the tail runs as single iterations instead of a second compile
        K = 16
        if self.snapshot_stride > 0:
            # checkpointing run: batches end exactly on snapshot
            # boundaries (one extra program per distinct stride, and the
            # resumed run re-aligns to the identical batch shapes). The
            # saver fires on ABSOLUTE iterations, so grafted init-model
            # iterations count toward the alignment
            abs_iter = self.iter + self.num_init_iteration
            K = min(K, self.snapshot_stride
                    - (abs_iter % self.snapshot_stride))
        return K if remaining >= K and K > 1 else 1

    @telemetry.timed("boosting::TrainMultiIterFast(launch)",
                     category="boosting", always=True, new_launch=True)
    def _train_multi_iter_fast(self, k: int) -> bool:
        """K fused iterations (one device dispatch); see
        SerialTreeLearner.train_arrays_scan / train_arrays_scan_persist."""
        learner = self.tree_learner
        ntpi = self.num_tree_per_iteration
        # no-ops past iteration 0
        init0s = tuple(self.boost_from_average(c, True)
                       for c in range(ntpi))
        fmasks = jnp.asarray(
            np.stack([learner.col_sampler.sample()
                      for _ in range(k * ntpi)]))
        if ntpi > 1:
            fmasks = fmasks.reshape(k, ntpi, -1)
        if getattr(learner, "can_persist_scan", None) \
                and learner.can_persist_scan(self.objective):
            if getattr(learner, "_persist_carry", None) is None:
                score0 = (self.train_score.score_device(0) if ntpi == 1
                          else self.train_score.score_matrix())
            else:
                score0 = None
            bag_spec = self._persist_bag_spec()
            wkeys, iters = self._persist_bag_keys(bag_spec, k)
            if bag_spec[0] != "none":
                self._persist_bag_active = True
            stacked = learner.train_arrays_scan_persist(
                self.objective, score0, fmasks, wkeys, iters,
                self.shrinkage_rate, k, bag_spec)
            # scores live payload-ordered on the learner until synced
            self._persist_scores_dirty = True
        else:
            self._sync_persist_scores()
            keys = jnp.stack([learner._next_extras().key for _ in range(k)])
            score0 = self.train_score.score_device(0)
            scoreK, fuK, stacked = learner.train_arrays_scan(
                self.objective, score0, fmasks, keys, self.shrinkage_rate, k)
            learner._feature_used_dev = fuK
            self.train_score._score[0] = scoreK
        start = len(self.models)
        self._pending_batches.append((start, stacked, self.shrinkage_rate,
                                      init0s, "gbdt"))
        self._note_run_tags(start)
        self.models.extend([None] * (k * ntpi))
        self.iter += k
        self._batch_credit = k - 1
        return False

    def _persist_bag_keys(self, bag_spec, k: int):
        """Per-iteration window keys + iteration indices for the persist
        driver's bag transform. Bagging folds the bagging_seed key at the
        WINDOW index (it // bagging_freq), so every iteration inside a
        window redraws the identical per-row mask — the reference's cached
        bag (gbdt.cpp:210-244) without a mask row in the payload."""
        import jax
        start = self.iter
        iters = np.arange(start, start + k, dtype=np.int32)
        if bag_spec[0] == "none":
            return np.zeros((k, 2), np.uint32), iters
        freq = max(int(self.config.bagging_freq), 1)
        base = jax.random.PRNGKey(int(self.config.bagging_seed))
        windows = (iters // freq if bag_spec[0] == "bagging" else iters)
        wkeys = np.stack([
            np.asarray(jax.random.key_data(jax.random.fold_in(base, int(w))))
            for w in windows]).astype(np.uint32)
        return wkeys, iters

    def _sync_persist_scores(self) -> None:
        """Write the persistent-payload carry's scores back into the
        row-ordered score buffer (one device scatter; keeps the carry)."""
        if not getattr(self, "_persist_scores_dirty", False):
            return
        with telemetry.scope("boosting::SyncPersistScores",
                             category="boosting", always=True,
                             **self._run_tags):
            sc = self.tree_learner.persist_finalize_scores()
            if sc is not None:
                if sc.ndim == 2:    # multiclass: [K, N] class-major
                    for c in range(sc.shape[0]):
                        self.train_score._score[c] = sc[c]
                else:
                    self.train_score._score[0] = sc
        self._persist_scores_dirty = False

    def _train_one_iter_fast(self) -> bool:
        if self._batch_credit > 0:
            self._batch_credit -= 1
            return False
        k = self._batch_size()
        if k > 1:
            return self._train_multi_iter_fast(k)
        if (getattr(self, "_persist_bag_active", False)
                or getattr(self.tree_learner, "_persist_carry", None)
                is not None):
            # device bagging already ran in a fused batch: the tail
            # iterations must keep drawing the same hash-keyed window bags
            # (a host redraw mid-window would break the cached-bag
            # contract, gbdt.cpp:210-244). Likewise a LIVE persist carry
            # keeps the tail on the persist driver as k=1 batches — the
            # v1 per-iteration path would sync scores out and, for the
            # voting/data learners, re-dispatch the far slower XLA eval
            return self._train_multi_iter_fast(1)
        self._sync_persist_scores()
        ntpi = self.num_tree_per_iteration
        init_scores = [self.boost_from_average(k, True) for k in range(ntpi)]
        g_dev, h_dev = self._compute_gradients()
        self._cur_grad_hess = (g_dev, h_dev)
        self.bagging(self.iter)
        bag_mask = self._bag_mask_dev
        bagw = self._bag_weight_dev
        for k in range(ntpi):
            grad = g_dev[k]
            hess = h_dev[k]
            if bagw is not None:
                grad = grad * bagw
                hess = hess * bagw
            else:
                m = bag_mask.astype(grad.dtype)
                grad = grad * m
                hess = hess * m
            arrays = self.tree_learner.train_arrays(grad, hess, bag_mask)
            self.train_score.add_score_tree_device(
                arrays.leaf_value, arrays.row_leaf, self.shrinkage_rate,
                arrays.num_leaves, k)
            self._pending.append((len(self.models), arrays, k,
                                  self.shrinkage_rate, init_scores[k]))
            self.models.append(None)
        self.iter += 1
        # bound the async backlog: each pending tree pins its [N] row_leaf
        # (and its dispatch chain) on device; at HIGGS/MS-LTR scale hundreds
        # of unsynced single-iteration dispatches exhaust device memory
        if len(self._pending) >= 8:
            self._materialize_pending()
        return False

    def _materialize_pending(self) -> None:
        """Pull all pending device trees to host in one transfer; detect a
        no-split stop (reference stops and pops that iteration's trees —
        our device update contributed nothing for 1-leaf trees, so
        truncation reproduces the same model)."""
        self._sync_persist_scores()
        if not self._pending and not self._pending_batches:
            return
        # fused batches are O(1) per launch and belong to the run record;
        # the per-iteration path's pending trees stay behind the mode
        with telemetry.scope("boosting::MaterializePending",
                             category="boosting",
                             always=bool(self._pending_batches),
                             **self._run_tags):
            self._materialize_now()

    def _note_run_tags(self, start: int) -> None:
        self._run_tags = self._batch_run_tags[start] = telemetry.run_tags()

    def _materialize_now(self) -> None:
        import jax

        def get_packed(pytree, **record):
            """One device->host transfer for a whole pytree: bitcast every
            leaf to a flat u8 blob, concatenate, transfer once, re-split.
            Each leaf transferred separately costs one D2H round trip.
            `record`: how the transfer's span is recorded."""
            leaves, treedef = jax.tree.flatten(pytree)
            blobs = []
            for x in leaves:
                if x.dtype == jnp.bool_:
                    x = x.astype(jnp.uint8)
                if x.dtype != jnp.uint8:
                    x = jax.lax.bitcast_convert_type(x, jnp.uint8)
                blobs.append(x.reshape(-1))
            dev = (jnp.concatenate(blobs) if blobs else
                   jnp.zeros((0,), jnp.uint8))
            # the one transfer blocks until the device has grown the trees
            with telemetry.scope("boosting::MaterializePending(D2H+wait)",
                                 category="device_wait", **record):
                blob = np.asarray(dev)
            out, off = [], 0
            for x in leaves:
                nb = (int(np.prod(x.shape)) * x.dtype.itemsize
                      if x.ndim else x.dtype.itemsize)
                raw = blob[off:off + nb]
                off += nb
                if x.dtype == jnp.bool_:
                    out.append(raw.astype(bool).reshape(x.shape))
                else:
                    out.append(np.frombuffer(raw.tobytes(),
                                             dtype=np.dtype(x.dtype))
                               .reshape(x.shape))
            return jax.tree.unflatten(treedef, out)

        # batch-scan entries are already stacked on device: one transfer
        ntpi = self.num_tree_per_iteration
        for start, stacked, shrink, init0s, bmode in self._pending_batches:
            if not isinstance(init0s, tuple):
                init0s = (init0s,)
            tags = self._batch_run_tags.get(start, {})
            host_b = get_packed(stacked, always=True, **tags)
            with telemetry.scope("boosting::MaterializePending(host trees)",
                                 category="boosting", always=True, **tags):
                self._batch_to_trees(host_b, start, shrink, init0s, bmode)
        self._pending_batches = []
        self._batch_run_tags = {}
        if not self._pending:
            self._truncate_if_stopped()
            return
        # one stacked transfer per FIELD, not per (tree, field): the host
        # Tree never reads row_leaf (it exists for device score updates),
        # and every D2H round trip is a host sync
        empty_rl = jnp.zeros((0,), jnp.int32)
        stripped = [p[1]._replace(row_leaf=empty_rl) for p in self._pending]
        batched = jax.tree.map(lambda *xs: jnp.stack(xs), *stripped)
        host_batched = get_packed(batched)
        host_arrays = [jax.tree.map(lambda a, i=i: a[i], host_batched)
                       for i in range(len(stripped))]
        stop_pos = None
        iter0_stubs = 0
        ntpi = self.num_tree_per_iteration
        for (pos, _, k, shrink, init), ha in zip(self._pending, host_arrays):
            tree = Tree.from_grower(ha, self.train_data)
            if tree.num_leaves > 1:
                tree.shrink(shrink)
                if abs(init) > K_EPSILON:
                    tree.add_bias(init)
            else:
                tree = Tree(1)
                if pos < ntpi and self.num_init_iteration == 0:
                    # reference keeps iteration-0 constant trees at the
                    # boosted-from-average output (gbdt.cpp:396-411); the
                    # model only STOPS if no class split at all
                    # (should_continue is OR-ed across classes)
                    tree.leaf_value[0] = init
                    iter0_stubs += 1
                elif stop_pos is None:
                    stop_pos = pos
            self.models[pos] = tree
        self._pending = []
        if iter0_stubs == ntpi:
            stop_pos = ntpi if len(self.models) > ntpi else None
        if stop_pos is not None:
            cut = (stop_pos // ntpi) * ntpi
            if cut < len(self.models):
                Log.warning("Stopped training because there are no more "
                            "leaves that meet the split requirements")
                del self.models[cut:]
                self.iter = len(self.models) // ntpi
        self._truncate_if_stopped()

    def _batch_to_trees(self, host_b, start: int, shrink, init0s,
                        bmode: str) -> None:
        """Host trees of one fused batch, from its arrays on the host."""
        import jax
        ntpi = self.num_tree_per_iteration
        kb = int(host_b.num_leaves.shape[0])
        parent_rows = small_rows = splits = cat_splits = 0
        for i in range(kb):
            cls = i % ntpi
            ha = jax.tree.map(lambda a, i=i: a[i], host_b)
            tree = Tree.from_grower(ha, self.train_data)
            if tree.num_leaves > 1:
                # run record: the rows the batch's splits moved, and the
                # rows of each split's smaller child (what its histogram
                # has to cost), from the counts the tree carries
                ni = tree.num_leaves - 1
                kids = [np.where(c >= 0, tree.internal_count[np.maximum(c, 0)],
                                 tree.leaf_count[np.maximum(~c, 0)])
                        for c in (tree.left_child[:ni], tree.right_child[:ni])]
                parent_rows += int(tree.internal_count[:ni].sum(dtype=np.int64))
                small_rows += int(np.minimum(*kids).sum(dtype=np.int64))
                # and how many of the splits are categorical
                splits += ni
                cat_splits += int(np.count_nonzero(
                    np.asarray(tree.decision_type[:ni]) & kCategoricalMask))
                if bmode == "rf":
                    # rf.hpp:103-160: no shrinkage, EVERY tree gets
                    # the constant init-score bias (the device dance
                    # already folded it into the payload scores)
                    if abs(init0s[cls]) > K_EPSILON:
                        tree.add_bias(init0s[cls])
                else:
                    tree.shrink(shrink)
                    if i < ntpi and abs(init0s[cls]) > K_EPSILON:
                        tree.add_bias(init0s[cls])
            else:
                tree = Tree(1)
                if bmode != "rf" and start + i < ntpi:
                    # reference keeps the iteration-0 constant tree at
                    # the boosted-from-average output (gbdt.cpp:396-411)
                    tree.leaf_value[0] = init0s[cls]
            self.models[start + i] = tree
        telemetry.count("tree_learner::split_parent_rows",
                        float(parent_rows), category="tree_learner")
        telemetry.count("tree_learner::split_small_rows",
                        float(small_rows), category="tree_learner")
        telemetry.count("tree_learner::splits", float(splits),
                        category="tree_learner")
        telemetry.count("tree_learner::cat_splits", float(cat_splits),
                        category="tree_learner")

    def _truncate_if_stopped(self) -> None:
        """Batch entries can contain a 1-leaf tree (no-split stop
        mid-batch): truncate at the FIRST stub, exactly like the
        single-iteration stop logic (initial constant trees and any trees
        from a continued-training init model are exempt)."""
        ntpi = self.num_tree_per_iteration
        floor = max(ntpi, self.num_init_iteration * ntpi)
        first_stub = None
        for i, t in enumerate(self.models):
            if t is not None and t.num_leaves <= 1 and i >= floor:
                first_stub = i
                break
        if first_stub is not None:
            cut = (first_stub // ntpi) * ntpi
            if cut < len(self.models):
                Log.warning("Stopped training because there are no more "
                            "leaves that meet the split requirements")
                del self.models[cut:]
                self.iter = len(self.models) // ntpi

    @telemetry.timed("boosting::TrainOneIter", category="boosting")
    def train_one_iter(self, gradients: Optional[np.ndarray] = None,
                       hessians: Optional[np.ndarray] = None) -> bool:
        """One boosting iteration; returns True when training should STOP
        (no splittable leaves), mirroring gbdt.cpp:338-420."""
        self._invalidate_predictors()
        ntpi = self.num_tree_per_iteration
        self._rounds_done += 1
        if gradients is None and hessians is None and self._fast_path_ok():
            return self._train_one_iter_fast()
        self._materialize_pending()
        init_scores = [0.0] * ntpi
        if gradients is None or hessians is None:
            for k in range(ntpi):
                init_scores[k] = self.boost_from_average(k, True)
            g_dev, h_dev = self._compute_gradients()
        else:
            n = self.num_data
            g_dev = jnp.asarray(
                np.asarray(gradients, dtype=np.float32).reshape(ntpi, n))
            h_dev = jnp.asarray(
                np.asarray(hessians, dtype=np.float32).reshape(ntpi, n))

        self._cur_grad_hess = (g_dev, h_dev)   # GOSS bagging reads these
        self.bagging(self.iter)
        bag_mask = self._bag_mask_dev
        bagw = self._bag_weight_dev
        should_continue = False
        for k in range(ntpi):
            grad = g_dev[k]
            hess = h_dev[k]
            if bagw is not None:
                grad = grad * bagw
                hess = hess * bagw
            else:
                m = bag_mask.astype(grad.dtype)
                grad = grad * m
                hess = hess * m

            tree = None
            row_leaf = None
            if self.class_need_train[k] and self.train_data.num_features > 0:
                tree, row_leaf = self.tree_learner.train(grad, hess, bag_mask)

            if tree is not None and tree.num_leaves > 1:
                should_continue = True
                if (self.objective is not None
                        and self.objective.is_renew_tree_output):
                    self._renew_tree_output(tree, row_leaf, k)
                tree.shrink(self.shrinkage_rate)
                self.update_score(tree, row_leaf, k)
                if abs(init_scores[k]) > K_EPSILON:
                    tree.add_bias(init_scores[k])
            else:
                tree = Tree(1)
                # constant tree: only once at the start (gbdt.cpp:396-411)
                if len(self.models) < ntpi:
                    output = 0.0
                    if not self.class_need_train[k]:
                        if self.objective is not None:
                            output = self.objective.boost_from_score(k)
                    else:
                        output = init_scores[k]
                    tree.leaf_value[0] = output
                    self.train_score.add_score_const(output, k)
                    for su in self.valid_score:
                        su.add_score_const(output, k)
            self.models.append(tree)

        if not should_continue:
            Log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            if len(self.models) > ntpi:
                del self.models[-ntpi:]
            return True
        self.iter += 1
        return False

    def _renew_tree_output(self, tree: Tree, row_leaf, tree_id: int) -> None:
        """Leaf re-fit for L1-family objectives
        (SerialTreeLearner::RenewTreeOutput, serial_tree_learner.cpp:628-666).
        Residuals = label - current score over the leaf's in-bag rows."""
        rl = np.asarray(row_leaf)
        score = np.asarray(self.train_score.score_device(tree_id))
        label = self.train_data.metadata.label
        weight = self.train_data.metadata.weight
        bag = np.asarray(self._bag_mask_dev)
        obj = self.objective
        if obj.name == "mape":
            weight = obj.label_weight
        for leaf in range(tree.num_leaves):
            rows = np.nonzero((rl == leaf) & bag)[0]
            if len(rows) == 0:
                continue
            w = weight[rows] if weight is not None else None
            new_out = obj.renew_tree_output(score[rows], label[rows], w)
            tree.set_leaf_output(leaf, new_out)

    def update_score(self, tree: Tree, row_leaf, tree_id: int) -> None:
        """gbdt.cpp:459-483: train scores via the leaf partition (device
        gather), valid scores via binned tree walk."""
        self.train_score.add_score_leaf(
            tree.leaf_value[:max(tree.num_leaves, 1)], row_leaf, tree_id)
        for su in self.valid_score:
            su.add_tree(tree, tree_id)

    def refit(self, X: np.ndarray, decay_rate: float = 0.9) -> None:
        """Refit leaf values on this booster's train data keeping the tree
        structures (GBDT::RefitTree, gbdt.cpp:267 + FitByExistingTree /
        CalculateSplittedLeafOutput): boost through the existing trees,
        re-estimating each leaf's output from the gradients at the staged
        scores and blending old/new by decay_rate. The objective must be
        bound to the refit dataset (Booster.refit builds such a booster)."""
        self._materialize_pending()
        self._invalidate_predictors()
        X = np.ascontiguousarray(X, dtype=np.float64)
        n = X.shape[0]
        ntpi = self.num_tree_per_iteration
        cfg = self.config
        if self.objective is None:
            Log.fatal("Cannot refit a booster without an objective")
        score = np.zeros((ntpi, n))
        lam1, lam2 = float(cfg.lambda_l1), float(cfg.lambda_l2)
        mds = float(cfg.max_delta_step)
        for it in range(len(self.models) // ntpi):
            sc_dev = jnp.asarray(score[0] if ntpi == 1 else score)
            g, h = self.objective.get_gradients(sc_dev)
            g = np.asarray(g).reshape(ntpi, n)
            h = np.asarray(h).reshape(ntpi, n)
            for k in range(ntpi):
                tree = self.models[it * ntpi + k]
                nl = max(tree.num_leaves, 1)
                leaves = tree.predict_leaf(X)
                sg = np.bincount(leaves, weights=g[k], minlength=nl)[:nl]
                sh = np.bincount(leaves, weights=h[k], minlength=nl)[:nl]
                thr = np.sign(sg) * np.maximum(0.0, np.abs(sg) - lam1)
                out = -thr / (sh + lam2 + 1e-15)
                if mds > 0:
                    out = np.sign(out) * np.minimum(np.abs(out), mds)
                out *= self.shrinkage_rate
                old = tree.leaf_value[:nl]
                tree.leaf_value[:nl] = (decay_rate * old
                                        + (1 - decay_rate) * out)
                tree.leaf_count[:nl] = np.bincount(leaves, minlength=nl)[:nl]
                score[k] += tree.leaf_value[leaves]

    def rollback_one_iter(self) -> None:
        """gbdt.cpp:422-438."""
        self._materialize_pending()
        self._invalidate_predictors()
        if self.iter <= 0:
            return
        ntpi = self.num_tree_per_iteration
        for k in range(ntpi):
            tree = self.models[len(self.models) - ntpi + k]
            tree.shrink(-1.0)
            # subtract from scores: re-walk tree
            self.train_score.add_score_np(
                tree.predict_binned(self.train_data), k)
            for su in self.valid_score:
                su.add_tree(tree, k)
        del self.models[-ntpi:]
        self.iter -= 1

    # ------------------------------------------------------------------
    # resilience: full training-state snapshot / restore at an iteration
    # boundary (resilience/checkpoint.py owns the container + IO). The
    # captured set is everything the next iteration reads that is not a
    # pure function of (config, dataset): exact f64 scores, the bag
    # mask/weights, every host RNG stream, the learner's key counter and
    # CEGB bitsets, and the model itself.
    # ------------------------------------------------------------------
    def capture_training_state(self):
        """(arrays, state) for a bit-exact resume; arrays are numpy, state
        is JSON-able. Only valid on a training booster (init() ran)."""
        self._materialize_pending()
        if len(self.models) != ((self.iter + self.num_init_iteration)
                                * self.num_tree_per_iteration):
            # a snapshot mid-batch would label trees with the wrong
            # iteration and desync scores from the model — loud, not torn
            Log.fatal("checkpoint capture off an iteration boundary: "
                      "%d trees vs iteration %d (+%d init)"
                      % (len(self.models), self.iter,
                         self.num_init_iteration))
        arrays = {
            "scores": np.stack([np.asarray(s)
                                for s in self.train_score._score]),
            "bag_mask": np.asarray(self._bag_mask_dev).astype(np.uint8),
            "model_text": np.frombuffer(
                self.save_model_to_string().encode(), dtype=np.uint8),
        }
        # model text keeps the reference's lossy %g for shrinkage /
        # internal_value; boosters that keep MUTATING old trees after a
        # resume (DART's renormalize) need them exact, so the checkpoint
        # carries the full-precision values alongside
        ivs = [np.asarray(t.internal_value[:max(t.num_leaves - 1, 0)],
                          np.float64) for t in self.models]
        arrays["tree_shrinkage"] = np.asarray(
            [t.shrinkage for t in self.models], np.float64)
        arrays["tree_iv_len"] = np.asarray([len(v) for v in ivs], np.int64)
        arrays["tree_iv_flat"] = (np.concatenate(ivs) if ivs
                                  else np.zeros(0, np.float64))
        if self._bag_weight_dev is not None:
            arrays["bag_weight"] = np.asarray(self._bag_weight_dev)
        learner = getattr(self, "tree_learner", None)
        if learner is not None:
            if learner._feature_used_dev is not None:
                arrays["feature_used"] = np.asarray(
                    learner._feature_used_dev)
            if learner._row_feat_used_dev is not None:
                arrays["row_feat_used"] = np.asarray(
                    learner._row_feat_used_dev).astype(np.uint8)
        if self.objective is not None and hasattr(self.objective, "_lcg_x"):
            # rank_xendcg's reference-exact LCG planes advance per
            # iteration; without them a resume would re-randomize
            arrays["obj_lcg_x"] = np.asarray(self.objective._lcg_x)
        state = {
            "boosting": type(self).__name__,
            "iter": int(self.iter),
            "num_init_iteration": int(self.num_init_iteration),
            "shrinkage_rate": float(self.shrinkage_rate),
            "bag_data_cnt": int(self.bag_data_cnt),
            "need_re_bagging": bool(self.need_re_bagging),
            "bagging_rng": self._bagging_rng.bit_generator.state,
            "col_sampler_rng": (
                learner.col_sampler.rng.bit_generator.state
                if learner is not None else None),
            "tree_counter": (int(learner._tree_counter)
                             if learner is not None else 0),
        }
        state.update(self._extra_resilience_state())
        return arrays, state

    def _extra_resilience_state(self) -> dict:
        """Subclass hook (DART adds its drop RNG + tree weights)."""
        return {}

    def _restore_extra_state(self, state: dict) -> None:
        pass

    def restore_training_state(self, arrays, state) -> None:
        """Inverse of capture_training_state onto a freshly init()-ed
        booster of the same config + dataset: the next train_one_iter
        behaves exactly as iteration `state['iter']` of the snapshotted
        run would have."""
        if state.get("boosting") != type(self).__name__:
            Log.fatal("checkpoint was written by boosting=%s, cannot "
                      "restore into %s"
                      % (state.get("boosting"), type(self).__name__))
        self._invalidate_predictors()
        stump = GBDT()
        stump.config = self.config
        stump.load_model_from_string(
            arrays["model_text"].tobytes().decode())
        for tree in stump.models:
            # loaded trees carry real thresholds; the binned walks (valid
            # replay, DART subtraction, rollback) need dataset bins
            tree.bind_to_dataset(self.train_data)
        self.models = list(stump.models)
        if "tree_shrinkage" in arrays:
            # overwrite the %g-lossy fields with the exact snapshot values
            off = 0
            lens = arrays["tree_iv_len"]
            flat = arrays["tree_iv_flat"]
            for i, tree in enumerate(self.models):
                tree.shrinkage = float(arrays["tree_shrinkage"][i])
                ln = int(lens[i])
                tree.internal_value[:ln] = flat[off:off + ln]
                off += ln
        self.iter = int(state["iter"])
        self.num_init_iteration = int(state["num_init_iteration"])
        self.shrinkage_rate = float(state["shrinkage_rate"])
        scores = arrays["scores"]
        for k in range(self.num_tree_per_iteration):
            self.train_score._score[k] = jnp.asarray(scores[k])
        self._bag_mask_dev = jnp.asarray(arrays["bag_mask"].astype(bool))
        self._bag_weight_dev = (jnp.asarray(arrays["bag_weight"])
                                if "bag_weight" in arrays else None)
        self.bag_data_cnt = int(state["bag_data_cnt"])
        self.need_re_bagging = bool(state["need_re_bagging"])
        self._bagging_rng.bit_generator.state = state["bagging_rng"]
        learner = getattr(self, "tree_learner", None)
        if learner is not None:
            if state.get("col_sampler_rng") is not None:
                learner.col_sampler.rng.bit_generator.state = \
                    state["col_sampler_rng"]
            learner._tree_counter = int(state.get("tree_counter", 0))
            if "feature_used" in arrays:
                learner._feature_used_dev = jnp.asarray(
                    arrays["feature_used"])
            if "row_feat_used" in arrays:
                learner._row_feat_used_dev = jnp.asarray(
                    arrays["row_feat_used"].astype(bool))
        if "obj_lcg_x" in arrays and self.objective is not None:
            self.objective._lcg_x = arrays["obj_lcg_x"].copy()
        self._restore_extra_state(state)

    # ------------------------------------------------------------------
    def train(self) -> None:
        """Full training loop (GBDT::Train, gbdt.cpp:246-265)."""
        cfg = self.config
        monitor = None
        if telemetry.enabled():
            from ..telemetry.monitor import TrainingMonitor
            monitor = TrainingMonitor()
        for it in range(self.iter, cfg.num_iterations):
            finished = self.train_one_iter(None, None)
            if not finished:
                finished = self.eval_and_check_early_stopping()
            if monitor is not None:
                monitor.record(it, model=self)
            if finished:
                break
            if (cfg.snapshot_freq > 0
                    and (it + 1) % cfg.snapshot_freq == 0):
                # reference-style model snapshot, made atomic: a worker
                # killed mid-write must never leave a torn snapshot
                from ..resilience.checkpoint import atomic_write_text
                snapshot_out = cfg.output_model + ".snapshot_iter_%d" % (it + 1)
                atomic_write_text(snapshot_out, self.save_model_to_string())
        self._materialize_pending()

    # ------------------------------------------------------------------
    def eval_and_check_early_stopping(self) -> bool:
        met_early_stop = self.output_metric(self.iter)
        if met_early_stop:
            Log.info("Early stopping at iteration %d, the best iteration "
                     "round is %d"
                     % (self.iter, self.iter - self.config.early_stopping_round))
            cut = self.config.early_stopping_round * self.num_tree_per_iteration
            del self.models[-cut:]
        return met_early_stop

    @telemetry.timed("boosting::OutputMetric(eval)", category="eval")
    def output_metric(self, it: int) -> bool:
        """GBDT::OutputMetric (gbdt.cpp:485-543): print/record metrics and
        check early stopping. Returns True when early stop triggers."""
        cfg = self.config
        early_stopping_round = cfg.early_stopping_round
        need_print = (it % cfg.metric_freq == 0)
        met_early_stop = False
        # training metrics
        if need_print and cfg.is_provide_training_metric:
            score = self.train_score.score_host()
            for metric in self.training_metrics:
                vals = metric.eval(score, self.objective)
                for name, v in zip(metric.names, vals):
                    Log.info("Iteration:%d, training %s : %g" % (it, name, v))
                    self.evals_output.append((it, "training", name, v))
        # validation metrics (whole loop skipped unless printing or early
        # stopping needs them, gbdt.cpp:497)
        if not (need_print or early_stopping_round > 0):
            return False
        for i, (su, metrics) in enumerate(zip(self.valid_score,
                                              self.valid_metrics)):
            score = su.score_host()
            for j, metric in enumerate(metrics):
                vals = metric.eval(score, self.objective)
                factor = metric.factor_to_bigger_better
                if need_print:
                    for name, v in zip(metric.names, vals):
                        Log.info("Iteration:%d, %s %s : %g"
                                 % (it, self.valid_names[i], name, v))
                        self.evals_output.append(
                            (it, self.valid_names[i], name, v))
                # early stopping compares only the metric's LAST sub-score
                # (gbdt.cpp OutputMetric: factor * test_scores.back());
                # first_metric_only restricts the check to metric 0 only
                if early_stopping_round > 0 and not (
                        cfg.first_metric_only and j > 0):
                    key = "%s:%s" % (self.valid_names[i], metric.names[-1])
                    cur = vals[-1] * factor
                    if (key not in self.best_score_by_metric
                            or cur > self.best_score_by_metric[key]):
                        self.best_score_by_metric[key] = cur
                        self.best_iter_by_metric[key] = it
                    elif it - self.best_iter_by_metric[key] >= \
                            early_stopping_round:
                        met_early_stop = True
        return met_early_stop

    # ------------------------------------------------------------------
    # prediction (gbdt_prediction.cpp)
    # ------------------------------------------------------------------
    def _used_models(self, start_iteration=0, num_iteration=-1):
        self._materialize_pending()
        ntpi = self.num_tree_per_iteration
        total_iter = len(self.models) // ntpi
        start = max(0, min(int(start_iteration), total_iter))
        if num_iteration is not None and num_iteration > 0:
            end = min(start + int(num_iteration), total_iter)
        else:
            end = total_iter
        return self.models[start * ntpi:end * ntpi]

    def _invalidate_predictors(self) -> None:
        """Drop compiled device predictors whenever the model mutates
        (new/rolled-back/refit trees) — a stale HBM ensemble must never
        serve predictions for a changed model."""
        if self._tpu_predictors:
            self._tpu_predictors.clear()

    def device_predictor(self, start_iteration=0, num_iteration=-1):
        """Compiled TPU predictor for the selected iteration range
        (predict/ subsystem); cached per (range, model size) so repeated
        serving calls reuse the HBM-resident ensemble tensors."""
        from ..predict import TPUPredictor, compile_ensemble
        models = self._used_models(start_iteration, num_iteration)
        key = (int(start_iteration), int(num_iteration), len(self.models))
        cached = self._tpu_predictors.get(key)
        if cached is not None:
            return cached
        cfg = self.config
        dtype = getattr(cfg, "tpu_predict_dtype", "f64") if cfg else "f64"
        min_rows = (int(getattr(cfg, "tpu_predict_min_batch", 256))
                    if cfg else 256)
        ens = compile_ensemble(models, self.num_tree_per_iteration,
                               self.average_output, self.max_feature_idx)
        pred = TPUPredictor(ens, self.objective, dtype=dtype,
                            min_rows=min_rows)
        if len(self._tpu_predictors) >= 8:
            # model grew or many ranges requested: drop stale executables
            self._tpu_predictors.clear()
        self._tpu_predictors[key] = pred
        return pred

    def _predict_device_or_none(self, X, raw_score, start_iteration,
                                num_iteration, leaf=False):
        """TPU-path predict; None (with a logged counter) on any geometry
        the compiler rejects, so callers keep the numpy walk as fallback."""
        from ..predict import EnsembleCompileError
        try:
            pred = self.device_predictor(start_iteration, num_iteration)
            if leaf:
                return pred.predict_leaf(X)
            return pred.predict(X, raw_score=raw_score)
        except EnsembleCompileError as exc:
            telemetry.count("predict::fallback_compile", 1,
                            category="predict")
            Log.warning("predict_device=tpu: %s; falling back to the host "
                        "predictor" % exc)
            return None

    def predict_raw(self, X: np.ndarray, start_iteration=0,
                    num_iteration=-1, early_stop=None) -> np.ndarray:
        """Raw scores [N, ntpi] (PredictRaw).

        early_stop: optional (freq, margin) — the margin-based prediction
        early exit of src/boosting/prediction_early_stop.cpp: every `freq`
        iterations, rows whose margin (binary: 2|score|; multiclass: top1 -
        top2) already exceeds `margin` stop accumulating further trees.
        """
        X = np.ascontiguousarray(X, dtype=np.float64)
        n = X.shape[0]
        ntpi = self.num_tree_per_iteration
        out = np.zeros((n, ntpi))
        models = self._used_models(start_iteration, num_iteration)
        if early_stop is None:
            for i, tree in enumerate(models):
                out[:, i % ntpi] += tree.predict(X)
        else:
            freq, margin = early_stop
            freq = max(int(freq), 1)
            active = np.ones(n, dtype=bool)
            idx = np.arange(n)
            for i, tree in enumerate(models):
                if not active.any():
                    break
                sub = idx[active]
                out[sub, i % ntpi] += tree.predict(X[sub])
                if (i + 1) % (freq * ntpi) == 0:
                    if ntpi == 1:
                        m = 2.0 * np.abs(out[sub, 0])
                    else:
                        top2 = np.partition(out[sub], -2, axis=1)[:, -2:]
                        m = top2[:, 1] - top2[:, 0]
                    active[sub[m >= margin]] = False
        if self.average_output:
            niter = max(len(models) // ntpi, 1)
            out /= niter
        return out

    def predict(self, X: np.ndarray, raw_score=False, start_iteration=0,
                num_iteration=-1, early_stop=None,
                device: str = "cpu") -> np.ndarray:
        if device == "tpu" and early_stop is None:
            # no pre-conversion: TPUPredictor does the one dtype-aware copy
            out = self._predict_device_or_none(X, raw_score,
                                               start_iteration,
                                               num_iteration)
            if out is not None:
                return out
        raw = self.predict_raw(X, start_iteration, num_iteration,
                               early_stop=early_stop)
        if not raw_score and self.objective is not None:
            if self.num_tree_per_iteration == 1:
                return self.objective.convert_output(raw[:, 0])
            return self.objective.convert_output(raw)
        return raw[:, 0] if self.num_tree_per_iteration == 1 else raw

    def predict_leaf_index(self, X: np.ndarray, start_iteration=0,
                           num_iteration=-1,
                           device: str = "cpu") -> np.ndarray:
        X = np.ascontiguousarray(X, dtype=np.float64)
        if device == "tpu":
            out = self._predict_device_or_none(X, False, start_iteration,
                                               num_iteration, leaf=True)
            if out is not None:
                return out
        models = self._used_models(start_iteration, num_iteration)
        out = np.zeros((X.shape[0], len(models)), dtype=np.int32)
        for i, tree in enumerate(models):
            out[:, i] = tree.predict_leaf(X)
        return out

    def predict_contrib(self, X: np.ndarray, start_iteration=0,
                        num_iteration=-1) -> np.ndarray:
        """SHAP feature contributions (GBDT::PredictContrib, gbdt.cpp:574):
        per class, [N, num_features + 1] where columns sum to the raw score
        and the last column is the expected value."""
        X = np.ascontiguousarray(X, dtype=np.float64)
        n = X.shape[0]
        ntpi = self.num_tree_per_iteration
        nf = self.max_feature_idx + 1
        models = self._used_models(start_iteration, num_iteration)
        phis = [np.zeros((n, nf + 1)) for _ in range(ntpi)]
        for i, tree in enumerate(models):
            tree.predict_contrib(X, nf, phis[i % ntpi])
        if self.average_output:
            niter = max(len(models) // ntpi, 1)
            for p in phis:
                p /= niter
        if ntpi == 1:
            return phis[0]
        # reference layout: per-row concatenation over classes
        return np.concatenate(phis, axis=1)

    # ------------------------------------------------------------------
    def feature_importance(self, importance_type: str = "split",
                           num_iteration: int = 0) -> np.ndarray:
        """GBDT::FeatureImportance (gbdt_model_text.cpp:363-400)."""
        models = self._used_models(0, num_iteration if num_iteration > 0 else -1)
        imp = np.zeros(self.max_feature_idx + 1)
        for tree in models:
            ni = tree.num_leaves - 1
            for k in range(ni):
                if tree.split_gain[k] <= 0:
                    continue
                f = tree.split_feature[k]
                if importance_type == "split":
                    imp[f] += 1.0
                else:
                    imp[f] += tree.split_gain[k]
        return imp

    # ------------------------------------------------------------------
    # model text IO (gbdt_model_text.cpp)
    # ------------------------------------------------------------------
    def save_model_to_string(self, start_iteration=0, num_iteration=-1) -> str:
        buf = []
        buf.append(self.sub_model_name)
        buf.append("version=%s" % K_MODEL_VERSION)
        buf.append("num_class=%d" % self.num_class)
        buf.append("num_tree_per_iteration=%d" % self.num_tree_per_iteration)
        buf.append("label_index=%d" % self.label_idx)
        buf.append("max_feature_idx=%d" % self.max_feature_idx)
        if self.objective is not None:
            buf.append("objective=%s" % self.objective.to_string())
        if self.average_output:
            buf.append("average_output")
        buf.append("feature_names=%s" % " ".join(self.feature_names))
        if self.monotone_constraints:
            buf.append("monotone_constraints=%s" % " ".join(
                str(int(m)) for m in self.monotone_constraints))
        buf.append("feature_infos=%s" % " ".join(self.feature_infos))

        models = self._used_models(start_iteration, num_iteration)
        tree_strs = []
        for i, tree in enumerate(models):
            tree_strs.append("Tree=%d\n%s\n" % (i, tree.to_string()))
        buf.append("tree_sizes=%s" % " ".join(
            str(len(s)) for s in tree_strs))
        buf.append("")
        text = "\n".join(buf) + "\n" + "".join(tree_strs)
        text += "end of trees\n"
        # feature importance block
        imp = self.feature_importance("split")
        pairs = [(int(imp[i]), self.feature_names[i])
                 for i in range(len(imp)) if imp[i] > 0]
        pairs.sort(key=lambda p: -p[0])
        text += "\nfeature importances:\n"
        for v, name in pairs:
            text += "%s=%d\n" % (name, v)
        params = self.loaded_parameter or ""
        if self.config is not None:
            params = json.dumps({k: v for k, v in self.config.to_dict().items()
                                 if not callable(v)}, default=str)
        text += "\nparameters:\n%s\nend of parameters\n" % params
        return text

    def save_model_to_file(self, filename: str, start_iteration=0,
                           num_iteration=-1) -> None:
        with open(filename, "w") as f:
            f.write(self.save_model_to_string(start_iteration, num_iteration))

    def model_to_if_else(self, num_iteration=-1) -> str:
        """Standalone C++ source hard-coding the model's prediction
        functions (GBDT::SaveModelToIfElse / ModelToIfElse,
        src/boosting/gbdt_model_text.cpp:105-300 + Tree::ToIfElse): per-tree
        PredictTree%d / PredictTree%dLeaf, and extern "C" PredictRaw /
        Predict / PredictLeafIndex aggregates. The objective transform is
        generated for the common cases (sigmoid / softmax / identity)."""
        models = self._used_models(0, num_iteration)
        ntpi = self.num_tree_per_iteration
        buf = ["// generated by lightgbm_tpu convert_model",
               "#include <cmath>", ""]
        for i, t in enumerate(models):
            buf.append(t.to_if_else(i, False))
            buf.append(t.to_if_else(i, True))
            buf.append("")
        n = len(models)
        ptrs = ", ".join("PredictTree%d" % i for i in range(n)) or ""
        lptrs = ", ".join("PredictTree%dLeaf" % i for i in range(n)) or ""
        buf.append("typedef double (*TreeFn)(const double*);")
        buf.append("static const TreeFn kTrees[] = {%s};" % ptrs)
        buf.append("static const TreeFn kTreeLeaves[] = {%s};" % lptrs)
        buf.append("static const int kNumTrees = %d;" % n)
        buf.append("static const int kNumClass = %d;" % ntpi)
        avg = ("/ (kNumTrees / kNumClass)" if self.average_output else "")
        buf.append("""
extern "C" void PredictRaw(const double* arr, double* out) {
  for (int k = 0; k < kNumClass; ++k) out[k] = 0.0;
  for (int i = 0; i < kNumTrees; ++i) out[i %% kNumClass] += kTrees[i](arr);
  for (int k = 0; k < kNumClass; ++k) out[k] = out[k] %s;
}

extern "C" void PredictLeafIndex(const double* arr, double* out) {
  for (int i = 0; i < kNumTrees; ++i) out[i] = kTreeLeaves[i](arr);
}
""" % (avg if avg else ""))
        obj = self.objective.name if self.objective is not None else ""
        if obj == "binary":
            sig = float(getattr(self.objective, "sigmoid", 1.0))
            transform = ("out[0] = 1.0 / (1.0 + std::exp(-%s * out[0]));"
                         % repr(sig))
        elif obj == "multiclass":
            transform = """double wmax = out[0];
  for (int k = 1; k < kNumClass; ++k) if (out[k] > wmax) wmax = out[k];
  double wsum = 0.0;
  for (int k = 0; k < kNumClass; ++k) { out[k] = std::exp(out[k] - wmax); wsum += out[k]; }
  for (int k = 0; k < kNumClass; ++k) out[k] /= wsum;"""
        elif obj == "multiclassova":
            sig = float(getattr(self.objective, "sigmoid", 1.0))
            transform = ("for (int k = 0; k < kNumClass; ++k) "
                         "out[k] = 1.0 / (1.0 + std::exp(-%s * out[k]));"
                         % repr(sig))
        elif obj == "cross_entropy":
            transform = ("for (int k = 0; k < kNumClass; ++k) "
                         "out[k] = 1.0 / (1.0 + std::exp(-out[k]));")
        elif obj == "cross_entropy_lambda":
            transform = ("for (int k = 0; k < kNumClass; ++k) "
                         "out[k] = std::log1p(std::exp(out[k]));")
        elif obj in ("poisson", "gamma", "tweedie"):
            transform = ("for (int k = 0; k < kNumClass; ++k) "
                         "out[k] = std::exp(out[k]);")
        elif obj == "regression" and getattr(self.objective, "sqrt", False):
            transform = ("out[0] = (out[0] >= 0 ? 1.0 : -1.0) "
                         "* out[0] * out[0];")
        elif self.objective is None or obj in (
                "regression", "regression_l1", "huber", "fair", "quantile",
                "mape", "lambdarank", "rank_xendcg"):
            transform = "// identity transform"
        else:
            Log.fatal("convert_model has no output transform for "
                      "objective %s" % obj)
        buf.append("""
extern "C" void Predict(const double* arr, double* out) {
  PredictRaw(arr, out);
  %s
}
""" % transform)
        return "\n".join(buf)

    def load_model_from_string(self, text: str) -> None:
        """GBDT::LoadModelFromString (gbdt_model_text.cpp:385+)."""
        self._invalidate_predictors()
        self.models = []
        lines = text.splitlines()
        kv: Dict[str, str] = {}
        i = 0
        while i < len(lines):
            line = lines[i].strip()
            if line.startswith("Tree="):
                break
            if "=" in line:
                k, v = line.split("=", 1)
                kv[k] = v
            elif line:
                kv[line] = ""
            i += 1
        if "num_class" not in kv:
            Log.fatal("Model file doesn't specify the number of classes")
        self.num_class = int(kv["num_class"])
        self.num_tree_per_iteration = int(
            kv.get("num_tree_per_iteration", self.num_class))
        self.label_idx = int(kv.get("label_index", 0))
        self.max_feature_idx = int(kv.get("max_feature_idx", 0))
        if "average_output" in kv:
            self.average_output = True
        self.feature_names = kv.get("feature_names", "").split()
        self.feature_infos = kv.get("feature_infos", "").split()
        if "monotone_constraints" in kv:
            self.monotone_constraints = [
                int(x) for x in kv["monotone_constraints"].split()]
        if "objective" in kv and kv["objective"]:
            cfg = self.config if self.config is not None else Config({})
            self.objective = parse_objective_string(kv["objective"], cfg)
        # parse tree blocks
        blocks: List[List[str]] = []
        cur: List[str] = []
        for line in lines[i:]:
            if line.startswith("Tree="):
                if cur:
                    blocks.append(cur)
                cur = []
            elif line.strip() == "end of trees":
                if cur:
                    blocks.append(cur)
                cur = []
                break
            else:
                cur.append(line)
        for b in blocks:
            self.models.append(Tree.from_string("\n".join(b)))
        self.iter = len(self.models) // max(self.num_tree_per_iteration, 1)
        self.num_init_iteration = self.iter

    # ------------------------------------------------------------------
    def dump_model(self, start_iteration=0, num_iteration=-1) -> dict:
        """GBDT::DumpModel JSON (gbdt_model_text.cpp:21-92)."""
        models = self._used_models(start_iteration, num_iteration)
        return {
            "name": "tree",
            "version": K_MODEL_VERSION,
            "num_class": self.num_class,
            "num_tree_per_iteration": self.num_tree_per_iteration,
            "label_index": self.label_idx,
            "max_feature_idx": self.max_feature_idx,
            "objective": (self.objective.to_string()
                          if self.objective else ""),
            "average_output": self.average_output,
            "feature_names": self.feature_names,
            "monotone_constraints": self.monotone_constraints,
            "tree_info": [t.to_json() for t in models],
            "feature_importances": {
                self.feature_names[i]: float(v)
                for i, v in enumerate(self.feature_importance("split"))
                if v > 0},
        }

    @property
    def current_iteration(self) -> int:
        return len(self.models) // max(self.num_tree_per_iteration, 1)
