"""Random Forest mode.

TPU-native rebuild of src/boosting/rf.hpp: mandatory bagging, no shrinkage,
gradients computed ONCE from the constant init score (Boosting override,
rf.hpp:81-101), cached scores hold the running AVERAGE of tree outputs
(MultiplyScore dance in TrainOneIter, rf.hpp:103-160), `average_output`
flagged in the model file so prediction divides by the iteration count.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from ..models.tree import Tree
from ..telemetry import events as telemetry
from ..utils.log import Log
from .gbdt import GBDT, K_EPSILON


class RF(GBDT):

    # RF batches through the persist driver's rf mode: the per-iteration
    # host work (bag RNG) ships as traced [k, n] weight vectors
    supports_batch = True
    sub_model_name = "tree"   # reference RF still writes "tree"
    average_output = True

    def init(self, config, train_data, objective, training_metrics=()):
        if not (config.bagging_freq > 0 and 0.0 < config.bagging_fraction < 1.0):
            Log.fatal("Random forest needs bagging_freq > 0 and "
                      "bagging_fraction in (0, 1)")
        super().init(config, train_data, objective, training_metrics)
        if objective is None:
            Log.fatal("RF mode does not support custom objective functions, "
                      "please use built-in objectives.")
        self.shrinkage_rate = 1.0
        # gradients from the constant init score, computed once (rf.hpp:81)
        self.init_scores = [self.objective.boost_from_score(k)
                            for k in range(self.num_tree_per_iteration)]
        n = self.num_data
        score = jnp.asarray(
            np.tile(np.asarray(self.init_scores, dtype=np.float64)[:, None],
                    (1, n)))
        if self.num_tree_per_iteration == 1:
            g, h = self.objective.get_gradients(score[0])
            g, h = g.reshape(1, -1), h.reshape(1, -1)
        else:
            g, h = self.objective.get_gradients(score)
        self._rf_grad = (g, h)

    # -- fused device path (ops/grow_persist rf driver mode) -----------
    def _fast_path_ok(self) -> bool:
        """RF rides the persist driver when the whole iteration fits the
        compiled rf program: constant-init-score gradient kernel
        (payload fill contract), host-RNG bag masks as traced weight
        vectors, and the running-average dance inside the scan. The
        1-leaf guard in apply_scores_avg skips the dance exactly like
        the host mid-run stub path, so an init-score FILE (whose
        contributions the host's score *= 0 at iteration 0 would zero)
        is the one configuration routed back to the host loop."""
        from ..treelearner.serial import SerialTreeLearner
        learner = self.tree_learner
        return (super()._fast_path_ok()
                and self.num_tree_per_iteration == 1
                and not self.train_score.has_init_score
                and type(learner) is SerialTreeLearner
                and getattr(learner, "can_persist_scan", None) is not None
                and learner.can_persist_scan(self.objective)
                and self.objective.persist_grad_mode() == "payload")

    def _train_one_iter_fast(self) -> bool:
        # every k lands on the rf driver — the generic v1 fallback would
        # boost from average and shrink, neither of which RF does
        if self._batch_credit > 0:
            self._batch_credit -= 1
            return False
        return self._train_multi_iter_fast(max(self._batch_size(), 1))

    @telemetry.timed("boosting::TrainMultiIterFast(launch)",
                     category="boosting", always=True, new_launch=True)
    def _train_multi_iter_fast(self, k: int) -> bool:
        learner = self.tree_learner
        fmasks = jnp.asarray(
            np.stack([learner.col_sampler.sample() for _ in range(k)]))
        masks, ts = [], []
        for j in range(k):
            # the HOST bag RNG, consumed in the host path's exact order:
            # the masks ride into the compiled program as per-iteration
            # weight vectors, so device and host paths draw identical
            # bags (bit-exact parity, unlike the hash-keyed device bags)
            self.bagging(self.iter + j)
            masks.append(np.asarray(self._bag_mask_dev))
            ts.append(float(self.iter + j + self.num_init_iteration))
        bagw = np.stack(masks).astype(np.float32)
        tvec = np.asarray(ts, np.float64)
        aux = np.stack([tvec, 1.0 / (tvec + 1.0)], axis=1)
        if getattr(learner, "_persist_carry", None) is None:
            score0 = self.train_score.score_device(0)
        else:
            score0 = None
        stacked = learner.train_arrays_scan_persist_rf(
            self.objective, score0, fmasks, bagw, aux,
            float(self.init_scores[0]), k)
        self._persist_scores_dirty = True
        start = len(self.models)
        self._pending_batches.append(
            (start, stacked, 1.0, (float(self.init_scores[0]),), "rf"))
        self._note_run_tags(start)
        self.models.extend([None] * k)
        self.iter += k
        self._batch_credit = k - 1
        return False

    def _truncate_if_stopped(self) -> None:
        # a 1-leaf tree is NOT a stop for RF: the reference appends a
        # constant stub and keeps sampling (rf.hpp:145-155)
        return

    def train_one_iter(self, gradients=None, hessians=None) -> bool:
        if gradients is not None or hessians is not None:
            Log.fatal("RF mode does not support custom objective functions")
        self._invalidate_predictors()
        if self._fast_path_ok():
            self._rounds_done += 1
            return self._train_one_iter_fast()
        self._materialize_pending()
        self.bagging(self.iter)
        g_dev, h_dev = self._rf_grad
        bag_mask = self._bag_mask_dev
        ntpi = self.num_tree_per_iteration
        total_iter = self.iter + self.num_init_iteration
        for k in range(ntpi):
            m = bag_mask.astype(g_dev.dtype)
            grad = g_dev[k] * m
            hess = h_dev[k] * m
            tree = None
            row_leaf = None
            if self.class_need_train[k]:
                tree, row_leaf = self.tree_learner.train(grad, hess, bag_mask)
            if tree is not None and tree.num_leaves > 1:
                if (self.objective is not None
                        and self.objective.is_renew_tree_output):
                    self._renew_rf_tree_output(tree, row_leaf, k)
                if abs(self.init_scores[k]) > K_EPSILON:
                    tree.add_bias(self.init_scores[k])
                # scores hold averages: scale up, add, scale back down
                self._multiply_score(k, float(total_iter))
                self.update_score(tree, row_leaf, k)
                self._multiply_score(k, 1.0 / (total_iter + 1))
            else:
                tree = Tree(1)
                if len(self.models) < ntpi:
                    # reference rf.hpp:145-155: non-zero constant only when
                    # the class is untrainable; trainable classes keep 0.0
                    output = 0.0
                    if not self.class_need_train[k]:
                        output = self.objective.boost_from_score(k)
                    tree.leaf_value[0] = output
                    self._multiply_score(k, float(total_iter))
                    self.train_score.add_score_const(output, k)
                    for su in self.valid_score:
                        su.add_score_const(output, k)
                    self._multiply_score(k, 1.0 / (total_iter + 1))
            self.models.append(tree)
        self.iter += 1
        return False

    def _renew_rf_tree_output(self, tree, row_leaf, tree_id):
        """RF renewal: residuals against the constant init score (rf.hpp:131)."""
        rl = np.asarray(row_leaf)
        label = self.train_data.metadata.label
        weight = self.train_data.metadata.weight
        bag = np.asarray(self._bag_mask_dev)
        obj = self.objective
        if obj.name == "mape":
            weight = obj.label_weight
        pred = self.init_scores[tree_id]
        for leaf in range(tree.num_leaves):
            rows = np.nonzero((rl == leaf) & bag)[0]
            if len(rows) == 0:
                continue
            w = weight[rows] if weight is not None else None
            new_out = obj.renew_tree_output(
                np.full(len(rows), pred), label[rows], w)
            tree.set_leaf_output(leaf, new_out)

    def _multiply_score(self, tree_id: int, val: float) -> None:
        self.train_score.multiply_score(val, tree_id)
        for su in self.valid_score:
            su.multiply_score(val, tree_id)
