"""Host-side orchestration for batched multi-model training.

A *member* is an ordinary Booster — its own config, objective instance,
tree learner, column sampler, bagging RNG and score cache — whose tree
GROWTH is dispatched through the shared vmapped programs in
multimodel/driver.py instead of per-model programs. Everything the
serial path computes on the host (bagging masks, column masks, per-tree
RNG keys, boost-from-average, tree materialization, stop truncation) is
computed by the member's OWN booster code here, in the same order the
serial loop would call it, so the per-model inputs fed to the batched
program are bit-identical to what the member would have fed its own
program — that, plus the vmapped body mirroring the scalar scan body,
is the whole bit-exactness argument.

Members are partitioned into *static groups*: models that share every
compile-time attribute (grower config, objective fingerprint, bagging
on/off, boosting kind). Each group trains through one program chain;
per-model knobs that differ inside a group (learning_rate, lambdas,
min_gain_to_split, min_data_in_leaf, seeds, ...) ride as traced [B]
inputs. Members that cannot take the batched path at all (DART/RF,
custom learners, CEGB, persist-eligible setups, unsupported objectives)
fall back to their own serial training loop — the sweep still returns
one Booster per grid point either way.

Known divergence (documented, degenerate regime only): after a model's
first no-split tree at round >= 1 the serial loop rewinds and keeps
drawing — occasionally re-splitting before a later truncation — while
the batched active-mask freezes the lane at the first stub. Both paths
truncate at the first stub, so they differ only when a serial re-split
lands AFTER a stub, i.e. when training has already effectively stopped.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..telemetry import events as telemetry
from ..utils.log import Log
from . import driver

# mirrors GBDT._batch_size: one fused 16-iteration program plus a k=1
# tail program, and the same size guard on one batch at very large
# row*feature products
MM_BATCH_K = 16
MM_SIZE_GUARD = 150_000_000


class Member:
    """One sweep entry: the public Booster plus its training internals."""

    def __init__(self, booster, params: dict):
        self.booster = booster
        self.params = params
        self.inner = booster._booster
        self.learner = self.inner.tree_learner
        self.objective = self.inner.objective


def eligibility(member: Member) -> Tuple[Optional[str], str]:
    """(kind, reason): kind is "scan" (gbdt), "goss", or None with the
    fallback reason. Mirrors the gates GBDT._batch_size applies before
    fusing, minus bagging (precomputed masks make bagged members
    batchable here) and plus the CEGB/forced-split extras the shared
    GrowExtras base cannot carry per-model."""
    from ..boosting.gbdt import GBDT
    from ..boosting.goss import GOSS
    from ..treelearner.serial import SerialTreeLearner
    inner = member.inner
    if type(inner) is GOSS:
        kind = "goss"
    elif type(inner) is GBDT:
        kind = "scan"
    else:
        return None, "boosting type %s" % type(inner).__name__
    obj = member.objective
    if obj is None:
        return None, "custom objective"
    if not obj.supports_fused_scan:
        return None, "objective lacks device gradients"
    if obj.is_renew_tree_output:
        return None, "objective renews leaves on host"
    if inner.num_tree_per_iteration != 1:
        return None, "multiclass"
    if not all(inner.class_need_train):
        return None, "untrainable class"
    if inner.train_data.num_features <= 0:
        return None, "no features"
    learner = member.learner
    if type(learner) is not SerialTreeLearner:
        return None, "non-serial tree learner"
    gc = learner.grow_config
    if gc.use_cegb or gc.use_cegb_lazy:
        return None, "CEGB"
    if gc.n_forced != 0:
        return None, "forced splits"
    if learner.can_persist_scan(obj):
        # the persist driver is a different program family; batching it
        # is future work — fall back so results match the serial path
        return None, "persist-scan eligible"
    return kind, ""


def _has_bag(inner) -> bool:
    return bool(inner.bag_data_cnt < inner.num_data
                or inner.balanced_bagging)


def group_key(member: Member, kind: str):
    """Compile-time identity: members sharing a key share programs."""
    return (kind, _has_bag(member.inner) if kind == "scan" else True,
            member.learner.grow_config,
            member.objective.static_fingerprint())


def serial_train(member: Member, num_boost_round: int) -> None:
    """The member's own serial loop, flags set exactly as engine.train
    sets them (no callbacks / eval sets / custom objective here)."""
    inner = member.inner
    inner.allow_batch = True
    inner.planned_rounds = num_boost_round
    for _ in range(num_boost_round):
        inner.train_one_iter(None, None)
    inner._materialize_pending()


def _stack_params(members: List[Member]):
    return jax.tree.map(lambda *xs: jnp.stack(xs),
                        *[m.learner.params for m in members])


def _member_slice(stacked, b: int, keep_axis: bool = False):
    if keep_axis:
        return jax.tree.map(lambda a: a[b:b + 1], stacked)
    return jax.tree.map(lambda a: a[b], stacked)


def train_scan_group(members: List[Member], num_boost_round: int,
                     bag_fn=None, prog_member: Optional[Member] = None
                     ) -> None:
    """Batched training for a gbdt static group: fused 16-iteration
    blocks (k=1 tail), per-model bag masks precomputed by each member's
    own bagging() in serial call order.

    ``bag_fn(member_index, iteration) -> bool [N] mask`` overrides the
    members' own bagging (the cv fast path injects fold-intersect-bag
    masks); ``prog_member`` supplies the learner/objective the compiled
    programs and traced gradient args come from when the members' own
    objectives are not full-dataset-shaped (cv's per-fold objectives)."""
    b = len(members)
    bucket = driver.bucket_for(b)
    lead = prog_member if prog_member is not None else members[0]
    learner0 = lead.learner
    n = lead.inner.num_data
    has_bag = bag_fn is not None or _has_bag(members[0].inner)
    fn16 = None
    fn1 = None

    # per-member carries; initialized from each member's own state
    init0s = [m.inner.boost_from_average(0, True) for m in members]
    scores = [m.inner.train_score.score_device(0) for m in members]
    fus = [m.learner._feature_used_dev
           if m.learner._feature_used_dev is not None
           else m.learner._extras_base.feature_used for m in members]
    act = jnp.ones((b,), bool)
    shrinks = jnp.asarray([m.inner.shrinkage_rate for m in members],
                          jnp.float64)
    paramss = _stack_params(members)
    base = learner0._extras_base
    gargs = lead.objective._grad_args()

    score_c = jnp.stack(scores)
    fu_c = jnp.stack(fus)

    size_guarded = (n * max(lead.inner.train_data.num_features, 1)
                    > MM_SIZE_GUARD)
    pos = 0
    while pos < num_boost_round:
        remaining = num_boost_round - pos
        k = (MM_BATCH_K if remaining >= MM_BATCH_K and not size_guarded
             else 1)
        fmasks = []
        keys = []
        bags = []
        for mi, m in enumerate(members):
            fmasks.append(np.stack([m.learner.col_sampler.sample()
                                    for _ in range(k)]))
            keys.append(np.stack(
                [np.asarray(m.learner._next_extras().key)
                 for _ in range(k)]))
            if bag_fn is not None:
                bags.append(np.stack([bag_fn(mi, it)
                                      for it in range(pos, pos + k)]))
            elif has_bag:
                bm = []
                for it in range(pos, pos + k):
                    m.inner.bagging(it)
                    bm.append(np.asarray(m.inner._bag_mask_dev))
                bags.append(np.stack(bm))
        fmasks = jnp.asarray(np.stack(fmasks))
        keys = jnp.asarray(np.stack(keys))
        bags = (jnp.asarray(np.stack(bags)) if has_bag
                else jnp.zeros((b, k, 0), bool))
        idx = jnp.arange(pos, pos + k, dtype=jnp.int32)

        fn = fn16 if k == MM_BATCH_K else fn1
        if fn is None:
            fn = driver.get_scan_program(learner0, lead.objective, k,
                                         has_bag)
            if k == MM_BATCH_K:
                fn16 = fn
            else:
                fn1 = fn

        args = driver.pad_lanes(
            b, bucket,
            (score_c, fu_c, fmasks, keys, bags, act, shrinks, paramss))
        score_p, fu_p, fmasks_p, keys_p, bags_p, act_p, shr_p, par_p = args
        scoreK, fuK, actK, stacked = fn(
            learner0.layout, score_p, fu_p, fmasks_p, keys_p, bags_p,
            act_p, shr_p, base, learner0.meta, par_p, learner0.fix,
            gargs, learner0.forced, idx)
        score_c, fu_c, act = scoreK[:b], fuK[:b], actK[:b]
        for i, m in enumerate(members):
            inner = m.inner
            stacked_b = _member_slice(stacked, i)
            # boost_from_average is a no-op past iteration 0: only the
            # first block's entry carries the init-score bias
            init0 = init0s[i] if pos == 0 else 0.0
            inner._pending_batches.append(
                (len(inner.models), stacked_b, inner.shrinkage_rate,
                 (init0,), "gbdt"))
            inner.models.extend([None] * k)
            inner.iter += k
        pos += k

    for i, m in enumerate(members):
        m.inner.train_score._score[0] = score_c[i]
        m.learner._feature_used_dev = fu_c[i]
        m.inner._materialize_pending()


def train_goss_group(members: List[Member], num_boost_round: int) -> None:
    """Batched training for a GOSS static group: per-iteration programs
    (GOSS's gradient-dependent sampling runs on the host between the
    batched gradient and grow steps, driven by each member's own
    GOSS.bagging so the sampling RNG stream is bit-identical)."""
    b = len(members)
    bucket = driver.bucket_for(b)
    lead = members[0]
    learner0 = lead.learner
    n = lead.inner.num_data

    grad_fn = driver.get_grad_program(learner0, lead.objective)
    step_fn = driver.get_step_program(learner0, lead.objective,
                                      has_weight=True)

    init0s = [m.inner.boost_from_average(0, True) for m in members]
    score_c = jnp.stack([m.inner.train_score.score_device(0)
                         for m in members])
    fu_c = jnp.stack([m.learner._feature_used_dev
                      if m.learner._feature_used_dev is not None
                      else m.learner._extras_base.feature_used
                      for m in members])
    act = jnp.ones((b,), bool)
    shrinks = jnp.asarray([m.inner.shrinkage_rate for m in members],
                          jnp.float64)
    paramss = _stack_params(members)
    base = learner0._extras_base
    gargs = lead.objective._grad_args()
    ones_w = np.ones(n, np.float32)

    for it in range(num_boost_round):
        score_p = driver.pad_lanes(b, bucket, score_c)
        g_all, h_all = grad_fn(score_p, gargs)
        ws, bags, fmasks, keys = [], [], [], []
        for i, m in enumerate(members):
            inner = m.inner
            # the member's own GOSS sampler sees exactly the gradients
            # its serial twin would (class axis restored)
            inner._cur_grad_hess = (g_all[i:i + 1], h_all[i:i + 1])
            inner.bagging(it)
            w = inner._bag_weight_dev
            ws.append(np.asarray(w) if w is not None else ones_w)
            bags.append(np.asarray(inner._bag_mask_dev))
            fmasks.append(np.asarray(m.learner.col_sampler.sample()))
            keys.append(np.asarray(m.learner._next_extras().key))
        args = driver.pad_lanes(
            b, bucket,
            (score_c, g_all[:b], h_all[:b],
             jnp.asarray(np.stack(ws)), jnp.asarray(np.stack(bags)),
             jnp.asarray(np.stack(fmasks)), jnp.asarray(np.stack(keys)),
             fu_c, act, shrinks, paramss))
        (score_p, g_p, h_p, w_p, bag_p, fm_p, key_p, fu_p, act_p,
         shr_p, par_p) = args
        score2, fu2, act2, stacked = step_fn(
            learner0.layout, score_p, g_p, h_p, w_p, bag_p, fm_p, key_p,
            fu_p, act_p, shr_p, base, learner0.meta, par_p,
            learner0.fix, learner0.forced,
            jnp.asarray(it, jnp.int32))
        score_c, fu_c, act = score2[:b], fu2[:b], act2[:b]
        for i, m in enumerate(members):
            inner = m.inner
            stacked_b = _member_slice(stacked, i, keep_axis=True)
            init0 = init0s[i] if it == 0 else 0.0
            inner._pending_batches.append(
                (len(inner.models), stacked_b, inner.shrinkage_rate,
                 (init0,), "gbdt"))
            inner.models.extend([None])
            inner.iter += 1

    for i, m in enumerate(members):
        m.inner.train_score._score[0] = score_c[i]
        m.learner._feature_used_dev = fu_c[i]
        m.inner._materialize_pending()


def train_members(members: List[Member], num_boost_round: int) -> None:
    """Partition into static groups, chunk to the bucket cap, train."""
    groups: dict = {}
    fallback: List[Member] = []
    for m in members:
        kind, reason = eligibility(m)
        if kind is None:
            Log.debug("multimodel: %s falls back to serial (%s)"
                      % (type(m.inner).__name__, reason))
            fallback.append(m)
            continue
        groups.setdefault(group_key(m, kind), []).append(m)
    for key, ms in groups.items():
        kind = key[0]
        trainer = (train_goss_group if kind == "goss"
                   else train_scan_group)
        for lo in range(0, len(ms), driver.MM_MAX_BUCKET):
            chunk = ms[lo:lo + driver.MM_MAX_BUCKET]
            telemetry.count("tree_learner::mm_models", float(len(chunk)),
                            category="tree_learner")
            trainer(chunk, num_boost_round)
    for m in fallback:
        serial_train(m, num_boost_round)
