"""Batched (vmapped) fused-iteration drivers for multi-model training.

PR 17 made a whole boosting iteration ONE pure compiled program (the
fused ``lax.scan`` in SerialTreeLearner.train_arrays_scan). That shape —
gradients -> grow -> score update with no host sync — is exactly what
``jax.vmap`` wants: this module wraps the identical per-model scan body
in a model-axis vmap so B boosters train over ONE shared HBM-resident
binned Dataset in a single compiled program.

Batching contract (what is per-model vs shared):

* per-model, traced with a leading ``[B]`` axis: initial scores,
  feature_used carries, per-tree column masks and RNG keys, bag masks,
  shrinkage, SplitParams (lambda_l1/l2, min_gain_to_split,
  min_data_in_leaf, ... ride as traced ``[B]`` scalars), and the
  ``active`` mask below;
* shared (in_axes=None): the DataLayout (ONE HBM copy of the binned
  matrix — see Dataset.to_device's layout cache), FeatureMeta, FixInfo,
  GrowExtras base, the objective's device args, and forced-split info.

Early-stop semantics: a model whose tree fails to split at a global tree
index >= 1 would, in the serial loop, end training there
(GBDT._truncate_if_stopped). In the batch it instead rides an inert
``[B]`` active-mask — its lane keeps dispatching (vmap has no ragged
lanes) but its score/feature_used carries freeze and its emitted trees
are forced to 1-leaf stubs, which the host-side truncation then discards
exactly like the serial stop. One straggler model never blocks the
batch, and the final model texts are bit-identical either way. The
iteration-0 no-split case does NOT deactivate a lane: the reference
keeps the boosted-from-average constant tree and continues.

Program count is independent of B: B is padded up to a power-of-two
bucket (pad lanes replicate model 0 and are discarded), so the compile
surface is the bucket ladder — see analysis/compile_audit.mm_ladder_bound.

Programs are cached on the Dataset (``_mm_scan_cache``) for the same
reason train_arrays_scan caches there: every Booster builds a fresh
learner, and the program only depends on layout + grow config +
objective fingerprint (+ the batch bucket).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..telemetry import events as telemetry

# bucket ladder for the model-batch axis: B pads up to the next power of
# two so distinct sweep widths reuse programs. Sweeps wider than
# MM_MAX_BUCKET train in chunks of MM_MAX_BUCKET (multimodel/batch.py),
# keeping the ladder — and the compile-surface bound — finite.
MM_MIN_BUCKET = 1
MM_MAX_BUCKET = 64


def bucket_for(b: int) -> int:
    """Smallest power-of-two bucket >= b (callers chunk above the cap)."""
    if b < 1:
        raise ValueError("batch size must be >= 1")
    if b > MM_MAX_BUCKET:
        raise ValueError("batch size %d exceeds MM_MAX_BUCKET=%d; chunk "
                         "the sweep first" % (b, MM_MAX_BUCKET))
    return 1 << (b - 1).bit_length()


def _cache(dataset):
    cache = getattr(dataset, "_mm_scan_cache", None)
    if cache is None:
        cache = dataset._mm_scan_cache = {}
    return cache


def get_scan_program(learner, objective, k: int, has_bag: bool):
    """The vmapped k-iteration scan program for ``learner``'s dataset.

    Mirrors SerialTreeLearner.train_arrays_scan's body line for line —
    gradient cast, grower dispatch, f64 leaf-gather score update — so a
    B=1 batch is bit-exact vs the scalar program (pinned in tests), with
    three batch-only additions: the per-iteration bag multiply, the
    active-mask freeze, and the global tree index carried for the
    iteration-0 stub exemption.
    """
    ds = learner.dataset
    cache = _cache(ds)
    key = ("scan", k, bool(has_bag), learner.grow_config,
           objective.static_fingerprint())
    fn = cache.get(key)
    if fn is not None:
        return fn
    telemetry.count("tree_learner::mm_programs", category="tree_learner")

    grad_fn = objective.grad_fn()
    gc = learner.grow_config
    use_part = learner.use_partitioned
    cat, gw = learner.cat_layout, learner.gw_global
    n = ds.num_data
    from ..ops.grow import grow_tree, grow_tree_partitioned

    def one_model(score0, fu0, fmasks, keys, bags, active0, shrink_t,
                  params, layout, base_extras, meta, fix, gargs, forced,
                  idx):
        def body(carry, per):
            score, fu, act = carry
            fmask, kk, bag_i, i = per
            g, h = grad_fn(score, *gargs)
            ex = base_extras._replace(key=kk, feature_used=fu)
            if has_bag:
                # multiply in the gradient's native dtype FIRST (the
                # per-iteration host path's order), then cast: the mask is
                # exact 1.0/0.0 so this is also bit-equal to the serial
                # scan body's cast-then-train on unmasked gradients
                m = bag_i.astype(g.dtype)
                g = (g * m).astype(jnp.float32)
                h = (h * m).astype(jnp.float32)
                bag = bag_i
            else:
                g = g.astype(jnp.float32)
                h = h.astype(jnp.float32)
                bag = jnp.ones(n, bool)
            if use_part:
                arrays, fu2 = grow_tree_partitioned(
                    layout, g, h, bag, meta, params, fmask, fix, gc,
                    gw_global=gw, cat=cat, extras=ex, forced=forced)
            else:
                arrays, fu2 = grow_tree(
                    layout, g, h, bag, meta, params, fmask, fix, gc,
                    cat=cat, extras=ex, forced=forced)
            grew = arrays.num_leaves > 1
            upd = arrays.leaf_value.astype(jnp.float64)[
                arrays.row_leaf] * shrink_t
            score2 = score + jnp.where(act & grew, upd, 0.0)
            # frozen lanes emit 1-leaf stubs (host truncation discards
            # them) and keep their carries; a global-index-0 stub keeps
            # the lane live (reference keeps the constant tree)
            nl = jnp.where(act, arrays.num_leaves, jnp.int32(1))
            act2 = act & (grew | (i == 0))
            fu2 = jnp.where(act, fu2, fu)
            out = arrays._replace(row_leaf=jnp.zeros((0,), jnp.int32),
                                  num_leaves=nl)
            return (score2, fu2, act2), out

        (scoreK, fuK, actK), stacked = jax.lax.scan(
            body, (score0, fu0, active0), (fmasks, keys, bags, idx),
            length=k)
        return scoreK, fuK, actK, stacked

    # B and k are inferred from argument shapes — no static argnums, so
    # this jit contributes exactly one program per (bucket, k) shape and
    # the compile surface is the analytic ladder bound
    @jax.jit
    def run(layout, score0s, fu0s, fmasks, keys, bags, active0, shrinks,
            base_extras, meta, params, fix, gargs, forced, idx):
        vm = jax.vmap(
            one_model,
            in_axes=(0, 0, 0, 0, 0, 0, 0, 0,
                     None, None, None, None, None, None, None))
        return vm(score0s, fu0s, fmasks, keys, bags, active0, shrinks,
                  params, layout, base_extras, meta, fix, gargs, forced,
                  idx)

    cache[key] = run
    return run


def get_grad_program(learner, objective):
    """Vmapped gradient program: [B, N] scores -> ([B, N] g, [B, N] h) in
    the objective's native dtype (GOSS samples on the host from these)."""
    ds = learner.dataset
    cache = _cache(ds)
    key = ("grad", objective.static_fingerprint())
    fn = cache.get(key)
    if fn is not None:
        return fn
    telemetry.count("tree_learner::mm_programs", category="tree_learner")
    grad_fn = objective.grad_fn()

    @jax.jit
    def run(scores, gargs):
        return jax.vmap(lambda s: grad_fn(s, *gargs))(scores)

    cache[key] = run
    return run


def get_step_program(learner, objective, has_weight: bool):
    """Vmapped single-tree step from EXTERNAL gradients: the GOSS path.

    Serial GOSS never fuses iterations (its sampling needs |g*h| on the
    host each round), so its batched twin is a per-iteration program
    taking host-orchestrated per-model gradients, sample weights and bag
    masks. Mirrors GBDT._train_one_iter_fast's tree step exactly: the
    weight multiply happens in the gradient's native dtype and the
    grower performs the f32 cast internally.
    """
    ds = learner.dataset
    cache = _cache(ds)
    key = ("step", bool(has_weight), learner.grow_config,
           objective.static_fingerprint())
    fn = cache.get(key)
    if fn is not None:
        return fn
    telemetry.count("tree_learner::mm_programs", category="tree_learner")

    gc = learner.grow_config
    use_part = learner.use_partitioned
    cat, gw = learner.cat_layout, learner.gw_global
    from ..ops.grow import grow_tree, grow_tree_partitioned

    def one_model(score, g, h, w, bag, fmask, kk, fu, act, shrink_t,
                   params, layout, base_extras, meta, fix, forced, i):
        if has_weight:
            g2 = g * w
            h2 = h * w
        else:
            m = bag.astype(g.dtype)
            g2 = g * m
            h2 = h * m
        ex = base_extras._replace(key=kk, feature_used=fu)
        if use_part:
            arrays, fu2 = grow_tree_partitioned(
                layout, g2, h2, bag, meta, params, fmask, fix, gc,
                gw_global=gw, cat=cat, extras=ex, forced=forced)
        else:
            arrays, fu2 = grow_tree(
                layout, g2, h2, bag, meta, params, fmask, fix, gc,
                cat=cat, extras=ex, forced=forced)
        grew = arrays.num_leaves > 1
        upd = arrays.leaf_value.astype(jnp.float64)[
            arrays.row_leaf] * shrink_t
        score2 = score + jnp.where(act & grew, upd, 0.0)
        nl = jnp.where(act, arrays.num_leaves, jnp.int32(1))
        act2 = act & (grew | (i == 0))
        fu2 = jnp.where(act, fu2, fu)
        out = arrays._replace(row_leaf=jnp.zeros((0,), jnp.int32),
                              num_leaves=nl)
        return score2, fu2, act2, out

    @jax.jit
    def run(layout, scores, gs, hs, ws, bags, fmasks, keys, fus, active,
            shrinks, base_extras, meta, params, fix, forced, i):
        vm = jax.vmap(
            one_model,
            in_axes=(0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0,
                     None, None, None, None, None, None))
        return vm(scores, gs, hs, ws, bags, fmasks, keys, fus, active,
                  shrinks, params, layout, base_extras, meta, fix,
                  forced, i)

    cache[key] = run
    return run


def pad_lanes(b: int, bucket: int, tree):
    """Pad every [b, ...] leaf of ``tree`` to [bucket, ...] by replicating
    lane 0 (pad lanes train model 0 again; outputs are discarded)."""
    if b == bucket:
        return tree

    def pad(x):
        reps = jnp.repeat(x[:1], bucket - b, axis=0)
        return jnp.concatenate([x, reps], axis=0)

    return jax.tree.map(pad, tree)


def stack_members(values):
    """Stack a per-member list of pytrees along a new leading model axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *values)


def np_stack_members(values):
    return np.stack(values)
