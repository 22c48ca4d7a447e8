"""Training and cross-validation entry points.

TPU-native rebuild of the reference python-package surface: `train`
(python-package/lightgbm/engine.py:18) and `cv` (:375) with the same
observable contract — callback staging/timing via CallbackEnv, alias
precedence for round counts and early stopping, train-set evaluation when
the train set appears among the valid sets, `best_score`/`best_iteration`
population, and stratified/group fold construction. The implementation is
organized around a CallbackRegistry (staged, order-sorted dispatch) and an
EvalPlan (which datasets get evaluated each round, and under what names)
rather than the reference's inline loops; the per-round work itself —
gradients, tree growth, score updates — runs as jitted device programs
behind Booster.update.
"""
from __future__ import annotations

import collections
import copy
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import callback
from .basic import Booster, Dataset
from .telemetry import events as telemetry_events
from .utils.log import LightGBMError, Log

_ROUND_COUNT_KEYS = (
    "num_iterations", "num_iteration", "n_iter", "num_tree", "num_trees",
    "num_round", "num_rounds", "num_boost_round", "n_estimators")
_STOP_ROUND_KEYS = ("early_stopping_round", "early_stopping_rounds",
                    "early_stopping", "n_iter_no_change")


def _alias_override(params: Dict[str, Any], keys, fallback):
    """Pop the first matching alias out of `params`; params win over the
    keyword argument (reference alias precedence, engine.py:119-155)."""
    for key in keys:
        if key in params:
            Log.warning("Found `%s` in params. Will use it instead of "
                        "argument" % key)
            return int(params.pop(key))
    return fallback


class _CallbackRegistry:
    """Staged callback dispatch.

    Callbacks carry an `order` (implicit ones set their own; user-supplied
    ones default to negative offsets so they fire ahead of implicit ones)
    and a `before_iteration` flag selecting the stage. Dispatch is a stable
    sort by order within each stage.
    """

    def __init__(self, user_callbacks=None):
        self._pre: List = []
        self._post: List = []
        user_callbacks = list(user_callbacks or ())
        for offset, cb in enumerate(user_callbacks):
            cb.__dict__.setdefault("order", offset - len(user_callbacks))
        # identical objects registered twice fire once
        for cb in dict.fromkeys(user_callbacks):
            self.add(cb)

    def add(self, cb) -> None:
        stage = (self._pre if getattr(cb, "before_iteration", False)
                 else self._post)
        stage.append(cb)

    def seal(self) -> None:
        self._pre.sort(key=lambda cb: getattr(cb, "order", 0))
        self._post.sort(key=lambda cb: getattr(cb, "order", 0))

    @property
    def has_pre_stage(self) -> bool:
        return bool(self._pre)

    def fire_pre(self, env: "callback.CallbackEnv") -> None:
        for cb in self._pre:
            cb(env)

    def fire_post(self, env: "callback.CallbackEnv") -> None:
        """May raise callback.EarlyStopException."""
        for cb in self._post:
            cb(env)


class _EvalPlan(collections.namedtuple(
        "_EvalPlan", ["eval_train", "train_name", "attached"])):
    """Which datasets each round evaluates: the train set itself (when the
    caller listed it among valid_sets) plus the attached held-out sets."""

    @classmethod
    def build(cls, train_set: Dataset, valid_sets, valid_names):
        if valid_sets is None:
            return cls(False, "training", [])
        if isinstance(valid_sets, Dataset):
            valid_sets = [valid_sets]
        if isinstance(valid_names, str):
            valid_names = [valid_names]
        names = list(valid_names) if valid_names is not None else []
        eval_train = False
        train_name = "training"
        attached: List[Tuple[Dataset, str]] = []
        for pos, ds in enumerate(valid_sets):
            label = names[pos] if pos < len(names) else "valid_%d" % pos
            if ds is train_set:
                eval_train = True
                if pos < len(names):
                    train_name = label
            else:
                if not isinstance(ds, Dataset):
                    raise TypeError("Training only accepts Dataset object")
                attached.append((ds, label))
        return cls(eval_train, train_name, attached)

    def attach(self, booster: Booster, params: Dict[str, Any],
               train_set: Dataset) -> None:
        if self.eval_train:
            booster.set_train_data_name(self.train_name)
        for ds, label in self.attached:
            ds._update_params(params).set_reference(train_set)
            booster.add_valid(ds, label)

    def evaluate(self, booster: Booster, feval) -> List:
        out: List = []
        if self.eval_train:
            out.extend(booster.eval_train(feval))
        out.extend(booster.eval_valid(feval))
        return out

    @property
    def active(self) -> bool:
        return self.eval_train or bool(self.attached)


def _load_init_model(init_model) -> Optional[str]:
    if init_model is None:
        return None
    if isinstance(init_model, Booster):
        # an early-stopped Booster carries its rollback point in
        # best_iteration; continued training must resume from there
        # (model_to_string's default honors it) — the old explicit
        # num_iteration=-1 grafted the over-trained tail trees while
        # best_iteration kept pointing at the truncated model
        return init_model.model_to_string(num_iteration=None)
    with open(init_model) as fh:
        return fh.read()


def _graft_init_model(booster: Booster, model_str: str,
                      train_set: Dataset) -> int:
    """Continued training (reference engine.py:159-165 feeds an
    _InnerPredictor whose cached scores seed the new booster): prepend the
    init model's trees and push their binned-walk predictions into the
    fresh score updater."""
    stump = Booster(model_str=model_str)
    inner = booster._booster
    ntpi = inner.num_tree_per_iteration
    for pos, tree in enumerate(stump._booster.models):
        # loaded trees carry only real-valued thresholds; bind them to the
        # new dataset's bins before the binned walk
        tree.bind_to_dataset(train_set._inner)
        inner.train_score.add_score_np(
            tree.predict_binned(train_set._inner), pos % ntpi)
    inner.models = stump._booster.models + inner.models
    inner.num_init_iteration = stump.current_iteration
    inner.iter = 0
    return stump.current_iteration


def _distributed_raw(ds, cfg, categorical_feature="auto"):
    """(X, label, weight, cat_indices) host arrays of a not-yet-
    constructed Dataset for per-rank sharding; file-backed Datasets load
    through the text reader, matrices through the same pandas/categorical
    coercion the single-host path uses (basic._data_to_2d)."""
    import numpy as np
    from .utils.log import LightGBMError
    if isinstance(ds.data, (str, bytes)):
        from .main import load_text_file
        loaded = load_text_file(str(ds.data), cfg)
        return loaded.X, loaded.label, loaded.weight, [], loaded.group
    if ds.data is None:
        raise LightGBMError(
            "num_machines > 1 needs the raw data to shard rows; pass the "
            "matrix/file to Dataset (free_raw_data has no effect here)")
    if hasattr(ds.data, "tocsr"):
        raise LightGBMError(
            "num_machines > 1 does not accept scipy sparse input yet: "
            "each rank shards dense rows (parallel/multihost.py); pass a "
            "dense matrix or a data file")
    from .basic import _data_to_2d
    X, _names, cat_idx = _data_to_2d(ds.data, ds.feature_name,
                                     categorical_feature)
    y = None if ds.label is None else np.asarray(ds.label, dtype=np.float64)
    w = None if ds.weight is None else np.asarray(ds.weight,
                                                 dtype=np.float64)
    return X, y, w, cat_idx, ds.group


def _serialization_stump(cfg, ds):
    """A serialization-only GBDT populated with just the fields
    save_model_to_string reads (a full init would rebuild a tree learner
    + device score state per rank only to be discarded). Built ONCE per
    training run — the objective init can be O(shard) host work
    (lambdarank's inverse-max-DCG tables) — then reused by every
    snapshot-hook invocation and the final Booster assembly by swapping
    the model list (_serialize_distributed_model)."""
    from .boosting.gbdt import GBDT
    from .objectives import create_objective
    inner = GBDT()
    obj = create_objective(cfg.objective, cfg)
    obj.init(ds.metadata, ds.num_data)
    inner.config = cfg
    inner.objective = obj
    inner.num_class = int(cfg.num_class)
    inner.num_tree_per_iteration = getattr(obj, "num_model_per_iteration", 1)
    inner.max_feature_idx = ds.num_total_features - 1
    inner.feature_names = list(ds.feature_names)
    inner.feature_infos = [GBDT._feature_info(m) for m in ds.bin_mappers]
    inner.monotone_constraints = list(cfg.monotone_constraints)
    return inner


def _serialize_distributed_model(stump, models, num_init_iteration=0):
    """Model text from the (identical-on-every-rank) tree list."""
    stump.models = list(models)
    stump.num_init_iteration = int(num_init_iteration)
    stump.iter = len(stump.models)
    return stump.save_model_to_string()


def _train_distributed(params, train_set, num_boost_round, valid_sets,
                       fobj=None, feval=None, init_model=None,
                       early_stopping_rounds=None, callbacks=None,
                       categorical_feature="auto", learning_rates=None,
                       keep_training_booster=False):
    """num_machines > 1 from the Python API — the reference reaches this
    through params (machines/local_listen_port -> Network::Init inside
    Booster, basic.py set_network / network.cpp); here every participating
    process runs the same program, ranks wire up via jax.distributed, and
    training shards rows over the global mesh (parallel/multihost.py).
    Returns a prediction-ready Booster holding the full model on every
    rank. Custom objectives and callbacks are not supported."""
    from .basic import Booster, params_to_config
    from .parallel.multihost import (init_network, shard_rows,
                                     train_multihost)
    from .utils.log import LightGBMError, Log
    if fobj is not None:
        raise LightGBMError("custom objectives are not supported with "
                            "num_machines > 1")
    if feval is not None:
        raise LightGBMError("custom eval functions are not supported with "
                            "num_machines > 1 (metrics aggregate "
                            "count-weighted across ranks)")
    if callbacks:
        Log.warning("callbacks are ignored with num_machines > 1")
    if learning_rates is not None:
        raise LightGBMError("learning_rates schedules are not supported "
                            "with num_machines > 1; set learning_rate")
    if keep_training_booster:
        Log.warning("keep_training_booster is ignored with "
                    "num_machines > 1 (the returned Booster is "
                    "prediction-ready on every rank)")
    # same params precedence as the single-host path: Dataset-level
    # params (max_bin, binning knobs) overlaid by train() params
    merged = dict(getattr(train_set, "params", None) or {})
    merged.update(params)
    cfg = params_to_config(merged)
    if early_stopping_rounds:
        cfg.early_stopping_round = int(early_stopping_rounds)
    # categorical features: the kwarg wins, else the Dataset's own
    cat = categorical_feature
    if cat == "auto":
        cat = getattr(train_set, "categorical_feature", "auto")
    rank = init_network(cfg)
    X, y, w, cat_idx, grp = _distributed_raw(
        train_set, cfg, "auto" if cat == "auto" else cat)
    if cat not in ("auto", None):
        if any(isinstance(c, str) for c in cat):
            raise LightGBMError("categorical_feature by NAME needs a "
                                "DataFrame; pass column indices with "
                                "num_machines > 1")
        cat_idx = sorted(set(int(c) for c in cat) | set(cat_idx))
    # world=1 is a legal mesh here: the small end of an elastic resume
    # (engine.train routes a matching single-host run into this driver)
    world = max(int(cfg.num_machines), 1)
    if grp is not None:
        # ranking: shard whole queries, never splitting one across ranks
        from .parallel.multihost import shard_queries
        if bool(cfg.pre_partition):
            import numpy as np
            idx, glocal = np.arange(len(X)), np.asarray(grp, np.int64)
        else:
            idx, glocal = shard_queries(grp, rank, world)
    else:
        idx, glocal = shard_rows(len(X), rank, world,
                                 bool(cfg.pre_partition)), None
    Xv = yv = gvalid = None
    if valid_sets:
        others = [v for v in valid_sets if v is not train_set]
        if len(others) < len(valid_sets):
            Log.warning("train-set metrics are not reported with "
                        "num_machines > 1; the train entry of valid_sets "
                        "is ignored")
        if len(others) > 1:
            Log.warning("num_machines > 1 evaluates only the FIRST "
                        "validation set; %d more ignored"
                        % (len(others) - 1))
        vset = others[0] if others else None
        if vset is not None:
            Xv_all, yv_all, _, _, vgrp = _distributed_raw(vset, cfg)
            if yv_all is None:
                raise LightGBMError("the validation Dataset needs a label "
                                    "with num_machines > 1")
            if vgrp is not None:
                from .parallel.multihost import shard_queries
                if bool(cfg.pre_partition):
                    import numpy as np
                    vidx = np.arange(len(Xv_all))
                    gvalid = np.asarray(vgrp, np.int64)
                else:
                    vidx, gvalid = shard_queries(vgrp, rank, world)
            else:
                vidx = shard_rows(len(Xv_all), rank, world,
                                  bool(cfg.pre_partition))
            Xv, yv = Xv_all[vidx], yv_all[vidx]
    # ---- resilience: per-rank auto-resume + snapshot stream ----------
    # checkpoints on the distributed path are model-only (kind=model);
    # resume re-enters the init-model machinery below, so every rank's
    # score shard is reconstructed from the checkpointed model's raw
    # predictions rather than recomputed from scratch
    from .resilience import reshard as resilience_reshard
    from .resilience import restore as resilience_restore
    from .resilience.checkpoint import (CheckpointWriter, array_fingerprint,
                                        config_hash)
    y_local = None if y is None else y[idx]
    # the dataset-GLOBAL fingerprint (pre-shard rows): the identity that
    # survives a mesh resize, unlike the shard-local one below
    global_fp = array_fingerprint(X, y)
    resume_iter = 0
    ck_text = None
    es_resume = None
    ck_orig_init = None
    resume_man = None
    if str(cfg.checkpoint_dir):
        man = resilience_reshard.load_manifest(str(cfg.checkpoint_dir))
        if resilience_reshard.manifest_matches(man, config_hash(cfg),
                                               global_fp):
            # a matching manifest pins this run's binning for EVERY
            # generation: once a run has hopped meshes, even a same-mesh
            # resume must keep the SOURCE bin boundaries — re-deriving
            # them from this mesh's local samples would silently break
            # the bit-exact continuation
            resume_man = man
        if resume_man is not None and int(man.get("world", 1)) != world:
            # this run's snapshots, written by a DIFFERENT mesh size:
            # elastic resume (agreement on iteration + source layout)
            found = resilience_reshard.find_elastic(cfg, rank, world,
                                                    global_fp)
            if found is not None:
                resume_iter, ck_text, ck_meta, _man = found
                es_resume = ck_meta.get("early_stopping")
                ck_orig_init = int(ck_meta.get("n_init", 0))
                telemetry_events.count("resilience::reshard_rows",
                                       len(idx), category="resilience")
        else:
            found = resilience_restore.find_distributed(
                cfg, rank, X[idx], y_local, global_fp=global_fp)
            if found is not None:
                resume_iter, ck_text, ck_meta = found
                es_resume = ck_meta.get("early_stopping")
                # iterations of the ORIGINAL init model (if any) embedded
                # in the checkpoint — propagated across resume chains so
                # the round-space <-> tree-list accounting stays right
                ck_orig_init = int(ck_meta.get("n_init", 0))
    model_str = _load_init_model(init_model)
    if ck_text is not None:
        if model_str is not None:
            Log.warning("auto-resume from checkpoint_dir overrides "
                        "init_model")
        model_str = ck_text
        # num_boost_round is the TOTAL target when resuming the same run
        num_boost_round = max(int(num_boost_round) - resume_iter, 0)
    # continued training: seed every rank's score shard with the init
    # model's raw predictions (the distributed analog of
    # _graft_init_model's binned-walk score push), then prepend its trees
    init_stump = None
    isc_local = isc_valid = None
    if model_str is not None:
        init_stump = Booster(model_str=model_str)
        ntpi0 = init_stump._booster.num_tree_per_iteration
        raw = init_stump._booster.predict_raw(X[idx])      # [n, K]
        isc_local = raw[:, 0] if ntpi0 == 1 else raw.T
        if Xv is not None:
            vraw = init_stump._booster.predict_raw(Xv)
            isc_valid = vraw[:, 0] if ntpi0 == 1 else vraw.T
    init_models = (list(init_stump._booster.models)
                   if init_stump is not None else [])
    n_init = init_stump.current_iteration if init_stump is not None else 0
    # round space counts iterations beyond the ORIGINAL init model; on a
    # resume the checkpoint model already contains round-space trees, so
    # the original offset comes from the checkpoint meta, not n_init
    orig_init_iters = ck_orig_init if ck_text is not None else n_init
    stump_cache = {}

    def _stump(ds_):
        if "inner" not in stump_cache:
            stump_cache["inner"] = _serialization_stump(cfg, ds_)
        return stump_cache["inner"]

    snapshot_hook = None
    if str(cfg.checkpoint_dir) and int(cfg.snapshot_freq) > 0:
        writer = CheckpointWriter(
            str(cfg.checkpoint_dir), keep=int(cfg.checkpoint_keep),
            cfg_hash=config_hash(cfg), rank=rank,
            fingerprint=array_fingerprint(X[idx], y_local),
            global_fingerprint=global_fp, world=world)
        assignment = ("pre_partition" if bool(cfg.pre_partition)
                      else "query_blocks" if grp is not None
                      else "round_robin")
        manifest_state = {"written": False}

        def snapshot_hook(it_done, new_trees, ds_, es_state=None):
            # every rank holds the identical trees; each writes its own
            # rank-tagged snapshot (no shared-filesystem assumption); the
            # early-stopping patience clock and the original-init offset
            # ride the snapshot meta
            extra = {"n_init": orig_init_iters}
            if es_state:
                extra["early_stopping"] = es_state
            writer.write_model_text(
                _serialize_distributed_model(
                    _stump(ds_), init_models + list(new_trees),
                    num_init_iteration=n_init),
                it_done, extra_meta=extra)
            # the mesh-layout manifest rides beside the shards (once):
            # world size, row assignment, the global fingerprint, and
            # the global BinMappers — everything a DIFFERENT mesh size
            # needs to resume this run bit-exactly. Written AFTER the
            # first snapshot of this generation: a manifest must never
            # describe a world no snapshot in the directory has yet (a
            # crash in that window would brick the next resume)
            if not manifest_state["written"]:
                resilience_reshard.ensure_manifest(
                    writer.directory,
                    resilience_reshard.build_manifest(
                        config_hash(cfg), global_fp, world, len(X),
                        ds_.bin_mappers, assignment=assignment,
                        group_sizes=grp))
                manifest_state["written"] = True
    result_info = {}
    trees, _mappers, ds, _score = train_multihost(
        cfg, X[idx], y_local,
        num_rounds=int(num_boost_round),
        categorical_features=tuple(cat_idx),
        weight_local=None if w is None else w[idx],
        X_valid=Xv, y_valid=yv,
        group_local=glocal, group_valid=gvalid,
        init_score_local=isc_local, init_score_valid=isc_valid,
        start_iteration=resume_iter, snapshot_hook=snapshot_hook,
        es_resume=es_resume, result_info=result_info,
        mappers_override=(resilience_reshard.manifest_mappers(resume_man)
                          if resume_man is not None else None))
    models_all = init_models + trees
    best_iter = result_info.get("early_stop_best_iter")
    if best_iter is not None:
        # a resumed patience clock rolled back into the restored model:
        # keep the original init model plus best_iter round-space rounds
        keep = ((orig_init_iters + best_iter)
                * int(result_info["trees_per_iteration"]))
        models_all = models_all[:keep]
    return Booster(
        model_str=_serialize_distributed_model(
            _stump(ds), models_all, num_init_iteration=n_init),
        params=dict(params))


@telemetry_events.train_root
def train(params: Dict[str, Any], train_set: Dataset,
          num_boost_round: int = 100,
          valid_sets: Optional[List[Dataset]] = None,
          valid_names: Optional[List[str]] = None,
          fobj=None, feval=None, init_model=None,
          feature_name="auto", categorical_feature="auto",
          early_stopping_rounds: Optional[int] = None,
          evals_result: Optional[Dict] = None,
          verbose_eval=True, learning_rates=None,
          keep_training_booster: bool = False, callbacks=None) -> Booster:
    """Train a booster (reference engine.py:18-290)."""
    if not isinstance(train_set, Dataset):
        raise TypeError("Training only accepts Dataset object")
    params = copy.deepcopy(params)
    num_boost_round = _alias_override(params, _ROUND_COUNT_KEYS,
                                      num_boost_round)
    early_stopping_rounds = _alias_override(params, _STOP_ROUND_KEYS,
                                            early_stopping_rounds)
    if num_boost_round <= 0:
        raise ValueError("num_boost_round should be greater than zero.")
    from .basic import params_to_config
    cfg0 = params_to_config(params)
    # configure before the num_machines split so tpu_telemetry/telemetry_out
    # params also activate the collective spans on the distributed path
    # (multihost scans, allreduce/allgather DCN time)
    telemetry_events.configure_from_config(cfg0)
    # resilience knobs ride the same pattern: the fault plan and the
    # collective retry policy apply to whichever path runs below
    from .resilience import faults as resilience_faults
    from .resilience import retry as resilience_retry
    resilience_faults.configure_from_config(cfg0)
    resilience_retry.configure_from_config(cfg0)
    # crash flight recorder: armed whenever this run can die in a way
    # worth a postmortem (telemetry on / fault plan / multihost); dumps
    # land next to the checkpoints (telemetry/flight.py)
    from .telemetry import flight as telemetry_flight
    telemetry_flight.configure_from_config(cfg0)
    # numerics sentinel: install the tpu_health_abort policy and reset
    # the run-scoped numerics::*/health::* registry state (the flight-
    # ring pattern — an aborted run's split margins must not leak into
    # this run's report or collapse baseline)
    from .telemetry import health as telemetry_health
    telemetry_health.configure_from_config(cfg0)
    # elastic resume onto world=1: a single-host run whose checkpoint_dir
    # holds a MATCHING multi-host run (mesh manifest: same config hash +
    # dataset-global fingerprint, world > 1) continues through the
    # distributed driver — the same sharded grower / stateless-hash
    # bagging the source mesh used, which is what keeps the resumed
    # model bit-exact (resilience/reshard.py)
    elastic_world = None
    if int(cfg0.num_machines) <= 1 and str(cfg0.checkpoint_dir):
        from .resilience import reshard as resilience_reshard
        from .resilience.checkpoint import array_fingerprint, config_hash
        _man = resilience_reshard.load_manifest(str(cfg0.checkpoint_dir))
        if (_man is not None and int(_man.get("world", 1)) > 1
                and resilience_reshard.manifest_matches(
                    _man, config_hash(cfg0))):
            try:
                # fingerprint-only load; _train_distributed re-loads with
                # the caller's categorical coercion (reusing this pass
                # could change cat_idx) — the double load is confined to
                # elastic-resume startup
                _X0, _y0, _w0, _c0, _g0 = _distributed_raw(train_set, cfg0)
                if resilience_reshard.manifest_matches(
                        _man, config_hash(cfg0),
                        array_fingerprint(_X0, _y0)):
                    elastic_world = int(_man["world"])
                else:
                    Log.warning(
                        "checkpoint_dir holds an elastic world=%d run of "
                        "this config but a DIFFERENT dataset; staying on "
                        "the single-host driver" % int(_man["world"]))
            except LightGBMError:
                # raw rows unavailable (freed / sparse input): the
                # distributed driver could not train anyway
                Log.warning("checkpoint_dir holds an elastic manifest but "
                            "the raw rows are unavailable for resharding; "
                            "staying on the single-host driver")
    if int(cfg0.num_machines) > 1 or elastic_world is not None:
        if elastic_world is not None:
            Log.info("Elastic resume: continuing a world=%d run on "
                     "world=1 through the distributed driver"
                     % elastic_world)
        if evals_result is not None:
            # NOTE: no local Log import here — a function-local binding
            # would shadow the module-level Log for the whole function
            Log.warning("evals_result is not populated with "
                        "num_machines > 1")
        try:
            return _train_distributed(
                params, train_set, num_boost_round,
                valid_sets, fobj=fobj, feval=feval,
                init_model=init_model,
                early_stopping_rounds=early_stopping_rounds,
                callbacks=callbacks,
                categorical_feature=categorical_feature,
                learning_rates=learning_rates,
                keep_training_booster=keep_training_booster)
        except LightGBMError as exc:
            # this rank's postmortem; kill / collective-failure sites
            # dump with a sharper reason and mark the exception so a
            # generic re-dump doesn't overwrite it (an EARLIER recovered
            # timeout's dump must not suppress this death's record)
            if not getattr(exc, "_flight_dumped", False):
                telemetry_flight.dump(
                    "train_error:%s" % type(exc).__name__)
            raise
        finally:
            if telemetry_events.enabled():
                from .telemetry.export import maybe_export
                maybe_export()
    if fobj is not None:
        params["objective"] = "none"

    train_set._update_params(params) \
             .set_feature_name(feature_name) \
             .set_categorical_feature(categorical_feature)
    plan = _EvalPlan.build(train_set, valid_sets, valid_names)

    registry = _CallbackRegistry(callbacks)
    if verbose_eval is True:
        registry.add(callback.print_evaluation())
    elif isinstance(verbose_eval, int) and not isinstance(verbose_eval, bool):
        registry.add(callback.print_evaluation(verbose_eval))
    es_cb = None
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        es_cb = callback.early_stopping(
            early_stopping_rounds, params.get("first_metric_only", False),
            verbose=bool(verbose_eval))
        registry.add(es_cb)
    if learning_rates is not None:
        registry.add(callback.reset_parameter(learning_rate=learning_rates))
    if evals_result is not None:
        registry.add(callback.record_evaluation(evals_result))
    from .telemetry.monitor import TrainingMonitor
    monitor = None
    if telemetry_events.enabled():
        # post-iteration CallbackEnv consumer: per-iteration wall time,
        # phase buckets, leaf counts, memory watermarks, recompile counts
        monitor = TrainingMonitor()
        registry.add(monitor)
    saver = None
    if int(cfg0.snapshot_freq) > 0:
        # the reference's snapshot_freq (config.h, alias save_period):
        # here it gates full training-state checkpoints into
        # checkpoint_dir (resilience/), written post-iteration AFTER the
        # early-stopping callback so a stopping round never snapshots
        if str(cfg0.checkpoint_dir):
            from .resilience.checkpoint import (CheckpointWriter,
                                                TrainingSaver, config_hash)
            saver = TrainingSaver(
                CheckpointWriter(str(cfg0.checkpoint_dir),
                                 keep=int(cfg0.checkpoint_keep),
                                 cfg_hash=config_hash(cfg0)),
                int(cfg0.snapshot_freq),
                # the engine-made early-stopping trackers ride the
                # snapshot (user-supplied callbacks stay outside it)
                extra_state_fn=(
                    (lambda: {"early_stopping": es_cb.state_dict()})
                    if es_cb is not None else None))
            registry.add(saver)
        else:
            Log.warning("snapshot_freq=%d has no checkpoint_dir=; set one "
                        "to write resume checkpoints (the CLI train task "
                        "keeps writing model-only snapshots next to "
                        "output_model)" % int(cfg0.snapshot_freq))

    registry.seal()

    booster = Booster(params=params, train_set=train_set)
    model_str = _load_init_model(init_model)
    first_round = 0
    last_round = num_boost_round
    restored = None
    if str(cfg0.checkpoint_dir):
        # auto-resume: newest valid snapshot matching this config +
        # dataset; corruption falls back, a foreign run starts fresh
        from .resilience import restore as resilience_restore
        restored = resilience_restore.find_restorable(cfg0,
                                                      train_set._inner)
    if restored is not None:
        if model_str is not None:
            Log.warning("auto-resume from checkpoint_dir overrides "
                        "init_model")
        first_round = resilience_restore.resume_booster(booster, restored)
        # num_boost_round is the TOTAL target of NEW rounds when resuming
        # the same run: a snapshotted run that itself started from an
        # init model counts its grafted iterations in first_round, so the
        # target is offset by the restored num_init_iteration
        last_round = max(
            num_boost_round + booster._booster.num_init_iteration,
            first_round)
        es_state = resilience_restore.extra_state(restored,
                                                  "early_stopping")
        if es_state and es_cb is not None:
            # the patience clock and rollback point survive the resume
            es_cb.load_state_dict(es_state)
    elif model_str is not None:
        first_round = _graft_init_model(booster, model_str, train_set)
        last_round = first_round + num_boost_round
    plan.attach(booster, params, train_set)
    booster.best_iteration = 0
    # with no per-iteration host work (no before-iter callbacks, no eval
    # sets, no custom objective), the booster may fuse iterations into one
    # jitted multi-tree scan (one device dispatch per K trees)
    inner = getattr(booster, "_booster", None)
    if inner is not None:
        inner.allow_batch = (not registry.has_pre_stage
                             and not plan.active and fobj is None)
        inner.planned_rounds = last_round - first_round
        if saver is not None:
            # fused batches must end exactly on snapshot boundaries
            inner.snapshot_stride = int(cfg0.snapshot_freq)

    def env_for(round_no: int, evals) -> callback.CallbackEnv:
        return callback.CallbackEnv(
            model=booster, params=params, iteration=round_no,
            begin_iteration=first_round, end_iteration=last_round,
            evaluation_result_list=evals)

    final_evals: List = []
    fault_plan = resilience_faults.active()
    try:
        for round_no in range(first_round, last_round):
            if fault_plan is not None:
                # deterministic preemption: raises TrainingKilled before
                # this iteration trains (checkpoints up to here are on
                # disk; check_kill writes its own flight dump)
                fault_plan.check_kill(round_no)
            registry.fire_pre(env_for(round_no, None))
            booster.update(fobj=fobj)
            final_evals = plan.evaluate(booster, feval) if plan.active \
                else []
            try:
                registry.fire_post(env_for(round_no, final_evals))
            except callback.EarlyStopException as stop:
                booster.best_iteration = stop.best_iteration + 1
                final_evals = stop.best_score
                break
    except LightGBMError as exc:
        # a failed run leaves its flight record even when the failure
        # site didn't dump one itself; sites that did (kill, collective
        # exhaustion) mark the exception so their sharper reason wins
        if not getattr(exc, "_flight_dumped", False):
            telemetry_flight.dump("train_error:%s" % type(exc).__name__)
        raise

    booster.best_score = collections.defaultdict(collections.OrderedDict)
    for entry in final_evals:
        booster.best_score[entry[0]][entry[1]] = entry[2]
    if monitor is not None:
        booster._telemetry_monitor = monitor
        if inner is not None:
            # flush the async pipeline so the trace's device_wait bucket
            # covers this run's trees (telemetry-on only: the off path
            # keeps the pipeline open exactly as before)
            inner._materialize_pending()
        from .telemetry.export import maybe_export
        maybe_export()   # tpu_telemetry=trace -> Chrome trace + metrics
    return booster


# ---------------------------------------------------------------------------
# cross-validation (reference engine.py:293-610)
# ---------------------------------------------------------------------------

class CVBooster:
    """Ensemble of per-fold boosters (reference _CVBooster, engine.py:296):
    attribute access fans out to every fold and returns the list of
    results."""

    def __init__(self):
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def append(self, booster: Booster) -> None:
        self.boosters.append(booster)

    def __getattr__(self, name):
        def fan_out(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]
        return fan_out


def _sklearn_available() -> bool:
    try:
        import sklearn  # noqa: F401
        return True
    except ImportError:
        return False


def _query_memberships(full_data: Dataset) -> np.ndarray:
    """Row -> query id from the dataset's group boundaries (for group-aware
    fold splitting)."""
    sizes = np.asarray(full_data.get_group(), dtype=np.int64)
    return np.repeat(np.arange(len(sizes)), sizes)


def _fold_indices(full_data: Dataset, folds, nfold: int,
                  params: Dict[str, Any], seed: int, stratified: bool,
                  shuffle: bool):
    """Yield (train_idx, test_idx) pairs.

    Explicit `folds` win (an iterable of index pairs or an sklearn-style
    splitter). Otherwise: ranking objectives split whole queries
    (GroupKFold), stratified classification uses StratifiedKFold, and the
    default is an (optionally shuffled) nfold partition of the row range.
    """
    n = full_data.num_data()
    if folds is not None:
        if hasattr(folds, "split"):
            sizes = full_data.get_group()
            groups = (_query_memberships(full_data) if sizes is not None
                      else np.zeros(n, dtype=np.int64))
            return folds.split(X=np.zeros(n), y=full_data.get_label(),
                               groups=groups)
        if not hasattr(folds, "__iter__"):
            raise AttributeError(
                "folds should be a generator or iterator of (train_idx, "
                "test_idx) tuples or scikit-learn splitter object")
        return folds

    objective = next((params[k] for k in ("objective", "application", "app")
                      if k in params), "")
    if objective in ("lambdarank", "rank_xendcg"):
        if not _sklearn_available():
            raise LightGBMError("scikit-learn is required for ranking cv")
        from sklearn.model_selection import GroupKFold
        return GroupKFold(n_splits=nfold).split(
            X=np.zeros(n), groups=_query_memberships(full_data))
    if stratified:
        if not _sklearn_available():
            raise LightGBMError("scikit-learn is required for stratified cv")
        from sklearn.model_selection import StratifiedKFold
        return StratifiedKFold(n_splits=nfold, shuffle=shuffle,
                               random_state=seed).split(
            X=np.zeros(n), y=full_data.get_label())
    order = (np.random.RandomState(seed).permutation(n) if shuffle
             else np.arange(n))
    held_out = np.array_split(order, nfold)
    return ((np.concatenate(held_out[:k] + held_out[k + 1:]), held_out[k])
            for k in range(nfold))


def _build_fold_boosters(full_data: Dataset, folds, nfold: int,
                         params: Dict[str, Any], seed: int, fpreproc,
                         stratified: bool, shuffle: bool,
                         eval_train_metric: bool) -> CVBooster:
    ensemble = CVBooster()
    for train_idx, test_idx in _fold_indices(full_data, folds, nfold, params,
                                             seed, stratified, shuffle):
        fit_part = full_data.subset(sorted(train_idx))
        held_part = full_data.subset(sorted(test_idx))
        fold_params = params
        if fpreproc is not None:
            fit_part, held_part, fold_params = fpreproc(
                fit_part, held_part, params.copy())
        member = Booster(fold_params, fit_part)
        if eval_train_metric:
            member.add_valid(fit_part, "train")
        member.add_valid(held_part, "valid")
        ensemble.append(member)
    return ensemble


def _pool_fold_evals(per_fold: List[List], eval_train_metric: bool):
    """Mean/std across folds for each (dataset, metric) series
    (reference engine.py:354-372): returns entries shaped like a booster
    eval record plus the cross-fold standard deviation."""
    series = collections.OrderedDict()
    higher_better = {}
    for fold_entries in per_fold:
        for ds_name, metric_name, value, is_higher in fold_entries:
            key = ("%s %s" % (ds_name, metric_name) if eval_train_metric
                   else "valid %s" % metric_name)
            higher_better[key] = is_higher
            series.setdefault(key, []).append(value)
    return [("cv_agg", key, float(np.mean(vals)), higher_better[key],
             float(np.std(vals))) for key, vals in series.items()]


def cv(params: Dict[str, Any], train_set: Dataset, num_boost_round: int = 100,
       folds=None, nfold: int = 5, stratified: bool = True,
       shuffle: bool = True, metrics=None, fobj=None, feval=None,
       init_model=None, feature_name="auto", categorical_feature="auto",
       early_stopping_rounds: Optional[int] = None, fpreproc=None,
       verbose_eval=None, show_stdv: bool = True, seed: int = 0,
       callbacks=None, eval_train_metric: bool = False,
       return_cvbooster: bool = False):
    """Cross-validation (reference engine.py:375-610)."""
    if not isinstance(train_set, Dataset):
        raise TypeError("Training only accepts Dataset object")
    params = copy.deepcopy(params)
    num_boost_round = _alias_override(params, _ROUND_COUNT_KEYS,
                                      num_boost_round)
    early_stopping_rounds = _alias_override(params, _STOP_ROUND_KEYS,
                                            early_stopping_rounds)
    if num_boost_round <= 0:
        raise ValueError("num_boost_round should be greater than zero.")
    if fobj is not None:
        params["objective"] = "none"
    if metrics is not None:
        params["metric"] = metrics

    train_set._update_params(params) \
             .set_feature_name(feature_name) \
             .set_categorical_feature(categorical_feature)
    if train_set.free_raw_data:
        # cv needs subsetting: keep the raw matrix
        train_set.free_raw_data = False

    # fold indices may come from a one-shot generator: materialize once so
    # the device fast path and the host fold loop see the same folds
    fold_pairs = list(_fold_indices(train_set, folds, nfold, params, seed,
                                    stratified, shuffle))

    registry = _CallbackRegistry(callbacks)
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        registry.add(callback.early_stopping(
            early_stopping_rounds, params.get("first_metric_only", False),
            verbose=False))
    if verbose_eval is True:
        registry.add(callback.print_evaluation(show_stdv=show_stdv))
    elif isinstance(verbose_eval, int) and not isinstance(verbose_eval, bool):
        registry.add(callback.print_evaluation(verbose_eval, show_stdv))
    registry.seal()

    from .multimodel.cv import maybe_device_cv
    res = maybe_device_cv(params, train_set, num_boost_round, fold_pairs,
                          registry, eval_train_metric, fobj, feval,
                          fpreproc, return_cvbooster)
    if res is not None:
        return res

    ensemble = _build_fold_boosters(train_set, fold_pairs, nfold, params,
                                    seed, fpreproc, stratified, shuffle,
                                    eval_train_metric)

    def env_for(round_no: int, evals) -> callback.CallbackEnv:
        return callback.CallbackEnv(
            model=ensemble, params=params, iteration=round_no,
            begin_iteration=0, end_iteration=num_boost_round,
            evaluation_result_list=evals)

    history = collections.defaultdict(list)
    for round_no in range(num_boost_round):
        registry.fire_pre(env_for(round_no, None))
        per_fold = []
        for member in ensemble.boosters:
            member.update(fobj=fobj)
        for member in ensemble.boosters:
            entries: List = []
            if eval_train_metric:
                entries.extend(member.eval_train(feval))
            entries.extend(member.eval_valid(feval))
            per_fold.append(entries)
        pooled = _pool_fold_evals(per_fold, eval_train_metric)
        for _, key, mean, _, std in pooled:
            history[key + "-mean"].append(mean)
            history[key + "-stdv"].append(std)
        try:
            registry.fire_post(env_for(round_no, pooled))
        except callback.EarlyStopException as stop:
            ensemble.best_iteration = stop.best_iteration + 1
            for key in history:
                history[key] = history[key][:ensemble.best_iteration]
            break
    if return_cvbooster:
        history["cvbooster"] = ensemble
    return dict(history)
