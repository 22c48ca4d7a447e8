"""Dataset and Booster: the user-facing core API.

TPU-native rebuild of python-package/lightgbm/basic.py. The reference binds
a C library via ctypes (basic.py:24, _load_lib); here Dataset wraps the
host-side BinnedDataset (data/dataset.py) whose binned matrix ships to TPU
HBM at Booster construction, and Booster drives the jitted boosting engine
(boosting/) directly — same surface, no C round-trips. Lazy construction
(_lazy_init, reference basic.py:868), reference-aligned validation binning
(set_reference / Dataset alignment, basic.py:730-1090), pandas and
categorical handling (basic.py:331-418) all follow the reference semantics.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from .config import Config, params_to_config, _METRIC_ALIASES
from .data.dataset import BinnedDataset
from .metrics import create_metric
from .objectives import create_objective
from .telemetry import events as telemetry_events
from .utils.log import LightGBMError, Log

try:
    import pandas as pd
    _PANDAS = True
except ImportError:  # pragma: no cover
    _PANDAS = False

try:
    from scipy import sparse as _sp
    _SCIPY = True
except ImportError:  # pragma: no cover
    _SCIPY = False


def _data_to_2d(data, feature_name="auto", categorical_feature="auto"):
    """Coerce input data to (float64 2D array, feature_names, cat_indices).

    Mirrors the pandas/categorical handling in reference basic.py:331-418
    (_data_from_pandas): category dtypes are codified, bad object columns
    rejected.
    """
    cat_idx: List[int] = []
    names: Optional[List[str]] = None
    if _PANDAS and isinstance(data, pd.DataFrame):
        names = [str(c) for c in data.columns]
        df = data.copy()
        auto_cat = categorical_feature == "auto"
        cat_names = ([] if auto_cat or categorical_feature is None
                     else list(categorical_feature))
        for i, col in enumerate(df.columns):
            if str(df[col].dtype) == "category":
                df[col] = df[col].cat.codes.astype(np.float64).replace(-1, np.nan) \
                    if hasattr(df[col].cat.codes, "replace") \
                    else df[col].cat.codes.astype(np.float64)
                if auto_cat:
                    cat_idx.append(i)
            if (not auto_cat) and (col in cat_names or i in cat_names):
                cat_idx.append(i)
        bad = [c for c in df.columns
               if df[c].dtype == object]
        if bad:
            raise LightGBMError(
                "DataFrame.dtypes for data must be int, float or bool. Did "
                "not expect the data types in the following fields: "
                + ", ".join(str(b) for b in bad))
        X = df.values.astype(np.float64)
    elif _SCIPY and _sp.issparse(data):
        X = np.asarray(data.todense(), dtype=np.float64)
    elif isinstance(data, list):
        X = np.asarray(data, dtype=np.float64)
    else:
        X = np.asarray(data, dtype=np.float64)
    if X.ndim == 1:
        X = X.reshape(-1, 1)
    if categorical_feature not in ("auto", None) and not cat_idx:
        for c in categorical_feature:
            if isinstance(c, int):
                cat_idx.append(c)
            elif names is not None and c in names:
                cat_idx.append(names.index(c))
    if feature_name not in ("auto", None):
        names = list(feature_name)
    return X, names, sorted(set(cat_idx))


def _label_from_pandas(label):
    if _PANDAS and isinstance(label, (pd.Series, pd.DataFrame)):
        return np.asarray(label).reshape(-1)
    return label


class Dataset:
    """Training/validation data container (reference basic.py:730)."""

    def __init__(self, data, label=None, reference: Optional["Dataset"] = None,
                 weight=None, group=None, init_score=None,
                 feature_name="auto", categorical_feature="auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = True, silent=False):
        self.data = data
        self.label = _label_from_pandas(label)
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params or {})
        self.free_raw_data = free_raw_data
        self._inner: Optional[BinnedDataset] = None
        self.used_indices = None
        self._predictor = None

    # -- laziness (reference _lazy_init, basic.py:868) -------------------
    def construct(self) -> "Dataset":
        if self._inner is not None:
            return self
        if self.data is None:
            raise LightGBMError(
                "Cannot construct Dataset since the raw data has been freed; "
                "set free_raw_data=False when creating the Dataset")
        # run record: binning from inside (children io::ToFloat64(copy),
        # io::FindBinAndGroup, io::PushMatrix(binning))
        with telemetry_events.scope("io::Construct", category="setup",
                                    always=True):
            return self._construct()

    def _construct(self) -> "Dataset":
        if isinstance(self.data, (str, bytes)):
            return self._construct_from_path(str(self.data))
        cfg = params_to_config(self.params)
        ref_inner = None
        if self.reference is not None:
            self.reference.construct()
            ref_inner = self.reference._inner
        if _SCIPY and _sp.issparse(self.data):
            # streaming CSR ingest: never densifies the full matrix
            # (dense-on-device is a TPU design choice; dense-on-host at
            # ingest would need ~n*features*8 bytes)
            cat_idx = (list(self.categorical_feature)
                       if isinstance(self.categorical_feature, (list, tuple))
                       else ())
            self._inner = BinnedDataset.from_sparse(
                self.data, cfg,
                categorical_features=cat_idx,
                label=self.label,
                weight=self.weight,
                group=self.group,
                init_score=self.init_score,
                feature_names=(list(self.feature_name)
                               if isinstance(self.feature_name, (list, tuple))
                               else None),
                reference=ref_inner,
            )
            self._raw_X = None if self.free_raw_data else self.data
            if self.free_raw_data:
                self.data = None
            return self
        with telemetry_events.scope("io::ToFloat64(copy)", category="io",
                                    always=True):
            X, names, cat_idx = _data_to_2d(self.data, self.feature_name,
                                            self.categorical_feature)
        self._inner = BinnedDataset.from_matrix(
            X, cfg,
            categorical_features=cat_idx,
            label=self.label,
            weight=self.weight,
            group=self.group,
            init_score=self.init_score,
            feature_names=names,
            reference=ref_inner,
        )
        self._raw_X = None if self.free_raw_data else X
        if self.free_raw_data:
            self.data = None
        return self

    def _construct_from_path(self, path: str) -> "Dataset":
        """File-path Dataset (reference Dataset('file') via
        LGBM_DatasetCreateFromFile): binary cache fast path
        (dataset_loader.cpp:179-274), two_round streaming, or one-round
        text load; save_binary writes <path>.bin for next time."""
        from .data.loader import load_text_file
        cfg = params_to_config(self.params)

        if not BinnedDataset.is_binary_file(path) \
                and BinnedDataset.is_binary_file(path + ".bin"):
            # CheckCanLoadFromBin probes <data>.bin (dataset_loader.cpp:179)
            path = path + ".bin"
        if BinnedDataset.is_binary_file(path) and self.reference is not None:
            # a binary cache is only usable for a reference-aligned set when
            # its binning layout matches the reference's exactly (e.g. it
            # was saved FROM a reference-aligned validation set)
            self.reference.construct()
            cached = BinnedDataset.from_binary(path)
            if cached.layout_matches(self.reference._inner):
                self._inner = cached
                self._apply_field_overrides()
                self.data = None if self.free_raw_data else self.data
                return self
            if path != str(self.data):
                # auto-probed <data>.bin next to a text file: re-bin the text
                Log.warning("Ignoring binary cache %s: its bin layout does "
                            "not match the reference dataset" % path)
                path = str(self.data)
            else:
                raise LightGBMError(
                    "Binary dataset %s was binned standalone and does not "
                    "match the reference's bin layout; recreate it from the "
                    "raw text/matrix" % path)
        if BinnedDataset.is_binary_file(path):
            self._inner = BinnedDataset.from_binary(path)
            self._apply_field_overrides()
            self.data = None if self.free_raw_data else self.data
            return self
        cat_idx = (list(self.categorical_feature)
                   if isinstance(self.categorical_feature, (list, tuple))
                   else ())
        ref_inner = None
        if self.reference is not None:
            self.reference.construct()
            ref_inner = self.reference._inner
        if cfg.two_round and ref_inner is None:
            self._inner = BinnedDataset.from_text_two_round(
                path, cfg, categorical_features=cat_idx)
            self._apply_field_overrides()
        else:
            loaded = load_text_file(path, cfg)
            self._inner = BinnedDataset.from_matrix(
                loaded.X, cfg, categorical_features=cat_idx,
                label=(self.label if self.label is not None
                       else loaded.label),
                weight=self.weight if self.weight is not None
                else loaded.weight,
                group=self.group if self.group is not None else loaded.group,
                init_score=(self.init_score if self.init_score is not None
                            else loaded.init_score),
                feature_names=loaded.feature_names,
                reference=ref_inner)
        if cfg.save_binary and not path.endswith(".bin"):
            self._inner.save_binary(path + ".bin")
        self.data = None if self.free_raw_data else self.data
        return self

    def _apply_field_overrides(self) -> None:
        """User-supplied fields take precedence over whatever the loaded
        dataset (binary cache / parsed file) carried."""
        md = self._inner.metadata
        if self.label is not None:
            md.set_label(self.label)
        if self.weight is not None:
            md.set_weight(self.weight)
        if self.group is not None:
            md.set_query(self.group)
        if self.init_score is not None:
            md.set_init_score(self.init_score)

    @property
    def constructed(self) -> bool:
        return self._inner is not None

    # -- field access (reference set_field/get_field) --------------------
    def set_label(self, label) -> "Dataset":
        self.label = _label_from_pandas(label)
        if self._inner is not None:
            self._inner.metadata.set_label(self.label)
        return self

    def set_weight(self, weight) -> "Dataset":
        self.weight = weight
        if self._inner is not None:
            self._inner.metadata.set_weight(weight)
        return self

    def set_group(self, group) -> "Dataset":
        self.group = group
        if self._inner is not None:
            self._inner.metadata.set_query(group)
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = init_score
        if self._inner is not None:
            self._inner.metadata.set_init_score(init_score)
        return self

    def set_reference(self, reference: "Dataset") -> "Dataset":
        if self._inner is not None and self.reference is not reference:
            raise LightGBMError("Cannot set reference after constructed")
        self.reference = reference
        return self

    def set_feature_name(self, feature_name) -> "Dataset":
        if feature_name not in (None, "auto"):
            self.feature_name = feature_name
        return self

    def set_categorical_feature(self, categorical_feature) -> "Dataset":
        if categorical_feature not in (None, "auto"):
            if self._inner is not None:
                Log.warning("categorical_feature set after construction is "
                            "ignored")
            else:
                self.categorical_feature = categorical_feature
        return self

    def get_label(self):
        if self._inner is not None:
            return self._inner.metadata.label
        return self.label

    def get_weight(self):
        if self._inner is not None:
            return self._inner.metadata.weight
        return self.weight

    def get_group(self):
        if self._inner is not None and \
                self._inner.metadata.query_boundaries is not None:
            return np.diff(self._inner.metadata.query_boundaries)
        return self.group

    def get_init_score(self):
        if self._inner is not None:
            return self._inner.metadata.init_score
        return self.init_score

    def get_field(self, field_name):
        return {"label": self.get_label, "weight": self.get_weight,
                "group": self.get_group,
                "init_score": self.get_init_score}[field_name]()

    def set_field(self, field_name, data):
        return {"label": self.set_label, "weight": self.set_weight,
                "group": self.set_group,
                "init_score": self.set_init_score}[field_name](data)

    # -- info ------------------------------------------------------------
    def num_data(self) -> int:
        self.construct()
        return self._inner.num_data

    def num_feature(self) -> int:
        self.construct()
        return self._inner.num_total_features

    def get_feature_name(self) -> List[str]:
        self.construct()
        return list(self._inner.feature_names)

    def subset(self, used_indices, params=None) -> "Dataset":
        """Row subset sharing this dataset's BinMappers (reference
        Dataset.subset, basic.py:1330)."""
        self.construct()
        X = self._raw_X if getattr(self, "_raw_X", None) is not None else None
        if X is None:
            raise LightGBMError("subset requires free_raw_data=False")
        idx = np.asarray(used_indices)
        n = self.num_data()
        # recompute per-fold query sizes from the parent group vector so
        # ranking cv folds keep their query structure
        group_sub = None
        parent_group = self.get_group()
        if parent_group is not None and len(parent_group):
            qid = np.repeat(np.arange(len(parent_group)),
                            np.asarray(parent_group, dtype=np.int64))
            qid_sub = qid[idx]
            if len(qid_sub):
                change = np.flatnonzero(np.diff(qid_sub) != 0)
                bounds = np.concatenate([[0], change + 1, [len(qid_sub)]])
                group_sub = np.diff(bounds)
        # slice init_score rows ([n], [n*k] class-major, or [n, k])
        init_sub = None
        isc = self.get_init_score()
        if isc is not None:
            isc = np.asarray(isc)
            if isc.ndim == 2:
                init_sub = isc[idx]
            elif isc.size == n:
                init_sub = isc[idx]
            elif isc.size % n == 0:
                init_sub = isc.reshape(-1, n)[:, idx].reshape(-1)
            else:
                raise LightGBMError(
                    "init_score size %d is not compatible with num_data %d"
                    % (isc.size, n))
        sub = Dataset(X[idx],
                      label=None if self.label is None else
                      np.asarray(self.label)[idx],
                      reference=self,
                      weight=None if self.weight is None else
                      np.asarray(self.weight)[idx],
                      group=group_sub,
                      init_score=init_sub,
                      params=params or self.params,
                      free_raw_data=self.free_raw_data)
        sub.used_indices = idx
        return sub

    def add_features_from(self, other: "Dataset") -> "Dataset":
        """Append other's features to this Dataset (reference
        Dataset.add_features_from / LGBM_DatasetAddFeaturesFrom)."""
        self.construct()
        other.construct()
        self._inner.add_features_from(other._inner)
        if getattr(self, "_raw_X", None) is not None \
                and getattr(other, "_raw_X", None) is not None:
            self._raw_X = np.concatenate([self._raw_X, other._raw_X], axis=1)
        else:
            self._raw_X = None
        return self

    def _update_params(self, params) -> "Dataset":
        if params:
            self.params.update(params)
        return self

    def _reverse_update_params(self) -> "Dataset":
        return self

    def _set_predictor(self, predictor) -> "Dataset":
        self._predictor = predictor
        return self


class Booster:
    """The trained model handle (reference basic.py:1704)."""

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None, silent=False):
        from .boosting import create_boosting
        self.params = dict(params or {})
        self.best_iteration = -1
        self.best_score: Dict = {}
        self.train_set = None
        self._train_data_name = "training"
        self._valid_sets: List[Dataset] = []
        self.name_valid_sets: List[str] = []

        if train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError("Training data should be Dataset instance, "
                                "met %s" % type(train_set).__name__)
            cfg = params_to_config(self.params)
            train_set._update_params(self.params)
            train_set.construct()
            self.train_set = train_set
            self._cfg = cfg
            inner = train_set._inner
            objective = create_objective(cfg.objective, cfg)
            if objective is not None:
                objective.init(inner.metadata, inner.num_data)
            self._booster = create_boosting(cfg.boosting)
            self._booster.init(cfg, inner, objective)
            self._metrics = self._make_metrics(cfg, inner)
            for m in self._metrics:
                m.init(inner.metadata, inner.num_data)
        elif model_file is not None:
            with open(model_file) as f:
                model_str = f.read()
            self._init_from_string(model_str)
        elif model_str is not None:
            self._init_from_string(model_str)
        else:
            raise TypeError("Need at least one training dataset or model "
                            "file or model string to create Booster instance")

    def _init_from_string(self, model_str: str) -> None:
        from .boosting import create_boosting
        self._cfg = params_to_config(self.params)
        self._booster = create_boosting("gbdt")
        self._booster.config = self._cfg
        self._booster.load_model_from_string(model_str)
        self._metrics = []

    @staticmethod
    def _make_metrics(cfg: Config, inner: BinnedDataset):
        """Config metric list; falls back to the objective's own metric
        (reference config.cpp metric default resolution)."""
        names = list(cfg.metric)
        if not names:
            default = _METRIC_ALIASES.get(cfg.objective)
            if default and default != "none":
                names = [default]
        out = []
        for n in names:
            if n in ("none",):
                continue
            m = create_metric(n, cfg)
            if m is not None:
                out.append(m)
        return out

    # ------------------------------------------------------------------
    def reset_parameter(self, params: dict) -> "Booster":
        """Change training-control parameters of the Booster (reference
        Booster.reset_parameter, python-package basic.py /
        LGBM_BoosterResetParameter): routes through GBDT.reset_config,
        which warns on structurally-fixed keys."""
        if params:
            self._booster.reset_config(params)
            self.params.update(params)
        return self

    # ------------------------------------------------------------------
    def refit(self, data, label, decay_rate: float = 0.9,
              **kwargs) -> "Booster":
        """Refit the existing model's leaf values to new data
        (reference Booster.refit, basic.py:2614 / GBDT::RefitTree): tree
        structures are kept; each leaf output is re-estimated from the new
        data's gradients and blended by decay_rate."""
        import copy
        self._booster._materialize_pending()
        if not self._booster.models:
            raise LightGBMError("Cannot refit an empty model")
        X, _, _ = _data_to_2d(data)
        params = dict(self.params)
        params.pop("input_model", None)
        new_set = Dataset(X, label, params=params)
        new_booster = Booster(params=params, train_set=new_set)
        self._booster._materialize_pending()
        new_booster._booster.models = [copy.deepcopy(t)
                                       for t in self._booster.models]
        new_booster._booster.refit(np.ascontiguousarray(X, np.float64),
                                   decay_rate=float(decay_rate))
        return new_booster

    def set_train_data_name(self, name: str) -> "Booster":
        self._train_data_name = name
        return self

    def add_valid(self, data: Dataset, name: str) -> "Booster":
        if not isinstance(data, Dataset):
            raise TypeError("Validation data should be Dataset instance, "
                            "met %s" % type(data).__name__)
        if data is not self.train_set:
            # the training set itself may ride as a named valid set (cv's
            # eval_train_metric folds); it is its own reference
            data.set_reference(self.train_set)
        data.construct()
        self._valid_sets.append(data)
        self.name_valid_sets.append(name)
        cfg = self._cfg
        metrics = self._make_metrics(cfg, data._inner)
        self._booster.add_valid_dataset(data._inner, metrics, name)
        return self

    # ------------------------------------------------------------------
    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting round (reference basic.py:2089). Returns True when
        no further splits were possible (training finished)."""
        if train_set is not None and train_set is not self.train_set:
            raise LightGBMError("Replacing train_set is not yet supported "
                                "on device_type=tpu")
        if fobj is None:
            return self._booster.train_one_iter(None, None)
        if self._cfg.boosting == "rf":
            raise LightGBMError("RF mode does not support custom objective")
        preds = self._booster.train_score.score_host()
        grad, hess = fobj(preds, self.train_set)
        return self.__boost(grad, hess)

    def __boost(self, grad, hess) -> bool:
        grad = np.ascontiguousarray(grad, dtype=np.float32)
        hess = np.ascontiguousarray(hess, dtype=np.float32)
        ntpi = self._booster.num_tree_per_iteration
        n = self._booster.num_data
        if grad.size != n * ntpi:
            raise ValueError(
                "Lengths of gradients (%d) and expected (%d) don't match"
                % (grad.size, n * ntpi))
        return self._booster.train_one_iter(grad, hess)

    def rollback_one_iter(self) -> "Booster":
        self._booster.rollback_one_iter()
        return self

    @property
    def current_iteration(self):
        return self._booster.current_iteration

    def num_trees(self) -> int:
        return len(self._booster.models)

    def num_model_per_iteration(self) -> int:
        return self._booster.num_tree_per_iteration

    def num_feature(self) -> int:
        return self._booster.max_feature_idx + 1

    # ------------------------------------------------------------------
    def _eval_one(self, score: np.ndarray, metrics, data_name: str,
                  feval=None, dataset: Optional[Dataset] = None):
        out = []
        obj = self._booster.objective
        for m in metrics:
            vals = m.eval(score, obj)
            for name, v in zip(m.names, vals):
                out.append((data_name, name, v,
                            m.factor_to_bigger_better > 0))
        if feval is not None:
            ntpi = self._booster.num_tree_per_iteration
            n = score.size // ntpi
            preds = score if ntpi == 1 else score
            res = feval(preds, dataset)
            if isinstance(res, tuple):
                res = [res]
            for name, v, is_higher_better in res:
                out.append((data_name, name, v, is_higher_better))
        return out

    def eval_train(self, feval=None):
        score = self._booster.train_score.score_host()
        return self._eval_one(score, self._metrics, self._train_data_name,
                              feval, self.train_set)

    def eval_valid(self, feval=None):
        out = []
        for i, (su, metrics) in enumerate(zip(self._booster.valid_score,
                                              self._booster.valid_metrics)):
            out.extend(self._eval_one(su.score_host(), metrics,
                                      self.name_valid_sets[i], feval,
                                      self._valid_sets[i]
                                      if i < len(self._valid_sets) else None))
        return out

    def eval(self, data: Dataset, name: str, feval=None):
        if data is self.train_set:
            return self.eval_train(feval)
        for i, vs in enumerate(self._valid_sets):
            if data is vs:
                su = self._booster.valid_score[i]
                return self._eval_one(su.score_host(),
                                      self._booster.valid_metrics[i], name,
                                      feval, data)
        raise LightGBMError("Data for eval must be train or valid set")

    # ------------------------------------------------------------------
    def predict(self, data, num_iteration: Optional[int] = None,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False, data_has_header: bool = False,
                is_reshape: bool = True, start_iteration: int = 0, **kwargs):
        if _SCIPY and _sp.issparse(data):
            # stream CSR row blocks through the dense predictor instead of
            # densifying the whole matrix (reference PredictForCSR,
            # src/c_api.cpp, walks rows sparsely); each block densifies to
            # ~32MB so predict memory stays bounded regardless of n
            csr = data.tocsr()
            step = max(1, (32 << 20) // max(int(csr.shape[1]) * 8, 1))
            if csr.shape[0] > step:
                outs = [self.predict(
                    np.asarray(csr[i:i + step].todense(), dtype=np.float64),
                    num_iteration=num_iteration, raw_score=raw_score,
                    pred_leaf=pred_leaf, pred_contrib=pred_contrib,
                    data_has_header=data_has_header, is_reshape=is_reshape,
                    start_iteration=start_iteration, **kwargs)
                    for i in range(0, int(csr.shape[0]), step)]
                return np.concatenate(outs, axis=0)
        X, _, _ = _data_to_2d(data)
        # reference LGBM_BoosterPredict* shape guard (predict_disable_
        # shape_check): feature-count mismatch is fatal unless disabled
        nf_model = self._booster.max_feature_idx + 1
        if X.shape[1] != nf_model and not bool(kwargs.get(
                "predict_disable_shape_check",
                self.params.get("predict_disable_shape_check", False))):
            raise LightGBMError(
                "The number of features in data (%d) is not the same as "
                "it was in training data (%d).\nYou can set "
                "predict_disable_shape_check=true to discard this error, "
                "but please be aware what you are doing." % (X.shape[1],
                                                             nf_model))
        if num_iteration is None:
            num_iteration = (self.best_iteration
                             if self.best_iteration > 0 else -1)
        # inference device selection (predict/ subsystem): kwarg wins over
        # the Booster params; default cpu keeps the numpy walk
        device = str(kwargs.get(
            "predict_device",
            self.params.get("predict_device", "cpu"))).lower()
        if pred_leaf:
            return self._booster.predict_leaf_index(
                X, start_iteration, num_iteration, device=device)
        if pred_contrib:
            if device == "tpu":
                # native TreeSHAP stays host-side (logged, counter-pinned)
                from .telemetry import events as _ev
                _ev.count("predict::fallback_pred_contrib", 1,
                          category="predict")
                Log.info("predict_device=tpu does not cover pred_contrib; "
                         "using the host TreeSHAP path")
            return self._booster.predict_contrib(
                X, start_iteration, num_iteration)
        early_stop = None
        # the reference only honors pred_early_stop where accuracy is not
        # required (binary/multiclass objectives, NeedAccuratePrediction)
        obj = getattr(self._booster, "objective", None)
        es_ok = obj is not None and getattr(obj, "name", "") in (
            "binary", "multiclass", "multiclassova")
        if es_ok and kwargs.get(
                "pred_early_stop", self.params.get("pred_early_stop",
                                                   False)):
            early_stop = (
                int(kwargs.get("pred_early_stop_freq",
                               self.params.get("pred_early_stop_freq", 10))),
                float(kwargs.get("pred_early_stop_margin",
                                 self.params.get("pred_early_stop_margin",
                                                 10.0))))
        if early_stop is not None and device == "tpu":
            # the margin early exit is a host-walk optimization; honoring
            # it beats ignoring it silently
            from .telemetry import events as _ev
            _ev.count("predict::fallback_early_stop", 1, category="predict")
            Log.info("pred_early_stop is host-only; predict_device=tpu "
                     "request served by the host predictor")
            device = "cpu"
        return self._booster.predict(X, raw_score=raw_score,
                                     start_iteration=start_iteration,
                                     num_iteration=num_iteration,
                                     early_stop=early_stop, device=device)

    # ------------------------------------------------------------------
    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0) -> str:
        if num_iteration is None:
            num_iteration = (self.best_iteration
                             if self.best_iteration > 0 else -1)
        return self._booster.save_model_to_string(start_iteration,
                                                  num_iteration)

    def save_model(self, filename: str,
                   num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> "Booster":
        with open(filename, "w") as f:
            f.write(self.model_to_string(num_iteration, start_iteration))
        return self

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> dict:
        if num_iteration is None:
            num_iteration = (self.best_iteration
                             if self.best_iteration > 0 else -1)
        return self._booster.dump_model(start_iteration, num_iteration)

    def model_from_string(self, model_str: str, verbose=True) -> "Booster":
        self._init_from_string(model_str)
        return self

    # ------------------------------------------------------------------
    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        imp = self._booster.feature_importance(
            importance_type, iteration if iteration else 0)
        if importance_type == "split":
            return imp.astype(np.int32)
        return imp

    def feature_name(self) -> List[str]:
        return list(self._booster.feature_names)

    # -- pickling -------------------------------------------------------
    def __getstate__(self):
        state = {"params": self.params,
                 "model_str": self.model_to_string(num_iteration=-1),
                 "best_iteration": self.best_iteration,
                 "best_score": self.best_score}
        return state

    def __setstate__(self, state):
        self.params = state["params"]
        self.best_iteration = state["best_iteration"]
        self.best_score = state["best_score"]
        self.train_set = None
        self._train_data_name = "training"
        self._valid_sets = []
        self.name_valid_sets = []
        self._init_from_string(state["model_str"])

    def __copy__(self):
        return self.__deepcopy__(None)

    def __deepcopy__(self, _):
        model_str = self.model_to_string(num_iteration=-1)
        return Booster(model_str=model_str)
