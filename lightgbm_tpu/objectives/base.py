"""Objective function interface + factory.

TPU-native rebuild of the reference objective layer
(include/LightGBM/objective_function.h, factory
src/objective/objective_function.cpp:15-53). Per-row (grad, hess) math runs
as one jitted vectorized function over the whole score vector — the TPU
equivalent of the reference's OpenMP loops — while the scalar decisions
(BoostFromScore, leaf renewal percentiles) stay host-side numpy, mirroring
where the reference computes them (on scalars / per-leaf subsets).
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import numpy as np

from ..utils.log import Log

# reference include/LightGBM/meta.h:51
K_EPSILON = 1e-15


class ObjectiveFunction:
    """Base objective (objective_function.h).

    Subclasses set `name` and implement `grad_fn()` returning a pure
    function (score, label, weight) -> (grad, hess) traced by jit once.
    `score` is [num_data] for single-model objectives and
    [num_class, num_data] for multiclass (reference layout: class-major,
    gbdt.cpp grad buffer is num_data * num_tree_per_iteration).
    """

    name = "none"

    def __init__(self, config):
        self.config = config
        self.num_data = 0
        self.label: Optional[np.ndarray] = None
        self.weight: Optional[np.ndarray] = None

    # -- lifecycle ------------------------------------------------------
    def init(self, metadata, num_data: int) -> None:
        self.num_data = num_data
        self.label = metadata.label
        self.weight = metadata.weight

    # -- behavior flags (objective_function.h) --------------------------
    @property
    def num_model_per_iteration(self) -> int:
        return 1

    @property
    def num_predict_one_row(self) -> int:
        return 1

    @property
    def is_constant_hessian(self) -> bool:
        return False

    @property
    def is_renew_tree_output(self) -> bool:
        return False

    def device_gradients(self):
        """THE capability surface for the fused boosting scan: the
        device-side gradient kernel as (mode, fn), or None when this
        objective is host-only. mode selects the scan driver's fill
        contract — 'payload' (label-only, fastest; also the
        K-tree-per-iteration snapshot fill), 'pos' (payload-order through
        the row-id row, lambdarank), 'row' (full row-order round trip
        through the objective's standard grad_fn). Objectives whose
        gradients need fresh per-iteration HOST inputs (rank_xendcg's
        randomization) override this to return None — the traced
        inputs of the compiled K-iteration program are fixed for the
        whole batch. `supports_fused_scan` derives from this; the two
        flags are one surface."""
        if getattr(self, "num_model_per_iteration", 1) > 1:
            fn = self.payload_grad_fn_multi()
            return ("payload", fn) if fn is not None else None
        fn = self.payload_grad_fn()
        if fn is not None:
            return ("payload", fn)
        fn = self.payload_pos_fn()
        if fn is not None:
            return ("pos", fn)
        return ("row", self.grad_fn())

    @property
    def supports_fused_scan(self) -> bool:
        """Derived view of device_gradients() — kept for the booster's
        batch gate; never override this, override device_gradients."""
        return self.device_gradients() is not None

    @property
    def average_output(self) -> bool:
        """RF sets this through boosting, not the objective (kept for parity
        with ObjectiveFunction::IsAverageOutput used by ScoreUpdater)."""
        return False

    def class_need_train(self, class_id: int) -> bool:
        return True

    # -- main hooks -----------------------------------------------------
    def grad_fn(self) -> Callable:
        """Return pure (score, *device_args) -> (grad, hess); jax code.
        device_args defaults to (label, weight) — see `_grad_args`."""
        raise NotImplementedError

    def payload_grad_fn(self):
        """Pure (score, label) -> (grad, hess) for the persistent-payload
        scan (ops/grow_persist.py), where the LABEL rides in the payload and
        no other per-row inputs exist. Returns None when this objective
        needs more than the label (weights, query groups, per-iteration
        host inputs) — those configurations take the v1 path."""
        return None

    def payload_grad_fn_multi(self):
        """K-tree-per-iteration analog of payload_grad_fn: pure
        (scores [K, NP], label, cls) -> (grad, hess) for class `cls`,
        where `scores` is the payload's per-class score block (snapshot
        at iteration start). None when unsupported."""
        return None

    def payload_pos_fn(self):
        """Pure (score, rid, live, *pos_args) -> (grad, hess) ALL in
        payload order, for objectives whose gradients need global row
        structure (lambdarank's query groups) but can reach it through the
        carried row-id payload row instead of a full row-order round trip.
        None when unsupported (the persist driver then falls back to
        row-order mode)."""
        return None

    def persist_grad_mode(self) -> str:
        """Which gradient mode the persist scan driver should use —
        a view of device_gradients(); 'row' for host-only objectives
        (they never reach the driver, can_persist_scan gates them)."""
        dg = self.device_gradients()
        return dg[0] if dg is not None else "row"

    def persist_grad_args(self) -> tuple:
        """Extra traced args for the persist driver's gradient fill,
        matching persist_grad_mode ('payload' mode takes none)."""
        mode = self.persist_grad_mode()
        if mode == "payload":
            return ()
        if mode == "pos":
            return self._pos_grad_args()
        return self._grad_args()

    def _grad_args(self):
        """Device arrays bound as extra args of the jitted grad function."""
        import jax.numpy as jnp
        label = jnp.asarray(self.label) if self.label is not None else None
        weight = jnp.asarray(self.weight) if self.weight is not None else None
        return (label, weight)

    def get_gradients(self, score):
        """score (device array) -> (grad, hess) on device, jit-compiled."""
        if getattr(self, "_jit_fn", None) is None:
            import jax
            self._jit_fn = jax.jit(self.grad_fn())
            self._jit_args = self._grad_args()
        return self._jit_fn(score, *self._jit_args)

    def boost_from_score(self, class_id: int) -> float:
        """Initial score (BoostFromScore); host-side."""
        return 0.0

    def convert_output(self, raw: np.ndarray) -> np.ndarray:
        """Raw scores -> user-facing predictions (ConvertOutput)."""
        return raw

    def renew_tree_output(self, pred_in_leaf: np.ndarray,
                          label_in_leaf: np.ndarray,
                          weight_in_leaf: Optional[np.ndarray]) -> float:
        """New leaf output from the leaf's rows (RenewTreeOutput)."""
        raise NotImplementedError

    def static_fingerprint(self) -> tuple:
        """Hashable digest of every scalar the grad_fn CLOSURE bakes in
        (sigmoid, class weights, alpha, need_train flags, ...). Compiled-
        program caches keyed on this stay valid across objective instances
        with equal hyperparameters while instances that differ in any
        scalar get their own compilation. Device arrays (label, weight,
        masks) are excluded — they are traced arguments, not constants."""
        items = []
        for k, v in sorted(vars(self).items()):
            if k == "config":
                continue
            if isinstance(v, (np.number, np.bool_)):
                items.append((k, v.item()))
            elif isinstance(v, (int, float, bool, str, bytes, type(None))):
                items.append((k, v))
            elif isinstance(v, (list, tuple)) and all(
                    isinstance(x, (int, float, bool, str, np.number))
                    for x in v):
                items.append((k, tuple(
                    x.item() if isinstance(x, np.number) else x for x in v)))
        return (type(self).__name__, tuple(items))

    def to_string(self) -> str:
        """Model-file objective string (ToString)."""
        return self.name

    def __str__(self) -> str:
        return self.to_string()


# ---------------------------------------------------------------------------
# percentile helpers — exact reference semantics
# (PercentileFun / WeightedPercentileFun, src/objective/regression_objective.hpp:18-90)
# ---------------------------------------------------------------------------

def percentile(data: np.ndarray, alpha: float) -> float:
    """Reference PercentileFun: interpolated percentile computed from the top."""
    data = np.asarray(data, dtype=np.float64)
    n = len(data)
    if n == 0:
        return 0.0
    if n <= 1:
        return float(data[0])
    s = np.sort(data)[::-1]  # descending
    float_pos = (1.0 - alpha) * n
    pos = int(float_pos)
    if pos < 1:
        return float(s[0])
    if pos >= n:
        return float(s[-1])
    bias = float_pos - pos
    v1 = float(s[pos - 1])
    v2 = float(s[pos])
    return v1 - (v1 - v2) * bias


def weighted_percentile(data: np.ndarray, weight: np.ndarray,
                        alpha: float) -> float:
    """Reference WeightedPercentileFun (stable sort + weighted cdf walk)."""
    data = np.asarray(data, dtype=np.float64)
    weight = np.asarray(weight, dtype=np.float64)
    n = len(data)
    if n == 0:
        return 0.0
    if n <= 1:
        return float(data[0])
    order = np.argsort(data, kind="stable")
    cdf = np.cumsum(weight[order])
    threshold = cdf[-1] * alpha
    pos = int(np.searchsorted(cdf, threshold, side="right"))
    pos = min(pos, n - 1)
    if pos == 0 or pos == n - 1:
        return float(data[order[pos]])
    v1 = float(data[order[pos - 1]])
    v2 = float(data[order[pos]])
    if cdf[pos + 1] - cdf[pos] >= 1.0:
        return float((threshold - cdf[pos]) / (cdf[pos + 1] - cdf[pos])
                     * (v2 - v1) + v1)
    return v2


# ---------------------------------------------------------------------------
# factory (objective_function.cpp:15-53)
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, type] = {}


def register(cls):
    _REGISTRY[cls.name] = cls
    return cls


def create_objective(name: str, config) -> Optional[ObjectiveFunction]:
    """ObjectiveFunction::CreateObjectiveFunction. Returns None for 'none'
    (custom objective driven from the binding layer, like the reference)."""
    # late imports populate the registry
    from . import binary, multiclass, rank, regression, xentropy  # noqa: F401
    if name in ("none", "null", "custom", "na", ""):
        return None
    if name not in _REGISTRY:
        Log.fatal("Unknown objective type name: %s" % name)
    return _REGISTRY[name](config)


def parse_objective_string(s: str, config) -> Optional[ObjectiveFunction]:
    """Rebuild an objective from a model-file string like
    'binary sigmoid:1' (reference CreateObjectiveFunction(str) overload)."""
    parts = s.strip().split()
    if not parts:
        return None
    name = parts[0]
    for tok in parts[1:]:
        if ":" in tok:
            k, v = tok.split(":", 1)
            if k == "sigmoid":
                config.sigmoid = float(v)
            elif k == "num_class":
                config.num_class = int(v)
        elif tok == "sqrt":
            config.reg_sqrt = True
    return create_objective(name, config)
