"""Fused boosting iteration (PR 17): the cheap tier-1 pins.

The whole-iteration fusion folds the score update and the
gradient/hessian recompute into the per-tree compiled program
(ops/grow_persist.make_scan_driver), opening DART and RF to the device
fast path via per-tree weight vectors. This module pins the host-side
contracts that need no training run:

  * the ONE capability surface — `supports_fused_scan` and
    `persist_grad_mode` are derived views of `device_gradients()`,
    never independent flags;
  * the loud refusal when the config FORCES the fused path with a
    host-only objective (silent v1 fallback would diverge in launch
    count and, for quantized modes, in bits);
  * the stats-vector layout the drivers and the flush agree on
    (level_programs | fallback_splits | iter_launches | health...);
  * the perf-gate direction of the new `launches_per_iter` bench key.

The expensive halves — DART/RF bit-exact device-vs-host parity and the
launch-count pins on real training runs — live in test_level_grow.py
(slow-marked); the traced-program invariants (gradient kernels f64-free,
no host transfers between tree boundaries, payload aliasing) are the
`fused_iteration` auditor, exercised via test_analysis.py.
"""
from types import SimpleNamespace

import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.objectives.base import create_objective


def _obj(name, **extra):
    cfg = Config({"objective": name, "verbosity": -1, **extra})
    return create_objective(name, cfg)


# ---------------------------------------------------------------------------
# the one capability surface
# ---------------------------------------------------------------------------

def test_device_gradient_capability_is_one_surface():
    rng = np.random.RandomState(0)
    cases = [
        ("binary", {}, (rng.rand(64) > 0.5).astype(np.float64)),
        ("regression", {}, rng.rand(64)),
        ("multiclass", {"num_class": 3},
         (np.arange(64) % 3).astype(np.float64)),
    ]
    for name, extra, label in cases:
        obj = _obj(name, **extra)
        obj.init(SimpleNamespace(label=label, weight=None), len(label))
        dg = obj.device_gradients()
        assert dg is not None and dg[0] == "payload", name
        assert callable(dg[1]), name
        # derived views, not independent flags
        assert obj.supports_fused_scan, name
        assert obj.persist_grad_mode() == "payload", name
        assert obj.persist_grad_args() == (), name


def test_host_only_objective_reports_none_everywhere():
    """rank_xendcg's per-iteration randomization needs fresh host
    inputs; the one surface must say so consistently."""
    obj = _obj("rank_xendcg")
    assert obj.device_gradients() is None
    assert not obj.supports_fused_scan
    assert obj.persist_grad_mode() == "row"


def test_mape_has_no_latent_payload_kernel():
    """MAPE's weights are recomputed per tree from the residual scale —
    inheriting L2's label-only payload kernel would silently train the
    wrong model. The override must refuse it."""
    obj = _obj("mape")
    obj.init(SimpleNamespace(label=np.abs(np.random.RandomState(1)
                                          .rand(32)) + 1.0,
                             weight=None), 32)
    assert obj.payload_grad_fn() is None


def test_forced_persist_with_host_only_objective_refuses_loudly():
    from lightgbm_tpu.data.dataset import BinnedDataset
    from lightgbm_tpu.treelearner.serial import SerialTreeLearner
    from lightgbm_tpu.utils.log import LightGBMError

    rng = np.random.RandomState(3)
    X = rng.rand(256, 4)
    y = (rng.rand(256) > 0.5).astype(np.float64)
    cfg = Config({"objective": "binary", "num_leaves": 7, "max_bin": 63,
                  "verbosity": -1, "tpu_persist_scan": "force"})
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    learner = SerialTreeLearner(cfg, ds)
    with pytest.raises(LightGBMError, match="no device gradient"):
        learner.can_persist_scan(_obj("rank_xendcg"))


# ---------------------------------------------------------------------------
# stats layout
# ---------------------------------------------------------------------------

def test_driver_stats_layout():
    from lightgbm_tpu.ops.grow_persist import (STAT_FALLBACK,
                                               STAT_HEALTH0,
                                               STAT_ITER_LAUNCH,
                                               STAT_LEVELS, STATS_LEN)
    assert (STAT_LEVELS, STAT_FALLBACK, STAT_ITER_LAUNCH) == (0, 1, 2)
    # the health tail starts right after the launch slot; the flush
    # (serial.flush_level_stats) and both drivers index off these
    assert STAT_HEALTH0 == 3
    assert STATS_LEN > STAT_HEALTH0
