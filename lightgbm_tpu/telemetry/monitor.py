"""Per-iteration training monitor.

A CallbackEnv consumer (``order``/``before_iteration`` attributes like
every other callback in :mod:`lightgbm_tpu.callback`) that records one
dict per boosting iteration:

  * ``wall`` — host wall time since the previous iteration boundary;
  * ``buckets`` — per-category host-seconds deltas (boosting /
    tree_learner / ops / io / eval / device_wait / collective / compile)
    from the span registry. Under the async fast path most device work is
    pipelined, so the honest per-iteration decomposition is launch +
    gradient + the device_wait bucket at sync points; op-level
    histogram/split/partition attribution on the chip comes from the
    xplane profile (``python -m lightgbm_tpu.profile``);
  * ``trees_materialized`` / ``last_num_leaves`` — model growth (pending
    async trees show up once a sync point materializes them);
  * ``compiles`` — XLA backend recompiles observed during the iteration;
  * ``memory`` — every local device's ``memory_stats()`` bytes_in_use /
    peak watermark, one dict a device (``events.device_memory_stats``),
    when the backend reports them (TPU does; CPU returns nothing).

Attach it explicitly via ``callbacks=[TrainingMonitor()]`` or let
``engine.train`` attach one automatically when ``tpu_telemetry`` is on.
Records accumulate on the instance (``.records``) and in the registry
(:func:`events.record_iteration`) for the JSONL metrics export.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

from . import events


class TrainingMonitor:
    """Per-iteration telemetry recorder (CallbackEnv protocol)."""

    def __init__(self, name: str = "train"):
        # fire after evaluation/printing so the eval bucket lands in the
        # iteration that paid it, but before early-stop raises (order 30)
        self.order = 25
        self.before_iteration = False
        self.name = name
        self.records: List[dict] = []
        self._t_prev: Optional[float] = None
        self._cat_prev: Dict[str, float] = {}
        self._counts_prev: Dict[str, float] = {}

    # -- bucket accounting -------------------------------------------------
    def _deltas(self):
        cat = events.category_totals()
        buckets = {k: round(v - self._cat_prev.get(k, 0.0), 6)
                   for k, v in cat.items()
                   if v - self._cat_prev.get(k, 0.0) > 1e-9}
        self._cat_prev = cat
        counts = events.counts_snapshot()
        compiles = int(counts.get("jax::backend_compile", 0)
                       - self._counts_prev.get("jax::backend_compile", 0))
        self._counts_prev = counts
        return buckets, compiles

    def _model_state(self, model):
        """(trees materialized, leaves of the last materialized tree) —
        async-pending entries are None until a sync point pulls them."""
        inner = getattr(model, "_booster", model)   # Booster or inner GBDT
        models = getattr(inner, "models", None)
        if not models:
            return 0, None
        done = [t for t in models if t is not None]
        last = done[-1].num_leaves if done else None
        return len(done), last

    def record(self, iteration: int, model=None,
               evals: Optional[list] = None) -> dict:
        """Record one iteration boundary; usable without a CallbackEnv
        (the GBDT.train loop calls this directly)."""
        now = time.perf_counter()
        wall = (now - self._t_prev) if self._t_prev is not None else 0.0
        self._t_prev = now
        buckets, compiles = self._deltas()
        trees, leaves = self._model_state(model)
        rec = {"monitor": self.name, "iteration": int(iteration),
               "wall": round(wall, 6), "buckets": buckets,
               "trees_materialized": trees, "compiles": compiles}
        if leaves is not None:
            rec["last_num_leaves"] = int(leaves)
        mem = events.device_memory_stats()
        if mem is not None:
            rec["memory"] = mem
        if evals:
            rec["num_evals"] = len(evals)
        # numerics-health anomaly probes (telemetry/health.py): a
        # non-finite eval metric, a split-margin collapse against the
        # rolling baseline, or a collective::stall burst each flight-
        # note and count health::<kind>; kinds listed in
        # tpu_health_abort= raise (with a flight dump) INSTEAD of
        # letting the run train garbage to completion
        from . import health
        anomalies = health.check_record(iteration, evals)
        if anomalies:
            rec["health"] = sorted({a["kind"] for a in anomalies})
        self.records.append(rec)
        events.record_iteration(rec)
        # periodic Prometheus snapshot (telemetry_out=...prom): throttled
        # inside maybe_flush, a no-op for non-.prom out paths
        from . import promexport
        promexport.maybe_flush()
        return rec

    # -- CallbackEnv protocol ---------------------------------------------
    def __call__(self, env) -> None:
        if events.mode() == events.OFF:
            return
        self.record(env.iteration, model=env.model,
                    evals=env.evaluation_result_list)
