"""Bucketed batch-serving layer over the device predictor.

Serving traffic is ragged: every distinct row count is a distinct XLA
program, and an unbounded shape set means unbounded recompiles. This layer
pads each incoming batch up to a power-of-two row bucket between
`min_batch` and `max_batch`, so the steady-state program cache holds at
most ``ceil(log2(max_batch / min_batch)) + 1`` traversal executables no
matter what batch sizes arrive — the property the serve-layer test pins
via the `predict::serve_compile` / `predict::serve_bucket_hit` counters.

Batches larger than `max_batch` stream through in `max_batch` chunks
(bounded device memory). When more than one local device is visible and
the bucket divides evenly, the padded batch is placed row-sharded over the
local mesh (`NamedSharding` + jit — the pjit path), so one large request
fans out across chips; input buffers are donated on accelerator backends
(the padded copy is serving-owned, never reused).
"""
from __future__ import annotations


import threading
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..telemetry import events as telemetry
from ..telemetry import histo as telemetry_histo
from ..telemetry.histo import Histogram
from .runtime import TPUPredictor, _next_pow2

C_SERVE_COMPILE = "predict::serve_compile"
C_SERVE_HIT = "predict::serve_bucket_hit"
C_SERVE_SHARDED = "predict::serve_sharded_batches"
H_E2E = "predict::e2e_latency"
H_QUEUE = "predict::queue_wait"
H_QDEPTH = "predict::queue_depth"

ROWS_AXIS = "rows"


def build_mesh(devices) -> "Mesh | None":
    """1-D row mesh over the given devices (None when a single device —
    plain placement is then strictly cheaper than a degenerate mesh)."""
    return Mesh(np.array(devices), (ROWS_AXIS,)) if len(devices) > 1 \
        else None


def place_padded(Xp: np.ndarray, dtype, mesh, devices,
                 shard_min_rows: int):
    """Padded host batch -> device array, row-sharded over the local
    mesh when large enough and evenly divisible. Returns (X_dev,
    sharded_flag); shared by the sync BatchServer and the async serving
    admission loop so both take the identical pjit fan-out path."""
    np_dt = np.float32 if dtype == jnp.float32 else np.float64
    if (mesh is not None and Xp.shape[0] >= shard_min_rows
            and Xp.shape[0] % len(devices) == 0):
        telemetry.count(C_SERVE_SHARDED, 1, category="predict")
        return jax.device_put(
            Xp.astype(np_dt, copy=False),
            NamedSharding(mesh, P(ROWS_AXIS, None))), True
    return jnp.asarray(Xp, dtype=dtype), False


class BatchServer:
    """Pad-to-bucket batching + mesh fan-out for one TPUPredictor.

    ``min_batch``/``max_batch`` bound the power-of-two bucket ladder (and
    with it the compile count); ``shard_min_rows`` gates when a padded
    batch is worth sharding across the local devices.
    """

    def __init__(self, predictor: TPUPredictor, min_batch: int = 256,
                 max_batch: int = 1 << 16, shard_min_rows: int = 8192,
                 devices=None):
        if max_batch < min_batch:
            raise ValueError("max_batch %d < min_batch %d"
                             % (max_batch, min_batch))
        self.predictor = predictor
        self.min_batch = _next_pow2(max(int(min_batch), 1))
        self.max_batch = _next_pow2(int(max_batch))
        self.shard_min_rows = int(shard_min_rows)
        self.devices = list(devices) if devices is not None \
            else list(jax.local_devices())
        self._mesh = build_mesh(self.devices)
        # instance-local serving stats: stats() reports this server's own
        # counts (events.count() is process-wide)
        self._compiled_buckets = set()
        self._bucket_hits = 0
        self._sharded_batches = 0
        # SLO histograms, same instance-local rule: per-request
        # end-to-end latency and queue wait (arrival -> service start,
        # when the caller supplies arrival_t — the open-loop Poisson
        # bench does). Mirrored into the global registry when telemetry
        # is on so they ride the metrics/prom exports.
        self._h_e2e = Histogram(H_E2E, unit="s", category="predict")
        self._h_queue = Histogram(H_QUEUE, unit="s", category="predict")
        # queue depth is sampled at ADMISSION as well as at service
        # start: depth that builds up between flushes (concurrent
        # callers stacking behind an in-service batch) is real queueing
        # the service-start sample alone never sees. _depth counts
        # requests admitted but not yet answered; the running max is the
        # stats() headline.
        self._h_qdepth = Histogram(H_QDEPTH, unit="req",
                                   category="predict")
        self._depth = 0
        self._qdepth_max = 0
        self._depth_lock = threading.Lock()

    # ------------------------------------------------------------------
    def bucket_rows(self, n: int) -> int:
        """Smallest ladder bucket holding n rows (n <= max_batch)."""
        return min(max(_next_pow2(n), self.min_batch), self.max_batch)

    def max_compiles(self) -> int:
        """The compile bound the ladder guarantees."""
        return int(np.log2(self.max_batch // self.min_batch)) + 1

    def _place(self, Xp: np.ndarray):
        """Padded host batch -> device array (module helper; counts
        sharded placements on this instance)."""
        X_dev, sharded = place_padded(Xp, self.predictor._dtype,
                                      self._mesh, self.devices,
                                      self.shard_min_rows)
        if sharded:
            with self._depth_lock:
                self._sharded_batches += 1
        return X_dev

    def _serve_chunk(self, X: np.ndarray, raw_score: bool) -> np.ndarray:
        n = X.shape[0]
        bucket = self.bucket_rows(n)
        with self._depth_lock:
            # check-then-act on the bucket set: two concurrent callers
            # hitting a fresh bucket must not both count a compile
            hit = bucket in self._compiled_buckets
            if hit:
                self._bucket_hits += 1
            else:
                self._compiled_buckets.add(bucket)
        telemetry.count(C_SERVE_HIT if hit else C_SERVE_COMPILE, 1,
                        category="predict")
        Xp = np.zeros((bucket, X.shape[1]), dtype=np.float64)
        Xp[:n] = X
        return self.predictor.predict_padded(self._place(Xp), n,
                                             raw_score=raw_score)

    def predict(self, X, raw_score: bool = False,
                arrival_t: float = None) -> np.ndarray:
        """Serve one request of any size; rows beyond max_batch stream in
        max_batch chunks.

        ``arrival_t`` (a ``time.perf_counter()`` timestamp) marks when
        the request entered the system: the gap to service start is the
        request's QUEUE WAIT, and end-to-end latency is measured from
        arrival rather than from service start — the numbers an SLO is
        written against. Omitted, queue wait records as 0 and e2e is
        pure service time."""
        d_adm = self._admit()
        telemetry_histo.observe(H_QDEPTH, float(d_adm), unit="req",
                                category="predict")
        t_start = time.perf_counter()
        try:
            q_wait = max(t_start - arrival_t, 0.0) \
                if arrival_t is not None else 0.0
            X = np.ascontiguousarray(np.asarray(X, dtype=np.float64))
            if X.ndim == 1:
                X = X.reshape(1, -1)
            if X.shape[0] <= self.max_batch:
                out = self._serve_chunk(X, raw_score)
            else:
                outs = [self._serve_chunk(X[i:i + self.max_batch],
                                          raw_score)
                        for i in range(0, X.shape[0], self.max_batch)]
                out = np.concatenate(outs, axis=0)
        finally:
            with self._depth_lock:
                self._depth -= 1
        e2e = time.perf_counter() - (arrival_t if arrival_t is not None
                                     else t_start)
        with self._depth_lock:
            # histogram record is a multi-field read-modify-write; the
            # instance histograms share _depth_lock with the depth state
            self._h_queue.record(q_wait)
            self._h_e2e.record(e2e)
        telemetry_histo.observe(H_QUEUE, q_wait, unit="s",
                                category="predict")
        telemetry_histo.observe(H_E2E, e2e, unit="s", category="predict")
        return out

    def _admit(self) -> int:
        """Count a request in; returns the post-admission depth — the
        admission-time queue-depth sample. Depth that builds up behind
        an in-service batch was invisible to service-start-only
        sampling (the bench's probe), so the server samples at both
        points and keeps the true max."""
        with self._depth_lock:
            self._depth += 1
            if self._depth > self._qdepth_max:
                self._qdepth_max = self._depth
            self._h_qdepth.record(float(self._depth))
            return self._depth

    # ------------------------------------------------------------------
    def stats(self) -> dict:
        """Per-server serving stats (telemetry-independent; the same
        figures also land on the telemetry counters/histograms when
        enabled). `latency`/`queue_wait` carry the full histogram dicts;
        the p50/p99 shortcuts are what the bench SLO keys read."""
        with self._depth_lock:
            # consistent snapshot vs concurrent predict() callers (and
            # no set-changed-during-iteration on _compiled_buckets)
            return {
                "buckets_compiled": sorted(self._compiled_buckets),
                "compiles": len(self._compiled_buckets),
                "compile_bound": self.max_compiles(),
                "bucket_hits": self._bucket_hits,
                "sharded_batches": self._sharded_batches,
                "requests": self._h_e2e.count,
                "latency_p50": self._h_e2e.percentile(0.50),
                "latency_p99": self._h_e2e.percentile(0.99),
                "queue_wait_p99": self._h_queue.percentile(0.99),
                "qdepth_max": self._qdepth_max,
                "latency": self._h_e2e.to_dict(with_buckets=False),
                "queue_wait": self._h_queue.to_dict(with_buckets=False),
                "queue_depth": self._h_qdepth.to_dict(
                    with_buckets=False),
            }
