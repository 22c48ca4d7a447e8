"""Resilience subsystem: atomic checkpoint/resume, collective retry,
deterministic fault injection.

The acceptance contract (ISSUE 5):
  * a run killed at iteration k and auto-resumed produces a BYTE-IDENTICAL
    final model to the uninterrupted run (all boosting modes, with the
    host RNG streams — bagging / GOSS / DART drops / feature_fraction —
    mid-stream);
  * a corrupted latest checkpoint falls back to the previous valid one;
  * a dropped DCN collective surfaces as a bounded-retry LightGBMError
    (no hang), with collective::retry / collective::timeout pinned;
  * checkpoint::write overhead stays < 3% of train wall.

The two-process distributed kill/resume sibling lives at the bottom
(slow-marked); everything above runs single-process in tier-1.
"""
import json
import os
import shutil
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.resilience import checkpoint as ckpt
from lightgbm_tpu.resilience import faults, restore, retry
from lightgbm_tpu.resilience.faults import FaultPlan, TrainingKilled
from lightgbm_tpu.utils.log import LightGBMError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_binary(n=900, nf=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, nf))
    y = (X[:, 0] - 0.5 * X[:, 2] + rng.normal(size=n) * 0.3 > 0)
    return X, y.astype(float)


def _fresh_dir(tmp_path, name):
    d = str(tmp_path / name)
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    return d


BASE = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
        "min_data_in_leaf": 5, "learning_rate": 0.3}


def _train(params, X, y, rounds=12):
    return lgb.train(dict(params), lgb.Dataset(X, y), rounds,
                     verbose_eval=False)


# ---------------------------------------------------------------------------
# fault-plan grammar
# ---------------------------------------------------------------------------

def test_fault_plan_grammar():
    p = FaultPlan("kill@iter=12;rank=1,drop_collective@round=3;times=2,"
                  "corrupt_checkpoint@n=2")
    assert p.kill_iter == 12 and p.kill_rank == 1
    assert p.kill_point(0) is None and p.kill_point(1) == 12
    assert p.drop_round == 3 and p.drop_times == 2
    assert p.corrupt_n == 2
    # times=2: the round fails twice, then recovers
    assert p.collective_should_drop(3) and p.collective_should_drop(3)
    assert not p.collective_should_drop(3)
    assert not p.collective_should_drop(2)
    # rank-less kill applies to every rank
    assert FaultPlan("kill@iter=4").kill_point(7) == 4


def test_fault_plan_grammar_stall_resize():
    p = FaultPlan("stall@round=2;secs=3,resize@iter=9;world=4")
    assert p.stall_round == 2 and p.stall_secs == 3 and p.stall_rank is None
    assert p.collective_stall_secs(2) == 3.0
    assert p.collective_stall_secs(1) == 0.0
    assert p.resize_iter == 9 and p.resize_world == 4
    # rank-filtered stall: this process is rank 0
    q = FaultPlan("stall@round=1;secs=2;rank=5")
    assert q.collective_stall_secs(1) == 0.0
    # batch clamping sees the earliest stop point, rank filters ignored
    assert FaultPlan("kill@iter=7;rank=1,resize@iter=5;world=2"
                     ).clamp_iter() == 5
    assert FaultPlan("kill@iter=3").clamp_iter() == 3
    assert FaultPlan("stall@round=1;secs=1").clamp_iter() is None


def test_resize_raises_typed_error():
    from lightgbm_tpu.resilience.faults import TrainingResized
    from lightgbm_tpu.telemetry import flight
    flight.disarm()   # check_kill dumps wherever a previous test left
    #                   the recorder armed (default '.': repo litter)
    p = FaultPlan("resize@iter=6;world=2")
    p.check_kill(5)                      # before the resize point: fine
    with pytest.raises(TrainingResized) as exc:
        p.check_kill(6, rank=3)          # fires on EVERY rank
    assert exc.value.target_world == 2
    assert isinstance(exc.value, TrainingKilled)
    assert "world=2" in str(exc.value)
    # when both land on the same run, the earlier point wins
    pk = FaultPlan("kill@iter=4,resize@iter=8;world=2")
    with pytest.raises(TrainingKilled) as exc2:
        pk.check_kill(4)
    assert not isinstance(exc2.value, TrainingResized)


@pytest.mark.parametrize("bad", ["kill", "kill@iter=x", "explode@n=1",
                                 "drop_collective@times=1",
                                 "corrupt_checkpoint@iter=1",
                                 # duplicates would silently last-win
                                 "kill@iter=1,kill@iter=2",
                                 "drop_collective@round=1,"
                                 "drop_collective@round=5",
                                 # stall/resize mirror the same rules
                                 "stall@round=1",
                                 "stall@secs=2",
                                 "stall@round=1;secs=-1",
                                 "stall@round=1;secs=2,stall@round=3;secs=1",
                                 "resize@iter=1",
                                 "resize@world=2",
                                 "resize@iter=1;world=0",
                                 "resize@iter=1;world=2,"
                                 "resize@iter=3;world=1"])
def test_fault_plan_rejects_malformed(bad):
    with pytest.raises(LightGBMError):
        FaultPlan(bad)


# ---------------------------------------------------------------------------
# container: CRC + atomic write
# ---------------------------------------------------------------------------

def test_checkpoint_container_roundtrip_and_crc(tmp_path):
    path = str(tmp_path / "c.lgc")
    arrays = {"a": np.arange(7, dtype=np.float64),
              "txt": np.frombuffer(b"hello", dtype=np.uint8)}
    blob = ckpt.pack_checkpoint(5, arrays, {"kind": "train", "rank": 0,
                                            "config_hash": "ch",
                                            "data_fingerprint": "fp"})
    ckpt.atomic_write_bytes(path, blob)
    assert not [n for n in os.listdir(str(tmp_path)) if "tmp" in n]
    meta, back = ckpt.load_checkpoint(path)
    assert meta["iteration"] == 5 and meta["config_hash"] == "ch"
    np.testing.assert_array_equal(back["a"], arrays["a"])
    assert back["txt"].tobytes() == b"hello"
    # flip payload bytes -> CRC mismatch must be detected
    with open(path, "r+b") as f:
        f.seek(-4, os.SEEK_END)
        f.write(b"\x00\x01\x02\x03")
    with pytest.raises(ckpt.CheckpointError):
        ckpt.load_checkpoint(path)
    # truncation too
    with open(path, "rb") as f:
        head = f.read(40)
    with open(path, "wb") as f:
        f.write(head)
    with pytest.raises(ckpt.CheckpointError):
        ckpt.load_checkpoint(path)


def test_checkpoint_keep_prunes(tmp_path):
    w = ckpt.CheckpointWriter(str(tmp_path), keep=2, cfg_hash="h",
                              fingerprint="fp")
    for it in (2, 4, 6, 8):
        w.write_model_text("model %d" % it, it)
    assert [i for i, _ in ckpt.list_checkpoints(str(tmp_path))] == [6, 8]


# ---------------------------------------------------------------------------
# kill -> auto-resume -> byte-identical final model
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("boosting,extra", [
    # gbdt is the cheap tier-1 sibling (bagging + feature-fraction RNG
    # mid-stream); goss/dart/rf ride the slow tier — they share the same
    # capture/restore machinery plus their per-mode state hooks
    ("gbdt", {"bagging_fraction": 0.8, "bagging_freq": 2,
              "feature_fraction": 0.7}),
    pytest.param("goss", {}, marks=pytest.mark.slow),
    pytest.param("dart", {"drop_rate": 0.5}, marks=pytest.mark.slow),
    pytest.param("rf", {"bagging_fraction": 0.7, "bagging_freq": 1},
                 marks=pytest.mark.slow),
])
def test_kill_and_resume_byte_identical(tmp_path, boosting, extra):
    """Uninterrupted run == killed-at-k + auto-resumed run, byte for byte
    — including the mid-stream host RNG state (bagging draw, GOSS
    sampling, DART drops, feature-fraction columns)."""
    X, y = _make_binary()
    d = _fresh_dir(tmp_path, "ck")
    params = dict(BASE, boosting=boosting, snapshot_freq=4,
                  checkpoint_dir=d, **extra)
    model_a = _train(params, X, y).model_to_string(num_iteration=-1)
    # wipe and replay the same run, preempted before iteration 10
    shutil.rmtree(d)
    os.makedirs(d)
    killed = dict(params, tpu_fault_plan="kill@iter=10")
    with pytest.raises(TrainingKilled):
        _train(killed, X, y)
    iters = [i for i, _ in ckpt.list_checkpoints(d)]
    assert iters == [4, 8]
    resumed = _train(params, X, y)
    assert resumed.num_trees() == 12
    assert resumed.model_to_string(num_iteration=-1) == model_a


def test_corrupt_latest_falls_back_to_previous(tmp_path):
    """corrupt_checkpoint@n=2 poisons the iteration-8 snapshot; resume
    must reject it on CRC, fall back to iteration 4, and STILL finish
    byte-identical to the uninterrupted run."""
    X, y = _make_binary()
    d = _fresh_dir(tmp_path, "ck")
    # same params as the gbdt kill/resume case: the three trains here
    # reuse its compiled programs instead of building a fresh set
    params = dict(BASE, snapshot_freq=4, checkpoint_dir=d,
                  bagging_fraction=0.8, bagging_freq=2,
                  feature_fraction=0.7)
    model_a = _train(params, X, y).model_to_string(num_iteration=-1)
    shutil.rmtree(d)
    os.makedirs(d)
    killed = dict(params,
                  tpu_fault_plan="kill@iter=10,corrupt_checkpoint@n=2")
    with pytest.raises(TrainingKilled):
        _train(killed, X, y)
    cfg = lgb.Config(params)
    ds = lgb.Dataset(X, y)
    ds.construct()
    found = restore.find_restorable(cfg, ds._inner)
    assert found is not None and int(found[0]["iteration"]) == 4
    resumed = _train(params, X, y)
    assert resumed.model_to_string(num_iteration=-1) == model_a


def test_foreign_config_or_data_starts_fresh(tmp_path):
    """A checkpoint_dir holding a DIFFERENT run's snapshots (config hash
    or dataset fingerprint mismatch) must not be resumed from — while the
    volatile keys (num_iterations, fault plan, telemetry) keep matching."""
    X, y = _make_binary()
    d = _fresh_dir(tmp_path, "ck")
    params = dict(BASE, snapshot_freq=4, checkpoint_dir=d)
    _train(params, X, y, rounds=8)
    assert ckpt.list_checkpoints(d)
    ds = lgb.Dataset(X, y)
    ds.construct()
    # matching run resumes ...
    assert restore.find_restorable(lgb.Config(params), ds._inner) is not None
    # ... and so does one differing only in volatile keys
    volatile = dict(params, num_iterations=50, tpu_fault_plan="kill@iter=9",
                    tpu_telemetry="timers")
    assert restore.find_restorable(lgb.Config(volatile),
                                   ds._inner) is not None
    # different config (num_leaves): config-hash mismatch -> fresh
    other = dict(params, num_leaves=15)
    assert restore.find_restorable(lgb.Config(other), ds._inner) is None
    # different data, same config: fingerprint mismatch -> fresh
    X2, y2 = _make_binary(seed=9)
    ds2 = lgb.Dataset(X2, y2)
    ds2.construct()
    assert restore.find_restorable(lgb.Config(params), ds2._inner) is None


def test_checkpoint_params_roundtrip_and_alias(tmp_path):
    """snapshot_freq rides its reference alias (save_period) and the new
    checkpoint params round-trip into the model's parameters block, like
    the predict_device params do."""
    cfg = lgb.Config({"save_period": 7})
    assert cfg.snapshot_freq == 7
    X, y = _make_binary(n=400)
    d = _fresh_dir(tmp_path, "ck")
    params = dict(BASE, save_period=4, checkpoint_dir=d, checkpoint_keep=1)
    b = _train(params, X, y, rounds=8)
    assert len(ckpt.list_checkpoints(d)) == 1   # keep=1 pruned
    text = b.model_to_string(num_iteration=-1)
    saved = json.loads(text.split("parameters:\n", 1)[1]
                       .split("\nend of parameters", 1)[0])
    assert saved["checkpoint_dir"] == d
    assert saved["checkpoint_keep"] == 1
    assert saved["snapshot_freq"] == 4


@pytest.mark.slow
def test_kill_resume_with_early_stopping_state(tmp_path):
    """The early-stopping best trackers ride the checkpoint: a resumed
    run keeps the same patience clock and rollback point, so it stops at
    the same iteration with the same best_iteration and a byte-identical
    saved model."""
    rng = np.random.default_rng(7)
    X = rng.normal(size=(600, 5))
    y = (X[:, 0] + rng.normal(size=600) * 1.5 > 0).astype(float)
    Xv = rng.normal(size=(250, 5))
    yv = (Xv[:, 0] + rng.normal(size=250) * 1.5 > 0).astype(float)
    d = _fresh_dir(tmp_path, "ck")
    params = dict(BASE, metric="binary_logloss", snapshot_freq=4,
                  checkpoint_dir=d)

    def run(extra=None):
        p = dict(params, **(extra or {}))
        return lgb.train(p, lgb.Dataset(X, y, params=p), 40,
                         valid_sets=[lgb.Dataset(Xv, yv)],
                         early_stopping_rounds=4, verbose_eval=False)

    b_a = run()
    # the run must stop early AFTER the kill point for the test to bite
    assert 4 < b_a.best_iteration < 40
    model_a = b_a.model_to_string()
    shutil.rmtree(d)
    os.makedirs(d)
    with pytest.raises(TrainingKilled):
        run({"tpu_fault_plan": "kill@iter=4"})
    b_r = run()
    assert b_r.best_iteration == b_a.best_iteration
    assert b_r.model_to_string() == model_a


@pytest.mark.slow
def test_resume_of_init_model_run_trains_full_target(tmp_path):
    """A checkpointed run started from an init model: num_boost_round
    counts NEW rounds beyond the graft, and a resume must finish exactly
    that target (not stop short at the absolute checkpoint iteration)."""
    X, y = _make_binary()
    b_init = lgb.train(dict(BASE), lgb.Dataset(X, y), 5,
                       verbose_eval=False)
    d = _fresh_dir(tmp_path, "ck")
    params = dict(BASE, snapshot_freq=4, checkpoint_dir=d)
    model_a = lgb.train(dict(params), lgb.Dataset(X, y), 10,
                        init_model=b_init,
                        verbose_eval=False).model_to_string(
        num_iteration=-1)
    shutil.rmtree(d)
    os.makedirs(d)
    killed = dict(params, tpu_fault_plan="kill@iter=12")
    with pytest.raises(TrainingKilled):
        lgb.train(killed, lgb.Dataset(X, y), 10, init_model=b_init,
                  verbose_eval=False)
    resumed = lgb.train(dict(params), lgb.Dataset(X, y), 10,
                        init_model=b_init, verbose_eval=False)
    assert resumed.num_trees() == 15          # 5 grafted + 10 new
    assert resumed.model_to_string(num_iteration=-1) == model_a


# ---------------------------------------------------------------------------
# telemetry counters (pinned like predict::serve_compile)
# ---------------------------------------------------------------------------

def test_checkpoint_counters_pinned(tmp_path):
    """checkpoint::write/bytes/restore pinned the same way
    predict::serve_compile is — and re-running a finished job is a pure
    restore: zero extra writes, byte-identical model out."""
    from lightgbm_tpu import telemetry
    X, y = _make_binary()
    d = _fresh_dir(tmp_path, "ck")
    params = dict(BASE, snapshot_freq=4, checkpoint_dir=d)
    telemetry.enable("timers")
    try:
        telemetry.reset()
        model_a = _train(params, X, y).model_to_string(
            num_iteration=-1)                      # writes at 4, 8, 12
        counts = telemetry.events.counts_snapshot()
        assert counts.get("checkpoint::write", 0) == 3, counts
        assert counts.get("checkpoint::bytes", 0) > 0, counts
        assert counts.get("checkpoint::restore", 0) == 0, counts
        scopes = telemetry.events.snapshot_full()
        assert "checkpoint::write" in scopes
        telemetry.reset()
        again = _train(params, X, y)               # resumes at 12: no-op
        counts = telemetry.events.counts_snapshot()
        assert counts.get("checkpoint::restore", 0) == 1, counts
        assert counts.get("checkpoint::write", 0) == 0, counts
        assert again.num_trees() == 12
        assert again.model_to_string(num_iteration=-1) == model_a
    finally:
        telemetry.reset()
        telemetry.disable()


def test_checkpoint_write_overhead_under_3_percent(tmp_path):
    """The acceptance budget: checkpoint::write seconds < 3% of train
    wall on a HIGGS-like shape (on the CPU; not measured at full scale)."""
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.data.synth import make_higgs_like
    X, y = make_higgs_like(6_000)
    # tmpfs when available: this CI box's fsync latency is wildly
    # variable (0.1-1s under IO contention) and would dominate the toy
    # 10s train wall; the pin targets the serialization/write PATH cost
    base = "/dev/shm" if os.access("/dev/shm", os.W_OK) else str(tmp_path)
    d = os.path.join(base, "lgbtpu_ck_overhead")
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "snapshot_freq": 8, "checkpoint_dir": d}
    telemetry.enable("timers")
    try:
        telemetry.reset()
        t0 = time.time()
        lgb.train(dict(params), lgb.Dataset(X, y), 16, verbose_eval=False)
        wall = time.time() - t0
        scopes = telemetry.events.snapshot_full()
        write_s, nwrites, _ = scopes.get("checkpoint::write",
                                         (0.0, 0, ""))
        assert nwrites == 2
        assert write_s < 0.03 * wall, \
            "checkpoint::write %.3fs of %.3fs wall" % (write_s, wall)
    finally:
        telemetry.reset()
        telemetry.disable()
        shutil.rmtree(d, ignore_errors=True)


# ---------------------------------------------------------------------------
# collective retry: bounded error instead of a hang
# ---------------------------------------------------------------------------

def test_drop_collective_bounded_retry_error():
    from lightgbm_tpu import telemetry
    telemetry.enable("timers")
    try:
        telemetry.reset()
        retry.reset_rounds()
        faults._PLAN = FaultPlan("drop_collective@round=2")
        # timeout_s=0: injected drops never reach the collective, so
        # the watchdog thread is noise here (and thread creation deep
        # into a long tier-1 run is the one flaky dependency)
        retry._POLICY = retry.RetryPolicy(timeout_s=0.0, retries=2,
                                          backoff_s=0.0)
        assert retry.guard("c1", lambda: "ok") == "ok"   # round 1 clean
        with pytest.raises(LightGBMError) as exc:        # round 2 dropped
            retry.guard("c2", lambda: "never")
        assert "after 3 attempt(s)" in str(exc.value)
        counts = telemetry.events.counts_snapshot()
        assert counts.get("collective::retry", 0) == 2, counts
        assert counts.get("faults::injected", 0) == 3, counts
    finally:
        faults.reset()
        retry._POLICY = retry.RetryPolicy()
        telemetry.reset()
        telemetry.disable()


def test_drop_collective_transient_recovers():
    retry.reset_rounds()
    faults._PLAN = FaultPlan("drop_collective@round=1;times=1")
    retry._POLICY = retry.RetryPolicy(timeout_s=0.0, retries=2,
                                      backoff_s=0.0)
    try:
        assert retry.guard("c", lambda: 42) == 42   # fails once, retried
    finally:
        faults.reset()
        retry._POLICY = retry.RetryPolicy()


def test_collective_timeout_no_hang():
    """A peer that never answers: the guard's deadline converts the hang
    into a clean LightGBMError in bounded time."""
    from lightgbm_tpu import telemetry
    telemetry.enable("timers")
    try:
        telemetry.reset()
        retry.reset_rounds()
        retry._POLICY = retry.RetryPolicy(timeout_s=0.2, retries=1,
                                          backoff_s=0.0)
        t0 = time.time()
        with pytest.raises(LightGBMError):
            retry.guard("stuck", time.sleep, 60)
        assert time.time() - t0 < 5.0
        counts = telemetry.events.counts_snapshot()
        assert counts.get("collective::timeout", 0) == 2, counts
    finally:
        retry._POLICY = retry.RetryPolicy()
        telemetry.reset()
        telemetry.disable()


def test_retry_policy_from_config():
    cfg = lgb.Config({"tpu_collective_timeout": 7.5,
                      "tpu_collective_retries": 4,
                      "tpu_collective_backoff": 0.0})
    retry.configure_from_config(cfg)
    try:
        pol = retry.policy()
        assert (pol.timeout_s, pol.retries, pol.backoff_s) == (7.5, 4, 0.0)
        # soft deadline: auto = a quarter of the hard deadline
        assert pol.effective_soft_s() == pytest.approx(7.5 / 4)
    finally:
        retry._POLICY = retry.RetryPolicy()
    cfg2 = lgb.Config({"tpu_collective_timeout": 10.0,
                       "tpu_collective_soft_timeout": 2.0})
    retry.configure_from_config(cfg2)
    try:
        assert retry.policy().effective_soft_s() == 2.0
    finally:
        retry._POLICY = retry.RetryPolicy()
    # a soft deadline >= the hard one (or timeout 0) disables the watchdog
    assert retry.RetryPolicy(timeout_s=1.0,
                             soft_timeout_s=5.0).effective_soft_s() == 0.0
    assert retry.RetryPolicy(timeout_s=0.0).effective_soft_s() == 0.0


# ---------------------------------------------------------------------------
# straggler watchdog: collective::stall + flight dump BEFORE the hard
# deadline decides (the ISSUE-12 acceptance pin)
# ---------------------------------------------------------------------------

def test_stall_fault_emits_stall_event_and_flight_dump(tmp_path):
    """A stall@ fault longer than the soft deadline but shorter than the
    hard one: the collective SUCCEEDS, yet collective::stall is counted
    and a flight record is on disk from before the call returned."""
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.telemetry import flight
    d = _fresh_dir(tmp_path, "stall")
    telemetry.enable("timers")
    try:
        telemetry.reset()
        flight.reset()
        flight.arm(dump_dir=d)
        retry.reset_rounds()
        faults._PLAN = FaultPlan("stall@round=1;secs=1")
        retry._POLICY = retry.RetryPolicy(timeout_s=30.0, retries=0,
                                          backoff_s=0.0,
                                          soft_timeout_s=0.1)
        assert retry.guard("allgather:probe", lambda: "ok") == "ok"
        counts = telemetry.events.counts_snapshot()
        assert counts.get("collective::stall", 0) == 1, counts
        assert counts.get("collective::timeout", 0) == 0, counts
        assert counts.get("faults::injected", 0) == 1, counts
        dump = flight.last_dump_path()
        assert dump and os.path.exists(dump)
        rec = json.load(open(dump))
        assert rec["reason"].startswith("collective_stall:")
        stalls = [e for e in rec["events"]
                  if e["kind"] == "collective_stall"]
        assert stalls and stalls[0]["soft_deadline_s"] == 0.1
    finally:
        faults.reset()
        retry._POLICY = retry.RetryPolicy()
        flight.disarm()
        telemetry.reset()
        telemetry.disable()


def test_stall_past_hard_deadline_still_bounded(tmp_path):
    """A stall longer than the hard deadline: the soft watchdog fires
    first (stall counted), then the deadline converts the straggler into
    the usual bounded timeout error — never a hang."""
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.telemetry import flight
    telemetry.enable("timers")
    flight.disarm()       # the stall path dumps wherever a previous
    try:                  # test left the recorder armed
        telemetry.reset()
        retry.reset_rounds()
        faults._PLAN = FaultPlan("stall@round=1;secs=30")
        retry._POLICY = retry.RetryPolicy(timeout_s=0.4, retries=0,
                                          backoff_s=0.0,
                                          soft_timeout_s=0.1)
        t0 = time.time()
        with pytest.raises(LightGBMError):
            retry.guard("allgather:wedge", lambda: "never")
        assert time.time() - t0 < 5.0
        counts = telemetry.events.counts_snapshot()
        assert counts.get("collective::stall", 0) == 1, counts
        assert counts.get("collective::timeout", 0) == 1, counts
    finally:
        faults.reset()
        retry._POLICY = retry.RetryPolicy()
        telemetry.reset()
        telemetry.disable()


def test_peer_loss_error_names_resume_point():
    """After a checkpoint write, a permanently-gone peer surfaces as
    'resumable at iteration K on a smaller mesh', not a generic failure
    (the watchdog half of the elastic story)."""
    retry.reset_rounds()
    retry._POLICY = retry.RetryPolicy(timeout_s=0.0, retries=0,
                                      backoff_s=0.0)
    try:
        retry.set_resume_hint(24, 4)

        def gone():
            raise ConnectionError("peer vanished")
        with pytest.raises(LightGBMError) as exc:
            retry.guard("allgather:x", gone)
        assert "resumable at iteration 24 on a smaller mesh" in \
            str(exc.value)
        assert "num_machines < 4" in str(exc.value)
        # single-host hint names the checkpoint, not a mesh
        retry.reset_rounds()
        retry.set_resume_hint(8, 1)
        with pytest.raises(LightGBMError) as exc2:
            retry.guard("allgather:y", gone)
        assert "resumable at iteration 8 from checkpoint_dir" in \
            str(exc2.value)
    finally:
        retry.set_resume_hint(None)
        retry._POLICY = retry.RetryPolicy()


# ---------------------------------------------------------------------------
# checkpoint hygiene: orphaned tmp sweep + concurrent-prune tolerance
# ---------------------------------------------------------------------------

def test_writer_sweeps_orphaned_tmp_files(tmp_path):
    """A kill mid-write leaves `.ckpt_*.tmp` behind forever; the next
    saver startup sweeps them: own-rank orphans unconditionally, foreign
    ones (another rank's snapshot, the shared manifest) only once old
    enough to be provably dead — a shared dir may have live writers."""
    d = _fresh_dir(tmp_path, "tmpsweep")
    own_orphan = os.path.join(d, ".ckpt_00000004.r0.lgc.1234.tmp")
    aged_foreign = os.path.join(d, ".elastic.manifest.json.77.tmp")
    live_foreign = os.path.join(d, ".ckpt_00000002.r7.lgc.99.tmp")
    for p in (own_orphan, aged_foreign, live_foreign):
        with open(p, "w") as f:
            f.write("torn half-write")
    os.utime(aged_foreign, (time.time() - 3600, time.time() - 3600))
    keepers = [os.path.join(d, "keep.txt"),
               os.path.join(d, "tmpnotdot.tmp.txt")]
    for k in keepers:
        with open(k, "w") as f:
            f.write("x")
    ckpt.CheckpointWriter(d, keep=2, cfg_hash="h", fingerprint="fp")
    assert not os.path.exists(own_orphan)      # rank 0's own: swept
    assert not os.path.exists(aged_foreign)    # provably dead: swept
    assert os.path.exists(live_foreign)        # maybe mid-write: kept
    assert all(os.path.exists(k) for k in keepers)


def test_prune_tolerates_concurrent_delete(tmp_path, monkeypatch):
    """checkpoint_keep pruning on a shared directory: a concurrent rank
    removing the same stale snapshot must not crash the writer."""
    d = _fresh_dir(tmp_path, "prunerace")
    w = ckpt.CheckpointWriter(d, keep=1, cfg_hash="h", fingerprint="fp")
    w.write_model_text("m2", 2)
    real_remove = os.remove
    raced = {"n": 0}

    def racing_remove(path):
        # the other rank wins the unlink race on every prune target
        if path.endswith(".lgc"):
            raced["n"] += 1
            real_remove(path)
            raise FileNotFoundError(path)
        real_remove(path)

    monkeypatch.setattr(os, "remove", racing_remove)
    w.write_model_text("m4", 4)          # prunes ckpt_2 under the race
    monkeypatch.setattr(os, "remove", real_remove)
    assert raced["n"] >= 1
    assert [i for i, _ in ckpt.list_checkpoints(d)] == [4]


# ---------------------------------------------------------------------------
# engine resume edge (satellite): early-stopped init model
# ---------------------------------------------------------------------------

def test_init_model_resumes_from_rollback_point():
    """keep_training_booster + early stopping leaves the booster holding
    trees past best_iteration; continuing from it as init_model must
    restore the ROLLBACK point (best_iteration), not graft the dead tail
    — byte-equal to resuming from an explicitly truncated model file."""
    rng = np.random.default_rng(3)
    X = rng.normal(size=(400, 5))
    y = (X[:, 0] + rng.normal(size=400) * 2.0 > 0).astype(float)
    Xv = rng.normal(size=(200, 5))
    yv = (Xv[:, 0] + rng.normal(size=200) * 2.0 > 0).astype(float)
    params = dict(BASE, metric="binary_logloss")
    ds = lgb.Dataset(X, y, params=params)
    b1 = lgb.train(dict(params), ds, 30,
                   valid_sets=[lgb.Dataset(Xv, yv)],
                   early_stopping_rounds=2, verbose_eval=False,
                   keep_training_booster=True)
    assert 0 < b1.best_iteration < 30
    assert b1.num_trees() > b1.best_iteration   # the over-trained tail
    b2 = lgb.train(dict(params), lgb.Dataset(X, y, params=params), 5,
                   init_model=b1, verbose_eval=False)
    assert b2.num_trees() == b1.best_iteration + 5
    truncated = b1.model_to_string(num_iteration=b1.best_iteration)
    b3 = lgb.train(dict(params), lgb.Dataset(X, y, params=params), 5,
                   init_model=lgb.Booster(model_str=truncated),
                   verbose_eval=False)
    assert (b2.model_to_string(num_iteration=-1)
            == b3.model_to_string(num_iteration=-1))


# ---------------------------------------------------------------------------
# two-process distributed kill/resume (slow sibling)
# ---------------------------------------------------------------------------

DIST_WORKER = r"""
import json, os, sys
import numpy as np
sys.path.insert(0, %(repo)r)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
import jax
jax.config.update("jax_num_cpu_devices", 2)
jax.config.update("jax_cpu_collectives_implementation", "gloo")

rank = int(sys.argv[1])
port = sys.argv[2]
out = sys.argv[3]
ckdir = sys.argv[4]
refdir = sys.argv[5]
os.environ["JAX_PROCESS_ID"] = str(rank)

import lightgbm_tpu as lgb
from lightgbm_tpu.resilience import retry
from lightgbm_tpu.resilience.faults import TrainingKilled
from lightgbm_tpu.utils.log import LightGBMError

rng = np.random.default_rng(17)
n, nf = 2400, 6
X = rng.normal(size=(n, nf))
y = (X[:, 1] + 0.5 * X[:, 4] + rng.normal(size=n) * 0.3 > 0).astype(float)

params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
          "num_machines": 2,
          "machines": "127.0.0.1:%%s,127.0.0.1:0" %% port,
          "min_data_in_leaf": 5, "tree_learner": "data",
          "bagging_fraction": 0.8, "bagging_freq": 2,
          "feature_fraction": 0.7,
          "snapshot_freq": 3, "tpu_collective_backoff": 0.0}

def digest(b):
    return [round(float(v), 10) for v in b.predict(X[:300], raw_score=True)]

# (a) uninterrupted 9-round reference, its own snapshot stream
pa = dict(params, checkpoint_dir=refdir)
ref = digest(lgb.train(pa, lgb.Dataset(X, y), 9, verbose_eval=False))

# (b) same run, killed before iteration 6 (both ranks)
pb = dict(params, checkpoint_dir=ckdir, tpu_fault_plan="kill@iter=6")
killed = False
try:
    lgb.train(pb, lgb.Dataset(X, y), 9, verbose_eval=False)
except TrainingKilled:
    killed = True

# (c) auto-resume from the agreed per-rank snapshots -> must match (a)
pc = dict(params, checkpoint_dir=ckdir)
res = digest(lgb.train(pc, lgb.Dataset(X, y), 9, verbose_eval=False))

# (d) drop_collective: the first guarded DCN collective fails on every
# attempt on BOTH ranks -> bounded-retry LightGBMError, no hang
retry.reset_rounds()
pd = dict(params)
pd.pop("snapshot_freq")
pd["tpu_fault_plan"] = "drop_collective@round=1"
err = ""
try:
    lgb.train(pd, lgb.Dataset(X, y), 3, verbose_eval=False)
except LightGBMError as e:
    err = str(e)

with open(out, "w") as fh:
    json.dump({"rank": rank, "killed": killed, "ref": ref, "res": res,
               "match": ref == res, "err": err}, fh)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


@pytest.mark.slow
def test_two_process_distributed_kill_resume(tmp_path):
    """Two jax.distributed processes: checkpointed run killed at iteration
    6, auto-resumed bit-exactly against the uninterrupted reference; plus
    a dropped DCN collective surfacing as a bounded LightGBMError on both
    ranks (no hang)."""
    port = _free_port()
    script = tmp_path / "dist_worker.py"
    script.write_text(DIST_WORKER % {"repo": REPO})
    ckdir = _fresh_dir(tmp_path, "dist_ck")
    refdir = _fresh_dir(tmp_path, "dist_ref")
    outs = [str(tmp_path / ("dr%d.json" % r)) for r in range(2)]
    procs = []
    for r in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.pop("JAX_PLATFORMS", None)
        procs.append(subprocess.Popen(
            [sys.executable, str(script), str(r), str(port), outs[r],
             ckdir, refdir],
            env=env, cwd=str(tmp_path),   # fault-plan flight dumps
            # with no checkpoint_dir land in the worker's cwd — keep
            # that litter in tmp, not the repo root
            stdout=subprocess.PIPE, stderr=subprocess.PIPE))
    for p in procs:
        try:
            _, err = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("distributed resilience worker timed out")
        assert p.returncode == 0, err.decode()[-2000:]
    r0 = json.load(open(outs[0]))
    r1 = json.load(open(outs[1]))
    assert r0["killed"] and r1["killed"]
    assert r0["match"] and r1["match"], (r0, r1)
    assert r0["res"] == r1["res"]            # ranks agree on the model
    for r in (r0, r1):
        assert "failed after" in r["err"], r["err"]
    # per-rank snapshot streams: both ranks wrote rank-tagged files,
    # plus the (rank-less) mesh manifest the elastic resume path reads
    ranks = {n.split(".r")[1] for n in os.listdir(ckdir)
             if n.endswith(".lgc")}
    assert ranks == {"0.lgc", "1.lgc"}
    assert os.path.exists(os.path.join(ckdir, "elastic.manifest.json"))
