"""Seed-determined chaos tier: random-but-replayable fault schedules.

Every schedule is a pure function of its seed (zlib.crc32 arithmetic,
same determinism contract as faults.py — no RNG object, no clock), so a
failing chaos run replays identically from its seed. The cheap smoke
(single-host kill/resize/corrupt schedules + a guard-level stall
schedule) runs in tier-1 under the ``chaos`` marker; the real
two-process world=2 schedule — kill + straggler stall + elastic resume
onto world=1 — is the slow sibling at the bottom.
"""
import json
import os
import shutil
import socket
import subprocess
import sys
import zlib

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.resilience import checkpoint as ckpt
from lightgbm_tpu.resilience import faults, retry
from lightgbm_tpu.resilience.faults import FaultPlan, TrainingKilled

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.chaos


def _h(seed: int, field: bytes) -> int:
    # one crc PER FIELD: bit-slices of a single crc correlate across
    # adjacent seeds (crc32 is linear in its input)
    return zlib.crc32(b"lgbtpu-chaos:%s:%d" % (field, seed))


def chaos_schedule(seed: int) -> dict:
    """The seed's fault schedule: which verb kills the run, when, what
    rides along. Pure integer arithmetic on crcs — replayable forever."""
    kill_iter = 3 + _h(seed, b"kill") % 9       # 3..11
    freq = 2 + _h(seed, b"freq") % 3            # snapshot_freq 2..4
    resize = _h(seed, b"resize") % 2 == 0       # resize@ vs kill@
    corrupt = _h(seed, b"corrupt") % 2 == 0     # poison the 1st snapshot
    plan = ("resize@iter=%d;world=2" % kill_iter if resize
            else "kill@iter=%d" % kill_iter)
    if corrupt:
        plan += ",corrupt_checkpoint@n=1"
    return {"seed": seed, "kill_iter": kill_iter, "freq": freq,
            "resize": resize, "corrupt": corrupt, "plan": plan,
            "stall_round": 1 + _h(seed, b"stall") % 3,
            "stall_secs": 1}


def test_schedules_are_deterministic_and_diverse():
    a = [chaos_schedule(s) for s in range(16)]
    b = [chaos_schedule(s) for s in range(16)]
    assert a == b
    # the seed space actually exercises every verb combination
    assert any(s["resize"] for s in a) and any(not s["resize"] for s in a)
    assert any(s["corrupt"] for s in a) and any(not s["corrupt"] for s in a)
    assert len({s["freq"] for s in a}) >= 2


def _make_binary(n=900, nf=6, seed=0):
    # identical shape/params to test_resilience: the chaos trains reuse
    # the same compiled programs inside the tier-1 process
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, nf))
    y = (X[:, 0] - 0.5 * X[:, 2] + rng.normal(size=n) * 0.3 > 0)
    return X, y.astype(float)


BASE = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
        "min_data_in_leaf": 5, "learning_rate": 0.3,
        "bagging_fraction": 0.8, "bagging_freq": 2,
        "feature_fraction": 0.7}


# seeds chosen so tier-1 drives one plain kill@ and one
# resize@+corrupt_checkpoint@ schedule (the diversity test above proves
# the space; these pin the paths cheaply)
@pytest.mark.parametrize("seed", [0, 4])
def test_chaos_kill_resume_single_host(tmp_path, seed):
    """One seed-determined schedule end to end: train, die at the
    scheduled point (kill or resize, maybe through a corrupted
    snapshot), resume, finish bit-exact with the uninterrupted run."""
    sched = chaos_schedule(seed)
    X, y = _make_binary()
    d = str(tmp_path / ("chaos%d" % seed))
    os.makedirs(d)
    params = dict(BASE, snapshot_freq=sched["freq"], checkpoint_dir=d)
    model_a = lgb.train(dict(params), lgb.Dataset(X, y), 12,
                        verbose_eval=False).model_to_string(
        num_iteration=-1)
    shutil.rmtree(d)
    os.makedirs(d)
    with pytest.raises(TrainingKilled) as exc:
        lgb.train(dict(params, tpu_fault_plan=sched["plan"]),
                  lgb.Dataset(X, y), 12, verbose_eval=False)
    if sched["resize"]:
        assert exc.value.target_world == 2
    # the scheduled death left only boundary-aligned snapshots behind
    snaps = [i for i, _ in ckpt.list_checkpoints(d)]
    assert all(i % sched["freq"] == 0 and i <= sched["kill_iter"]
               for i in snaps)
    resumed = lgb.train(dict(params), lgb.Dataset(X, y), 12,
                        verbose_eval=False)
    assert resumed.num_trees() == 12
    assert resumed.model_to_string(num_iteration=-1) == model_a


@pytest.mark.parametrize("seed", [1, 2])
def test_chaos_stall_schedule_guard_level(seed):
    """The stall half of a schedule, driven through the guard directly:
    exactly the scheduled round stalls, the soft watchdog counts it,
    every call still succeeds."""
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.telemetry import flight
    sched = chaos_schedule(seed)
    telemetry.enable("timers")
    # a previous test may have left the flight recorder armed at the
    # cwd; the stall path dumps whenever armed, so disarm explicitly
    flight.disarm()
    try:
        telemetry.reset()
        retry.reset_rounds()
        faults._PLAN = FaultPlan("stall@round=%d;secs=%d"
                                 % (sched["stall_round"],
                                    sched["stall_secs"]))
        retry._POLICY = retry.RetryPolicy(timeout_s=30.0, retries=0,
                                          backoff_s=0.0,
                                          soft_timeout_s=0.1)
        for r in range(1, 4):
            assert retry.guard("allgather:chaos%d" % r,
                               lambda r=r: r) == r
        counts = telemetry.events.counts_snapshot()
        assert counts.get("collective::stall", 0) == 1, counts
        assert counts.get("collective::timeout", 0) == 0, counts
    finally:
        faults.reset()
        retry._POLICY = retry.RetryPolicy()
        telemetry.reset()
        telemetry.disable()


# ---------------------------------------------------------------------------
# the real thing (slow): two-process world=2 chaos schedule — straggler
# stall mid-run, scheduled death, elastic resume onto world=1
# ---------------------------------------------------------------------------

CHAOS_WORKER = r"""
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
plan = sys.argv[6]
os.environ["JAX_PROCESS_ID"] = str(rank)

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.resilience.faults import TrainingKilled

rng = np.random.default_rng(23)
n, nf = 2400, 6
X = rng.normal(size=(n, nf))
y = (X[:, 1] + 0.5 * X[:, 4] + rng.normal(size=n) * 0.3 > 0).astype(float)

params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
          "num_machines": 2,
          "machines": "127.0.0.1:%%s,127.0.0.1:0" %% port,
          "min_data_in_leaf": 5, "tree_learner": "data",
          "bagging_fraction": 0.8, "bagging_freq": 2,
          "snapshot_freq": 3, "tpu_collective_backoff": 0.0,
          "tpu_collective_soft_timeout": 0.05,
          "tpu_telemetry": "timers"}

def digest(b):
    return [round(float(v), 10) for v in b.predict(X[:300], raw_score=True)]

# (a) uninterrupted world=2 reference (its own snapshot stream)
pa = dict(params, checkpoint_dir=refdir)
ref_b = lgb.train(pa, lgb.Dataset(X, y), 9, verbose_eval=False)
ref = digest(ref_b)

# (b) the chaos schedule: a straggler stall mid-run, then the scheduled
# death — both ranks die at the same iteration boundary
telemetry.enable("timers"); telemetry.reset()
pb = dict(params, checkpoint_dir=ckdir, tpu_fault_plan=plan)
killed = False
try:
    lgb.train(pb, lgb.Dataset(X, y), 9, verbose_eval=False)
except TrainingKilled:
    killed = True
counts = telemetry.events.counts_snapshot()
stalls = counts.get("collective::stall", 0)
telemetry.reset(); telemetry.disable()

with open(out, "w") as fh:
    json.dump({"rank": rank, "killed": killed, "ref": ref,
               "stalls": stalls,
               "model_ref": ref_b.model_to_string(num_iteration=-1)}, fh)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


@pytest.mark.slow
def test_two_process_chaos_elastic_resume(tmp_path):
    """The full elastic chaos story: a REAL two-process world=2 run hits
    a seed-determined schedule (straggler stall on a guarded DCN
    collective, then death at an iteration boundary), leaving rank-
    tagged shards + the mesh manifest + per-rank flight dumps; the
    parent process then resumes the run ELASTICALLY on world=1 and must
    reproduce the uninterrupted world=2 model."""
    sched = chaos_schedule(7)
    plan = "kill@iter=6,stall@round=%d;secs=1" % sched["stall_round"]
    port = _free_port()
    script = tmp_path / "chaos_worker.py"
    script.write_text(CHAOS_WORKER % {"repo": REPO})
    ckdir = str(tmp_path / "chaos_ck")
    refdir = str(tmp_path / "chaos_ref")
    os.makedirs(ckdir)
    os.makedirs(refdir)
    outs = [str(tmp_path / ("cw%d.json" % r)) for r in range(2)]
    procs = []
    for r in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.pop("JAX_PLATFORMS", None)
        procs.append(subprocess.Popen(
            [sys.executable, str(script), str(r), str(port), outs[r],
             ckdir, refdir, plan],
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
            pytest.fail("chaos worker timed out")
        assert p.returncode == 0, err.decode()[-2000:]
    r0 = json.load(open(outs[0]))
    r1 = json.load(open(outs[1]))
    assert r0["killed"] and r1["killed"]
    assert r0["ref"] == r1["ref"]
    # the straggler stall was observed by the soft watchdog on each rank
    assert r0["stalls"] >= 1 and r1["stalls"] >= 1, (r0["stalls"],
                                                     r1["stalls"])
    # the dead mesh left both rank streams, the manifest, and postmortems
    ranks = {n.split(".r")[1] for n in os.listdir(ckdir)
             if n.endswith(".lgc")}
    assert ranks == {"0.lgc", "1.lgc"}
    from lightgbm_tpu.resilience import reshard
    man = reshard.load_manifest(ckdir)
    assert man is not None and man["world"] == 2
    assert os.path.exists(os.path.join(ckdir, "flight.r0.json"))
    assert os.path.exists(os.path.join(ckdir, "flight.r1.json"))

    # elastic resume IN THIS PROCESS on world=1: same params minus the
    # mesh (num_machines/machines are resume-volatile by design)
    rng = np.random.default_rng(23)
    n, nf = 2400, 6
    X = rng.normal(size=(n, nf))
    y = (X[:, 1] + 0.5 * X[:, 4]
         + rng.normal(size=n) * 0.3 > 0).astype(float)
    rp = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
          "min_data_in_leaf": 5, "tree_learner": "data",
          "bagging_fraction": 0.8, "bagging_freq": 2,
          "snapshot_freq": 3, "tpu_collective_backoff": 0.0,
          "tpu_collective_soft_timeout": 0.05,
          "checkpoint_dir": ckdir}
    res = lgb.train(rp, lgb.Dataset(X, y), 9, verbose_eval=False)
    assert res.num_trees() == 9
    assert reshard.load_manifest(ckdir)["world"] == 1
    got = [round(float(v), 10) for v in res.predict(X[:300],
                                                    raw_score=True)]
    assert got == r0["ref"], "elastic world=2 -> world=1 resume diverged"
