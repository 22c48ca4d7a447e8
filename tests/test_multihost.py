"""Multi-host end-to-end: two jax.distributed processes on localhost train
through parallel/multihost.train_multihost (Network::Init -> row shard ->
distributed binning -> sharded growth) and must produce identical models
on every rank that match a single-process replay with the same layout
(application.cpp:164-210 contract)."""
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import json, os, sys
import numpy as np
sys.path.insert(0, %(repo)r)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
import jax
jax.config.update("jax_num_cpu_devices", 2)
jax.config.update("jax_cpu_collectives_implementation", "gloo")
from lightgbm_tpu.config import Config
from lightgbm_tpu.parallel.multihost import shard_rows, train_multihost

rank = int(sys.argv[1])
port = sys.argv[2]
out = sys.argv[3]

rng = np.random.default_rng(7)
n, nf = 3000, 8
X = rng.normal(size=(n, nf))
y = (X[:, 0] - 0.7 * X[:, 3] + rng.normal(size=n) * 0.3 > 0).astype(float)

cfg = Config({"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "num_machines": 2,
              "machines": "127.0.0.1:%%s,127.0.0.1:0" %% port,
              "min_data_in_leaf": 5, "tree_learner": "data",
              "bagging_fraction": 0.8, "bagging_freq": 2,
              "metric": "binary_logloss", "early_stopping_round": 50})
idx = shard_rows(n, rank, 2, False)
w = np.ones(n)
Xv = rng.normal(size=(400, nf))
yv = (Xv[:, 0] - 0.7 * Xv[:, 3] > 0).astype(float)
vidx = shard_rows(400, rank, 2, False)
trees, mappers, ds, score = train_multihost(
    cfg, X[idx], y[idx], num_rounds=12, process_id=rank,
    weight_local=w[idx], X_valid=Xv[vidx], y_valid=yv[vidx])
digest = [[int(t.num_leaves),
           [int(f) for f in t.split_feature[:t.num_leaves - 1]],
           [round(float(v), 6) for v in t.threshold[:t.num_leaves - 1]],
           [round(float(v), 6) for v in t.leaf_value[:t.num_leaves]]]
          for t in trees]
with open(out, "w") as fh:
    json.dump({"rank": rank, "digest": digest,
               "nbins": [m.num_bin for m in mappers]}, fh)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


@pytest.mark.slow
def test_two_process_training(tmp_path):
    port = _free_port()
    script = tmp_path / "worker.py"
    script.write_text(WORKER % {"repo": REPO})
    outs = [str(tmp_path / f"rank{r}.json") for r in range(2)]
    procs = []
    for r in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.pop("JAX_PLATFORMS", None)
        procs.append(subprocess.Popen(
            [sys.executable, str(script), str(r), str(port), outs[r]],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE))
    for p in procs:
        try:
            _, err = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multihost worker timed out")
        assert p.returncode == 0, err.decode()[-2000:]
    r0 = json.load(open(outs[0]))
    r1 = json.load(open(outs[1]))
    # every rank materializes the identical model + identical global binning
    assert r0["nbins"] == r1["nbins"]
    assert r0["digest"] == r1["digest"]
    # the model learned (root split on an informative feature)
    assert r0["digest"][0][1][0] in (0, 3)

    # single-process replay with the identical layout + row order must
    # reproduce the distributed model (DataParallel psum == multihost psum)
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    import jax
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data.bin_mapper import BinMapper, BinType, kZeroThreshold
    from lightgbm_tpu.parallel.distributed import (_feature_slice,
                                                   distributed_bin_mappers)
    from lightgbm_tpu.parallel.multihost import shard_rows

    rng = np.random.default_rng(7)
    n, nf = 3000, 8
    X = rng.normal(size=(n, nf))
    y = (X[:, 0] - 0.7 * X[:, 3] + rng.normal(size=n) * 0.3 > 0).astype(float)
    cfg = Config({"objective": "binary", "num_leaves": 15, "verbosity": -1,
                  "min_data_in_leaf": 5})
    shards = [shard_rows(n, r, 2, False) for r in range(2)]
    samples = [X[s][:int(cfg.bin_construct_sample_cnt)] for s in shards]

    # emulate the 2-rank mapper allgather in process
    blobs = {}
    for r in range(2):
        distributed_bin_mappers(
            np.ascontiguousarray(samples[r]), len(shards[r]), cfg,
            rank=r, world=2,
            allgather=lambda p, r=r: (blobs.__setitem__(r, p)
                                      or [p, p])[:0] or [p, p])
    mappers = []
    for r in range(2):
        for st in json.loads(blobs[r].decode()):
            mappers.append(BinMapper.from_state(st))
    assert [m.num_bin for m in mappers] == r0["nbins"]


PY_API_WORKER = r"""
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
os.environ["JAX_PROCESS_ID"] = str(rank)

import lightgbm_tpu as lgb

rng = np.random.default_rng(11)
n, nf = 2400, 6
X = rng.normal(size=(n, nf))
y = (X[:, 1] + 0.5 * X[:, 4] + rng.normal(size=n) * 0.3 > 0).astype(float)

params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
          "num_machines": 2,
          "machines": "127.0.0.1:%%s,127.0.0.1:0" %% port,
          "min_data_in_leaf": 5, "tree_learner": "data"}
bst = lgb.train(params, lgb.Dataset(X, y), num_boost_round=8,
                verbose_eval=False)
pred = bst.predict(X[:200])
with open(out, "w") as fh:
    json.dump({"rank": rank,
               "pred": [round(float(p), 8) for p in pred],
               "model_hash": hash(bst.model_to_string()) %% (2**31)}, fh)
"""


@pytest.mark.slow
def test_python_api_distributed_train(tmp_path):
    """lgb.train(params with num_machines=2) from two processes — the
    Python-API distributed entry (reference: network params on Booster,
    basic.py set_network) — returns the identical full model on every
    rank."""
    port = _free_port()
    script = tmp_path / "pyapi_worker.py"
    script.write_text(PY_API_WORKER % {"repo": REPO})
    outs = [str(tmp_path / f"api_rank{r}.json") for r in range(2)]
    procs = []
    for r in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.pop("JAX_PLATFORMS", None)
        procs.append(subprocess.Popen(
            [sys.executable, str(script), str(r), str(port), outs[r]],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE))
    for p in procs:
        try:
            _, err = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("python-api multihost worker timed out")
        assert p.returncode == 0, err.decode()[-2000:]
    r0 = json.load(open(outs[0]))
    r1 = json.load(open(outs[1]))
    assert r0["pred"] == r1["pred"]
    # the model learned something nontrivial
    assert np.std(r0["pred"]) > 0.05


RESUME_WORKER = r"""
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
os.environ["JAX_PROCESS_ID"] = str(rank)

import lightgbm_tpu as lgb

rng = np.random.default_rng(51)
n, nf = 2400, 6
X = rng.normal(size=(n, nf))
y = (X[:, 1] + 0.5 * X[:, 4] + rng.normal(size=n) * 0.3 > 0).astype(float)

params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
          "num_machines": 2,
          "machines": "127.0.0.1:%%s,127.0.0.1:0" %% port,
          "min_data_in_leaf": 5, "tree_learner": "data"}
b6 = lgb.train(dict(params), lgb.Dataset(X, y), num_boost_round=6,
               verbose_eval=False)
b12 = lgb.train(dict(params), lgb.Dataset(X, y), num_boost_round=6,
                init_model=b6, verbose_eval=False)
p6 = b6.predict(X[:400])
p12 = b12.predict(X[:400])
ll = lambda p: float(-np.mean(y[:400] * np.log(np.clip(p, 1e-9, 1))
                              + (1 - y[:400])
                              * np.log(np.clip(1 - p, 1e-9, 1))))
with open(out, "w") as fh:
    json.dump({"rank": rank, "trees6": b6.num_trees(),
               "trees12": b12.num_trees(),
               "loss6": ll(p6), "loss12": ll(p12),
               "pred": [round(float(p), 8) for p in p12]}, fh)
"""


@pytest.mark.slow
def test_python_api_distributed_init_model_resume(tmp_path):
    """Continued training over num_machines=2: each rank seeds its score
    shard from the init model's raw predictions and the resumed booster
    carries init + new trees (train 6 -> resume 6 == 12-tree model that
    keeps improving), identical on every rank."""
    port = _free_port()
    script = tmp_path / "resume_worker.py"
    script.write_text(RESUME_WORKER % {"repo": REPO})
    outs = [str(tmp_path / f"resume_rank{r}.json") for r in range(2)]
    procs = []
    for r in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.pop("JAX_PLATFORMS", None)
        procs.append(subprocess.Popen(
            [sys.executable, str(script), str(r), str(port), outs[r]],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE))
    for p in procs:
        try:
            _, err = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("resume multihost worker timed out")
        assert p.returncode == 0, err.decode()[-2000:]
    r0 = json.load(open(outs[0]))
    r1 = json.load(open(outs[1]))
    assert r0["pred"] == r1["pred"]
    assert r0["trees6"] == 6 and r0["trees12"] == 12
    assert r0["loss12"] < r0["loss6"]


MC_WORKER = r"""
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
os.environ["JAX_PROCESS_ID"] = str(rank)

import lightgbm_tpu as lgb

rng = np.random.default_rng(21)
n, nf = 2400, 6
X = rng.normal(size=(n, nf))
logits = np.stack([X[:, 0], X[:, 1] - 0.5 * X[:, 2], -X[:, 0] + X[:, 3]])
y = np.argmax(logits + rng.normal(size=(3, n)) * 0.3, axis=0).astype(float)

params = {"objective": "multiclass", "num_class": 3, "num_leaves": 15,
          "verbosity": -1, "num_machines": 2,
          "machines": "127.0.0.1:%%s,127.0.0.1:0" %% port,
          "min_data_in_leaf": 5, "tree_learner": "data"}
bst = lgb.train(params, lgb.Dataset(X, y), num_boost_round=10,
                verbose_eval=False)
pred = bst.predict(X[:300])
acc = float((np.argmax(pred, axis=1) == y[:300]).mean())
with open(out, "w") as fh:
    json.dump({"rank": rank, "acc": acc,
               "pred": [round(float(p), 8) for p in pred.ravel()[:600]]},
              fh)
"""


@pytest.mark.slow
def test_python_api_distributed_multiclass(tmp_path):
    """Multiclass (K trees per iteration) over two jax.distributed
    processes: one [K, N] gradient pass per iteration, K sharded class
    trees, identical model on every rank (gbdt.cpp:372-435 contract)."""
    port = _free_port()
    script = tmp_path / "mc_worker.py"
    script.write_text(MC_WORKER % {"repo": REPO})
    outs = [str(tmp_path / f"mc_rank{r}.json") for r in range(2)]
    procs = []
    for r in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.pop("JAX_PLATFORMS", None)
        procs.append(subprocess.Popen(
            [sys.executable, str(script), str(r), str(port), outs[r]],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE))
    for p in procs:
        try:
            _, err = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multiclass multihost worker timed out")
        assert p.returncode == 0, err.decode()[-2000:]
    r0 = json.load(open(outs[0]))
    r1 = json.load(open(outs[1]))
    assert r0["pred"] == r1["pred"]
    assert r0["acc"] > 0.8, r0["acc"]


LTR_WORKER = r"""
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
os.environ["JAX_PROCESS_ID"] = str(rank)

import lightgbm_tpu as lgb

rng = np.random.default_rng(31)
nq, docs = 240, 10
n = nq * docs
X = rng.normal(size=(n, 5))
rel = np.clip((X[:, 0] + 0.5 * X[:, 1]
               + rng.normal(size=n) * 0.4) * 1.2 + 1.5, 0, 4)
y = np.floor(rel)
group = np.full(nq, docs)

params = {"objective": "lambdarank", "num_leaves": 15, "verbosity": -1,
          "num_machines": 2,
          "machines": "127.0.0.1:%%s,127.0.0.1:0" %% port,
          "min_data_in_leaf": 5, "tree_learner": "data",
          "metric": "ndcg", "eval_at": [5]}
vX = rng.normal(size=(400, 5))
vrel = np.clip((vX[:, 0] + 0.5 * vX[:, 1]) * 1.2 + 1.5, 0, 4)
vy = np.floor(vrel)
vgroup = np.full(40, 10)
bst = lgb.train(params, lgb.Dataset(X, y, group=group),
                num_boost_round=10,
                valid_sets=[lgb.Dataset(vX, vy, group=vgroup)],
                verbose_eval=False)
pred = bst.predict(X[:200])
with open(out, "w") as fh:
    json.dump({"rank": rank,
               "pred": [round(float(p), 8) for p in pred]}, fh)
"""


@pytest.mark.slow
def test_python_api_distributed_lambdarank(tmp_path):
    """Lambdarank over two jax.distributed processes: queries shard whole
    to ranks AND to local devices (padded blocks), per-query lambdas stay
    shard-local, ndcg aggregates query-weighted — every rank returns the
    identical model."""
    port = _free_port()
    script = tmp_path / "ltr_worker.py"
    script.write_text(LTR_WORKER % {"repo": REPO})
    outs = [str(tmp_path / f"ltr_rank{r}.json") for r in range(2)]
    procs = []
    for r in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.pop("JAX_PLATFORMS", None)
        procs.append(subprocess.Popen(
            [sys.executable, str(script), str(r), str(port), outs[r]],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE))
    for p in procs:
        try:
            _, err = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("lambdarank multihost worker timed out")
        assert p.returncode == 0, err.decode()[-2000:]
    r0 = json.load(open(outs[0]))
    r1 = json.load(open(outs[1]))
    assert r0["pred"] == r1["pred"]
    assert np.std(r0["pred"]) > 0.05   # learned a nontrivial ranking


GOSS_WORKER = r"""
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
os.environ["JAX_PROCESS_ID"] = str(rank)

import lightgbm_tpu as lgb

rng = np.random.default_rng(31)
n, nf = 2400, 6
X = rng.normal(size=(n, nf))
y = (X[:, 1] + 0.5 * X[:, 4] + rng.normal(size=n) * 0.3 > 0).astype(float)

params = {"objective": "binary", "boosting": "goss", "num_leaves": 15,
          "verbosity": -1, "num_machines": 2, "learning_rate": 0.2,
          "machines": "127.0.0.1:%%s,127.0.0.1:0" %% port,
          "top_rate": 0.2, "other_rate": 0.1,
          "min_data_in_leaf": 5, "tree_learner": "data"}
bst = lgb.train(params, lgb.Dataset(X, y), num_boost_round=12,
                verbose_eval=False)
pred = bst.predict(X[:400])
acc = float(((pred > 0.5) == y[:400]).mean())
with open(out, "w") as fh:
    json.dump({"rank": rank, "acc": acc,
               "pred": [round(float(p), 8) for p in pred[:200]]}, fh)
"""


@pytest.mark.slow
def test_python_api_distributed_goss(tmp_path):
    """boosting=goss over num_machines=2: the GLOBAL |g*h| threshold comes
    from the radix select with psum'd counts, warmup keeps all rows, and
    every rank materializes the identical model."""
    port = _free_port()
    script = tmp_path / "goss_worker.py"
    script.write_text(GOSS_WORKER % {"repo": REPO})
    outs = [str(tmp_path / f"goss_rank{r}.json") for r in range(2)]
    procs = []
    for r in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.pop("JAX_PLATFORMS", None)
        procs.append(subprocess.Popen(
            [sys.executable, str(script), str(r), str(port), outs[r]],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE))
    for p in procs:
        try:
            _, err = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("goss multihost worker timed out")
        assert p.returncode == 0, err.decode()[-2000:]
    r0 = json.load(open(outs[0]))
    r1 = json.load(open(outs[1]))
    assert r0["pred"] == r1["pred"]
    assert r0["acc"] > 0.85, r0["acc"]


MV_WORKER = r"""
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
os.environ["JAX_PROCESS_ID"] = str(rank)

import lightgbm_tpu as lgb

rng = np.random.default_rng(41)
n, nf = 2400, 40
X = np.zeros((n, nf))
hit = rng.random((n, nf)) < 0.15
X[hit] = rng.normal(loc=1.0, size=int(hit.sum()))
beta = rng.normal(size=nf)
y = ((X @ beta) > 0).astype(float)

params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
          "num_machines": 2, "tpu_multival": "force",
          "machines": "127.0.0.1:%%s,127.0.0.1:0" %% port,
          "min_data_in_leaf": 5, "tree_learner": "data"}
bst = lgb.train(params, lgb.Dataset(X, y), num_boost_round=8,
                verbose_eval=False)
pred = bst.predict(X[:300])
acc = float(((pred > 0.5) == y[:300]).mean())
with open(out, "w") as fh:
    json.dump({"rank": rank, "acc": acc,
               "pred": [round(float(p), 8) for p in pred[:150]]}, fh)
"""


@pytest.mark.slow
def test_python_api_distributed_multival(tmp_path):
    """The multi-value (ELL) layout over num_machines=2: the row-sparse
    arrays shard with the rows across processes and the scatter
    histograms psum; both ranks materialize the identical model."""
    port = _free_port()
    script = tmp_path / "mv_worker.py"
    script.write_text(MV_WORKER % {"repo": REPO})
    outs = [str(tmp_path / f"mv_rank{r}.json") for r in range(2)]
    procs = []
    for r in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.pop("JAX_PLATFORMS", None)
        procs.append(subprocess.Popen(
            [sys.executable, str(script), str(r), str(port), outs[r]],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE))
    for p in procs:
        try:
            _, err = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multival multihost worker timed out")
        assert p.returncode == 0, err.decode()[-2000:]
    r0 = json.load(open(outs[0]))
    r1 = json.load(open(outs[1]))
    assert r0["pred"] == r1["pred"]
    assert r0["acc"] > 0.8, r0["acc"]


QUANT_WORKER = r"""
import json, os, sys
import numpy as np
sys.path.insert(0, %(repo)r)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
import jax
jax.config.update("jax_num_cpu_devices", 2)
jax.config.update("jax_cpu_collectives_implementation", "gloo")
from lightgbm_tpu.config import Config
from lightgbm_tpu.parallel.fingerprint import DivergenceError
from lightgbm_tpu.parallel.multihost import shard_rows, train_multihost
from lightgbm_tpu.resilience import faults

rank = int(sys.argv[1])
port = sys.argv[2]
out = sys.argv[3]

rng = np.random.default_rng(9)
n, nf = 3000, 8
X = rng.normal(size=(n, nf))
y = (X[:, 0] - 0.7 * X[:, 3] > 0).astype(float)
idx = shard_rows(n, rank, 2, False)

base = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
        "num_machines": 2,
        "machines": "127.0.0.1:%%s,127.0.0.1:0" %% port,
        "min_data_in_leaf": 5, "tree_learner": "data",
        "tpu_hist_quant": "int16", "tpu_divergence_probe": "on"}

# phase 1: quantized training must be bit-identical on every rank —
# the PR 14 divergence probe (model CRC + hist CRC per iteration over
# the metrics-values collective) must NOT fire
cfg = Config(dict(base))
faults.configure_from_config(cfg)
trees, mappers, ds, score = train_multihost(
    cfg, X[idx], y[idx], num_rounds=8, process_id=rank)
digest = [[int(t.num_leaves),
           [int(f) for f in t.split_feature[:t.num_leaves - 1]],
           [round(float(v), 9) for v in t.leaf_value[:t.num_leaves]]]
          for t in trees]

# phase 2: a genuinely corrupted quantized payload must still be CAUGHT
# — the corrupt_hist chaos verb perturbs rank 1's hist fingerprint at
# round 2, and the probe must raise on BOTH ranks naming hist
probe_fired = False
named_hist = False
cfg2 = Config(dict(base,
                   tpu_fault_plan="corrupt_hist@round=2;rank=1"))
faults.configure_from_config(cfg2)
try:
    train_multihost(cfg2, X[idx], y[idx], num_rounds=6, process_id=rank)
except DivergenceError as e:
    probe_fired = True
    named_hist = "hist" in str(e)

with open(out, "w") as fh:
    json.dump({"rank": rank, "digest": digest,
               "probe_fired": probe_fired,
               "named_hist": named_hist}, fh)
"""


@pytest.mark.slow
def test_two_process_quantized_bitexact_and_probe(tmp_path):
    """tpu_hist_quant=int16 over two real processes: the rank-uniform
    seeded stochastic rounding reconstructs the identical global
    histograms on every rank, so training is BIT-IDENTICAL and the
    divergence probe stays quiet — while a corrupt_hist chaos seed on
    the same quantized path still trips the probe on both ranks
    (quantization must not launder genuine corruption)."""
    port = _free_port()
    script = tmp_path / "quant_worker.py"
    script.write_text(QUANT_WORKER % {"repo": REPO})
    outs = [str(tmp_path / f"q_rank{r}.json") for r in range(2)]
    procs = []
    for r in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.pop("JAX_PLATFORMS", None)
        procs.append(subprocess.Popen(
            [sys.executable, str(script), str(r), str(port), outs[r]],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE))
    for p in procs:
        try:
            _, err = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("quantized multihost worker timed out")
        assert p.returncode == 0, err.decode()[-2000:]
    r0 = json.load(open(outs[0]))
    r1 = json.load(open(outs[1]))
    assert r0["digest"] == r1["digest"], \
        "int16-quantized training diverged across ranks"
    assert r0["digest"][0][1][0] in (0, 3)      # learned the signal
    for r in (r0, r1):
        assert r["probe_fired"], "corrupt_hist probe did not fire"
        assert r["named_hist"], "probe must blame the hist component"
