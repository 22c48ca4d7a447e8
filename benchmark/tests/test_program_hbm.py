"""The per-layer metrics that read the HBM readings of the run record's spans
and the unnamed part of a first launch (``readers/program_hbm.py``), on a
hand-made ring: the six byte metrics, on the device whose peak ends highest,
with either name of the launch's dispatch span, and None where the spans
carry no ``hbm`` (a commit from before PR 40, the CPU). On a tiny CPU train
the three seconds metrics read something and the six byte metrics nothing,
by design: the CPU keeps no allocator statistics."""
import glob
import json
import os

import numpy as np
import pytest

from readers import program_hbm, program_named

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPECS = {}
for path in sorted(glob.glob(os.path.join(HERE, "layer_metrics", "*.json"))):
    with open(path) as f:
        spec = json.load(f)
    if spec["reader"] == "program_hbm" or spec["name"] in (
            "booster_init_s", "layout_put_s"):
        SPECS[spec["name"]] = spec
BYTE_METRICS = sorted(n for n in SPECS if n.startswith("hbm_"))
SECONDS_METRICS = ("booster_init_s", "layout_put_s", "setup_unnamed_s")
GB = 1e9
CONTAINER = "boosting::TrainMultiIterFast(launch)"


def span(name, ts, dur, parent=None, launch=None, train=1, hbm=None,
         self_s=None, tid=7):
    e = {"name": name, "cat": "setup", "ts": ts, "dur": dur,
         "self": dur if self_s is None else self_s, "tid": tid,
         "train": train}
    if parent is not None:
        e["parent"] = parent
    if launch is not None:
        e["launch"] = launch
    if hbm is not None:
        e["hbm"] = {"open": hbm[0], "close": hbm[1]}
    return e


def hand_made(dispatch="ops::persist_scan(launch)", with_hbm=True):
    """One job on two devices, device 1 the fuller at the end though device
    0 leads before the construct. Readings are [bytes_in_use, peak] a
    device, in GB here and turned into bytes below."""
    def h(open_, close):
        if not with_hbm:
            return None
        return [[[int(b * GB), int(p * GB)] for b, p in end]
                for end in (open_, close)]
    root = "engine::train"
    ring = [
        # an older train, whose spans must not be read
        span(root, 0.0, 5.0, train=1,
             hbm=h([[0, 0], [0, 0]], [[9, 9.5], [0, 0]])),
        # the generator's watermark: 0.6 on device 0, 0.5 on device 1
        span("io::Construct", 10.0, 3.0, train=0,
             hbm=h([[0.1, 0.6], [0.2, 0.5]], [[0.1, 0.6], [0.2, 0.5]])),
        span("tree_learner::ToDevice(layout H2D)", 20.2, 0.5,
             parent="boosting::Init", train=2,
             hbm=h([[0.1, 0.6], [0.2, 0.5]], [[0.1, 0.6], [0.65, 0.65]])),
        span("boosting::Init", 20.1, 1.0, parent=root, train=2, self_s=0.5,
             hbm=h([[0.1, 0.6], [0.2, 0.5]], [[0.1, 0.6], [0.7, 0.7]])),
        span("ops::BuildPersistPayload(pack)", 21.5, 2.0, parent=CONTAINER,
             launch=0, train=2,
             hbm=h([[0.1, 0.6], [0.7, 0.7]], [[0.1, 0.6], [0.7, 0.7]])),
        span("tree_learner::InitCarry(H2D launch)", 23.6, 0.2,
             parent=CONTAINER, launch=0, train=2,
             hbm=h([[0.1, 0.6], [0.7, 0.7]], [[0.1, 0.6], [2.7, 3.5]])),
        span(dispatch, 24.0, 30.0, parent=CONTAINER, launch=0, train=2,
             hbm=h([[0.1, 0.6], [2.7, 3.5]], [[0.1, 0.6], [2.9, 4.0]])),
        span(CONTAINER, 21.4, 32.7, parent=root, launch=0, train=2,
             self_s=0.5,
             hbm=h([[0.1, 0.6], [0.7, 0.7]], [[0.1, 0.6], [2.9, 4.0]])),
        span(CONTAINER, 54.2, 4.0, parent=root, launch=1, train=2,
             hbm=h([[0.1, 0.6], [2.9, 4.0]], [[0.1, 0.6], [2.95, 4.2]])),
        span(CONTAINER, 58.3, 4.0, parent=root, launch=2, train=2,
             hbm=h([[0.1, 0.6], [2.95, 4.2]], [[0.1, 0.6], [3.0, 4.25]])),
        span(root, 20.0, 43.0, train=2, self_s=0.4,
             hbm=h([[0.1, 0.6], [0.2, 0.5]], [[0.1, 0.6], [3.0, 4.25]])),
    ]
    return ring


@pytest.fixture
def ring(monkeypatch):
    """Put a hand-made ring in the run record's place."""
    from lightgbm_tpu import telemetry

    def put(entries):
        monkeypatch.setattr(telemetry, "ring_snapshot",
                            lambda: list(entries))
    return put


def read_all(names):
    return {n: program_hbm.read(SPECS[n], {}) if
            SPECS[n]["reader"] == "program_hbm"
            else program_named.read(SPECS[n], {}) for n in names}


def test_the_nine_metrics_are_declared():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    assert len(SPECS) == 9 and len(BYTE_METRICS) == 6
    for name, spec in SPECS.items():
        entry = declared[name]
        assert "workloads" not in entry         # every cell reports them
        for key in ("unit", "better", "source", "layer", "moves"):
            assert entry[key] == spec[key], (name, key)
        assert entry["moves"] == ("peak_hbm" if name in BYTE_METRICS
                                  else "setup_s")


@pytest.mark.parametrize("dispatch", ["ops::persist_scan(launch)",
                                      "collective::persist_scan(launch)"])
def test_byte_metrics_of_a_hand_made_ring(ring, dispatch):
    ring(hand_made(dispatch))
    got = read_all(BYTE_METRICS)
    # device 1 ends with the highest peak, so every reading is device 1's,
    # the construct's too, where device 0 stood higher
    assert got == pytest.approx({
        "hbm_peak_before_construct_gb": 0.5,
        "hbm_layout_gb": 0.45,
        "hbm_payload_gb": 2.0,
        "hbm_peak_before_launch_gb": 3.5,
        "hbm_peak_first_launch_gb": 4.0,
        "hbm_resident_gb": 3.0,
    })
    assert (got["hbm_peak_before_construct_gb"]
            <= got["hbm_peak_before_launch_gb"]
            <= got["hbm_peak_first_launch_gb"] <= 4.25)


def test_fullest_device_is_the_one_whose_peak_ends_highest(ring):
    entries = hand_made()
    assert program_hbm.fullest_device(entries[1:]) == 1
    # the same job with the devices' readings swapped reads device 0
    for e in entries:
        for end in ("open", "close"):
            e["hbm"][end].reverse()
    ring(entries)
    assert program_hbm.fullest_device(entries[1:]) == 0
    assert program_hbm.read(SPECS["hbm_resident_gb"], {}) == \
        pytest.approx(3.0)


def test_no_hbm_keys_reads_none_and_the_seconds_still_read(ring):
    ring(hand_made(with_hbm=False))
    got = read_all(sorted(SPECS))
    assert all(got[n] is None for n in BYTE_METRICS), got
    assert got["booster_init_s"] == pytest.approx(0.5)
    assert got["layout_put_s"] == pytest.approx(0.5)
    # engine::train's start to launch 0's close is 34.1 s; boosting::Init
    # covers 1.0 and the launch 32.7 of them, and the launch's own is 0.5
    assert got["setup_unnamed_s"] == pytest.approx(34.1 - 33.7 + 0.5)


def test_overlapping_children_are_covered_once(ring):
    """A trace reports the traces it called before itself, both under the
    span open on the thread: the union of the children counts, not the sum;
    a span of another thread covers nothing."""
    entries = hand_made(with_hbm=False)
    entries[-1:-1] = [
        span("jax::jaxpr_trace", 21.15, 0.2, parent="engine::train",
             train=2),
        span("jax::jaxpr_trace", 21.12, 0.25, parent="engine::train",
             train=2),
        span("jax::backend_compile", 21.0, 0.1, parent="engine::train",
             train=2, tid=8),
    ]
    ring(entries)
    assert program_hbm.read(SPECS["setup_unnamed_s"], {}) == \
        pytest.approx(34.1 - 33.7 - 0.25 + 0.5)


def test_a_program_without_the_record_reads_none(ring):
    ring([])
    assert all(v is None for v in read_all(sorted(SPECS)).values())
    ring([e for e in hand_made() if e["name"] != CONTAINER])
    got = read_all(sorted(SPECS))
    assert got["setup_unnamed_s"] is None
    assert got["hbm_peak_first_launch_gb"] is None
    assert got["hbm_resident_gb"] is None
    assert got["hbm_layout_gb"] == pytest.approx(0.45)


@pytest.mark.real_allocator
def test_cpu_train_reports_the_seconds_and_not_the_bytes():
    import lightgbm_tpu as lgb
    from lightgbm_tpu import telemetry
    telemetry.disable()
    telemetry.reset()
    rng = np.random.default_rng(40)
    X = rng.normal(size=(2000, 8)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "metric": "none", "tpu_persist_scan": "force"}
    ds = lgb.Dataset(X, y)
    ds.construct()
    lgb.train(params, ds, 32, verbose_eval=False)
    got = read_all(sorted(SPECS))
    assert all(got[n] is None for n in BYTE_METRICS), got
    for name in SECONDS_METRICS:
        assert got[name] is not None and got[name] >= 0, got
    root = [e for e in telemetry.ring_snapshot()
            if e["name"] == "engine::train"][-1]
    assert got["setup_unnamed_s"] + got["booster_init_s"] \
        + got["layout_put_s"] <= root["dur"]
