"""The reduction from a profiler trace to seconds, checked on a recorded trace
(the head of one traced launch of ``higgs.train_steady`` on a TPU v5e, cut by
``tools/slice_trace.py``, which also wrote the expected numbers by a parser
and an interval sweep of its own), and a ``--trace 1`` run through the
harness's entry point that reads that trace in place of the CPU's."""
import json
import os

import pytest

from harness import xtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "higgs_launch_head.xplane.pb")


def test_reducer_reads_the_recorded_trace():
    with open(RECORDED + ".expected.json") as f:
        want = json.load(f)
    trace = xtrace.load(RECORDED)
    assert len(trace["device"]) == want["planes"] == 1
    events = next(iter(trace["device"].values()))
    assert len(events) == want["events"]
    assert not any(xtrace.is_container(name) for _, _, name in events)
    span = (max(e for _, e, _ in events) - min(s for s, _, _ in events)) * 1e-9
    got = xtrace.reduce(trace, span)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-5)
    assert 0.99 * span < got["busy_s"] <= span     # a container would read 1
    assert set(got["op_seconds"]) == set(want["op_seconds"])
    for name, seconds in want["op_seconds"].items():
        # the profiler's reader rounds every event to whole nanoseconds
        assert got["op_seconds"][name] == pytest.approx(
            seconds, rel=1e-4, abs=1e-9 * want["events"])
    assert got["device_ops"][0][0] == "split_pass"
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10
    assert xtrace.kernel_seconds(got, ["root_hist", "seg_hist"]) == \
        pytest.approx(want["op_seconds"]["root_hist"]
                      + want["op_seconds"]["seg_hist"], rel=1e-4)
    assert xtrace.kernel_seconds(got, ["no_such_kernel"]) is None


def test_traced_run_reports_the_per_layer_metrics(rehearsal, monkeypatch,
                                                  capsys):
    monkeypatch.setattr(xtrace, "find_xplane", lambda logdir: RECORDED)
    rc = rehearsal.main(["--workload", "higgs.train_steady", "--seed", "41",
                         "--seconds", "0.5", "--trace", "1"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    with open(os.path.join(os.path.dirname(DATA), "..", "..",
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = {m["name"] for m in bench["per_layer"]}
    assert set(result["metrics"]) == names, set(result["metrics"]) ^ names
    for name in ("train_step_mfu", "hist_roofline", "partition_roofline"):
        assert result["metrics"][name]["value"] > 0
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    assert result["attempted"] >= 32        # the traced launch and one more
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(result)[-1] == "checks"
