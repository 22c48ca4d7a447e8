"""``correct`` is a comparison that has been shown to fail: the control (the
reference in bfloat16) and every fault a training cell can have come out as
not correct, and a sound run comes out correct, through the harness's own
entry point with the chip look stubbed."""
import json

import pytest

from conftest import tiny_config

# Limits of this file's tiny CPU runs (float64 program, 15 leaves): sound runs
# read 2e-6 / 1e-5 here, the bfloat16 control 4e-4 and more.
CPU_LIMITS = {"count_mismatch": 0, "leaf_value_gap": 1e-4,
              "split_gain_gap": 1e-4, "median_leaf_gap": 1e-5}


def last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def judged(result):
    return all(result["checks"][k]["value"] <= CPU_LIMITS[k]
               for k in CPU_LIMITS)


@pytest.mark.parametrize("cell", ["higgs.train_steady", "msltr.train_steady"])
def test_sound_run_is_correct_and_line_is_well_formed(rehearsal, capsys, cell):
    rc = rehearsal.main(["--workload", cell, "--seed", "3000000019",
                         "--seconds", "0.5", "--trace", "0"])
    result = last_line(capsys)
    assert rc == 0
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"train_throughput", "peak_hbm",
                                      "heldout_score", "setup_s"}
    assert result["attempted"] >= 16 and result["failed"] == 0
    assert judged(result), result["checks"]


@pytest.mark.parametrize("cell", ["higgs.train_steady", "msltr.train_steady"])
def test_control_in_bfloat16_is_not_correct(cell):
    import lightgbm_tpu as lgb
    from drivers import train
    from harness import reference
    cfg = tiny_config(cell.split(".")[0])
    rows, X, y, _, _, group = train.inputs(cfg, 17)
    bst = train.train_call(lgb, cfg["params"], X, y, group, 16, [], {})
    trees = reference.parse_model(bst.model_to_string(num_iteration=-1))
    init = train.init_score(cfg["params"], y)
    sound = train.check(rows, trees, cfg, init)[0]
    low = train.control(rows, trees, cfg, init)[0]
    assert all(sound[k] <= CPU_LIMITS[k] for k in CPU_LIMITS), sound
    assert low["count_mismatch"] == 0
    assert low["median_leaf_gap"] > 3 * CPU_LIMITS["median_leaf_gap"], low
    print(sound, low)


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered",
                                   "half_batch"])
def test_fault_under_the_harness_is_not_correct(rehearsal, monkeypatch,
                                                capsys, fault):
    from drivers import train
    from harness import faults, reference
    real_call = train.train_call

    if fault in faults.FEED:
        def broken(lgb, params, X, y, group, *rest):
            return real_call(lgb, params, *faults.FEED[fault](X, y, group),
                             *rest)
    else:
        def broken(*args):
            return _Tampered(real_call(*args), faults.MODEL[fault])
        monkeypatch.setattr(
            reference, "parse_model",
            lambda text, real=reference.parse_model: (
                text if isinstance(text, list) else real(text)))
    monkeypatch.setattr(train, "train_call", broken)
    rehearsal.main(["--workload", "higgs.train_steady", "--seed", "23",
                    "--seconds", "0.5", "--trace", "0"])
    result = last_line(capsys)
    assert not judged(result), result["checks"]


class _Tampered:
    """The booster a broken step would have returned: everything the harness
    asks of it is the real one's, but the model it hands over."""

    def __init__(self, bst, plant):
        self._bst, self._plant = bst, plant

    def __getattr__(self, name):
        return getattr(self._bst, name)

    def model_to_string(self, **kw):
        from harness import reference
        return self._plant(reference.parse_model(
            self._bst.model_to_string(**kw)))
