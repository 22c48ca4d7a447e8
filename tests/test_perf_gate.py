"""Perf-regression sentinel + roofline attribution (PR 11).

Covers the ISSUE-11 acceptance pins:

* synthetic round series: a regression beyond the band FAILS, an
  improvement passes, within-band noise passes, never-recorded
  trajectory keys are named loudly;
* round schema validation: malformed / meta-less rounds raise a clear
  RoundError instead of a KeyError mid-series;
* the meta block round-trips through bench.build_meta / BENCH_REPEATS
  median-of-k spread math;
* roofline fractions pinned against hand-computed values for two bench
  shapes + the bound taxonomy (hbm/compute/host/comms);
* the --perf CLI gates on a regressed synthetic series and runs green
  on an archived r01..r06 series written to tmp_path (tier-1 smoke).
"""
import json
import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from lightgbm_tpu.analysis import perf_gate
from lightgbm_tpu.analysis.perf_gate import (RoundError, Verdict,  # noqa: F401
                                             evaluate, load_round,
                                             validate_round)
from lightgbm_tpu.telemetry import perfmodel
from lightgbm_tpu.telemetry.devices import get_profile

BAND = 0.15


def _round(index, parsed, meta=None):
    return validate_round({"parsed": parsed, "meta": meta},
                          "BENCH_r%02d.json" % index, index)


def _meta(device_kind="tpu-test", spread=None, knobs=None):
    return {"schema": 1, "device": {"kind": device_kind},
            "jax": "0.0", "knobs": knobs or {},
            "spread": spread or {}}


FULL = {"value": 10.0, "ranking_value": 5.0, "expo_value": 3.0,
        "expo_level_value": 4.0}


# ---------------------------------------------------------------------------
# trajectory verdicts on synthetic series
# ---------------------------------------------------------------------------

def test_regression_beyond_band_fails():
    rounds = [_round(1, FULL),
              _round(2, dict(FULL, value=7.0))]   # -30% >> 15% band
    rep = evaluate(rounds, BAND)
    assert [v.key for v in rep.regressions] == ["value"]
    results = {r.name: r for r in perf_gate.run(artifact=rep)}
    assert not results["perf_trajectory"].ok
    assert "value" in results["perf_trajectory"].detail


def test_improvement_and_within_band_pass():
    rounds = [_round(1, FULL),
              _round(2, dict(FULL, value=20.0,          # improved
                             ranking_value=4.8))]        # -4% within band
    rep = evaluate(rounds, BAND)
    assert not rep.regressions
    assert [v.key for v in rep.improvements] == ["value"]
    within = {v.key: v.status for v in rep.verdicts}
    assert within["ranking_value"] == "ok"
    results = {r.name: r for r in perf_gate.run(artifact=rep)}
    assert results["perf_trajectory"].ok


def test_missing_trajectory_key_named_loudly():
    parsed = {"value": 10.0, "ranking_value": 5.0, "expo_value": 3.0}
    rep = evaluate([_round(1, parsed), _round(2, parsed)], BAND)
    assert rep.missing_keys == ["expo_level_value"]
    results = {r.name: r for r in perf_gate.run(artifact=rep)}
    assert not results["perf_trajectory"].ok
    assert "expo_level_value" in results["perf_trajectory"].detail


def test_lower_better_keys_gate_in_the_right_direction():
    base = dict(FULL, predict_p99=0.010)
    rounds = [_round(1, base),
              _round(2, dict(base, predict_p99=0.020))]  # p99 doubled
    rep = evaluate(rounds, BAND)
    assert [v.key for v in rep.regressions] == ["predict_p99"]
    # and a p99 DROP is an improvement, not a regression
    rep2 = evaluate([_round(1, base),
                     _round(2, dict(base, predict_p99=0.005))], BAND)
    assert not rep2.regressions
    assert "predict_p99" in [v.key for v in rep2.improvements]


def test_device_change_opens_new_lineage_instead_of_regressing():
    # a CPU round after TPU rounds: NOT comparable — no regression even
    # though every number is 100x worse
    rounds = [_round(1, FULL),
              _round(2, {k: v / 100 for k, v in FULL.items()},
                     meta=_meta(device_kind="cpu"))]
    rep = evaluate(rounds, BAND)
    assert not rep.regressions
    assert len(rep.lineages) == 2
    statuses = {(v.key, v.round): v.status for v in rep.verdicts}
    assert statuses[("value", 2)] == "new"


def test_recorded_spread_widens_the_noise_band():
    # a 25% drop REGRESSES on the default band but passes when the
    # rounds recorded a 30% median-of-k spread for that key
    prev = _round(6, FULL, meta=_meta())
    noisy = _round(7, dict(FULL, value=7.5),
                   meta=_meta(spread={"value": 0.30}))
    rep = evaluate([prev, noisy], BAND)
    assert not rep.regressions
    tight = _round(7, dict(FULL, value=7.5), meta=_meta())
    rep2 = evaluate([prev, tight], BAND)
    assert [v.key for v in rep2.regressions] == ["value"]


def test_key_vanishing_from_latest_round_gates():
    """bench.py catches per-phase crashes and keeps going — a headline
    key the lineage used to record but the latest round lacks must FAIL
    the gate, not pass silently."""
    rounds = [_round(1, FULL),
              _round(2, {k: v for k, v in FULL.items()
                         if k != "expo_value"})]
    rep = evaluate(rounds, BAND)
    missing = [v for v in rep.verdicts if v.status == "missing"]
    assert [v.key for v in missing] == ["expo_value"]
    results = {r.name: r for r in perf_gate.run(artifact=rep)}
    assert not results["perf_trajectory"].ok
    assert "vanished" in results["perf_trajectory"].detail


def test_vanished_key_keeps_gating_on_later_rounds():
    """The predecessor for a key is the last round that CARRIED it —
    recording another crashed round must not launder the loss."""
    rounds = [_round(1, FULL),
              _round(2, {k: v for k, v in FULL.items()
                         if k != "expo_value"}),
              _round(3, {k: v for k, v in FULL.items()
                         if k != "expo_value"})]
    rep = evaluate(rounds, BAND)
    missing = [v for v in rep.verdicts if v.status == "missing"]
    assert [(v.key, v.round, v.prev_round) for v in missing] == \
        [("expo_value", 3, 1)]
    results = {r.name: r for r in perf_gate.run(artifact=rep)}
    assert not results["perf_trajectory"].ok
    # and a key SKIPPING a round compares against its real last carrier
    rep2 = evaluate([_round(1, FULL),
                     _round(2, {k: v for k, v in FULL.items()
                                if k != "value"}),
                     _round(3, dict(FULL, value=5.0))], BAND)
    reg = [v for v in rep2.regressions if v.key == "value"]
    assert reg and reg[0].prev_round == 1


def test_median_merge_nested_predict_layout():
    import bench
    runs = [{"higgs": {"value": 1.0}, "poisson": {"p99": 0.010}},
            {"higgs": {"value": 1.2}, "poisson": {"p99": 0.030}},
            {"higgs": {"value": 1.1}, "poisson": {"p99": 0.020}}]
    merged, spread = bench._median_merge_nested(
        runs, ("higgs", "expo", "poisson"))
    assert merged["higgs"]["value"] == pytest.approx(1.1)
    assert merged["poisson"]["p99"] == pytest.approx(0.020)
    assert spread["poisson.p99"] == pytest.approx(0.020 / 0.020)
    assert "expo" not in spread  # sub-dict absent from every run


def test_find_phase_snapshot_numeric_round_order(tmp_path):
    from lightgbm_tpu.telemetry import perfmodel
    assert perfmodel.find_phase_snapshot(str(tmp_path)) is None
    for n in (9, 10, 100, 99):
        (tmp_path / ("BENCH_r%02d_phases.json" % n)).write_text("{}")
    got = perfmodel.find_phase_snapshot(str(tmp_path))
    assert got.endswith("BENCH_r100_phases.json")
    (tmp_path / "only" ).mkdir()
    (tmp_path / "only" / "BENCH_phases.json").write_text("{}")
    assert perfmodel.find_phase_snapshot(
        str(tmp_path / "only")).endswith("BENCH_phases.json")


def test_perf_card_rejects_non_object_snapshot(tmp_path, capsys):
    from lightgbm_tpu.profile import main
    p = tmp_path / "snap.json"
    p.write_text("[]")   # valid JSON, wrong shape
    assert main(["--perf-card", "higgs", str(p)]) == 2
    assert "not a JSON object" in capsys.readouterr().err


def test_measurement_knobs_do_not_sever_the_lineage():
    """BENCH_REPEATS / BENCH_TELEMETRY / BENCH_SKIP_* / *_OUT change how
    a round is MEASURED, not what it measures — flipping them must keep
    the regression comparison alive."""
    meta_a = _meta(knobs={"BENCH_ROWS": "1000"})
    meta_b = _meta(knobs={"BENCH_ROWS": "1000", "BENCH_REPEATS": "3",
                          "BENCH_TELEMETRY": "0", "BENCH_SKIP_EXPO": "1",
                          "BENCH_PHASES_OUT": "x.json"})
    rounds = [_round(6, FULL, meta=meta_a),
              _round(7, dict(FULL, value=5.0), meta=meta_b)]
    rep = evaluate(rounds, BAND)
    assert len(rep.lineages) == 1
    assert [v.key for v in rep.regressions] == ["value"]
    # a WORKLOAD knob change does sever it
    meta_c = _meta(knobs={"BENCH_ROWS": "9999"})
    rep2 = evaluate([_round(6, FULL, meta=meta_a),
                     _round(7, dict(FULL, value=5.0), meta=meta_c)],
                    BAND)
    assert len(rep2.lineages) == 2 and not rep2.regressions


def test_check_fixture_positive_and_negative():
    bad = [{"index": 1, "parsed": FULL},
           {"index": 2, "parsed": dict(FULL, value=5.0)}]
    assert perf_gate.check_fixture(bad)
    good = [{"index": 1, "parsed": FULL},
            {"index": 2, "parsed": dict(FULL, value=11.0)}]
    assert not perf_gate.check_fixture(good)


# ---------------------------------------------------------------------------
# round schema validation
# ---------------------------------------------------------------------------

def test_malformed_round_raises_clear_error():
    with pytest.raises(RoundError, match="parsed"):
        validate_round({"tail": "..."}, "BENCH_r03.json", 3)
    with pytest.raises(RoundError, match="object"):
        validate_round([1, 2], "BENCH_r03.json", 3)


def test_metaless_round_grandfathered_only_before_r06():
    # r01..r05 predate the meta block: accepted as legacy
    r = validate_round({"parsed": {"value": 1.0}}, "BENCH_r05.json", 5)
    assert r.legacy and r.fingerprint() == "legacy"
    with pytest.raises(RoundError, match="meta"):
        validate_round({"parsed": {"value": 1.0}}, "BENCH_r07.json", 7)


def test_meta_missing_required_fields_rejected():
    with pytest.raises(RoundError, match="schema"):
        validate_round({"parsed": {"value": 1.0},
                        "meta": {"device": {}, "jax": "0.0"}},
                       "BENCH_r07.json", 7)
    with pytest.raises(RoundError, match="object"):
        validate_round({"parsed": {"value": 1.0}, "meta": "v1"},
                       "BENCH_r07.json", 7)


def test_load_round_bad_json_and_bad_name(tmp_path):
    p = tmp_path / "BENCH_r09.json"
    p.write_text("{not json")
    with pytest.raises(RoundError, match="unreadable"):
        load_round(str(p))
    with pytest.raises(RoundError, match="not a BENCH"):
        load_round(str(tmp_path / "OTHER.json"))


def test_meta_rides_inside_parsed_too():
    """bench.py stamps meta into its printed metric line; the driver
    archives that line as `parsed` — the validator finds it there."""
    r = validate_round({"parsed": {"value": 1.0, "meta": _meta()}},
                       "BENCH_r07.json", 7)
    assert not r.legacy and r.meta["schema"] == 1


# ---------------------------------------------------------------------------
# bench meta block + BENCH_REPEATS median-of-k spread
# ---------------------------------------------------------------------------

def test_median_merge_and_spread():
    import bench
    runs = [{"value": 1.0, "train_s": 10.0, "rows": 500},
            {"value": 1.2, "train_s": 8.0, "rows": 500},
            {"value": 1.1, "train_s": 9.0, "rows": 500}]
    merged, spread = bench._median_merge(runs)
    assert merged["value"] == pytest.approx(1.1)
    assert merged["train_s"] == pytest.approx(9.0)
    assert merged["rows"] == 500 and isinstance(merged["rows"], int)
    assert spread["value"] == pytest.approx(0.2 / 1.1)
    assert spread["rows"] == 0.0


def test_repeat_phase_single_run_has_no_spread():
    import bench
    out, spread = bench._repeat_phase(lambda: {"value": 2.0}, 1)
    assert out == {"value": 2.0} and spread == {}


def test_build_meta_roundtrips_through_validator(monkeypatch):
    import bench
    monkeypatch.setenv("BENCH_ROWS", "1234")
    monkeypatch.setenv("BENCH_REPEATS", "3")
    meta = bench.build_meta(repeats=3, spread={"value": 0.0512345})
    assert meta["schema"] == bench.BENCH_SCHEMA_VERSION
    assert meta["knobs"]["BENCH_ROWS"] == "1234"
    assert meta["repeats"] == 3
    assert meta["spread"]["value"] == pytest.approx(0.0512, abs=1e-4)
    assert meta["device"]["profile"]["name"]
    r = validate_round({"parsed": {"value": 1.0}, "meta": meta},
                       "BENCH_r07.json", 7)
    assert not r.legacy
    # the lineage fingerprint keys off device + workload knobs
    assert "BENCH_ROWS=1234" in r.fingerprint()


def test_bench_params_knob_parsing(monkeypatch):
    import bench
    monkeypatch.setenv("BENCH_PARAMS",
                       "tpu_persist_scan=force, num_leaves=63")
    assert bench._extra_params() == {"tpu_persist_scan": "force",
                                     "num_leaves": "63"}
    p = bench._phase_params({"num_leaves": 255, "objective": "binary"})
    assert p["num_leaves"] == "63" and p["objective"] == "binary"
    monkeypatch.delenv("BENCH_PARAMS")
    assert bench._extra_params() == {}


# ---------------------------------------------------------------------------
# roofline: hand-computed pins for two bench shapes + bound taxonomy
# ---------------------------------------------------------------------------

def _snap(wall_ops, wall_other, program_total, program_count=10,
          comms_total=0.0, work=None):
    histos = {}
    if program_count:
        histos[perfmodel.PROGRAM_WALL_HISTO] = {
            "count": program_count, "total": program_total}
    if comms_total:
        histos["collective::allreduce::latency"] = {
            "count": 4, "total": comms_total}
    return {"categories": {"ops": wall_ops, "boosting": wall_other},
            "histograms": histos, "work": work or {}}


def test_work_model_hand_computed_higgs():
    # rows=1000 iters=10 leaves=255 -> depth 8, nodes 509,
    # rows_scanned = 1000 * (1 + 3.5) = 4500
    m = perfmodel.work_model(rows=1000, groups=28, features=28,
                             iters=10, num_leaves=255)
    assert m["depth"] == 8 and m["nodes"] == 509
    assert m["rows_scanned"] == pytest.approx(4500.0)
    hist_bytes = 4500 * (28 + 8)                      # 162_000
    plane_bytes = 509 * 28 * 256 * 2 * 4 * 2          # 58_363_904
    assert m["bytes"] == pytest.approx(10 * (hist_bytes + plane_bytes))
    flops = 4500 * 28 * 2 + 509 * 28 * 256 * 8        # 29_436_904
    assert m["flops"] == pytest.approx(10 * flops)


def test_report_card_fraction_pinned_higgs_v5e():
    prof = get_profile("v5e")
    work = {"rows": 10_500_000, "iters": 500, "num_leaves": 255}
    snap = _snap(wall_ops=10.0, wall_other=2.0, program_total=10.0,
                 work=work)
    card = perfmodel.report_card(snap, "higgs", profile=prof)
    m = perfmodel.work_model(10_500_000, 28, 28, 500, 255)
    t_hbm = m["bytes"] / 819e9
    t_comp = m["flops"] / (197e12 * perfmodel.F32_DERATE)
    assert t_hbm > t_comp                  # HIGGS hist build streams HBM
    assert card.bound == "hbm"
    assert card.achieved_frac == pytest.approx(t_hbm / 10.0, rel=1e-6)
    assert card.t_hbm == pytest.approx(t_hbm, rel=1e-6)


def test_report_card_fraction_pinned_expo_v5e():
    # expo bundles 700 features into 16 byte groups: the plane traffic
    # collapses but the split scan still walks all 700 features
    prof = get_profile("v5e")
    work = {"rows": 2_000_000, "iters": 96, "num_leaves": 255}
    snap = _snap(wall_ops=8.0, wall_other=1.0, program_total=8.0,
                 work=work)
    card = perfmodel.report_card(snap, "expo", profile=prof)
    m = perfmodel.work_model(2_000_000, 16, 700, 96, 255)
    t_hbm = m["bytes"] / 819e9
    t_comp = m["flops"] / (197e12 * perfmodel.F32_DERATE)
    expect = max(t_hbm, t_comp)
    assert card.achieved_frac == pytest.approx(expect / 8.0, rel=1e-6)
    assert card.bound == ("hbm" if t_hbm >= t_comp else "compute")
    assert card.rows == 2_000_000 and card.iters == 96


def test_bound_taxonomy_host_and_comms():
    work = {"rows": 20_000, "iters": 8, "num_leaves": 63}
    # programs took 1% of the wall: the python driver binds, not the chip
    host = perfmodel.report_card(
        _snap(wall_ops=0.1, wall_other=9.9, program_total=0.1,
              work=work), "higgs", profile=get_profile("v5e"))
    assert host.bound == "host"
    # DCN time over 40% of wall: comms-bound
    comms = perfmodel.report_card(
        _snap(wall_ops=4.0, wall_other=1.0, program_total=4.0,
              comms_total=4.0, work=work),
        "higgs", profile=get_profile("v5e"))
    assert comms.bound == "comms"


def test_cards_from_phases_covers_the_five_shapes():
    work = {"rows": 1000, "iters": 4, "num_leaves": 63}
    snaps = {k: _snap(1.0, 0.1, 1.0, work=work)
             for k in ("higgs", "ltr", "expo", "allstate", "yahoo_ltr")}
    cards = perfmodel.cards_from_phases(snaps,
                                        profile=get_profile("v5e"))
    assert sorted(c.shape for c in cards) == [
        "allstate", "expo", "higgs", "msltr", "yahoo"]
    for c in cards:
        assert c.bound in ("compute", "hbm", "comms", "host")
        assert c.achieved_frac >= 0.0
    text = perfmodel.render_cards(cards)
    assert "perf report card" in text and "bound" in text


def test_format_report_appends_perf_cards():
    from lightgbm_tpu.telemetry import export
    card = perfmodel.report_card(
        _snap(1.0, 0.1, 1.0, work={"rows": 1000, "iters": 4,
                                   "num_leaves": 63}),
        "higgs", profile=get_profile("v5e"))
    text = export.format_report(snap={}, perf_cards=[card])
    assert "perf report card" in text and "higgs" in text


# ---------------------------------------------------------------------------
# a whole archived series + the CLI gate
# ---------------------------------------------------------------------------

SHAPE_PHASES = {"higgs": (10_500_000, 500), "ltr": (2_270_000, 160),
                "expo": (2_000_000, 96), "allstate": (1_000_000, 64),
                "yahoo_ltr": (473_134, 120)}


def _write_series(root):
    """A synthetic archive in the layout the repo's own records use:
    five legacy rounds (r01..r05, no meta block), a self-describing
    r06 that adds the expo_level_* keys, multichip dry runs, and the
    r06 phase snapshot covering all five bench shapes. (The repo's own
    r01..r06 were deleted in PR 23; the series under test is written
    here so the gate's verdict does not hang on what the repo archives.)"""
    value = 1.0
    for i in range(1, 6):
        value *= 1.5
        (root / ("BENCH_r%02d.json" % i)).write_text(json.dumps(
            {"parsed": {"value": value, "ranking_value": value / 8,
                        "expo_value": value / 6}}))
    r06 = {"value": value * 1.1, "ranking_value": value / 7,
           "expo_value": value / 5, "expo_level_value": value / 4,
           "expo_level_launches_per_tree": 5.0}
    (root / "BENCH_r06.json").write_text(json.dumps(
        {"parsed": dict(r06, meta=_meta())}))
    for i in (1, 2, 3):
        (root / ("MULTICHIP_r%02d.json" % i)).write_text(json.dumps(
            {"n_devices": 8, "rc": 0, "ok": True, "skipped": False}))
    (root / "BENCH_r06_phases.json").write_text(json.dumps(
        {phase: _snap(2.0, 0.5, 2.0,
                      work={"phase": phase, "rows": rows, "iters": iters,
                            "num_leaves": 255})
         for phase, (rows, iters) in SHAPE_PHASES.items()}))


def test_archived_round_series_green(tmp_path):
    """The acceptance pin: an archived r01..r06 series passes the
    sentinel — r06 carries the meta block and the expo_level_* keys, so
    the stale-trajectory failure mode is CLOSED."""
    _write_series(tmp_path)
    rounds, multichip, errors = perf_gate.discover_rounds(str(tmp_path))
    assert not errors
    assert len(rounds) >= 6
    r06 = [r for r in rounds if r.index == 6]
    assert r06 and not r06[0].legacy, "r06 must be self-describing"
    assert "expo_level_value" in r06[0].parsed
    rep = evaluate(rounds, 0.15, multichip=multichip, errors=errors)
    results = {r.name: r for r in perf_gate.run(artifact=rep)}
    assert results["perf_rounds"].ok
    assert results["perf_trajectory"].ok, \
        results["perf_trajectory"].detail
    assert results["perf_multichip"].ok


def test_perf_cli_green_and_tables(tmp_path, monkeypatch, capsys):
    _write_series(tmp_path)
    monkeypatch.setenv("LGBTPU_PERF_ROUNDS_DIR", str(tmp_path))
    from lightgbm_tpu.analysis.__main__ import main
    rc = main(["lightgbm_tpu/analysis/perf_gate.py", "--no-audit",
               "--perf", "--json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 0, payload["audits"]
    names = {a["name"] for a in payload["audits"]}
    assert {"perf_rounds", "perf_trajectory"} <= names
    pt = payload["perf_tables"]
    assert pt["rounds"][0]["index"] == 1
    assert "value" in pt["trajectories"]
    assert not pt["missing_keys"]
    # the archived r06 phase snapshot feeds the roofline cards: all five
    # bench shapes get a bound + achieved fraction (acceptance pin)
    shapes = {c["shape"]: c for c in pt["roofline"]["cards"]}
    assert set(shapes) == {"higgs", "msltr", "expo", "allstate",
                           "yahoo"}
    for c in shapes.values():
        assert c["bound"] in ("compute", "hbm", "comms", "host")
        assert isinstance(c["achieved_frac"], float)


def test_perf_cli_fails_on_regressed_series(tmp_path, monkeypatch,
                                            capsys):
    """The demonstrable-failure pin: a synthetic regressed round flips
    the SAME CLI invocation to exit 1 (and advisory mode back to 0)."""
    for i, v in ((1, 10.0), (2, 4.0)):
        (tmp_path / ("BENCH_r%02d.json" % i)).write_text(json.dumps(
            {"parsed": dict(FULL, value=v)}))
    monkeypatch.setenv("LGBTPU_PERF_ROUNDS_DIR", str(tmp_path))
    from lightgbm_tpu.analysis.__main__ import main
    rc = main(["lightgbm_tpu/analysis/perf_gate.py", "--no-audit",
               "--perf", "--json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    traj = [a for a in payload["audits"]
            if a["name"] == "perf_trajectory"][0]
    assert not traj["ok"] and "value" in traj["detail"]
    # advisory mode reports the same verdict but never blocks
    rc = main(["lightgbm_tpu/analysis/perf_gate.py", "--no-audit",
               "--perf-advisory"])
    out = capsys.readouterr().out
    assert rc == 0 and "ADVISORY-FAIL" in out


def test_perf_cli_zero_rounds_reports_cleanly(tmp_path, monkeypatch,
                                              capsys):
    """A directory with ZERO BENCH_r* rounds is reported as "no rounds
    recorded" with a RoundError-style message — gate mode exits 1
    (judging nothing is a bench-refresh bug), advisory mode exits 0 —
    never a traceback, never a silent pass."""
    monkeypatch.setenv("LGBTPU_PERF_ROUNDS_DIR", str(tmp_path))
    from lightgbm_tpu.analysis.__main__ import main
    rc = main(["lightgbm_tpu/analysis/perf_gate.py", "--no-audit",
               "--perf", "--json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1 and payload["exit_code"] == 1
    rounds = [a for a in payload["audits"]
              if a["name"] == "perf_rounds"][0]
    assert not rounds["ok"] and "rounds recorded" in rounds["detail"]
    assert str(tmp_path) in rounds["detail"]
    traj = [a for a in payload["audits"]
            if a["name"] == "perf_trajectory"][0]
    assert traj["ok"] and traj["skipped"]
    # the pre-commit advisory mode reports the same state, exit 0
    rc = main(["lightgbm_tpu/analysis/perf_gate.py", "--no-audit",
               "--perf-advisory"])
    out = capsys.readouterr().out
    assert rc == 0 and "ADVISORY-FAIL" in out
    # evaluate()/run() on the empty series also stay exception-free
    rep = evaluate([], BAND)
    results = perf_gate.run(artifact=rep)
    assert any(not r.ok for r in results)
    # a multichip-only archive still gets its series judged: the
    # zero-BENCH-rounds failure must not swallow the multichip verdict
    rep_mc = evaluate([], BAND, multichip=[
        {"index": 1, "ok": True, "rc": 0, "n_devices": 8}])
    names = {r.name: r for r in perf_gate.run(artifact=rep_mc)}
    assert not names["perf_rounds"].ok
    assert "perf_multichip" in names and names["perf_multichip"].ok
    # ...and in the sibling state where every BENCH round failed to
    # PARSE, a failing multichip series must still be reported
    rep_err = evaluate([], BAND,
                       multichip=[{"index": 1, "ok": False, "rc": 1}],
                       errors=["BENCH_r01.json: unreadable round json"])
    names = {r.name: r for r in perf_gate.run(artifact=rep_err)}
    assert not names["perf_rounds"].ok
    assert not names["perf_multichip"].ok


def test_profile_perf_card_cli(tmp_path, capsys):
    """profile --perf-card SHAPE reads an archived snapshot — no bench
    re-run, no accelerator."""
    snap = {"higgs": _snap(2.0, 0.5, 2.0,
                           work={"rows": 50_000, "iters": 10,
                                 "num_leaves": 63})}
    p = tmp_path / "BENCH_phases.json"
    p.write_text(json.dumps(snap))
    from lightgbm_tpu.profile import main
    assert main(["--perf-card", "higgs", str(p), "--json"]) == 0
    card = json.loads(capsys.readouterr().out)
    assert card["shape"] == "higgs" and card["bound"] in (
        "compute", "hbm", "comms", "host")
    # directory form picks the snapshot up too
    assert main(["--perf-card", "higgs", str(tmp_path)]) == 0
    assert "perf report card" in capsys.readouterr().out
    # a missing shape is a clear error, not a traceback
    assert main(["--perf-card", "nope", str(p)]) == 2
