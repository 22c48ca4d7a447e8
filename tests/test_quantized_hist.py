"""Communication-efficient distributed exchange (ROADMAP item 2):
int16-quantized histogram collectives, the PV-Tree top-k vote allgather,
and the double-buffered level-program reduction.

The contract under test is the certificate <-> runtime seam: the wire
format shipped by ``ops/quantize.plane_psum`` must be exactly the spec
the ``quant_certify`` static certificate blesses (asserted at config
time — int8 is refused there), quantized training must be DETERMINISTIC
and bit-identical across ranks (rank-uniform seeded stochastic
rounding), and decisions whose empirical split margins clear the static
perturbation bound must be identical to the full-width path's.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.ops.quantize import (HistQuant, dequantize_plane,
                                       plane_psum, quant_from_spec,
                                       quant_tag, quantize_plane,
                                       runtime_quant_spec)
from lightgbm_tpu.utils.log import LightGBMError


# ---------------------------------------------------------------------------
# quantizer math (tier-1: no mesh programs)
# ---------------------------------------------------------------------------

def _q16(rows=768, ranks=8):
    return quant_from_spec(runtime_quant_spec("int16", rows, ranks))


def test_quantize_roundtrip_bounded_zero_preserving_deterministic():
    q = _q16()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 256)).astype(np.float32) * 10)
    tag = quant_tag(3, 7)
    codes = quantize_plane(x, q.scale_g, q.levels, tag)
    assert codes.dtype == jnp.int16          # the wire payload IS int16
    deq = dequantize_plane(codes, q.scale_g, q.levels, jnp.float32)
    # per-element error bounded by one step (floor + uniform offset)
    assert float(jnp.max(jnp.abs(deq - x))) <= q.delta_g * (1 + 1e-6)
    # empty bins stay empty through the wire (floor(0 + u) == 0): u must
    # be STRICTLY < 1 — a raw u32->f32 hash cast rounds up to 1.0 one
    # lane in ~2^25 (regression: tag quant_tag(2108, 0) used to produce
    # a nonzero code on an all-zero 4096-lane plane)
    for it, st in [(0, 0), (2108, 0), (3, 7)] + [
            (i * 97, i) for i in range(40)]:
        z = quantize_plane(jnp.zeros((4096,)), q.scale_g, q.levels,
                           quant_tag(it, st))
        assert not np.any(np.asarray(z)), (it, st)
    # deterministic per tag; different tags draw different noise
    again = quantize_plane(x, q.scale_g, q.levels, tag)
    assert np.array_equal(np.asarray(codes), np.asarray(again))
    other = quantize_plane(x, q.scale_g, q.levels, quant_tag(3, 8))
    assert not np.array_equal(np.asarray(codes), np.asarray(other))
    # contract saturation: values beyond the certified scale clamp
    big = quantize_plane(jnp.full((8,), q.scale_g * 3), q.scale_g,
                         q.levels, tag)
    assert int(np.max(np.asarray(big))) == q.levels // 2


def test_plane_psum_unsharded_identity():
    """axis_name=None is the unsharded fast path: no collective, no
    quantization noise — the knob is inert on a single shard."""
    g = jnp.asarray(np.random.default_rng(1).normal(size=(16,)))
    h = jnp.abs(g)
    rg, rh = plane_psum("psum:test", g, h, None, _q16(), quant_tag(0, 0))
    assert rg is g and rh is h


def test_prefix_sum_error_within_certificate_envelope():
    """Empirical accumulated error of the certified exchange: 8 ranks'
    stochastically quantized 256-bin planes, summed and prefix-scanned,
    must stay inside the certificate's Hoeffding envelope ``err_grad``
    (the bound every split decision reads through)."""
    from lightgbm_tpu.analysis import quant_audit
    rows, ranks = 768, 8
    spec = runtime_quant_spec("int16", rows, ranks)
    cert = quant_audit.certify(spec)
    q = quant_from_spec(spec)
    rng = np.random.default_rng(5)
    worst = 0.0
    for trial in range(20):
        planes = rng.uniform(-1, 1, size=(ranks, 256)) * (q.scale_g / 256)
        exact = planes.sum(axis=0)
        acc = np.zeros(256, np.int64)
        for r in range(ranks):
            acc += np.asarray(
                quantize_plane(jnp.asarray(planes[r]), q.scale_g,
                               q.levels, quant_tag(trial, 0)),
                np.int64)
        deq = acc * q.delta_g
        err = np.abs(np.cumsum(deq - exact)).max()
        worst = max(worst, float(err))
    assert worst <= cert["err_grad"], (worst, cert["err_grad"])


# ---------------------------------------------------------------------------
# certificate <-> config seam (tier-1)
# ---------------------------------------------------------------------------

def test_runtime_spec_certifies_int16_refuses_int8():
    from lightgbm_tpu.analysis import quant_audit
    c16 = quant_audit.certify(runtime_quant_spec("int16", 768, 8))
    assert c16["ok"] and c16["margin"] > 1.0
    assert c16["bound"] <= quant_audit.SPLIT_DECISION_BUDGET
    c8 = quant_audit.certify(runtime_quant_spec("int8", 768, 8))
    assert not c8["ok"]


def test_resolve_hist_quant_config_seam():
    from lightgbm_tpu.parallel.distributed import resolve_hist_quant
    cfg = Config({"objective": "binary", "tpu_hist_quant": "int16",
                  "verbosity": -1})
    q, cert = resolve_hist_quant(cfg, 768, 8)
    assert isinstance(q, HistQuant) and q.bits == 16
    assert cert["ok"] and cert["spec"]["target"] == "int16"
    # world=1: inert, not an error (elastic-resume small end)
    assert resolve_hist_quant(cfg, 768, 1) is None
    # off
    assert resolve_hist_quant(Config({"objective": "binary",
                                      "verbosity": -1}), 768, 8) is None


def test_int8_refused_at_config_time_names_certificate():
    from lightgbm_tpu.parallel.distributed import resolve_hist_quant
    cfg = Config({"objective": "binary", "tpu_hist_quant": "int8",
                  "verbosity": -1})
    with pytest.raises(LightGBMError) as ei:
        resolve_hist_quant(cfg, 768, 8)
    msg = str(ei.value)
    assert "quant_certify" in msg and "SPLIT_DECISION_BUDGET" in msg


def test_unknown_hist_quant_value_rejected():
    with pytest.raises(LightGBMError):
        Config({"tpu_hist_quant": "int4"})


def test_unbounded_objective_refused():
    """The contract caps are the certificate's domain assumption:
    objectives without a static per-row gradient bound (regression:
    grad = pred - label, unbounded) and data-dependent weightings
    (is_unbalance) are refused loudly instead of silently saturating
    the quantized planes."""
    from lightgbm_tpu.parallel.distributed import resolve_hist_quant
    with pytest.raises(LightGBMError) as ei:
        resolve_hist_quant(Config({"objective": "regression",
                                   "tpu_hist_quant": "int16",
                                   "verbosity": -1}), 768, 8)
    assert "gradient bound" in str(ei.value)
    with pytest.raises(LightGBMError):
        resolve_hist_quant(Config({"objective": "binary",
                                   "is_unbalance": True,
                                   "tpu_hist_quant": "int16",
                                   "verbosity": -1}), 768, 8)
    # bounded objectives certify, with the caps scaled into the spec:
    # GOSS amplification and scale_pos_weight widen the contract scale
    q_plain, _ = resolve_hist_quant(
        Config({"objective": "binary", "tpu_hist_quant": "int16",
                "verbosity": -1}), 768, 8)
    q_goss, _ = resolve_hist_quant(
        Config({"objective": "binary", "boosting": "goss",
                "tpu_hist_quant": "int16", "verbosity": -1}), 768, 8)
    assert q_goss.scale_g > q_plain.scale_g   # (1-a)/b amplification
    q_w, _ = resolve_hist_quant(
        Config({"objective": "binary", "tpu_hist_quant": "int16",
                "verbosity": -1}), 768, 8, weight_max=3.0)
    assert q_w.scale_g == pytest.approx(q_plain.scale_g * 3.0)
    # multiclass softmax caps (h <= 0.5)
    q_mc, cert_mc = resolve_hist_quant(
        Config({"objective": "multiclass", "num_class": 3,
                "tpu_hist_quant": "int16", "verbosity": -1}), 768, 8)
    assert cert_mc["ok"] and cert_mc["spec"]["h_max"] == 0.5


def test_quant_knobs_are_checkpoint_volatile():
    """Flipping the wire-format knobs must not orphan an existing
    resume (the PR 14 sentinel-knob treatment)."""
    from lightgbm_tpu.resilience.checkpoint import config_hash
    base = Config({"objective": "binary", "num_leaves": 15})
    quant = Config({"objective": "binary", "num_leaves": 15,
                    "tpu_hist_quant": "int16", "tpu_comm_overlap": "off"})
    other = Config({"objective": "binary", "num_leaves": 31})
    assert config_hash(base) == config_hash(quant)
    assert config_hash(base) != config_hash(other)


def test_wire_bytes_model_shapes():
    """The flush-time byte model mirrors the reduce sites: int16 codes
    quarter the widened-f64 planes; voting ships windows, not planes."""
    from lightgbm_tpu.data.dataset import BinnedDataset
    from lightgbm_tpu.ops.grow_persist import (build_assets,
                                               make_persist_grower)
    from lightgbm_tpu.treelearner.serial import SerialTreeLearner
    rng = np.random.default_rng(0)
    X = rng.normal(size=(512, 6))
    y = (X[:, 0] > 0).astype(float)
    cfg = Config({"objective": "binary", "num_leaves": 7,
                  "max_bin": 63, "verbosity": -1})
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    learner = SerialTreeLearner(cfg, ds)
    assets = build_assets(ds, y, score64=True)
    q = _q16(512, 8)
    gr_q = make_persist_grower(assets, learner.meta, learner.grow_config,
                               kernel_impl="xla", axis_name="data",
                               quant=q)
    gr_f = make_persist_grower(assets, learner.meta, learner.grow_config,
                               kernel_impl="xla", axis_name="data")
    aq, fq = gr_q.wire_bytes_model(0, 6, 1)
    af, ff = gr_f.wire_bytes_model(0, 6, 1)
    assert fq == ff                      # same full-width denominator
    assert af == ff                      # full-width path ships full f64
    assert aq * 4 == af                  # int16 vs f64 planes: exactly 4x
    # unsharded growers model zero wire bytes
    gr_1 = make_persist_grower(assets, learner.meta, learner.grow_config,
                               kernel_impl="xla")
    assert gr_1.wire_bytes_model(0, 6, 1) == (0, 0)


# ---------------------------------------------------------------------------
# end-to-end sharded training (slow: 8-device shard_map compiles)
# ---------------------------------------------------------------------------

N = 6144
F = 6


def _sep_data(seed=3, f=F):
    """Strongly separated problem: split margins dwarf the certified
    perturbation bound, so quantized decisions cannot flip."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(N, f))
    y = (X[:, 0] > 0).astype(float)
    return X, y


def _train(X, y, rounds=16, **extra):
    params = {"objective": "binary", "num_leaves": 4, "verbosity": -1,
              "min_data_in_leaf": 10, "max_bin": 63,
              "learning_rate": 0.01, "tpu_persist_scan": "force",
              "tree_learner": "data"}
    params.update(extra)
    bst = lgb.train(params, lgb.Dataset(X, y), rounds, verbose_eval=False)
    bst._booster._materialize_pending()
    return bst


def _tree_digest(bst):
    return [(t.num_leaves, tuple(t.split_feature[:t.num_leaves - 1]),
             tuple(int(v) for v in t.threshold_in_bin[:t.num_leaves - 1]))
            for t in bst._booster.models]


@pytest.mark.slow
def test_quantized_sharded_certificate_runtime_seam():
    """The certificate<->runtime seam on a real sharded run: empirical
    split-margin p01 sits above the static gain-perturbation bound, so
    full-width and int16-quantized training take the IDENTICAL split
    decisions; the quantized run is deterministic; the wire-byte
    telemetry records the 4x (f64 -> int16) plane compression."""
    import lightgbm_tpu.telemetry as tel
    from lightgbm_tpu.telemetry import events as tel_events
    from lightgbm_tpu.telemetry import histo as tel_histo
    X, y = _sep_data()
    # STUMPS: every split is the dominant separating split, so every
    # recorded margin must clear the certificate's absolute bound — the
    # regime where the certificate actually promises decision stability
    bst_full = _train(X, y, num_leaves=2)
    tl_full = bst_full._booster.tree_learner
    assert getattr(tl_full, "_persist_carry", None) is not None

    tel.enable("timers")
    try:
        tel.reset()
        bst_q = _train(X, y, num_leaves=2, tpu_hist_quant="int16")
        tl = bst_q._booster.tree_learner
        assert getattr(tl, "_persist_carry", None) is not None
        assert tl.hist_quant is not None and tl.hist_quant.bits == 16
        tl.flush_level_stats()
        counts = tel_events.counts_snapshot()
        mh = tel_histo.get("numerics::split_margin")
        assert mh is not None and mh.count
        p01 = mh.percentile(0.01)
        cert = tl.hist_quant_cert
    finally:
        tel.reset()
        tel.enable("off")

    # (1) empirical margin p01 clears the static SPLIT_DECISION_BUDGET
    # perturbation bound -> every decision of this run is certified
    assert p01 > cert["gain_perturbation"], (p01, cert)
    # (2) certified decisions are identical to full-width
    assert _tree_digest(bst_q) == _tree_digest(bst_full)
    # (3) deterministic (rank-uniform seeded stochastic rounding)
    bst_q2 = _train(X, y, num_leaves=2, tpu_hist_quant="int16")
    assert _tree_digest(bst_q2) == _tree_digest(bst_q)
    # (4) the wire-byte telemetry recorded the compression (widened-f64
    # emulation planes -> int16 codes: exactly 4x on this path)
    actual = counts.get("collective::dcn_hist_bytes", 0)
    full = counts.get("collective::dcn_hist_bytes_fullwidth", 0)
    assert actual > 0 and full / actual >= 3.0


@pytest.mark.slow
def test_comm_overlap_staged_reduce_bitexact():
    """The double-buffered level-program reduction is numerically
    neutral: identical trees with tpu_comm_overlap on and off, with and
    without quantization (the rounding noise is seeded by GLOBAL slot
    position, so the staged halves draw the unsplit batch's noise)."""
    X, y = _sep_data(seed=11)
    base = dict(max_depth=3, num_leaves=8)
    for quant_extra in ({}, {"tpu_hist_quant": "int16"}):
        on = _train(X, y, tpu_comm_overlap="auto", **base, **quant_extra)
        off = _train(X, y, tpu_comm_overlap="off", **base, **quant_extra)
        assert _tree_digest(on) == _tree_digest(off)
        # the level phase actually ran (the overlap has something to
        # stage) — counter flushed at finalize
        import lightgbm_tpu.telemetry as tel
        tel.enable("timers")
        try:
            tl = on._booster.tree_learner
            assert tl.comm_overlap is True
        finally:
            tel.enable("off")


@pytest.mark.slow
def test_voting_quantized_exchange_learns_and_compresses():
    """PV-Tree voting with the int16 winner-window exchange: the model
    still learns the separating feature, the exchange is deterministic,
    and the byte model records the window compression (windows + vote
    indices far below full planes)."""
    import lightgbm_tpu.telemetry as tel
    from lightgbm_tpu.telemetry import events as tel_events
    # 12 features, top_k=2: the voted window (2k = 4 features) is a
    # third of the feature space, so the window exchange + int16 codes
    # clear the 3x acceptance pin with margin (at Expo widths the
    # pre-selection alone is ~16x)
    X, y = _sep_data(seed=5, f=12)
    tel.enable("timers")
    try:
        tel.reset()
        bst = _train(X, y, tree_learner="voting", top_k=2,
                     tpu_hist_quant="int16")
        tl = bst._booster.tree_learner
        assert getattr(tl, "_persist_carry", None) is not None
        gr = tl._persist_gr
        assert gr.voting and gr.quant is not None
        assert 0.0 < gr.reduced_feature_frac < 1.0
        tl.flush_level_stats()
        counts = tel_events.counts_snapshot()
    finally:
        tel.reset()
        tel.enable("off")
    # the separating feature must win the vote and the splits
    feats = {int(f) for t in bst._booster.models
             for f in t.split_feature[:t.num_leaves - 1]}
    assert 0 in feats
    bst2 = _train(X, y, tree_learner="voting", top_k=2,
                  tpu_hist_quant="int16")
    assert _tree_digest(bst2) == _tree_digest(bst)
    actual = counts.get("collective::dcn_hist_bytes", 0)
    full = counts.get("collective::dcn_hist_bytes_fullwidth", 0)
    assert actual > 0 and full / actual >= 3.0
