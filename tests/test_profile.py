"""Per-dispatch profiling + online cost-model calibration
(repro.obs.profile, repro.core.costmodel.CalibratedCostModel).

The structural guarantee mirrors PR 8's tracing audits: an enabled
DispatchProfiler consumes only host timestamps the engines already take
at block-boundary syncs, so sync_count AND the greedy token streams are
bit-identical with profiling on and off — audited here on all three
engines (paged, scheduler under preemption, speculative).  On top, the
calibration layer's contract: prequential EMA corrections over
log(measured/predicted) per (kind × arm), kind-level fallback, JSON
round-trip, and the measured drift feeding back into predict() and an
already-fit AutoTuner's surrogates.
"""
import json
import math

import jax
import numpy as np
import pytest

from repro.core.costmodel import (TIERS, CalibratedCostModel,
                                  dispatch_estimate, predict,
                                  tier_for_devices)
from repro.core.space import EfficiencyConfig
from repro.obs import DISPATCH_KINDS, DispatchProfiler


def _setup(kv_dtype=None):
    from repro.configs import get_smoke_config
    from repro.models.model import LM
    cfg = get_smoke_config("qwen2-1.5b").with_(dtype="float32")
    params = LM(cfg).init(jax.random.PRNGKey(0))
    if kv_dtype:
        cfg = cfg.with_(kv_cache_dtype=kv_dtype)
    rng = np.random.default_rng(0)
    return LM(cfg), params, rng


def _drive(eng, prompts, max_new=9):
    ids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    done = eng.run_to_completion()
    return [done[i].out_tokens for i in ids]


# ---------------------------------------------------------------------------
# sync-count + token identity: profiling must be free


def test_profiling_is_sync_free_paged_engine():
    from repro.serve.engine import PagedEngine
    lm, params, rng = _setup()
    prompts = [rng.integers(0, lm.cfg.vocab_size, (n,)).tolist()
               for n in (8, 5)]

    def run(profiler=None):
        eng = PagedEngine(lm, params, n_slots=2, max_len=64, seed=0,
                          page_size=8, decode_block=4, profiler=profiler)
        return _drive(eng, prompts), eng.sync_count

    base_toks, base_syncs = run()
    prof = DispatchProfiler(enabled=True)
    toks, syncs = run(profiler=prof)
    assert toks == base_toks
    assert syncs == base_syncs
    kinds = {s.kind for s in prof.samples}
    assert kinds == {"admit", "decode_block"}
    assert all(s.dur_s > 0 for s in prof.samples)
    # every dispatch the engine synced on is attributed exactly once
    assert len(prof.samples) == base_syncs


def test_profiling_is_sync_free_sched_under_preemption():
    """The scheduler's most dispatch-dense path: chunked prefill over a
    pool tight enough to force preemption."""
    from repro.sched import SchedEngine
    lm, params, rng = _setup()
    prompts = [rng.integers(0, lm.cfg.vocab_size, (8,)).tolist(),
               rng.integers(0, lm.cfg.vocab_size, (5,)).tolist()]

    def run(profiler=None):
        eng = SchedEngine(lm, params, policy="fcfs", prefix_cache=False,
                          n_slots=2, seed=0, page_size=8, decode_block=4,
                          prefill_chunk=8, max_len=48, n_pages=7,
                          profiler=profiler)
        toks = _drive(eng, prompts, max_new=20)
        return toks, eng.sync_count, eng.stats.preemptions

    base_toks, base_syncs, base_preempt = run()
    prof = DispatchProfiler(enabled=True)
    toks, syncs, preempt = run(profiler=prof)
    assert base_preempt > 0
    assert toks == base_toks
    assert syncs == base_syncs
    assert preempt == base_preempt
    assert {s.kind for s in prof.samples} <= {"admit", "prefill_chunk",
                                              "decode_block"}
    assert any(s.kind == "admit" for s in prof.samples)


def test_profiling_is_sync_free_spec_engine():
    from repro.spec import SpecEngine
    lm, params, rng = _setup()
    prompts = []
    for _ in range(3):
        pat = rng.integers(0, lm.cfg.vocab_size, (6,)).tolist()
        prompts.append(pat * 3 + rng.integers(0, lm.cfg.vocab_size,
                                              (3,)).tolist())

    def run(profiler=None):
        eng = SpecEngine(lm, params, spec="ngram", n_slots=2, max_len=96,
                         seed=0, page_size=8, decode_block=4,
                         prefill_chunk=16, policy="fcfs",
                         prefix_cache=False, profiler=profiler)
        toks = _drive(eng, prompts, max_new=16)
        return toks, eng.sync_count, eng

    base_toks, base_syncs, base = run()
    prof = DispatchProfiler(enabled=True)
    toks, syncs, eng = run(profiler=prof)
    assert base.spec_stats.verify_steps > 0        # speculation happened
    assert toks == base_toks
    assert syncs == base_syncs
    kinds = {s.kind for s in prof.samples}
    assert "draft_propose" in kinds and "spec_round" in kinds


# ---------------------------------------------------------------------------
# profiler mechanics


def test_disabled_profiler_is_noop_and_schema_safe():
    from repro.obs import MetricsRegistry
    prof = DispatchProfiler(enabled=False)
    prof.bind(object())                      # never touches the cfg
    prof.record("admit", 0.0, 1.0, tokens=4)
    assert prof.samples == [] and prof.arm == ""
    m = MetricsRegistry()
    prof.export_gauges(m)
    assert m.snapshot()["gauges"] == {}      # no profile_* families


def test_profiler_arm_label_and_bucket():
    lm, _, _ = _setup(kv_dtype="int8")
    prof = DispatchProfiler(enabled=True)
    prof.bind(lm.cfg, model_parallel=2)
    assert prof.arm == (f"kv=int8,q={lm.cfg.quant}:"
                        f"{lm.cfg.quant_matmul_impl},"
                        f"k={lm.cfg.spec_draft_k},mp=2")
    prof.record("decode_block", 1.0, 1.5, steps=4, bucket=4)
    s = prof.samples[0]
    assert s.arm.endswith(",b=4") and s.dur_s == pytest.approx(0.5)


def test_profiler_summary_cost_analysis_and_gauges():
    """The lazy cost_analysis path: summary() lowers the engine's own
    jit functions against the captured abstract shapes and reports
    achieved FLOP/s + HBM B/s and roofline attainment vs the tier."""
    from repro.obs import MetricsRegistry
    from repro.serve.engine import PagedEngine
    lm, params, rng = _setup()
    prompts = [rng.integers(0, lm.cfg.vocab_size, (6,)).tolist()]
    prof = DispatchProfiler(enabled=True)
    eng = PagedEngine(lm, params, n_slots=2, max_len=64, seed=0,
                      page_size=8, decode_block=4, profiler=prof)
    _drive(eng, prompts, max_new=8)
    summ = prof.summary(TIERS["v5e-1"])
    assert summ                              # at least one (kind, arm)
    for agg in summ.values():
        assert agg["count"] >= 1 and agg["seconds"] > 0
        assert agg["flops"] > 0              # compiled cost_analysis
        assert 0 < agg["attainment"] < 1     # CPU never hits TPU peak
    m = MetricsRegistry()
    prof.export_gauges(m, TIERS["v5e-1"])
    fams = {k.split("{")[0] for k in m.snapshot()["gauges"]}
    assert fams == {"profile_dispatch_seconds_total",
                    "profile_dispatch_count",
                    "profile_roofline_attainment"}


# ---------------------------------------------------------------------------
# dispatch-level analytic estimates


def test_dispatch_estimate_covers_all_kinds():
    lm, _, _ = _setup()
    for kind in DISPATCH_KINDS:
        s = dispatch_estimate(lm.cfg, kind=kind, tokens=16, rows=2,
                              steps=4, bucket=8, ctx=32)
        assert s > 0, kind
    with pytest.raises(ValueError):
        dispatch_estimate(lm.cfg, kind="warp")


def test_dispatch_estimate_scales_with_steps_and_spec_floor():
    lm, _, _ = _setup()
    one = dispatch_estimate(lm.cfg, kind="decode_block", rows=2, steps=1,
                            ctx=32)
    four = dispatch_estimate(lm.cfg, kind="decode_block", rows=2, steps=4,
                             ctx=32)
    assert four == pytest.approx(4 * one)
    # spec_decode="none" on the config must not zero the draft estimate
    # (an engine built with an explicit drafter still dispatches drafts,
    # and a zero prediction is uncalibratable)
    assert lm.cfg.spec_decode == "none"
    assert dispatch_estimate(lm.cfg, kind="draft_propose", rows=2,
                             bucket=4, ctx=32) > 0


# ---------------------------------------------------------------------------
# CalibratedCostModel


def test_calibration_ema_correction_and_fallback():
    c = CalibratedCostModel(beta=0.25)
    assert c.correction("decode_block") == 1.0         # nothing fit yet
    c.update("decode_block", "armA", measured_s=2e-3, predicted_s=1e-3)
    assert c.correction("decode_block", "armA") == pytest.approx(2.0)
    # EMA: second sample at ratio 4 moves the factor toward it
    c.update("decode_block", "armA", measured_s=4e-3, predicted_s=1e-3)
    expect = math.exp(0.75 * math.log(2) + 0.25 * math.log(4))
    assert c.correction("decode_block", "armA") == pytest.approx(expect)
    # unseen arm falls back to the kind-level weighted mean
    assert c.correction("decode_block", "armB") == pytest.approx(expect)
    assert c.correction("spec_round", "armA") == 1.0   # unseen kind
    assert c.calibrate("decode_block", 1e-3, "armA") == pytest.approx(
        expect * 1e-3)


def test_calibration_feeds_back_into_predict():
    lm, _, _ = _setup()
    eff = EfficiencyConfig.default()
    tier = TIERS["v5e-1"]
    base = predict(lm.cfg, eff, tier, prompt=64, gen=32)
    c = CalibratedCostModel()
    c.update("decode_block", "arm", measured_s=3e-3, predicted_s=1e-3)
    assert c.phase_scale("decode") == pytest.approx(3.0)
    assert c.phase_scale("prefill") == 1.0             # no prefill samples
    cal = predict(lm.cfg, eff, tier, prompt=64, gen=32, calibration=c)
    assert cal["latency_ms"] > base["latency_ms"]
    assert cal["energy_j"] > base["energy_j"]


def test_calibration_json_roundtrip(tmp_path):
    c = CalibratedCostModel(beta=0.5)
    c.update("admit", "a1", 2e-3, 1e-3)
    c.update("decode_block", "a2", 5e-3, 1e-3)
    p = tmp_path / "calib.json"
    c.save(str(p))
    c2 = CalibratedCostModel.load(str(p))
    assert c2.beta == 0.5 and c2.n_samples == c.n_samples
    assert c2.correction("admit", "a1") == pytest.approx(
        c.correction("admit", "a1"))
    assert json.loads(p.read_text())["factors"]        # sorted, stable


def test_fit_profile_prequential_halves_median_error():
    """The PR's acceptance claim in miniature: samples whose measured
    times sit at a consistent multiple of the analytic estimate must see
    their median relative prediction error drop >= 2x once the online
    corrections are in the loop (the first sample per series is
    predicted uncorrected — that's the prequential part)."""
    lm, _, _ = _setup()
    prof = DispatchProfiler(enabled=True)
    prof.bind(lm.cfg)
    rng = np.random.default_rng(7)
    for i in range(24):
        kind = ("admit", "decode_block")[i % 2]
        est = dispatch_estimate(lm.cfg, TIERS["v5e-1"], kind=kind,
                                tokens=8, rows=2, steps=4, bucket=8,
                                ctx=32)
        measured = 50.0 * est * float(rng.uniform(0.9, 1.1))
        prof.record(kind, 0.0, measured, tokens=8, rows=2, steps=4,
                    bucket=8, ctx=32)
    calib = CalibratedCostModel()
    recs = calib.fit_profile(prof, lm.cfg)
    assert len(recs) == 24

    def med_err(key):
        return float(np.median([abs(r[key] - r["measured_s"])
                                / r["measured_s"] for r in recs]))

    assert med_err("predicted_s") >= 2 * med_err("calibrated_s")
    # drift gauges export one series per (kind, arm)
    from repro.obs import MetricsRegistry
    m = MetricsRegistry()
    calib.register_metrics(m)
    g = m.snapshot()["gauges"]
    assert sum(k.startswith("costmodel_drift_ratio") for k in g) == 2
    assert all(np.isfinite(v) for v in g.values())


# ---------------------------------------------------------------------------
# tuner / evaluator consumption


def test_tuner_recalibrate_shifts_fitted_surrogates():
    from repro.core.evaluator import Evaluator
    from repro.core.features import TASKS
    from repro.core.tuner import AutoTuner
    from repro.configs import get_config
    cfg = get_config("qwen2-1.5b")
    ev = Evaluator(cfg, TASKS["mmlu"], TIERS["v5e-1"])
    tuner = AutoTuner(ev, n0=4, refine_iters=0, k_per_iter=2,
                      pop_size=8, generations=2, seed=0, ensemble_k=2)
    # fit tiny surrogates directly (run() is exercised elsewhere)
    rng = np.random.default_rng(0)
    from repro.core.space import encode_config, sample_config
    cfgs = [sample_config(rng, tuner.mask) for _ in range(8)]
    tuner.X = [encode_config(c) for c in cfgs]
    tuner.Y = [ev.evaluate(c) for c in cfgs]
    tuner._fit()
    x = np.asarray(tuner.X[:2])
    mu_before, _ = tuner.surrogates["lat"].predict(x)

    calib = CalibratedCostModel()
    calib.update("decode_block", "arm", measured_s=4e-3, predicted_s=1e-3)
    shifts = tuner.recalibrate(calib)
    assert shifts["lat"] > 0                  # slower than analytic
    mu_after, _ = tuner.surrogates["lat"].predict(x)
    np.testing.assert_allclose(mu_after - mu_before, shifts["lat"])
    assert tuner.ev.calibration is calib      # future evals calibrated
    # accuracy surrogate untouched (corrections are latency/energy-only)
    assert tuner.surrogates["acc"].offset == 0.0


def test_tuner_constructor_threads_calibration_into_evaluator():
    from repro.core.evaluator import Evaluator
    from repro.core.features import TASKS
    from repro.core.tuner import AutoTuner
    from repro.configs import get_config
    cfg = get_config("qwen2-1.5b")
    ev = Evaluator(cfg, TASKS["mmlu"], TIERS["v5e-1"])
    calib = CalibratedCostModel()
    calib.update("admit", "arm", 2e-3, 1e-3)
    AutoTuner(ev, calibration=calib)
    assert ev.calibration is calib
    eff = EfficiencyConfig.default()
    uncal = Evaluator(cfg, TASKS["mmlu"], TIERS["v5e-1"])
    assert ev.evaluate(eff)[1] > uncal.evaluate(eff)[1]   # lat_ms scaled


def test_tier_for_devices_by_device_kind():
    """Peak rates come from the device kind: a v5e chip gets the v5e
    tier, a chip count or kind with no tier gets none (no roofline share
    is reported rather than another chip's)."""
    from types import SimpleNamespace as NS
    v5e, other = NS(device_kind="TPU v5 lite"), NS(device_kind="cpu")
    assert tier_for_devices([v5e]) is TIERS["v5e-1"]
    assert tier_for_devices([v5e] * 8) is TIERS["v5e-8"]
    assert tier_for_devices([v5e] * 4) is None
    assert tier_for_devices([other]) is None
    prof = DispatchProfiler(enabled=True)
    prof.record("admit", 0.0, 1.0, tokens=4)
    assert all("attainment" not in a for a in prof.summary(None).values())
