"""Observability layer (repro.obs): metrics-registry primitives,
trace-event recorder, per-engine snapshot schema stability (golden key
sets), request-span invariants (nesting / closure / token coverage /
readmit spans after preemption), and per-drive telemetry deltas.

The sync-free guarantee itself — tracing on changes neither sync_count
nor the greedy token streams — is audited in tests/test_serving.py and
tests/test_sched.py next to the engines' own sync accounting.
"""
import json

import jax
import numpy as np
import pytest

from repro.obs import MetricsRegistry, Tracer
from repro.obs.metrics import (DEFAULT_BUCKETS, histogram_quantile,
                               histogram_quantiles, series_key)
from repro.obs.trace import PID_REQUESTS, request_span_trees


# ---------------------------------------------------------------------------
# registry primitives


def test_series_key_sorts_labels():
    assert series_key("m") == "m"
    assert series_key("m", {"b": 1, "a": "x"}) == 'm{a="x",b="1"}'
    assert series_key("m", {"a": "x", "b": 1}) == series_key(
        "m", {"b": 1, "a": "x"})


def test_counter_gauge_histogram_snapshot():
    m = MetricsRegistry()
    c = m.counter("reqs_total", "requests")
    c.inc()
    c.inc(2, phase="prefill")
    m.gauge("depth", "queue depth").set(3)
    h = m.histogram("lat_seconds", "latency")
    h.observe(0.002)
    h.observe(7.0)
    snap = m.snapshot()
    assert snap["counters"]["reqs_total"] == 1.0
    assert snap["counters"]['reqs_total{phase="prefill"}'] == 2.0
    assert snap["gauges"]["depth"] == 3.0
    hs = snap["histograms"]["lat_seconds"]
    assert hs["count"] == 2 and hs["sum"] == pytest.approx(7.002)
    # cumulative buckets: 0.002 lands in every le >= 0.0025; 7.0 only
    # in le >= 10 and +Inf
    assert hs["buckets"][-1] == 2                      # +Inf
    assert hs["buckets"][DEFAULT_BUCKETS.index(0.001)] == 0
    assert hs["buckets"][DEFAULT_BUCKETS.index(0.0025)] == 1
    assert hs["buckets"][DEFAULT_BUCKETS.index(10.0)] == 2


def test_fn_backed_series_read_live_values():
    m = MetricsRegistry()
    box = {"v": 5}
    m.counter("acc_total", "bridged accumulator", fn=lambda: box["v"])
    assert m.snapshot()["counters"]["acc_total"] == 5.0
    box["v"] = 9
    assert m.snapshot()["counters"]["acc_total"] == 9.0


def test_register_idempotent_same_kind_raises_on_mismatch():
    m = MetricsRegistry()
    a = m.counter("x_total")
    b = m.counter("x_total")
    assert a is b
    with pytest.raises(ValueError, match="already registered"):
        m.gauge("x_total")


def test_delta_counters_subtract_gauges_pass_through():
    m = MetricsRegistry()
    c = m.counter("n_total")
    g = m.gauge("occ")
    h = m.histogram("w_seconds")
    c.inc(3)
    g.set(10)
    h.observe(0.5)
    snap = m.snapshot()
    c.inc(4)
    g.set(2)
    h.observe(0.5)
    h.observe(1.5)
    d = m.delta(snap)
    assert d["counters"]["n_total"] == 4.0
    assert d["gauges"]["occ"] == 2.0                   # current, not diff
    assert d["histograms"]["w_seconds"]["count"] == 2
    assert d["histograms"]["w_seconds"]["sum"] == pytest.approx(2.0)
    # a series born after the snapshot keeps its full value
    c.inc(1, new="yes")
    assert m.delta(snap)["counters"]['n_total{new="yes"}'] == 1.0


def test_delta_histogram_new_labeled_series_after_snapshot():
    """A labeled histogram series born after the snapshot has no
    baseline to subtract: the delta carries its full value."""
    m = MetricsRegistry()
    h = m.histogram("lat_seconds")
    h.observe(0.1, phase="prefill")
    snap = m.snapshot()
    h.observe(0.2, phase="prefill")
    h.observe(0.4, phase="decode")           # new series post-snapshot
    d = m.delta(snap)["histograms"]
    assert d['lat_seconds{phase="prefill"}']["count"] == 1
    assert d['lat_seconds{phase="decode"}']["count"] == 1
    assert d['lat_seconds{phase="decode"}']["sum"] == pytest.approx(0.4)


def test_delta_histogram_buckets_subtract_elementwise():
    """Cumulative bucket counts subtract bucket-by-bucket, so quantiles
    over a delta reflect only the observations since the snapshot."""
    m = MetricsRegistry()
    h = m.histogram("w_seconds")
    h.observe(0.002)                         # le >= 0.0025 before snap
    snap = m.snapshot()
    h.observe(0.2)                           # le >= 0.25 after snap
    d = m.delta(snap)["histograms"]["w_seconds"]
    assert d["count"] == 1
    assert d["buckets"][DEFAULT_BUCKETS.index(0.0025)] == 0   # pre-snap
    assert d["buckets"][DEFAULT_BUCKETS.index(0.1)] == 0
    assert d["buckets"][DEFAULT_BUCKETS.index(0.25)] == 1
    assert d["buckets"][-1] == 1                              # +Inf


# ---------------------------------------------------------------------------
# histogram quantiles (shared percentile path for exporters + benchmarks)


def test_histogram_quantile_interpolates_within_bucket():
    # 10 observations uniformly credited to the (0.1, 0.25] bucket:
    # cumulative counts are 0 up to le=0.1, then 10 from le=0.25 on
    cum = [0] * DEFAULT_BUCKETS.index(0.25) + [10] * (
        len(DEFAULT_BUCKETS) - DEFAULT_BUCKETS.index(0.25) + 1)
    # rank q*10 interpolates linearly between the 0.1 and 0.25 bounds
    assert histogram_quantile(0.5, cum) == pytest.approx(
        0.1 + (0.25 - 0.1) * 0.5)
    assert histogram_quantile(1.0, cum) == pytest.approx(0.25)
    # ranks below the first populated bucket stay inside it
    assert histogram_quantile(0.01, cum) <= 0.25


def test_histogram_quantile_edge_cases():
    n = len(DEFAULT_BUCKETS) + 1
    assert histogram_quantile(0.5, [0] * n) == 0.0          # empty
    # everything in +Inf: clamp to the largest finite bound
    cum = [0] * len(DEFAULT_BUCKETS) + [5]
    assert histogram_quantile(0.99, cum) == DEFAULT_BUCKETS[-1]
    # first bucket: interpolate from 0 toward the first bound
    cum = [4] * n
    assert 0.0 < histogram_quantile(0.5, cum) <= DEFAULT_BUCKETS[0]


def test_histogram_quantiles_from_snapshot_dict():
    m = MetricsRegistry()
    h = m.histogram("lat_seconds")
    for v in (0.03, 0.03, 0.03, 4.0):
        h.observe(v)
    qs = histogram_quantiles(m.snapshot()["histograms"]["lat_seconds"])
    assert set(qs) == {"p50", "p95", "p99"}
    assert qs["p50"] <= 0.05                  # p50 in the 0.05 bucket
    assert 2.5 < qs["p99"] <= 5.0             # tail lands in (2.5, 5]


def test_prometheus_text_exports_quantile_series():
    m = MetricsRegistry()
    h = m.histogram("lat_seconds", "latency")
    h.observe(0.3)
    h.observe(0.3, phase="decode")
    text = m.to_prometheus_text()
    # bare and labeled series each get interpolated quantile lines
    assert 'lat_seconds{quantile="0.5"}' in text
    assert 'lat_seconds{phase="decode",quantile="0.99"}' in text
    for line in text.splitlines():
        if line.startswith('lat_seconds{quantile="0.5"}'):
            v = float(line.split()[-1])
            assert 0.25 < v <= 0.5            # inside the covering bucket


def test_prometheus_text_and_json_exporters():
    m = MetricsRegistry()
    m.counter("reqs_total", "requests seen").inc(2, kind="a")
    m.gauge("depth").set(1)
    m.histogram("lat_seconds", "latency").observe(0.3)
    text = m.to_prometheus_text()
    assert "# HELP reqs_total requests seen" in text
    assert "# TYPE reqs_total counter" in text
    assert 'reqs_total{kind="a"} 2.0' in text
    assert "# TYPE lat_seconds histogram" in text
    assert 'lat_seconds_bucket{le="0.5"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 1' in text
    assert "lat_seconds_sum 0.3" in text
    assert "lat_seconds_count 1" in text
    doc = json.loads(m.to_json(arch="smoke"))
    assert doc["arch"] == "smoke"
    assert doc["counters"]['reqs_total{kind="a"}'] == 2.0


# ---------------------------------------------------------------------------
# tracer


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    tr.begin("request", 0)
    tr.complete("decode_block", 0, 0.0, 1.0)
    tr.instant("preempt", 0)
    tr.end("request", 0)
    tr.name_thread(0, "req 0")
    assert tr.events == []
    assert tr.to_json()["traceEvents"] == []


def test_request_span_trees_nesting_and_malformed():
    tr = Tracer(enabled=True)
    tr.begin("request", 7, ts=tr._t0 + 0.0)
    tr.begin("queue", 7, ts=tr._t0 + 0.001)
    tr.end("queue", 7, ts=tr._t0 + 0.002)
    tr.complete("decode_block", 7, tr._t0 + 0.003, tr._t0 + 0.004,
                args={"tokens": 4})
    tr.end("request", 7, ts=tr._t0 + 0.005)
    tr.begin("request", 8, ts=tr._t0 + 0.0)       # never closed
    trees = request_span_trees(tr.to_json())
    assert trees[7]["complete"] and trees[7]["stack_ok"]
    names = [s[0] for s in trees[7]["spans"]]
    assert set(names) == {"request", "queue", "decode_block"}
    assert not trees[8]["complete"] and not trees[8]["stack_ok"]


# ---------------------------------------------------------------------------
# engine snapshot schema (golden key sets)

EAGER_COUNTERS = {
    "serve_requests_submitted_total", "serve_requests_retired_total",
    "serve_tokens_emitted_total", "serve_phase_seconds_total",
    "resil_requests_total",
}
EAGER_GAUGES = {"serve_queue_depth", "serve_slots_active"}
EAGER_HISTS = {"serve_queue_wait_seconds", "serve_ttft_seconds",
               "serve_tpot_seconds"}

PAGED_COUNTERS = EAGER_COUNTERS | {
    "serve_host_syncs_total", "serve_decode_steps_total",
    "serve_decode_tokens_total", "serve_eos_total",
    "serve_kv_requant_events_total", "serve_prefill_dispatches_total",
    "serve_decode_dispatches_total",
    "serve_span_seconds_total", "serve_spans_total",
}
PAGED_GAUGES = EAGER_GAUGES | {"serve_pages_free", "serve_pages_total"}

SCHED_COUNTERS = PAGED_COUNTERS | {
    "sched_admitted_total", "sched_preemptions_total",
    "sched_chunks_total", "sched_prefill_tokens_total",
    "sched_prefix_hit_tokens_total", "sched_slo_rejected_total",
    "prefix_lookups_total", "prefix_hits_total",
    "prefix_hit_tokens_total", "prefix_inserted_total",
    "prefix_evicted_total",
}
SCHED_GAUGES = PAGED_GAUGES | {"sched_policy_info", "prefix_cached_pages"}

SPEC_COUNTERS = SCHED_COUNTERS | {
    "spec_verify_steps_total", "spec_slot_steps_total",
    "spec_drafts_proposed_total", "spec_drafts_accepted_total",
    "spec_spec_tokens_total", "spec_fallback_steps_total",
    "spec_skipped_urgent_total", "spec_cow_pages_total",
}
SPEC_GAUGES = SCHED_GAUGES | {"spec_arm_info"}


def _setup():
    from repro.configs import get_smoke_config
    from repro.models.model import LM
    cfg = get_smoke_config("qwen2-1.5b").with_(dtype="float32")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    return lm, params, np.random.default_rng(0)


def _basenames(series: dict) -> set:
    return {k.split("{")[0] for k in series}


def _drive(eng, prompts, max_new=6):
    ids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    done = eng.run_to_completion()
    return {i: done[i].out_tokens for i in ids}


@pytest.fixture(scope="module")
def smoke():
    return _setup()


def _schema_of(eng, prompts):
    _drive(eng, prompts)
    snap = eng.metrics.snapshot()
    return (_basenames(snap["counters"]), _basenames(snap["gauges"]),
            _basenames(snap["histograms"]))


def test_metrics_schema_eager_engine(smoke):
    """Golden key set: adding/renaming engine metrics must be a
    deliberate, test-visible change (dashboards key on these names)."""
    from repro.serve.engine import Engine
    lm, params, rng = smoke
    prompts = [rng.integers(0, lm.cfg.vocab_size, (n,)).tolist()
               for n in (8, 5)]
    c, g, h = _schema_of(Engine(lm, params, n_slots=2, max_len=64,
                                seed=0), prompts)
    assert c == EAGER_COUNTERS
    assert g == EAGER_GAUGES
    assert h == EAGER_HISTS


def test_metrics_schema_paged_engine(smoke):
    from repro.serve.engine import PagedEngine
    lm, params, rng = smoke
    prompts = [rng.integers(0, lm.cfg.vocab_size, (n,)).tolist()
               for n in (8, 5)]
    c, g, h = _schema_of(PagedEngine(lm, params, n_slots=2, max_len=64,
                                     seed=0, page_size=8, decode_block=4),
                         prompts)
    assert c == PAGED_COUNTERS
    assert g == PAGED_GAUGES
    assert h == EAGER_HISTS


def test_metrics_schema_sched_and_spec_engines(smoke):
    from repro.sched import SchedEngine
    from repro.spec import SpecEngine
    lm, params, rng = smoke
    prompts = [rng.integers(0, lm.cfg.vocab_size, (n,)).tolist()
               for n in (8, 5)]
    kw = dict(n_slots=2, max_len=64, seed=0, page_size=8, decode_block=4,
              prefill_chunk=16, policy="fcfs", prefix_cache=True)
    c, g, h = _schema_of(SchedEngine(lm, params, **kw), prompts)
    assert c == SCHED_COUNTERS
    assert g == SCHED_GAUGES
    assert h == EAGER_HISTS
    c, g, h = _schema_of(SpecEngine(lm, params, spec="ngram", **kw),
                         prompts)
    assert c == SPEC_COUNTERS
    assert g == SPEC_GAUGES
    # label payloads on the info gauges
    snap = None
    eng = SpecEngine(lm, params, spec="ngram", **kw)
    snap = eng.metrics.snapshot()
    assert snap["gauges"]['sched_policy_info{policy="fcfs"}'] == 1.0
    assert snap["gauges"]['spec_arm_info{arm="ngram"}'] == 1.0


def test_metrics_counters_match_legacy_accumulators(smoke):
    """The registry is a view over the legacy accumulators — both read
    surfaces must agree after a drive."""
    from repro.sched import SchedEngine
    lm, params, rng = smoke
    eng = SchedEngine(lm, params, n_slots=2, max_len=64, seed=0,
                      page_size=8, decode_block=4, prefill_chunk=16,
                      policy="fcfs", prefix_cache=False)
    prompts = [rng.integers(0, lm.cfg.vocab_size, (n,)).tolist()
               for n in (8, 5, 12)]
    outs = _drive(eng, prompts, max_new=8)
    c = eng.metrics.snapshot()["counters"]
    assert c["serve_host_syncs_total"] == eng.sync_count
    assert c["sched_chunks_total"] == eng.stats.chunks
    assert c["sched_prefill_tokens_total"] == eng.stats.prefill_tokens
    assert c["serve_requests_submitted_total"] == len(prompts)
    assert c["serve_requests_retired_total"] == len(prompts)
    total = sum(len(t) for t in outs.values())
    assert c["serve_tokens_emitted_total"] == total
    # device-counted decode tokens + one first-token per prefill
    assert c["serve_decode_tokens_total"] == total - len(prompts)


# ---------------------------------------------------------------------------
# span invariants


def _emitted_from_spans(spans) -> int:
    n = 0
    for name, _, _, args in spans:
        if name in ("decode_block", "decode_step", "spec_round"):
            n += args.get("tokens", 0)
        elif name in ("prefill", "prefill_chunk"):
            n += args.get("emitted", 0)
    return n


def test_span_tree_invariants_sched(smoke):
    """Every request's track closes cleanly, prefill chunks cover the
    whole prompt, and decode/prefill spans account for every emitted
    token."""
    from repro.sched import SchedEngine
    lm, params, rng = smoke
    tr = Tracer(enabled=True)
    eng = SchedEngine(lm, params, n_slots=2, max_len=64, seed=0,
                      page_size=8, decode_block=4, prefill_chunk=16,
                      policy="fcfs", prefix_cache=False, tracer=tr)
    prompts = [rng.integers(0, lm.cfg.vocab_size, (n,)).tolist()
               for n in (8, 5, 12, 20)]
    outs = _drive(eng, prompts, max_new=9)
    trees = request_span_trees(tr.to_json())
    assert set(trees) == set(outs)
    for rid, out_toks in outs.items():
        t = trees[rid]
        assert t["complete"] and t["stack_ok"], f"rid {rid} malformed"
        names = [s[0] for s in t["spans"]]
        assert names.count("request") == 1
        assert names.count("queue") >= 1
        chunk_toks = sum(s[3]["tokens"] for s in t["spans"]
                         if s[0] == "prefill_chunk")
        assert chunk_toks == len(prompts[rid])
        assert _emitted_from_spans(t["spans"]) == len(out_toks)
        # spans nest inside the request envelope
        req = [s for s in t["spans"] if s[0] == "request"][0]
        for name, t0, t1, _ in t["spans"]:
            assert req[1] <= t0 and t1 <= req[2] + 1e-3, \
                f"{name} escapes the request span"


def test_preempted_request_gets_readmit_queue_span(smoke):
    """A page-pressure preemption must show up on the victim's track:
    a 'preempt' instant plus a re-opened queue span per preemption —
    and the track still closes cleanly."""
    from repro.sched import SchedEngine
    lm, params, rng = smoke
    tr = Tracer(enabled=True)
    eng = SchedEngine(lm, params, n_slots=2, max_len=48, seed=0,
                      page_size=8, decode_block=4, prefill_chunk=8,
                      policy="fcfs", prefix_cache=False, n_pages=7,
                      tracer=tr)
    prompts = [rng.integers(0, lm.cfg.vocab_size, (8,)).tolist(),
               rng.integers(0, lm.cfg.vocab_size, (5,)).tolist()]
    outs = _drive(eng, prompts, max_new=20)
    assert eng.stats.preemptions > 0
    victims = [r for r in eng.registry.values() if r.preemptions]
    assert victims
    trees = request_span_trees(tr.to_json())
    instants = [e for e in tr.events if e.get("ph") == "i"
                and e["name"] == "preempt"]
    assert len(instants) == eng.stats.preemptions
    for req in victims:
        t = trees[req.rid]
        assert t["complete"] and t["stack_ok"]
        queue_spans = [s for s in t["spans"] if s[0] == "queue"]
        assert len(queue_spans) == 1 + req.preemptions
        assert any(e["tid"] == req.rid for e in instants)
        assert _emitted_from_spans(t["spans"]) == len(outs[req.rid])


def test_spec_round_spans_cover_emitted_tokens(smoke):
    """SpecEngine rounds appear as per-request spec_round spans whose
    token args sum (with prefill first-tokens and fallback blocks) to
    the emitted stream."""
    from repro.spec import SpecEngine
    lm, params, rng = smoke
    pat = rng.integers(0, lm.cfg.vocab_size, (6,)).tolist()
    prompts = [pat * 3 + rng.integers(0, lm.cfg.vocab_size, (3,)).tolist()
               for _ in range(2)]
    tr = Tracer(enabled=True)
    eng = SpecEngine(lm, params, spec="ngram", draft_k=6, n_slots=2,
                     max_len=96, seed=0, page_size=8, decode_block=4,
                     prefill_chunk=16, policy="fcfs", prefix_cache=False,
                     tracer=tr)
    outs = _drive(eng, prompts, max_new=16)
    assert eng.spec_stats.verify_steps > 0
    trees = request_span_trees(tr.to_json())
    saw_round = False
    for rid, out_toks in outs.items():
        t = trees[rid]
        assert t["complete"] and t["stack_ok"]
        rounds = [s for s in t["spans"] if s[0] == "spec_round"]
        saw_round = saw_round or bool(rounds)
        for s in rounds:
            assert 0 <= s[3]["accepted"] <= s[3]["proposed"]
        assert _emitted_from_spans(t["spans"]) == len(out_toks)
    assert saw_round


# ---------------------------------------------------------------------------
# per-drive telemetry deltas (satellite: steady-state benchmark rows)


def test_telemetry_since_reports_per_drive_numbers(smoke):
    from repro.sched import SchedEngine
    lm, params, rng = smoke
    eng = SchedEngine(lm, params, n_slots=2, max_len=64, seed=0,
                      page_size=8, decode_block=4, prefill_chunk=16,
                      policy="fcfs", prefix_cache=False)
    prompts = [rng.integers(0, lm.cfg.vocab_size, (n,)).tolist()
               for n in (8, 5)]
    _drive(eng, prompts, max_new=6)                  # warm-up drive
    lifetime_before = eng.telemetry()
    snap = eng.metrics.snapshot()
    _drive(eng, prompts, max_new=6)                  # measured drive
    per_drive = eng.telemetry(since=snap)
    lifetime = eng.telemetry()
    assert per_drive["admitted"] == len(prompts)
    assert lifetime["admitted"] == 2 * len(prompts)
    assert per_drive["prefill_tokens"] == sum(len(p) for p in prompts)
    assert per_drive["chunks"] == lifetime["chunks"] \
        - lifetime_before["chunks"]
    assert per_drive["sync_count"] == lifetime["sync_count"] \
        - lifetime_before["sync_count"]
    assert per_drive["policy"] == "fcfs"


# ---------------------------------------------------------------------------
# program spans (repro.obs.trace.span): host phases of a scheduler tick

LEAF_SPANS = ("sched.admit", "sched.prefill.prep", "sched.prefill.launch",
              "sched.prefill.wait", "sched.prefill.finish", "sched.grow",
              "engine.decode.prep", "engine.decode.launch",
              "engine.decode.wait", "engine.decode.emit")


def _span_drive(smoke, tracer=None):
    """A tiny SchedEngine drive that reaches every phase: a staging chunk,
    continuation chunks (a prompt longer than the chunk), decode blocks
    with page growth."""
    from repro.sched import SchedEngine
    lm, params, _ = smoke
    rng = np.random.default_rng(3)
    eng = SchedEngine(lm, params, n_slots=2, max_len=64, seed=0,
                      page_size=8, decode_block=4, prefill_chunk=16,
                      policy="fcfs", prefix_cache=True, tracer=tracer)
    prompts = [rng.integers(0, lm.cfg.vocab_size, (n,)).tolist()
               for n in (40, 5, 12)]
    return eng, _drive(eng, prompts, max_new=9)


def _span_counters(eng) -> dict:
    c = eng.metrics.snapshot()["counters"]
    out = {}
    for k, v in c.items():
        if k.startswith(("serve_span_seconds_total{",
                         "serve_spans_total{")):
            family, name = k.split('{span="')
            out.setdefault(name.rstrip('"}'), {})[family] = v
    return out


def test_every_leaf_span_counts_within_the_tick(smoke):
    """Each leaf span's counters move on a drive that reaches every
    phase, and the leaf spans, which never overlap, take no more than
    the ticks (sched.step) that hold them."""
    eng, _ = _span_drive(smoke)
    spans = _span_counters(eng)
    for name in LEAF_SPANS:
        assert spans[name]["serve_spans_total"] > 0, name
        assert spans[name]["serve_span_seconds_total"] > 0, name
    tick = spans["sched.step"]
    assert tick["serve_spans_total"] >= spans["sched.admit"][
        "serve_spans_total"]
    leaf_s = sum(spans[n]["serve_span_seconds_total"] for n in LEAF_SPANS)
    assert leaf_s <= tick["serve_span_seconds_total"]
    # launch + wait are the phases' seconds (serve_phase_seconds_total)
    c = eng.metrics.snapshot()["counters"]
    for phase, head in (("decode", "engine.decode"),
                        ("prefill", "sched.prefill")):
        both = sum(spans[f"{head}.{p}"]["serve_span_seconds_total"]
                   for p in ("launch", "wait"))
        got = c[f'serve_phase_seconds_total{{phase="{phase}"}}']
        assert both <= got and got - both < 1e-3 * spans[f"{head}.wait"][
            "serve_spans_total"], phase


def test_chrome_tracer_changes_no_sync_and_no_token(smoke):
    """The Chrome tracer on or off: the same host syncs, the same greedy
    streams, the same span counts."""
    off, out_off = _span_drive(smoke)
    on, out_on = _span_drive(smoke, Tracer(enabled=True))
    assert on.sync_count == off.sync_count
    assert out_on == out_off
    counts = {k: v["serve_spans_total"] for k, v in
              _span_counters(off).items()}
    assert counts == {k: v["serve_spans_total"] for k, v in
                      _span_counters(on).items()}


def test_chrome_trace_carries_the_spans_on_the_engine_track(smoke):
    """Every program span is an X event on the engine track, one per
    span closed, each leaf inside a tick; the hand-written dispatch
    events they replace are gone."""
    from repro.obs.trace import PID_ENGINE
    tr = Tracer(enabled=True)
    eng, _ = _span_drive(smoke, tr)
    ev = [e for e in tr.events if e.get("pid") == PID_ENGINE
          and e.get("ph") == "X"]
    names = [e["name"] for e in ev]
    for name, v in _span_counters(eng).items():
        assert names.count(name) == v["serve_spans_total"], name
    assert "prefill_dispatch" not in names and "decode_block" not in names
    ticks = [(e["ts"], e["ts"] + e["dur"]) for e in ev
             if e["name"] == "sched.step"]
    for e in ev:
        if e["name"] in LEAF_SPANS:
            assert any(a <= e["ts"] and e["ts"] + e["dur"] <= b + 1e-3
                       for a, b in ticks), e["name"]
    wait = [e for e in ev if e["name"] == "engine.decode.wait"]
    assert all(e["args"]["steps"] == 4 for e in wait)
