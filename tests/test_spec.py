"""Speculative decoding subsystem (repro.spec): verify kernel vs
oracle, exact accept/reject math, drafters, adaptive controller,
copy-on-write rollback guard, engine token-identity vs the
non-speculative scheduler on bf16 AND int8 paged caches, the EDF
urgency gate, and the c_inf search-arm wiring.

Engine tests run the same CPU/interpret dispatch as the TPU artifact,
sized like tests/test_sched.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.spec import (AdaptiveDraftController, NgramDrafter, SpecEngine,
                        ensure_exclusive_tail, rollback_length, spec_accept)


# ---------------------------------------------------------------------------
# verify kernel vs oracle


def _quant_pool(rng, n, page, kh, d, dtype):
    raw = rng.normal(size=(n, kh, page, d)).astype(np.float32)
    if dtype == "bf16":
        return jnp.asarray(raw, jnp.bfloat16), None
    sc = np.abs(raw).max(axis=(2, 3)) / 127.0 + 1e-9            # (N,KH)
    q = np.clip(np.round(raw / sc[:, :, None, None]), -127, 127)
    return jnp.asarray(q, jnp.int8), jnp.asarray(sc, jnp.float32)


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
@pytest.mark.parametrize("kh", [1, 2, 4])
def test_verify_kernel_matches_ref(dtype, kh):
    """Multi-query prefix-extend kernel (verify instantiation) == gather
    oracle across GQA widths, partial pages, width-1 (plain decode) and
    width-0 (inactive) slots."""
    from repro.kernels.paged_attention.paged_attention import (
        paged_prefix_extend_pallas)
    from repro.kernels.paged_attention.ref import paged_prefix_extend_ref
    rng = np.random.default_rng(0)
    s_n, w_n, h, d, page, p_n = 4, 4, 4, 16, 8, 4
    n_pages = 1 + s_n * p_n
    q = jnp.asarray(rng.normal(size=(s_n, w_n, h, d)), jnp.float32)
    kp, ks = _quant_pool(rng, n_pages, page, kh, d, dtype)
    vp, vs = _quant_pool(rng, n_pages, page, kh, d, dtype)
    bt = jnp.asarray(rng.permutation(np.arange(1, n_pages))
                     .reshape(s_n, p_n), jnp.int32)
    lengths = jnp.asarray([13, 0, 24, 32], jnp.int32)   # partial/empty/full
    widths = jnp.asarray([4, 0, 1, 2], jnp.int32)
    ck = jnp.asarray(rng.normal(size=(s_n, w_n, kh, d)), jnp.bfloat16)
    cv = jnp.asarray(rng.normal(size=(s_n, w_n, kh, d)), jnp.bfloat16)
    ref = paged_prefix_extend_ref(q, kp, vp, bt, lengths, ck, cv,
                                  widths, ks, vs)
    ker = paged_prefix_extend_pallas(q, kp, vp, bt, lengths, ck, cv,
                                     widths, ks, vs, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)
    # width-0 slot returns exact zeros on both paths
    assert float(jnp.abs(ker[1]).max()) == 0.0
    assert float(jnp.abs(ref[1]).max()) == 0.0


def test_verify_width1_matches_decode_kernel():
    """A width-1 verify (no drafts) must score exactly what the plain
    decode kernel scores AFTER writing the token — same conditional."""
    from repro.kernels.paged_attention.ops import (
        paged_attention, paged_prefix_extend_attention)
    rng = np.random.default_rng(1)
    s_n, h, kh, d, page, p_n = 2, 4, 2, 16, 8, 3
    n_pages = 1 + s_n * p_n
    kp = jnp.asarray(rng.normal(size=(n_pages, kh, page, d)), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=(n_pages, kh, page, d)), jnp.bfloat16)
    bt = jnp.asarray(np.arange(1, n_pages).reshape(s_n, p_n), jnp.int32)
    lengths = jnp.asarray([9, 17], jnp.int32)
    q = jnp.asarray(rng.normal(size=(s_n, 1, h, d)), jnp.float32)
    ck = jnp.asarray(rng.normal(size=(s_n, 1, kh, d)), jnp.bfloat16)
    cv = jnp.asarray(rng.normal(size=(s_n, 1, kh, d)), jnp.bfloat16)
    ver = paged_prefix_extend_attention(q, kp, vp, bt, lengths, ck, cv,
                                        jnp.ones((s_n,), jnp.int32))
    # decode path: write the token at lengths, attend with lengths+1
    kp2 = kp.at[bt[jnp.arange(s_n), lengths // page], :,
                lengths % page].set(ck[:, 0])
    vp2 = vp.at[bt[jnp.arange(s_n), lengths // page], :,
                lengths % page].set(cv[:, 0])
    dec = paged_attention(q[:, 0], kp2, vp2, bt, lengths + 1)
    np.testing.assert_allclose(np.asarray(ver[:, 0]), np.asarray(dec),
                               atol=2e-2, rtol=2e-2)


# ---------------------------------------------------------------------------
# exact accept/reject math


def _accept(logits, fed, widths, active, temps, remaining, lengths,
            eos=-1, max_len=10_000, seed=0):
    y, n_emit, n_match = spec_accept(
        jnp.asarray(logits, jnp.float32), jnp.asarray(fed, jnp.int32),
        jnp.asarray(widths, jnp.int32), jnp.asarray(active),
        jnp.asarray(temps, jnp.float32), jnp.asarray(remaining, jnp.int32),
        jnp.asarray(lengths, jnp.int32), eos, max_len,
        jax.random.PRNGKey(seed))
    return np.asarray(y), np.asarray(n_emit), np.asarray(n_match)


def test_spec_accept_greedy_prefix_rule():
    """Greedy: drafts accepted up to the first argmax mismatch; the
    correction token is the target argmax at the mismatch position; all
    emitted tokens equal the teacher-forced argmax stream."""
    v, w = 8, 4
    logits = np.full((1, w, v), -10.0, np.float32)
    targets = [3, 5, 2, 7]                     # argmax at each position
    for j, t in enumerate(targets):
        logits[0, j, t] = 10.0
    fed = np.array([[1, 3, 5, 6]])             # drafts 3,5 accepted; 6 != 2
    y, n_emit, n_match = _accept(logits, fed, [4], [True], [0.0], [100], [0])
    assert n_match[0] == 2 and n_emit[0] == 3
    assert list(y[0, :3]) == [3, 5, 2]         # 2 drafts + correction
    # full acceptance: bonus token from the last position
    fed = np.array([[1, 3, 5, 2]])
    y, n_emit, n_match = _accept(logits, fed, [4], [True], [0.0], [100], [0])
    assert n_match[0] == 3 and n_emit[0] == 4
    assert list(y[0]) == [3, 5, 2, 7]
    # width 1 (no drafts) = plain decode step
    y, n_emit, n_match = _accept(logits, fed, [1], [True], [0.0], [100], [0])
    assert n_match[0] == 0 and n_emit[0] == 1 and y[0, 0] == 3


def test_spec_accept_rejection_sampling_deterministic_cases():
    """Temperature rows: a draft with target probability ~1 is always
    accepted; probability ~0 is always rejected and the residual sample
    never re-emits the rejected token."""
    v, w = 8, 3
    logits = np.zeros((1, w, v), np.float32)
    logits[0, 0, 4] = 30.0                      # p(4) ~ 1 at position 0
    logits[0, 1, :] = 0.0                       # uniform at position 1
    logits[0, 1, 6] = -40.0                     # ...except token 6 ~ 0
    for seed in range(8):
        fed = np.array([[1, 4, 6]])             # draft 4 (accept), 6 (reject)
        y, n_emit, n_match = _accept(logits, fed, [3], [True], [1.0],
                                     [100], [0], seed=seed)
        assert n_match[0] == 1 and n_emit[0] == 2
        assert y[0, 0] == 4
        assert y[0, 1] != 6                     # residual excludes the draft


def test_spec_accept_caps_eos_budget_maxlen():
    v, w = 8, 4
    logits = np.full((1, w, v), -10.0, np.float32)
    for j, t in enumerate([3, 5, 2, 7]):
        logits[0, j, t] = 10.0
    fed = np.array([[1, 3, 5, 2]])              # would fully accept
    # EOS mid-stream: token 5 == eos stops after emitting it
    y, n_emit, _ = _accept(logits, fed, [4], [True], [0.0], [100], [0],
                           eos=5)
    assert n_emit[0] == 2 and list(y[0, :2]) == [3, 5]
    # budget: remaining=2 caps the haul
    _, n_emit, _ = _accept(logits, fed, [4], [True], [0.0], [2], [0])
    assert n_emit[0] == 2
    # max_len: lengths near the ceiling caps too
    _, n_emit, _ = _accept(logits, fed, [4], [True], [0.0], [100], [7],
                           max_len=10)
    assert n_emit[0] == 2                       # 7 -> 9 == max_len-1 stops
    # inactive slots emit nothing
    _, n_emit, _ = _accept(logits, fed, [4], [False], [0.0], [100], [0])
    assert n_emit[0] == 0


# ---------------------------------------------------------------------------
# drafters & controller


def test_ngram_drafter_proposals():
    d = NgramDrafter(k_max=4, n_max=3)
    hist = np.array([7, 1, 2, 3, 9, 1, 2, 3], np.int32)
    # trailing [1,2,3] matched at pos 1 -> continuation [9, 1, 2, 3][:4]
    assert list(d.propose(hist, 4)) == [9, 1, 2, 3]
    # no recurring n-gram -> nothing proposed
    assert len(d.propose(np.arange(10, dtype=np.int32), 4)) == 0
    assert len(d.propose(hist, 0)) == 0
    # a cycle yields full-k drafts even when the most recent match is
    # truncated by the end of the history
    cyc = np.array([4, 5, 6] * 4, np.int32)
    assert len(d.propose(cyc, 4)) == 4


def test_adaptive_controller_tracks_acceptance():
    c = AdaptiveDraftController(n_slots=1, k_max=8, arm="ngram")
    k0 = c.k_for(0)
    assert 1 <= k0 <= 8
    for _ in range(12):                         # everything accepted
        c.update(0, proposed=k0, accepted=k0)
    assert c.ema[0] > 0.9
    assert c.k_for(0) == 8                      # high acceptance -> max k
    for _ in range(20):                         # nothing accepted
        c.update(0, proposed=8, accepted=0)
    assert c.ema[0] < 0.1
    assert c.k_for(0) == 0                      # speculation turns itself off
    c.reset(0)
    assert c.k_for(0) == k0


def test_draft_lm_self_speculation_proposes_target_tokens():
    """Self-speculation: the target model drafting for itself proposes
    exactly its own greedy continuation (the acceptance upper bound)."""
    from repro.configs import get_smoke_config
    from repro.models.model import LM
    from repro.spec import DraftLMDrafter
    cfg = get_smoke_config("qwen2-1.5b").with_(dtype="float32")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
    # reference greedy continuation via the eager engine
    from repro.serve.engine import Engine
    eng = Engine(lm, params, n_slots=1, max_len=64)
    rid = eng.submit(prompt, max_new_tokens=5)
    ref = eng.run_to_completion()[rid].out_tokens
    d = DraftLMDrafter(lm, params, n_slots=1, max_len=64, k_max=4)
    hist = np.concatenate([prompt, np.asarray(ref[:1], np.int32)])
    drafts = d.propose_batch([(0, rid, hist, 4)], 4)[0]
    assert list(drafts) == ref[1:5]
    assert d.syncs == 1                         # one dispatch per round


# ---------------------------------------------------------------------------
# rollback / copy-on-write invariants


def test_ensure_exclusive_tail_cows_shared_page():
    from repro.serve.paged import PageAllocator
    rng = np.random.default_rng(0)
    page, kh, d = 4, 2, 8
    al = PageAllocator(n_pages=8, max_pages_per_slot=4, n_slots=2)
    p0 = al.alloc(0, 2)                         # slot 0: two pages
    al.assign(1, [p0[1]], 1)                    # slot 1 SHARES page p0[1]
    cache = {"kv": {
        "k_pages": jnp.asarray(rng.normal(size=(8, kh, page, d)),
                               jnp.bfloat16),
        "v_pages": jnp.asarray(rng.normal(size=(8, kh, page, d)),
                               jnp.bfloat16),
        "k_scales": jnp.asarray(rng.random((8, kh)), jnp.float32),
        "v_scales": jnp.asarray(rng.random((8, kh)), jnp.float32),
        "block_table": jnp.asarray(al.table, jnp.int32),
    }}
    before = np.asarray(cache["kv"]["k_pages"])
    shared = p0[1]
    # the spec write span [5, 8) of slot 0 covers the SHARED page index 1
    out = ensure_exclusive_tail(cache, al, 0, 5, 8, page)
    fresh = al.table[0, 1]
    assert fresh != shared and al.refs[shared] == 1 == al.refs[fresh]
    # device copy: contents and scales moved to the fresh page; the
    # shared page (still mapped by slot 1) is untouched
    kp = np.asarray(out["kv"]["k_pages"])
    np.testing.assert_array_equal(kp[fresh], before[shared])
    np.testing.assert_array_equal(kp[shared], before[shared])
    np.testing.assert_array_equal(
        np.asarray(out["kv"]["k_scales"])[fresh],
        np.asarray(cache["kv"]["k_scales"])[shared])
    assert int(np.asarray(out["kv"]["block_table"])[0, 1]) == fresh
    # rollback through the now-exclusive tail passes the shared-page audit
    assert rollback_length(al, 0, 8, 5, page) == [fresh]
    # a second call is a no-op (already exclusive)
    out2 = ensure_exclusive_tail(out, al, 0, 5, 8, page)
    assert out2 is out


# ---------------------------------------------------------------------------
# engine end-to-end


def _setup(kv_dtype=None):
    from repro.configs import get_smoke_config
    from repro.models.model import LM
    cfg = get_smoke_config("qwen2-1.5b").with_(dtype="float32")
    params = LM(cfg).init(jax.random.PRNGKey(0))
    if kv_dtype:
        cfg = cfg.with_(kv_cache_dtype=kv_dtype)
    rng = np.random.default_rng(0)
    return LM(cfg), params, rng


def _mk(eng_cls, lm, params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 96)
    kw.setdefault("seed", 0)
    kw.setdefault("page_size", 8)
    kw.setdefault("decode_block", 4)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("policy", "fcfs")
    kw.setdefault("prefix_cache", False)
    return eng_cls(lm, params, **kw)


def _repetitive_prompts(rng, vocab, n=4):
    out = []
    for _ in range(n):
        pat = rng.integers(0, vocab, (6,)).tolist()
        out.append(pat * 3 + rng.integers(0, vocab, (3,)).tolist())
    return out


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_spec_greedy_token_identical_to_baseline(kv_dtype):
    """The acceptance criterion: ngram spec decode == non-spec greedy
    decode token-for-token on bf16 AND int8 paged caches (rollback
    exactness), with acceptance > 0 and > 1 accepted draft per slot-step
    on a repetitive workload."""
    from repro.sched import SchedEngine
    lm, params, rng = _setup(kv_dtype)
    prompts = _repetitive_prompts(rng, lm.cfg.vocab_size)

    def run(cls, **kw):
        eng = _mk(cls, lm, params, **kw)
        ids = [eng.submit(p, max_new_tokens=20) for p in prompts]
        done = eng.run_to_completion()
        return [done[i].out_tokens for i in ids], eng

    base_toks, _ = run(SchedEngine)
    spec_toks, spec = run(SpecEngine, spec="ngram", draft_k=6)
    assert base_toks == spec_toks
    assert all(len(t) == 20 for t in spec_toks)
    tele = spec.telemetry()["spec"]
    assert tele["acceptance_rate"] > 0
    assert tele["accepted_per_step"] > 1.0
    assert tele["tokens_per_step"] > 2.0
    # one host sync per verify round (plus prefill/fallback dispatches)
    assert spec.sync_count == spec.stats.chunks \
        + spec.spec_stats.verify_steps \
        + spec.steps_dispatched // spec.decode_block


def test_spec_draft_lm_self_speculation_engine():
    """Draft-LM arm with the target as its own drafter: acceptance 1.0,
    every round emits k+1 tokens per slot, stream token-identical."""
    from repro.sched import SchedEngine
    lm, params, rng = _setup()
    prompts = [rng.integers(0, lm.cfg.vocab_size, (n,)).tolist()
               for n in (8, 5)]

    def run(cls, **kw):
        eng = _mk(cls, lm, params, n_slots=2, **kw)
        ids = [eng.submit(p, max_new_tokens=16) for p in prompts]
        done = eng.run_to_completion()
        return [done[i].out_tokens for i in ids], eng

    base_toks, _ = run(SchedEngine)
    spec_toks, spec = run(SpecEngine, spec="draft", draft_lm=lm,
                          draft_params=params, draft_k=4, adaptive=False)
    assert base_toks == spec_toks
    tele = spec.telemetry()["spec"]
    assert tele["acceptance_rate"] == 1.0
    assert tele["tokens_per_step"] > 4.0        # k+1 = 5 minus end caps


def test_spec_temperature_runs_and_respects_budget():
    """Sampled speculation: the exact-rejection-sampling path executes
    every round (the draft arm always proposes, unlike n-gram lookup on
    high-entropy sampled text), emitted counts respect budgets, and
    partial acceptance is observed."""
    lm, params, rng = _setup()
    prompts = [rng.integers(0, lm.cfg.vocab_size, (8,)).tolist()
               for _ in range(3)]
    eng = _mk(SpecEngine, lm, params, spec="draft", draft_lm=lm,
              draft_params=params, adaptive=False, draft_k=4)
    ids = [eng.submit(p, max_new_tokens=12, temperature=0.8)
           for p in prompts]
    done = eng.run_to_completion()
    assert all(len(done[i].out_tokens) == 12 for i in ids)
    assert eng.spec_stats.verify_steps > 0
    assert eng.spec_stats.drafts_proposed > 0


def test_spec_edf_urgency_gate_falls_back_to_plain_decode():
    """With a queued request whose EDF deadline is inside the slack, the
    engine must NOT gamble on drafts: the round falls back to the fused
    decode block and the skip is counted."""
    lm, params, rng = _setup()
    long_p = _repetitive_prompts(rng, lm.cfg.vocab_size, n=1)[0]
    urgent = rng.integers(0, lm.cfg.vocab_size, (6,)).tolist()
    eng = _mk(SpecEngine, lm, params, spec="ngram", draft_k=6,
              policy="edf", n_slots=1, spec_slack_s=1e6)
    eng.submit(long_p, max_new_tokens=12, slo_ttft=10.0)
    eng.submit(urgent, max_new_tokens=4, slo_ttft=10.0)
    # while the urgent request is still QUEUED every decode round must
    # take the plain fused path
    for _ in range(4):
        if len(eng.queue) == 0:
            break
        eng.step()
        assert eng.spec_stats.verify_steps == 0
    assert eng.spec_stats.skipped_urgent > 0
    eng.run_to_completion()
    # and with no queue pressure the same engine speculates again
    eng2 = _mk(SpecEngine, lm, params, spec="ngram", draft_k=6,
               policy="edf", n_slots=1, spec_slack_s=1e-9)
    eng2.submit(long_p, max_new_tokens=12, slo_ttft=10.0)
    eng2.run_to_completion()
    assert eng2.spec_stats.verify_steps > 0


# ---------------------------------------------------------------------------
# search-space / cost-model wiring


def test_spec_is_a_search_axis():
    from repro.core.apply import apply_efficiency_config
    from repro.core.costmodel import (TIERS, predict, spec_speedup,
                                      spec_tokens_per_step)
    from repro.core.space import (EfficiencyConfig, InfChoice,
                                  encode_config, enumerate_space,
                                  space_size)
    from repro.configs import get_smoke_config
    full = enumerate_space()
    assert len(full) == space_size()
    arms = {c.inf.spec for c in full}
    assert arms == {"none", "ngram", "draft"}
    # encoding is stable and distinguishes the arms
    a = EfficiencyConfig(inf=InfChoice(spec="ngram", draft_k=4))
    b = EfficiencyConfig(inf=InfChoice(spec="none"))
    assert len(encode_config(a)) == len(encode_config(b))
    assert encode_config(a) != encode_config(b)
    # config rewrite reaches the engine knobs
    cfg = apply_efficiency_config(get_smoke_config("qwen2-1.5b"),
                                  EfficiencyConfig(
                                      inf=InfChoice(spec="ngram",
                                                    draft_k=8)))
    assert cfg.spec_decode == "ngram" and cfg.spec_draft_k == 8
    # expected-haul model: geometric series, monotone in acceptance
    assert spec_tokens_per_step(0.0, 4) == 1.0
    assert abs(spec_tokens_per_step(1.0, 4) - 5.0) < 1e-9
    assert spec_tokens_per_step(0.8, 4) > spec_tokens_per_step(0.3, 4)
    assert spec_speedup(0.9, 4) > 1.0 > spec_speedup(0.01, 8)
    # the cost model prices the arm: high-acceptance spec cuts latency
    tier = TIERS["v5e-1"]
    base = predict(get_smoke_config("qwen2-1.5b"), b, tier)
    spec = predict(get_smoke_config("qwen2-1.5b"), a, tier,
                   spec_accept_rate=0.8)
    assert spec["latency_ms"] < base["latency_ms"]
