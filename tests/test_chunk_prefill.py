"""Fused chunked prefill (the paged prefix-extend kernel): kernel-vs-
oracle sweeps across dtype x kv-style x width, model-layer fused ==
eager-gather equality (plus the static page-grid narrowing), the
no-eager-gather dispatch guarantee on the scheduler's default path,
ragged-chunk shape bucketing (no retraces, sync audit intact), and the
streamed-page cost model.

The kernel runs in interpret mode on CPU — the same dispatch the engines
use — so these sweeps cover the exact artifact that runs on TPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention.ops import paged_prefix_extend_attention
from repro.kvcache import CacheSpec
from repro.kvcache.quant import _qmax_of


def _pool(rng, n, page, kh, d, dtype):
    """Random page pool in ``dtype`` with per-page-per-kv-head scales."""
    raw = rng.normal(size=(n, kh, page, d)).astype(np.float32)
    if dtype == "bf16":
        return jnp.asarray(raw, jnp.bfloat16), None
    store = CacheSpec(dtype=dtype).store_dtype
    sc = np.abs(raw).max(axis=(2, 3)) / _qmax_of(store) + 1e-9
    q = raw / sc[:, :, None, None]
    if dtype == "int8":
        q = np.clip(np.round(q), -127, 127)
    return jnp.asarray(q, store), jnp.asarray(sc, jnp.float32)


@pytest.mark.parametrize("dtype", ["bf16", "int8", "fp8"])
@pytest.mark.parametrize("h,kvh", [(4, 4), (4, 2), (8, 1)])  # full/gqa/mqa
@pytest.mark.parametrize("w", [1, 5, 32])
def test_prefix_extend_kernel_matches_ref(dtype, h, kvh, w):
    """ONE kernel, every instantiation: W=1 (single query), W=k+1 (spec
    verify) and W=chunk (prefill continuation), over bf16/int8/fp8 pools
    and full/gqa/mqa head layouts.  Rows cover a pure-chunk start
    (prefix 0), page-aligned prefixes (the chunked-prefill contract), a
    partial last page (spec verify mid-page), a full-horizon prefix with
    width 0, and a completely empty slot."""
    rng = np.random.default_rng(0)
    s_n, d, page, p_n = 5, 16, 8, 4
    n = 1 + s_n * p_n
    q = jnp.asarray(rng.normal(size=(s_n, w, h, d)), jnp.float32)
    kp, ks = _pool(rng, n, page, kvh, d, dtype)
    vp, vs = _pool(rng, n, page, kvh, d, dtype)
    bt = jnp.asarray(rng.permutation(np.arange(1, n)).reshape(s_n, p_n),
                     jnp.int32)
    prefix = jnp.asarray([0, 16, 13, p_n * page, 0], jnp.int32)
    widths = jnp.asarray([w, max(w // 2, 1), w, 0, 0], jnp.int32)
    ck = jnp.asarray(rng.normal(size=(s_n, w, kvh, d)), jnp.float32)
    cv = jnp.asarray(rng.normal(size=(s_n, w, kvh, d)), jnp.float32)
    ker = paged_prefix_extend_attention(q, kp, vp, bt, prefix, ck, cv,
                                        widths, ks, vs, use_kernel=True)
    ref = paged_prefix_extend_attention(q, kp, vp, bt, prefix, ck, cv,
                                        widths, ks, vs, use_kernel=False)
    np.testing.assert_allclose(np.asarray(ker, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)
    # the empty slot (no prefix, no chunk) flushes exact zeros both ways
    assert float(jnp.abs(ker[4]).max()) == 0.0
    assert float(jnp.abs(ref[4]).max()) == 0.0


# ---------------------------------------------------------------------------
# model layer: fused kernel == eager gather, page-grid narrowing exact


def _prefill_paged_setup(kv_dtype):
    from repro import kvcache
    from repro.configs.base import AttentionConfig
    from repro.models.attention import init_attention
    rng = np.random.default_rng(3)
    a = AttentionConfig(kind="gqa", num_heads=4, num_kv_heads=2,
                       head_dim=16, rope_theta=10_000.0)
    p = init_attention(jax.random.PRNGKey(0), 32, a, jnp.float32)
    b, page, pps = 2, 8, 8
    n = 1 + b * pps
    spec = CacheSpec(layout="paged", dtype=kv_dtype, page_size=page)
    cache = kvcache.alloc_paged(spec, a, b, n, pps)
    cache["block_table"] = jnp.asarray(
        np.arange(1, n).reshape(b, pps), jnp.int32)
    # commit a page-aligned prefix per slot through the real write path
    starts = np.asarray([16, 8], np.int32)
    t = int(starts.max())
    k_hist = jnp.asarray(rng.normal(size=(b, t, 2, 16)), jnp.float32)
    v_hist = jnp.asarray(rng.normal(size=(b, t, 2, 16)), jnp.float32)
    cache = kvcache.paged_scatter_prefill(
        cache, jnp.arange(b, dtype=jnp.int32), jnp.asarray(starts),
        k_hist, v_hist)
    x = jnp.asarray(rng.normal(size=(b, 8, 32)), jnp.float32)
    spos = (jnp.arange(b, dtype=jnp.int32), jnp.asarray(starts),
            jnp.asarray([8, 5], jnp.int32))          # one ragged chunk
    return p, x, a, cache, spos


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_attention_prefill_paged_fused_matches_eager(kv_dtype):
    """The model-layer continuation path: fused kernel output matches the
    retired eager full-horizon gather (now the ref oracle) on bf16 and
    quantized pools, and both write the same pages."""
    from repro.models.attention import attention_prefill_paged
    p, x, a, cache, spos = _prefill_paged_setup(kv_dtype)
    y_k, c_k = attention_prefill_paged(p, x, a, cache, spos,
                                       use_kernel=True)
    y_e, c_e = attention_prefill_paged(p, x, a, cache, spos,
                                       use_kernel=False)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_e),
                               atol=2e-2, rtol=2e-2)
    for key in c_k:
        np.testing.assert_array_equal(np.asarray(c_k[key], np.float32),
                                      np.asarray(c_e[key], np.float32))


def test_prefill_paged_page_grid_narrowing_is_exact():
    """Narrowing the kernel's page grid to the prefix's pow2 page span
    (the scheduler's static ``max_pages``) runs the same active grid
    steps in the same order — bit-identical output."""
    from repro.models.attention import attention_prefill_paged
    p, x, a, cache, spos = _prefill_paged_setup("bf16")
    y_full, _ = attention_prefill_paged(p, x, a, cache, spos,
                                        use_kernel=True)
    y_nar, _ = attention_prefill_paged(p, x, a, cache, spos + (4,),
                                       use_kernel=True)
    np.testing.assert_array_equal(np.asarray(y_full, np.float32),
                                  np.asarray(y_nar, np.float32))


# ---------------------------------------------------------------------------
# engine: default path streams through the kernel (never the gather),
# ragged chunks reuse bucketed shapes, sync audit intact


def _setup_engine():
    from repro.configs import get_smoke_config
    from repro.models.model import LM
    cfg = get_smoke_config("qwen2-1.5b").with_(dtype="float32")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    return lm, params, rng


def test_sched_default_path_never_runs_eager_gather(monkeypatch):
    """The scheduler's continuation chunks must dispatch the Pallas
    prefix-extend kernel: the ref.py gather raising here proves no full-
    horizon context is materialized on the default path."""
    import repro.kernels.paged_attention.ops as pops
    import repro.kernels.paged_attention.paged_attention as pk
    from repro.sched import SchedEngine
    lm, params, rng = _setup_engine()
    calls = {"kernel": 0}
    real = pk.paged_prefix_extend_pallas

    def spy(*a, **kw):
        calls["kernel"] += 1
        return real(*a, **kw)

    def boom(*a, **kw):
        raise AssertionError("eager full-horizon gather on default path")

    monkeypatch.setattr(pk, "paged_prefix_extend_pallas", spy)
    monkeypatch.setattr(pops, "paged_prefix_extend_ref", boom)
    eng = SchedEngine(lm, params, n_slots=2, max_len=64, seed=0,
                      page_size=8, decode_block=4, prefill_chunk=16,
                      prefix_cache=False)
    rid = eng.submit(rng.integers(0, lm.cfg.vocab_size, (40,)).tolist(),
                     max_new_tokens=4)
    done = eng.run_to_completion()
    assert len(done[rid].out_tokens) == 4
    assert calls["kernel"] >= 1, "continuation chunks bypassed the kernel"


def test_ragged_final_chunks_bucket_shapes_and_keep_sync_audit():
    """Odd final-chunk widths and ragged row counts must land in a small
    set of pow2-bucketed traced shapes (no per-shape retrace), leave the
    sync audit intact (1 sync per prefill dispatch + 1 per decode
    block), stay token-identical to the unchunked base engine, and fill
    the phase timers the benchmark splits throughput by."""
    from repro.serve.engine import PagedEngine
    from repro.sched import SchedEngine
    lm, params, rng = _setup_engine()
    prompts = [rng.integers(0, lm.cfg.vocab_size, (ln,)).tolist()
               for ln in (41, 23, 17, 30, 9)]        # odd final chunks
    peng = PagedEngine(lm, params, n_slots=2, max_len=64, seed=0,
                       page_size=8, decode_block=4)
    pids = [peng.submit(p, max_new_tokens=8) for p in prompts]
    pdone = peng.run_to_completion()

    eng = SchedEngine(lm, params, n_slots=2, max_len=64, seed=0,
                      page_size=8, decode_block=4, prefill_chunk=16,
                      prefix_cache=False)
    sids = [eng.submit(p, max_new_tokens=8) for p in prompts]
    sdone = eng.run_to_completion()
    for a_, b_ in zip(pids, sids):
        assert pdone[a_].out_tokens == sdone[b_].out_tokens
    assert eng.sync_count == eng.stats.chunks \
        + eng.steps_dispatched // eng.decode_block, \
        "bucketing must not change the dispatch/sync structure"
    if hasattr(eng._chunk_jit, "_cache_size"):
        # widths in {8,16}, rows in {1,2}, page grids in {1,2,4}: a
        # handful of shapes, NOT one trace per ragged (rows, width)
        assert eng._chunk_jit._cache_size() <= 8, \
            f"{eng._chunk_jit._cache_size()} continuation traces"
    assert eng.t_prefill_s > 0 and eng.t_decode_s > 0


# ---------------------------------------------------------------------------
# cost model: chunked prefill priced at streamed-page bytes


def test_costmodel_prices_streamed_chunks_below_gather():
    from repro.configs import get_smoke_config
    from repro.core.costmodel import (TIERS, chunk_prefill_hbm_bytes,
                                      predict, service_estimate)
    from repro.core.space import EfficiencyConfig
    cfg = get_smoke_config("qwen2-1.5b")
    fused = chunk_prefill_hbm_bytes(cfg, 512, chunk=64)
    gather = chunk_prefill_hbm_bytes(cfg, 512, chunk=64, fused=False)
    assert fused < gather
    # the gather's cost scales with the slot's page horizon even when
    # the prompt doesn't; the streamed kernel's does not
    gather_long = chunk_prefill_hbm_bytes(cfg, 512, chunk=64, fused=False,
                                          horizon=4096)
    assert gather_long > 2 * gather
    assert chunk_prefill_hbm_bytes(cfg, 512, chunk=64) == fused
    # service_estimate(chunk=): monotone in prompt, >= one-shot (weights
    # re-read per chunk) but well under the gather pricing
    one_shot = service_estimate(cfg, prompt=512, gen=8)["t_prefill_s"]
    chunked = service_estimate(cfg, prompt=512, gen=8,
                               chunk=64)["t_prefill_s"]
    assert chunked >= one_shot
    assert service_estimate(cfg, prompt=128, gen=8,
                            chunk=64)["t_prefill_s"] < chunked
    # predict(prefill_chunk=) stays finite and no cheaper than the
    # one-shot slab (per-chunk weight re-reads)
    eff = EfficiencyConfig.default()
    base = predict(cfg, eff, TIERS["v5e-1"])
    chunk = predict(cfg, eff, TIERS["v5e-1"], prefill_chunk=64)
    assert chunk["latency_ms"] >= base["latency_ms"]
    assert np.isfinite(chunk["latency_ms"])
