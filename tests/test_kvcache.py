"""Unified KV-cache subsystem (repro.kvcache): quantize→dequant bounds,
int8/fp8 paged-kernel-vs-ref parity, quantized contiguous decode, and
engine end-to-end equality (paged int8 == eager bf16 on the smoke config).

The Pallas kernel runs in interpret mode on CPU — the same dispatch the
engine uses — so the fused-dequant path tested here is the TPU artifact.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AttentionConfig
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kvcache import (CacheSpec, alloc_contiguous, alloc_paged,
                           decode_write, dequantize, kv_bytes_per_token,
                           paged_scatter_prefill, paged_views,
                           paged_write_batch, pool_bytes, prefill_write,
                           quantize)

# quantization error bounds per dtype, as a fraction of the vector amax:
# int8 rounds to 1/127 steps (≤ half a step); fp8-e4m3 keeps 3 mantissa
# bits (≤ 2^-4 relative, bounded here against amax with slack for the
# fp32 scale division)
ERR_FRAC = {"int8": 0.5 / 127.0 + 1e-6, "fp8": 0.0625 + 1e-6}


# ---------------------------------------------------------------------------
# quantize → dequantize round trips


@pytest.mark.parametrize("dtype", ["int8", "fp8"])
def test_quantize_roundtrip_error_bound(dtype):
    rng = np.random.default_rng(0)
    spec = CacheSpec(dtype=dtype)
    x = jnp.asarray(rng.normal(size=(4, 16, 2, 64)) *
                    rng.uniform(0.01, 8.0, size=(4, 16, 2, 1)), jnp.float32)
    q, s = quantize(x, spec.store_dtype, axis=-1)
    back = dequantize(q, s, axis=-1)
    amax = np.max(np.abs(np.asarray(x)), axis=-1, keepdims=True)
    err = np.abs(np.asarray(back) - np.asarray(x))
    assert (err <= amax * ERR_FRAC[dtype]).all(), \
        f"max err {err.max()} vs bound {(amax * ERR_FRAC[dtype]).min()}"


def test_quantize_zero_vectors_exact():
    q, s = quantize(jnp.zeros((2, 3, 8)), jnp.int8, axis=-1)
    assert (np.asarray(s) == 0).all()
    assert (np.asarray(dequantize(q, s, axis=-1)) == 0).all()


# ---------------------------------------------------------------------------
# paged kernel vs oracle — quantized pools, fused dequant


def _paged_setup(rng, dtype, s, h, kvh, d, page, pps, t):
    """Build a quantized paged cache by the real write path: batched
    prefill scatter to length[s], then per-token decode writes."""
    a = AttentionConfig(kind="mha", num_heads=kvh, num_kv_heads=kvh,
                        head_dim=d)
    spec = CacheSpec(layout="paged", dtype=dtype, page_size=page)
    n = s * pps + 1
    cache = alloc_paged(spec, a, s, n, pps)
    pool = list(rng.permutation(np.arange(1, n)))
    bt = jnp.asarray([[pool.pop() for _ in range(pps)] for _ in range(s)],
                     jnp.int32)
    cache["block_table"] = bt
    # per-slot lengths: a free slot, partial pages, one full slot
    lengths = jnp.asarray(rng.integers(1, pps * page, (s,)), jnp.int32)
    lengths = lengths.at[0].set(0).at[-1].set(min(t, pps * page))
    plens = jnp.minimum(lengths, t // 2)         # prefill part
    k_rows = jnp.asarray(rng.normal(size=(s, t, kvh, d)), jnp.bfloat16)
    v_rows = jnp.asarray(rng.normal(size=(s, t, kvh, d)), jnp.bfloat16)
    cache = paged_scatter_prefill(cache, jnp.arange(s, dtype=jnp.int32),
                                  plens, k_rows, v_rows)
    # decode-extend the rest token by token (exercises the requant path)
    pos = np.asarray(plens).copy()
    max_steps = int(np.max(np.asarray(lengths) - np.asarray(plens)))
    for _ in range(max_steps):
        live = pos < np.asarray(lengths)
        kn = jnp.asarray(rng.normal(size=(s, kvh, d)), jnp.bfloat16)
        vn = jnp.asarray(rng.normal(size=(s, kvh, d)), jnp.bfloat16)
        # freeze finished slots by re-writing their last token position
        wpos = jnp.asarray(np.where(live, pos, np.maximum(pos - 1, 0)),
                           jnp.int32)
        cache = paged_write_batch(cache, wpos, kn, vn)
        pos = np.where(live, pos + 1, pos)
    q = jnp.asarray(rng.normal(size=(s, h, d)), jnp.bfloat16)
    return q, cache, lengths


@pytest.mark.parametrize("dtype", ["int8", "fp8"])
@pytest.mark.parametrize("s,h,kvh,d,page,pps", [
    (2, 4, 4, 32, 8, 3),      # MHA
    (3, 4, 2, 64, 8, 4),      # GQA
    (2, 8, 1, 64, 16, 2),     # MQA
])
def test_quantized_paged_kernel_matches_ref(dtype, s, h, kvh, d, page, pps):
    rng = np.random.default_rng(0)
    q, cache, lengths = _paged_setup(rng, dtype, s, h, kvh, d, page, pps,
                                     t=page * pps)
    kp, vp, ks, vs, bt = paged_views(cache)
    assert ks is not None and kp.dtype == CacheSpec(dtype=dtype).store_dtype
    o = paged_attention(q, kp, vp, bt, lengths, ks, vs)
    ref = paged_attention_ref(q, kp, vp, bt, lengths, ks, vs)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("dtype", ["int8", "fp8"])
def test_quantized_paged_matches_bf16_oracle(dtype):
    """The whole quantized pipeline (scatter + requant writes + fused
    kernel) stays within quantization tolerance of the bf16 pools."""
    rng = np.random.default_rng(1)
    s, h, kvh, d, page, pps = 3, 4, 2, 32, 8, 3
    q, cache, lengths = _paged_setup(rng, dtype, s, h, kvh, d, page, pps,
                                     t=page * pps)
    kp, vp, ks, vs, bt = paged_views(cache)
    o_q = paged_attention(q, kp, vp, bt, lengths, ks, vs)
    # bf16 truth: dequantize the pools and run the plain oracle
    k_f = dequantize(kp, ks[:, :, None], axis=-1, dtype=jnp.float32)
    v_f = dequantize(vp, vs[:, :, None], axis=-1, dtype=jnp.float32)
    o_f = paged_attention_ref(q.astype(jnp.float32), k_f, v_f, bt, lengths)
    tol = 0.06 if dtype == "int8" else 0.2       # softmax amplifies fp8 err
    np.testing.assert_allclose(np.asarray(o_q, np.float32),
                               np.asarray(o_f, np.float32),
                               atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# stacked pools: inside the layer loop the writes and both paged kernels
# take the whole (L, N, KH, page, D) stack plus the layer's index
# (models/transformer.stack_forward)

L_STACK = 3
POOL_KEYS = ("k_pages", "v_pages", "k_scales", "v_scales")


def _stacked_pool(rng, dtype, n, kh, page, d):
    """(L, N, KH, page, D) pool, different in every layer, and for int8
    its (L, N, KH) scales."""
    raw = rng.normal(size=(L_STACK, n, kh, page, d)).astype(np.float32)
    if dtype == "bf16":
        return jnp.asarray(raw, jnp.bfloat16), None
    sc = np.abs(raw).max(axis=(3, 4)) / 127.0 + 1e-9
    q = np.clip(np.round(raw / sc[..., None, None]), -127, 127)
    return jnp.asarray(q, jnp.int8), jnp.asarray(sc, jnp.float32)


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
@pytest.mark.parametrize("kernel", ["decode", "prefix_extend"])
def test_stacked_pool_kernel_at_layer_matches_its_slice(kernel, dtype):
    """Both paged kernels on a layer stack at layer l give exactly what
    the 4-D call on ``pool[l]`` gives, for every layer, with partial last
    pages and a free (length-0) slot."""
    from repro.kernels.paged_attention.paged_attention import (
        paged_attention_pallas, paged_prefix_extend_pallas)
    rng = np.random.default_rng(3)
    s_n, w_n, h, kh, d, page, p_n = 3, 4, 4, 2, 16, 8, 3
    n = 1 + s_n * p_n
    kp, ks = _stacked_pool(rng, dtype, n, kh, page, d)
    vp, vs = _stacked_pool(rng, dtype, n, kh, page, d)
    bt = jnp.asarray(rng.permutation(np.arange(1, n)).reshape(s_n, p_n),
                     jnp.int32)
    lengths = jnp.asarray([13, 0, 21], jnp.int32)
    if kernel == "decode":
        q = jnp.asarray(rng.normal(size=(s_n, h, d)), jnp.bfloat16)

        def run(kp, vp, ks, vs, layer=None):
            return paged_attention_pallas(q, kp, vp, bt, lengths, ks, vs,
                                          layer, interpret=True)
    else:
        q = jnp.asarray(rng.normal(size=(s_n, w_n, h, d)), jnp.bfloat16)
        ck = jnp.asarray(rng.normal(size=(s_n, w_n, kh, d)), jnp.bfloat16)
        cv = jnp.asarray(rng.normal(size=(s_n, w_n, kh, d)), jnp.bfloat16)
        widths = jnp.asarray([4, 0, 2], jnp.int32)

        def run(kp, vp, ks, vs, layer=None):
            return paged_prefix_extend_pallas(q, kp, vp, bt, lengths, ck, cv,
                                              widths, ks, vs, layer,
                                              interpret=True)

    def pick(x, layer):
        return None if x is None else x[layer]

    outs = []
    for layer in range(L_STACK):
        got = np.asarray(run(kp, vp, ks, vs, jnp.int32(layer)), np.float32)
        want = run(kp[layer], vp[layer], pick(ks, layer), pick(vs, layer))
        np.testing.assert_array_equal(got, np.asarray(want, np.float32))
        outs.append(got)
    assert not np.array_equal(outs[0], outs[1])     # the index picks


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_stacked_writes_touch_only_their_layer(dtype):
    """The token write (an int8 amax growth included) and the chunk
    scatter on a stacked node at layer l equal the same writes on layer
    l's own node, and leave every other layer as it was."""
    rng = np.random.default_rng(4)
    s, kh, d, page, pps = 3, 2, 16, 8, 3
    a = AttentionConfig(kind="mha", num_heads=kh, num_kv_heads=kh,
                        head_dim=d)
    spec = CacheSpec(layout="paged", dtype=dtype, page_size=page)
    bt = jnp.asarray(rng.permutation(np.arange(1, 1 + s * pps))
                     .reshape(s, pps), jnp.int32)
    slots = jnp.arange(s, dtype=jnp.int32)

    def rows(t, amp=1.0):
        return jnp.asarray(amp * rng.normal(size=(s, t, kh, d)),
                           jnp.bfloat16)

    nodes = []
    for _ in range(L_STACK):
        node = dict(alloc_paged(spec, a, s, 1 + s * pps, pps),
                    block_table=bt)
        nodes.append(paged_scatter_prefill(
            node, slots, jnp.asarray([12, 5, 9], jnp.int32), rows(12),
            rows(12)))
    keys = [k for k in POOL_KEYS if k in nodes[0]]
    stack = {k: jnp.stack([nd[k] for nd in nodes]) for k in keys}
    layer = 1
    k_tok, v_tok = rows(1, 4.0)[:, 0], rows(1, 4.0)[:, 0]
    k_chunk, v_chunk = rows(8), rows(8)
    writes = {
        "token": lambda nd: paged_write_batch(
            nd, jnp.asarray([12, 5, 9], jnp.int32), k_tok, v_tok),
        "chunk": lambda nd: paged_scatter_prefill(
            nd, slots, jnp.asarray([8, 3, 0], jnp.int32), k_chunk, v_chunk,
            jnp.asarray([16, 8, 16], jnp.int32)),
    }
    for name, write in writes.items():
        got = write({**stack, "block_table": bt, "layer": jnp.int32(layer)})
        want = write(nodes[layer])
        for k in keys:
            for other in range(L_STACK):
                expect = want[k] if other == layer else stack[k][other]
                np.testing.assert_array_equal(
                    np.asarray(got[k][other], np.float32),
                    np.asarray(expect, np.float32), err_msg=f"{name} {k}")


def _per_layer(tree, n):
    """A layer-stacked tree as the unscanned stack's {"g<i>": ...}."""
    return {f"g{i}": jax.tree.map(lambda x: x[i], tree) for i in range(n)}


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
def test_layer_scan_carrying_pools_matches_layer_loop(dtype):
    """The paged programs' layer scan, which carries the stacked pools
    and hands each layer its index, gives the logits and pools of the
    unscanned layer loop (one 4-D pool per layer): a chunk prefilled
    into the pools, then two decode steps."""
    from repro.configs import get_smoke_config
    from repro.models.model import LM
    from repro.serve.paged import set_block_table_rows
    # float32 activations: in bf16 the scan and the unrolled loop round
    # differently on the CPU, whatever the cache
    cfg = get_smoke_config("qwen2-1.5b").with_(kv_cache_dtype=dtype,
                                               dtype="float32")
    lm, lm_loop = LM(cfg), LM(cfg.with_(scan_layers=False))
    params = lm.init(jax.random.PRNGKey(0))
    n_g = cfg.num_groups
    params_loop = {**params, "layers": _per_layer(params["layers"], n_g)}
    s, page, pps, c = 2, 8, 4, 12
    cache = lm.init_paged_cache(s, 1 + s * pps, pps, page_size=page)
    cache = set_block_table_rows(cache, [0, 1],
                                 np.arange(1, 1 + s * pps).reshape(s, pps))
    cache_loop = _per_layer(cache, n_g)
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (s, c)), jnp.int32)
    slots = jnp.arange(s, dtype=jnp.int32)
    starts = jnp.zeros((s,), jnp.int32)
    lens = jnp.asarray([c, 7], jnp.int32)
    lg, cache = lm.prefill_paged(params, toks, cache, slots, starts, lens)
    lg_l, cache_loop = lm_loop.prefill_paged(params_loop, toks, cache_loop,
                                             slots, starts, lens)
    steps = [(lg, lg_l)]
    pos = lens
    for _ in range(2):
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
        lg, cache = lm.decode_step(params, tok, cache, pos)
        lg_l, cache_loop = lm_loop.decode_step(params_loop, tok, cache_loop,
                                               pos)
        steps.append((lg, lg_l))
        pos = pos + 1
    for got, want in steps:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
    # int8 scales may differ in the last float32 bit, as the activations
    for k in POOL_KEYS:
        if k in cache["blk0"]["kv"]:
            for i in range(n_g):
                np.testing.assert_allclose(
                    np.asarray(cache["blk0"]["kv"][k][i], np.float32),
                    np.asarray(cache_loop[f"g{i}"]["blk0"]["kv"][k],
                               np.float32), rtol=1e-6, atol=0)


def test_requant_growth_keeps_earlier_tokens():
    """Decode writes with growing amax requantize the page in place; the
    earlier tokens must survive within (a couple of) quantization steps
    of the final scale."""
    a = AttentionConfig(kind="mha", num_heads=1, num_kv_heads=1, head_dim=8)
    spec = CacheSpec(layout="paged", dtype="int8", page_size=8)
    cache = alloc_paged(spec, a, 1, 2, 1)
    cache["block_table"] = jnp.ones((1, 1), jnp.int32)
    mags = [0.5, 1.0, 2.0, 4.0, 8.0]             # forces 4 scale growths
    toks = []
    for i, m in enumerate(mags):
        t = jnp.full((1, 1, 8), m, jnp.bfloat16)
        toks.append(np.asarray(t, np.float32))
        cache = paged_write_batch(cache, jnp.asarray([i], jnp.int32),
                                  t, t)
    kp, _, ks, _, bt = paged_views(cache)
    final_step = float(ks[1, 0])                 # scale after all growths
    got = np.asarray(kp[1, 0, :5], np.float32) * final_step   # (5, 8)
    want = np.concatenate(toks)[:, 0]                         # (5, 8)
    assert np.abs(got - want).max() <= 2.5 * final_step + 1e-6


# ---------------------------------------------------------------------------
# quantized contiguous cache (eager decode path)


@pytest.mark.parametrize("dtype", ["int8", "fp8"])
def test_contiguous_quantized_decode_matches_bf16(dtype):
    from repro.models.attention import attention_decode, init_attention
    a = AttentionConfig(kind="gqa", num_heads=4, num_kv_heads=2,
                        head_dim=16, rope_theta=10_000.0)
    p = init_attention(jax.random.PRNGKey(0), 32, a, jnp.float32)
    b = 2
    c_bf = alloc_contiguous(CacheSpec(dtype="bf16"), a, b, 32)
    c_q = alloc_contiguous(CacheSpec(dtype=dtype), a, b, 32)
    assert "k_scale" in c_q and c_q["k_scale"].shape == (b, 32, 2)
    hist_k = jax.random.normal(jax.random.PRNGKey(1), (b, 8, 2, 16))
    hist_v = jax.random.normal(jax.random.PRNGKey(2), (b, 8, 2, 16))
    c_bf = prefill_write(c_bf, {"k": hist_k, "v": hist_v})
    c_q = prefill_write(c_q, {"k": hist_k, "v": hist_v})
    x = jax.random.normal(jax.random.PRNGKey(3), (b, 1, 32), jnp.float32)
    pos = jnp.full((b,), 8, jnp.int32)
    y_bf, _ = attention_decode(p, x, a, c_bf, pos)
    y_q, c_q2 = attention_decode(p, x, a, c_q, pos)
    tol = 0.05 if dtype == "int8" else 0.15
    np.testing.assert_allclose(np.asarray(y_q), np.asarray(y_bf),
                               atol=tol, rtol=tol)
    # the write landed quantized, with a scale at the written position
    assert c_q2["k"].dtype == CacheSpec(dtype=dtype).store_dtype
    assert (np.asarray(c_q2["k_scale"])[:, 8] > 0).all()


def test_decode_write_is_quantized_not_truncated():
    """The pre-kvcache bug: bf16 values in [-1, 1] stored via a bare
    .astype(int8) truncate to 0.  The quantized write must preserve
    them."""
    a = AttentionConfig(kind="mha", num_heads=2, num_kv_heads=2, head_dim=8)
    cache = alloc_contiguous(CacheSpec(dtype="int8"), a, 1, 4)
    small = jnp.full((1, 1, 2, 8), 0.37, jnp.bfloat16)
    cache = decode_write(cache, {"k": small, "v": small},
                         jnp.zeros((1,), jnp.int32))
    back = dequantize(cache["k"][:, 0], cache["k_scale"][:, 0], axis=-1)
    np.testing.assert_allclose(np.asarray(back), 0.37, rtol=0.01)
    assert np.abs(np.asarray(cache["k"][0, 0], np.int32)).max() > 100


# ---------------------------------------------------------------------------
# byte accounting


def test_kv_bytes_per_token_ratio():
    from repro.configs import get_config
    cfg = get_config("qwen2-1.5b")                # real head_dim
    bf = kv_bytes_per_token(cfg)
    i8 = kv_bytes_per_token(cfg.with_(kv_cache_dtype="int8"))
    f8 = kv_bytes_per_token(cfg.with_(kv_cache_dtype="fp8"))
    assert bf / i8 >= 1.8 and bf / f8 >= 1.8
    # paged layout amortizes the scales over the page -> strictly closer
    # to the ideal 2× than the per-position contiguous scales
    i8p = kv_bytes_per_token(cfg.with_(kv_cache_dtype="int8"),
                             layout="paged")
    assert bf / i8p > bf / i8 and bf / i8p >= 1.95


def test_pool_bytes_halve_under_int8():
    a = AttentionConfig(kind="gqa", num_heads=8, num_kv_heads=4,
                        head_dim=64)
    kw = dict(n_slots=4, n_pages=33, pages_per_slot=8)
    bf = pool_bytes(alloc_paged(CacheSpec(layout="paged", dtype="bf16",
                                          page_size=64), a, **kw))
    i8 = pool_bytes(alloc_paged(CacheSpec(layout="paged", dtype="int8",
                                          page_size=64), a, **kw))
    assert bf / i8 >= 1.8


# ---------------------------------------------------------------------------
# engine end-to-end: paged int8 == eager bf16 on the smoke config


def _engine_setup():
    from repro.configs import get_smoke_config
    from repro.models.model import LM
    cfg = get_smoke_config("qwen2-1.5b")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).tolist()
               for n in (8, 5, 12)]
    return cfg, lm, params, prompts


def test_paged_int8_engine_matches_eager_bf16_engine():
    """Greedy decode through the int8 paged engine (fused-dequant Pallas
    kernel, requantizing page writes, batched quantizing admission)
    reproduces the bf16 eager engine's token streams on the smoke
    config — the end-to-end statement that kv_cache_dtype="int8" is a
    memory knob, not an accuracy knob."""
    from repro.models.model import LM
    from repro.serve.engine import Engine, PagedEngine
    cfg, lm, params, prompts = _engine_setup()
    eng = Engine(lm, params, n_slots=2, max_len=64, seed=0)
    ids = [eng.submit(p, max_new_tokens=9) for p in prompts]
    done = eng.run_to_completion()

    lm8 = LM(cfg.with_(kv_cache_dtype="int8"))
    peng = PagedEngine(lm8, params, n_slots=2, max_len=64, seed=0,
                       page_size=8, decode_block=4)
    pids = [peng.submit(p, max_new_tokens=9) for p in prompts]
    pdone = peng.run_to_completion()
    for a_, b_ in zip(ids, pids):
        assert done[a_].out_tokens == pdone[b_].out_tokens


def test_int8_decode_logits_close_to_bf16():
    """decode_step logits under an int8 contiguous cache stay within
    quantization tolerance of the bf16 cache (deterministic check under
    the engine-level greedy equality)."""
    from repro.models.model import LM
    cfg, lm, params, prompts = _engine_setup()
    lm8 = LM(cfg.with_(kv_cache_dtype="int8"))
    b, plen = 2, 8
    rng = np.random.default_rng(7)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, plen)), jnp.int32)
    lg_bf, c_bf = lm.prefill(params, toks, lm.init_cache(b, 32))
    lg_i8, c_i8 = lm8.prefill(params, toks, lm8.init_cache(b, 32))
    nxt = jnp.argmax(lg_bf, -1).astype(jnp.int32)
    pos = jnp.full((b,), plen, jnp.int32)
    d_bf, _ = lm.decode_step(params, nxt, c_bf, pos)
    d_i8, _ = lm8.decode_step(params, nxt, c_i8, pos)
    np.testing.assert_allclose(np.asarray(d_i8), np.asarray(d_bf),
                               atol=0.12, rtol=0.05)
    assert (jnp.argmax(d_i8, -1) == jnp.argmax(d_bf, -1)).all()
