"""Paged serving: kernel-vs-oracle equivalence, page pool accounting,
and engine end-to-end equality (paged Pallas path == eager path).

The Pallas kernel runs in interpret mode on CPU (same dispatch the
engine uses), so these tests cover the exact artifact that runs on TPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kvcache import paged_scatter_prefill, paged_write_batch
from repro.serve.paged import OutOfPagesError, PageAllocator, PagedKVPool


def _rand_paged(rng, s, h, kvh, d, page, pps, dtype):
    """Random q + pools with distinct allocated pages per slot."""
    n = s * pps + 1
    q = jnp.asarray(rng.normal(size=(s, h, d)), dtype)
    kp = jnp.asarray(rng.normal(size=(n, kvh, page, d)), dtype)
    vp = jnp.asarray(rng.normal(size=(n, kvh, page, d)), dtype)
    pool = list(rng.permutation(np.arange(1, n)))
    bt = jnp.asarray([[pool.pop() for _ in range(pps)] for _ in range(s)],
                     jnp.int32)
    return q, kp, vp, bt


# ---------------------------------------------------------------------------
# kernel vs oracle


@pytest.mark.parametrize("s,h,kvh,d,page,pps", [
    (2, 4, 4, 32, 8, 3),      # MHA
    (3, 4, 2, 64, 8, 4),      # GQA
    (2, 8, 1, 64, 16, 2),     # MQA
    (4, 8, 2, 128, 32, 2),    # bigger head dim / page
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_matches_ref(s, h, kvh, d, page, pps, dtype):
    rng = np.random.default_rng(0)
    q, kp, vp, bt = _rand_paged(rng, s, h, kvh, d, page, pps, dtype)
    # per-slot lengths: a free slot, a partial last page, a full slot
    lengths = jnp.asarray(rng.integers(1, pps * page, (s,)), jnp.int32)
    lengths = lengths.at[0].set(0).at[-1].set(pps * page)
    o = paged_attention(q, kp, vp, bt, lengths)
    ref = paged_attention_ref(q, kp, vp, bt, lengths)
    tol = 1e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_paged_ref_matches_contiguous():
    """Paging a contiguous cache changes nothing: oracle == plain masked
    attention over the unpaged K/V."""
    rng = np.random.default_rng(1)
    s, h, kvh, d, page, pps = 2, 4, 2, 32, 8, 4
    t = pps * page
    k = jnp.asarray(rng.normal(size=(s, t, kvh, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(s, t, kvh, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32)
    lengths = jnp.asarray([t // 2 + 3, t], jnp.int32)
    # page it: slot i gets pages 1+i*pps .. (contiguous layout), each
    # page head-major (KH, page, D)
    def paged(x):
        x = x.reshape(s * pps, page, kvh, d).transpose(0, 2, 1, 3)
        return jnp.concatenate([jnp.zeros((1, kvh, page, d)), x])
    kp, vp = paged(k), paged(v)
    bt = (1 + jnp.arange(s * pps, dtype=jnp.int32)).reshape(s, pps)
    o = paged_attention_ref(q, kp, vp, bt, lengths)
    # dense reference
    g = h // kvh
    qg = q.reshape(s, kvh, g, d)
    scores = jnp.einsum("skgd,stkd->skgt", qg, k) / np.sqrt(d)
    valid = jnp.arange(t)[None] < lengths[:, None]
    scores = jnp.where(valid[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    dense = jnp.einsum("skgt,stkd->skgd", probs, v).reshape(s, h, d)
    np.testing.assert_allclose(np.asarray(o), np.asarray(dense),
                               atol=1e-5, rtol=1e-5)


def test_paged_write_and_scatter():
    rng = np.random.default_rng(2)
    s, kvh, d, page, pps = 2, 2, 16, 4, 3
    n = s * pps + 1
    bt = (1 + jnp.arange(s * pps, dtype=jnp.int32)).reshape(s, pps)
    cache = {"k_pages": jnp.zeros((n, kvh, page, d)),
             "v_pages": jnp.zeros((n, kvh, page, d)),
             "block_table": bt}
    # batched prefill scatter: ragged lengths, padding -> null page
    t_pad = 8
    k_rows = jnp.asarray(rng.normal(size=(s, t_pad, kvh, d)), jnp.float32)
    v_rows = jnp.asarray(rng.normal(size=(s, t_pad, kvh, d)), jnp.float32)
    lengths = jnp.asarray([5, 8], jnp.int32)
    slot_ids = jnp.arange(s, dtype=jnp.int32)
    cache = paged_scatter_prefill(cache, slot_ids, lengths, k_rows, v_rows)
    for sl in range(s):
        ln = int(lengths[sl])
        for t in range(ln):
            got = np.asarray(
                cache["k_pages"][bt[sl, t // page], :, t % page])
            np.testing.assert_allclose(got, np.asarray(k_rows[sl, t]),
                                       atol=1e-6)
    # single-token batched write at per-slot positions
    k_new = jnp.asarray(rng.normal(size=(s, kvh, d)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(s, kvh, d)), jnp.float32)
    cache = paged_write_batch(cache, lengths, k_new, v_new)
    for sl in range(s):
        ln = int(lengths[sl])
        got = np.asarray(
            cache["k_pages"][bt[sl, ln // page], :, ln % page])
        np.testing.assert_allclose(got, np.asarray(k_new[sl]), atol=1e-6)


# ---------------------------------------------------------------------------
# page pool accounting


def test_pool_alloc_raises_and_rolls_back():
    pool = PagedKVPool(n_pages=4, kv_heads=1, head_dim=8,
                       max_pages_per_slot=4, n_slots=2, page_size=4)
    assert len(pool.free) == 3              # page 0 reserved
    pool.alloc(0, seq_len=8)                # 2 pages
    free_before = list(pool.free)
    with pytest.raises(OutOfPagesError):
        pool.alloc(1, seq_len=8)            # needs 2, only 1 free
    assert pool.free == free_before, "partial pops must roll back"
    pool.release(0)
    assert len(pool.free) == 3
    pool.alloc(1, seq_len=12)               # all 3 pages: now satisfiable
    assert not pool.free


def test_allocator_per_slot_cap_and_release():
    al = PageAllocator(n_pages=10, max_pages_per_slot=2, n_slots=3)
    with pytest.raises(OutOfPagesError):
        al.alloc(0, need=3)                 # over the per-slot cap
    pages = al.alloc(0, need=2)
    assert list(al.table[0, :2]) == pages
    with pytest.raises(OutOfPagesError):
        al.alloc(0, need=1)                 # double alloc
    al.release(0)
    assert (al.table[0] == 0).all()
    assert len(al.free) == 9


# ---------------------------------------------------------------------------
# engine end-to-end


def _serving_setup(dtype="float32"):
    from repro.configs import get_smoke_config
    from repro.models.model import LM
    cfg = get_smoke_config("qwen2-1.5b").with_(dtype=dtype)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).tolist()
               for n in (8, 5, 12, 8, 3)]
    return lm, params, prompts


def test_paged_engine_matches_eager_engine():
    """Greedy outputs are bit-identical between the eager per-token
    engine and the paged engine (Pallas kernel, fused 4-token blocks,
    batched admission, multi-page slots), across slot churn."""
    from repro.serve.engine import Engine, PagedEngine
    lm, params, prompts = _serving_setup()
    eng = Engine(lm, params, n_slots=2, max_len=64, seed=0)
    ids = [eng.submit(p, max_new_tokens=9) for p in prompts]
    done = eng.run_to_completion()

    peng = PagedEngine(lm, params, n_slots=2, max_len=64, seed=0,
                       page_size=8, decode_block=4)
    pids = [peng.submit(p, max_new_tokens=9) for p in prompts]
    pdone = peng.run_to_completion()
    for a, b in zip(ids, pids):
        assert done[a].out_tokens == pdone[b].out_tokens
        assert len(pdone[b].out_tokens) == 9


def test_paged_engine_syncs_per_block_not_per_token():
    """The fused decode loop must sync the host once per K-token block:
    total device->host transitions stay well under the token count."""
    from repro.serve.engine import PagedEngine
    lm, params, prompts = _serving_setup()
    peng = PagedEngine(lm, params, n_slots=2, max_len=64, seed=0,
                       page_size=8, decode_block=8)
    ids = [peng.submit(p, max_new_tokens=17) for p in prompts]
    done = peng.run_to_completion()
    n_tok = sum(len(done[i].out_tokens) for i in ids)
    assert n_tok == 17 * len(prompts)
    # eager syncs once per token (n_tok); the paged engine syncs once
    # per admission batch + once per decode block
    assert peng.sync_count <= n_tok // 4, \
        f"{peng.sync_count} syncs for {n_tok} tokens"


def test_tracing_and_metrics_are_sync_free():
    """The obs layer's structural guarantee: an enabled tracer reuses
    host timestamps the engine already takes and the decode-loop device
    stats are carried through the existing scan either way — so the
    traced run performs EXACTLY the same device->host syncs and emits
    bit-identical greedy streams as the default run."""
    from repro.obs import Tracer
    from repro.obs.trace import request_span_trees
    from repro.serve.engine import PagedEngine
    lm, params, prompts = _serving_setup()

    def run(tracer=None):
        peng = PagedEngine(lm, params, n_slots=2, max_len=64, seed=0,
                           page_size=8, decode_block=4, tracer=tracer)
        ids = [peng.submit(p, max_new_tokens=9) for p in prompts]
        done = peng.run_to_completion()
        return [done[i].out_tokens for i in ids], peng.sync_count

    base_toks, base_syncs = run()
    tr = Tracer(enabled=True)
    toks, syncs = run(tracer=tr)
    assert toks == base_toks
    assert syncs == base_syncs
    trees = request_span_trees(tr.to_json())
    assert len(trees) == len(prompts)
    assert all(t["complete"] for t in trees.values())


def test_paged_engine_eos_and_page_reuse():
    """EOS mid-block retires the slot, frees its pages, and the reused
    pages serve later requests correctly."""
    from repro.serve.engine import Engine, PagedEngine
    lm, params, prompts = _serving_setup()
    # discover the greedy token stream to pick a real EOS id
    eng = Engine(lm, params, n_slots=1, max_len=64, seed=0)
    rid = eng.submit(prompts[0], max_new_tokens=6)
    probe = eng.run_to_completion()[rid].out_tokens
    eos = probe[3]                      # stop 4 tokens in

    eng = Engine(lm, params, n_slots=1, max_len=64, eos_id=eos, seed=0)
    ids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    done = eng.run_to_completion()

    peng = PagedEngine(lm, params, n_slots=1, max_len=64, eos_id=eos,
                       seed=0, page_size=8, decode_block=4)
    pids = [peng.submit(p, max_new_tokens=6) for p in prompts]
    pdone = peng.run_to_completion()
    for a, b in zip(ids, pids):
        assert done[a].out_tokens == pdone[b].out_tokens
    # pool fully drained back
    assert len(peng.alloc.free) == peng.alloc.n_pages - 1


def test_paged_engine_temperature_sampling_on_device():
    from repro.serve.engine import PagedEngine
    lm, params, prompts = _serving_setup()
    peng = PagedEngine(lm, params, n_slots=2, max_len=64, seed=0,
                       page_size=8, decode_block=4)
    i = peng.submit(prompts[0], max_new_tokens=6, temperature=0.8)
    j = peng.submit(prompts[1], max_new_tokens=6)          # greedy
    done = peng.run_to_completion()
    assert len(done[i].out_tokens) == 6
    assert len(done[j].out_tokens) == 6
    cfg = lm.cfg
    assert all(0 <= t < cfg.vocab_size for t in done[i].out_tokens)


def test_submit_rejects_overlong_prompt():
    """Both engines refuse prompts that cannot fit the slot horizon
    (the paged path would otherwise clamp the gather and corrupt the
    slot's last page silently)."""
    from repro.serve.engine import Engine, PagedEngine
    lm, params, _ = _serving_setup()
    long_prompt = list(range(16))
    eng = Engine(lm, params, n_slots=1, max_len=16)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(long_prompt)
    peng = PagedEngine(lm, params, n_slots=1, max_len=16, page_size=8)
    with pytest.raises(ValueError, match="max_len"):
        peng.submit(long_prompt)


def test_paged_engine_out_of_pages_defers_admission():
    """With pages for only one request in flight, the second request
    waits (no crash) and completes after the first retires."""
    from repro.serve.engine import PagedEngine
    lm, params, prompts = _serving_setup()
    # n_pages budget: null + enough for ONE slot's horizon
    peng = PagedEngine(lm, params, n_slots=2, max_len=32, seed=0,
                       page_size=8, decode_block=4, n_pages=4)
    ids = [peng.submit(prompts[0][:8], max_new_tokens=5),
           peng.submit(prompts[1][:5], max_new_tokens=5)]
    done = peng.run_to_completion()
    for i in ids:
        assert len(done[i].out_tokens) == 5
