"""Attention: MHA / MQA / GQA (one GQA impl with variable kv heads) + MLA.

Entry points per layer:
  * ``attention_forward``  — train / prefill (full sequence, causal or not)
  * ``attention_decode``   — one-token step against a contiguous KV cache
  * ``attention_decode_paged`` — one-token step, all slots, against paged
    KV pools via the Pallas flash-decoding kernel
    (``kernels/paged_attention``; page bookkeeping in ``repro.serve.paged``)
  * ``attention_prefill_paged`` / ``attention_verify_paged`` — W-query
    steps against paged pools plus a fresh causal chunk, both through the
    ONE width-parameterized prefix-extend kernel (W = chunk width for
    chunked prefill continuation, W = draft_k + 1 for spec verify)

Cache allocation / writes / dequant live in ``repro.kvcache`` (the one
implementation for every layout × dtype × style combination); this module
only computes.  Quantized caches are consumed FUSED: the per-position K
scale folds into the score contraction and the V scale into the
probs·V contraction, so no dequantized copy of the cache is materialized.

MLA (DeepSeek-V2 style) compresses KV into a latent ``c_kv`` plus a shared
decoupled-RoPE key; decode uses the absorbed-matmul trick so the cache is
only ``(B, S, kv_lora_rank + rope_head_dim)``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import AttentionConfig
from repro.models.layers import apply_rope, init_linear, linear_apply

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Init


def init_attention(key, d_model: int, a: AttentionConfig, dtype=jnp.bfloat16) -> dict:
    ks = jax.random.split(key, 8)
    if a.kind == "mla":
        p = {
            "kv_down": init_linear(ks[0], d_model, a.kv_lora_rank, dtype=dtype),
            "k_rope": init_linear(ks[1], d_model, a.rope_head_dim, dtype=dtype),
            "kv_up_k": init_linear(ks[2], a.kv_lora_rank,
                                   a.num_heads * a.head_dim, dtype=dtype),
            "kv_up_v": init_linear(ks[3], a.kv_lora_rank,
                                   a.num_heads * a.head_dim, dtype=dtype),
            "wo": init_linear(ks[5], a.num_heads * a.head_dim, d_model, dtype=dtype),
        }
        if a.q_lora_rank:
            p["q_down"] = init_linear(ks[6], d_model, a.q_lora_rank, dtype=dtype)
            p["q_up"] = init_linear(ks[4], a.q_lora_rank,
                                    a.num_heads * (a.head_dim + a.rope_head_dim),
                                    dtype=dtype)
        else:
            p["q_up"] = init_linear(ks[4], d_model,
                                    a.num_heads * (a.head_dim + a.rope_head_dim),
                                    dtype=dtype)
        return p
    kvh = a.kv_heads_effective()
    hp = a.heads_padded
    p = {
        "wq": init_linear(ks[0], d_model, hp * a.head_dim,
                          bias=a.qkv_bias, dtype=dtype),
        "wk": init_linear(ks[1], d_model, kvh * a.head_dim,
                          bias=a.qkv_bias, dtype=dtype),
        "wv": init_linear(ks[2], d_model, kvh * a.head_dim,
                          bias=a.qkv_bias, dtype=dtype),
        "wo": init_linear(ks[3], hp * a.head_dim, d_model, dtype=dtype),
    }
    if hp != a.num_heads:
        # zero-init the padded heads (wq cols / wo rows), group-aware:
        # exact semantics, zero grads — they stay dead under training
        mask = _pad_head_mask(a)
        p["wq"]["w"] = p["wq"]["w"] * mask[None, :].astype(p["wq"]["w"].dtype)
        p["wo"]["w"] = p["wo"]["w"] * mask[:, None].astype(p["wo"]["w"].dtype)
        if "b" in p["wq"]:
            p["wq"]["b"] = p["wq"]["b"] * mask.astype(p["wq"]["b"].dtype)
    return p


# ---------------------------------------------------------------------------
# Core SDPA (grouped-query, fp32 softmax)


def sdpa(q: jax.Array, k: jax.Array, v: jax.Array,
         mask: Optional[jax.Array], scale: float) -> jax.Array:
    """q: (B,S,KH,G,D)  k,v: (B,T,KH,D)  mask: (S,T) or None -> (B,S,KH,G,D)."""
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgst,btkd->bskgd", probs, v)


def causal_mask(s: int, t: int, *, offset: int = 0,
                window: Optional[int] = None) -> jax.Array:
    """(s, t) boolean mask; query i (global pos offset+i) sees key j <= pos."""
    qpos = jnp.arange(s)[:, None] + offset
    kpos = jnp.arange(t)[None, :]
    m = kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    return m


# ---------------------------------------------------------------------------
# Chunked (flash-style) attention — pure-jnp online softmax.
#
# Never materializes the (S, T) score matrix: the kv axis is consumed
# block-by-block with a running (max, denom, acc) carry, the q axis in
# q_block slices.  Mirrors the math of kernels/flash_attention (which is
# the TPU hot path); this is the XLA fallback that makes prefill_32k /
# train_4k memory-feasible.  Each q-block body is rematerialized
# (jax.checkpoint), so backward peaks at one block of probs, exactly
# like a flash backward.
#
# ``unroll=True`` (dry-run accounting + TPU) uses python loops with
# exact causal/window block bounds -> no wasted flops above the causal
# diagonal and cost_analysis sees every block.


def _block_attn(q, k, v, carry, mask, scale):
    """One (q_block × kv_block) online-softmax update.
    q: (B,KH,G,Sq,D)  k,v: (B,KH,Bk,D)  carry = (m, l, acc)."""
    m_prev, l_prev, acc = carry
    s = jnp.einsum("bkgsd,bktd->bkgst", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bkgst,bktd->bkgsd", p.astype(v.dtype), v)
    acc = acc * corr[..., None].astype(acc.dtype) + pv.astype(acc.dtype)
    return m_new, l_new, acc


def chunked_attention(qg: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool, window: Optional[int], scale: float,
                      q_block: int = 1024, kv_block: int = 1024,
                      unroll: bool = False) -> jax.Array:
    """qg: (B,S,KH,G,D)  k,v: (B,T,KH,D) -> (B,S,KH,G,D)."""
    b, s, kh, g, d = qg.shape
    t = k.shape[1]
    qb = min(q_block, s)
    kb = min(kv_block, t)
    if s % qb or t % kb:
        qb, kb = s, t                       # fallback: single block
    nq, nk = s // qb, t // kb
    q_sw = qg.swapaxes(1, 2).swapaxes(2, 3)            # (B,KH,G,S,D)
    k_sw = k.swapaxes(1, 2)                            # (B,KH,T,D)
    v_sw = v.swapaxes(1, 2)

    def kv_bounds(qi: int) -> tuple:
        """Blocks [lo, hi) of kv that q block qi can see."""
        hi = nk if not causal else min(nk, ((qi + 1) * qb + kb - 1) // kb)
        lo = 0
        if window is not None:
            lo = max(0, (qi * qb - window) // kb)
        return lo, hi

    @jax.checkpoint
    def one_q_block(q_i, k_vis, v_vis, qi0, kj0):
        """q_i: (B,KH,G,qb,D); k_vis/v_vis: (B,KH,nvis*kb,D); global
        offsets qi0 (query) / kj0 (first key) for masking."""
        nvis = k_vis.shape[2] // kb
        m0 = jnp.full((b, kh, g, qb), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kh, g, qb), jnp.float32)
        a0 = jnp.zeros((b, kh, g, qb, d), jnp.float32)

        def body(carry, j):
            k_j = jax.lax.dynamic_slice_in_dim(k_vis, j * kb, kb, axis=2)
            v_j = jax.lax.dynamic_slice_in_dim(v_vis, j * kb, kb, axis=2)
            qpos = qi0 + jnp.arange(qb)[:, None]
            kpos = kj0 + j * kb + jnp.arange(kb)[None, :]
            mask = None
            if causal or window is not None:
                mask = jnp.ones((qb, kb), bool)
                if causal:
                    mask &= kpos <= qpos
                if window is not None:
                    mask &= kpos > qpos - window
            return _block_attn(q_i, k_j, v_j, carry, mask, scale), None

        if unroll:
            carry = (m0, l0, a0)
            for j in range(nvis):
                carry, _ = body(carry, j)
            m, l, acc = carry
        else:
            (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                          jnp.arange(nvis))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    if unroll:
        outs = []
        for qi in range(nq):
            lo, hi = kv_bounds(qi)
            k_vis = k_sw[:, :, lo * kb:hi * kb]
            v_vis = v_sw[:, :, lo * kb:hi * kb]
            q_i = q_sw[:, :, :, qi * qb:(qi + 1) * qb]
            outs.append(one_q_block(q_i, k_vis, v_vis, qi * qb, lo * kb))
        o = jnp.concatenate(outs, axis=3)
    else:
        def q_body(_, qi):
            q_i = jax.lax.dynamic_slice_in_dim(q_sw, qi * qb, qb, axis=3)
            return None, one_q_block(q_i, k_sw, v_sw, qi * qb, 0)

        _, o_blocks = jax.lax.scan(q_body, None, jnp.arange(nq))
        # (nq, B,KH,G,qb,D) -> (B,KH,G,S,D)
        o = jnp.moveaxis(o_blocks, 0, 3).reshape(b, kh, g, s, d)
    # (B,KH,G,S,D) -> (B,S,KH,G,D)
    return o.swapaxes(2, 3).swapaxes(1, 2).astype(v.dtype)



def _pad_head_mask(a: AttentionConfig) -> jax.Array:
    """bool[(hp·hd)]: True for live head slots.  Padding is group-aware:
    the (B,S,KH,G,D) reshape assigns heads to kv groups contiguously, so
    each kv group keeps its first num_heads/kvh slots live."""
    hp = a.heads_padded
    kvh = a.kv_heads_effective()
    g_pad = hp // kvh
    g_live = a.num_heads // kvh
    slot = jnp.arange(hp) % g_pad
    live = slot < g_live
    return jnp.repeat(live, a.head_dim)


def _mask_pad_heads(o_flat, a: AttentionConfig):
    """Zero the padded heads' outputs before wo: exact semantics AND
    exactly-zero grads for both wq cols and wo rows (dead stays dead)."""
    if a.heads_padded == a.num_heads:
        return o_flat
    return o_flat * _pad_head_mask(a).astype(o_flat.dtype)


# ---------------------------------------------------------------------------
# Forward (train / prefill)


def attention_forward(p: dict, x: jax.Array, a: AttentionConfig, *,
                      positions: Optional[jax.Array] = None,
                      cross_x: Optional[jax.Array] = None,
                      use_flash: bool = False,
                      attn_impl: str = "auto",
                      q_block: int = 1024, kv_block: int = 1024,
                      chunk_min: int = 2048,
                      unroll: bool = False) -> jax.Array:
    """Full-sequence attention.  ``cross_x`` switches to cross-attention
    (queries from x, keys/values from cross_x, no mask)."""
    if a.kind == "mla":
        return _mla_forward(p, x, a, positions=positions)
    b, s, d = x.shape
    kvh = a.kv_heads_effective()
    g = a.heads_padded // kvh
    src = cross_x if cross_x is not None else x
    t = src.shape[1]

    q = linear_apply(p["wq"], x).reshape(b, s, a.heads_padded, a.head_dim)
    k = linear_apply(p["wk"], src).reshape(b, t, kvh, a.head_dim)
    v = linear_apply(p["wv"], src).reshape(b, t, kvh, a.head_dim)

    if cross_x is None:
        if positions is None:
            positions = jnp.arange(s)[None, :]
        q = apply_rope(q, positions, a.rope_theta)
        k = apply_rope(k, positions, a.rope_theta)

    if cross_x is not None:
        mask = None
    elif a.causal:
        mask = causal_mask(s, t, window=a.window)
    else:
        mask = None

    scale = 1.0 / jnp.sqrt(a.head_dim).astype(jnp.float32)
    with jax.named_scope("attn_kernel"):
        if use_flash and cross_x is None and mask is not None:
            from repro.kernels.flash_attention.ops import flash_attention
            o = flash_attention(q, k, v, causal=True, window=a.window)
            o = o.reshape(b, s, a.heads_padded * a.head_dim)
        elif cross_x is None and (attn_impl == "chunked"
                                  or (attn_impl == "auto"
                                      and s >= chunk_min)):
            qg = q.reshape(b, s, kvh, g, a.head_dim)
            o = chunked_attention(qg, k, v, causal=a.causal,
                                  window=a.window, scale=scale,
                                  q_block=q_block, kv_block=kv_block,
                                  unroll=unroll)
            o = o.reshape(b, s, a.heads_padded * a.head_dim)
        else:
            qg = q.reshape(b, s, kvh, g, a.head_dim)
            o = sdpa(qg, k, v, mask, scale)
            o = o.reshape(b, s, a.heads_padded * a.head_dim)
    return linear_apply(p["wo"], _mask_pad_heads(o, a))


def _mla_forward(p: dict, x: jax.Array, a: AttentionConfig, *,
                 positions: Optional[jax.Array]) -> jax.Array:
    b, s, d = x.shape
    h, hd, rr = a.num_heads, a.head_dim, a.rope_head_dim
    if positions is None:
        positions = jnp.arange(s)[None, :]

    c_kv = linear_apply(p["kv_down"], x)                          # (B,S,dc)
    k_pe = linear_apply(p["k_rope"], x).reshape(b, s, 1, rr)
    k_pe = apply_rope(k_pe, positions, a.rope_theta)

    qx = linear_apply(p["q_down"], x) if "q_down" in p else x
    q = linear_apply(p["q_up"], qx).reshape(b, s, h, hd + rr)
    q_nope, q_pe = q[..., :hd], q[..., hd:]
    q_pe = apply_rope(q_pe, positions, a.rope_theta)

    k_nope = linear_apply(p["kv_up_k"], c_kv).reshape(b, s, h, hd)
    v = linear_apply(p["kv_up_v"], c_kv).reshape(b, s, h, hd)

    scale = 1.0 / jnp.sqrt(hd + rr).astype(jnp.float32)
    scores = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshr,btur->bhst", q_pe, k_pe,
                           preferred_element_type=jnp.float32)) * scale
    mask = causal_mask(s, s, window=a.window) if a.causal else None
    if mask is not None:
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhst,bthd->bshd", probs, v).reshape(b, s, h * hd)
    return linear_apply(p["wo"], o)


# ---------------------------------------------------------------------------
# KV cache consumption (allocation/writes: repro.kvcache)


def _merge_heads(x: jax.Array, kvh_store: int) -> jax.Array:
    """Mean-merge kv heads (B,T,KH,D) -> (B,T,kvh_store,D) for narrowed cache."""
    b, t, kh, d = x.shape
    if kh == kvh_store:
        return x
    return x.reshape(b, t, kvh_store, kh // kvh_store, d).mean(axis=3)


def attention_prefill(p: dict, x: jax.Array, a: AttentionConfig, cache: dict, *,
                      style: str = "full",
                      use_flash: bool = False,
                      **chunk_kw) -> tuple[jax.Array, dict]:
    """Run full-seq attention AND fill the cache for positions [0, s)."""
    b, s, _ = x.shape
    y = attention_forward(p, x, a, use_flash=use_flash, **chunk_kw)
    from repro import kvcache
    if a.kind == "mla":
        c_kv = linear_apply(p["kv_down"], x)
        k_pe = linear_apply(p["k_rope"], x).reshape(b, s, 1, a.rope_head_dim)
        k_pe = apply_rope(k_pe, jnp.arange(s)[None, :], a.rope_theta)[:, :, 0]
        return y, kvcache.prefill_write(cache, {"c_kv": c_kv, "k_pe": k_pe})
    kvh = a.kv_heads_effective()
    k = linear_apply(p["wk"], x).reshape(b, s, kvh, a.head_dim)
    v = linear_apply(p["wv"], x).reshape(b, s, kvh, a.head_dim)
    k = apply_rope(k, jnp.arange(s)[None, :], a.rope_theta)
    kvh_store = cache["k"].shape[2]
    k, v = _merge_heads(k, kvh_store), _merge_heads(v, kvh_store)
    # pin the cache-bound k/v to batch sharding: the flattened-head
    # col-shard of wk would otherwise leak a (kvh × head_dim) sharding
    # into the cache write and trigger a resharding storm
    from repro.sharding.ctx import maybe_constrain
    k = maybe_constrain(k, ("pod", "data"), None, None, None)
    v = maybe_constrain(v, ("pod", "data"), None, None, None)
    with jax.named_scope("kv_write"):
        cache = kvcache.prefill_write(cache, {"k": k, "v": v})
    return y, cache


def attention_prefill_paged(p: dict, x: jax.Array, a: AttentionConfig,
                            cache: dict, spos, *, style: str = "full",
                            use_kernel: bool = True, mesh=None,
                            tp_impl: str = "kv_shard") -> tuple[jax.Array, dict]:
    """Chunked / continuation prefill directly against a paged KV cache.

    x: (B, c, d) — one prompt chunk per admitted row; ``spos`` is
    ``(slot_ids (B,), starts (B,), lengths (B,))``: row b's chunk covers
    logical positions ``starts[b] .. starts[b]+lengths[b]-1`` of slot
    ``slot_ids[b]`` (rows right-padded to the common width c).  An
    optional 4th entry ``max_pages`` (static python int) narrows the
    kernel's page grid to the first ``max_pages`` block-table columns —
    the scheduler passes the pow2-bucketed page span of the batch's
    deepest prefix, so grid steps scale with the ACTUAL context, not the
    slot's full page horizon (the eager oracle keeps the full horizon:
    that is exactly the old gather's cost being benchmarked against).

    The chunk's K/V is written into the slot's pages (quantized pools
    reset each touched page's scale, so ``starts`` must be page-aligned)
    and its queries attend over ``[0, starts[b]+i]`` through the shared
    prefix-extend dispatch (``kernels/paged_attention``): the cached
    prefix is STREAMED page by page (dequant fused when quantized) while
    the chunk attends to its own fresh K/V causally — the same kernel
    speculative verify runs at W = draft_k + 1, here at W = chunk width.
    No full-horizon context is materialized; the old eager gather
    survives only as the ref.py oracle (``use_kernel=False``).  A
    prefix-cache warm start and a cold chunked run execute the SAME
    computation for any continuation chunk — that is what makes
    shared-prefix admission token-identical to a cold cache.
    """
    from repro import kvcache
    from repro.kernels.paged_attention.ops import paged_prefix_extend_attention
    if a.window is not None:
        raise NotImplementedError("paged prefill: sliding window unsupported")
    slot_ids, starts, lengths, *rest = spos
    max_pages = rest[0] if rest else None
    b, c, _ = x.shape
    kvh = a.kv_heads_effective()
    kvh_store = cache["k_pages"].shape[-3]

    apos = starts[:, None] + jnp.arange(c)[None, :]              # (B,c)
    q = linear_apply(p["wq"], x).reshape(b, c, a.heads_padded, a.head_dim)
    k_new = linear_apply(p["wk"], x).reshape(b, c, kvh, a.head_dim)
    v_new = linear_apply(p["wv"], x).reshape(b, c, kvh, a.head_dim)
    q = apply_rope(q, apos, a.rope_theta)
    k_new = apply_rope(k_new, apos, a.rope_theta)
    k_new = _merge_heads(k_new, kvh_store)
    v_new = _merge_heads(v_new, kvh_store)
    # pin the cache-bound k/v to batch × kv-head sharding before the pool
    # scatter — batch over DP (the old resharding-storm guard), kv heads
    # over "model" to match the sharded pools (the scatter is then a
    # purely local slice per shard; maybe_constrain degrades either axis
    # when absent or non-dividing)
    from repro.sharding.ctx import maybe_constrain
    k_new = maybe_constrain(k_new, ("pod", "data"), None, "model", None)
    v_new = maybe_constrain(v_new, ("pod", "data"), None, "model", None)

    with jax.named_scope("kv_write"):
        cache = kvcache.paged_scatter_prefill(cache, slot_ids, lengths,
                                              k_new, v_new, starts)
        cache = kvcache.constrain_paged_pools(cache)

    # prefix < starts[b] streamed from the pages; the chunk's own
    # just-scattered rows are masked out in favour of the fresh values
    with jax.named_scope("attn_kernel"):
        kp, vp, k_sc, v_sc, bt = kvcache.paged_views(cache)
        rows = bt[slot_ids]                                      # (B,P)
        if use_kernel and max_pages is not None \
                and max_pages < rows.shape[1]:
            rows = rows[:, :max_pages]
        o = paged_prefix_extend_attention(q, kp, vp, rows, starts,
                                          k_new, v_new, lengths, k_sc,
                                          v_sc, layer=cache.get("layer"),
                                          use_kernel=use_kernel,
                                          mesh=mesh, tp_impl=tp_impl)
    o = o.reshape(b, c, a.heads_padded * a.head_dim)
    y = linear_apply(p["wo"], _mask_pad_heads(o.astype(x.dtype), a))
    return y, cache


def attention_verify_paged(p: dict, x: jax.Array, a: AttentionConfig,
                           cache: dict, stage: dict, spos, *,
                           style: str = "full", use_kernel: bool = True,
                           mesh=None, tp_impl: str = "kv_shard") -> tuple:
    """Speculative-verify attention: score W draft positions per slot in
    ONE dispatch against the paged cache (``repro.spec``).

    x: (S, W, d) — the fed chunk (last accepted token + draft tokens),
    right-padded; ``spos`` is ``(lengths (S,), widths (S,))``: slot s's
    chunk sits at logical positions ``lengths[s] + [0, widths[s])``.
    An optional 3rd entry ``max_pages`` (static python int) narrows the
    kernel's page grid to the first ``max_pages`` block-table columns —
    the spec engine passes the pow2-bucketed page span of the deepest
    slot, so verify grid steps scale with the ACTUAL context instead of
    the full slot horizon (the chunk's own K/V is fresh, never paged, so
    only the prefix ``< lengths[s]`` bounds the span).
    Query w attends the cached prefix (positions < lengths[s], read from
    the pages — quantized pools dequant fused in the kernel) plus the
    chunk's own fresh bf16 K/V causally (keys j <= w, j < widths[s]).

    Write-after-accept: the chunk's K/V goes into the contiguous
    ``stage`` node (bf16), NOT the pages — the engine commits only the
    accepted prefix afterwards by replaying the exact sequential
    quantized token writes (``kvcache.paged_write_batch(mask=)``), so a
    rejected tail can never grow a page's running amax or requantize
    live entries: the paged pools evolve bit-identically to non-
    speculative decode and rollback is a pure length truncation.

    Attention itself is the shared prefix-extend dispatch
    (``kernels/paged_attention``) at W = draft_k + 1 — the same entry
    point ``attention_prefill_paged`` runs at W = chunk width."""
    from repro import kvcache
    from repro.kernels.paged_attention.ops import paged_prefix_extend_attention
    if a.window is not None:
        raise NotImplementedError("paged verify: sliding window unsupported")
    lengths, widths, *rest = spos
    max_pages = rest[0] if rest else None
    b, w, _ = x.shape
    kvh = a.kv_heads_effective()
    kvh_store = cache["k_pages"].shape[-3]

    apos = lengths[:, None] + jnp.arange(w)[None, :]             # (S,W)
    q = linear_apply(p["wq"], x).reshape(b, w, a.heads_padded, a.head_dim)
    k_new = linear_apply(p["wk"], x).reshape(b, w, kvh, a.head_dim)
    v_new = linear_apply(p["wv"], x).reshape(b, w, kvh, a.head_dim)
    q = apply_rope(q, apos, a.rope_theta)
    k_new = apply_rope(k_new, apos, a.rope_theta)
    k_new = _merge_heads(k_new, kvh_store)
    v_new = _merge_heads(v_new, kvh_store)
    from repro.sharding.ctx import maybe_constrain
    k_new = maybe_constrain(k_new, ("pod", "data"), None, "model", None)
    v_new = maybe_constrain(v_new, ("pod", "data"), None, "model", None)

    stage = kvcache.prefill_write(stage, {"k": k_new, "v": v_new})
    kp, vp, k_sc, v_sc, bt = kvcache.paged_views(cache)
    if use_kernel and max_pages is not None and max_pages < bt.shape[1]:
        bt = bt[:, :max_pages]
    o = paged_prefix_extend_attention(q, kp, vp, bt, lengths,
                                      k_new.astype(jnp.bfloat16),
                                      v_new.astype(jnp.bfloat16), widths,
                                      k_sc, v_sc, layer=cache.get("layer"),
                                      use_kernel=use_kernel,
                                      mesh=mesh, tp_impl=tp_impl)
    o = o.reshape(b, w, a.heads_padded * a.head_dim)
    y = linear_apply(p["wo"], _mask_pad_heads(o.astype(x.dtype), a))
    return y, stage


def _posv(pos: jax.Array, b: int) -> jax.Array:
    """Normalize pos (scalar or (B,)) to a (B,) vector."""
    return jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(pos)), (b,))


def attention_decode(p: dict, x: jax.Array, a: AttentionConfig, cache: dict,
                     pos: jax.Array, *, style: str = "full") -> tuple[jax.Array, dict]:
    """One-token step.  x: (B,1,d); pos: scalar or per-batch (B,) position.
    int8/fp8 caches are read fused: the per-position K scale multiplies the
    scores and the V scale folds into probs before the V contraction."""
    from repro import kvcache
    if a.kind == "mla":
        return _mla_decode(p, x, a, cache, pos)
    b, _, d = x.shape
    kvh = a.kv_heads_effective()
    kvh_store = cache["k"].shape[2]
    g = a.heads_padded // kvh_store
    pos = _posv(pos, b)

    q = linear_apply(p["wq"], x).reshape(b, 1, a.heads_padded, a.head_dim)
    k_new = linear_apply(p["wk"], x).reshape(b, 1, kvh, a.head_dim)
    v_new = linear_apply(p["wv"], x).reshape(b, 1, kvh, a.head_dim)
    posv = pos[:, None]
    q = apply_rope(q, posv, a.rope_theta)
    k_new = apply_rope(k_new, posv, a.rope_theta)
    k_new = _merge_heads(k_new, kvh_store)
    v_new = _merge_heads(v_new, kvh_store)

    cache = kvcache.decode_write(cache, {"k": k_new, "v": v_new}, pos)
    k_cache, v_cache, k_s, v_s = kvcache.kv_views(cache)

    t = k_cache.shape[1]
    kpos = jnp.arange(t)
    valid = kpos[None, :] <= pos[:, None]                       # (B,T)
    if a.window is not None:
        valid &= kpos[None, :] > pos[:, None] - a.window
    qg = q.reshape(b, 1, kvh_store, g, a.head_dim)
    scale = 1.0 / jnp.sqrt(a.head_dim).astype(jnp.float32)
    if k_s is None:
        scores = jnp.einsum("bskgd,btkd->bkgst", qg,
                            k_cache.astype(qg.dtype),
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        o = jnp.einsum("bkgst,btkd->bskgd", probs, v_cache.astype(x.dtype))
    else:
        # (B,T,KH) scales -> (B,KH,1,1,T) factors on the score/probs axes
        ks_t = k_s.transpose(0, 2, 1)[:, :, None, None, :]
        vs_t = v_s.transpose(0, 2, 1)[:, :, None, None, :]
        scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                            k_cache.astype(jnp.float32),
                            preferred_element_type=jnp.float32) * scale * ks_t
        scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bkgst,btkd->bskgd", probs * vs_t,
                       v_cache.astype(jnp.float32)).astype(x.dtype)
    o = o.reshape(b, 1, a.heads_padded * a.head_dim)
    y = linear_apply(p["wo"], _mask_pad_heads(o, a))
    return y, cache


def attention_decode_paged(p: dict, x: jax.Array, a: AttentionConfig,
                           cache: dict, pos: jax.Array, *,
                           style: str = "full", use_kernel: bool = True,
                           mesh=None,
                           tp_impl: str = "kv_shard") -> tuple[jax.Array, dict]:
    """One-token decode against a paged KV cache, ALL slots in one kernel
    launch (``decode_attn_impl == "paged_pallas"``).

    x: (S,1,d); pos: (S,) per-slot lengths — position where this token's
    K/V is written.  cache: {k_pages, v_pages[, k_scales, v_scales],
    block_table} from ``repro.kvcache.alloc_paged`` — inside the layer
    loop the whole layer stack of pools plus ``layer``, which the token
    write and the kernel address in place.  Slots without
    allocated pages write to the null page and read back zeros (their
    outputs are garbage; the engine masks them).  Quantized pools run
    the fused-dequant kernel variant (scales scalar-prefetched).
    """
    from repro import kvcache
    from repro.kernels.paged_attention.ops import paged_attention
    if a.window is not None:
        raise NotImplementedError("paged decode: sliding window unsupported")
    b, _, d = x.shape
    kvh = a.kv_heads_effective()
    kvh_store = cache["k_pages"].shape[-3]
    pos = _posv(pos, b)
    posv = pos[:, None]

    q = linear_apply(p["wq"], x).reshape(b, 1, a.heads_padded, a.head_dim)
    k_new = linear_apply(p["wk"], x).reshape(b, 1, kvh, a.head_dim)
    v_new = linear_apply(p["wv"], x).reshape(b, 1, kvh, a.head_dim)
    q = apply_rope(q, posv, a.rope_theta)[:, 0]                # (S,H,D)
    k_new = apply_rope(k_new, posv, a.rope_theta)
    k_new = _merge_heads(k_new, kvh_store)[:, 0]               # (S,KH,D)
    v_new = _merge_heads(v_new, kvh_store)[:, 0]
    # kv-head-pin the token write to match the sharded pools (local write
    # per shard; degrades off-mesh / non-dividing)
    from repro.sharding.ctx import maybe_constrain
    k_new = maybe_constrain(k_new, None, "model", None)
    v_new = maybe_constrain(v_new, None, "model", None)

    with jax.named_scope("kv_write"):
        cache = kvcache.paged_write_batch(cache, pos, k_new, v_new)
        cache = kvcache.constrain_paged_pools(cache)
    with jax.named_scope("attn_kernel"):
        k_pages, v_pages, k_sc, v_sc, bt = kvcache.paged_views(cache)
        o = paged_attention(q, k_pages, v_pages, bt, pos + 1, k_sc, v_sc,
                            layer=cache.get("layer"),
                            use_kernel=use_kernel, mesh=mesh,
                            tp_impl=tp_impl)                   # (S,H,D)
    o = o.reshape(b, 1, a.heads_padded * a.head_dim)
    y = linear_apply(p["wo"], _mask_pad_heads(o.astype(x.dtype), a))
    return y, cache


def attention_decode_cp(p: dict, x: jax.Array, a: AttentionConfig,
                        cache: dict, pos: jax.Array, *,
                        mesh, axis: str = "model") -> tuple[jax.Array, dict]:
    """Context-parallel decode (flash-decoding combine, beyond-paper):
    the KV cache is sharded over ``axis`` on the SEQUENCE dim; each shard
    updates its owned slice and computes partial softmax stats; one tiny
    (B,KH,G) psum replaces the all-gather of the whole cache that naive
    pjit emits when the kv-head count doesn't divide the model axis.
    x: (B,1,d); cache k/v: (B,S,KH,D) sharded P(dp, axis, None, None)."""
    from jax.sharding import PartitionSpec as P
    from repro.sharding.rules import dp_axes

    b, _, d = x.shape
    kvh = a.kv_heads_effective()
    kvh_store = cache["k"].shape[2]
    pos = _posv(pos, b)
    posv = pos[:, None]
    q = linear_apply(p["wq"], x).reshape(b, 1, a.heads_padded, a.head_dim)
    q = apply_rope(q, posv, a.rope_theta)[:, 0]                # (B,H,D)
    k_new = linear_apply(p["wk"], x).reshape(b, 1, kvh, a.head_dim)
    v_new = linear_apply(p["wv"], x).reshape(b, 1, kvh, a.head_dim)
    k_new = apply_rope(k_new, posv, a.rope_theta)
    k_new = _merge_heads(k_new, kvh_store)[:, 0]               # (B,KH,D)
    v_new = _merge_heads(v_new, kvh_store)[:, 0]
    scale = 1.0 / jnp.sqrt(a.head_dim).astype(jnp.float32)
    n_shards = mesh.shape[axis]
    s_global = cache["k"].shape[1]
    s_local = s_global // n_shards
    dp = tuple(a_ for a_ in dp_axes(mesh))
    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)

    def per_shard(q_l, kn, vn, k_l, v_l, pos_l):
        i = jax.lax.axis_index(axis)
        lo = i * s_local

        def upd(c_b, n_b, p_b):
            own = (p_b >= lo) & (p_b < lo + s_local)
            tgt = jnp.clip(p_b - lo, 0, s_local - 1)
            updated = jax.lax.dynamic_update_slice_in_dim(
                c_b, n_b[None].astype(c_b.dtype), tgt, axis=0)
            return jnp.where(own, updated, c_b)

        k_l = jax.vmap(upd)(k_l, kn, pos_l)
        v_l = jax.vmap(upd)(v_l, vn, pos_l)
        bl = q_l.shape[0]
        kpos = lo + jnp.arange(s_local)
        valid = kpos[None, :] <= pos_l[:, None]
        if a.window is not None:
            valid &= kpos[None, :] > pos_l[:, None] - a.window
        g = a.heads_padded // kvh_store
        qg = q_l.reshape(bl, kvh_store, g, a.head_dim)
        s = jnp.einsum("bkgd,btkd->bkgt", qg.astype(jnp.float32),
                       k_l.astype(jnp.float32)) * scale
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1)
        pr = jnp.exp(s - m[..., None])
        pr = jnp.where(valid[:, None, None, :], pr, 0.0)
        l = jnp.sum(pr, axis=-1)
        o = jnp.einsum("bkgt,btkd->bkgd", pr, v_l.astype(jnp.float32))
        m_g = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, axis)
        o_g = jax.lax.psum(o * corr[..., None], axis)
        o_f = o_g / jnp.maximum(l_g, 1e-30)[..., None]
        return o_f.reshape(bl, a.heads_padded * a.head_dim).astype(x.dtype), \
            k_l, v_l

    cache_spec = P(dp_spec, axis, None, None)
    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(dp_spec, None, None), P(dp_spec, None, None),
                  P(dp_spec, None, None), cache_spec, cache_spec,
                  P(dp_spec)),
        out_specs=(P(dp_spec, None), cache_spec, cache_spec),
        check_vma=False)
    o, k_cache, v_cache = fn(q, k_new, v_new, cache["k"], cache["v"], pos)
    y = linear_apply(p["wo"], _mask_pad_heads(o[:, None], a))
    return y, {"k": k_cache, "v": v_cache}


def _mla_decode(p: dict, x: jax.Array, a: AttentionConfig, cache: dict,
                pos: jax.Array) -> tuple[jax.Array, dict]:
    """Absorbed-matmul MLA decode: score against the latent cache directly."""
    from repro import kvcache
    b = x.shape[0]
    h, hd, rr, dc = a.num_heads, a.head_dim, a.rope_head_dim, a.kv_lora_rank
    pos = _posv(pos, b)
    posv = pos[:, None]

    c_new = linear_apply(p["kv_down"], x)                         # (B,1,dc)
    k_pe_new = linear_apply(p["k_rope"], x).reshape(b, 1, 1, rr)
    k_pe_new = apply_rope(k_pe_new, posv, a.rope_theta)[:, :, 0]
    cache = kvcache.decode_write(cache, {"c_kv": c_new, "k_pe": k_pe_new},
                                 pos)
    c_cache, pe_cache = cache["c_kv"], cache["k_pe"]

    qx = linear_apply(p["q_down"], x) if "q_down" in p else x
    q = linear_apply(p["q_up"], qx).reshape(b, 1, h, hd + rr)
    q_nope, q_pe = q[..., :hd], q[..., hd:]
    q_pe = apply_rope(q_pe, posv, a.rope_theta)

    # absorb W_uk into q: (B,1,H,hd) @ (dc,H*hd)->(B,1,H,dc)
    w_uk = p["kv_up_k"]["w"].reshape(dc, h, hd)
    q_abs = jnp.einsum("bshd,chd->bshc", q_nope, w_uk.astype(q_nope.dtype))

    t = c_cache.shape[1]
    valid = jnp.arange(t)[None, :] <= pos[:, None]               # (B,T)
    scale = 1.0 / jnp.sqrt(hd + rr).astype(jnp.float32)
    scores = (jnp.einsum("bshc,btc->bhst", q_abs, c_cache.astype(q_abs.dtype),
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshr,btr->bhst", q_pe, pe_cache.astype(q_pe.dtype),
                           preferred_element_type=jnp.float32)) * scale
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o_lat = jnp.einsum("bhst,btc->bshc", probs, c_cache.astype(x.dtype))
    w_uv = p["kv_up_v"]["w"].reshape(dc, h, hd)
    o = jnp.einsum("bshc,chd->bshd", o_lat, w_uv.astype(o_lat.dtype))
    o = o.reshape(b, 1, h * hd)
    y = linear_apply(p["wo"], o)
    return y, cache
