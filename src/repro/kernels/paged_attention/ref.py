"""Pure-jnp oracle for paged decode attention (GQA, per-slot lengths).

The reference gathers every slot's pages into a contiguous copy — exactly
the memory traffic the Pallas kernel avoids — and runs a masked fp32
softmax.  Fully-masked slots (length 0, i.e. a free engine slot) return
zeros, matching the kernel's "no live page ever touched" behaviour; a
plain ``jax.nn.softmax`` would return a uniform distribution there.

Quantized pools (int8 / fp8, ``repro.kvcache``) pass per-page-per-kv-head
fp32 amax scales; the oracle dequantizes the gathered pages up front —
the readable counterpart of the kernel's fused dequant.

``paged_prefix_extend_ref`` is additionally the surviving home of the
eager chunked-prefill gather: models/attention.py used to carry its own
copy of this full-horizon gather + dense softmax; that hot path now runs
the fused kernel and falls back here only through the ops dispatch.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gather_context(k_pages, v_pages, block_table, k_scales, v_scales):
    """Every slot's pages, dequantized, as contiguous fp32 (S, P·page, KH, D)
    context — head-major pools (N, KH, page, D) turned position-major."""
    s_n, p_n = block_table.shape
    _, kh, page, d = k_pages.shape

    def one(pages, scales):
        x = pages[block_table].astype(jnp.float32)       # (S,P,KH,page,D)
        if scales is not None:
            x = x * scales[block_table][..., None, None]
        return x.transpose(0, 1, 3, 2, 4).reshape(s_n, p_n * page, kh, d)

    return one(k_pages, k_scales), one(v_pages, v_scales)


def paged_prefix_extend_ref(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, block_table: jax.Array,
                            prefix_lens: jax.Array, chunk_k: jax.Array,
                            chunk_v: jax.Array, widths: jax.Array,
                            k_scales: Optional[jax.Array] = None,
                            v_scales: Optional[jax.Array] = None,
                            ) -> jax.Array:
    """Multi-query prefix-extend attention oracle — the eager full-
    horizon gather the fused kernel replaces (this is the old
    ``attention_prefill_paged`` gather, kept as the reference and the
    off-kernel fallback).

    q: (S, W, H, D) — W query positions per slot, query ``w`` sitting at
    logical position ``prefix_lens[s] + w``; k_pages/v_pages hold the
    cached prefix (positions < prefix_lens[s] are attended; anything the
    pages hold at or past the prefix — e.g. a prefill chunk's own
    just-scattered rows — is masked in favour of the fresh chunk).  The
    chunk's own K/V (``chunk_k``/``chunk_v``: (S, W, KH, D), fresh — for
    spec verify deliberately NOT yet in the pages: write-after-accept,
    see repro.spec) is attended causally in-chunk: query ``w`` sees
    chunk keys ``j <= w`` with ``j < widths[s]``.  Queries at ``w >=
    widths[s]`` are padding; their outputs are garbage the engine masks.
    -> (S, W, H, D).
    """
    lengths = prefix_lens
    s_n, w_n, h, d = q.shape
    _, kh, page, _ = k_pages.shape
    p_n = block_table.shape[1]
    g = h // kh
    k, v = _gather_context(k_pages, v_pages, block_table, k_scales, v_scales)
    t = p_n * page
    qg = q.reshape(s_n, w_n, kh, g, d).astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    s_ctx = jnp.einsum("swkgd,stkd->skgwt", qg, k) * scale
    ctx_ok = jnp.arange(t)[None, :] < lengths[:, None]           # (S,T)
    s_ctx = jnp.where(ctx_ok[:, None, None, None, :], s_ctx, NEG_INF)
    s_chk = jnp.einsum("swkgd,sjkd->skgwj", qg,
                       chunk_k.astype(jnp.float32)) * scale
    jj = jnp.arange(w_n)
    chk_ok = (jj[None, :] <= jj[:, None])[None] \
        & (jj[None, None, :] < widths[:, None, None])            # (S,W,W)
    s_chk = jnp.where(chk_ok[:, None, None], s_chk, NEG_INF)

    s_all = jnp.concatenate([s_ctx, s_chk], axis=-1)
    ok_all = jnp.concatenate(
        [jnp.broadcast_to(ctx_ok[:, None, :], (s_n, w_n, t)),
         chk_ok], axis=-1)                                       # (S,W,T+W)
    m = jnp.max(s_all, axis=-1, keepdims=True)
    p = jnp.exp(s_all - m) * ok_all[:, None, None]
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(l, 1e-30)
    o = jnp.einsum("skgwt,stkd->swkgd", p[..., :t], v) \
        + jnp.einsum("skgwj,sjkd->swkgd", p[..., t:],
                     chunk_v.astype(jnp.float32))
    return o.reshape(s_n, w_n, h, d).astype(q.dtype)


def paged_attention_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                        block_table: jax.Array, lengths: jax.Array,
                        k_scales: Optional[jax.Array] = None,
                        v_scales: Optional[jax.Array] = None) -> jax.Array:
    """q: (S,H,D); k_pages/v_pages: (N,KH,page,D); block_table: (S,P) int32;
    lengths: (S,) int32 — keys at kpos < lengths[s] are live;
    k_scales/v_scales: (N,KH) fp32 for quantized pools -> (S,H,D)."""
    s_n, h, d = q.shape
    _, kh, page, _ = k_pages.shape
    p_n = block_table.shape[1]
    g = h // kh
    k, v = _gather_context(k_pages, v_pages, block_table, k_scales, v_scales)
    qg = q.reshape(s_n, kh, g, d)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    scores = jnp.einsum("skgd,stkd->skgt", qg.astype(jnp.float32),
                        k) * scale
    valid = jnp.arange(p_n * page)[None, :] < lengths[:, None]  # (S,T)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m) * valid[:, None, None, :]
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("skgt,stkd->skgd", p / jnp.maximum(l, 1e-30), v)
    return o.reshape(s_n, h, d).astype(q.dtype)
