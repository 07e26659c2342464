"""The one KV-cache implementation: allocation, quantized writes, views.

Caches stay plain pytrees (nested dicts of arrays) so they flow through
jit / lax.scan / tree.map unchanged; this module owns every layout ×
dtype combination so models/transformer.py, models/model.py and
serve/paged.py stop carrying their own copies.

Contiguous node:  {"k": (B,S,KH,D), "v": (B,S,KH,D)
                   [, "k_scale": (B,S,KH) f32, "v_scale": (B,S,KH) f32]}
MLA node:         {"c_kv": (B,S,dc), "k_pe": (B,S,rr)}          (bf16)
Paged node:       {"k_pages"/"v_pages": (N,KH,page,D)  (head-major: a
                   page's (page, D) tile per kv head is what the paged
                   kernels stream; kernels/paged_attention),
                   [, "k_scales"/"v_scales": (N,KH) f32]
                   "block_table": (n_slots, pages_per_slot) int32}

Inside the serving programs' layer loop (``models/transformer``) a paged
node holds the WHOLE layer stack of pools, (L,N,KH,page,D) and (L,N,KH)
scales, beside the layer's own block table and ``"layer"``, an int32
scalar: the writes below and the paged kernels address the layer's pool
in place at that index, so no layer's pool is sliced out or copied.

Quantized scales are fp32 amax scales: per (batch, position, kv_head)
for contiguous caches, per (page, kv_head) for paged pools.  Paged page
scales are *running* maxima — a decode write that raises a page's amax
requantizes the page in place (``quant.requantize``; factor ≤ 1, so
int8 never re-clips).  Page 0 is the null page (serve/paged.py): free
slots' writes collide there and reads are masked by per-slot lengths.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import AttentionConfig
from repro.kvcache.quant import (_qmax_of, quantize, quantize_with_scale,
                                 requantize)
from repro.kvcache.spec import CacheSpec


# ---------------------------------------------------------------------------
# Allocation


def alloc_contiguous(spec: CacheSpec, a: AttentionConfig, batch: int,
                     max_len: int) -> dict:
    if a.kind == "mla":
        return {
            "c_kv": jnp.zeros((batch, max_len, a.kv_lora_rank),
                              spec.store_dtype_for(a)),
            "k_pe": jnp.zeros((batch, max_len, a.rope_head_dim),
                              spec.store_dtype_for(a)),
        }
    kvh = spec.stored_kv_heads(a)
    c = {
        "k": jnp.zeros((batch, max_len, kvh, a.head_dim), spec.store_dtype),
        "v": jnp.zeros((batch, max_len, kvh, a.head_dim), spec.store_dtype),
    }
    if spec.quantized:
        c["k_scale"] = jnp.zeros((batch, max_len, kvh), jnp.float32)
        c["v_scale"] = jnp.zeros((batch, max_len, kvh), jnp.float32)
    return c


def alloc_paged(spec: CacheSpec, a: AttentionConfig, n_slots: int,
                n_pages: int, pages_per_slot: int) -> dict:
    """Page pools shared by all slots + a per-slot block table (replicated
    into every layer's cache dict so decode stays a pure function of
    (params, token, cache, pos))."""
    if a.kind == "mla":
        raise NotImplementedError("paged decode: standard attention only")
    kvh = spec.stored_kv_heads(a)
    page = spec.page_size
    c = {
        "k_pages": jnp.zeros((n_pages, kvh, page, a.head_dim),
                             spec.store_dtype),
        "v_pages": jnp.zeros((n_pages, kvh, page, a.head_dim),
                             spec.store_dtype),
        "block_table": jnp.zeros((n_slots, pages_per_slot), jnp.int32),
    }
    if spec.quantized:
        c["k_scales"] = jnp.zeros((n_pages, kvh), jnp.float32)
        c["v_scales"] = jnp.zeros((n_pages, kvh), jnp.float32)
    return c


# ---------------------------------------------------------------------------
# Contiguous writes


def prefill_write(cache: dict, updates: dict) -> dict:
    """Slab-write full-sequence values at position 0.  ``updates`` maps
    node keys ("k"/"v" or "c_kv"/"k_pe") to (B, s, ...) arrays; keys with
    a ``<key>_scale`` sibling in the cache are quantized on the way in."""
    out = dict(cache)
    for name, new in updates.items():
        tgt = cache[name]
        sk = name + "_scale"
        if sk in cache:
            q, s = quantize(new, tgt.dtype, axis=-1)
            out[name] = jax.lax.dynamic_update_slice(tgt, q, (0,) * tgt.ndim)
            out[sk] = jax.lax.dynamic_update_slice(
                cache[sk], s, (0,) * cache[sk].ndim)
        else:
            out[name] = jax.lax.dynamic_update_slice(
                tgt, new.astype(tgt.dtype), (0,) * tgt.ndim)
    return out


def _scatter_rows(tgt: jax.Array, new: jax.Array, pos: jax.Array):
    """Per-batch scatter of (B, 1, ...) ``new`` into (B, S, ...) at pos (B,)."""
    def one(c, n, p):
        idx = (p,) + (0,) * (c.ndim - 1)
        return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), idx)
    return jax.vmap(one)(tgt, new, pos)


def decode_write(cache: dict, updates: dict, pos: jax.Array) -> dict:
    """One-token write at per-batch positions ``pos`` (B,)."""
    out = dict(cache)
    for name, new in updates.items():
        sk = name + "_scale"
        if sk in cache:
            q, s = quantize(new, cache[name].dtype, axis=-1)
            out[name] = _scatter_rows(cache[name], q, pos)
            out[sk] = _scatter_rows(cache[sk], s, pos)
        else:
            out[name] = _scatter_rows(cache[name], new, pos)
    return out


def kv_views(cache: dict):
    """(k, v, k_scale, v_scale) — scales are None for bf16 caches.
    Attention folds the scales into its contractions (no dequantized
    copy of the cache is materialized)."""
    return (cache["k"], cache["v"],
            cache.get("k_scale"), cache.get("v_scale"))


# ---------------------------------------------------------------------------
# Paged writes


def constrain_paged_pools(cache: dict) -> dict:
    """Pin paged pools to their serving sharding: pages (…,KH,page,D)
    kv-head-sharded over "model", scale tensors (…,KH) likewise, block
    table replicated.  Called after every paged write so the pools carried
    through the decode scan / chunk loop never drift to replicated (a
    single resharding all-gather would dwarf the attention collectives).
    Degrades to a no-op off-mesh or when KH doesn't divide
    (``maybe_constrain``)."""
    from repro.sharding.ctx import maybe_constrain
    out = dict(cache)
    for name in ("k_pages", "v_pages"):
        if name in out:
            x = out[name]
            axes = (None,) * (x.ndim - 3) + ("model", None, None)
            out[name] = maybe_constrain(x, *axes)
    for name in ("k_scales", "v_scales"):
        if name in out:
            x = out[name]
            axes = (None,) * (x.ndim - 1) + ("model",)
            out[name] = maybe_constrain(x, *axes)
    return out


def paged_views(cache: dict):
    """(k_pages, v_pages, k_scales, v_scales, block_table) — scales are
    None for bf16 pools."""
    return (cache["k_pages"], cache["v_pages"],
            cache.get("k_scales"), cache.get("v_scales"),
            cache["block_table"])


def _lead(cache: dict) -> tuple:
    """Index prefix that picks the node's layer out of stacked pools and
    scales: ``(layer,)`` inside the layer loop, ``()`` for one layer's."""
    return (cache["layer"],) if "layer" in cache else ()


def _rows_at(lead: tuple, pidx, off, kv_heads: int) -> tuple:
    """Index of the (…, KH) head rows at page ``pidx``, offset ``off``
    (same shapes) of a pool: one D-long row per kv head.  A head-major
    pool's (page, D) tile keeps D minor, so each update is a row of the
    layout the paged kernels read, and the scatter runs in that layout;
    a (KH, D) window per token would make XLA re-lay the pool with KH
    second-minor and copy it back for the kernel."""
    kh = jnp.arange(kv_heads)
    return lead + (pidx[..., None], kh, off[..., None])


def _quant_token_write(pages, scales, pidx, off, new, lead=()):
    """Append one quantized token per slot at (pidx, off), growing the
    page's running amax scale and requantizing the page when it grows.
    pages: (N,KH,page,D); scales: (N,KH); new: (S,KH,D) bf16; stacked
    (L,…) pools and scales with ``lead`` = (layer,).

    A write at offset 0 RESETS the page's scale instead of growing it: a
    page's first token is always written at offset 0 (allocations, lazy
    growth, and prefill chunks are page-aligned), so this is where a
    reused page sheds its previous occupant's amax — entirely on device,
    with no host round trip at admission/retire (the prefill scatter
    resets its touched pages the same way).

    Steady state (no real page's amax grew — after a page's first few
    tokens the running max ratchets flat) takes the O(row) fast path; the
    full-page gather→requantize→rewrite runs only under ``lax.cond`` when
    a scale actually grows.  Null-page growth and offset-0 resets are
    excluded from the predicate: their pages hold only garbage beyond the
    written token, masked by per-slot lengths, so nothing needs
    requantizing."""
    s_n = pidx.shape[0]
    qmax = _qmax_of(pages.dtype)
    amax = jnp.max(jnp.abs(new.astype(jnp.float32)), axis=-1)    # (S,KH)
    old = scales[lead + (pidx,)]                                 # (S,KH)
    fresh = (off == 0)[:, None]                                  # (S,1)
    ns = jnp.where(fresh, amax / qmax, jnp.maximum(old, amax / qmax))
    tok = quantize_with_scale(new, ns, pages.dtype, axis=-1)     # (S,KH,D)
    # old == 0 (fresh/reset page) also skips the rescale: everything in
    # the page beyond the written token is masked by the slot's length
    # until overwritten, so stale contents are never dequantized
    grew = jnp.any((ns > old) & (old > 0) & ~fresh & (pidx != 0)[:, None])

    def rescale_pages(pages):
        pg = pages[lead + (pidx,)]                          # (S,KH,page,D)
        pg = requantize(pg, old[:, :, None], ns[:, :, None], axis=-1)
        pg = pg.at[jnp.arange(s_n), :, off].set(tok)
        # duplicate pidx entries only ever alias the null page (free
        # slots); whichever garbage write wins there is masked away
        return pages.at[lead + (pidx,)].set(pg)

    def append_only(pages):
        return pages.at[_rows_at(lead, pidx, off, tok.shape[1])].set(tok)

    pages = jax.lax.cond(grew, rescale_pages, append_only, pages)
    return pages, scales.at[lead + (pidx,)].set(ns)


def paged_write_batch(cache: dict, positions: jax.Array,
                      k_new: jax.Array, v_new: jax.Array,
                      mask: jax.Array | None = None) -> dict:
    """Write one token per slot: k_new/v_new (S, KH, D) land at logical
    position ``positions[s]`` of each slot's pages.  Slots whose block-
    table row is unallocated resolve to the null page.  ``mask`` (S,)
    bool reroutes masked-out slots' writes to the null page (the
    speculative-decode commit replays only ACCEPTED tokens this way —
    rejected drafts never touch a live page, so rollback is exact even
    for quantized pools whose scales a rejected tail could have grown)."""
    kp, vp, ks, vs, bt = paged_views(cache)
    lead = _lead(cache)
    page = kp.shape[-2]
    s_n = positions.shape[0]
    lpage = jnp.minimum(positions // page, bt.shape[1] - 1)      # pad-safe
    pidx = bt[jnp.arange(s_n), lpage]                            # (S,)
    off = positions % page
    if mask is not None:
        pidx = jnp.where(mask, pidx, 0)
        off = jnp.where(mask, off, 0)
    out = dict(cache)
    if ks is None:
        at = _rows_at(lead, pidx, off, kp.shape[-3])
        out["k_pages"] = kp.at[at].set(k_new.astype(kp.dtype))
        out["v_pages"] = vp.at[at].set(v_new.astype(vp.dtype))
        return out
    out["k_pages"], out["k_scales"] = _quant_token_write(kp, ks, pidx, off,
                                                         k_new, lead)
    out["v_pages"], out["v_scales"] = _quant_token_write(vp, vs, pidx, off,
                                                         v_new, lead)
    return out


def _quant_scatter(pages, scales, pidx, off, rows, amax, lead=()):
    """Scatter a prefill's rows into pages with fresh per-page scales.
    pidx/off: (B,T); rows: (B,T,KH,D); amax: (B,T,KH), zeroed at
    invalid (padding) positions; ``lead`` as in ``_quant_token_write``."""
    qmax = _qmax_of(pages.dtype)
    # reset-then-max: scattered pages get exactly this prefill's amax
    # (stale scales from a released slot would otherwise linger)
    scales = scales.at[lead + (pidx,)].set(0.0)
    scales = scales.at[lead + (pidx,)].max(amax / qmax)
    per_tok = scales[lead + (pidx,)]                             # (B,T,KH)
    q = quantize_with_scale(rows, per_tok, pages.dtype, axis=-1)
    return pages.at[_rows_at(lead, pidx, off, q.shape[-2])].set(q), scales


def paged_scatter_prefill(cache: dict, slot_ids: jax.Array,
                          lengths: jax.Array, k_rows: jax.Array,
                          v_rows: jax.Array,
                          starts: jax.Array | None = None) -> dict:
    """Scatter a batched prefill's contiguous K/V into pages.

    k_rows/v_rows: (B, T, KVH, D) — row b's tokens [0, lengths[b]) go to
    slot ``slot_ids[b]``'s pages at logical positions ``starts[b] +
    [0, lengths[b])`` (``starts`` defaults to 0 — classic whole-prompt
    admission); padding tokens (and rows with length 0) are routed to the
    null page.  One scatter per array, no host loop.

    Non-zero ``starts`` must be page-aligned: the quantized path resets
    every touched page's scale to this scatter's amax (a page's scale
    lifecycle is tied to its first write at offset 0), so a chunk that
    started mid-page would clobber the previous chunk's scale.  The
    scheduler's chunked prefill enforces chunk % page_size == 0.
    """
    kp, vp, ks, vs, bt = paged_views(cache)
    lead = _lead(cache)
    b, t = k_rows.shape[:2]
    page = kp.shape[-2]
    tpos = jnp.arange(t)[None, :]                                # (1,T)
    if starts is None:
        starts = jnp.zeros((b,), jnp.int32)
    valid = tpos < lengths[:, None]                              # (B,T)
    apos = starts[:, None] + tpos                                # (B,T)
    lpage = jnp.minimum(apos // page, bt.shape[1] - 1)           # pad-safe
    pidx = bt[slot_ids[:, None], lpage]                          # (B,T)
    pidx = jnp.where(valid, pidx, 0)
    off = jnp.where(valid, apos % page, 0)
    out = dict(cache)
    if ks is None:
        at = _rows_at(lead, pidx, off, kp.shape[-3])
        out["k_pages"] = kp.at[at].set(k_rows.astype(kp.dtype))
        out["v_pages"] = vp.at[at].set(v_rows.astype(vp.dtype))
        return out
    vm = valid[..., None].astype(jnp.float32)                    # (B,T,1)
    k_amax = jnp.max(jnp.abs(k_rows.astype(jnp.float32)), axis=-1) * vm
    v_amax = jnp.max(jnp.abs(v_rows.astype(jnp.float32)), axis=-1) * vm
    out["k_pages"], out["k_scales"] = _quant_scatter(kp, ks, pidx, off,
                                                     k_rows, k_amax, lead)
    out["v_pages"], out["v_scales"] = _quant_scatter(vp, vs, pidx, off,
                                                     v_rows, v_amax, lead)
    return out


# ---------------------------------------------------------------------------
# Accounting


def pool_bytes(cache) -> int:
    """Total bytes of KV storage (pages/slabs + scale tensors) in a cache
    pytree; block tables excluded (bookkeeping, not KV).  Works on real
    arrays and ShapeDtypeStructs alike."""
    import numpy as np
    tot = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        if "block_table" in jax.tree_util.keystr(path):
            continue
        tot += int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
    return int(tot)
