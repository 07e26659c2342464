"""Pallas TPU paged decode attention (flash-decoding over a paged KV cache).

One launch covers EVERY active slot: grid = (slots, kv_heads, page_blocks)
with the page axis minor-most, so TPU walks a slot's pages sequentially and
the online-softmax running state (m, l, acc) lives in VMEM scratch across
page steps — the flash-decoding recurrence of serve/decode_attn.py, but per
page instead of per shard.

Pages are STREAMED, never gathered: the layer index, the block table and
per-slot lengths ride in as scalar-prefetch operands
(``PrefetchScalarGridSpec``), and the K/V BlockSpec index maps look the
tile up as ``(layer, block_table[slot, page_block], kv_head)`` — each
grid step DMAs exactly one (page_size, head_dim) tile from HBM.  Pools
are HEAD-MAJOR and stacked over layers, (L, N, KH, page, D): the tile is
then the pool's own last two dims, the only (page, D) block the TPU
compiler accepts for every page size and dtype (a page-major
(N, page, KH, D) pool would need a (page, 1, D) block, whose
second-minor dim of 1 Mosaic refuses).  The serving programs' layer loop
carries the whole stack and passes each layer's index, so no layer's
pool is ever sliced out, re-laid or copied for the kernel
(``models/transformer.stack_forward``); a 4-D (N, KH, page, D) pool is
a one-layer stack at layer 0.

GQA is handled like kernels/flash_attention: the kv-head grid axis selects
one stored head, the q block carries that head's ``group`` query heads, and
repeated KV heads are never materialized.  Pages past a slot's length are
skipped with ``pl.when`` (their grid steps fetch the null page but run no
compute); partially-filled last pages are masked via a broadcasted iota
against the slot's length.  fp32 accumulation throughout.

Quantized pools (int8 / fp8-e4m3, ``repro.kvcache``): the layer's
per-page-per-kv-head fp32 amax scales ride in as two extra
scalar-prefetch operands, flattened to 1-D (N·KH,) — a 2-D (N, KH) SMEM
array pads every row to 512 B and a pool of a few thousand pages
overflows the 1 MiB SMEM — and dequant is FUSED into the online-softmax
inner loop — the K scale folds into the score scale (``(q·k_q)·s·k_s``)
and the V scale folds into the p·v accumulation (``(p·v_q)·v_s``), so no
dequantized page is ever materialized in HBM or VMEM.  Streaming int8
pages halves the decode HBM traffic vs bf16.

Two kernels share this machinery: ``_paged_kernel`` is single-query
decode (one token per slot), and ``_prefix_extend_kernel`` is the
width-parameterized multi-query generalization — W queries per slot
against the paged prefix plus a fresh causal chunk — instantiated at
W = draft_k + 1 for speculative verify and W = chunk width for chunked
prefill continuation (one entry point for both; see ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Scoped VMEM for the prefix-extend kernel.  Its chunk step holds a
# (W·G, W) f32 score tile beside (W·G, D) q/out/accumulator tiles, and
# XLA may place the kernel's output in VMEM too: a 512-token chunk with
# G = 6 and f32 queries inside a sharded serving program needed 20.8 MB,
# past the compiler's 16 MiB default.  A v5e core has 128 MiB of VMEM.
PREFIX_EXTEND_VMEM_BYTES = 48 * 2 ** 20


def _paged_kernel(*refs, scale: float, page_size: int, n_page_blocks: int,
                  n_kv_heads: int, quantized: bool):
    if quantized:
        (_, bt_ref, len_ref, ks_ref, vs_ref,
         q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr) = refs
    else:
        (_, bt_ref, len_ref,
         q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr) = refs
    s_i = pl.program_id(0)
    k_i = pl.program_id(1)
    p_i = pl.program_id(2)

    @pl.when(p_i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[s_i]
    page_start = p_i * page_size

    @pl.when(page_start < length)
    def _body():
        q = q_ref[...].astype(jnp.float32)                   # (G, D)
        k = k_ref[...].astype(jnp.float32)                   # (page, D)
        v = v_ref[...].astype(jnp.float32)
        if quantized:
            flat = bt_ref[s_i, p_i] * n_kv_heads + k_i
            k_s = ks_ref[flat]                               # fp32 scalars
            v_s = vs_ref[flat]
            sc = scale * k_s                                 # fused K dequant
        else:
            v_s = None
            sc = scale
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sc
        kpos = page_start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)

        m_prev = m_scr[...]                                   # (G, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                                # (G, page)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, 1, keepdims=True)
        pv = jax.lax.dot(p, v, preferred_element_type=jnp.float32)
        if quantized:
            pv = pv * v_s                                     # fused V dequant
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new

    @pl.when(p_i == n_page_blocks - 1)
    def _flush():
        # length-0 slots (free engine slots) never ran _body: l is 0 and
        # the flush writes zeros, matching ref.py's masked softmax.
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


def _prefix_extend_kernel(*refs, scale: float, page_size: int,
                          n_page_blocks: int, n_kv_heads: int, group: int,
                          quantized: bool):
    """Width-parameterized prefix-extend attention: W query positions per
    slot against the slot's paged prefix plus a fresh causal chunk.  Grid
    = (slots, kv_heads, page_blocks + 1); the first ``n_page_blocks``
    steps stream the cached prefix exactly like ``_paged_kernel`` (every
    query sees the whole prefix — uniform mask over positions <
    prefix_lens[slot]), and the FINAL step attends the chunk's own fresh
    K/V causally (query w sees chunk keys j <= w, j < widths[slot]).
    Online-softmax state is (W·G, ·) so the chunk's queries share one
    scratch walk.

    One kernel, two instantiations: speculative verify runs it at
    W = draft_k + 1 (prefix = committed lengths, chunk = draft K/V held
    OUT of the pages for write-after-accept), and chunked prefill runs it
    at W = chunk width (prefix = the chunk's page-aligned start, chunk =
    the chunk's own K/V — already scattered into the pages but attended
    from the fresh activations).  Pages past the prefix are skipped with
    ``pl.when``, so a chunk's cost is O(prefix + W), not O(page horizon):
    that is what replaces the eager full-horizon gather of the old
    ``attention_prefill_paged`` (now the oracle in ref.py)."""
    if quantized:
        (_, bt_ref, len_ref, wid_ref, ks_ref, vs_ref,
         q_ref, k_ref, v_ref, ck_ref, cv_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        (_, bt_ref, len_ref, wid_ref,
         q_ref, k_ref, v_ref, ck_ref, cv_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    s_i = pl.program_id(0)
    k_i = pl.program_id(1)
    p_i = pl.program_id(2)

    @pl.when(p_i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[s_i]
    wid = wid_ref[s_i]

    def _online(s, v, v_s):
        """One online-softmax update with scores s: (W·G, cols)."""
        m_prev = m_scr[...]                                   # (W·G, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, 1, keepdims=True)
        pv = jax.lax.dot(p, v, preferred_element_type=jnp.float32)
        if v_s is not None:
            pv = pv * v_s
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new

    @pl.when((p_i < n_page_blocks) & (p_i * page_size < length))
    def _prefix_body():
        q = q_ref[...].astype(jnp.float32)                   # (W·G, D)
        k = k_ref[...].astype(jnp.float32)                   # (page, D)
        v = v_ref[...].astype(jnp.float32)
        if quantized:
            flat = bt_ref[s_i, p_i] * n_kv_heads + k_i
            k_s = ks_ref[flat]
            v_s = vs_ref[flat]
            sc = scale * k_s
        else:
            v_s = None
            sc = scale
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sc
        kpos = p_i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        _online(s, v, v_s)

    @pl.when((p_i == n_page_blocks) & (wid > 0))
    def _chunk_body():
        q = q_ref[...].astype(jnp.float32)                   # (W·G, D)
        ck = ck_ref[...].astype(jnp.float32)                 # (W, D)
        cv = cv_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, ck, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        w_of_row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
        j_of_col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((j_of_col <= w_of_row) & (j_of_col < wid), s, NEG_INF)
        _online(s, cv, None)

    @pl.when(p_i == n_page_blocks)
    def _flush():
        # width-0 slots never ran a body: l stays 0 and the flush writes
        # zeros, matching ref.py's masked softmax
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


def _tile_spec(rows: int, d: int) -> pl.BlockSpec:
    """(rows, D) tile of a (S, KH, rows, D) operand at grid (slot, kv head)."""
    return pl.BlockSpec((None, None, rows, d),
                        lambda s, k, p, *_: (s, k, 0, 0))


def _page_spec(page: int, d: int, p_n: int) -> pl.BlockSpec:
    """One (page, D) tile of a stacked head-major (L, N, KH, page, D)
    pool: layer ``layer[0]``, physical page ``block_table[slot,
    page_block]``.  Index maps see every scalar-prefetch operand after the
    grid coordinates; only the layer and the block table are consulted.
    The prefix-extend grid runs one step past the table (its chunk step),
    hence the clamp."""
    return pl.BlockSpec(
        (None, None, None, page, d),
        lambda s, k, p, layer, bt, *_: (
            layer[0], bt[s, jnp.minimum(p, p_n - 1)], k, 0, 0))


def _stacked(k_pages, v_pages, k_scales, v_scales, layer):
    """Pools as a layer stack (L, N, KH, page, D) and the layer's scales:
    a 4-D pool is a one-layer stack, addressed at layer 0.  Quantized
    scales (L, N, KH) are sliced to the layer's (N, KH): the whole stack's
    would overflow SMEM, and the slice is a few KiB."""
    if k_pages.ndim == 4:
        k_pages, v_pages = k_pages[None], v_pages[None]
        if k_scales is not None:
            k_scales, v_scales = k_scales[None], v_scales[None]
    if layer is None:
        assert k_pages.shape[0] == 1, "a stacked pool needs its layer index"
        layer = 0
    layer = jnp.asarray(layer, jnp.int32)
    if k_scales is not None:
        k_scales = jax.lax.dynamic_index_in_dim(k_scales, layer, 0, False)
        v_scales = jax.lax.dynamic_index_in_dim(v_scales, layer, 0, False)
    return k_pages, v_pages, k_scales, v_scales, layer.reshape(1)


def _prefetch(layer, block_table, per_slot, k_scales, v_scales):
    """Scalar-prefetch operands: the layer index (1,), the block table,
    the per-slot int32 vectors, and for quantized pools the layer's
    (N, KH) scales flattened to (N·KH,) (1-D SMEM does not pad rows)."""
    ops = [layer, block_table.astype(jnp.int32)] + [
        x.astype(jnp.int32) for x in per_slot]
    if k_scales is not None:
        ops += [k_scales.astype(jnp.float32).reshape(-1),
                v_scales.astype(jnp.float32).reshape(-1)]
    return ops


def _check_pools(q_heads, k_pages, k_scales):
    kh, page = k_pages.shape[-3:-1]
    assert q_heads % kh == 0, (q_heads, kh)
    quantized = k_scales is not None
    assert quantized == (k_pages.dtype not in (jnp.bfloat16, jnp.float32)), \
        (k_pages.dtype, quantized)
    return kh, page, quantized


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefix_extend_pallas(q, k_pages, v_pages, block_table,
                               prefix_lens, chunk_k, chunk_v, widths,
                               k_scales=None, v_scales=None, layer=None, *,
                               interpret: bool = False) -> jax.Array:
    """q: (S,W,H,D) — W query positions per slot at logical positions
    ``prefix_lens[s] + [0, W)``; chunk_k/chunk_v: (S,W,KH,D) fresh K/V
    attended causally up to ``widths[s]``; everything else (pools, scales,
    ``layer``) as :func:`paged_attention_pallas` -> (S,W,H,D).  Spec
    verify calls this at W = k+1 (prefix = committed lengths), chunked
    prefill at W = chunk width (prefix = the chunk's page-aligned
    start)."""
    s_n, w_n, h, d = q.shape
    kh, page, quantized = _check_pools(h, k_pages, k_scales)
    g = h // kh
    p_n = block_table.shape[1]
    scale = 1.0 / (d ** 0.5)
    # (S,W,H,D) -> (S,KH,W·G,D): row r of a slot/kv-head tile is query
    # w = r // G, query head r % G
    q4 = q.reshape(s_n, w_n, kh, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(s_n, kh, w_n * g, d)
    # chunk K/V head-major like the pools: a (W, D) tile per kv head
    ck = chunk_k.transpose(0, 2, 1, 3)
    cv = chunk_v.transpose(0, 2, 1, 3)
    k_pages, v_pages, k_scales, v_scales, layer = _stacked(
        k_pages, v_pages, k_scales, v_scales, layer)
    prefetch = _prefetch(layer, block_table, (prefix_lens, widths),
                         k_scales, v_scales)
    kv_spec = _page_spec(page, d, p_n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(s_n, kh, p_n + 1),
        in_specs=[_tile_spec(w_n * g, d), kv_spec, kv_spec,
                  _tile_spec(w_n, d), _tile_spec(w_n, d)],
        out_specs=_tile_spec(w_n * g, d),
        scratch_shapes=[
            pltpu.VMEM((w_n * g, 1), jnp.float32),
            pltpu.VMEM((w_n * g, 1), jnp.float32),
            pltpu.VMEM((w_n * g, d), jnp.float32),
        ])
    out = pl.pallas_call(
        functools.partial(_prefix_extend_kernel, scale=scale, page_size=page,
                          n_page_blocks=p_n, n_kv_heads=kh, group=g,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_n, kh, w_n * g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=PREFIX_EXTEND_VMEM_BYTES),
        interpret=interpret,
        name="paged_prefix_extend_pallas",
    )(*prefetch, q4, k_pages, v_pages, ck, cv)
    return out.reshape(s_n, kh, w_n, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(s_n, w_n, h, d)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_pallas(q, k_pages, v_pages, block_table, lengths,
                           k_scales=None, v_scales=None, layer=None, *,
                           interpret: bool = False) -> jax.Array:
    """q: (S,H,D); k_pages/v_pages: (L,N,KH,page,D) layer stacks, read at
    layer ``layer`` (int32 scalar), or (N,KH,page,D) one-layer pools
    (``layer`` None or 0); block_table: (S,P) int32; lengths: (S,) int32
    -> (S,H,D).  Quantized pools additionally take k_scales/v_scales:
    (L,N,KH) — (N,KH) beside a 4-D pool — fp32 per-page-per-kv-head amax
    scales."""
    s_n, h, d = q.shape
    kh, page, quantized = _check_pools(h, k_pages, k_scales)
    g = h // kh
    p_n = block_table.shape[1]
    scale = 1.0 / (d ** 0.5)
    k_pages, v_pages, k_scales, v_scales, layer = _stacked(
        k_pages, v_pages, k_scales, v_scales, layer)
    prefetch = _prefetch(layer, block_table, (lengths,), k_scales, v_scales)
    kv_spec = _page_spec(page, d, p_n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(s_n, kh, p_n),
        in_specs=[_tile_spec(g, d), kv_spec, kv_spec],
        out_specs=_tile_spec(g, d),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ])
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, page_size=page,
                          n_page_blocks=p_n, n_kv_heads=kh,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_n, kh, g, d), q.dtype),
        interpret=interpret,
        name="paged_attention_pallas",
    )(*prefetch, q.reshape(s_n, kh, g, d), k_pages, v_pages)
    return out.reshape(s_n, h, d)
