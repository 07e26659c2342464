"""Public ops: paged decode + prefix-extend attention, kernel/oracle
dispatch, and the mesh-sharded (tensor-parallel) wrappers.

bf16/fp32 pools run the plain kernels; int8/fp8 pools (with their
per-page-per-kv-head scales from ``repro.kvcache``) run the fused-dequant
variants.  On TPU the kernels compile; on CPU they run in interpret mode,
so the engine tests cover the same kernel bodies; any other backend is
refused (``repro.kernels.interpret_mode``).

``paged_prefix_extend_attention`` is the ONE multi-query entry point:
speculative verify (W = draft_k + 1, prefix = committed lengths) and
chunked prefill continuation (W = chunk width, prefix = the chunk's
page-aligned start) both dispatch through it, so the two instantiations
can never drift.

Sharded serving (``mesh=`` + ``tp_impl``): both entry points accept a
mesh with a ``"model"`` axis.  Under ``tp_impl="kv_shard"`` the KV pools
and scale tensors are sharded BY KV HEAD over that axis and the q/output
head dim is split to match (q heads are kv-head-major, so contiguous
head chunks align with kv-head chunks whenever both divide); each shard
then runs the identical kernel on its local head slice inside
``shard_map`` — block tables / lengths / widths replicated, and NO
full-horizon KV ever crosses the interconnect (the per-head partial
outputs combine downstream via the wo row-shard's psum).
``tp_impl="gather"`` is the naive output-all-gather TP baseline: the
same shard_map with every spec replicated, which forces jit to
all-gather the full pools into each shard every step — kept only so the
collective-byte win is measurable (benchmarks/serving_throughput.py
``--sharded``).  Head counts the axis does not divide degrade to the
gather path.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import interpret_mode
from repro.kernels.paged_attention.ref import (paged_attention_ref,
                                               paged_prefix_extend_ref)


def _model_size(mesh, axis: str) -> int:
    if mesh is None:
        return 1
    return int(dict(mesh.shape).get(axis, 1))


def _shard_axis(tp_impl: str, m: int, heads: int, kv_heads: int,
                axis: str) -> Optional[str]:
    """The mesh axis to split the head dims over, or None (replicate —
    the naive gather baseline / non-dividing fallback)."""
    if tp_impl == "kv_shard" and heads % m == 0 and kv_heads % m == 0:
        return axis
    return None


def _pool_spec(pool, hs) -> P:
    """Pools (…, KH, page, D): split by kv head, stacked or not."""
    return P(*(None,) * (pool.ndim - 3), hs, None, None)


def _scale_spec(scales, hs) -> P:
    """Scales (…, KH): split by kv head like their pools."""
    return P(*(None,) * (scales.ndim - 1), hs)


def _layer_pools(layer, *pools):
    """The layer's own (N, KH, page, D) pools and (N, KH) scales out of
    stacked ones, for the oracle (which gathers anyway); a 4-D pool's
    arrays pass through."""
    if pools[0].ndim == 4:
        return pools
    return tuple(None if x is None else x[layer] for x in pools)


def _prefix_extend_local(q, k_pages, v_pages, block_table, prefix_lens,
                         chunk_k, chunk_v, widths, k_scales, v_scales,
                         layer, use_kernel):
    if use_kernel:
        from repro.kernels.paged_attention.paged_attention import (
            paged_prefix_extend_pallas)
        return paged_prefix_extend_pallas(
            q, k_pages, v_pages, block_table, prefix_lens, chunk_k, chunk_v,
            widths, k_scales, v_scales, layer, interpret=interpret_mode())
    k_pages, v_pages, k_scales, v_scales = _layer_pools(
        layer, k_pages, v_pages, k_scales, v_scales)
    return paged_prefix_extend_ref(q, k_pages, v_pages, block_table,
                                   prefix_lens, chunk_k, chunk_v, widths,
                                   k_scales, v_scales)


def paged_prefix_extend_attention(q, k_pages, v_pages, block_table,
                                  prefix_lens, chunk_k, chunk_v, widths,
                                  k_scales: Optional[jax.Array] = None,
                                  v_scales: Optional[jax.Array] = None, *,
                                  layer: Optional[jax.Array] = None,
                                  use_kernel: bool = True,
                                  mesh=None, axis: str = "model",
                                  tp_impl: str = "kv_shard") -> jax.Array:
    """Multi-query prefix-extend attention: q (S,W,H,D) queries at
    logical positions ``prefix_lens[s] + [0, W)`` against the paged
    prefix plus the chunk's own fresh K/V (``chunk_k``/``chunk_v``
    (S,W,KH,D), causal up to ``widths[s]``) -> (S,W,H,D).  Pools and
    scales as :func:`paged_attention` (stacked ones read at ``layer``).
    One dispatch scores all W positions — the multi-query extension of
    :func:`paged_attention`; ``use_kernel=False`` (or the eager
    ``chunk_prefill_impl``) falls back to the full-horizon gather
    oracle.  ``mesh``/``tp_impl``: see the module docstring."""
    layer = jnp.zeros((), jnp.int32) if layer is None else layer
    m = _model_size(mesh, axis)
    if m <= 1:
        return _prefix_extend_local(q, k_pages, v_pages, block_table,
                                    prefix_lens, chunk_k, chunk_v, widths,
                                    k_scales, v_scales, layer, use_kernel)
    hs = _shard_axis(tp_impl, m, q.shape[2], k_pages.shape[-3], axis)
    args = [q, k_pages, v_pages, block_table, prefix_lens,
            chunk_k, chunk_v, widths, layer]
    specs = [P(None, None, hs, None),          # q        (S,W,H,D)
             _pool_spec(k_pages, hs),          # k_pages  (…,KH,page,D)
             _pool_spec(v_pages, hs),          # v_pages
             P(None, None),                    # block_table (replicated)
             P(None),                          # prefix_lens (replicated)
             P(None, None, hs, None),          # chunk_k  (S,W,KH,D)
             P(None, None, hs, None),          # chunk_v
             P(None),                          # widths (replicated)
             P()]                              # layer (replicated)
    if k_scales is not None:
        args += [k_scales, v_scales]
        specs += [_scale_spec(k_scales, hs), _scale_spec(v_scales, hs)]

    def local(*xs):
        ks, vs = xs[9:] if len(xs) > 9 else (None, None)
        return _prefix_extend_local(*xs[:8], ks, vs, xs[8], use_kernel)

    fn = jax.shard_map(local, mesh=mesh, in_specs=tuple(specs),
                       out_specs=P(None, None, hs, None), check_vma=False)
    return fn(*args)


def _paged_attention_local(q, k_pages, v_pages, block_table, lengths,
                           k_scales, v_scales, layer, use_kernel):
    if use_kernel:
        from repro.kernels.paged_attention.paged_attention import (
            paged_attention_pallas)
        return paged_attention_pallas(q, k_pages, v_pages, block_table,
                                      lengths, k_scales, v_scales, layer,
                                      interpret=interpret_mode())
    k_pages, v_pages, k_scales, v_scales = _layer_pools(
        layer, k_pages, v_pages, k_scales, v_scales)
    return paged_attention_ref(q, k_pages, v_pages, block_table, lengths,
                               k_scales, v_scales)


def paged_attention(q, k_pages, v_pages, block_table, lengths,
                    k_scales: Optional[jax.Array] = None,
                    v_scales: Optional[jax.Array] = None, *,
                    layer: Optional[jax.Array] = None,
                    use_kernel: bool = True,
                    mesh=None, axis: str = "model",
                    tp_impl: str = "kv_shard") -> jax.Array:
    """q: (S,H,D); k_pages/v_pages: (L,N,KH,page,D) layer stacks read at
    ``layer`` (int32 scalar; the serving programs' layer loop passes
    its index and the whole stack), or one layer's (N,KH,page,D);
    block_table: (S,P); lengths: (S,); k_scales/v_scales: (L,N,KH) or
    (N,KH) fp32 for quantized pools -> (S,H,D).  ``mesh``/``tp_impl``:
    see the module docstring."""
    layer = jnp.zeros((), jnp.int32) if layer is None else layer
    m = _model_size(mesh, axis)
    if m <= 1:
        return _paged_attention_local(q, k_pages, v_pages, block_table,
                                      lengths, k_scales, v_scales, layer,
                                      use_kernel)
    hs = _shard_axis(tp_impl, m, q.shape[1], k_pages.shape[-3], axis)
    args = [q, k_pages, v_pages, block_table, lengths, layer]
    specs = [P(None, hs, None),                # q       (S,H,D)
             _pool_spec(k_pages, hs),          # k_pages (…,KH,page,D)
             _pool_spec(v_pages, hs),          # v_pages
             P(None, None),                    # block_table (replicated)
             P(None),                          # lengths (replicated)
             P()]                              # layer (replicated)
    if k_scales is not None:
        args += [k_scales, v_scales]
        specs += [_scale_spec(k_scales, hs), _scale_spec(v_scales, hs)]

    def local(*xs):
        ks, vs = xs[6:] if len(xs) > 6 else (None, None)
        return _paged_attention_local(*xs[:5], ks, vs, xs[5], use_kernel)

    fn = jax.shard_map(local, mesh=mesh, in_specs=tuple(specs),
                       out_specs=P(None, hs, None), check_vma=False)
    return fn(*args)


def check_paged_kernels(cache, heads: int, q_dtype, *, decode: bool = True,
                        widths=()) -> None:
    """Compile the paged kernels at the shapes of ``cache``'s pools — the
    decode kernel when ``decode``, the prefix-extend kernel at each width
    in ``widths`` — so that a page size or pool the TPU compiler refuses
    (VMEM for a page × chunk tile, SMEM for the scales of a huge pool)
    fails at engine construction with the pool named, not inside the
    first serving program's compile.  Shapes are the unsharded ones, a
    superset of what any kv-head shard runs.  No-op in interpret mode."""
    if interpret_mode():
        return
    from repro.kernels.paged_attention.paged_attention import (
        paged_attention_pallas, paged_prefix_extend_pallas)
    node = _first_paged_node(cache)
    sds = jax.ShapeDtypeStruct
    kp = node["k_pages"]
    n, kh, page, d = kp.shape[-4:]
    s_n, p_n = node["block_table"].shape[-2:]
    # the pools as the serving programs pass them: the whole layer stack
    # plus a layer index
    pool = sds(kp.shape, kp.dtype)
    scales = (sds(node["k_scales"].shape, jnp.float32),) * 2 \
        if "k_scales" in node else (None, None)
    layer = sds((), jnp.int32)
    i32 = jnp.int32
    try:
        if decode:
            paged_attention_pallas.lower(
                sds((s_n, heads, d), q_dtype), pool, pool,
                sds((s_n, p_n), i32), sds((s_n,), i32), *scales,
                layer).compile()
        for w in widths:
            chunk = sds((1, w, kh, d), q_dtype)
            paged_prefix_extend_pallas.lower(
                sds((1, w, heads, d), q_dtype), pool, pool,
                sds((1, p_n), i32), sds((1,), i32), chunk, chunk,
                sds((1,), i32), *scales, layer).compile()
    except jax.errors.JaxRuntimeError as e:
        raise ValueError(
            f"the TPU compiler refuses the paged-attention kernels for "
            f"page_size={page} over {n} pages of {jnp.dtype(kp.dtype).name} "
            f"({kh} kv heads x head_dim {d}, chunk widths {tuple(widths)}): "
            f"{str(e).splitlines()[0]}") from e


def _first_paged_node(tree) -> Optional[dict]:
    """The first ``{k_pages, v_pages, ...}`` node of a model cache tree."""
    if isinstance(tree, dict):
        if "k_pages" in tree:
            return tree
        for sub in tree.values():
            node = _first_paged_node(sub)
            if node is not None:
                return node
    return None
