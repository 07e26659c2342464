"""Context-parallel decode attention (flash-decoding combine).

When TP size doesn't divide the KV-head count (GQA kv=8 on a 16-way model
axis), naive pjit decode all-gathers the whole KV cache — the collective
term explodes (this is exactly what the baseline dry-run shows for
deepseek-33b decode_32k; see EXPERIMENTS.md §Perf).  The fix: shard the KV
cache *sequence* dim over the model axis, compute partial softmax stats
(m, l, o·l) per shard, and combine with one tiny all-reduce over
(heads × head_dim) instead of (seq × heads × head_dim):

    m_g = max_s m_s;   l_g = Σ_s l_s·e^{m_s−m_g};
    o_g = Σ_s o_s·l_s·e^{m_s−m_g} / l_g

Exposed as ``context_parallel_decode`` (shard_map) and used by
``serve_step`` when ``cfg.decode_attn_impl == "flash_combine"``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _partial_attn(q, k, v, valid, scale):
    """q: (B,H,hd); k,v: (B,T,KH,hd); valid: (B,T) -> (o·l, m, l) partials."""
    kh = k.shape[2]
    g = q.shape[1] // kh
    b = q.shape[0]
    qg = q.reshape(b, kh, g, -1)
    s = jnp.einsum("bkgd,btkd->bkgt", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1)                                   # (B,KH,G)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)                                   # (B,KH,G)
    o = jnp.einsum("bkgt,btkd->bkgd", p, v.astype(jnp.float32))
    return o, m, l


def context_parallel_decode(q, k_cache, v_cache, pos, mesh: Mesh, *,
                            axis: str = "model",
                            window: Optional[int] = None) -> jax.Array:
    """q: (B,H,hd); caches: (B,S,KH,hd) sharded (None, axis, None, None);
    pos: scalar.  Returns (B,H,hd) attention output, replicated over axis."""
    b, h, hd = q.shape
    s_global = k_cache.shape[1]
    n = mesh.shape[axis]
    scale = 1.0 / (hd ** 0.5)

    def per_shard(q_l, k_l, v_l):
        i = jax.lax.axis_index(axis)
        s_local = k_l.shape[1]
        kpos = i * s_local + jnp.arange(s_local)
        valid = kpos <= pos
        if window is not None:
            valid &= kpos > pos - window
        valid = jnp.broadcast_to(valid[None], (b, s_local))
        o, m, l = _partial_attn(q_l, k_l, v_l, valid, scale)
        # softmax combine across shards
        m_g = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - m_g)
        l_c = l * corr
        o_c = o * corr[..., None]
        l_g = jax.lax.psum(l_c, axis)
        o_g = jax.lax.psum(o_c, axis)
        o_final = o_g / jnp.maximum(l_g, 1e-30)[..., None]
        return o_final.reshape(b, h, hd).astype(q_l.dtype)

    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(), P(None, axis, None, None), P(None, axis, None, None)),
        out_specs=P(), check_vma=False)
    return fn(q, k_cache, v_cache)
