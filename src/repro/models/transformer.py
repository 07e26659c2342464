"""Block / group / stack assembly.

A *group* is one repeat of ``cfg.block_pattern`` (dense: 1 block; jamba:
1 attn + 7 mamba; VLM: ``cross_attn_every`` blocks with cross-attn on the
last).  All groups share a pytree structure, so the stack scans over
group-stacked parameters (compile size O(group), not O(layers)) with an
optional remat policy.

Block layout (pre-norm residual):
    x = x + mixer(norm1(x))            mixer ∈ {attn, mamba, rwkv6}
    [x = x + xattn(norm_x(x), src)]    (VLM / enc-dec blocks)
    x = x + mlp_or_moe(norm2(x))

Paged pools are carried, not sliced: when a cache holds paged nodes
(``k_pages``), the layer scan keeps their stacked pools (L,N,KH,page,D)
and scales in its carry and hands each layer the whole stack plus its
index (``"layer"``), so the token write scatters into the stack in
place and the paged kernels read the layer's pages at that index; only
the small per-layer leaves (block tables, verify's staging K/V) ride
the scan's xs/ys.  Contiguous caches, SSM states and training
(``cache=None``) are sliced per layer as xs/ys.

Named regions (``jax.named_scope``) mark what a serving program spends
its device time on; an op belongs to the innermost region on its path:
``kv_pool`` — the layer loop itself, around the layer body: the slicing
of a layer's weights and small cache leaves (XLA keeps a few of those
slices as ops of their own) and, for caches it does not carry, the
slicing of each layer's cache out of the stack and its write back;
``proj_mlp`` — a layer's norms, projections, residuals and MLP (the
whole layer body, around the two below), plus the embedding and the
head; ``kv_write`` — the cache write of a layer's new keys and values
(for a paged pool, a scatter of the new rows into the carried stack);
``attn_kernel`` — the attention computation over the cache; ``sample``
— sampling, in the engines' programs (``repro.serve``).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import init_mlp, init_norm, mlp_apply, norm_apply
from repro.models.moe import init_moe, moe_apply
from repro.sharding.ctx import maybe_constrain


# ---------------------------------------------------------------------------
# Block structure helpers


def block_kinds(cfg: ModelConfig) -> list[dict]:
    """Per-block metadata for one group."""
    out = []
    for i, kind in enumerate(cfg.block_pattern):
        has_moe = cfg.moe is not None and (i % cfg.moe_every == 0)
        has_xattn = (cfg.cross_attn_every > 0
                     and (i + 1) % cfg.cross_attn_every == 0) \
            or (cfg.encoder is not None and kind == "attn")
        out.append({"kind": kind, "moe": has_moe, "xattn": has_xattn})
    return out


def init_group(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    kinds = block_kinds(cfg)
    keys = jax.random.split(key, len(kinds))
    group = {}
    for i, (bk, k) in enumerate(zip(kinds, keys)):
        ks = jax.random.split(k, 6)
        blk: Dict[str, Any] = {"norm1": init_norm(cfg.norm, cfg.d_model, dtype)}
        if bk["kind"] == "attn":
            blk["attn"] = attn_mod.init_attention(ks[0], cfg.d_model,
                                                  cfg.attention, dtype)
        elif bk["kind"] == "mamba":
            blk["mamba"] = ssm_mod.init_mamba(ks[0], cfg.d_model, cfg.ssm, dtype)
        elif bk["kind"] == "rwkv6":
            blk["rwkv"] = ssm_mod.init_rwkv6(ks[0], cfg.d_model, cfg.ssm, dtype)
        else:
            raise ValueError(bk["kind"])
        if bk["xattn"]:
            blk["norm_x"] = init_norm(cfg.norm, cfg.d_model, dtype)
            xa = cfg.attention.__class__(**{**cfg.attention.__dict__,
                                            "causal": False})
            blk["xattn"] = attn_mod.init_attention(ks[1], cfg.d_model, xa, dtype)
        blk["norm2"] = init_norm(cfg.norm, cfg.d_model, dtype)
        if bk["moe"]:
            blk["moe"] = init_moe(ks[2], cfg.d_model, cfg.moe, dtype)
        else:
            blk["mlp"] = init_mlp(ks[3], cfg.d_model, cfg.d_ff,
                                  bias=cfg.mlp_bias, dtype=dtype)
        group[f"blk{i}"] = blk
    return group


def init_group_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                     paged: bool = False, n_pages: int = 0,
                     pages_per_slot: int = 0, page_size: int = 256,
                     kv_dtype: Optional[str] = None) -> dict:
    """KV caches / recurrent states for one group (decode & prefill).
    ``paged=True`` swaps each attention layer's contiguous (B, S, KH, D)
    cache for page pools + a block table (decode_attn_impl="paged_pallas");
    SSM states and cross-attention caches are position-free and unchanged.
    ``kv_dtype`` overrides ``cfg.kv_cache_dtype`` (the paged engine
    prefills into a bf16 staging cache and quantizes at the scatter)."""
    from repro.kvcache import CacheSpec, alloc_contiguous, alloc_paged
    kinds = block_kinds(cfg)
    cache = {}
    spec = CacheSpec(layout="paged" if paged else "contiguous",
                     dtype=kv_dtype or cfg.kv_cache_dtype,
                     style=cfg.kv_cache_style, page_size=page_size)
    for i, bk in enumerate(kinds):
        c: Dict[str, Any] = {}
        if bk["kind"] == "attn":
            if paged:
                c["kv"] = alloc_paged(spec, cfg.attention, batch, n_pages,
                                      pages_per_slot)
            else:
                c["kv"] = alloc_contiguous(spec, cfg.attention, batch,
                                           max_len)
        elif bk["kind"] == "mamba":
            c["state"] = ssm_mod.init_mamba_state(batch, cfg.d_model, cfg.ssm)
        elif bk["kind"] == "rwkv6":
            c["state"] = ssm_mod.init_rwkv6_state(batch, cfg.d_model, cfg.ssm)
        if bk["xattn"]:
            a = cfg.attention
            kvh = a.kv_heads_effective()
            src_len = (cfg.encoder.max_source_len if cfg.encoder is not None
                       else cfg.num_image_tokens)
            c["xk"] = jnp.zeros((batch, src_len, kvh, a.head_dim), jnp.bfloat16)
            c["xv"] = jnp.zeros((batch, src_len, kvh, a.head_dim), jnp.bfloat16)
        cache[f"blk{i}"] = c
    return cache


# ---------------------------------------------------------------------------
# Cross-attention helpers (precomputed source K/V for decode)


def _xattn_kv(p: dict, src: jax.Array, a) -> Tuple[jax.Array, jax.Array]:
    from repro.models.layers import linear_apply
    b, t, _ = src.shape
    kvh = a.kv_heads_effective()
    xk = linear_apply(p["wk"], src).reshape(b, t, kvh, a.head_dim)
    xv = linear_apply(p["wv"], src).reshape(b, t, kvh, a.head_dim)
    return xk, xv


def _xattn_with_kv(p: dict, x: jax.Array, a, xk, xv) -> jax.Array:
    from repro.models.attention import sdpa
    from repro.models.layers import linear_apply
    b, s, _ = x.shape
    kvh = xk.shape[2]
    g = a.heads_padded // kvh
    q = linear_apply(p["wq"], x).reshape(b, s, kvh, g, a.head_dim)
    o = sdpa(q, xk.astype(x.dtype), xv.astype(x.dtype), None,
             1.0 / jnp.sqrt(a.head_dim).astype(jnp.float32))
    from repro.models.attention import _mask_pad_heads
    return linear_apply(p["wo"], _mask_pad_heads(
        o.reshape(b, s, a.heads_padded * a.head_dim), a))


# ---------------------------------------------------------------------------
# Group forward


def _constrain_act(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.seq_parallel:
        return maybe_constrain(x, ("pod", "data"), "model", None)
    return maybe_constrain(x, ("pod", "data"), None, None)


def group_forward(gp: dict, x: jax.Array, cfg: ModelConfig, *,
                  mode: str, cache: Optional[dict], pos: Optional[jax.Array],
                  cross_src: Optional[jax.Array],
                  train: bool) -> Tuple[jax.Array, Optional[dict], dict]:
    kinds = block_kinds(cfg)
    new_cache: Dict[str, Any] = {}
    aux_total: Dict[str, jax.Array] = {}
    a = cfg.attention
    # mesh-sharded paged attention (serving TP): the paged entry points
    # shard_map their kernels over the "model" axis by kv head.  Gated on
    # cfg.model_parallel so single-device traces stay byte-identical.
    from repro.sharding.ctx import current_mesh
    tp_kw = {}
    if cfg.model_parallel > 1:
        tp_kw = dict(mesh=current_mesh(), tp_impl=cfg.tp_attn_impl)
    for i, bk in enumerate(kinds):
        blk = gp[f"blk{i}"]
        c = cache[f"blk{i}"] if cache is not None else None
        nc: Dict[str, Any] = {}
        x = _constrain_act(x, cfg)
        h = norm_apply(cfg.norm, blk["norm1"], x, cfg.norm_eps)

        chunk_kw = dict(attn_impl=cfg.attn_impl, q_block=cfg.attn_q_block,
                        kv_block=cfg.attn_kv_block,
                        chunk_min=cfg.attn_chunk_min,
                        unroll=cfg.scan_unroll)
        if bk["kind"] == "attn":
            if mode == "train":
                y = attn_mod.attention_forward(blk["attn"], h, a,
                                               use_flash=cfg.use_kernels,
                                               **chunk_kw)
            elif mode == "verify":
                # speculative verify (repro.spec): W draft queries against
                # the paged cache; fresh chunk K/V lands in the bf16
                # "stage" node (write-after-accept), pages untouched.
                y, stage = attn_mod.attention_verify_paged(
                    blk["attn"], h, a, c["kv"], c["stage"], pos,
                    style=cfg.kv_cache_style,
                    use_kernel=cfg.chunk_prefill_impl != "eager", **tp_kw)
                nc["stage"] = stage
            elif mode == "prefill":
                if "k_pages" in c["kv"]:
                    # chunked/continuation prefill straight into the paged
                    # pools; pos carries (slot_ids, starts, lengths).
                    # Same prefix-extend dispatch as mode="verify".
                    y, kv = attn_mod.attention_prefill_paged(
                        blk["attn"], h, a, c["kv"], pos,
                        style=cfg.kv_cache_style,
                        use_kernel=cfg.chunk_prefill_impl != "eager",
                        **tp_kw)
                else:
                    y, kv = attn_mod.attention_prefill(
                        blk["attn"], h, a, c["kv"], style=cfg.kv_cache_style,
                        use_flash=cfg.use_kernels, **chunk_kw)
                nc["kv"] = kv
            else:  # decode
                mesh = current_mesh()
                if "k_pages" in c["kv"]:
                    # paged cache present <=> decode_attn_impl="paged_pallas"
                    y, kv = attn_mod.attention_decode_paged(
                        blk["attn"], h, a, c["kv"], pos,
                        style=cfg.kv_cache_style, **tp_kw)
                elif (cfg.decode_attn_impl == "cp" and mesh is not None
                        and a.kind != "mla" and "k_scale" not in c["kv"]):
                    # CP decode reads/writes shard-local slabs inside
                    # shard_map; quantized caches fall through to eager

                    y, kv = attn_mod.attention_decode_cp(
                        blk["attn"], h, a, c["kv"], pos, mesh=mesh)
                else:
                    y, kv = attn_mod.attention_decode(
                        blk["attn"], h, a, c["kv"], pos,
                        style=cfg.kv_cache_style)
                nc["kv"] = kv
        elif bk["kind"] == "mamba":
            st = c["state"] if c is not None else \
                ssm_mod.init_mamba_state(x.shape[0], cfg.d_model, cfg.ssm)
            if mode == "decode":
                y, st2 = ssm_mod.mamba_decode(blk["mamba"], h, cfg.ssm, st)
            else:
                y, st2 = ssm_mod.mamba_forward(blk["mamba"], h, cfg.ssm, st)
            if c is not None:
                nc["state"] = st2
        else:  # rwkv6
            st = c["state"] if c is not None else \
                ssm_mod.init_rwkv6_state(x.shape[0], cfg.d_model, cfg.ssm)
            if mode == "decode":
                y, st2 = ssm_mod.rwkv6_decode(blk["rwkv"], h, cfg.ssm, st)
            else:
                y, st2 = ssm_mod.rwkv6_forward(blk["rwkv"], h, cfg.ssm, st,
                                               use_kernel=cfg.use_kernels)
            if c is not None:
                nc["state"] = st2
        x = x + y

        if bk["xattn"]:
            hx = norm_apply(cfg.norm, blk["norm_x"], x, cfg.norm_eps)
            if mode == "decode":
                y = _xattn_with_kv(blk["xattn"], hx, a, c["xk"], c["xv"])
                nc["xk"], nc["xv"] = c["xk"], c["xv"]
            else:
                assert cross_src is not None, "xattn needs cross_src"
                xk, xv = _xattn_kv(blk["xattn"], cross_src, a)
                y = _xattn_with_kv(blk["xattn"], hx, a, xk, xv)
                if c is not None:
                    nc["xk"] = xk.astype(c["xk"].dtype)
                    nc["xv"] = xv.astype(c["xv"].dtype)
            x = x + y

        x = _constrain_act(x, cfg)
        h = norm_apply(cfg.norm, blk["norm2"], x, cfg.norm_eps)
        if bk["moe"]:
            y, aux = moe_apply(blk["moe"], h, cfg.moe, train=train,
                               group_size=cfg.moe_group_size,
                               impl=cfg.moe_impl)
            for k, v in aux.items():
                aux_total[k] = aux_total.get(k, 0.0) + v
        else:
            y = mlp_apply(blk["mlp"], h)
        x = x + y
        new_cache[f"blk{i}"] = nc
    return x, (new_cache if cache is not None else None), aux_total


# ---------------------------------------------------------------------------
# Stack (scan over groups)


def _remat_wrap(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)  # "full": save only block boundaries


def init_stack(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    g = cfg.num_groups
    keys = jax.random.split(key, g)
    if cfg.scan_layers:
        return jax.vmap(lambda k: init_group(k, cfg, dtype))(keys)
    return {f"g{i}": init_group(keys[i], cfg, dtype) for i in range(g)}


def init_stack_cache(cfg: ModelConfig, batch: int, max_len: int,
                     **paged_kw) -> dict:
    g = cfg.num_groups
    one = init_group_cache(cfg, batch, max_len, **paged_kw)
    if cfg.scan_layers:
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (g,) + x.shape), one)
    return {f"g{i}": init_group_cache(cfg, batch, max_len, **paged_kw)
            for i in range(g)}


_POOL_KEYS = ("k_pages", "v_pages", "k_scales", "v_scales")


def _paged_paths(tree, path: tuple = ()) -> list:
    """Key paths of the paged nodes (``{k_pages, ...}``) in a cache tree."""
    if not isinstance(tree, dict):
        return []
    if "k_pages" in tree:
        return [path]
    return [p for k, v in tree.items() for p in _paged_paths(v, path + (k,))]


def _get(tree, path: tuple):
    """The node at ``path``, or None where the tree has none."""
    for k in path:
        if not isinstance(tree, dict) or k not in tree:
            return None
        tree = tree[k]
    return tree


def _put(tree, path: tuple, node):
    """``tree`` with the node at ``path`` replaced by ``node``."""
    if not path:
        return node
    return {**tree, path[0]: _put(tree[path[0]], path[1:], node)}


def _without_pools(node: dict) -> dict:
    return {k: v for k, v in node.items()
            if k not in _POOL_KEYS and k != "layer"}


def _scan_carrying_pools(body, x, params, cache, paths, n_groups, unroll):
    """The layer scan over a cache with paged nodes at ``paths``: their
    stacked pools ride the carry and every layer gets the whole stack plus
    its index, so no layer's pool is sliced out or written back; the rest
    of the cache (block tables, staging K/V) is sliced per layer as xs/ys.
    A layer that hands back no paged node (spec verify reads the pools
    and returns only its staging node) leaves the carried pools as they
    were."""
    pools = [{k: v for k, v in _get(cache, p).items() if k in _POOL_KEYS}
             for p in paths]
    rest = cache
    for p in paths:
        rest = _put(rest, p, _without_pools(_get(cache, p)))

    def scan_body(carry, xs):
        x, pools = carry
        gp, c, layer = xs
        for p, pool in zip(paths, pools):
            c = _put(c, p, {**_get(c, p), **pool, "layer": layer})
        y, nc, aux = body(x, gp, c)
        new_pools = []
        for p, pool in zip(paths, pools):
            node = _get(nc, p)
            if node is None or "k_pages" not in node:
                new_pools.append(pool)
                continue
            new_pools.append({k: node[k] for k in pool})
            nc = _put(nc, p, _without_pools(node))
        return (y, new_pools), (nc, aux)

    layers = jnp.arange(n_groups, dtype=jnp.int32)
    (x, pools), (new_cache, auxs) = jax.lax.scan(
        scan_body, (x, pools), (params, rest, layers), unroll=unroll)
    for p, pool in zip(paths, pools):
        node = _get(new_cache, p)
        if node is not None:
            new_cache = _put(new_cache, p, {**node, **pool})
    return x, new_cache, auxs


def stack_forward(params: dict, x: jax.Array, cfg: ModelConfig, *,
                  mode: str = "train", cache: Optional[dict] = None,
                  pos: Optional[jax.Array] = None,
                  cross_src: Optional[jax.Array] = None,
                  train: bool = True) -> Tuple[jax.Array, Optional[dict], dict]:
    def body_fn(x, gp, c):
        with jax.named_scope("proj_mlp"):
            return group_forward(gp, x, cfg, mode=mode, cache=c, pos=pos,
                                 cross_src=cross_src, train=train)

    if cfg.scan_layers:
        wrapped = _remat_wrap(body_fn, cfg.remat_policy if mode == "train"
                              else "none")

        def scan_body(carry, xs):
            gp, c = xs
            y, nc, aux = wrapped(carry, gp, c)
            return y, (nc, aux)

        unroll = cfg.num_groups if cfg.scan_unroll else 1
        paths = _paged_paths(cache)
        if cache is None:
            def scan_body_nocache(carry, gp):
                y, _, aux = wrapped(carry, gp, None)
                return y, aux
            x, auxs = jax.lax.scan(scan_body_nocache, x, params,
                                   unroll=unroll)
            new_cache = None
        elif paths:
            with jax.named_scope("kv_pool"):
                x, new_cache, auxs = _scan_carrying_pools(
                    wrapped, x, params, cache, paths, cfg.num_groups, unroll)
        else:
            with jax.named_scope("kv_pool"):
                x, (new_cache, auxs) = jax.lax.scan(
                    scan_body, x, (params, cache), unroll=unroll)
        aux = {k: jnp.sum(v) for k, v in auxs.items()}
        return x, new_cache, aux

    aux_total: Dict[str, jax.Array] = {}
    new_cache = {} if cache is not None else None
    for i in range(cfg.num_groups):
        c = cache[f"g{i}"] if cache is not None else None
        x, nc, aux = body_fn(x, params[f"g{i}"], c)
        if cache is not None:
            new_cache[f"g{i}"] = nc
        for k, v in aux.items():
            aux_total[k] = aux_total.get(k, 0.0) + v
    return x, new_cache, aux_total


# ---------------------------------------------------------------------------
# Whisper-style encoder (bidirectional attention stack, no cache)


def init_encoder(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    enc_attn = cfg.attention.__class__(**{**cfg.attention.__dict__,
                                          "causal": False})
    keys = jax.random.split(key, cfg.encoder.num_layers)

    def one(k):
        ks = jax.random.split(k, 3)
        return {
            "norm1": init_norm(cfg.norm, cfg.d_model, dtype),
            "attn": attn_mod.init_attention(ks[0], cfg.d_model, enc_attn, dtype),
            "norm2": init_norm(cfg.norm, cfg.d_model, dtype),
            "mlp": init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype=dtype),
        }

    return {"layers": jax.vmap(one)(keys),
            "final_norm": init_norm(cfg.norm, cfg.d_model, dtype)}


def encoder_forward(p: dict, frames: jax.Array, cfg: ModelConfig) -> jax.Array:
    enc_attn = cfg.attention.__class__(**{**cfg.attention.__dict__,
                                          "causal": False})

    def body(x, lp):
        h = norm_apply(cfg.norm, lp["norm1"], x, cfg.norm_eps)
        x = x + attn_mod.attention_forward(lp["attn"], h, enc_attn)
        h = norm_apply(cfg.norm, lp["norm2"], x, cfg.norm_eps)
        return x + mlp_apply(lp["mlp"], h), None

    unroll = cfg.encoder.num_layers if cfg.scan_unroll else 1
    x, _ = jax.lax.scan(body, frames, p["layers"], unroll=unroll)
    return norm_apply(cfg.norm, p["final_norm"], x, cfg.norm_eps)
