"""Step functions + sharding assignment for dry-run / train / serve.

``build_cell(cfg, shape, mesh)`` returns the jitted-able step function,
its abstract arguments (ShapeDtypeStructs from ``configs.specs``), and
matching in/out shardings — one "cell" of the (arch × shape × mesh)
grid.  The SAME factories drive the real Trainer/Engine and the AOT
dry-run, so the roofline is derived from the artifact that would run.

Sharding policy (baseline; hillclimbs override via ``overrides``):
  * params: path-rules TP over "model"; big models (> ``fsdp_gb`` per
    chip) additionally ZeRO-3 shard over "data".
  * batch: (B, S) over ("pod","data").
  * KV caches: batch over DP when divisible; the sequence dim is
    spread over remaining axes until the per-chip slab is < 4 GB
    (context parallelism); recurrent states shard their feature dim
    over "model".
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.configs.specs import input_specs
from repro.models.model import LM
from repro.optim.adamw import AdamWState, adamw_update, clip_by_global_norm, init_adamw
from repro.sharding.rules import dp_axes, make_param_specs


@dataclasses.dataclass
class Cell:
    name: str
    fn: Callable
    args: tuple                        # abstract args (ShapeDtypeStructs)
    in_shardings: tuple
    out_shardings: Any
    donate_argnums: tuple = ()
    mesh: Optional[Mesh] = None

    def lower(self):
        jitted = jax.jit(self.fn, in_shardings=self.in_shardings,
                         out_shardings=self.out_shardings,
                         donate_argnums=self.donate_argnums)
        return jitted.lower(*self.args)


# ---------------------------------------------------------------------------
# Sharding helpers


def _ns(mesh: Mesh, *axes) -> NamedSharding:
    return NamedSharding(mesh, P(*axes))


def _dp(mesh: Mesh):
    axes = dp_axes(mesh)
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def _dp_total(mesh: Mesh) -> int:
    n = 1
    for a in dp_axes(mesh):
        n *= mesh.shape[a]
    return n


def batch_shardings(batch_abs: dict, mesh: Mesh) -> dict:
    dpt = _dp_total(mesh)
    dp = _dp(mesh)

    def leaf(l):
        if dp is not None and l.shape[0] % dpt == 0:
            return _ns(mesh, dp, *([None] * (l.ndim - 1)))
        return _ns(mesh, *([None] * l.ndim))

    return jax.tree.map(leaf, batch_abs)


def cache_shardings(cache_abs: Any, mesh: Mesh, cfg: ModelConfig,
                    shape: ShapeConfig, *,
                    seq_threshold: Optional[float] = None) -> Any:
    """Sharding for KV caches / recurrent states (see module docstring).
    Prefill writes the whole cache, and a seq-sharded destination makes
    XLA reshard every layer's k/v (a collective storm) — so prefill only
    seq-shards past 12 GB/chip; decode reads are cheap to distribute, so
    it spreads at 4 GB/chip."""
    if seq_threshold is None:
        seq_threshold = (12 if shape.mode == "prefill" else 4) * 2**30
    dpt = _dp_total(mesh)
    dp = _dp(mesh)
    model = mesh.shape.get("model", 1)
    total_bytes = sum(
        np.prod(l.shape) * l.dtype.itemsize
        for l in jax.tree.leaves(cache_abs))

    def leaf(path, l):
        ks = jax.tree_util.keystr(path)
        dims = [None] * l.ndim
        if "k_scales" in ks or "v_scales" in ks:
            # paged per-page-per-kv-head amax scales (N, KH) [+ stacked
            # group dim]: follow the pools' TP split of the kv-head dim
            if model > 1 and l.shape[-1] % model == 0:
                dims[-1] = "model"
            return _ns(mesh, *dims)
        if "pages" in ks:
            # paged KV pools (decode_attn_impl="paged_pallas"): pages have
            # no batch dim (slots share the pool), so never batch-shard;
            # TP splits the stored kv-head dim over "model".
            h_dim = l.ndim - 3                # (…, KH, page, D)
            if model > 1 and l.shape[h_dim] % model == 0:
                dims[h_dim] = "model"
            return _ns(mesh, *dims)
        if "block_table" in ks:
            return _ns(mesh, *dims)           # tiny; replicate
        off = 1 if cfg.scan_layers else 0     # leading stacked group dim
        b_dim = off
        batch_sharded = False
        if dp is not None and l.shape[b_dim] % dpt == 0:
            dims[b_dim] = dp
            batch_sharded = True
        if "state" in ks or "x_prev" in ks:
            # recurrent state: shard the first big feature dim over model
            for i in range(b_dim + 1, l.ndim):
                if l.shape[i] % model == 0 and l.shape[i] >= 2 * model:
                    dims[i] = "model"
                    break
            return _ns(mesh, *dims)
        s_dim = b_dim + 1
        from repro.kvcache import normalize_dtype
        if (cfg.decode_attn_impl == "cp" and shape.mode == "decode"
                and normalize_dtype(cfg.kv_cache_dtype) == "bfloat16"
                and "['kv']" in ks and l.ndim > s_dim
                and l.shape[s_dim] % model == 0):
            # context-parallel decode: cache sequence over "model".
            # Quantized caches are excluded — transformer.group_forward
            # routes them to eager decode (CP is shard-local), and a
            # seq-sharded cache there would all-gather every step.
            dims[s_dim] = "model"
            return _ns(mesh, *dims)
        if l.ndim > s_dim and l.shape[s_dim] == shape.seq_len:
            used = set(dp_axes(mesh)) if batch_sharded else set()
            free = [a for a in ("data", "model") if a not in used]
            per_chip = total_bytes / (dpt if batch_sharded else 1)
            seq_axes = []
            for a in free:
                if per_chip <= seq_threshold and (batch_sharded or seq_axes):
                    break
                if l.shape[s_dim] % mesh.shape[a] == 0:
                    seq_axes.append(a)
                    per_chip /= mesh.shape[a]
            if seq_axes:
                dims[s_dim] = tuple(seq_axes) if len(seq_axes) > 1 \
                    else seq_axes[0]
        return _ns(mesh, *dims)

    return jax.tree_util.tree_map_with_path(leaf, cache_abs)


def param_shardings(params_abs: Any, mesh: Mesh, *, fsdp: bool) -> Any:
    specs = make_param_specs(params_abs, mesh, fsdp=fsdp)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))


def auto_fsdp(cfg: ModelConfig, mesh: Mesh, mode: str = "train", *,
              budget_gb: float = 12.0) -> bool:
    """ZeRO-3 the params over "data" only when TP alone cannot hold the
    training state (params+grads+AdamW ≈ 4× bf16 weights) / the serving
    weights within ``budget_gb`` per chip.  Inference prefers pure TP:
    FSDP gathers weights every step, which decode latency cannot hide."""
    model = mesh.shape.get("model", 1)
    w = cfg.param_count() * 2 / model                 # bf16 weights/chip
    need = 4 * w if mode == "train" else w
    return need > budget_gb * 2**30


# ---------------------------------------------------------------------------
# Cells


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, *,
               fsdp: Optional[bool] = None, lr: float = 3e-4,
               microbatches: int = 1) -> Cell:
    lm = LM(cfg)
    if fsdp is None:
        fsdp = auto_fsdp(cfg, mesh, shape.mode)
    if cfg.quant != "bf16" and shape.mode != "train":
        # serving with AE-LLM's c_inf weight arm applied: the abstract
        # params carry {'qw','scale'} leaves (linear_apply dispatches)
        from repro.quant.qops import quantize_tree

        def init_q(key):
            return quantize_tree(lm.init(key), quant=cfg.quant)

        params_abs = jax.eval_shape(init_q, jax.random.PRNGKey(0))
    else:
        params_abs = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    p_sh = param_shardings(params_abs, mesh, fsdp=fsdp)
    specs = input_specs(cfg, shape)
    repl = lambda tree: jax.tree.map(                       # noqa: E731
        lambda l: _ns(mesh, *([None] * getattr(l, "ndim", 0))), tree)

    if shape.mode == "train":
        opt_abs = jax.eval_shape(init_adamw, params_abs)
        o_sh = AdamWState(step=_ns(mesh),
                          mu=jax.tree.map(lambda s: s, p_sh),
                          nu=jax.tree.map(lambda s: s, p_sh))
        batch_abs = specs["batch"]
        b_sh = batch_shardings(batch_abs, mesh)
        scalar = _ns(mesh)

        def grad_fn(params, batch):
            if microbatches == 1:
                (_, metrics), grads = jax.value_and_grad(
                    lm.loss, has_aux=True)(params, batch)
                return grads, metrics

            def one(params, mb):
                (_, metrics), g = jax.value_and_grad(
                    lm.loss, has_aux=True)(params, mb)
                return g, metrics

            def body(acc, mb):
                g, metrics = one(params, mb)
                return jax.tree.map(jnp.add, acc, g), metrics

            mbs = jax.tree.map(
                lambda x: x.reshape(microbatches, -1, *x.shape[1:]), batch)
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, p.dtype), params)
            grads, metrics = jax.lax.scan(
                body, zeros, mbs,
                unroll=microbatches if cfg.scan_unroll else 1)
            metrics = jax.tree.map(lambda m: m[-1], metrics)
            return jax.tree.map(lambda g: g / microbatches, grads), metrics

        def train_step(params, opt_state, batch):
            grads, metrics = grad_fn(params, batch)
            # Pin gradient sharding to the parameter sharding.  Without
            # this the scan-backward gradient accumulator loses its
            # sharding and XLA all-reduces FULL-size gradients (ZeRO
            # reduce-scatter degenerates to replicated all-reduce).
            grads = jax.tree.map(
                lambda g, s: jax.lax.with_sharding_constraint(g, s),
                grads, p_sh)
            grads, gnorm = clip_by_global_norm(grads, 1.0)
            params, opt_state = adamw_update(grads, opt_state, params, lr=lr)
            metrics = dict(metrics, grad_norm=gnorm)
            return params, opt_state, metrics

        metrics_sh = None  # scalars: let XLA replicate
        return Cell(
            name=f"{cfg.name}:{shape.name}",
            fn=train_step,
            args=(params_abs, opt_abs, batch_abs),
            in_shardings=(p_sh, o_sh, b_sh),
            out_shardings=(p_sh, o_sh, metrics_sh),
            donate_argnums=(0, 1),
            mesh=mesh)

    if shape.mode == "prefill":
        cache_abs = specs["cache"]
        c_sh = cache_shardings(cache_abs, mesh, cfg, shape)
        tok_sh = batch_shardings({"t": specs["tokens"]}, mesh)["t"]
        args = [specs["tokens"], cache_abs]
        in_sh = [tok_sh, c_sh]
        kw = {}
        if "modality_input" in specs:
            args.append(specs["modality_input"])
            in_sh.append(batch_shardings(
                {"m": specs["modality_input"]}, mesh)["m"])

            def prefill_step(params, tokens, cache, modality_input):
                return lm.prefill(params, tokens, cache,
                                  modality_input=modality_input)
        else:
            def prefill_step(params, tokens, cache):
                return lm.prefill(params, tokens, cache)

        logits_sh = _ns(mesh, _dp(mesh), None)
        return Cell(
            name=f"{cfg.name}:{shape.name}",
            fn=prefill_step,
            args=(params_abs, *args),
            in_shardings=(p_sh, *in_sh),
            out_shardings=(logits_sh, c_sh),
            donate_argnums=(2,),
            mesh=mesh)

    # decode
    cache_abs = specs["cache"]
    c_sh = cache_shardings(cache_abs, mesh, cfg, shape)
    b = shape.global_batch
    dpt = _dp_total(mesh)
    vec_sh = _ns(mesh, _dp(mesh)) if b % dpt == 0 else _ns(mesh, None)

    def serve_step(params, token, cache, pos):
        return lm.decode_step(params, token, cache, pos)

    logits_sh = _ns(mesh, _dp(mesh) if b % dpt == 0 else None, None)
    return Cell(
        name=f"{cfg.name}:{shape.name}",
        fn=serve_step,
        args=(params_abs, specs["token"], cache_abs, specs["pos"]),
        in_shardings=(p_sh, vec_sh, c_sh, vec_sh),
        out_shardings=(logits_sh, c_sh),
        donate_argnums=(2,),
        mesh=mesh)
