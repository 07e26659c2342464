"""Public model API: init / loss / forward / prefill / decode_step.

Functional style: ``LM`` holds only the config; parameters are explicit
pytrees so pjit/shard_map own placement.  The LM head uses a chunked
cross-entropy (scan over sequence segments, rematerialized) so (B, S,
vocab) logits are never fully resident — at 100k vocab that is the
difference between 26 GB and <300 MB per device.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (dtype_of, embedding_apply, init_embedding,
                                 init_norm, norm_apply)
from repro.models.transformer import (encoder_forward, init_encoder,
                                      init_stack, init_stack_cache,
                                      stack_forward)
from repro.sharding.ctx import maybe_constrain


class LM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.dtype = dtype_of(cfg.dtype)

    # ------------------------------------------------------------------
    def init(self, key) -> dict:
        cfg = self.cfg
        ks = jax.random.split(key, 5)
        # Vocab padding must be exact: draw embed/head at the REAL vocab
        # size and zero-pad to padded_vocab, so the live rows are
        # bit-identical to the unpadded model's (padding the *draw shape*
        # would change every value).  Pad rows are never gathered, pad
        # logits are masked to -inf, and the mask zeroes their grads.
        v_pad = cfg.padded_vocab - cfg.vocab_size
        embed = init_embedding(ks[0], cfg.vocab_size, cfg.d_model, self.dtype)
        if v_pad:
            embed["w"] = jnp.pad(embed["w"], ((0, v_pad), (0, 0)))
        params: Dict[str, Any] = {
            "embed": embed,
            "layers": init_stack(ks[1], cfg, self.dtype),
            "final_norm": init_norm(cfg.norm, cfg.d_model, self.dtype),
        }
        if not cfg.tie_embeddings:
            from repro.models.layers import init_linear
            head = init_linear(ks[2], cfg.d_model, cfg.vocab_size,
                               dtype=self.dtype)
            if v_pad:
                head["w"] = jnp.pad(head["w"], ((0, 0), (0, v_pad)))
            params["lm_head"] = head
        if cfg.encoder is not None:
            params["encoder"] = init_encoder(ks[3], cfg, self.dtype)
        return params

    def abstract_params(self, key=None) -> dict:
        key = key if key is not None else jax.random.PRNGKey(0)
        return jax.eval_shape(self.init, key)

    # ------------------------------------------------------------------
    def _head_w(self, params) -> jax.Array:
        if self.cfg.tie_embeddings:
            return params["embed"]["w"].T
        p = params["lm_head"]
        if "qw" in p:  # quantized head: dequantize (serving path)
            return (p["qw"].astype(jnp.float32)
                    * p["scale"][None, :]).astype(self.dtype)
        return p["w"]

    def _mask_pad_logits(self, logits: jax.Array) -> jax.Array:
        """-inf the padded vocab columns (vocab_pad_multiple)."""
        v = self.cfg.vocab_size
        if logits.shape[-1] == v:
            return logits
        ids = jnp.arange(logits.shape[-1])
        return jnp.where(ids < v, logits, -1e30)

    def _encode_source(self, params, modality_input):
        """Stub frontends: modality_input is precomputed frame/patch
        embeddings (B, T_src, d_model)."""
        cfg = self.cfg
        if cfg.encoder is not None:
            return encoder_forward(params["encoder"], modality_input, cfg)
        return modality_input  # VLM: patch embeddings consumed by xattn

    def backbone(self, params, tokens, *, mode="train", cache=None, pos=None,
                 modality_input=None, train=True):
        cfg = self.cfg
        # Quantized-matmul impl for every linear under this forward —
        # the ONE choke point all serving paths (prefill, paged decode,
        # spec verify, chunked-prefill continuation, the draft LM) pass
        # through.  Entered at trace time, so the choice is static in
        # each jitted program.  Training forwards stay on the jnp ref
        # path: Pallas kernels are not differentiable (QLoRA backprops
        # through quantized_matmul).
        from repro.models.layers import f32_accum
        from repro.quant.qops import quant_impl
        impl = "ref" if train else cfg.quant_matmul_impl
        # Sharded serving keeps dense matmuls f32-accumulated so the TP
        # psum over row-sharded contractions reduces f32 partials and
        # rounds once — greedy decode stays token-identical to a single
        # device (see models/layers.f32_accum).  Quantized matmuls need
        # no flag: int8 partial sums are exact in any reduce order.
        with quant_impl(impl), \
                f32_accum(cfg.model_parallel > 1 and not train):
            with jax.named_scope("proj_mlp"):
                x = embedding_apply(params["embed"], tokens).astype(
                    self.dtype)
                x = maybe_constrain(x, ("pod", "data"), None, None)
            cross_src = None
            if modality_input is not None and mode != "decode":
                cross_src = self._encode_source(params, modality_input)
            x, new_cache, aux = stack_forward(
                params["layers"], x, cfg, mode=mode, cache=cache, pos=pos,
                cross_src=cross_src, train=train)
            with jax.named_scope("proj_mlp"):
                x = norm_apply(cfg.norm, params["final_norm"], x,
                               cfg.norm_eps)
        return x, new_cache, aux

    # ------------------------------------------------------------------
    def loss(self, params, batch: dict) -> Tuple[jax.Array, dict]:
        """batch: {tokens (B,S), labels (B,S), [mask (B,S)],
        [modality_input]} -> (scalar loss, metrics)."""
        cfg = self.cfg
        x, _, aux = self.backbone(params, batch["tokens"], mode="train",
                                  modality_input=batch.get("modality_input"),
                                  train=True)
        mask = batch.get("mask")
        ce, acc = chunked_cross_entropy(x, self._head_w(params),
                                        batch["labels"], mask=mask,
                                        chunk=cfg.ce_chunk,
                                        unroll=cfg.scan_unroll,
                                        n_valid=cfg.vocab_size)
        loss = ce
        metrics = {"ce_loss": ce, "accuracy": acc}
        for k, v in aux.items():
            metrics[k] = v
            if k.endswith("_loss"):
                loss = loss + v
        metrics["loss"] = loss
        return loss, metrics

    def logits(self, params, tokens, *, modality_input=None) -> jax.Array:
        x, _, _ = self.backbone(params, tokens, mode="train",
                                modality_input=modality_input, train=False)
        out = x.astype(jnp.float32) @ self._head_w(params).astype(jnp.float32)
        return self._mask_pad_logits(out)

    # ------------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, *,
                   kv_dtype: Optional[str] = None) -> dict:
        """Contiguous decode/prefill cache (layout/dtype/style resolved by
        ``repro.kvcache.CacheSpec``); ``kv_dtype`` overrides the config
        (e.g. a bf16 staging cache for the paged engine's admission)."""
        return init_stack_cache(self.cfg, batch, max_len, kv_dtype=kv_dtype)

    def init_paged_cache(self, n_slots: int, n_pages: int,
                         pages_per_slot: int, *, page_size: int = 256) -> dict:
        """Paged decode cache (decode_attn_impl="paged_pallas"): per-layer
        page pools + block tables instead of (B, S, KH, D) slabs."""
        return init_stack_cache(self.cfg, n_slots, 0, paged=True,
                                n_pages=n_pages,
                                pages_per_slot=pages_per_slot,
                                page_size=page_size)

    def prefill(self, params, tokens, cache, *, modality_input=None,
                lengths=None):
        """Full-context pass filling the cache; returns last-token logits.
        ``lengths`` (B,) switches to ragged selection — logits are taken at
        each row's position ``lengths[b]-1`` instead of the final column,
        so right-padded batched admission gets real last-token logits."""
        x, cache, _ = self.backbone(params, tokens, mode="prefill",
                                    cache=cache,
                                    modality_input=modality_input,
                                    train=False)
        with jax.named_scope("proj_mlp"):
            if lengths is None:
                last = x[:, -1:]
            else:
                idx = jnp.clip(lengths - 1, 0,
                               x.shape[1] - 1).astype(jnp.int32)
                last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
            logits = last.astype(jnp.float32) @ self._head_w(
                params).astype(jnp.float32)
            return self._mask_pad_logits(logits[:, 0]), cache

    def prefill_paged(self, params, tokens, cache, slot_ids, starts,
                      lengths, max_pages=None):
        """Chunked prefill continuation straight into the paged cache:
        ``tokens`` (B, c) right-padded chunks land at absolute positions
        ``starts[b] + [0, lengths[b])`` of slot ``slot_ids[b]``; each
        chunk's queries attend to the slot's cached prefix (streamed page
        by page through the fused prefix-extend kernel — the W = chunk
        instantiation of the spec-verify kernel) plus the chunk itself
        (models/attention.attention_prefill_paged).  Returns
        logits at each row's last chunk token and the updated cache —
        the scheduler samples from them only on a prompt's final chunk.
        ``max_pages`` (static python int) bounds the kernel's page grid
        to the batch's actual prefix span (see attention_prefill_paged).
        """
        pos = (slot_ids, starts, lengths) if max_pages is None \
            else (slot_ids, starts, lengths, max_pages)
        x, cache, _ = self.backbone(params, tokens, mode="prefill",
                                    cache=cache, pos=pos, train=False)
        with jax.named_scope("proj_mlp"):
            idx = jnp.clip(lengths - 1, 0, x.shape[1] - 1).astype(jnp.int32)
            last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
            logits = last.astype(jnp.float32) @ self._head_w(
                params).astype(jnp.float32)
            return self._mask_pad_logits(logits[:, 0]), cache

    def verify_paged(self, params, tokens, cache, stage, lengths, widths,
                     max_pages=None):
        """Speculative verify (``repro.spec``): score ``tokens`` (S, W) —
        the last accepted token followed by draft tokens, right-padded —
        in ONE dispatch.  Row s's chunk sits at logical positions
        ``lengths[s] + [0, widths[s])`` of its slot; queries attend the
        slot's paged prefix plus the chunk itself causally
        (models/attention.attention_verify_paged).  The chunk's K/V is
        written into ``stage`` (a (S, W) bf16 contiguous cache from
        :meth:`init_cache`), NOT the paged pools — the engine commits
        only the accepted prefix afterwards (write-after-accept).
        Returns logits at ALL W positions ((S, W, V)) and the filled
        stage cache; the paged ``cache`` is read-only here.
        ``max_pages`` (static python int) narrows the prefix-extend
        kernel's page grid to the batch's actual prefix span, same as
        :meth:`prefill_paged` (see attention_verify_paged)."""
        combined = _zip_verify_cache(cache, stage)
        pos = (lengths, widths) if max_pages is None \
            else (lengths, widths, max_pages)
        x, out, _ = self.backbone(params, tokens, mode="verify",
                                  cache=combined, pos=pos,
                                  train=False)
        logits = x.astype(jnp.float32) @ self._head_w(params).astype(
            jnp.float32)
        return self._mask_pad_logits(logits), _unzip_stage(out)

    def decode_step(self, params, token, cache, pos):
        """token: (B,) int32; pos: scalar position -> (logits (B,V), cache)."""
        x, cache, _ = self.backbone(params, token[:, None], mode="decode",
                                    cache=cache, pos=pos, train=False)
        with jax.named_scope("proj_mlp"):
            logits = x[:, 0].astype(jnp.float32) @ self._head_w(
                params).astype(jnp.float32)
            return self._mask_pad_logits(logits), cache


# ---------------------------------------------------------------------------
# Speculative-verify cache plumbing (repro.spec)


def _zip_verify_cache(paged: dict, stage: dict) -> dict:
    """Merge a paged cache tree with a contiguous staging tree into the
    per-block ``{"kv": <paged node>, "stage": <contig k/v node>}`` shape
    ``mode="verify"`` consumes.  Both trees share the block structure
    (scan-stacked leaves included); only attention blocks are supported —
    the paged engines gate on attention-only decoders."""
    if isinstance(paged, dict) and "kv" in paged \
            and isinstance(paged["kv"], dict) and "k_pages" in paged["kv"]:
        return {"kv": paged["kv"], "stage": stage["kv"]}
    if isinstance(paged, dict):
        return {k: _zip_verify_cache(paged[k], stage[k]) for k in paged}
    raise NotImplementedError(
        f"verify: unsupported cache leaf {type(paged)}")


def _unzip_stage(out: dict) -> dict:
    """Invert :func:`_zip_verify_cache` on the verify output tree: keep
    only the written staging nodes, renamed back to ``kv`` so the result
    mirrors an :meth:`LM.init_cache` tree (what the engine's commit and
    ``scatter_prefill_cache``-style walkers expect)."""
    if isinstance(out, dict) and "stage" in out:
        return {"kv": out["stage"]}
    if isinstance(out, dict):
        return {k: _unzip_stage(v) for k, v in out.items()}
    raise NotImplementedError(f"verify: unsupported output leaf {type(out)}")


# ---------------------------------------------------------------------------
# Chunked cross-entropy


def chunked_cross_entropy(x: jax.Array, head_w: jax.Array, labels: jax.Array,
                          *, mask: Optional[jax.Array] = None,
                          chunk: int = 1024, unroll: bool = False,
                          n_valid: Optional[int] = None,
                          ) -> Tuple[jax.Array, jax.Array]:
    """Mean next-token CE over (B,S,d) final states without materializing
    full (B,S,V) logits: scans over S-chunks, rematerializing in backward."""
    b, s, d = x.shape
    c = min(chunk, s)
    if s % c != 0:
        c = s  # fallback: single chunk
    nc = s // c
    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)
    mask = mask.astype(jnp.float32)

    v_total = head_w.shape[-1]

    @jax.checkpoint
    def chunk_loss(x_c, labels_c, mask_c):
        logits = x_c.astype(jnp.float32) @ head_w.astype(jnp.float32)
        logits = maybe_constrain(logits, ("pod", "data"), None, "model")
        if n_valid is not None and n_valid < v_total:
            logits = jnp.where(jnp.arange(v_total) < n_valid, logits, -1e30)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        lab = jnp.take_along_axis(logits, labels_c[..., None],
                                  axis=-1)[..., 0]
        ce = (lse - lab) * mask_c
        hit = (jnp.argmax(logits, -1) == labels_c).astype(jnp.float32) * mask_c
        return jnp.sum(ce), jnp.sum(hit)

    def body(carry, args):
        tot, hits = carry
        ce, hit = chunk_loss(*args)
        return (tot + ce, hits + hit), None

    xs = (x.reshape(b, nc, c, d).swapaxes(0, 1),
          labels.reshape(b, nc, c).swapaxes(0, 1),
          mask.reshape(b, nc, c).swapaxes(0, 1))
    (tot, hits), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())), xs,
                                  unroll=nc if unroll else 1)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return tot / denom, hits / denom
