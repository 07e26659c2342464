"""Random weights from ``--seed``, in the benchmark's own layout.

One jitted call makes every weight on the device, in the type it is
served in.  The layout is the plain one of a decoder of the Llama /
Qwen2 family (``x @ W`` for every projection, layers stacked on a
leading axis), shared by the reference; the program's own layout is made
from it in :mod:`benchlib.system`.  Nothing here imports the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchlib.flops import Dims

LAYER_KEYS = ("attn_norm", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
              "mlp_norm", "w_gate", "w_up", "w_down")


def base_key(seed: int) -> jax.Array:
    """A PRNG key for any whole ``seed`` of up to 62 bits (two 31-bit
    halves folded in, so no 32-bit overflow)."""
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, seed % 2 ** 31)
    return jax.random.fold_in(k, (seed // 2 ** 31) % 2 ** 31)


def shapes(m: Dims, *, tied: bool, bias: bool) -> dict:
    L, d, f = m.layers, m.d, m.f
    q, kv = m.h * m.hd, m.kh * m.hd
    s = {"embed": (m.vocab, d), "final_norm": (d,),
         "attn_norm": (L, d), "wq": (L, d, q), "wk": (L, d, kv),
         "wv": (L, d, kv), "wo": (L, q, d), "mlp_norm": (L, d),
         "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d)}
    if bias:
        s.update(bq=(L, q), bk=(L, kv), bv=(L, kv))
    if not tied:
        s["head"] = (d, m.vocab)
    return s


def make_weights(cfg: dict, m: Dims, seed: int, dtype=jnp.bfloat16) -> dict:
    """Every weight of ``cfg`` from ``seed``, made on the device in one
    jitted call.  Projections are normal with std ``1/sqrt(fan_in)``,
    the embedding normal with std ``init.embed_std``, biases normal with
    std ``init.bias_std``, norm scales ``1 + init.norm_jitter * normal``.
    """
    init = cfg["init"]
    tied = bool(cfg["tie_word_embeddings"])
    shp = shapes(m, tied=tied, bias=bool(cfg["attention_bias"]))

    def std(name, shape):
        if name == "embed":
            return init["embed_std"]
        if name in ("bq", "bk", "bv"):
            return init["bias_std"]
        return shape[-2] ** -0.5

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shp.items())):
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, shape, jnp.float32)
            if name.endswith("norm"):
                out[name] = (1.0 + init["norm_jitter"] * z).astype(dtype)
            else:
                out[name] = (std(name, shape) * z).astype(dtype)
        return out

    return jax.jit(make)(base_key(seed))
