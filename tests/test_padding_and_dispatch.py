"""Exactness guarantees for the beyond-paper perf levers (§Perf):
head / vocab / expert padding and the gather MoE dispatch must be
semantics-preserving, with provably-dead padding (zero grads)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.configs import get_smoke_config
from repro.configs.base import MoEConfig
from repro.models.model import LM
from repro.models.moe import init_moe, moe_apply
from tests.conftest import make_batch


def _widen_attention(params, a, ap):
    """Embed unpadded attention weights into the padded layout
    (group-aware: each kv group keeps its live slots first)."""
    kvh = a.kv_heads_effective()
    gl = a.num_heads // kvh
    gp = ap.heads_padded // kvh
    hd = a.head_dim

    def widen_q(w):
        *lead, d, _ = w.shape
        w4 = w.reshape(*lead, d, kvh, gl, hd)
        pad = jnp.zeros((*lead, d, kvh, gp - gl, hd), w.dtype)
        return jnp.concatenate([w4, pad], axis=-2).reshape(
            *lead, d, ap.heads_padded * hd)

    def widen_o(w):
        *lead, _, d = w.shape
        w4 = w.reshape(*lead, kvh, gl, hd, d)
        pad = jnp.zeros((*lead, kvh, gp - gl, hd, d), w.dtype)
        return jnp.concatenate([w4, pad], axis=-3).reshape(
            *lead, ap.heads_padded * hd, d)

    out = jax.tree.map(lambda x: x, params)
    for blk in out["layers"].values():
        if "attn" in blk:
            blk["attn"]["wq"]["w"] = widen_q(blk["attn"]["wq"]["w"])
            blk["attn"]["wo"]["w"] = widen_o(blk["attn"]["wo"]["w"])
    return out


def test_head_padding_exact_and_dead():
    cfg = get_smoke_config("deepseek-coder-33b").with_(dtype="float32")
    cfgp = cfg.with_(attention=dataclasses.replace(
        cfg.attention, head_pad_multiple=8))
    assert cfgp.attention.heads_padded == 8 and cfg.attention.num_heads == 4
    lmu, lmp = LM(cfg), LM(cfgp)
    pu = lmu.init(jax.random.PRNGKey(0))
    pp = _widen_attention(pu, cfg.attention, cfgp.attention)
    batch = make_batch(cfg, b=2, s=32)
    l1, _ = lmu.loss(pu, batch)
    l2, _ = lmp.loss(pp, batch)
    assert float(l1) == float(l2), "head padding changed the loss"
    # pad slots provably dead: zero grads in wq cols and wo rows
    from repro.models.attention import _pad_head_mask
    (_, _), g = jax.jit(jax.value_and_grad(
        lmp.loss, has_aux=True))(lmp.init(jax.random.PRNGKey(1)), batch)
    mask = np.asarray(_pad_head_mask(cfgp.attention))
    gq = np.asarray(g["layers"]["blk0"]["attn"]["wq"]["w"])
    go = np.asarray(g["layers"]["blk0"]["attn"]["wo"]["w"])
    assert np.abs(gq[..., :, ~mask]).max() == 0.0
    assert np.abs(go[..., ~mask, :]).max() == 0.0


def test_vocab_padding_exact():
    cfg = get_smoke_config("granite-moe-3b-a800m").with_(
        dtype="float32", vocab_size=500)
    cfgp = cfg.with_(vocab_pad_multiple=64)
    assert cfgp.padded_vocab == 512
    lm0, lm1 = LM(cfg), LM(cfgp)
    batch = make_batch(cfg, b=2, s=32)
    l0, _ = lm0.loss(lm0.init(jax.random.PRNGKey(0)), batch)
    l1, _ = lm1.loss(lm1.init(jax.random.PRNGKey(0)), batch)
    assert float(l0) == float(l1)
    lg = lm1.logits(lm1.init(jax.random.PRNGKey(0)), batch["tokens"])
    assert lg.shape[-1] == 512
    assert bool((jnp.argmax(lg, -1) < 500).all()), "pad token predicted"


def test_expert_padding_exact():
    m0 = MoEConfig(num_experts=5, top_k=2, d_ff=32, capacity_factor=5.0)
    m1 = dataclasses.replace(m0, expert_pad_multiple=8)
    assert m1.padded_experts == 8
    p1 = init_moe(jax.random.PRNGKey(0), 16, m1, jnp.float32)
    p0 = {"router": {"w": p1["router"]["w"][:, :5]},
          "gate_e": p1["gate_e"][:5], "up_e": p1["up_e"][:5],
          "down_e": p1["down_e"][:5]}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 16), jnp.float32)
    for impl in ("einsum", "gather"):
        o0, a0 = moe_apply(p0, x, m0, train=True, group_size=32, impl=impl)
        o1, a1 = moe_apply(p1, x, m1, train=True, group_size=32, impl=impl)
        np.testing.assert_allclose(np.asarray(o0), np.asarray(o1), atol=1e-6)
        assert float(a0["moe_lb_loss"]) == pytest.approx(
            float(a1["moe_lb_loss"]), rel=1e-6)


def test_gather_dispatch_matches_einsum():
    m = MoEConfig(num_experts=8, top_k=2, d_ff=64,
                  capacity_factor=8.0, eval_capacity_factor=8.0)
    p = init_moe(jax.random.PRNGKey(0), 32, m, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 32), jnp.float32)
    o1, a1 = moe_apply(p, x, m, train=True, group_size=64, impl="einsum")
    o2, a2 = moe_apply(p, x, m, train=True, group_size=64, impl="gather")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)

    def loss(p, impl):
        return moe_apply(p, x, m, train=True, group_size=64,
                         impl=impl)[0].sum()

    g1 = jax.grad(lambda p: loss(p, "einsum"))(p)
    g2 = jax.grad(lambda p: loss(p, "gather"))(p)
    for k in ("gate_e", "up_e", "down_e"):
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g2[k]),
                                   atol=2e-5)
    np.testing.assert_allclose(np.asarray(g1["router"]["w"]),
                               np.asarray(g2["router"]["w"]), atol=2e-5)


def test_cp_decode_matches_eager():
    """Context-parallel flash-decoding == eager decode on a 1×1 mesh
    (structural + numerical check; multi-device runs in the dry-run)."""
    from repro.models.attention import (attention_decode,
                                        attention_decode_cp, init_attention)
    from repro.configs.base import AttentionConfig
    from repro.sharding.ctx import use_mesh
    a = AttentionConfig(kind="gqa", num_heads=4, num_kv_heads=2,
                        head_dim=16, rope_theta=10_000.0)
    p = init_attention(jax.random.PRNGKey(0), 32, a, jnp.float32)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    b = 2
    cache = {"k": jnp.zeros((b, 64, 2, 16), jnp.float32),
             "v": jnp.zeros((b, 64, 2, 16), jnp.float32)}
    # put some history into the cache
    hist = jax.random.normal(jax.random.PRNGKey(1), (b, 8, 2, 16))
    cache = {"k": cache["k"].at[:, :8].set(hist),
             "v": cache["v"].at[:, :8].set(hist * 0.5)}
    x = jax.random.normal(jax.random.PRNGKey(2), (b, 1, 32), jnp.float32)
    pos = jnp.full((b,), 8, jnp.int32)
    y1, c1 = attention_decode(p, x, a, cache, pos)
    with use_mesh(mesh):
        y2, c2 = attention_decode_cp(p, x, a, cache, pos, mesh=mesh)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(c1["k"]), np.asarray(c2["k"]),
                               atol=1e-6)
