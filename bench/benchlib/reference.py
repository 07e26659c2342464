"""The plain reference, and the comparison that decides ``correct``.

The reference is the decoder written out in ``jax.numpy`` at float32
under ``default_matmul_precision("highest")``: embedding, then per layer
``x += o(attn(rmsnorm(x)))`` and ``x += down(silu(gate(h)) * up(h))``
with ``h = rmsnorm(x)``, rotary embeddings on q and k (halves rotated;
position ``p`` turns pair ``i`` by ``p / factor * theta ** (-2i / hd)``,
where ``factor`` is the file's linear ``rope_scaling`` factor and 1
without one), grouped-query attention (query head ``i`` reads kv head
``i // (H / KH)``), causal softmax, a final rmsnorm and the head (the
embedding's transpose when tied).  No cache, no kernel, no batching: one
sequence at a time, padded at its end to one length so that one program
serves every request.  It imports nothing of the program and takes only the
benchmark's weights, made anew from the seed.

What is compared: for each served token of a sample of finished
requests, the gap by which the reference's logit of that token lies
below the reference's best logit at that position, and the widest such
gap.  A greedy server that computes what the reference computes serves
a token with a gap near 0 at every position.

The control is the reference put in the program's place one precision
step down from the configuration's bfloat16, with activations rounded
to bfloat16 between operations as the program's are: ``fp8`` stores
every projection's and the head's weights as float8 e4m3 (scaled per
output channel) and keys and values as e4m3 (scaled per token and
head), the arithmetic of the program's own fp8 arm.  Its gap is that of
the token it ranks first, read under the float32 reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.config_keys import rope_factor
from benchlib.flops import Dims
from benchlib.weights import LAYER_KEYS

Q_BLOCK = 512        # attention query rows per block
ROW_BLOCK = 256      # head rows per block


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, theta, factor):
    t, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    pos = jnp.arange(t, dtype=jnp.float32)
    if factor != 1.0:           # linear position interpolation
        pos = pos / factor
    ang = pos[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _f8(x, axis):
    """float8 e4m3 round trip, scaled along ``axis`` to its range."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, mode):
    if mode == "fp8":           # weights only, per output channel
        return x @ _f8(w, 0)
    return x @ w


def _kv(x, mode):
    """Keys or values as the mode stores them (per token and head)."""
    if mode == "fp8":
        return _f8(x, -1)
    return x


def _bf(x, mode):
    return x if mode == "f32" else \
        x.astype(jnp.bfloat16).astype(jnp.float32)


def _attend(q, k, v):
    t, h, hd = q.shape
    kh = k.shape[1]
    qg = q.reshape(t, kh, h // kh, hd)
    keys = jnp.arange(t)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * Q_BLOCK, Q_BLOCK, 0)
        s = jnp.einsum("bkgd,tkd->kgbt", qb, k) / jnp.sqrt(jnp.float32(hd))
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(keys[None, :] <= rows[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgbt,tkd->bkgd", p, v).reshape(Q_BLOCK, h * hd)

    out = jax.lax.map(block, jnp.arange(t // Q_BLOCK))
    return out.reshape(t, h * hd)


def _hidden(w, tokens, m: Dims, eps, theta, factor, mode):
    f32 = jnp.float32
    x = jnp.take(w["embed"], tokens, axis=0).astype(f32)
    layer_w = {k: w[k] for k in LAYER_KEYS if k in w}

    def layer(x, lw):
        lw = {k: v.astype(f32) for k, v in lw.items()}
        t = x.shape[0]
        h = _bf(_rms(x, lw["attn_norm"], eps), mode)
        q = _mm(h, lw["wq"], mode) + lw.get("bq", 0.0)
        k = _mm(h, lw["wk"], mode) + lw.get("bk", 0.0)
        v = _mm(h, lw["wv"], mode) + lw.get("bv", 0.0)
        q = _rope(_bf(q, mode).reshape(t, m.h, m.hd), theta, factor)
        k = _kv(_rope(_bf(k, mode).reshape(t, m.kh, m.hd), theta, factor),
                mode)
        v = _kv(_bf(v, mode).reshape(t, m.kh, m.hd), mode)
        o = _bf(_attend(q, k, v), mode)
        x = _bf(x + _mm(o, lw["wo"], mode), mode)
        h = _bf(_rms(x, lw["mlp_norm"], eps), mode)
        g = _bf(_mm(h, lw["w_gate"], mode), mode)
        u = _bf(_mm(h, lw["w_up"], mode), mode)
        x = _bf(x + _mm(_bf(jax.nn.silu(g) * u, mode), lw["w_down"], mode),
                mode)
        return x, None

    x, _ = jax.lax.scan(layer, x, layer_w)
    return _rms(x, w["final_norm"].astype(f32), eps)


def _head_w(w):
    return w["head"] if "head" in w else w["embed"].T


def _rows(x, fn):
    """``fn`` over ``x`` in blocks of ``ROW_BLOCK`` rows."""
    n = x.shape[0] // ROW_BLOCK

    def block(i):
        return fn(i, jax.lax.dynamic_slice_in_dim(x, i * ROW_BLOCK,
                                                  ROW_BLOCK, 0))

    return jax.lax.map(block, jnp.arange(n))


@functools.lru_cache(maxsize=None)
def _programs(m: Dims, eps: float, theta: float, factor: float):
    def ref(w, tokens, targets):
        """Best logit at each position, and the logits of ``targets``
        (one row of tokens per reading: served, then each control's)."""
        x = _hidden(w, tokens, m, eps, theta, factor, "f32")
        hw = _head_w(w).astype(jnp.float32)

        def fn(i, xb):
            lg = xb @ hw
            tb = jax.lax.dynamic_slice_in_dim(targets, i * ROW_BLOCK,
                                              ROW_BLOCK, 1)
            at = jnp.take_along_axis(lg[None], tb[:, :, None], 2)[..., 0]
            return jnp.max(lg, -1), at

        best, at = _rows(x, fn)
        return best.reshape(-1), jnp.moveaxis(at, 1, 0).reshape(
            targets.shape)

    def control_top(w, tokens, mode):
        x = _hidden(w, tokens, m, eps, theta, factor, mode)
        hw = _head_w(w).astype(jnp.float32)
        return _rows(x, lambda i, xb: jnp.argmax(
            _mm(_bf(xb, mode), hw, mode), -1).astype(jnp.int32)).reshape(-1)

    return jax.jit(ref), jax.jit(control_top, static_argnames=("mode",))


def gaps(w: dict, m: Dims, cfg: dict, samples, length: int, *,
         controls=()) -> dict:
    """Gaps of ``samples`` (``[(prompt, served tokens)]``) under the
    float32 reference, each sequence padded to ``length`` tokens; and,
    for each mode in ``controls`` ("fp8"), the gaps of the
    tokens that the control ranks first at the same positions."""
    ref, ctrl_top = _programs(m, float(cfg["rms_norm_eps"]),
                              float(cfg["rope_theta"]), rope_factor(cfg))
    pad = -(-length // Q_BLOCK) * Q_BLOCK
    names = ["served"] + list(controls)
    out = {k: [] for k in names}
    with jax.default_matmul_precision("highest"):
        for prompt, served in samples:
            p, n = len(prompt), len(served)
            seq = np.zeros(pad, np.int32)
            seq[:p] = prompt
            seq[p:p + n - 1] = served[:-1]
            pos = np.arange(p - 1, p - 1 + n)
            tgt = np.zeros(pad, np.int32)
            tgt[pos] = served
            tokens = jnp.asarray(seq)
            rows = [jnp.asarray(tgt)] + [ctrl_top(w, tokens, mode=c)
                                         for c in controls]
            best, at = (np.asarray(a) for a in
                        ref(w, tokens, jnp.stack(rows)))
            for k, row in zip(names, at):
                out[k].append(best[pos] - row[pos])
    res = {}
    for k, v in out.items():
        if v:
            g = np.concatenate(v)
            res[k] = {"widest_gap": float(g.max()), "tokens": int(g.size),
                      "exact_share": float(np.mean(g == 0))}
    return res
