"""Per-kernel allclose sweeps: Pallas (interpret=True on CPU) vs the
pure-jnp ref.py oracle, across shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.int8_matmul.int8_matmul import (fp8_decode_matmul_pallas,
                                                   w8a8_decode_matmul_pallas)
from repro.kernels.int8_matmul.ops import (fp8_matmul_decode, int4_matmul,
                                           int8_matmul, int8_matmul_dynamic,
                                           w8a8_matmul_decode)
from repro.kernels.int8_matmul.ref import (int4_matmul_ref, int8_matmul_ref,
                                           pack_int4, quantize_colwise,
                                           quantize_int4_colwise,
                                           quantize_rowwise, unpack_int4)
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.wkv6.ops import wkv6
from repro.kernels.wkv6.ref import wkv6_chunked_ref, wkv6_scan_ref


# ---------------------------------------------------------------------------
# flash attention


@pytest.mark.parametrize("b,s,h,kvh,d", [
    (1, 128, 4, 4, 64),      # MHA
    (2, 256, 4, 2, 64),      # GQA
    (1, 256, 4, 1, 64),      # MQA
    (2, 512, 8, 2, 128),     # bigger head dim
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(b, s, h, kvh, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kvh, d), dtype)
    v = jax.random.normal(ks[2], (b, s, kvh, d), dtype)
    o = flash_attention(q, k, v, causal=True)
    ref = attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [64, 128])
def test_flash_attention_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    b, s, h, d = 2, 256, 4, 64
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, d))
    o = flash_attention(q, k, v, causal=True, window=window)
    ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_chunked_attention_matches_flash():
    """The pure-jnp chunked path (XLA fallback) == the Pallas kernel."""
    from repro.models.attention import chunked_attention
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    b, s, h, kvh, d = 2, 256, 4, 2, 64
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kvh, d))
    v = jax.random.normal(ks[2], (b, s, kvh, d))
    o_kernel = flash_attention(q, k, v, causal=True)
    qg = q.reshape(b, s, kvh, h // kvh, d)
    o_chunk = chunked_attention(qg, k, v, causal=True, window=None,
                                scale=1.0 / np.sqrt(d), q_block=64,
                                kv_block=64).reshape(b, s, h, d)
    np.testing.assert_allclose(np.asarray(o_chunk), np.asarray(o_kernel),
                               atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# rmsnorm


@pytest.mark.parametrize("shape", [(4, 64, 256), (2, 128, 512), (1, 8, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(shape, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, shape, dtype)
    w = jax.random.normal(k2, shape[-1:], dtype)
    o = rmsnorm(x, w)
    ref = rmsnorm_ref(x, w)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# int8 / int4 matmul


@pytest.mark.parametrize("m,k,n", [(128, 256, 128), (256, 512, 256),
                                   (64, 128, 512)])
def test_int8_matmul(m, k, n):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (m, k))
    w = jax.random.normal(k2, (k, n))
    xq, xs = quantize_rowwise(x)
    wq, ws = quantize_colwise(w)
    o = int8_matmul(xq, wq, xs, ws)
    ref = int8_matmul_ref(xq, wq, xs, ws)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(ref, np.float32),
                               atol=1e-2, rtol=1e-2)


def test_int4_pack_roundtrip():
    w4 = jnp.asarray(np.random.default_rng(0).integers(-8, 8, (64, 32)),
                     jnp.int8)
    packed = pack_int4(w4)
    assert packed.shape == (32, 32)
    np.testing.assert_array_equal(np.asarray(unpack_int4(packed)),
                                  np.asarray(w4))


def test_int4_matmul():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (64, 128))
    w = jax.random.normal(k2, (128, 64))
    packed, scale = quantize_int4_colwise(w)
    o = int4_matmul(x, packed, scale)
    ref = int4_matmul_ref(x, packed, scale)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(ref, np.float32),
                               atol=1e-2, rtol=1e-2)
    # int4 RTN error vs the dense matmul stays statistically bounded:
    # per-element dequant err ~0.1 accumulates ~sqrt(K)·E|x| over K=128
    dense = x @ w
    err = np.abs(np.asarray(o, np.float32) - np.asarray(dense)).mean()
    assert err < 2.0


# ---------------------------------------------------------------------------
# decode-shaped W8A8 / fp8 matmul (skinny ragged M — the serving shapes)


def _decode_operands(m, k, n, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    w = rng.standard_normal((k, n))
    ws = np.abs(w).max(axis=0) / 127.0
    wq = jnp.asarray(np.clip(np.round(w / ws), -127, 127), jnp.int8)
    bias = jnp.asarray(rng.standard_normal((n,)), jnp.float32)
    return x, wq, jnp.asarray(ws, jnp.float32), bias


# M = live decode slots (1 = single request, 3 = ragged batch, 8 = full);
# K/N sweep model-ish, ragged, and GQA-projection (K > N) dims
DECODE_SHAPES = [(1, 64, 64), (3, 160, 96), (8, 512, 768), (4, 64, 32),
                 (8, 768, 128)]


@pytest.mark.parametrize("m,k,n", DECODE_SHAPES)
@pytest.mark.parametrize("with_bias", [False, True])
def test_w8a8_decode_matmul_matches_ref(m, k, n, with_bias):
    """Fused decode kernel == the jnp oracle BIT-identically: the
    in-kernel per-tile activation quant is elementwise identical to
    quantize_rowwise, the int32 accumulate is exact, and the epilogue
    is the same f32 expression."""
    x, wq, ws, bias = _decode_operands(m, k, n)
    b = bias if with_bias else None
    o = w8a8_matmul_decode(x, wq, ws, bias=b)
    ref = int8_matmul_dynamic(x, wq, ws)
    if b is not None:
        ref = (ref.astype(jnp.float32) + b[None, :]).astype(ref.dtype)
    if b is None:
        np.testing.assert_array_equal(np.asarray(o), np.asarray(ref))
    else:
        # the ref adds bias AFTER the bf16 cast (epilogue adds before):
        # one rounding step apart, not bit-comparable
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("m,k,n", DECODE_SHAPES)
def test_fp8_decode_matmul_matches_ref(m, k, n):
    x, wq8, ws, bias = _decode_operands(m, k, n)
    rng = np.random.default_rng(1)
    w = rng.standard_normal((k, n))
    ws = jnp.asarray(np.abs(w).max(axis=0) / 448.0, jnp.float32)
    wq8 = jnp.asarray(w / np.asarray(ws), jnp.float8_e4m3fn)
    o = fp8_matmul_decode(x, wq8, ws, bias=bias)
    ref = ((x.astype(jnp.float32) @ wq8.astype(jnp.float32))
           * ws[None, :] + bias[None, :]).astype(x.dtype)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("m,k,n", [(3, 160, 96), (8, 512, 768)])
def test_decode_kernels_emulation_matches_pallas(m, k, n):
    """The off-TPU tile emulation (interpret=True) is pinned bit-exactly
    against the real kernel program run under the pl.pallas_call
    interpreter (interpret="pallas") — the emulation may never drift
    from what the TPU kernel computes."""
    x, wq, ws, bias = _decode_operands(m, k, n)
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    xs = jnp.maximum(amax, 1e-8) / 127.0
    # small blocks when they divide the shape — exercises a multi-tile
    # grid (several K partial tiles, N concat) instead of one big tile
    bkw = dict(block_n=96, block_k=80) if (n % 96 == 0 and k % 80 == 0) \
        else {}
    emu = w8a8_decode_matmul_pallas(x, wq, xs, ws, bias, interpret=True,
                                    **bkw)
    pal = w8a8_decode_matmul_pallas(x, wq, xs, ws, bias,
                                    interpret="pallas", **bkw)
    np.testing.assert_array_equal(np.asarray(emu), np.asarray(pal))
    rng = np.random.default_rng(2)
    w = rng.standard_normal((k, n))
    ws8 = jnp.asarray(np.abs(w).max(axis=0) / 448.0, jnp.float32)
    wq8 = jnp.asarray(w / np.asarray(ws8), jnp.float8_e4m3fn)
    emu8 = fp8_decode_matmul_pallas(x, wq8, ws8, bias, interpret=True)
    pal8 = fp8_decode_matmul_pallas(x, wq8, ws8, bias, interpret="pallas")
    np.testing.assert_array_equal(np.asarray(emu8), np.asarray(pal8))


@pytest.mark.parametrize("m,k,n", [(130, 520, 320), (65, 192, 96),
                                   (257, 513, 129)])
def test_int8_matmul_kernel_ragged_pad(m, k, n):
    """Non-multiple shapes go through pad-to-tile dispatch (the old
    fallback degraded the block to the whole dimension — a VMEM blowup
    at large ragged M) and still match the oracle exactly."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(k1, (m, k))
    w = jax.random.normal(k2, (k, n))
    xq, xs = quantize_rowwise(x)
    wq, ws = quantize_colwise(w)
    o = int8_matmul(xq, wq, xs, ws, use_kernel=True)
    ref = int8_matmul_ref(xq, wq, xs, ws)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(ref))


def test_int4_matmul_decode_shapes():
    """W4A16 at skinny decode M: ref-path only, but the serving dispatch
    hits it — keep the drift bound pinned at these shapes too."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    x = jax.random.normal(k1, (3, 128), jnp.bfloat16)
    w = jax.random.normal(k2, (128, 96))
    packed, scale = quantize_int4_colwise(w)
    o = int4_matmul(x, packed, scale)
    assert o.shape == (3, 96) and o.dtype == x.dtype
    dense = np.asarray(x, np.float32) @ np.asarray(w)
    err = np.abs(np.asarray(o, np.float32) - dense).mean()
    assert err < 2.0


# ---------------------------------------------------------------------------
# wkv6


@pytest.mark.parametrize("b,t,h,d", [(1, 64, 2, 16), (2, 128, 4, 16),
                                     (2, 256, 2, 32)])
def test_wkv6_chunked_vs_scan(b, t, h, d):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    r = jax.random.normal(ks[0], (b, t, h, d))
    k = jax.random.normal(ks[1], (b, t, h, d)) * 0.3
    v = jax.random.normal(ks[2], (b, t, h, d))
    logw = -jnp.abs(jax.random.normal(ks[3], (b, t, h, d))) * 0.1 - 0.01
    u = jax.random.normal(ks[4], (h, d)) * 0.1
    s0 = jnp.zeros((b, h, d, d))
    o1, s1 = wkv6_chunked_ref(r, k, v, logw, u, s0, chunk=32)
    o2, s2 = wkv6_scan_ref(r, k, v, logw, u, s0)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4,
                               rtol=1e-4)


def test_wkv6_kernel_nonzero_state():
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    b, t, h, d = 2, 128, 2, 16
    r = jax.random.normal(ks[0], (b, t, h, d))
    k = jax.random.normal(ks[1], (b, t, h, d)) * 0.3
    v = jax.random.normal(ks[2], (b, t, h, d))
    logw = -jnp.abs(jax.random.normal(ks[3], (b, t, h, d))) * 0.1 - 0.01
    u = jax.random.normal(ks[4], (h, d)) * 0.1
    s0 = jax.random.normal(ks[5], (b, h, d, d)) * 0.2
    o1, s1 = wkv6(r, k, v, logw, u, s0)
    o2, s2 = wkv6_scan_ref(r, k, v, logw, u, s0)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4,
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# interpret mode follows the backend


@pytest.mark.parametrize("backend,expect", [("cpu", True), ("tpu", False),
                                            ("gpu", RuntimeError)])
def test_interpret_mode_only_on_cpu(monkeypatch, backend, expect):
    """Kernels interpret on the CPU, compile on TPU, and refuse any
    other backend instead of serving through the interpreter there."""
    from repro import kernels
    monkeypatch.setattr(kernels.jax, "default_backend", lambda: backend)
    if expect is RuntimeError:
        with pytest.raises(RuntimeError, match="gpu"):
            kernels.interpret_mode()
    else:
        assert kernels.interpret_mode() is expect
