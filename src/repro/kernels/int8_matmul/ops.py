"""Public ops: quantized matmuls with kernel/oracle dispatch.

Shape handling: the Pallas kernels require every tiled dimension to be a
multiple of its block.  Rather than degrading the block to the full
dimension on a non-multiple (the old fallback — a VMEM blowup on large
ragged shapes), dispatch zero-pads the operands up to the block multiple
and slices the result: padded K columns contribute exact zeros to the
contraction, padded M rows / N columns are discarded, and pad scales are
ones so no 0/0 ever forms.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.int8_matmul.ref import (
    int4_matmul_ref, int8_matmul_ref, quantize_rowwise)


def _block(dim: int, pref: int) -> int:
    """Block size for one dimension: the preferred tile, or the whole
    (small) dimension when it fits inside one tile."""
    return min(pref, dim)


def _pad_dim(a: jax.Array, axis: int, mult: int, value=0) -> jax.Array:
    """Zero/one-pad ``axis`` of ``a`` up to a multiple of ``mult``."""
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


def int8_matmul(xq, wq, x_scale, w_scale, *, out_dtype=jnp.bfloat16,
                use_kernel: bool = False) -> jax.Array:
    if use_kernel:
        from repro.kernels.int8_matmul.int8_matmul import int8_matmul_pallas
        m, k = xq.shape
        n = wq.shape[1]
        bm, bn, bk = _block(m, 256), _block(n, 256), _block(k, 512)
        xq = _pad_dim(_pad_dim(xq, 0, bm), 1, bk)
        wq = _pad_dim(_pad_dim(wq, 0, bk), 1, bn)
        x_scale = _pad_dim(x_scale, 0, bm, value=1)
        w_scale = _pad_dim(w_scale, 0, bn, value=1)
        y = int8_matmul_pallas(xq, wq, x_scale, w_scale, block_m=bm,
                               block_n=bn, block_k=bk, out_dtype=out_dtype,
                               interpret=interpret_mode())
        return y[:m, :n]
    return int8_matmul_ref(xq, wq, x_scale, w_scale, out_dtype=out_dtype)


def int8_matmul_dynamic(x, wq, w_scale, *, use_kernel: bool = False):
    """Quantize activations on the fly (W8A8 serving path)."""
    shp = x.shape
    x2 = x.reshape(-1, shp[-1])
    xq, xs = quantize_rowwise(x2)
    y = int8_matmul(xq, wq, xs, w_scale, out_dtype=x.dtype,
                    use_kernel=use_kernel)
    return y.reshape(*shp[:-1], wq.shape[1])


def w8a8_matmul_decode(x2, wq, w_scale, *, bias=None,
                       out_dtype=None) -> jax.Array:
    """Decode-shaped fused W8A8: x2 (M,K) RAW activations with M = live
    slots (skinny/ragged, untiled), wq (K,N) int8.  The kernel quantizes
    the activation tile in-register (per-row scales precomputed here —
    the row amax needs the full K before tiling) and applies per-row ×
    per-channel scales + optional bias once in the epilogue.  Bit-
    identical to ``int8_matmul_dynamic``'s ref path."""
    from repro.kernels.int8_matmul.int8_matmul import w8a8_decode_matmul_pallas
    m, k = x2.shape
    n = wq.shape[1]
    out_dtype = out_dtype or x2.dtype
    amax = jnp.max(jnp.abs(x2.astype(jnp.float32)), axis=-1)
    xs = jnp.maximum(amax, 1e-8) / 127.0
    b = jnp.zeros((n,), jnp.float32) if bias is None \
        else bias.astype(jnp.float32)
    bn, bk = _block(n, 256), _block(k, 512)
    x2 = _pad_dim(x2, 1, bk)
    wq = _pad_dim(_pad_dim(wq, 0, bk), 1, bn)
    w_scale = _pad_dim(w_scale, 0, bn, value=1)
    b = _pad_dim(b, 0, bn)
    y = w8a8_decode_matmul_pallas(x2, wq, xs, w_scale, b, block_n=bn,
                                  block_k=bk, out_dtype=out_dtype,
                                  interpret=interpret_mode())
    return y[:, :n]


def fp8_matmul_decode(x2, wq, w_scale, *, bias=None,
                      out_dtype=None) -> jax.Array:
    """Decode-shaped weight-only fp8: x2 (M,K) wide activations, wq (K,N)
    e4m3 streamed at 1 byte/elem and upcast in-register; the per-channel
    scale stays out of the contraction (epilogue only)."""
    from repro.kernels.int8_matmul.int8_matmul import fp8_decode_matmul_pallas
    m, k = x2.shape
    n = wq.shape[1]
    out_dtype = out_dtype or x2.dtype
    b = jnp.zeros((n,), jnp.float32) if bias is None \
        else bias.astype(jnp.float32)
    bn, bk = _block(n, 256), _block(k, 512)
    x2 = _pad_dim(x2, 1, bk)
    wq = _pad_dim(_pad_dim(wq, 0, bk), 1, bn)
    w_scale = _pad_dim(w_scale, 0, bn, value=1)
    b = _pad_dim(b, 0, bn)
    y = fp8_decode_matmul_pallas(x2, wq, w_scale, b, block_n=bn, block_k=bk,
                                 out_dtype=out_dtype,
                                 interpret=interpret_mode())
    return y[:, :n]


def int4_matmul(x, packed, w_scale) -> jax.Array:
    """Weight-only int4 (W4A16); XLA fuses the unpack+dequant into the gemm."""
    shp = x.shape
    x2 = x.reshape(-1, shp[-1])
    y = int4_matmul_ref(x2, packed, w_scale)
    return y.reshape(*shp[:-1], packed.shape[1])
