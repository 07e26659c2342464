"""Pallas TPU W8A8 matmul: int8×int8 → int32 MXU accumulate, fused dequant.

Two kernel shapes:

* :func:`int8_matmul_pallas` — the tiled prefill/training shape.  Grid =
  (M/bm, N/bn, K/bk), K minor-most; the int32 accumulator lives in VMEM
  scratch across K steps and per-row/per-col fp32 scales are applied once
  on the final K step (one multiply per output element instead of per K
  tile).  Default tiles 256×256×512: a 256×512 int8 x-tile (128 KiB) +
  512×256 w-tile (128 KiB) + 256×256 int32 acc (256 KiB) sit well inside
  the ~16 MiB VMEM while giving the MXU full 128-lane contractions.

* :func:`w8a8_decode_matmul_pallas` / :func:`fp8_decode_matmul_pallas` —
  the decode/verify shape: M = live slots (tiny, ragged) while K/N are
  model-sized, so M is NOT tiled.  Grid = (N/bn, K/bk), K minor-most; the
  whole skinny-M activation block rides along every grid step, the W8A8
  variant quantizes it per K-tile in-register against precomputed per-row
  scales (dynamic activation quant fused in — no int8 activation copy is
  ever materialized), and the epilogue applies per-row × per-channel
  scales plus the optional bias once on the final K step.  The fp8
  variant upcasts the e4m3 weight tile inside the kernel and keeps the
  per-channel scale out of the contraction entirely (it commutes), the
  same fused-dequant idiom as the paged-attention pool reads.

Off-TPU execution of the decode kernels (``interpret``): decode calls
are tiny (a few microseconds of real work), so ``pl.pallas_call``'s
interpreter — a masked grid loop with per-step dynamic slicing — costs
more than the matmul it emulates and would make the fused serving path
LOSE to the jnp ref path on CPU CI.  ``interpret=True`` therefore
evaluates the kernel's own tile program directly as unrolled jnp ops
(same tiling, same op order, bit-identical results — the grid is static
and small at decode shapes); ``interpret="pallas"`` forces the real
``pl.pallas_call`` interpreter and exists so tests can pin the kernel
against its emulation.  On TPU (``interpret=False``) the compiled
kernel runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _int8_dot(a, b):
    """int8 x int8 -> int32 on the MXU.  Precision is pinned: an ambient
    ``default_matmul_precision("highest")`` would otherwise ask Mosaic for
    an fp32-contract integer matmul, which it refuses."""
    return jax.lax.dot(a, b, precision=jax.lax.Precision.DEFAULT,
                       preferred_element_type=jnp.int32)


def _int8_mm_kernel(xq_ref, wq_ref, xs_ref, ws_ref, o_ref, acc, *, nk: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    # int8 operands straight into the MXU, int32 accumulate (Mosaic has no
    # int32 x int32 matmul)
    acc[...] += _int8_dot(xq_ref[...], wq_ref[...])

    @pl.when(ki == nk - 1)
    def _flush():
        o_ref[...] = (acc[...].astype(jnp.float32) * xs_ref[...]
                      * ws_ref[...]).astype(o_ref.dtype)


def _row(v: jax.Array) -> jax.Array:
    """(N,) per-channel vector as a (1, N) f32 row: 2-D blocks are the ones
    whose layout Mosaic and XLA agree on."""
    return v.astype(jnp.float32).reshape(1, -1)


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_n", "block_k", "out_dtype", "interpret"))
def int8_matmul_pallas(xq, wq, x_scale, w_scale, *, block_m: int = 256,
                       block_n: int = 256, block_k: int = 512,
                       out_dtype=jnp.bfloat16,
                       interpret: bool = False) -> jax.Array:
    """xq: (M,K) int8; wq: (K,N) int8; x_scale: (M,); w_scale: (N,).
    Scales enter as (M,1) / (1,N) columns and rows: a 1-D block's layout
    does not match the one XLA gives the operand on TPU."""
    m, k = xq.shape
    n = wq.shape[1]
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    grid = (m // bm, n // bn, k // bk)

    return pl.pallas_call(
        functools.partial(_int8_mm_kernel, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((bm, 1), lambda mi, ni, ki: (mi, 0)),
            pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(xq, wq, x_scale.astype(jnp.float32).reshape(m, 1), _row(w_scale))


# ---------------------------------------------------------------------------
# Decode-shaped variants: skinny ragged M, grid over N/K only


def _w8a8_decode_emulate(x, wq, x_scale, w_scale, bias, *, bn, bk,
                         out_dtype):
    """The decode kernel's tile program, unrolled as jnp ops (see module
    docstring).  Mirrors :func:`_w8a8_decode_kernel` step for step —
    per-K-tile in-register activation quant, int32 tile accumulate,
    scale+bias epilogue — so results are bit-identical to the kernel."""
    m, k = x.shape
    n = wq.shape[1]
    xs = x_scale.astype(jnp.float32)
    cols = []
    for ni in range(n // bn):
        acc = jnp.zeros((m, bn), jnp.int32)
        for ki in range(k // bk):
            xq = jnp.clip(
                jnp.round(x[:, ki * bk:(ki + 1) * bk].astype(jnp.float32)
                          / xs[:, None]), -127, 127).astype(jnp.int8)
            wt = wq[ki * bk:(ki + 1) * bk, ni * bn:(ni + 1) * bn]
            if bk * 127 * 127 < 2 ** 24:
                # every partial sum of int8 products is an integer below
                # 2^24 when bk <= 1040, so the f32 GEMM — the backend's
                # fast path, unlike int32 GEMM — computes the tile dot
                # EXACTLY and the int32 accumulate stays bit-identical
                # to the kernel's
                acc += jax.lax.dot(
                    xq.astype(jnp.float32), wt.astype(jnp.float32),
                    preferred_element_type=jnp.float32).astype(jnp.int32)
            else:
                acc += jax.lax.dot(
                    xq.astype(jnp.int32), wt.astype(jnp.int32),
                    preferred_element_type=jnp.int32)
        ws = w_scale[ni * bn:(ni + 1) * bn].astype(jnp.float32)
        b = bias[ni * bn:(ni + 1) * bn].astype(jnp.float32)
        y = acc.astype(jnp.float32) * xs[:, None] * ws[None, :] + b[None, :]
        cols.append(y.astype(out_dtype))
    return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)


def _fp8_decode_emulate(x, wq, w_scale, bias, *, bn, bk, out_dtype):
    """:func:`_fp8_decode_kernel`'s tile program as unrolled jnp ops —
    per-K-tile f32 partial sums in kernel order, scale epilogue."""
    m, k = x.shape
    n = wq.shape[1]
    cols = []
    for ni in range(n // bn):
        acc = jnp.zeros((m, bn), jnp.float32)
        for ki in range(k // bk):
            acc += jax.lax.dot(
                x[:, ki * bk:(ki + 1) * bk].astype(jnp.float32),
                wq[ki * bk:(ki + 1) * bk,
                   ni * bn:(ni + 1) * bn].astype(jnp.float32),
                preferred_element_type=jnp.float32)
        ws = w_scale[ni * bn:(ni + 1) * bn].astype(jnp.float32)
        b = bias[ni * bn:(ni + 1) * bn].astype(jnp.float32)
        cols.append((acc * ws[None, :] + b[None, :]).astype(out_dtype))
    return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)


def _w8a8_decode_kernel(x_ref, wq_ref, xs_ref, ws_ref, b_ref, o_ref, acc,
                        *, nk: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    # dynamic per-row activation quant, fused: the raw (m, bk) activation
    # tile is quantized in-register against the precomputed full-row
    # scale — elementwise identical to ref.quantize_rowwise, so the int32
    # accumulate (and therefore the output) is bit-identical to the
    # jnp oracle's
    xs = xs_ref[...]                                  # (m, 1) f32
    xq = jnp.clip(jnp.round(x_ref[...].astype(jnp.float32) / xs),
                  -127, 127).astype(jnp.int8)
    acc[...] += _int8_dot(xq, wq_ref[...])

    @pl.when(ki == nk - 1)
    def _flush():
        y = acc[...].astype(jnp.float32) * xs * ws_ref[...]
        o_ref[...] = (y + b_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_n", "block_k", "out_dtype", "interpret"))
def w8a8_decode_matmul_pallas(x, wq, x_scale, w_scale, bias, *,
                              block_n: int = 256, block_k: int = 512,
                              out_dtype=jnp.bfloat16,
                              interpret: bool = False) -> jax.Array:
    """x: (M,K) bf16/f32 RAW activations; wq: (K,N) int8; x_scale: (M,)
    per-row quant scales (amax/127, precomputed — the full row is needed
    before K is tiled); w_scale: (N,); bias: (N,) fp32 (zeros when the
    linear has none).  M is the whole (skinny) batch, untiled.

    ``interpret``: True = unrolled jnp tile emulation (off-TPU default,
    bit-identical); "pallas" = pl.pallas_call interpreter (tests);
    False = compiled TPU kernel."""
    m, k = x.shape
    n = wq.shape[1]
    bn, bk = min(block_n, n), min(block_k, k)
    assert n % bn == 0 and k % bk == 0
    grid = (n // bn, k // bk)
    if interpret is True:
        return _w8a8_decode_emulate(x, wq, x_scale, w_scale, bias,
                                    bn=bn, bk=bk, out_dtype=out_dtype)

    return pl.pallas_call(
        functools.partial(_w8a8_decode_kernel, nk=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, bk), lambda ni, ki: (0, ki)),
            pl.BlockSpec((bk, bn), lambda ni, ki: (ki, ni)),
            pl.BlockSpec((m, 1), lambda ni, ki: (0, 0)),
            pl.BlockSpec((1, bn), lambda ni, ki: (0, ni)),
            pl.BlockSpec((1, bn), lambda ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda ni, ki: (0, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((m, bn), jnp.int32)],
        interpret=interpret == "pallas",
    )(x, wq, x_scale.astype(jnp.float32).reshape(m, 1), _row(w_scale),
      _row(bias))


def _fp8_decode_kernel(x_ref, wq_ref, ws_ref, b_ref, o_ref, acc, *, nk: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    # the e4m3 weight tile is upcast in-register (streamed from HBM at
    # 1 byte/elem); the per-channel scale stays OUT of the contraction —
    # it commutes with the K sum and is applied once in the epilogue
    acc[...] += jax.lax.dot(
        x_ref[...].astype(jnp.float32), wq_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _flush():
        o_ref[...] = (acc[...] * ws_ref[...] + b_ref[...]).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_n", "block_k", "out_dtype", "interpret"))
def fp8_decode_matmul_pallas(x, wq, w_scale, bias, *, block_n: int = 256,
                             block_k: int = 512, out_dtype=jnp.bfloat16,
                             interpret: bool = False) -> jax.Array:
    """x: (M,K) bf16/f32; wq: (K,N) float8_e4m3; w_scale: (N,); bias: (N,)
    fp32 (zeros when absent).  Weight-only fp8: activations stay wide.
    ``interpret`` as in :func:`w8a8_decode_matmul_pallas`."""
    m, k = x.shape
    n = wq.shape[1]
    bn, bk = min(block_n, n), min(block_k, k)
    assert n % bn == 0 and k % bk == 0
    grid = (n // bn, k // bk)
    if interpret is True:
        return _fp8_decode_emulate(x, wq, w_scale, bias,
                                   bn=bn, bk=bk, out_dtype=out_dtype)

    return pl.pallas_call(
        functools.partial(_fp8_decode_kernel, nk=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, bk), lambda ni, ki: (0, ki)),
            pl.BlockSpec((bk, bn), lambda ni, ki: (ki, ni)),
            pl.BlockSpec((1, bn), lambda ni, ki: (0, ni)),
            pl.BlockSpec((1, bn), lambda ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda ni, ki: (0, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((m, bn), jnp.float32)],
        interpret=interpret == "pallas",
    )(x, wq, _row(w_scale), _row(bias))
