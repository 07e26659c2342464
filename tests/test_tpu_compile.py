"""Compile-only tests: the serving path's Pallas kernels at qwen2-1.5b
widths (H=12, KH=2, D=128, page 64, d_model 1536, d_ff 8960), compiled
for a described TPU v5e with no chip attached.  Nothing runs; these
catch what interpret mode cannot — block shapes the TPU compiler
refuses, SMEM/VMEM overflow — before any chip time is spent.

The ``*_pallas`` functions are called with ``interpret=False`` directly:
the ``ops.py`` entry points ask the backend, which here is the CPU.
The topology is described inside a module fixture (never at import), so
every xdist worker collects the same tests and only the worker running
this file loads the TPU compiler.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

FP8 = jnp.float8_e4m3fn
H, KH, D, PAGE = 12, 2, 128, 64
N_PAGES = 4097                      # >= 4096 pages plus the null page


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def sds(one_chip):
    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _pool_args(sds, dtype, lead=()):
    """One layer's pool and scales, or a stack of them (``lead`` = (L,))."""
    pool = sds(lead + (N_PAGES, KH, PAGE, D), dtype)
    scales = () if dtype == jnp.bfloat16 \
        else (sds(lead + (N_PAGES, KH), jnp.float32),) * 2
    return pool, scales


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8, FP8],
                         ids=["bf16", "int8", "fp8"])
def test_paged_decode_compiles(sds, dtype):
    from repro.kernels.paged_attention.paged_attention import (
        paged_attention_pallas)
    s_n, p_n = 16, 64
    pool, scales = _pool_args(sds, dtype)
    text = _compiled_text(
        paged_attention_pallas, sds((s_n, H, D), jnp.bfloat16), pool, pool,
        sds((s_n, p_n), jnp.int32), sds((s_n,), jnp.int32), *scales)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("width", [512, 5], ids=["chunk512", "verify5"])
@pytest.mark.parametrize("dtype,q_dtype", [
    (jnp.bfloat16, jnp.bfloat16), (jnp.int8, jnp.bfloat16),
    (jnp.bfloat16, jnp.float32),    # the float32 logit checks' queries
], ids=["bf16", "int8", "f32q"])
def test_prefix_extend_compiles(sds, width, dtype, q_dtype):
    from repro.kernels.paged_attention.paged_attention import (
        paged_prefix_extend_pallas)
    s_n, p_n = 4, 64
    pool, scales = _pool_args(sds, dtype)
    chunk = sds((s_n, width, KH, D), q_dtype)
    per_slot = sds((s_n,), jnp.int32)
    text = _compiled_text(
        paged_prefix_extend_pallas, sds((s_n, width, H, D), q_dtype),
        pool, pool, sds((s_n, p_n), jnp.int32), per_slot, chunk, chunk,
        per_slot, *scales)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8, FP8],
                         ids=["bf16", "int8", "fp8"])
def test_paged_decode_compiles_on_a_layer_stack(sds, dtype):
    """The decode kernel as the layer loop calls it: the whole
    (L, N, KH, page, D) stack, its (L, N, KH) scales, a layer index."""
    from repro.kernels.paged_attention.paged_attention import (
        paged_attention_pallas)
    s_n, p_n = 48, 64
    pool, scales = _pool_args(sds, dtype, (28,))
    text = _compiled_text(
        paged_attention_pallas, sds((s_n, H, D), jnp.bfloat16), pool, pool,
        sds((s_n, p_n), jnp.int32), sds((s_n,), jnp.int32),
        *(scales or (None, None)), sds((), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("width", [512, 5], ids=["chunk512", "verify5"])
@pytest.mark.parametrize("dtype,q_dtype", [
    (jnp.bfloat16, jnp.bfloat16), (jnp.int8, jnp.bfloat16),
    (jnp.bfloat16, jnp.float32),
], ids=["bf16", "int8", "f32q"])
def test_prefix_extend_compiles_on_a_layer_stack(sds, width, dtype, q_dtype):
    from repro.kernels.paged_attention.paged_attention import (
        paged_prefix_extend_pallas)
    s_n, p_n = 4, 64
    pool, scales = _pool_args(sds, dtype, (28,))
    chunk = sds((s_n, width, KH, D), q_dtype)
    per_slot = sds((s_n,), jnp.int32)
    text = _compiled_text(
        paged_prefix_extend_pallas, sds((s_n, width, H, D), q_dtype),
        pool, pool, sds((s_n, p_n), jnp.int32), per_slot, chunk, chunk,
        per_slot, *(scales or (None, None)), sds((), jnp.int32))
    assert "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# the serving programs update the paged pool in place

SLOTS, MAX_LEN, LAYERS = 48, 4096, 2
_INST = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")
_COMP = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$")
# what may produce a pool-sized value: the donated parameters, the loops
# that carry them, views of them, and the token/chunk scatter into them
_POOL_OPS = {"parameter", "get-tuple-element", "bitcast", "tuple", "while",
             "conditional", "call", "scatter"}


def _pool_moves(text: str, pool_dims) -> list:
    """Instructions of an optimised HLO module that make a buffer the
    shape of one layer's pool or of the stacked pool other than by
    scattering into it (copies, slices, update-slices, fresh
    allocations...), and pool-shaped values in any layout but the
    default one the paged kernels read."""
    dims = ",".join(map(str, pool_dims))
    pool = re.compile(r"\[(?:\d+,)?%s\](?:\{([\d,]+))?" % dims)
    roots, comp, found = {}, None, []
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INST.match(line)
        if not m:
            continue
        name, shape, op, rest = m.groups()
        if line.lstrip().startswith("ROOT"):
            roots[comp] = op
        if pool.search(shape):
            found.append((name, shape, op, rest))
    moves = []
    for name, shape, op, rest in found:
        for layout in pool.findall(shape):
            dims_order = [int(i) for i in layout.split(",")] if layout else []
            if dims_order != sorted(dims_order, reverse=True):
                moves.append(f"{name}: {op} in layout {{{layout}}}")
        if op == "fusion":
            called = re.search(r"calls=%([\w.\-]+)", rest).group(1)
            if roots.get(called) != "scatter":
                moves.append(f"{name}: fusion of {roots.get(called)}")
        elif op not in _POOL_OPS:
            moves.append(f"{name}: {op} {shape[:60]}")
    return moves


@pytest.fixture(scope="module")
def qwen_engine():
    """A SchedEngine at qwen2-1.5b widths (2 layers, 4096-token
    vocabulary) over the benchmark's 48 x 4096 slots, page 64, built on
    the CPU with abstract weights and a two-page pool; its programs are
    lowered below at the full pool's shapes."""
    from repro.configs.qwen2_1_5b import config
    from repro.models.model import LM
    from repro.sched import SchedEngine
    lm = LM(config().with_(num_layers=LAYERS, vocab_size=4096))
    return SchedEngine(lm, lm.abstract_params(), n_slots=SLOTS,
                       max_len=MAX_LEN, page_size=PAGE, decode_block=8,
                       n_pages=2, prefill_chunk=512)


@pytest.mark.parametrize("program", ["decode", "chunk", "admit"])
def test_paged_programs_update_the_pool_in_place(topo, sds, qwen_engine,
                                                 monkeypatch, program):
    """The engine's own jitted decode block, prefix-extend chunk program
    and staging admission, compiled for a v5e with the cache donated: no
    copy, slice, update-slice or fresh buffer of a layer's pool or of
    the stack, the scatters in the kernels' layout, the pools aliased
    from input to output and no pool-sized temporary."""
    from repro.kernels.paged_attention import ops
    from repro.kvcache import paged_pool_shape, pool_bytes
    eng = qwen_engine
    pps, n_pages = paged_pool_shape(SLOTS, MAX_LEN, PAGE)

    def abstract(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

    params = abstract(eng.params)
    cache = abstract(jax.eval_shape(lambda: eng.lm.init_paged_cache(
        SLOTS, n_pages, pps, page_size=PAGE)))
    i32 = jnp.int32
    key = sds((2,), jnp.uint32)

    def vec(dtype):
        return sds((SLOTS,), dtype)

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    with jax.default_device(topo.devices[0]):
        if program == "decode":
            lowered = eng._decode_jit.lower(
                params, cache, vec(i32), vec(i32), vec(jnp.bool_),
                vec(i32), vec(jnp.float32), key)
        elif program == "chunk":
            lowered = eng._chunk_jit.lower(
                params, cache, sds((4, 512), i32), sds((4,), i32),
                sds((4,), i32), sds((4,), i32), sds((4,), jnp.float32), key,
                max_pages=pps)
        else:
            lowered = eng._admit_jit.lower(
                params, cache, sds((4, 512), i32), sds((4,), i32),
                sds((4,), i32), sds((4,), jnp.float32), key)
        compiled = lowered.compile()
    assert _pool_moves(compiled.as_text(), (n_pages, KH, PAGE, D)) == []
    mem = compiled.memory_analysis()
    layer_pool = n_pages * KH * PAGE * D * 2
    assert mem.alias_size_in_bytes >= pool_bytes(cache)
    assert mem.temp_size_in_bytes < layer_pool


# (M, K, N) as kernels/int8_matmul/ops.py pads them: the up/gate
# projection, and the down projection with K 8960 padded to 9216 (a
# multiple of the 512 K block)
DECODE_MKN = [(8, 1536, 8960), (8, 9216, 1536)]


# the float32 logit checks run under default_matmul_precision("highest"),
# which must not reach the int8 MXU dots
PRECISIONS = pytest.mark.parametrize("precision", ["default", "highest"])


@PRECISIONS
@pytest.mark.parametrize("m,k,n", DECODE_MKN, ids=["up", "down"])
def test_w8a8_decode_matmul_compiles(sds, m, k, n, precision):
    from repro.kernels.int8_matmul.int8_matmul import (
        w8a8_decode_matmul_pallas)
    vec = sds((n,), jnp.float32)
    with jax.default_matmul_precision(precision):
        text = _compiled_text(
            lambda *a: w8a8_decode_matmul_pallas(*a, interpret=False),
            sds((m, k), jnp.bfloat16), sds((k, n), jnp.int8),
            sds((m,), jnp.float32), vec, vec)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,k,n", DECODE_MKN, ids=["up", "down"])
def test_fp8_decode_matmul_compiles(sds, m, k, n):
    from repro.kernels.int8_matmul.int8_matmul import (
        fp8_decode_matmul_pallas)
    vec = sds((n,), jnp.float32)
    text = _compiled_text(
        lambda *a: fp8_decode_matmul_pallas(*a, interpret=False),
        sds((m, k), jnp.bfloat16), sds((k, n), FP8), vec, vec)
    assert "tpu_custom_call" in text


@PRECISIONS
def test_int8_prefill_matmul_compiles(sds, precision):
    """The tiled W8A8 kernel that int8 prefill chunks (M > 128) take."""
    from repro.kernels.int8_matmul.int8_matmul import int8_matmul_pallas
    m, k, n = 512, 1536, 8960
    with jax.default_matmul_precision(precision):
        text = _compiled_text(
            lambda *a: int8_matmul_pallas(*a, interpret=False),
            sds((m, k), jnp.int8), sds((k, n), jnp.int8),
            sds((m,), jnp.float32), sds((n,), jnp.float32))
    assert "tpu_custom_call" in text


def _paged_cache(page, n_pages, dtype):
    """Abstract stacked model cache (28 layers) with one paged node."""
    kv = jax.ShapeDtypeStruct((28, n_pages, KH, page, D), dtype)
    node = {"k_pages": kv, "v_pages": kv,
            "block_table": jax.ShapeDtypeStruct((28, 16, 64), jnp.int32)}
    if dtype != jnp.bfloat16:
        node["k_scales"] = node["v_scales"] = jax.ShapeDtypeStruct(
            (28, n_pages, KH), jnp.float32)
    return {"blk0": {"kv": node}}


@pytest.mark.parametrize("page,n_pages,refused", [
    (64, 1025, False),              # the chip check's pool, chunk 512
    (4096, 17, True),               # a (3072 x 4096) f32 score tile: VMEM
], ids=["page64", "page4096"])
def test_engine_kernel_check(topo, monkeypatch, page, n_pages, refused):
    """Engines compile their paged kernels at construction on TPU: a pool
    the compiler refuses fails there, naming the page size."""
    from repro.kernels.paged_attention import ops
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    cache = _paged_cache(page, n_pages, jnp.int8)
    with jax.default_device(topo.devices[0]):
        if not refused:
            ops.check_paged_kernels(cache, H, jnp.bfloat16, widths=(512,))
            return
        with pytest.raises(ValueError, match=f"page_size={page}"):
            ops.check_paged_kernels(cache, H, jnp.bfloat16, decode=False,
                                    widths=(512,))
