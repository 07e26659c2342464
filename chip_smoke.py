"""Bring-up check: the paged serving path on a TPU at qwen2-1.5b width.

    python chip_smoke.py              # one chip: bf16 and int8 phases
    python chip_smoke.py --chips 4    # 2x2 mesh vs device 0, nothing else

One process drives the main serving path the way ``repro.launch.serve
--policy fcfs`` builds it — ``SchedEngine`` over paged KV pools, the
Pallas paged-decode and prefix-extend kernels, chunked prefill and the
fused decode loop — at the published width of qwen2-1.5b (28 layers,
d_model 1536, GQA 12/2, head_dim 128, vocab 151936) with random weights
from ``--seed``.  Before each drive, one chunked prefill and one decode
step of the paged path are compared, as logits, with the plain float32
forward pass (``LM.logits``, jnp, no cache) under
``default_matmul_precision("highest")``; that comparison runs at full
width with the depth cut to ``REF_LAYERS``.

The run fails (non-zero exit, no result line) when JAX finds no TPU,
when any phase fails, or when ``src/`` is not beside this file.  The
last line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-1.5b"
N_SLOTS, MAX_LEN, PAGE = 16, 4096, 64
# prefill chunk is the scheduler default, 8 pages = 512 tokens: a 256
# prompt is one staging chunk, 768 adds one prefix-extend chunk, 1536
# adds two.  Three lengths keep the set of compiled shapes small.
PROMPT_LENS = (256, 768, 1536)
N_REQUESTS, MAX_NEW = 16, 64
REF_LAYERS = 2          # depth of the float32 logit comparison (width full)
PARITY_LAYERS = 4       # depth of the four-chip parity phase (width full)
# Logit tolerances, as max |paged - reference| / max |reference|.  The
# paged path stores K/V in the pool dtype and the reference keeps them in
# float32: bf16 storage rounds each K/V element by up to 2^-9 relative,
# int8 storage by up to half a step of the page's amax/127.  Everything
# else is float32 at "highest" on both sides.
TOL = {"bf16": 4e-3, "int8": 6e-2}
TOL_MESH = 1e-4         # sharded vs device 0: same math, other reduce order
# the one-chip phases: bf16 pools, then int8 pools with W8A8 weights
PHASES = {"bf16": {}, "int8": {"kv": "int8", "quant": "int8"}}


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit reports its retrieval time)."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def model_config(*, layers=None, dtype="bfloat16", kv="bfloat16",
                 quant="bf16"):
    """The serving config ``repro.launch.serve`` builds for ``ARCH``."""
    from repro.configs import get_config
    cfg = get_config(ARCH).with_(kv_cache_dtype=kv, quant=quant, dtype=dtype)
    return cfg if layers is None else cfg.with_(num_layers=layers)


def init_params(lm, seed: int):
    import jax
    from repro.quant.qops import quantize_tree
    params = lm.init(jax.random.PRNGKey(seed))
    return quantize_tree(params, quant=lm.cfg.quant)


def make_prompts(seed: int, vocab: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (PROMPT_LENS[i % len(PROMPT_LENS)],))
            .tolist() for i in range(N_REQUESTS)]


# ---------------------------------------------------------------------------
# logits: paged path vs the float32 forward pass


def paged_logits(lm, params, tokens, *, chunk: int, mesh=None):
    """Logits of the paged path for ``tokens`` (1, 2·chunk) and one decode
    step after them, as the scheduler computes them: chunk 1 through the
    staging prefill and the page scatter, chunk 2 through the
    prefix-extend kernel, the step through the decode kernel.  Returns
    ((V,) prefill logits, (V,) decode logits, fed token)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.serve.paged import scatter_prefill_cache, set_block_table_rows
    from repro.sharding.ctx import use_mesh
    if mesh is not None:
        from repro.serve.paged import paged_cache_shardings
        from repro.sharding.rules import make_param_shardings
        lm = type(lm)(lm.cfg.with_(model_parallel=int(mesh.shape["model"]),
                                   seq_parallel=True))
        params = jax.device_put(params, make_param_shardings(params, mesh))
    n = tokens.shape[1]
    pps = -(-(n + 1) // PAGE)
    cache = lm.init_paged_cache(1, pps + 1, pps, page_size=PAGE)
    cache = set_block_table_rows(cache, np.zeros(1, np.int32),
                                 np.arange(1, pps + 1, dtype=np.int32)[None])
    if mesh is not None:
        cache = jax.device_put(cache, paged_cache_shardings(cache, mesh))
    slot = jnp.zeros((1,), jnp.int32)
    width = jnp.full((1,), chunk, jnp.int32)

    def first(params, cache, toks):
        tmp = lm.init_cache(1, chunk, kv_dtype="bfloat16")
        _, tmp = lm.prefill(params, toks, tmp, lengths=width)
        return scatter_prefill_cache(cache, tmp, slot, width)

    def second(params, cache, toks):
        return lm.prefill_paged(params, toks, cache, slot, width, width)

    def step(params, cache, tok):
        return lm.decode_step(params, tok, cache,
                              jnp.full((1,), n, jnp.int32))[0]

    ctx = use_mesh(mesh) if mesh is not None else contextlib.nullcontext()
    with jax.default_matmul_precision("highest"), ctx:
        cache = jax.jit(first)(params, cache, tokens[:, :chunk])
        lp, cache = jax.jit(second)(params, cache, tokens[:, chunk:])
        fed = jnp.argmax(lp, axis=-1).astype(jnp.int32)
        ld = jax.jit(step)(params, cache, fed)
    return np.asarray(lp[0]), np.asarray(ld[0]), int(fed[0])


def reference_logits(lm, params, tokens, fed: int):
    """Float32 forward pass over ``tokens`` + ``fed`` (no cache, no
    kernels, jnp quantized matmuls): logits at the last prompt position
    and at the fed token."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    ref = type(lm)(lm.cfg.with_(quant_matmul_impl="ref"))
    full = jnp.concatenate([tokens, jnp.full((1, 1), fed, jnp.int32)], 1)
    with jax.default_matmul_precision("highest"):
        out = jax.jit(ref.logits)(params, full)
    return np.asarray(out[0, -2]), np.asarray(out[0, -1])


def rel_err(a, b) -> float:
    import numpy as np
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def check_logits(kind: str, seed: int, chunk: int) -> None:
    """Paged prefill + decode logits vs the float32 forward pass, at full
    width and ``REF_LAYERS`` depth, for the ``kind`` phase's config."""
    import jax
    import numpy as np
    from repro.models.model import LM
    lm = LM(model_config(layers=REF_LAYERS, dtype="float32", **PHASES[kind]))
    params = init_params(lm, seed)
    toks = np.random.default_rng(seed + 7).integers(
        0, lm.cfg.vocab_size, (1, 2 * chunk))
    toks = jax.numpy.asarray(toks, jax.numpy.int32)
    lp, ld, fed = paged_logits(lm, params, toks, chunk=chunk)
    rp, rd = reference_logits(lm, params, toks, fed)
    ep, ed = rel_err(lp, rp), rel_err(ld, rd)
    log(f"{kind}: paged vs float32 reference ({REF_LAYERS} layers, full "
        f"width): prefill rel err {ep:.3e}, decode rel err {ed:.3e} "
        f"(tolerance {TOL[kind]:g})")
    if not (np.isfinite(lp).all() and np.isfinite(ld).all()):
        raise AssertionError(f"{kind}: non-finite paged logits")
    if max(ep, ed) > TOL[kind]:
        raise AssertionError(f"{kind}: paged logits differ from the "
                             f"reference by {max(ep, ed):.3e}")


# ---------------------------------------------------------------------------
# the serving drive


def build_engine(lm, params, *, seed: int, mesh=None):
    """``SchedEngine`` as ``repro.launch.serve --policy fcfs`` builds it."""
    from repro.sched import SchedEngine
    return SchedEngine(lm, params, n_slots=N_SLOTS, max_len=MAX_LEN,
                       seed=seed, page_size=PAGE, decode_block=8, mesh=mesh,
                       policy="fcfs", prefix_cache=True)


def drive(eng, prompts):
    """Serve every prompt greedily to completion; returns the streams."""
    ids = [eng.submit(p, max_new_tokens=MAX_NEW, temperature=0.0)
           for p in prompts]
    done = eng.run_to_completion()
    vocab = eng.lm.cfg.vocab_size
    streams = []
    for rid in ids:
        req = done[rid]
        toks = req.out_tokens
        if req.outcome != "ok" or len(toks) != MAX_NEW \
                or not all(0 <= t < vocab for t in toks):
            raise AssertionError(f"request {rid}: outcome {req.outcome}, "
                                 f"{len(toks)} tokens")
        streams.append(list(toks))
    return streams


def decode_has_kernels(eng) -> bool:
    """Whether the engine's compiled decode program holds Pallas kernels."""
    import jax
    import numpy as np

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)

    s = eng.n_slots
    args = (jax.tree.map(sds, eng.params), jax.tree.map(sds, eng.cache),
            np.zeros(s, np.int32), np.zeros(s, np.int32), np.zeros(s, bool),
            np.zeros(s, np.int32), np.zeros(s, np.float32),
            jax.random.PRNGKey(0))
    with eng._mesh_ctx():
        text = eng._decode_jit.lower(*args).compile().as_text()
    return "tpu_custom_call" in text


def pool_bytes_per_device(cache) -> dict:
    """KV pool bytes each device holds (block tables excluded)."""
    import jax
    out: dict = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        if "block_table" in jax.tree_util.keystr(path):
            continue
        for sh in leaf.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return out


def serve_phase(kind: str, seed: int, clock: CompileClock) -> int:
    """One full-width drive; returns the tokens served."""
    import jax
    from repro.models.model import LM
    t0 = time.perf_counter()
    c0 = clock.seconds
    lm = LM(model_config(**PHASES[kind]))
    params = init_params(lm, seed)
    eng = build_engine(lm, params, seed=seed)
    prompts = make_prompts(seed, lm.cfg.vocab_size)
    t1 = time.perf_counter()
    streams = drive(eng, prompts)
    t2 = time.perf_counter()
    tokens = sum(len(s) for s in streams)
    if not decode_has_kernels(eng):
        raise AssertionError(f"{kind}: no tpu_custom_call in the decode "
                             "program")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"{kind}: set-up {t1 - t0:.2f}s, drive {t2 - t1:.2f}s "
        f"(compile {clock.seconds - c0:.2f}s within set-up + drive), "
        f"{len(streams)} requests, {tokens} tokens served, "
        f"{eng.sync_count} host syncs")
    log(f"{kind}: compiled decode program contains tpu_custom_call")
    log(f"{kind}: peak_bytes_in_use {peak}")
    return tokens


# ---------------------------------------------------------------------------
# four chips: a 2x2 mesh against device 0


def mesh_phase(seed: int) -> None:
    """``SchedEngine`` on ``make_host_mesh(model=2)`` vs the same requests
    on device 0: paged logits and greedy streams, float32 at full width
    and ``PARITY_LAYERS`` depth (float32 keeps random-weight near-ties
    from flipping on a reduction order)."""
    import jax
    import numpy as np
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import LM
    mesh = make_host_mesh(model=2)
    log(f"mesh {dict(mesh.shape)} over {len(jax.devices())} devices")
    lm = LM(model_config(layers=PARITY_LAYERS, dtype="float32"))
    params = init_params(lm, seed)
    toks = np.random.default_rng(seed + 7).integers(
        0, lm.cfg.vocab_size, (1, 1024))
    toks = jax.numpy.asarray(toks, jax.numpy.int32)
    one = paged_logits(lm, params, toks, chunk=512)
    two = paged_logits(lm, params, toks, chunk=512, mesh=mesh)
    ep, ed = rel_err(two[0], one[0]), rel_err(two[1], one[1])
    log(f"mesh vs device 0 logits: prefill rel err {ep:.3e}, decode rel "
        f"err {ed:.3e} (tolerance {TOL_MESH:g})")
    if max(ep, ed) > TOL_MESH or one[2] != two[2]:
        raise AssertionError("sharded logits differ from device 0")
    prompts = make_prompts(seed, lm.cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        single = build_engine(lm, params, seed=seed)
        ref = drive(single, prompts)
        del single
        gc.collect()                  # engines sit in reference cycles
        sharded = build_engine(lm, params, seed=seed, mesh=mesh)
        got = drive(sharded, prompts)
    same = sum(a == b for a, b in zip(got, ref))
    log(f"mesh vs device 0 greedy streams: {same}/{len(ref)} identical")
    per_dev = pool_bytes_per_device(sharded.cache)
    log(f"mesh pool bytes per device: {per_dev}")
    if same != len(ref):
        raise AssertionError("sharded streams differ from device 0")
    if len(per_dev) != len(jax.devices()) \
            or max(per_dev.values()) >= sum(per_dev.values()) / 2:
        raise AssertionError("pools are not split across the chips")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the 2x2-mesh parity phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"FAIL: no TPU found (JAX platform {dev.platform!r}); "
            "this check does not fall back to the CPU")
        return 1
    n = len(jax.devices())
    if n < args.chips:
        log(f"FAIL: --chips {args.chips} but {n} device(s)")
        return 1
    log(f"device_kind {dev.device_kind!r}, {n} device(s), compile cache "
        f"{cache_dir}")
    clock = CompileClock()
    if args.chips == 4:
        mesh_phase(args.seed)
    else:
        chunk = 8 * PAGE
        for kind in PHASES:
            check_logits(kind, args.seed, chunk)
            serve_phase(kind, args.seed, clock)
            # an engine's metric callbacks close over it, so its pools and
            # weights outlive the phase until the cycle collector runs
            gc.collect()
    log(f"total {time.perf_counter() - t_start:.2f}s, compile "
        f"{clock.seconds:.2f}s, persistent-cache hits {clock.hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
