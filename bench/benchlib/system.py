"""The system under test: the program's serving path, built from a
configuration file.

This is the one module of the benchmark that imports the program.  It
maps the configuration's published keys onto the program's
``ModelConfig`` (and refuses, by ``benchlib.config_keys``, the keys it
cannot express), lays the benchmark's weights out as the program keeps
them (and checks that layout against the program's own ``init``), and
builds ``SchedEngine`` the way ``repro.launch.serve --policy fcfs``
does: paged pools, the Pallas decode and prefix-extend kernels, chunked
prefill, the fused decode loop and the prefix cache.
"""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import config_keys


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file.  Raises
    ``config_keys.Unmapped`` on a key that it cannot express."""
    from repro.configs.base import AttentionConfig, ModelConfig
    config_keys.check(cfg)
    h = cfg["num_attention_heads"]
    s = cfg["serving"]
    attn = dict(kind="gqa", num_heads=h,
                num_kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg.get("head_dim") or cfg["hidden_size"] // h,
                rope_theta=float(cfg["rope_theta"]),
                qkv_bias=bool(cfg["attention_bias"]))
    factor = config_keys.rope_factor(cfg)
    if factor != 1.0:
        attn["rope_scaling_factor"] = factor
    try:
        attention = AttentionConfig(**attn)
    except TypeError as e:
        if "rope_scaling_factor" not in attn:
            raise
        # a program with no field for the factor
        raise config_keys.Unmapped("rope_scaling", cfg["rope_scaling"],
                                   str(e)) from e
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        attention=attention,
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm="rmsnorm", norm_eps=float(cfg["rms_norm_eps"]),
        dtype=s["dtype"], kv_cache_dtype=s["kv_dtype"],
        decode_attn_impl="paged_pallas")


def program_params(w: dict, lm) -> dict:
    """The benchmark's weights in the program's parameter tree.  Raises
    when the tree, a shape or a dtype differs from ``lm.init``'s."""
    def lin(name, bias):
        p = {"w": w[name]}
        if bias in w:
            p["b"] = w[bias]
        return p

    blk = {"attn": {"wq": lin("wq", "bq"), "wk": lin("wk", "bk"),
                    "wv": lin("wv", "bv"), "wo": lin("wo", None)},
           "mlp": {"gate": lin("w_gate", None), "up": lin("w_up", None),
                   "down": lin("w_down", None)},
           "norm1": {"scale": w["attn_norm"]},
           "norm2": {"scale": w["mlp_norm"]}}
    p = {"embed": {"w": w["embed"]}, "final_norm": {"scale": w["final_norm"]},
         "layers": {"blk0": blk}}
    if "head" in w:
        p["lm_head"] = {"w": w["head"]}
    want = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), p)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"weight layout differs from the program's: "
                         f"{jax.tree.map(lambda x: x.shape, want)}")
    return p


def build_engine(cfg: dict, params, lm, seed: int):
    """``SchedEngine`` with the configuration's serving settings."""
    from repro.sched import SchedEngine
    s = cfg["serving"]
    return SchedEngine(lm, params, n_slots=s["slots"], max_len=s["max_len"],
                       seed=seed % 2 ** 31, page_size=s["page"],
                       decode_block=s["decode_block"], policy=s["policy"],
                       prefix_cache=s["prefix_cache"],
                       prefill_chunk=s["prefill_chunk"])


def make_lm(cfg: dict):
    from repro.models.model import LM
    return LM(program_config(cfg))


def engine_busy(eng) -> bool:
    from repro.serve.engine import engine_busy as busy
    return busy(eng)


# ---------------------------------------------------------------------------
# warm-up: every program shape the cell's traffic can reach


def pow2_up(n: int, lo: int) -> int:
    """``n`` rounded up to a power of two of at least ``lo``, as the
    scheduler buckets widths, rows and page grids."""
    b = lo
    while b < n:
        b *= 2
    return b


def pow2_buckets(lo: int, hi: int, floor: int) -> list:
    """The buckets that values in ``[lo, hi]`` fall into."""
    out = [pow2_up(lo, floor)]
    while out[-1] < pow2_up(hi, floor):
        out.append(out[-1] * 2)
    return out


def warm_shapes(eng, shapes: dict) -> None:
    """Run the scheduler's staging and continuation programs once at
    each shape in ``shapes`` (``{"stage": [(rows, width)], "chunk":
    [(rows, width, max_pages)]}``), with every row's chunk length 0, so
    that every write lands on the null page and nothing else changes."""
    key = jax.random.PRNGKey(0)
    for rows, width in shapes.get("stage", ()):
        tok, eng.cache = eng._admit_jit(
            eng.params, eng.cache, jnp.zeros((rows, width), jnp.int32),
            jnp.zeros((rows,), jnp.int32), jnp.zeros((rows,), jnp.int32),
            jnp.zeros((rows,), jnp.float32), key)
        np.asarray(tok)
    for rows, width, mp in shapes.get("chunk", ()):
        tok, eng.cache = eng._chunk_jit(
            eng.params, eng.cache, jnp.zeros((rows, width), jnp.int32),
            jnp.zeros((rows,), jnp.int32), jnp.zeros((rows,), jnp.int32),
            jnp.zeros((rows,), jnp.int32), jnp.zeros((rows,), jnp.float32),
            key, max_pages=mp)
        np.asarray(tok)


def shape_plan(cfg: dict, mix: dict, traffic) -> dict:
    """The staging and continuation shapes a cell can reach.

    Staging rows are not bucketed by the scheduler, so their count is
    bounded by ``mix["warm"]["stage_rows"]``; continuation rows are
    bucketed to powers of two up to ``mix["warm"]["chunk_rows"]``.
    Widths and page grids follow from the prompt lengths that the
    traffic holds and the prefixes it shares."""
    s = cfg["serving"]
    page, chunk = s["page"], s["prefill_chunk"]
    w = mix["warm"]
    prompts = [len(r.prompt) for r in
               itertools.chain(traffic.requests, traffic.first_wave)]
    if not prompts:
        return {}
    shortest, longest = min(prompts), max(prompts)
    stage_w = pow2_buckets(min(shortest, chunk), min(longest, chunk), 8)
    plan = {"stage": [(r, c) for r in range(1, w["stage_rows"] + 1)
                      for c in stage_w]}
    if w.get("chunk_rows"):
        # a continuation starts after a prefix-cache hit (whole pages of
        # a shared prefix at least) or after the first chunk
        hits = [r.prefix_len // page * page for r in traffic.requests
                if r.prefix_len >= page]
        lo_start = min(hits + [chunk])
        rows = pow2_buckets(1, w["chunk_rows"], 1)
        widths = pow2_buckets(1, chunk, 8)
        pages = pow2_buckets(-(-lo_start // page), -(-longest // page), 1)
        pages = [p for p in pages if p <= -(-s["max_len"] // page)]
        plan["chunk"] = list(itertools.product(rows, widths, pages))
    return plan


def shapes_used(cfg: dict, steps) -> dict:
    """The staging and continuation shapes that ``steps`` (a work log)
    dispatched, bucketed as the scheduler buckets them, with counts."""
    s = cfg["serving"]
    page, top = s["page"], -(-s["max_len"] // s["page"])
    used: dict = {}
    for st in steps:
        fresh = [w for a, w, _ in st.chunks if a == 0]
        cont = [(a, w) for a, w, _ in st.chunks if a > 0]
        keys = []
        if fresh:
            keys.append(("stage", len(fresh), pow2_up(max(fresh), 8)))
        if cont:
            keys.append(("chunk", pow2_up(len(cont), 1),
                         pow2_up(max(w for _, w in cont), 8),
                         min(pow2_up(-(-max(a for a, _ in cont) // page), 1),
                             top)))
        for k in keys:
            used[k] = used.get(k, 0) + 1
    return dict(sorted(used.items()))

