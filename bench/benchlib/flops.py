"""Operations and bytes that the algorithm needs, from shapes alone.

Counts cover the work a request requires and nothing the program adds:
no rows or columns of a padded bucket, no prompt tokens served from the
prefix cache, no lock-step decode rows of slots that are idle or still
prefilling.  A query at absolute position ``p`` attends ``p + 1`` keys
(causal).  Elementwise work (norms, softmax, rotary) is not counted.
"""
from __future__ import annotations

import dataclasses

DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int                    # hidden size
    f: int                    # feed-forward width
    h: int                    # query heads
    kh: int                   # key/value heads
    hd: int                   # head size
    layers: int               # layers held on this chip
    vocab: int
    kv_bytes: int = 2         # bytes per stored K or V element
    act_bytes: int = 2        # bytes per activation element

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        h = cfg["num_attention_heads"]
        return cls(d=cfg["hidden_size"], f=cfg["intermediate_size"], h=h,
                   kh=cfg["num_key_value_heads"],
                   hd=cfg.get("head_dim") or cfg["hidden_size"] // h,
                   layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
                   kv_bytes=DTYPE_BYTES[cfg["serving"]["kv_dtype"]],
                   act_bytes=DTYPE_BYTES[cfg["serving"]["dtype"]])


def linear_flops_per_token(m: Dims) -> int:
    """Matmul operations of one token through one layer: q, k, v, o and
    the gated feed-forward (gate, up, down)."""
    qkvo = m.d * m.h * m.hd * 2 + 2 * m.d * m.kh * m.hd
    return 2 * (qkvo + 3 * m.d * m.f)


def attention_flops(m: Dims, keys: int) -> int:
    """Scores and weighted values of one query over ``keys`` keys, in
    one layer."""
    return 4 * m.h * m.hd * keys


def head_flops(m: Dims) -> int:
    """The vocabulary projection of one position."""
    return 2 * m.d * m.vocab


def chunk_keys(start: int, width: int) -> int:
    """Keys attended by a chunk of ``width`` queries at positions
    ``start .. start + width - 1``."""
    return width * start + width * (width + 1) // 2


# ---------------------------------------------------------------------------
# kernels (one call = one layer)


def paged_decode_cost(m: Dims, keys: list) -> tuple:
    """(flops, bytes) of one decode-kernel call over slots whose queries
    attend ``keys[i]`` keys: every key and value read once, the query
    read and the output written once per slot."""
    flops = sum(attention_flops(m, n) for n in keys)
    kv = sum(2 * m.kh * m.hd * n * m.kv_bytes for n in keys)
    qo = len(keys) * 2 * m.h * m.hd * m.act_bytes
    return flops, kv + qo


def prefix_extend_cost(m: Dims, rows: list) -> tuple:
    """(flops, bytes) of one prefix-extend call over rows ``(start,
    width)``: each row's ``width`` queries attend its ``start`` cached
    keys and, causally, the chunk itself.  The cached keys and values are
    read once; the chunk's q, k, v are read and its output written."""
    flops = sum(attention_flops(m, chunk_keys(s, w)) for s, w in rows)
    prefix = sum(2 * m.kh * m.hd * s * m.kv_bytes for s, _ in rows)
    chunk = sum(w * (2 * m.h * m.hd + 2 * m.kh * m.hd) * m.act_bytes
                for _, w in rows)
    return flops, prefix + chunk


# ---------------------------------------------------------------------------
# the whole model (all layers held here, and the head)


def prefill_flops(m: Dims, start: int, width: int, logits: bool) -> int:
    """Model operations of a prefill chunk of ``width`` tokens at
    ``start``; ``logits`` when the chunk ends the prompt (one position
    through the head)."""
    per_layer = linear_flops_per_token(m) * width \
        + attention_flops(m, chunk_keys(start, width))
    return m.layers * per_layer + (head_flops(m) if logits else 0)


def decode_flops(m: Dims, keys: int) -> int:
    """Model operations of one decode token whose query attends ``keys``
    keys."""
    return m.layers * (linear_flops_per_token(m) + attention_flops(m, keys)) \
        + head_flops(m)


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(seconds, bound): the larger of operations over peak rate and
    bytes over peak bandwidth, and which of the two it is."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
