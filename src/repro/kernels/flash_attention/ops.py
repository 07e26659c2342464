"""Public op: flash attention with kernel/oracle dispatch."""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.ref import attention_ref


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    use_kernel: bool = True) -> jax.Array:
    """q: (B,S,H,D); k,v: (B,T,KH,D) -> (B,S,H,D)."""
    if use_kernel:
        from repro.kernels.flash_attention.flash_attention import (
            flash_attention_pallas)
        bq = 128 if q.shape[1] % 128 == 0 else q.shape[1]
        bk = 128 if k.shape[1] % 128 == 0 else k.shape[1]
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      block_q=bq, block_k=bk,
                                      interpret=interpret_mode())
    return attention_ref(q, k, v, causal=causal, window=window)
