"""Public op: WKV-6 recurrence with kernel/oracle dispatch.

``use_kernel=True`` targets the Pallas TPU kernel (interpret mode when no
TPU is attached so CPU validation still exercises the kernel body);
otherwise the chunked pure-jnp form — same algorithm, XLA-fused — runs.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.wkv6.ref import wkv6_chunked_ref


def wkv6(r, k, v, logw, u, s0, *, use_kernel: bool = False,
         chunk: int = 64) -> Tuple[jax.Array, jax.Array]:
    """r,k,v,logw: (B,S,H,D); u: (H,D); s0: (B,H,D,D) fp32 state."""
    if use_kernel:
        from repro.kernels.wkv6.wkv6 import wkv6_pallas
        return wkv6_pallas(r, k, v, logw, u, s0, chunk=chunk,
                           interpret=interpret_mode())
    o, s = wkv6_chunked_ref(r.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32), logw.astype(jnp.float32),
                            u.astype(jnp.float32), s0.astype(jnp.float32),
                            chunk=chunk)
    return o, s
