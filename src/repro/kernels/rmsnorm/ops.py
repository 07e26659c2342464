"""Public op: fused RMSNorm with kernel/oracle dispatch."""
from __future__ import annotations

import jax

from repro.kernels import interpret_mode
from repro.kernels.rmsnorm.ref import rmsnorm_ref


def rmsnorm(x, scale, *, eps: float = 1e-5,
            use_kernel: bool = False) -> jax.Array:
    if use_kernel:
        from repro.kernels.rmsnorm.rmsnorm import rmsnorm_pallas
        rows = 1
        for s in x.shape[:-1]:
            rows *= s
        br = 256 if rows % 256 == 0 else rows
        return rmsnorm_pallas(x, scale, eps=eps, block_rows=br,
                              interpret=interpret_mode())
    return rmsnorm_ref(x, scale, eps)
