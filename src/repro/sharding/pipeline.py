"""GPipe-style pipeline parallelism over a "stage" mesh axis.

Scan-based schedule: with S stages and M microbatches the loop runs
S+M-1 ticks; at tick t, stage s processes microbatch t-s.  Stage-local
parameters are selected by the stage index of each device; activations
move between stages with a collective-permute (``jax.lax.ppermute``)
inside shard_map.

This is the optional PP feature (DESIGN.md §6): exercised by
tests/test_pipeline.py at small scale, not part of the main dry-run grid
(the assigned mesh axes are data×model).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(stage_fn: Callable, params_stacked, x_microbatches,
                     mesh: Mesh, *, axis: str = "stage"):
    """Run ``stage_fn(stage_params, x) -> x`` as a GPipe pipeline.

    params_stacked: pytree with leading dim = n_stages (stage-sharded).
    x_microbatches: (M, mb, ...) microbatched input, replicated.
    Returns (M, mb, ...) outputs from the last stage.
    """
    n_stages = mesh.shape[axis]
    m = x_microbatches.shape[0]

    def per_device(params_local, xs):
        # params_local: this stage's params (leading dim 1); xs: (M, mb, ...)
        stage = jax.lax.axis_index(axis)
        p_local = jax.tree.map(lambda a: a[0], params_local)
        mb_shape = xs.shape[1:]
        n_ticks = n_stages + m - 1

        def tick(carry, t):
            buf, outputs = carry          # buf: incoming activation (mb,...)
            mb_idx = t - stage
            # stage 0 feeds from the input stream; others from the buffer
            x_in = jnp.where(
                stage == 0,
                xs[jnp.clip(mb_idx, 0, m - 1)],
                buf)
            active = (mb_idx >= 0) & (mb_idx < m)
            y = stage_fn(p_local, x_in)
            y = jnp.where(active, y, buf)
            # pass activations to the next stage (ring permute)
            y_next = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            # last stage emits results
            out_idx = jnp.clip(mb_idx, 0, m - 1)
            emit = active & (stage == n_stages - 1)
            outputs = jnp.where(
                emit[..., None, None] if outputs.ndim > 1 else emit,
                outputs.at[out_idx].set(y), outputs)
            return (y_next, outputs), None

        outputs0 = jnp.zeros((m,) + mb_shape, xs.dtype)
        buf0 = jnp.zeros(mb_shape, xs.dtype)
        (_, outputs), _ = jax.lax.scan(tick, (buf0, outputs0),
                                       jnp.arange(n_ticks))
        # results live on the last stage only; replicate across stages
        return jax.lax.psum(outputs, axis)

    spec_p = jax.tree.map(lambda _: P(axis), params_stacked)
    fn = jax.shard_map(per_device, mesh=mesh,
                       in_specs=(spec_p, P()), out_specs=P(),
                       check_vma=False)
    return fn(params_stacked, x_microbatches)
