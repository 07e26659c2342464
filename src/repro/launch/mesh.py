"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — the dry-run must set XLA_FLAGS
*before* the first jax initialization.

Target: TPU v5e.  One pod = 16×16 = 256 chips ("data" × "model");
multi-pod = 2 × 256 = 512 chips with a leading "pod" axis (DCN between
pods, ICI within).
"""
from __future__ import annotations

import os
import re

import jax
from jax.sharding import AxisType

_FORCE_FLAG = "--xla_force_host_platform_device_count"


def _backend_initialized() -> bool:
    """True once jax has instantiated a backend (XLA_FLAGS is frozen)."""
    try:
        from jax._src import xla_bridge
        return bool(xla_bridge._backends)
    except Exception:
        return True            # cannot tell: assume live, don't mutate env


def ensure_host_devices(n: int) -> bool:
    """Opt-in: make the host CPU platform expose ``n`` devices by setting
    ``XLA_FLAGS=--xla_force_host_platform_device_count=n``.

    Must run BEFORE jax initializes its backends (env mutation has no
    effect afterwards).  Returns True when ``n`` devices are or will be
    visible; False when the backend already came up with fewer — callers
    (multi-device CPU tests, the sharded benchmark) should skip cleanly
    on False rather than assert.
    """
    if _backend_initialized():
        return len(jax.devices()) >= n
    cur = os.environ.get("XLA_FLAGS", "")
    if _FORCE_FLAG in cur:
        cur = re.sub(rf"{_FORCE_FLAG}=\d+", f"{_FORCE_FLAG}={n}", cur)
    else:
        cur = f"{cur} {_FORCE_FLAG}={n}".strip()
    os.environ["XLA_FLAGS"] = cur
    return True


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(*, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if model < 1 or n % model:
        raise ValueError(
            f"make_host_mesh(model={model}): {n} visible "
            f"device{'s' if n != 1 else ''} "
            f"({jax.default_backend()}) not divisible by the model axis. "
            f"On CPU, force more host devices BEFORE jax initializes: "
            f"XLA_FLAGS={_FORCE_FLAG}=N or "
            f"repro.launch.mesh.ensure_host_devices(N).")
    # Auto axes: model code places arrays with with_sharding_constraint,
    # which refuses Explicit axes (make_mesh's default since JAX 0.7)
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


# TPU v5e hardware constants (per chip) — used by roofline + cost model.
HW = {
    "name": "tpu_v5e",
    "peak_flops_bf16": 197e12,        # FLOP/s
    "peak_flops_int8": 394e12,
    "hbm_bw": 819e9,                  # B/s
    "hbm_bytes": 16 * 2**30,
    "ici_bw_per_link": 50e9,          # B/s per link (~45 GB/s usable)
    "ici_links": 4,                   # 2D torus: 4 links/chip
    "dcn_bw": 25e9,                   # inter-pod, per host aggregate share
    "tdp_watts": 220.0,               # chip TDP (energy model)
    "idle_watts": 60.0,
}
