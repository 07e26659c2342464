"""Sharding rules + cell building on a single-device mesh (the real
512-device meshes are exercised by launch/dryrun.py, which owns the
XLA_FLAGS device-count override)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.configs.base import SHAPES, ShapeConfig
from repro.launch.steps import auto_fsdp, build_cell, cache_shardings
from repro.models.model import LM
from repro.sharding.ctx import use_mesh
from repro.sharding.rules import make_param_specs, spec_for_path


def mesh1():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


CTX16 = {"model_size": 16, "data_size": 16}


def test_rules_cover_every_arch_param():
    """Every parameter of every architecture matches a rule and returns
    a spec of the right rank."""
    for arch in ARCH_IDS:
        cfg = get_smoke_config(arch)
        lm = LM(cfg)
        params = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
        specs = make_param_specs(params, mesh1())
        flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
        flat_s = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
        assert len(flat_p) == len(flat_s)
        for (path, leaf), spec in zip(flat_p, flat_s):
            assert len(spec) <= leaf.ndim, \
                f"{arch} {jax.tree_util.keystr(path)}: spec {spec} rank " \
                f"> {leaf.shape}"


def test_tp_rules_shard_projections_not_norms():
    assert spec_for_path("layers/blk0/attn/wq/w", (64, 256), CTX16) \
        == P(None, "model")
    assert spec_for_path("layers/blk0/attn/wo/w", (256, 64), CTX16) \
        == P("model", None)
    assert spec_for_path("layers/blk0/norm1/scale", (64,), CTX16) == P(None)
    assert spec_for_path("embed/w", (4096, 64), CTX16) == P("model", None)
    # EP when divisible, TP fallback otherwise
    assert spec_for_path("layers/blk0/moe/gate_e", (16, 64, 128), CTX16) \
        == P("model", None, None)
    assert spec_for_path("layers/blk0/moe/gate_e", (40, 64, 128), CTX16) \
        == P(None, None, "model")


def test_sanitize_drops_nondividing_axes():
    # granite vocab 49155 % 16 != 0 -> replicated, not an error
    cfg = get_config("granite-moe-3b-a800m")
    lm = LM(cfg)
    params = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    ctx_mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
    specs = make_param_specs(params, ctx_mesh)   # sizes 1: everything ok
    # emulate the 16×16 ctx directly through spec_for_path
    s = spec_for_path("embed/w", (49155, 1536), CTX16)
    from repro.sharding.rules import _sanitize
    assert _sanitize(s, (49155, 1536), CTX16) == P(None, None)


def test_fsdp_adds_data_axis_to_large_leaves():
    spec = spec_for_path("layers/blk0/mlp/gate/w", (8192, 32768), CTX16)
    from repro.sharding.rules import _with_fsdp
    out = _with_fsdp(spec, (8192, 32768), CTX16)
    assert "data" in jax.tree.leaves(tuple(out)) or \
        any(e == "data" or (isinstance(e, tuple) and "data" in e)
            for e in out)
    tiny = _with_fsdp(P(None), (64,), CTX16)
    assert tiny == P(None)


def test_auto_fsdp_policy():
    mesh = mesh1()

    class FakeMesh:
        shape = {"data": 16, "model": 16}
    assert auto_fsdp(get_config("jamba-1.5-large-398b"), FakeMesh(), "train")
    assert auto_fsdp(get_config("jamba-1.5-large-398b"), FakeMesh(), "decode")
    assert not auto_fsdp(get_config("llama3.2-1b"), FakeMesh(), "train")
    # 33B: ZeRO-3 for training state, pure TP for serving
    assert auto_fsdp(get_config("deepseek-coder-33b"), FakeMesh(), "train")
    assert not auto_fsdp(get_config("deepseek-coder-33b"), FakeMesh(),
                         "decode")


@pytest.mark.parametrize("arch", ["llama3.2-1b", "rwkv6-1.6b",
                                  "granite-moe-3b-a800m", "whisper-base",
                                  "jamba-1.5-large-398b"])
@pytest.mark.parametrize("shape_name", ["train_4k", "decode_32k"])
def test_build_cell_lowers_on_1x1_mesh(arch, shape_name):
    """The dry-run cell machinery lowers AOT for reduced configs on the
    single real device (structure check; 512-dev run is launch-owned)."""
    cfg = get_smoke_config(arch).with_(ce_chunk=64)
    shape = ShapeConfig(shape_name, 64, 4, SHAPES[shape_name].mode)
    mesh = mesh1()
    with use_mesh(mesh):
        cell = build_cell(cfg, shape, mesh, fsdp=False)
        lowered = cell.lower()
        compiled = lowered.compile()
    # list-or-dict cost_analysis drift is resolved by the same shim the
    # dry-run uses, so this test guards the production path
    from repro.launch.roofline import resolve_cost_analysis
    assert resolve_cost_analysis(compiled)["flops"] > 0


def test_cache_shardings_structure():
    cfg = get_smoke_config("jamba-1.5-large-398b")
    lm = LM(cfg)
    cache = jax.eval_shape(lambda: lm.init_cache(4, 64))
    mesh = mesh1()
    sh = cache_shardings(cache, mesh, cfg,
                         ShapeConfig("decode", 64, 4, "decode"))
    assert jax.tree.structure(sh, is_leaf=lambda x: hasattr(x, "spec")) \
        == jax.tree.structure(cache)
