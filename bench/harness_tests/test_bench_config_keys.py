"""What a configuration file's keys make of the model: the program's
mapping and the reference read linear RoPE scaling alike, and every key
that would change the forward pass beyond the mapping is refused by
name."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

import faults
import run
from benchlib import config_keys, reference, system
from smoke_cell import BENCH, smoke_cell, smoke_config

QWEN2 = json.loads((BENCH / "configs" / "qwen2-1.5b.json").read_text())

REFUSED = [
    ("model_type", "qwen3"),
    ("model_type", None),
    ("architectures", ["Qwen3ForCausalLM"]),
    ("rope_scaling", {"type": "dynamic", "factor": 2.0}),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0,
                      "original_max_position_embeddings": 4096}),
    ("rope_scaling", {"rope_type": "llama3", "factor": 8.0,
                      "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                      "original_max_position_embeddings": 8192}),
    ("rope_scaling", {"type": "linear"}),
    ("rope_scaling", {"type": "linear", "rope_type": "dynamic",
                      "factor": 4.0}),
    ("use_sliding_window", True),
    ("sliding_window", 4096),
    ("hidden_act", "gelu"),
    ("mlp_bias", True),
    ("partial_rotary_factor", 0.5),
    ("num_local_experts", 8),
    ("num_experts", 60),
    ("n_routed_experts", 64),
    ("n_shared_experts", 2),
    ("num_experts_per_tok", 2),
    ("moe_intermediate_size", 1408),
    ("kv_lora_rank", 512),
    ("q_lora_rank", 1536),
]


def test_qwen2_file_maps_as_published():
    """qwen2's file, with its window switched off, loads unchanged."""
    assert QWEN2["use_sliding_window"] is False and QWEN2["sliding_window"]
    pc = system.program_config(QWEN2)
    a = pc.attention
    assert (pc.num_layers, pc.d_model, pc.d_ff, pc.vocab_size) == (
        28, 1536, 8960, 151936)
    assert (a.num_heads, a.num_kv_heads, a.head_dim, a.rope_theta,
            a.qkv_bias) == (12, 2, 128, 1e6, True)
    assert pc.tie_embeddings and pc.norm_eps == 1e-6
    assert getattr(a, "rope_scaling_factor", 1.0) == 1.0
    assert config_keys.rope_factor(QWEN2) == 1.0


#: files of families outside the mapping, whose every other key would
#: pass: Qwen3 (q/k rmsnorm, implied by its type) and Gemma2 (logit
#: soft-capping, a query scale other than the head size)
OTHER_FAMILIES = {
    "qwen3": dict(QWEN2, model_type="qwen3",
                  architectures=["Qwen3ForCausalLM"], attention_bias=False,
                  head_dim=128),
    "gemma2": dict(QWEN2, model_type="gemma2",
                   architectures=["Gemma2ForCausalLM"],
                   attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
                   query_pre_attn_scalar=256, head_dim=256),
}


@pytest.mark.parametrize("family", sorted(OTHER_FAMILIES))
def test_a_file_of_another_family_is_refused(family):
    with pytest.raises(config_keys.Unmapped, match="model_type") as e:
        system.program_config(OTHER_FAMILIES[family])
    assert e.value.key == "model_type" and family in str(e.value)


def test_a_llama_file_maps():
    """deepseek-coder-33b's family: a Llama file with no bias loads."""
    cfg = dict(QWEN2, model_type="llama", architectures=["LlamaForCausalLM"],
               attention_bias=False, tie_word_embeddings=False)
    a = system.program_config(cfg).attention
    assert (a.num_heads, a.num_kv_heads, a.qkv_bias) == (12, 2, False)


@pytest.mark.parametrize("rs, factor", [
    (None, 1.0), ({"type": "linear", "factor": 4.0}, 4.0),
    ({"rope_type": "linear", "factor": 2}, 2.0),
    ({"type": "linear", "rope_type": "linear", "factor": 8.0}, 8.0)])
def test_linear_rope_scaling_maps_to_its_factor(rs, factor):
    assert config_keys.rope_factor(dict(QWEN2, rope_scaling=rs)) == factor


@pytest.mark.parametrize("key, value", REFUSED,
                         ids=[f"{k}-{i}" for i, (k, _) in
                              enumerate(REFUSED)])
def test_each_key_the_mapping_cannot_express_is_refused(key, value):
    cfg = dict(QWEN2, **{key: value})
    if key == "sliding_window":         # Mistral's files set it alone
        del cfg["use_sliding_window"]
    with pytest.raises(config_keys.Unmapped, match=key) as e:
        system.program_config(cfg)
    assert e.value.key == key and repr(value) in str(e.value)


def test_the_program_never_drops_a_linear_scaling():
    """A scaled file maps to the program's ``rope_scaling_factor``, or,
    where the program has no such field, is refused by name: it never
    runs unscaled."""
    cfg = smoke_config(scaled=True)
    try:
        pc = system.program_config(cfg)
    except config_keys.Unmapped as e:
        assert e.key == "rope_scaling" and "rope_scaling_factor" in str(e)
    else:
        assert pc.attention.rope_scaling_factor == 4.0


def test_reference_rope_divides_positions_by_the_factor():
    """Position ``2p`` under factor 2 turns a vector as position ``p``
    does unscaled; factor 1 is the plain rotation, bit for bit."""
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(1, 3, 16)).repeat(64, 0), jnp.float32)
    theta = 1e4
    plain = reference._rope(x, theta, 1.0)
    half = reference._rope(x, theta, 2.0)
    assert np.array_equal(np.asarray(half[::2]), np.asarray(plain[:32]))
    freqs = 1.0 / theta ** (jnp.arange(0, 16, 2, dtype=jnp.float32) / 16)
    ang = jnp.arange(64, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    want = jnp.concatenate([x[..., :8] * cos - x[..., 8:] * sin,
                            x[..., :8] * sin + x[..., 8:] * cos], -1)
    assert np.array_equal(np.asarray(plain), np.asarray(want))


def serve_linear_scaling(cfg: dict) -> list:
    """``[(owner, attribute, replacement)]`` that serve ``cfg``'s linear
    RoPE scaling with the program's own code: the program is built from
    the file without ``rope_scaling`` (``faults``' ``rope_unscaled``),
    and every rotary embedding of its attention divides its positions by
    the factor first."""
    from repro.models import attention
    factor = config_keys.rope_factor(cfg)
    rope = attention.apply_rope

    def scaled(x, positions, *args, **kw):
        return rope(x, jnp.asarray(positions, jnp.float32) / factor,
                    *args, **kw)
    return faults.patches("rope_unscaled") + [
        (attention, "apply_rope", scaled)]


@pytest.mark.parametrize("traffic", ("reasoning-batch", "chat-sessions"))
def test_scaled_smoke_cell_is_correct(monkeypatch, traffic):
    """The smoke cell whose file scales RoPE linearly by 4 comes out
    correct, through staging prefill and paged decode (reasoning) and
    prefix-extend chunks after a prefix hit (chat): the reference's
    scaling agrees with the program's attention serving scaled positions
    (``serve_linear_scaling``)."""
    cell = smoke_cell(traffic, scaled=True)
    for owner, attr, value in serve_linear_scaling(cell["config"]):
        monkeypatch.setattr(owner, attr, value)
    res = run.run("smoke", 2200000101, 2.0, False, cell=cell,
                  check_device=False)
    assert res["check"]["tokens_compared"]["value"] >= 20
    assert res["correct"] is True, res["check"]
