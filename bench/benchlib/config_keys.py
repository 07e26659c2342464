"""What a configuration file's keys make of the model, in one place.

The harness serves and checks a dense decoder of the Llama / Qwen2
family.  A file's keys either map onto the program
(``benchlib.system.program_config``) and the reference
(``benchlib.reference``), or say nothing of the forward pass (names,
sources, a window the cell never reaches), or would change the forward
pass in a way that neither expresses.  The last kind is refused here,
before anything is built, so that no file runs a model other than the
one it names.  Nothing here imports the program.
"""
from __future__ import annotations


class Unmapped(ValueError):
    """A configuration key whose value the harness cannot express."""

    def __init__(self, key: str, value, why: str):
        super().__init__(f"configuration key {key!r} = {value!r}: {why}")
        self.key = key


#: the families whose forward pass the mapping and the reference
#: compute, by ``model_type``, with their ``architectures`` name; any
#: other family (Qwen3's q/k norm, Gemma2's soft-capping, ...) may change
#: the forward pass by its type alone, with no key to say so
FAMILIES = {"llama": "LlamaForCausalLM", "qwen2": "Qwen2ForCausalLM"}

#: keys that count experts or set a latent rank: a model with sparse
#: experts or latent attention, which this decoder has neither of
EXPERT_OR_LATENT_KEYS = (
    "num_local_experts", "num_experts", "n_routed_experts",
    "n_shared_experts", "num_experts_per_tok", "moe_intermediate_size",
    "kv_lora_rank", "q_lora_rank")

#: keys the mapping leaves out, and the values at which they leave the
#: forward pass as the program and the reference compute it
PASSIVE_VALUES = {
    "hidden_act": ("silu",),
    "mlp_bias": (False, None),
    "partial_rotary_factor": (1, 1.0, None),
}


def rope_factor(cfg: dict) -> float:
    """The factor of ``rope_scaling`` of type ``linear`` (position
    interpolation: positions are divided by it before the rotation, as in
    HF's ``LlamaLinearScalingRotaryEmbedding``); 1.0 where the file has
    none.  HF writes the type as ``type`` or ``rope_type``.  Any other
    type changes the frequencies in a way not expressed here."""
    rs = cfg.get("rope_scaling")
    if rs is None:
        return 1.0
    kinds = {rs[k] for k in ("type", "rope_type") if k in rs}
    factor = rs.get("factor")
    if kinds != {"linear"} or set(rs) - {"type", "rope_type", "factor"} \
            or not isinstance(factor, (int, float)) or factor <= 0:
        raise Unmapped("rope_scaling", rs,
                       "only linear scaling by a positive factor is mapped")
    return float(factor)


def check(cfg: dict) -> None:
    """Raise :class:`Unmapped` on the first key that would change the
    forward pass beyond what the harness maps."""
    kind = cfg.get("model_type")
    if kind not in FAMILIES:
        raise Unmapped("model_type", kind,
                       f"only the families {sorted(FAMILIES)} are mapped")
    arch = cfg.get("architectures", [FAMILIES[kind]])
    if arch != [FAMILIES[kind]]:
        raise Unmapped("architectures", arch,
                       f"a {kind} file names {[FAMILIES[kind]]}")
    rope_factor(cfg)
    # Qwen2 turns its window on with use_sliding_window; Mistral's files
    # set sliding_window alone
    sliding = cfg.get("use_sliding_window",
                      cfg.get("sliding_window") is not None)
    if sliding:
        key = "use_sliding_window" if "use_sliding_window" in cfg \
            else "sliding_window"
        raise Unmapped(key, cfg[key], "sliding-window attention is not "
                       "mapped")
    for key, fine in PASSIVE_VALUES.items():
        if key in cfg and cfg[key] not in fine:
            raise Unmapped(key, cfg[key], f"only {fine} is mapped")
    for key in EXPERT_OR_LATENT_KEYS:
        if cfg.get(key):
            raise Unmapped(key, cfg[key], "sparse experts and latent "
                           "attention are not mapped")
