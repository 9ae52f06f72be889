"""A tiny Laguna preset for the CPU tests: the published layer pattern (a
dense full-attention layer 0, then periods of three window layers and a
full one, a tail of three window layers; unlike query-head counts over one
KV-head count, YaRN over half a head's lanes in the full layers and plain
rope in the window layers, a gate a head, sigmoid routing renormalised and
scaled, one shared expert, untied head) at small widths, with a window of
32 tokens: two pages of 16. ``share(rank)`` is the same model cut to one of
four expert shares."""

from __future__ import annotations

import json

_KINDS = ["full_attention"] + (["sliding_attention"] * 3 + ["full_attention"]) * 2 + [
    "sliding_attention"] * 3

TINY_LAGUNA = {
    "architectures": ["LagunaForCausalLM"], "model_type": "laguna",
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 12, "num_attention_heads": 6, "num_key_value_heads": 2,
    "head_dim": 16, "max_position_embeddings": 4096, "attention_bias": False,
    "rms_norm_eps": 1e-6, "num_experts": 16, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 32,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 64, "beta_slow": 1, "beta_fast": 64,
            "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 64},
    "layer_types": _KINDS, "moe_apply_router_weight_on_input": False,
    "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 11,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [6 if k == "full_attention" else 8 for k in _KINDS],
    "torch_dtype": "float32",
}


def share(rank: int, of: int = 4) -> dict:
    return {**TINY_LAGUNA, "num_experts": TINY_LAGUNA["num_experts"] // of,
            "ep_size": of, "ep_rank": rank}


def model_dir(tmp_path, **overrides) -> str:
    """A checkpoint directory that holds only ``config.json`` (the weights
    come from ``load_format=dummy``)."""
    path = tmp_path / "tiny-laguna"
    path.mkdir(parents=True, exist_ok=True)
    (path / "config.json").write_text(json.dumps({**TINY_LAGUNA, **overrides}))
    return str(path)
