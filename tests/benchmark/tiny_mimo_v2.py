"""A tiny MiMo-V2 preset for the CPU tests: the published pattern's first
twelve layers (full at 0, 5 and 11, window elsewhere: F W W W W F W W W W
W F; layer 0 dense, the others sparse; a key head of 24 lanes beside a
value head of 16, rope on the first 8; twice the KV heads in a window
layer; a sink logit a head in the window layers; sigmoid routing with a
selection bias, no shared expert; untied head) at small widths, with a
window of 24 tokens: three pages of 8, where a full group's page holds 16.
``share(rank)`` is the same model cut to one of four expert shares."""

from __future__ import annotations

import json

_PATTERN = [0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1, 0]

TINY_MIMO_V2 = {
    "architectures": ["MiMoV2ForCausalLM"], "model_type": "mimo_v2",
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 12, "num_attention_heads": 8, "num_key_value_heads": 2,
    "swa_num_attention_heads": 8, "swa_num_key_value_heads": 4,
    "head_dim": 24, "v_head_dim": 16, "swa_head_dim": 24, "swa_v_head_dim": 16,
    "max_position_embeddings": 4096, "attention_bias": False,
    "attention_value_scale": 0.707, "attention_chunk_size": 24,
    "add_full_attention_sink_bias": False, "add_swa_attention_sink_bias": True,
    "layernorm_epsilon": 1e-5, "hidden_act": "silu",
    "hybrid_layer_pattern": _PATTERN, "moe_layer_freq": [0] + [1] * 11,
    "n_routed_experts": 32, "num_experts_per_tok": 4, "n_group": 1, "topk_group": 1,
    "n_shared_experts": None, "norm_topk_prob": True, "routed_scaling_factor": None,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "moe_intermediate_size": 32, "partial_rotary_factor": 0.334,
    "rope_theta": 10000000, "swa_rope_theta": 10000,
    "rope_scaling": {"rope_type": "default", "type": "default"},
    "sliding_window": 24, "sliding_window_size": 24,
    "tie_word_embeddings": False, "torch_dtype": "float32",
}


def share(rank: int, of: int = 4) -> dict:
    return {**TINY_MIMO_V2, "n_routed_experts": TINY_MIMO_V2["n_routed_experts"] // of,
            "ep_size": of, "ep_rank": rank}


def model_dir(tmp_path, **overrides) -> str:
    """A checkpoint directory that holds only ``config.json`` (the weights
    come from ``load_format=dummy``)."""
    path = tmp_path / "tiny-mimo-v2"
    path.mkdir(parents=True, exist_ok=True)
    (path / "config.json").write_text(json.dumps({**TINY_MIMO_V2, **overrides}))
    return str(path)
