"""A tiny Kimi-Linear preset for the CPU tests: the published layer
pattern (a dense first layer, then periods of three KDA layers and one
latent-attention layer without rotation; sigmoid routing with a selection
bias, renormalised and scaled, one shared expert; untied head) at small
widths. ``share(rank)`` is the same model cut to one of four expert
shares."""

from __future__ import annotations

import json

TINY_KIMI = {
    "architectures": ["KimiLinearForCausalLM"], "model_type": "kimi_linear",
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 9, "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "linear_attn_config": {
        "kda_layers": [1, 2, 3, 5, 6, 7, 9], "full_attn_layers": [4, 8],
        "num_heads": 2, "head_dim": 16, "short_conv_kernel_size": 4,
    },
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "mla_use_nope": True,
    "num_experts": 16, "num_experts_per_token": 4, "num_shared_experts": 1,
    "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
    "routed_scaling_factor": 2.446, "num_expert_group": 1, "topk_group": 1,
    "use_grouped_topk": True, "vocab_size": 256, "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "rope_scaling": None, "tie_word_embeddings": False,
    "hidden_act": "silu", "torch_dtype": "float32", "model_max_length": 4096,
}


def share(rank: int, of: int = 4) -> dict:
    return {**TINY_KIMI, "num_experts": TINY_KIMI["num_experts"] // of,
            "ep_size": of, "ep_rank": rank}


def model_dir(tmp_path, **overrides) -> str:
    """A checkpoint directory that holds only ``config.json`` (the weights
    come from ``load_format=dummy``)."""
    path = tmp_path / "tiny-kimi"
    path.mkdir(parents=True, exist_ok=True)
    (path / "config.json").write_text(json.dumps({**TINY_KIMI, **overrides}))
    return str(path)
