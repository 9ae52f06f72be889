"""A tiny SDAR-MoE preset for the CPU tests: the published layer pattern
(per-head q/k norm, rope on the whole head, 8 experts of which 2 a token,
renormalised, no shared expert, untied head, blocks of 4) at small widths."""

from __future__ import annotations

import json

TINY_SDAR = {
    "architectures": ["SDARMoeForCausalLM"], "model_type": "sdar_moe",
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "vocab_size": 256, "max_position_embeddings": 256, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000.0, "tie_word_embeddings": False,
    "attention_bias": False, "hidden_act": "silu", "torch_dtype": "float32",
    "block_length": 4, "mask_token_id": 250,
    "remasking_strategy": "sequential", "denoising_steps": 4,
    "confidence_threshold": 0.9,
}


def model_dir(tmp_path, **overrides) -> str:
    """A checkpoint directory that holds only ``config.json`` (the weights
    come from ``load_format=dummy``)."""
    path = tmp_path / "tiny-sdar"
    path.mkdir(parents=True, exist_ok=True)
    (path / "config.json").write_text(json.dumps({**TINY_SDAR, **overrides}))
    return str(path)
