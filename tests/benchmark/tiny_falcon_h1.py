"""A tiny Falcon-H1 preset for the CPU tests: the published layer (a
Mamba-2 mixer and a rotary GQA mixer side by side on the same normed input,
then a SwiGLU; untied head) at small widths, with every multiplier present
and none equal to 1, so that leaving any one out shows."""

from __future__ import annotations

import json

TINY_FALCON_H1 = {
    "architectures": ["FalconH1ForCausalLM"], "model_type": "falcon_h1",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_ssm": 64, "mamba_expand": 2,
    "mamba_n_groups": 2, "mamba_d_state": 32, "mamba_d_conv": 4,
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_norm_before_gate": False, "mamba_use_mlp": True,
    "attention_bias": False, "mlp_bias": False, "projectors_bias": False,
    "attn_layer_indices": None, "num_logits_to_keep": 1,
    "embedding_multiplier": 3.0, "lm_head_multiplier": 0.25,
    "attention_in_multiplier": 0.5, "attention_out_multiplier": 0.3,
    "key_multiplier": 0.2, "ssm_in_multiplier": 0.5, "ssm_out_multiplier": 0.4,
    "ssm_multipliers": [0.6, 0.5, 0.4, 0.7, 0.8], "mlp_multipliers": [0.35, 0.15],
    "mlp_expansion_factor": 2,
    "vocab_size": 256, "rms_norm_eps": 1e-5, "rope_theta": 100000000000,
    "rope_scaling": None, "tie_word_embeddings": False, "hidden_act": "silu",
    "torch_dtype": "float32", "max_position_embeddings": 4096,
}

# Every multiplier of the config, as ``vllm_tpu/models/falcon_h1.py::multiplier`` names it.
MULTIPLIERS = (
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier", "ssm_multipliers.0", "ssm_multipliers.1", "ssm_multipliers.2",
    "ssm_multipliers.3", "ssm_multipliers.4", "mlp_multipliers.0", "mlp_multipliers.1",
)


def model_dir(tmp_path, **overrides) -> str:
    """A checkpoint directory that holds only ``config.json`` (the weights
    come from ``load_format=dummy``)."""
    path = tmp_path / "tiny-falcon-h1"
    path.mkdir(parents=True, exist_ok=True)
    (path / "config.json").write_text(json.dumps({**TINY_FALCON_H1, **overrides}))
    return str(path)
