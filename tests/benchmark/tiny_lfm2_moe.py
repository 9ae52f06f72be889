"""A tiny LFM2-MoE preset for the CPU tests: the published layer (a gated
short convolution of three taps or a rotary GQA mixer with a norm a head,
then a dense SwiGLU in the first two layers and sparse experts with a
selection bias after them; the head is the embedding) at small widths.
``TINY_LFM2_MOE`` has the benchmark cell's sixteen-layer pattern cut to
its first twelve (two leading dense layers, two periods of attention and
three convolutions in the scan, an attention and a convolution layer behind
it); ``PUBLISHED_PATTERN`` is
the published 24 entries, whose attention layers at 18 and 21 break the
period."""

from __future__ import annotations

import json

PUBLISHED_PATTERN = (
    ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 4
    + ["full_attention", "conv", "conv"] * 2)

TINY_LFM2_MOE = {
    "architectures": ["Lfm2MoeForCausalLM"], "model_type": "lfm2_moe",
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 12, "layer_types": PUBLISHED_PATTERN, "num_dense_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "conv_L_cache": 3, "conv_bias": False,
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 256, "norm_eps": 1e-5, "rope_theta": 1000000,
    "tie_embedding": True, "torch_dtype": "float32", "max_position_embeddings": 4096,
}


def model_dir(tmp_path, **overrides) -> str:
    """A checkpoint directory that holds only ``config.json`` (the weights
    come from ``load_format=dummy``)."""
    path = tmp_path / "tiny-lfm2-moe"
    path.mkdir(parents=True, exist_ok=True)
    (path / "config.json").write_text(json.dumps({**TINY_LFM2_MOE, **overrides}))
    return str(path)
