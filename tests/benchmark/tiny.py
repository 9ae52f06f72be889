"""Tiny presets of the benchmark's families, for the CPU tests only."""

from __future__ import annotations

import json
from pathlib import Path

TINY = {
    "llama_dense": {
        "architectures": ["MistralForCausalLM"], "model_type": "mistral",
        "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 256,
        "max_position_embeddings": 512, "rms_norm_eps": 1e-5, "rope_theta": 1000000.0,
        "sliding_window": None, "tie_word_embeddings": False, "hidden_act": "silu",
    },
}


def write_model_dir(path: Path, family: str) -> Path:
    path.mkdir(parents=True, exist_ok=True)
    (path / "config.json").write_text(json.dumps(TINY[family]))
    return path
