"""Counting functions of latent attention over the paged latent cache
(``vllm_tpu/ops/mla_kernel.py``, which the trace prints as ``mla_kernel``;
it takes the prefill chunks and the decode rows alike).

A row reads the latent rows of its whole context once a layer:
``(kv_lora_rank + qk_rope_head_dim) * dtype_bytes`` a context token, 1,152
bytes at 512 + 64 in bf16 (the cache stores 1,280, padded to whole lanes:
a lower bound), times the latent layers. Its operations are ``2 * 576 + 2
* 512`` a head a (query token, key) pair the mask lets through: 60 a byte
at 32 heads and one query a row, under the chip's 240, so the BYTES set
the least time of the decode rows. A chunk's queries share one read of
the context and each brings its own pairs, so its OPERATIONS do (382
tokens at context 2,000: 3 us of bytes, 270 us of operations a layer).
``least_seconds`` takes the larger of the two over the slices, as
``kernels/kda.py`` does, so that a slice which holds more chunks does not
read as a slower kernel.
"""

TRACE_KERNEL = "mla_kernel"


def latent_layers(config: dict) -> int:
    return len(config["linear_attn_config"]["full_attn_layers"])


def bytes_per_context_token(config: dict, dtype_bytes: int = 2) -> int:
    return ((config["kv_lora_rank"] + config["qk_rope_head_dim"]) * dtype_bytes
            * latent_layers(config))


def pair_ops(config: dict) -> int:
    """Operations of one live (query token, key) pair over every head and
    latent layer: the score over the row's lanes, and the value's part."""
    lanes = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    return ((2 * lanes + 2 * config["kv_lora_rank"]) * config["num_attention_heads"]
            * latent_layers(config))


def least_seconds(config: dict, context_tokens: float, peaks: dict,
                  live_pairs: float = 0.0) -> float:
    """``context_tokens``: the positions the rows attend to, once a row a
    step (``vllm:attn_context_tokens_total{kind="latent"}``).
    ``live_pairs``: the (query token, key) pairs the mask lets through, a
    head a layer (the latent kernel's ``vllm:attn_ragged_pairs_total``,
    kind ``live``; 0 where the program does not count them)."""
    return max(context_tokens * bytes_per_context_token(config) / peaks["hbm_bytes_per_s"],
               live_pairs * pair_ops(config) / peaks["bf16_flops_per_s"])
