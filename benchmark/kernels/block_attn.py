"""Counting functions of attention over blocks (``ops/rpa_kernel.py`` with
its mask causal over blocks), the attention of every step under generation
by diffusion: a block row of 4 query positions reads the keys and values of
its whole context ONCE for the row, in every layer:
``context * 2 * kv_heads * head_dim * dtype_bytes * layers`` bytes. Its
operations (``4 * context * heads * head_dim`` a query position a layer,
four positions a row) are 64 a byte at 32 query heads on 4 KV heads in
bf16, under the chip's 240, so the BYTES set the least time. A lower
bound: the prefill chunks that share the kernel's calls with the block
rows are not counted, nor are queries, outputs and the page table.
"""

TRACE_KERNEL = "rpa_kernel"


def kv_bytes_per_context_token(config: dict, dtype_bytes: int = 2) -> int:
    heads = config["num_attention_heads"]
    kv_heads = config.get("num_key_value_heads") or heads
    head_dim = config.get("head_dim") or config["hidden_size"] // heads
    return 2 * kv_heads * head_dim * dtype_bytes * config["num_hidden_layers"]


def least_seconds(config: dict, context_tokens: float, peaks: dict) -> float:
    """``context_tokens``: the positions the block rows attend to, summed
    over rows (``vllm:attn_context_tokens_total{kind="block"}``)."""
    return context_tokens * kv_bytes_per_context_token(config) / peaks["hbm_bytes_per_s"]
