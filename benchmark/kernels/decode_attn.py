"""Counting functions of decode attention (ops/rpa_decode_kernel.py).

A decode token with ``c`` positions of context has to read the keys and the
values of those positions in every layer:
``c * 2 * kv_heads * head_dim * dtype_bytes * layers`` bytes. Its FLOPs
(``4 * c * heads * head_dim`` a layer) are 8 a byte at 32 query heads on 8
KV heads in bf16, far under the chip's 240 FLOP a byte, so the BYTES set
the least time. Queries, outputs and the page table are left out (a lower
bound).
"""

# The names the trace prints for the attention kernels (instance numbers
# removed), as read on the v5e: the kernel of the decode-only steps, and
# the ragged kernel of the mixed steps. Each runs once a layer in its steps.
DECODE_KERNEL = "rpa_decode_kernel"
TRACE_KERNELS = (DECODE_KERNEL, "rpa_kernel")


def kv_bytes_per_context_token(config: dict, dtype_bytes: int = 2) -> int:
    heads = config["num_attention_heads"]
    kv_heads = config.get("num_key_value_heads") or heads
    head_dim = config.get("head_dim") or config["hidden_size"] // heads
    return 2 * kv_heads * head_dim * dtype_bytes * config["num_hidden_layers"]


def kv_bytes(config: dict, context_tokens: int, dtype_bytes: int = 2) -> int:
    """Bytes read for decode tokens whose context lengths sum to
    ``context_tokens``."""
    return context_tokens * kv_bytes_per_context_token(config, dtype_bytes)


def least_seconds(config: dict, context_tokens: int, peaks: dict) -> float:
    return kv_bytes(config, context_tokens) / peaks["hbm_bytes_per_s"]
