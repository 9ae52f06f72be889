"""Counting functions of attention in a model that mixes window and full
layers over one page pool (``vllm_tpu/models/laguna.py``): the ragged and
the decode kernel run once a layer of either kind in their steps, and the
trace prints the window layers' calls under a name of their own.

A full layer's row reads the keys and the values of its whole context, a
window layer's those its queries' windows cover: ``min(context, queries +
window - 1)`` (the runner's ``vllm:attn_context_tokens_total``, kind
``window``; the kernel's tile loop starts at the block that holds the
floor and so touches up to a block more: a lower bound). A token of a
layer is ``2 * kv_heads * head_dim * dtype_bytes`` bytes in BOTH kinds,
4,096 at 8 heads of 128 in bf16. Every query token also brings its q and
takes its o, ``2 * heads * head_dim * dtype_bytes`` a layer at the kind's
own head count (64 in a window layer, 48 in a full one of Laguna-XS.2).
The operations are ``4 * heads * head_dim`` a (query token, key) pair the
mask lets through. A decode row is bound by its bytes (8 or 6 operations a
byte against the chip's 240), a prefill chunk of 512 tokens by its
operations; ``least_seconds`` takes the larger over the slices, as
``kernels/mla_attn.py`` does.
"""

FULL, WINDOW = "full_attention", "sliding_attention"
# The names the trace prints (instance numbers removed): the decode-only
# steps' kernel and the mixed steps', a kind.
TRACE_KERNELS = {
    FULL: ("rpa_decode_kernel", "rpa_kernel"),
    WINDOW: ("rpa_decode_kernel_window", "rpa_kernel_window"),
}


def layers(config: dict, kind: str) -> int:
    return sum(1 for k in config["layer_types"] if k == kind)


def heads(config: dict, kind: str) -> int:
    return config["num_attention_heads_per_layer"][config["layer_types"].index(kind)]


def kv_bytes_per_context_token(config: dict, kind: str, dtype_bytes: int = 2) -> int:
    """Bytes of keys and values one context token holds over the layers of
    ``kind``."""
    return (2 * config["num_key_value_heads"] * config["head_dim"] * dtype_bytes
            * layers(config, kind))


def qo_bytes_per_query_token(config: dict, kind: str, dtype_bytes: int = 2) -> int:
    return 2 * heads(config, kind) * config["head_dim"] * dtype_bytes * layers(config, kind)


def pair_ops(config: dict, kind: str) -> int:
    """Operations of one live (query token, key) pair over every head and
    layer of ``kind``: the score and the value's part."""
    return 4 * heads(config, kind) * config["head_dim"] * layers(config, kind)


def kernel_seconds(trace: dict, kind: str) -> float:
    return sum(trace["kernel_seconds"].get(name, 0.0) for name in TRACE_KERNELS[kind])


def least_seconds(config: dict, kind: str, context_tokens: float, query_tokens: float,
                  live_pairs: float, peaks: dict) -> float:
    """``context_tokens``: the keys the rows' layers of ``kind`` must read,
    once a row a step. ``query_tokens``: the tokens the steps ran.
    ``live_pairs``: the (query token, key) pairs the kind's mask lets
    through, a head a layer."""
    moved = (context_tokens * kv_bytes_per_context_token(config, kind)
             + query_tokens * qo_bytes_per_query_token(config, kind))
    return max(moved / peaks["hbm_bytes_per_s"],
               live_pairs * pair_ops(config, kind) / peaks["bf16_flops_per_s"])
