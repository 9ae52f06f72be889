"""Counting functions of GQA attention over heads under a lane tile, whose
pages hold a KV head's key and value side by side in one 128-lane row (the
PACKED layout, ``vllm_tpu/ops/attention.py::packed_kv_layout``), in a model
where only SOME layers are attention (``vllm_tpu/models/lfm2_moe.py``): the
ragged and the decode kernel run once an attention layer in their steps,
under their plain names.

A module of its own because none that is there counts this work at these
widths: ``decode_attn.py`` multiplies by ``num_hidden_layers`` (here 4 of 16
layers hold attention), ``window_attn.py`` reads a head count a layer and
``attn_qk_v.py`` a pattern of window layers and a value's own width.

A row reads the keys and the values of its whole context in every attention
layer: ``2 * kv_heads * head_dim * dtype_bytes`` a token a layer (2,048
bytes at 8 heads of 64 in bf16; the packed row holds exactly that, no
padding lane). Every query token also brings its q and takes its o, ``2 *
heads * head_dim * dtype_bytes`` a layer. The operations are ``4 * heads *
head_dim`` a (query token, key) pair the mask lets through. A decode row is
bound by its bytes (8 operations a byte against the chip's 240), a prefill
chunk of some hundred tokens by its operations; ``least_seconds`` takes the
larger over the slices, as ``kernels/window_attn.py`` does."""

TRACE_KERNELS = ("rpa_decode_kernel", "rpa_kernel")


def layers(config: dict) -> int:
    """The attention layers the program runs: of the first
    ``num_hidden_layers`` entries of ``layer_types``."""
    return config["layer_types"][: config["num_hidden_layers"]].count("full_attention")


def geometry(config: dict) -> tuple[int, int, int]:
    """(q heads, KV heads, head size)."""
    heads = config["num_attention_heads"]
    return (heads, config.get("num_key_value_heads") or heads,
            config.get("head_dim") or config["hidden_size"] // heads)


def kv_bytes_per_context_token(config: dict, dtype_bytes: int = 2) -> int:
    _, kv_heads, head_dim = geometry(config)
    return 2 * kv_heads * head_dim * dtype_bytes * layers(config)


def qo_bytes_per_query_token(config: dict, dtype_bytes: int = 2) -> int:
    heads, _, head_dim = geometry(config)
    return 2 * heads * head_dim * dtype_bytes * layers(config)


def pair_ops(config: dict) -> int:
    """Operations of one live (query token, key) pair over every head and
    attention layer: the score and the value's part."""
    heads, _, head_dim = geometry(config)
    return 4 * heads * head_dim * layers(config)


def kernel_seconds(trace: dict) -> float:
    return sum(trace["kernel_seconds"].get(name, 0.0) for name in TRACE_KERNELS)


def least_seconds(config: dict, context_tokens: float, query_tokens: float,
                  live_pairs: float, peaks: dict) -> float:
    """``context_tokens``: the keys the rows must read, once a row a step.
    ``query_tokens``: the tokens the steps ran. ``live_pairs``: the (query
    token, key) pairs the causal mask lets through, a head a layer."""
    moved = (context_tokens * kv_bytes_per_context_token(config)
             + query_tokens * qo_bytes_per_query_token(config))
    return max(moved / peaks["hbm_bytes_per_s"],
               live_pairs * pair_ops(config) / peaks["bf16_flops_per_s"])
