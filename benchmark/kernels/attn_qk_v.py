"""Counting functions of attention whose key and value heads differ in
width and whose window and full layers differ in KV heads (``vllm_tpu/
models/mimo_v2.py``): the ragged and the decode kernel run once a layer of
either kind in their steps, and the trace prints the window layers' calls
under a name of their own.

Everything is counted at the PUBLISHED shapes, whatever the kernel and the
cache's layout do: a key of ``head_dim`` lanes and a value of
``v_head_dim`` a KV head (192 + 128 = 320 lanes, 640 bytes in bf16; not the
384 lanes a page row holds), ``swa_num_key_value_heads`` KV heads in a
window layer and ``num_key_value_heads`` in a full one, q of ``heads x
head_dim`` and o of ``heads x v_head_dim`` a query token. Padding lanes,
the page's overhang past a row's window and the tile's dead keys are the
kernel's cost: they are in the time and not in the least time, and lower
the share.

A full layer's row reads the keys and the values of its whole context, a
window layer's those its queries' windows cover: ``min(context, queries +
window - 1)`` (the runner's ``vllm:attn_context_tokens_total``, kind
``window``: a decode row ``min(context, 128)``). The operations are ``2 *
heads * (head_dim + v_head_dim)`` a (query token, key) pair the mask lets
through: the score and the value's part. The sink has no key and no value
and counts nothing.

Only the geometry lives here. The kinds' names, the names the trace prints
a kind (``TRACE_KERNELS``) and ``kernel_seconds`` are ``kernels/
window_attn.py``'s, which the readers ask for beside this module: the two
models run the same kernels under the same names.
"""

# As kernels/window_attn.py names the kinds.
FULL, WINDOW = "full_attention", "sliding_attention"


def layers(config: dict, kind: str) -> int:
    pattern = config["hybrid_layer_pattern"][: config["num_hidden_layers"]]
    return sum(1 for p in pattern if bool(p) == (kind == WINDOW))


def geometry(config: dict, kind: str) -> tuple[int, int, int, int]:
    """(q heads, KV heads, key width, value width) of a layer of ``kind``."""
    pre = "swa_" if kind == WINDOW else ""
    get = lambda key: config.get(pre + key, config[key])
    return (get("num_attention_heads"), get("num_key_value_heads"),
            get("head_dim"), get("v_head_dim"))


def kv_bytes_per_context_token(config: dict, kind: str, dtype_bytes: int = 2) -> int:
    """Bytes of keys and values one context token holds over the layers of
    ``kind``, as published."""
    _, kh, dk, dv = geometry(config, kind)
    return kh * (dk + dv) * dtype_bytes * layers(config, kind)


def qo_bytes_per_query_token(config: dict, kind: str, dtype_bytes: int = 2) -> int:
    h, _, dk, dv = geometry(config, kind)
    return h * (dk + dv) * dtype_bytes * layers(config, kind)


def pair_ops(config: dict, kind: str) -> int:
    """Operations of one live (query token, key) pair over every head and
    layer of ``kind``."""
    h, _, dk, dv = geometry(config, kind)
    return 2 * h * (dk + dv) * layers(config, kind)


def least_seconds(config: dict, kind: str, context_tokens: float, query_tokens: float,
                  live_pairs: float, peaks: dict) -> float:
    """``context_tokens``: the keys the rows' layers of ``kind`` must read,
    once a row a step. ``query_tokens``: the tokens the steps ran.
    ``live_pairs``: the (query token, key) pairs the kind's mask lets
    through, a head a layer. The larger of bytes and of operations over
    the slices, as ``kernels/window_attn.py`` takes it."""
    moved = (context_tokens * kv_bytes_per_context_token(config, kind)
             + query_tokens * qo_bytes_per_query_token(config, kind))
    return max(moved / peaks["hbm_bytes_per_s"],
               live_pairs * pair_ops(config, kind) / peaks["bf16_flops_per_s"])
