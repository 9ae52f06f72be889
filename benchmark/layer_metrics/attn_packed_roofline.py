"""Layer: kernels (ops/rpa_kernel.py, ops/rpa_decode_kernel.py over pages
in the PACKED layout: heads of 64 lanes, a KV head's key and value side by
side in one 128-lane row). Share of their roofline that the attention
layers' calls reach, the larger of bytes and of operations
(``benchmark/kernels/attn_packed.py``): 8 KV heads of 64 + 64 lanes a
context token, q and o of 32 x 64 a query token, in the 4 attention layers
of 16.

Time: the device time of ``rpa_kernel`` and ``rpa_decode_kernel`` in the
traced slices. Least time: the keys and values of the rows' whole contexts,
``vllm:attn_context_tokens_total`` of the decode-only and the mixed steps,
plus q and o of the steps' tokens, over the chip's HBM bandwidth; or the
operations of the live pairs: the mixed steps' live
``vllm:attn_ragged_pairs_total`` and a key a decode row. Scraped inside each
slice: under-read, never over-read. None where the configuration has no
such layers, the program ran neither kernel or counts no context."""


def read(ctx):
    trace, slice_delta = ctx.get("trace"), ctx.get("slice_delta")
    if not trace or slice_delta is None or "conv_L_cache" not in ctx["config"]:
        return None
    counts = ctx["kernel_counts"]("attn_packed")
    seconds = counts.kernel_seconds(trace)
    decode_keys = slice_delta('vllm:attn_context_tokens_total{kind="decode"}')
    mixed_keys = slice_delta('vllm:attn_context_tokens_total{kind="mixed"}')
    if seconds <= 0 or (decode_keys is None and mixed_keys is None):
        return None
    get = lambda name: slice_delta(name) or 0.0
    decode_keys = decode_keys or 0.0
    keys = decode_keys + (mixed_keys or 0.0)
    tokens = (get('vllm:engine_step_tokens_total{kind="decode"}')
              + get('vllm:engine_step_tokens_total{kind="mixed"}'))
    pairs = decode_keys + get('vllm:attn_ragged_pairs_total{kind="live"}')
    return 100.0 * counts.least_seconds(ctx["config"], keys, tokens, pairs, ctx["peaks"]) / seconds
