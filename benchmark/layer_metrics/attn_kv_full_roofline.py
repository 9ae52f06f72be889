"""Layer: kernels (ops/rpa_kernel.py, ops/rpa_decode_kernel.py under their
plain names, over pages whose rows hold a key and a value of unlike
widths). Share of their roofline that the FULL layers' attention calls
reach, the larger of bytes and of operations, counted at the PUBLISHED
shapes (``benchmark/kernels/attn_qk_v.py``): 4 KV heads of 320 lanes a
key, q of 64 x 192 and o of 64 x 128 a query token.

Time: the device time of ``rpa_kernel`` and ``rpa_decode_kernel`` in the
traced slices. Least time: the keys and values of the rows' whole
contexts, ``vllm:attn_context_tokens_total`` of the decode-only and the
mixed steps, plus q and o of the steps' tokens, over the chip's HBM
bandwidth; or the operations of the live pairs: the mixed steps' live
``vllm:attn_ragged_pairs_total`` (the runner counts the full layers' call)
and a key a decode row. Scraped inside each slice: under-read, never
over-read. The kernels' names and their time are ``kernels/
window_attn.py``'s. None where the program ran neither
kernel or counts no context."""


def read(ctx):
    trace, slice_delta = ctx.get("trace"), ctx.get("slice_delta")
    if not trace or slice_delta is None:
        return None
    counts, named = ctx["kernel_counts"]("attn_qk_v"), ctx["kernel_counts"]("window_attn")
    seconds = named.kernel_seconds(trace, named.FULL)
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
    return 100.0 * counts.least_seconds(
        ctx["config"], counts.FULL, keys, tokens, pairs, ctx["peaks"]) / seconds
