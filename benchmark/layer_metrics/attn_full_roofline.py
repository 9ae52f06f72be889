"""Layer: kernels (ops/rpa_kernel.py, ops/rpa_decode_kernel.py under their
plain names). Share of their roofline that the FULL layers' attention
calls reach in a model that mixes window and full layers (the reader asks
for the window layers' counter so that it reads nothing in a model whose
every layer runs under the plain names).

Time: the device time of ``rpa_kernel`` and ``rpa_decode_kernel`` in the
traced slices. Least time (``benchmark/kernels/window_attn.py``): the keys
and values of the rows' whole contexts, ``vllm:attn_context_tokens_total``
of the decode-only and the mixed steps, plus q and o of the steps' tokens
at the full layers' head count, over the chip's HBM bandwidth; or the
operations of the live pairs: the mixed steps' live
``vllm:attn_ragged_pairs_total`` (the runner counts the full layers' call)
and a key a decode row. Scraped inside each slice: under-read, never over-read."""


def read(ctx):
    trace, slice_delta = ctx.get("trace"), ctx.get("slice_delta")
    if not trace or slice_delta is None:
        return None
    counts = ctx["kernel_counts"]("window_attn")
    seconds = counts.kernel_seconds(trace, counts.FULL)
    if seconds <= 0 or slice_delta("vllm:window_attn_rows_total") is None:
        return None
    get = lambda name: slice_delta(name) or 0.0
    decode_keys = get('vllm:attn_context_tokens_total{kind="decode"}')
    keys = decode_keys + get('vllm:attn_context_tokens_total{kind="mixed"}')
    tokens = (get('vllm:engine_step_tokens_total{kind="decode"}')
              + get('vllm:engine_step_tokens_total{kind="mixed"}'))
    pairs = decode_keys + get('vllm:attn_ragged_pairs_total{kind="live"}')
    return 100.0 * counts.least_seconds(
        ctx["config"], counts.FULL, keys, tokens, pairs, ctx["peaks"]) / seconds
