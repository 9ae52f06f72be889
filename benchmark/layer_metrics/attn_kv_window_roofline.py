"""Layer: kernels (ops/rpa_kernel.py, ops/rpa_decode_kernel.py under the
names ``rpa_kernel_window`` and ``rpa_decode_kernel_window``, over pages
whose rows hold a key and a value of unlike widths). Share of their
roofline that the WINDOW layers' attention calls reach, the larger of
bytes and of operations, counted at the PUBLISHED shapes (``benchmark/
kernels/attn_qk_v.py``): 320 lanes a KV head a key, not the 384 a page
holds; what the layout pads and the kernel over-reads lowers the share.

Time: the device time of the two kernel names in the traced slices. Least
time: the keys and values the rows' windows cover,
``vllm:attn_context_tokens_total{kind="window"}`` (``min(context, queries +
window - 1)`` a row a step), plus q and o of the steps' tokens, over the
chip's HBM bandwidth; or the operations of ``vllm:window_attn_pairs_total``,
the pairs the window's mask lets through. The counters are scraped inside
each slice: under-read, never over-read. The kernels' names and their time
are ``kernels/window_attn.py``'s. None where the program has no such
counter or kernel."""


def read(ctx):
    trace, slice_delta = ctx.get("trace"), ctx.get("slice_delta")
    if not trace or slice_delta is None:
        return None
    counts, named = ctx["kernel_counts"]("attn_qk_v"), ctx["kernel_counts"]("window_attn")
    seconds = named.kernel_seconds(trace, named.WINDOW)
    keys = slice_delta('vllm:attn_context_tokens_total{kind="window"}')
    pairs = slice_delta("vllm:window_attn_pairs_total")
    if seconds <= 0 or keys is None or pairs is None:
        return None
    tokens = ((slice_delta('vllm:engine_step_tokens_total{kind="decode"}') or 0.0)
              + (slice_delta('vllm:engine_step_tokens_total{kind="mixed"}') or 0.0))
    return 100.0 * counts.least_seconds(
        ctx["config"], counts.WINDOW, keys, tokens, pairs, ctx["peaks"]) / seconds
