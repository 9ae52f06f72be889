"""Layer: kernels (ops/kda.py, the short convolution, as the LFM2 mixer
calls it: one stream, three taps, the tail the only state). Share of its
roofline that the convolution over the live rows' slots reaches.

Time: the device time of ``kda_conv_kernel`` in the traced slices (one call
a convolution layer in every step). Least time, by
``benchmark/kernels/short_conv.py::least_seconds``: the tokens of both
kinds of row, ``vllm:linear_attn_tokens_total{kind="chunk"}`` +
``{kind="decode"}``, times a token's channels in and float32 out, plus the
live rows, ``vllm:linear_attn_state_rows_total``, times a tail read and
written, over the chip's HBM bandwidth: the LEAST bytes any form must move.
The counters are scraped inside each slice: under-read, never over-read.
None where the configuration has no such mixer, the program no such counter
or the trace no such kernel."""


def read(ctx):
    trace, slice_delta = ctx.get("trace"), ctx.get("slice_delta")
    if not trace or slice_delta is None or "conv_L_cache" not in ctx["config"]:
        return None
    counts = ctx["kernel_counts"]("short_conv")
    seconds = counts.kernel_seconds(trace)
    parts = [slice_delta(name) for name in (
        'vllm:linear_attn_tokens_total{kind="chunk"}',
        'vllm:linear_attn_tokens_total{kind="decode"}',
        "vllm:linear_attn_state_rows_total")]
    if seconds <= 0 or None in parts:
        return None
    chunk, decode, rows = parts
    return 100.0 * counts.least_seconds(ctx["config"], chunk + decode, rows, ctx["peaks"]) / seconds
