"""Layer: kernels (ops/rpa_decode_kernel.py). Share of its roofline that the
decode attention kernel reaches, bound by BYTES, from the program's own
count of the work.

Time: the device time of ``rpa_decode_kernel`` in the traced slices. Least
time: the KV bytes its steps must read, from
``vllm:attn_context_tokens_total{kind="decode"}`` (the runner's sum, over
the rows of every decode-only step, of the positions each row attends to)
times the bytes a context token holds (``benchmark/kernels/decode_attn.py``),
over the chip's HBM bandwidth. The counter is scraped just after each
slice's start has returned and just before its stop is sent, so its delta
covers no more steps than the trace does: the share is under-read by the
edges, never over-read. ``decode_attn_roofline`` is the client's estimate of
the same share."""


def read(ctx):
    trace, slice_delta = ctx.get("trace"), ctx.get("slice_delta")
    if not trace or slice_delta is None:
        return None
    counts = ctx["kernel_counts"]("decode_attn")
    seconds = trace["kernel_seconds"].get(counts.DECODE_KERNEL, 0.0)
    ctx_tokens = slice_delta('vllm:attn_context_tokens_total{kind="decode"}')
    if seconds <= 0 or ctx_tokens is None:
        return None
    return 100.0 * counts.least_seconds(ctx["config"], ctx_tokens, ctx["peaks"]) / seconds
