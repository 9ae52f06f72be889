"""Layer: kernels (ops/mla_kernel.py). Share of its roofline that latent
attention reaches: the larger of its BYTES and of its OPERATIONS.

Time: the device time of ``mla_kernel`` in the traced slices (every step
of a latent-attention model runs it, chunks and decode rows alike). Least
time (``benchmark/kernels/mla_attn.py``): the latent rows the step's rows
must read, from ``vllm:attn_context_tokens_total{kind="latent"}`` (the
context a row attends to, counted once a row a step) times the bytes a
context token holds over the latent layers, over the chip's HBM
bandwidth; or, where that is more, the operations of the live (query
token, key) pairs, ``vllm:attn_ragged_pairs_total{kind="live"}`` (which
the runner fills from the latent kernel in such a model), over the bf16
peak. The decode rows are bound by the first, a prefill chunk by the
second. The counters are scraped inside each slice: under-read, never
over-read. None where the program has no such counter."""


def read(ctx):
    trace, slice_delta = ctx.get("trace"), ctx.get("slice_delta")
    if not trace or slice_delta is None:
        return None
    counts = ctx["kernel_counts"]("mla_attn")
    seconds = trace["kernel_seconds"].get(counts.TRACE_KERNEL, 0.0)
    ctx_tokens = slice_delta('vllm:attn_context_tokens_total{kind="latent"}')
    if seconds <= 0 or ctx_tokens is None:
        return None
    pairs = slice_delta('vllm:attn_ragged_pairs_total{kind="live"}') or 0.0
    return 100.0 * counts.least_seconds(ctx["config"], ctx_tokens, ctx["peaks"], pairs) / seconds
