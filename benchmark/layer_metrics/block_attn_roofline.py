"""Layer: kernels (ops/rpa_kernel.py, mask causal over blocks). Share of
its roofline that attention over blocks reaches, bound by BYTES.

Time: the device time of ``rpa_kernel`` in the traced slices (every step
under generation by diffusion runs it; there is no decode-only kernel).
Least time: the KV bytes the block rows must read, from
``vllm:attn_context_tokens_total{kind="block"}`` (the context a block row
attends to, counted once a row) times the bytes a context token holds
(``benchmark/kernels/block_attn.py``), over the chip's HBM bandwidth. The
prefill chunks that share the kernel's calls are not counted, and the
counter is scraped inside each slice: under-read, never over-read. None
where the program has no such counter."""


def read(ctx):
    trace, slice_delta = ctx.get("trace"), ctx.get("slice_delta")
    if not trace or slice_delta is None:
        return None
    counts = ctx["kernel_counts"]("block_attn")
    seconds = trace["kernel_seconds"].get(counts.TRACE_KERNEL, 0.0)
    ctx_tokens = slice_delta('vllm:attn_context_tokens_total{kind="block"}')
    if seconds <= 0 or ctx_tokens is None:
        return None
    return 100.0 * counts.least_seconds(ctx["config"], ctx_tokens, ctx["peaks"]) / seconds
