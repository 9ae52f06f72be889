"""Layer: kernels (ops/kda.py, the one-token form). Share of its roofline
that the state update of the decode rows reaches, bound by BYTES.

Time: the device time of ``kda_decode_kernel`` in the traced slices (the
decode-only steps and the decode rows of the mixed steps). Least time: the
rows it takes, ``vllm:linear_attn_tokens_total{kind="decode"}`` (rows of
one token, summed over the KDA layers), each reading and writing its state
once with its token's operands (``benchmark/kernels/kda.py``), over the
chip's HBM bandwidth. The chunked form is a kernel of its own since PR 35
(``kda_chunk_roofline``): neither its time nor its tokens are in here. The counter is
scraped inside each slice: under-read, never over-read. None where the
program has no such counter or the trace no such kernel."""


def read(ctx):
    trace, slice_delta = ctx.get("trace"), ctx.get("slice_delta")
    if not trace or slice_delta is None:
        return None
    counts = ctx["kernel_counts"]("kda")
    seconds = counts.kernel_seconds(trace)
    rows = slice_delta('vllm:linear_attn_tokens_total{kind="decode"}')
    if seconds <= 0 or rows is None:
        return None
    return 100.0 * counts.least_seconds(ctx["config"], rows, 0.0, ctx["peaks"]) / seconds
