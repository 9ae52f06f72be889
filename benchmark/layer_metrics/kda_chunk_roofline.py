"""Layer: kernels (ops/kda.py, the chunked form). Share of its roofline
that the state update of the rows with more than one token reaches.

Time: the device time of ``kda_chunk_kernel`` in the traced slices (the
mixed steps; the one-token kernel beside it is ``kda_roofline``'s). Least
time: the tokens it takes, ``vllm:linear_attn_tokens_total{kind="chunk"}``
(tokens of the rows of several tokens, summed over the KDA layers), through
``benchmark/kernels/kda.py::least_seconds``: the larger of their bytes
(operands, output, a 64th of a state in and out a token) over the chip's
HBM bandwidth and their operations over its bf16 peak. The kernel computes
in float32 from three bfloat16 passes, so the bound is low and the share
cannot over-read. The counter is scraped inside each slice: under-read,
never over-read. None where the program has no such counter or the trace
no such kernel (the parent of PR 35 runs the chunks as XLA fusions)."""

TRACE_KERNEL = "kda_chunk_kernel"


def read(ctx):
    trace, slice_delta = ctx.get("trace"), ctx.get("slice_delta")
    if not trace or slice_delta is None:
        return None
    seconds = sum(s for name, s in trace["kernel_seconds"].items()
                  if name.split(".")[0] == TRACE_KERNEL)
    tokens = slice_delta('vllm:linear_attn_tokens_total{kind="chunk"}')
    if seconds <= 0 or tokens is None:
        return None
    least = ctx["kernel_counts"]("kda").least_seconds(ctx["config"], 0.0, tokens, ctx["peaks"])
    return 100.0 * least / seconds
