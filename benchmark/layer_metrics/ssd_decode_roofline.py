"""Layer: kernels (ops/mamba.py, the one-token form of the Mamba-2 rule).
Share of its roofline that the state update of the decode rows reaches,
bound by BYTES.

Time: the device time of ``ssd_decode_kernel`` in the traced slices (the
decode-only steps and the rows of one token of the mixed steps). Least
time: the rows it takes, ``vllm:linear_attn_tokens_total{kind="decode"}``
(rows of one token, summed over the layers), each reading and writing its
state once with its token's operands (``benchmark/kernels/ssd.py``), over
the chip's HBM bandwidth. The counter is scraped inside each slice:
under-read, never over-read. None where the program has no such counter or
the trace no such kernel (a program that runs the rule as XLA, or the
parent, which cannot run the configuration)."""


def read(ctx):
    trace, slice_delta = ctx.get("trace"), ctx.get("slice_delta")
    if not trace or slice_delta is None or "mamba_d_state" not in ctx["config"]:
        return None
    counts = ctx["kernel_counts"]("ssd")
    seconds = counts.kernel_seconds(trace)
    rows = slice_delta('vllm:linear_attn_tokens_total{kind="decode"}')
    if seconds <= 0 or rows is None:
        return None
    return 100.0 * counts.least_seconds(ctx["config"], rows, ctx["peaks"]) / seconds
