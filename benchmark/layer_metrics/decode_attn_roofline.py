"""Layer: kernels (ops/rpa_decode_kernel.py). Share of its roofline that the
decode attention kernel reaches, bound by BYTES.

Time: the device time of ``rpa_decode_kernel`` in the traced slices, the
kernel of the decode-only steps. Least time: the KV bytes those steps must
read (``benchmark/kernels/decode_attn.py``) over the chip's HBM bandwidth.
The client's record gives the context length of every decode token that
arrived in the slices, but not the step it came from: a token of a mixed
step (a prefill chunk beside the decodes) comes from the ragged kernel,
``rpa_kernel``. Both kernels run once a layer in their steps, so the
decode-only steps' share of the slices' steps is the decode kernel's share
of the two kernels' calls, and that share of the tokens' bytes is counted:
an estimate that takes the mixed steps to hold as many running requests as
the decode-only steps around them."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    counts = ctx["kernel_counts"]("decode_attn")
    seconds = trace["kernel_seconds"].get(counts.DECODE_KERNEL, 0.0)
    calls = {k: trace["kernel_calls"].get(k, 0) for k in counts.TRACE_KERNELS}
    if seconds <= 0 or not calls[counts.DECODE_KERNEL]:
        return None
    _, ctx_tokens = ctx["decode_context_tokens"]()
    share = calls[counts.DECODE_KERNEL] / sum(calls.values())
    least = counts.least_seconds(ctx["config"], ctx_tokens * share, ctx["peaks"])
    return 100.0 * least / seconds
