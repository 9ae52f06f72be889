"""Layer: kernels (layers/moe.py, megablox ``gmm``). Share of its roofline
that the grouped matmul reaches, from the program's own count of the work.

Time: the device time of the ``gmm`` calls in the traced slices. Least
time: the larger of the bytes of the experts hit
(``vllm:moe_experts_hit_total``) over the chip's HBM bandwidth and the
operations of the token-expert pairs (``vllm:moe_assignments_total``) over
its bf16 peak (``benchmark/kernels/moe_gmm.py``). The counters are scraped
inside each slice (``slice_delta``), so their deltas cover no more steps
than the trace does: under-read, never over-read. None where the program
has no such counters or the trace no such kernel."""


def read(ctx):
    trace, slice_delta = ctx.get("trace"), ctx.get("slice_delta")
    if not trace or slice_delta is None:
        return None
    counts = ctx["kernel_counts"]("moe_gmm")
    seconds = counts.kernel_seconds(trace)
    hit = slice_delta("vllm:moe_experts_hit_total")
    pairs = slice_delta("vllm:moe_assignments_total")
    if seconds <= 0 or hit is None or pairs is None:
        return None
    return 100.0 * counts.least_seconds(ctx["config"], hit, pairs, ctx["peaks"]) / seconds
