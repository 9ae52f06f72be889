"""Layer: kernels (ops/sampler_kernel.py). Sampling rows a step that the
fused sampling kernel did NOT take and the XLA epilogue sampled instead
(31 bisection passes over [rows, vocabulary] in HBM): delta of
``vllm:sampler_fallback_rows_total`` over the delta steps. A block pass
counts every position of its sampling rows. 0 where the kernel runs; all
greedy steps count as neither. None where the program has no such counter."""


def read(ctx):
    rows, steps = ctx["delta"]("vllm:sampler_fallback_rows_total"), ctx["steps"]()
    return rows / steps if steps and rows is not None else None
