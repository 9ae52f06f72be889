"""Layer: kernels (ops/rpa_kernel.py). Of the (query token, key) pairs the
ragged attention kernel's tiles computed over the window, the share the
mask let through: delta of ``vllm:attn_ragged_pairs_total{kind="live"}``
over delta of ``vllm:attn_ragged_pairs_total{kind="computed"}``. The
runner counts both from each step's query and context lengths by the
kernel's own choice of rung and of key extent; decode-only steps run
another kernel and count nothing. At 100 a visit costs what is live in it.
None where the program has no such counter, or ran the kernel in no step."""


def read(ctx):
    live = ctx["delta"]('vllm:attn_ragged_pairs_total{kind="live"}')
    computed = ctx["delta"]('vllm:attn_ragged_pairs_total{kind="computed"}')
    return 100.0 * live / computed if live is not None and computed else None
