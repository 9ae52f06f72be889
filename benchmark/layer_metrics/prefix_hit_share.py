"""Layer: KV cache manager (core/kv_cache_manager.py). Share of the prompt
tokens looked up in the window that the prefix cache served: delta hits /
delta queries."""


def read(ctx):
    q = ctx["delta"]("vllm:prefix_cache_queries_total", "vllm:prefix_cache_queries")
    h = ctx["delta"]("vllm:prefix_cache_hits_total", "vllm:prefix_cache_hits")
    return 100.0 * h / q if q else None
