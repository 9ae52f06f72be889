"""Layer: KV cache manager (core/kv_cache_manager.py). Requests preempted
in the window for want of cache blocks: delta of ``vllm:num_preemptions``."""


def read(ctx):
    return ctx["delta"]("vllm:num_preemptions_total", "vllm:num_preemptions")
