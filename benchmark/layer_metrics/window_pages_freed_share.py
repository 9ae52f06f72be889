"""Layer: KV cache manager (core/kv_cache_manager.py, KV groups over one
page pool). Of the pages all groups took from the pool in the window, the
share the WINDOW groups gave back before their request ended (wholly under
the window's floor): delta of ``vllm:kv_window_pages_freed_total`` over the
summed deltas of ``vllm:kv_pages_allocated_total{group}``. It is the part
of the pool's traffic that one group managing every layer as full would
have kept until the request's end. None where the program has no such
counter (one group: nothing to give back)."""


def read(ctx):
    freed = ctx["delta"]("vllm:kv_window_pages_freed_total")
    taken, group = 0.0, 0
    while True:  # a sample a group, numbered from 0
        got = ctx["delta"]('vllm:kv_pages_allocated_total{group="' + str(group) + '"}')
        if got is None:
            break
        taken, group = taken + got, group + 1
    return 100.0 * freed / taken if freed is not None and taken else None
