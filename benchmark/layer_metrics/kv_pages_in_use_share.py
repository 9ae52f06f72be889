"""Layer: KV cache manager (core/kv_cache_manager.py). Mean over the
window's steps of the pages requests hold (all groups) over the pages the
pool has: delta of ``vllm:kv_page_steps_total{kind="held"}`` over delta of
``{kind="pool"}``, each a sum over the scheduler's steps. It says whether
the pages bind: near 100 the next request waits for pages, far under it
the batch is set by something else. None where the program has no such
counter."""


def read(ctx):
    held = ctx["delta"]('vllm:kv_page_steps_total{kind="held"}')
    pool = ctx["delta"]('vllm:kv_page_steps_total{kind="pool"}')
    return 100.0 * held / pool if held is not None and pool else None
