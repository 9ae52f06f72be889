"""Layer: KV cache manager (core/kv_cache_utils.py, KV groups of unlike
page shape over one pool). Of the bytes the pages taken in the window
hold, the share the model's config states: a group's published bytes a
token (``vllm:kv_token_bytes{group,what="published"}``: a key and a value
a KV head, no padding) over its held bytes a token (``what="held"``: the
page's lanes as laid out, and a short group's places no layer writes),
each weighted by the tokens of the pages the group took from the pool in
the window (delta of ``vllm:kv_pages_allocated_total{group}`` times
``vllm:kv_group_page_tokens{group}``). 83.3 where a key of 192 lanes is
held as 256 beside a value of 128; 100 for a layout without padding. None
where the program has no such gauge (a uniform model, the parent)."""


def read(ctx):
    m1 = ctx["m1"]
    published = held = 0.0
    group = 0
    while True:  # a sample a group, numbered from 0
        label = 'group="' + str(group) + '"'
        tokens = m1.get("vllm:kv_group_page_tokens{" + label + "}")
        pages = ctx["delta"]("vllm:kv_pages_allocated_total{" + label + "}")
        costs = [m1.get("vllm:kv_token_bytes{" + label + ',what="' + what + '"}')
                 for what in ("published", "held")]
        if tokens is None or pages is None or None in costs:
            break
        published += pages * tokens * costs[0]
        held += pages * tokens * costs[1]
        group += 1
    return 100.0 * published / held if held else None
