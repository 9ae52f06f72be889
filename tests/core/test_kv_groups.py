"""KV groups over one page pool (``core/kv_cache_manager.py``): a block
table a group and request, window groups that give back the pages their
window has passed, the pool's count over all groups, the prefix-cache hit
rule over groups, and the one-group case as it always was."""

from __future__ import annotations

import math

import pytest

from vllm_tpu.core.kv_cache_manager import KVCacheManager
from vllm_tpu.core.kv_cache_utils import (
    FullAttentionSpec,
    SlidingWindowSpec,
    group_kv_cache_specs,
    make_block_hasher,
)
from vllm_tpu.request import Request
from vllm_tpu.sampling_params import SamplingParams

BS, WINDOW = 16, 512


def specs(full: int, window: int, *, sliding_window: int = WINDOW, block_size: int = BS):
    """``full + window`` layers in the 1:k pattern of a Laguna-like stack."""
    common = dict(block_size=block_size, num_kv_heads=8, head_size=128, dtype_bytes=2)
    k = window // full if full else 0
    out = {}
    for i in range(full + window):
        is_full = k == 0 or i % (k + 1) == 0 if full else False
        out[f"layers.{i}"] = (
            FullAttentionSpec(**common) if is_full
            else SlidingWindowSpec(**common, sliding_window=sliding_window))
    return out


def manager(num_blocks=512, *, full=2, window=6, caching=False, sliding_window=WINDOW,
            block_size=BS):
    groups = group_kv_cache_specs(
        specs(full, window, sliding_window=sliding_window, block_size=block_size))
    return KVCacheManager(num_blocks=num_blocks, block_size=block_size,
                          enable_caching=caching, kv_cache_groups=groups)


def request(rid: str, n: int, block_size: int = BS, first: int = 0) -> Request:
    req = Request(rid, list(range(first, first + n)), SamplingParams(max_tokens=4096))
    hasher = make_block_hasher(block_size)
    req.block_hashes = hasher(req)
    return req


def advance(m, req, n_tokens, chunk):
    """Prefill ``n_tokens`` in chunks, as the scheduler does."""
    while req.num_computed_tokens < n_tokens:
        n = min(chunk, n_tokens - req.num_computed_tokens)
        assert m.allocate_slots(req, n) is not None
        req.num_computed_tokens += n


def live(m, rid):
    return [sum(not b.is_null for b in mgr.req_to_blocks.get(rid, ())) for mgr in m.managers]


# ---------------------------------------------------------------- grouping


def test_one_spec_gives_one_group_and_todays_block_count():
    uniform = {f"layers.{i}": FullAttentionSpec(16, 8, 128, 2) for i in range(32)}
    groups = group_kv_cache_specs(uniform)
    assert len(groups) == 1 and len(groups[0].layer_names) == 32
    page = 32 * 16 * 16 * 128 * 2
    assert groups[0].page_size_bytes == page
    # The worker divides the free memory by it: every layer's page, as ever.
    assert (10 * page + 5) // groups[0].page_size_bytes == 10
    m = KVCacheManager(num_blocks=64, block_size=16, kv_cache_groups=groups)
    assert m.num_groups == 1 and m.req_to_blocks is m.managers[0].req_to_blocks
    req = request("a", 40)
    (blocks,) = m.allocate_slots(req, 40)  # one list a group: a tuple of one
    assert len(blocks) == 3
    assert m.get_block_ids("a") == ([b.block_id for b in blocks],)
    assert m.num_pages_held("a") == 3


def test_window_and_full_layers_make_groups_of_equal_layer_count():
    groups = group_kv_cache_specs(specs(10, 30))
    assert [len(g.layer_names) for g in groups] == [10, 10, 10, 10]
    assert [g.sliding_window for g in groups] == [None, WINDOW, WINDOW, WINDOW]
    assert groups[0].layer_names == [f"layers.{i}" for i in range(0, 40, 4)]
    # A kind's i-th layer goes to its group i % k: one layer of each window
    # group a period.
    assert groups[1].layer_names[:3] == ["layers.1", "layers.5", "layers.9"]
    assert groups[3].layer_names[-1] == "layers.39"
    # A page id costs ONE group's layers: 16 tokens x 10 layers x 4,096 bytes.
    assert groups[0].page_size_bytes == 16 * 10 * 4096 == 655_360
    assert 2_700_000_000 // groups[0].page_size_bytes == 4119


def test_groups_that_cannot_share_a_page_are_refused():
    """Since PR 45 a kind whose layer count is no multiple gets a short
    last group and a kind of half the bytes a token twice the tokens a
    page (``tests/core/test_kv_group_block_sizes.py``); what is still
    refused is a ratio of bytes that is no whole number."""
    uneven = {**specs(2, 6), "layers.8": FullAttentionSpec(16, 8, 128, 2),
              "layers.9": FullAttentionSpec(16, 8, 128, 2)}
    del uneven["layers.9"]  # 3 full layers beside 6 window layers: fine
    assert len(group_kv_cache_specs(uneven)) == 3
    uneven["layers.9"] = SlidingWindowSpec(16, 8, 128, 2, sliding_window=WINDOW)
    groups = group_kv_cache_specs(uneven)  # 3 full, 7 window
    assert [len(g.layer_names) for g in groups] == [3, 3, 3, 1]
    other_page = {**specs(2, 6), "layers.0": FullAttentionSpec(16, 3, 128, 2)}
    with pytest.raises(ValueError, match="pages of equal bytes"):
        group_kv_cache_specs(other_page)  # 3 KV heads beside 8


def test_context_parallel_stripes_with_several_groups_raise():
    with pytest.raises(NotImplementedError, match="context parallelism"):
        KVCacheManager(num_blocks=64, block_size=16, num_stripes=2,
                       kv_cache_groups=group_kv_cache_specs(specs(2, 6)))


@pytest.mark.parametrize("n_groups", [1, 4])
def test_a_block_table_is_one_list_a_group_from_the_manager_to_the_runner(n_groups):
    """ONE format whatever the count of groups: a tuple of one list a
    group out of the manager, through the scheduler's ids, into the
    runner's tables; ``()`` where a row took no block."""
    from vllm_tpu.core.scheduler import _block_ids
    from vllm_tpu.worker.input_batch import InputBatch

    m = (KVCacheManager(num_blocks=64, block_size=BS) if n_groups == 1
         else manager(num_blocks=64, full=1, window=3))
    assert m.num_groups == n_groups
    req = request("a", 40)
    assert m.get_computed_blocks(req) == (tuple([] for _ in range(n_groups)), 0)
    new = m.allocate_slots(req, 40)
    assert isinstance(new, tuple) and [len(g) for g in new] == [3] * n_groups
    ids = _block_ids(new)
    assert ids == m.get_block_ids("a") and isinstance(ids, tuple)
    req.num_computed_tokens = 40
    none = m.allocate_slots(req, 1)  # the last block has room
    assert none == tuple([] for _ in range(n_groups)) and _block_ids(none) == ()
    batch = InputBatch(max_num_reqs=2, max_model_len=64, max_blocks_per_req=8,
                       num_kv_groups=n_groups)
    batch._set_blocks(0, 0, ids)
    assert [list(t[0, :3]) for t in batch.block_tables] == [list(g) for g in ids]
    assert batch.num_blocks[0] == 3
    with pytest.raises(ValueError):  # a table for every group, no fewer
        batch._set_blocks(0, 0, ids + ([1, 2, 3],))


# --------------------------------------------------------------- allocator


@pytest.mark.parametrize("chunk", [512, 128])
def test_a_window_group_holds_a_window_and_a_chunk_of_pages(chunk):
    """Through a prefill of 8,192 tokens in chunks and 600 decode steps a
    window group never holds more than the window's pages, a page for the
    straddle and the chunk being written; while decoding that is
    ``ceil(512 / 16) + 2``. The full group holds every page."""
    m = manager(num_blocks=2048)
    req = request("a", 8192)
    most = [0] * 4
    while req.num_computed_tokens < 8192:
        assert m.allocate_slots(req, chunk) is not None
        req.num_computed_tokens += chunk
        most = [max(a, b) for a, b in zip(most, live(m, "a"))]
    assert most[0] == 8192 // BS
    assert max(most[1:]) <= math.ceil(WINDOW / BS) + math.ceil(chunk / BS) + 1
    most = [0] * 4
    for _ in range(600):
        assert m.allocate_slots(req, 1) is not None
        req.num_computed_tokens += 1
        most = [max(a, b) for a, b in zip(most, live(m, "a"))]
    assert max(most[1:]) <= math.ceil(WINDOW / BS) + 2
    assert most[0] == math.ceil((8192 + 600) / BS)
    # Every table has one entry a block of the sequence, null where freed.
    tables = m.get_block_ids("a")
    assert len({len(t) for t in tables}) == 1 and tables[1][0] == 0 and tables[0][0] != 0
    held = m.num_pages_held("a")
    assert held == sum(live(m, "a")) == sum(m.pages_in_use_by_group())
    # One group managing all four as full would hold 4 x 550 pages.
    assert held < 0.31 * 4 * len(tables[0])
    assert m.window_pages_freed == 3 * len(tables[0]) - sum(live(m, "a")[1:])


def test_freed_pages_come_back_to_the_pool_and_go_to_another_request():
    m = manager(num_blocks=1 + 4 * 40)  # 160 pages: 40 blocks in four groups
    a = request("a", 640)
    advance(m, a, 640, 64)
    # 40 blocks of the sequence; without giving back: 160 pages, all of it.
    assert live(m, "a")[0] == 40 and max(live(m, "a")[1:]) <= 32 + 4 + 1
    free = m.get_num_free_blocks()
    assert free == 160 - sum(live(m, "a")) > 0
    freed_ids = {b.block_id for b in m.block_pool._free_queues[0].get_all_free_blocks()}
    b = request("b", 16 * (free // 4), first=10_000)
    got = m.allocate_slots(b, b.num_tokens)
    assert got is not None and m.get_num_free_blocks() == free % 4
    assert {blk.block_id for group in got for blk in group} <= freed_ids
    # The pool is empty now: one more block a group cannot be had.
    b.num_computed_tokens = b.num_tokens
    assert free % 4 or m.allocate_slots(b, 1) is None


def test_the_pool_counts_the_sum_over_groups():
    m = manager(num_blocks=1 + 10)  # 10 pages: two blocks a group, and two over
    req = request("a", 3 * BS)
    assert m.allocate_slots(req, 3 * BS) is None  # 12 pages asked of 10
    assert m.get_num_free_blocks() == 10 and not m.req_to_blocks.get("a")
    assert m.allocate_slots(req, 2 * BS) is not None
    assert m.get_num_free_blocks() == 2 and m.pages_in_use_by_group() == [2, 2, 2, 2]
    assert m.usage == pytest.approx(0.8)


def test_a_preempted_request_frees_every_group():
    m = manager(num_blocks=512)
    req = request("a", 1500)
    advance(m, req, 1500, 256)
    assert all(n > 0 for n in live(m, "a")) and m.window_pages_freed > 0
    m.free(req)
    assert m.get_num_free_blocks() == 511 and m.pages_in_use_by_group() == [0] * 4
    assert all("a" not in mgr.req_to_blocks and "a" not in mgr.pages_held for mgr in m.managers)
    assert all("a" not in mgr._first_live_blk for mgr in m.managers[1:])
    # Resumed from position 0: every group starts over.
    req.num_computed_tokens = 0
    assert m.allocate_slots(req, 256) is not None and live(m, "a") == [16] * 4


def test_the_counters_the_metrics_read():
    m = manager(num_blocks=512)
    req = request("a", 2048)
    advance(m, req, 2048, 512)
    taken = m.pages_allocated_by_group()
    assert taken == [128] * 4
    assert m.window_pages_freed == sum(taken[1:]) - sum(m.pages_in_use_by_group()[1:])
    assert m.num_pages_held("a") == sum(m.pages_in_use_by_group())


# ------------------------------------------------------------ the hit rule


def served(m, rid, n_tokens, chunk=64, block_size=4):
    req = request(rid, n_tokens, block_size)
    advance(m, req, n_tokens, chunk)
    return req


def test_a_hit_over_two_groups_is_where_both_hold_what_they_will_read():
    """Block size 4, window 8 (a run of two blocks covers it). After a
    request of 65 tokens has run and gone, its full group's 16 blocks and
    its window group's last blocks are cached; a second request with the
    same tokens hits 64 tokens: a contiguous prefix in the full group, the
    last run and null stand-ins in the window group."""
    m = manager(num_blocks=256, full=1, window=1, caching=True, sliding_window=8, block_size=4)
    assert m.num_groups == 2
    a = served(m, "a", 65)
    m.free(a)
    b = request("b", 65, 4)
    hits, tokens = m.get_computed_blocks(b)
    assert tokens == 64 and isinstance(hits, tuple) and [len(h) for h in hits] == [16, 16]
    assert not any(blk.is_null for blk in hits[0])
    assert [blk.is_null for blk in hits[1]] == [True] * 14 + [False] * 2
    assert m.allocate_slots(b, 1, new_computed_blocks=hits, num_new_computed_tokens=64) is not None
    assert live(m, "b") == [17, 3]


def test_a_group_that_lost_its_run_shortens_the_hit_for_every_group():
    m = manager(num_blocks=256, full=1, window=1, caching=True, sliding_window=8, block_size=4)
    a = served(m, "a", 65, chunk=65)  # one chunk: the window group freed nothing yet
    # The window group loses block 14 from the cache: its last run now ends
    # at block 14 (tokens 0..55), and the full group follows it down.
    m.block_pool._maybe_evict_cached_block(m.managers[1].req_to_blocks["a"][14])
    m.free(a)
    hits, tokens = m.get_computed_blocks(request("b", 65, 4))
    assert tokens == 56 and [len(h) for h in hits] == [14, 14]
    # The FULL group loses block 5: a contiguous prefix of 5 blocks is all it
    # has, and the window group must cover position 20 from what it holds.
    m2 = manager(num_blocks=256, full=1, window=1, caching=True, sliding_window=8, block_size=4)
    a2 = served(m2, "a", 65, chunk=65)
    m2.block_pool._maybe_evict_cached_block(m2.managers[0].req_to_blocks["a"][5])
    m2.free(a2)
    hits, tokens = m2.get_computed_blocks(request("b", 65, 4))
    assert tokens == 20 and [len(h) for h in hits] == [5, 5]
    assert [blk.is_null for blk in hits[1]] == [True] * 3 + [False] * 2


def test_no_group_hits_where_another_holds_nothing():
    m = manager(num_blocks=256, full=1, window=1, caching=True, sliding_window=8, block_size=4)
    a = served(m, "a", 65, chunk=65)
    for blk in m.managers[1].req_to_blocks["a"]:
        m.block_pool._maybe_evict_cached_block(blk)
    m.free(a)
    hits, tokens = m.get_computed_blocks(request("b", 65, 4))
    assert (hits, tokens) == (([], []), 0)
    assert m.prefix_cache_stats.queries == 65 and m.prefix_cache_stats.hits == 0


def test_groups_cache_under_their_own_keys():
    """The same tokens are a page in each group: one content hash, a key a
    group, so that a window group's page never answers for the full one."""
    m = manager(num_blocks=256, full=1, window=1, caching=True, sliding_window=8, block_size=4)
    served(m, "a", 9, chunk=9)
    keys = list(m.block_pool.cached_block_hash_to_block)
    assert len(keys) == 4 and sorted(k.group_id for k in keys) == [0, 0, 1, 1]
    assert len({k.block_hash for k in keys}) == 2
