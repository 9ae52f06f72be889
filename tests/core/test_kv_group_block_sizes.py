"""KV groups of unlike page SHAPE and equal page BYTES (PR 45): kinds of
layer that cost unlike bytes a token hold unlike tokens a page
(``KVCacheGroupSpec.block_size``), a kind whose layer count is no multiple
gets a short last group, and every count of pages is taken a group: by the
grouping, the allocator, the hit rule, the scheduler's ids and the runner's
tables. Groups of one page shape (Laguna) and a single spec (Mistral) are
what they were."""

from __future__ import annotations

import json
import math

import numpy as np
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

BS, WINDOW = 16, 128
PATTERN = [0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7 + [0]  # the published 48


def specs(pattern=PATTERN[:12], block_size=BS, window=WINDOW, dtype_bytes=2):
    """MiMo-V2.5's two kinds: a full layer of 4 KV heads, a window layer
    of 8, keys of 192 lanes beside values of 128."""
    common = dict(block_size=block_size, head_size=192, v_head_size=128,
                  dtype_bytes=dtype_bytes)
    return {
        f"layers.{i}": SlidingWindowSpec(num_kv_heads=8, sliding_window=window, **common)
        if p else FullAttentionSpec(num_kv_heads=4, **common)
        for i, p in enumerate(pattern)
    }


def manager(num_blocks=4096, caching=False, **kw):
    groups = group_kv_cache_specs(specs(**kw))
    return KVCacheManager(num_blocks=num_blocks, block_size=kw.get("block_size", BS),
                          enable_caching=caching, kv_cache_groups=groups)


def request(rid: str, n: int, block_size: int = BS, first: int = 0) -> Request:
    req = Request(rid, list(range(first, first + n)), SamplingParams(max_tokens=4096))
    req.block_hashes = make_block_hasher(block_size)(req)
    return req


def advance(m, req, n_tokens, chunk):
    while req.num_computed_tokens < n_tokens:
        n = min(chunk, n_tokens - req.num_computed_tokens)
        assert m.allocate_slots(req, n) is not None
        req.num_computed_tokens += n


def live(m, rid):
    return [sum(not b.is_null for b in mgr.req_to_blocks.get(rid, ())) for mgr in m.managers]


# ---------------------------------------------------------------- the spec


def test_a_spec_with_a_value_width_prices_a_padded_key_and_its_value():
    full = FullAttentionSpec(32, 4, 192, 2, v_head_size=128)
    window = SlidingWindowSpec(16, 8, 192, 2, v_head_size=128, sliding_window=128)
    # 384 lanes a KV head a token as held, 320 as published.
    assert full.page_size_bytes == 32 * 4 * 384 * 2 == 98_304
    assert window.page_size_bytes == 16 * 8 * 384 * 2 == 98_304
    assert full.published_token_bytes == 4 * 320 * 2 == 2560
    assert window.published_token_bytes == 8 * 320 * 2 == 5120
    # The same width for both (None, or said): the layouts there were.
    plain = FullAttentionSpec(16, 8, 128, 2)
    assert plain.page_size_bytes == FullAttentionSpec(
        16, 8, 128, 2, v_head_size=128).page_size_bytes == 16 * 16 * 128 * 2
    assert plain.published_token_bytes == 4096
    assert FullAttentionSpec(16, 8, 64, 2).page_size_bytes == 16 * 8 * 128 * 2


# ---------------------------------------------------------------- grouping


def test_two_to_one_bytes_a_token_give_32_and_16_tokens_a_page():
    groups = group_kv_cache_specs(specs())
    assert [len(g.layer_names) for g in groups] == [3, 3, 3, 3]
    assert [g.block_size for g in groups] == [32, 16, 16, 16]
    assert [g.sliding_window for g in groups] == [None, 128, 128, 128]
    # Equal page BYTES: 3 layers x 98,304.
    assert {g.page_size_bytes for g in groups} == {294_912}
    assert all(g.lost_bytes_per_page == 0 and g.num_places == 3 for g in groups)
    assert groups[0].layer_names == ["layers.0", "layers.5", "layers.11"]
    # 9 window layers over 3 groups: the kind's i-th layer in group i % 3.
    assert groups[1].layer_names == ["layers.1", "layers.4", "layers.8"]
    assert groups[3].layer_names == ["layers.3", "layers.7", "layers.10"]
    # float8: half the bytes, the same tokens a page.
    fp8 = group_kv_cache_specs(specs(dtype_bytes=1, block_size=32))
    assert [g.block_size for g in fp8] == [64, 32, 32, 32]
    assert {g.page_size_bytes for g in fp8} == {294_912}


def test_9_and_39_layers_give_a_short_last_group_with_its_loss_stated():
    groups = group_kv_cache_specs(specs(PATTERN))
    assert [len(g.layer_names) for g in groups] == [9, 9, 9, 9, 9, 3]
    assert [g.num_places for g in groups] == [9] * 6
    assert [g.block_size for g in groups] == [32] + [16] * 5
    assert {g.page_size_bytes for g in groups} == {9 * 98_304}
    assert [g.lost_bytes_per_page for g in groups] == [0] * 5 + [6 * 98_304]
    # 6 of 54 places hold no layer.
    assert sum(g.num_places - len(g.layer_names) for g in groups) == 6
    assert sum(g.num_places for g in groups) == 54
    # Filled in layer order: the window kind's first nine, ..., its last three.
    window = [f"layers.{i}" for i, p in enumerate(PATTERN) if p]
    assert groups[1].layer_names == window[:9] and groups[5].layer_names == window[36:]


def test_a_ratio_of_bytes_that_is_no_whole_number_is_refused():
    odd = specs()
    odd["layers.0"] = FullAttentionSpec(16, 3, 192, 2, v_head_size=128)
    with pytest.raises(ValueError, match="pages of equal bytes"):
        group_kv_cache_specs(odd)


def test_lagunas_and_mistrals_groups_are_what_they_were():
    """Read off the two accepted configurations through their own models:
    Laguna's four groups of ten layers at 16 tokens and 655,360 bytes a
    page, layer ``l`` in group ``l % 4``; Mistral's one group of every
    layer. What the parent's ``group_kv_cache_specs`` gave, value for
    value (``tests/benchmark/test_manifest_laguna.py`` holds the same
    numbers from PR 43)."""
    import jax.numpy as jnp
    from transformers import PretrainedConfig

    from benchmark.harness import manifest as M
    from vllm_tpu.models.registry import get_model_class

    def groups_of(cell_name):
        cfg = PretrainedConfig.from_dict(M.Cell(M.load_manifest(), cell_name).hf_config)
        model = get_model_class(cfg)(cfg, dtype=jnp.bfloat16)
        return model, group_kv_cache_specs(model.get_kv_cache_spec(16, 2))

    model, laguna = groups_of("lagunaxs2.code-mixed")
    assert [len(g.layer_names) for g in laguna] == [10] * 4
    assert [g.block_size for g in laguna] == [16] * 4
    assert [g.num_places for g in laguna] == [10] * 4
    assert [g.sliding_window for g in laguna] == [None, 512, 512, 512]
    assert {g.page_size_bytes for g in laguna} == {655_360}
    assert all(g.layer_names == [f"layers.{i}" for i in range(j, 40, 4)]
               for j, g in enumerate(laguna))
    assert all(g.kv_cache_spec.v_head_size is None for g in laguna)
    m = KVCacheManager(num_blocks=64, block_size=16, kv_cache_groups=laguna)
    assert [mgr.block_size for mgr in m.managers] == [16] * 4
    assert [mgr.hashes_per_block for mgr in m.managers] == [1] * 4
    _, (mistral,) = groups_of("mistral7b.chat")
    assert len(mistral.layer_names) == mistral.num_places == 16
    assert mistral.block_size == 16 and mistral.sliding_window is None
    assert mistral.page_size_bytes == 16 * 16 * 16 * 128 * 2
    assert mistral.lost_bytes_per_page == 0


# --------------------------------------------------------------- allocator


def test_pages_needed_are_counted_a_group():
    m = manager()
    assert [mgr.block_size for mgr in m.managers] == [32, 16, 16, 16]
    req = request("a", 100)
    new = m.allocate_slots(req, 100)
    # ceil(100 / 32) = 4 full pages, ceil(100 / 16) = 7 a window group.
    assert [len(g) for g in new] == [4, 7, 7, 7]
    assert m.get_num_free_blocks() == 4095 - 25
    req.num_computed_tokens = 100
    # Token 101..112 fit every group's last page.
    assert m.allocate_slots(req, 12) == ([], [], [], [])
    req.num_computed_tokens = 112
    # Token 113 opens a window page (112 = 7 x 16) and no full one (< 128).
    assert [len(g) for g in m.allocate_slots(req, 1)] == [0, 1, 1, 1]
    req.num_computed_tokens = 128
    assert [len(g) for g in m.allocate_slots(req, 1)] == [1, 1, 1, 1]
    assert [len(t) for t in m.get_block_ids("a")] == [5, 9, 9, 9]


def test_the_pool_refuses_what_the_groups_sum_to():
    m = manager(num_blocks=1 + 24)
    req = request("a", 100)
    assert m.allocate_slots(req, 100) is None  # 4 + 3 x 7 = 25 of 24
    assert m.get_num_free_blocks() == 24 and not m.req_to_blocks.get("a")
    assert m.allocate_slots(req, 96) is not None  # 3 + 3 x 6 = 21
    assert m.pages_in_use_by_group() == [3, 6, 6, 6]


def test_the_scheduler_hands_on_lists_of_unlike_length():
    from vllm_tpu.core.scheduler import _block_ids
    from vllm_tpu.worker.input_batch import InputBatch

    m = manager()
    req = request("a", 100)
    ids = _block_ids(m.allocate_slots(req, 100))
    assert [len(g) for g in ids] == [4, 7, 7, 7] and ids == m.get_block_ids("a")
    batch = InputBatch(max_num_reqs=2, max_model_len=256, max_blocks_per_req=16,
                       num_kv_groups=4)
    batch._set_blocks(0, 0, ids)
    assert batch.num_blocks_by_group[:, 0].tolist() == [4, 7, 7, 7]
    assert batch.num_blocks[0] == 4  # group 0's, as a uniform model reads it
    req.num_computed_tokens = 112
    grown = _block_ids(m.allocate_slots(req, 1))
    assert [len(g) for g in grown] == [0, 1, 1, 1]  # not (): some group grew
    batch.req_states["a"] = type("S", (), {"in_batch_row": 0})()
    batch.append_block_ids("a", grown)
    assert batch.num_blocks_by_group[:, 0].tolist() == [4, 8, 8, 8]
    assert [list(t[0, :n]) for t, n in zip(batch.block_tables, (4, 8, 8, 8))] == [
        list(g) for g in m.get_block_ids("a")]
    req.num_computed_tokens = 113
    assert _block_ids(m.allocate_slots(req, 1)) == ()  # no group grew


def test_the_windows_floor_is_in_the_groups_own_pages():
    """Through a prefill of 4,096 tokens in chunks of 512 and 300 decode
    steps a window group never holds more than the window's 8 pages of 16,
    one for the straddle and the chunk being written; the full group holds
    a page every 32 tokens."""
    m = manager()
    req = request("a", 4096)
    most = [0] * 4
    while req.num_computed_tokens < 4096:
        assert m.allocate_slots(req, 512) is not None
        req.num_computed_tokens += 512
        most = [max(a, b) for a, b in zip(most, live(m, "a"))]
    assert most[0] == 4096 // 32
    assert max(most[1:]) <= WINDOW // 16 + 512 // 16 + 1
    most = [0] * 4
    for _ in range(300):
        assert m.allocate_slots(req, 1) is not None
        req.num_computed_tokens += 1
        most = [max(a, b) for a, b in zip(most, live(m, "a"))]
    assert max(most[1:]) <= WINDOW // 16 + 2 == 10
    assert most[0] == math.ceil((4096 + 300) / 32) == 138
    tables = m.get_block_ids("a")
    assert [len(t) for t in tables] == [138, 275, 275, 275]
    assert tables[1][0] == 0 and tables[0][0] != 0  # null where the window passed
    # What the issue reckons: a request of 700 + 640 tokens ends on 42 full
    # pages and some 10 in each window group.
    held = m.num_pages_held("a")
    assert held == sum(live(m, "a")) <= 138 + 3 * 10
    assert m.window_pages_freed == 3 * 275 - sum(live(m, "a")[1:])


def test_a_preempted_request_frees_every_group_and_its_pages_serve_another():
    m = manager(num_blocks=1 + 200)
    a = request("a", 1500)
    advance(m, a, 1500, 256)
    assert live(m, "a")[0] == 47 and all(n > 0 for n in live(m, "a")) and m.window_pages_freed > 0
    m.free(a)
    assert m.get_num_free_blocks() == 200 and m.pages_in_use_by_group() == [0] * 4
    assert all("a" not in mgr.req_to_blocks and "a" not in mgr.pages_held for mgr in m.managers)
    # A full group's page (32 tokens of 3 layers of 4 heads) now serves a
    # window group (16 tokens of 3 layers of 8 heads): one pool, one size.
    freed = {b.block_id for b in m.block_pool._free_queues[0].get_all_free_blocks()}
    b = request("b", 640, first=50_000)
    got = m.allocate_slots(b, 640)
    assert [len(g) for g in got] == [20, 40, 40, 40]
    assert {blk.block_id for group in got for blk in group} <= freed
    # Resumed from position 0: every group starts over at its own count.
    a.num_computed_tokens = 0
    assert m.allocate_slots(a, 256) is not None and live(m, "a") == [8, 16, 16, 16]


# ------------------------------------------------------------ the hit rule


def test_a_hit_is_a_multiple_of_the_largest_page():
    """Hashes are made 16 tokens at a time; a full group's page of 32 is
    keyed by the second of its two. After 200 tokens have run and gone a
    second request with the same tokens hits 192: six pages of 32, twelve
    entries of 16 of which the last eight (the window's run) are cached and
    the others null stand-ins."""
    m = manager(caching=True)
    assert [mgr.hashes_per_block for mgr in m.managers] == [2, 1, 1, 1]
    a = request("a", 200)
    advance(m, a, 200, 64)
    m.free(a)
    b = request("b", 200)
    hits, tokens = m.get_computed_blocks(b)
    assert tokens == 192 and [len(h) for h in hits] == [6, 12, 12, 12]
    assert not any(blk.is_null for blk in hits[0])
    assert [blk.is_null for blk in hits[1]] == [True] * 4 + [False] * 8
    assert m.allocate_slots(b, 8, new_computed_blocks=hits, num_new_computed_tokens=192)
    assert live(m, "b") == [7, 9, 9, 9]
    # 48 tokens in common: the window groups could give 48, the full group
    # 32, and 32 covers no window of 128 from a run that starts at 0... it
    # does (a run anchored at block 0 is a plain prefix): the hit is 32.
    c = Request("c", list(range(48)) + list(range(900, 1000)), SamplingParams(max_tokens=8))
    c.block_hashes = make_block_hasher(BS)(c)
    hits, tokens = m.get_computed_blocks(c)
    assert tokens == 32 and [len(h) for h in hits] == [1, 2, 2, 2]


def test_a_full_page_is_registered_when_both_of_its_hashes_are_there():
    m = manager(caching=True)
    a = request("a", 48)
    advance(m, a, 48, 48)
    keys = lambda g: sum(1 for k in m.block_pool.cached_block_hash_to_block if k.group_id == g)
    assert [keys(g) for g in range(4)] == [1, 3, 3, 3]  # 32 of 48 tokens; 3 x 16
    # The full group's key is the chain's SECOND hash: content of all 32.
    full_key = next(k for k in m.block_pool.cached_block_hash_to_block if k.group_id == 0)
    assert full_key.block_hash == a.block_hashes[1]


# ------------------------------------------------------------- the runner


def test_the_runner_and_the_stats_carry_a_block_size_a_group(tmp_path):
    """Through the engine at a tiny size (that the slots and tables made
    from them are right is what ``tests/benchmark/test_reference_mimo_v2.py``
    holds to the reference): the runner's block size a group, the padded
    blocks a request by the longest table, ONE cache array for both page
    shapes, and the scheduler's stats with the gauges fixed at start."""
    from tests.benchmark.tiny_mimo_v2 import model_dir
    from vllm_tpu import LLM
    from vllm_tpu import SamplingParams as SP

    llm = LLM(model=model_dir(tmp_path), load_format="dummy", seed=1, dtype="float32",
              max_model_len=256, block_size=8, num_gpu_blocks_override=128,
              max_num_seqs=4, max_num_batched_tokens=32)
    core = llm.llm_engine.engine_core.engine_core
    runner = core.executor.worker.runner
    assert runner.group_block_sizes == [16, 8, 8, 8] and runner._longest_tables == 1
    # 2 KV heads of 16 tokens and 4 of 8: 32 rows of 128 + 16 lanes.
    assert runner.kv_cache.shape == (3, 128, 32, 128 + 16)
    llm.generate([{"prompt_token_ids": list(range(3, 53))}],
                 SP(temperature=0.0, max_tokens=20, ignore_eos=True))
    # 70 tokens: 5 pages of 16, 9 of 8: the step's block bucket covers 9.
    assert max(b for _, _, b in runner._seen_buckets) >= 9
    stats = core.scheduler.make_stats()
    assert stats.kv_group_page_tokens == [16, 8, 8, 8]
    # Published: 3 layers x KH x (24 + 16) x 4 bytes; held: a page's bytes
    # over its tokens, 3 x KH x 144 lanes x 4 bytes.
    assert stats.kv_token_bytes == [
        {"published": 3 * 2 * 40 * 4.0, "held": 3 * 2 * 144 * 4.0},
        *[{"published": 3 * 4 * 40 * 4.0, "held": 3 * 4 * 144 * 4.0}] * 3]
    assert stats.kv_pages_allocated == [5, 9, 9, 9]
    llm.shutdown()
