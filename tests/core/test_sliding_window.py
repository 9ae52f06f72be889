"""Sliding-window KV management: blocks wholly outside the attention
window are freed (reference: single_type_kv_cache_manager.py:507
SlidingWindowManager), bounding per-request memory.
"""

from __future__ import annotations

import numpy as np
import pytest

from vllm_tpu.core.kv_cache_manager import KVCacheManager
from vllm_tpu.request import EngineCoreRequest, Request
from vllm_tpu.sampling_params import SamplingParams


def make_request(rid: str, prompt_len: int) -> Request:
    core = EngineCoreRequest(
        request_id=rid,
        prompt_token_ids=list(range(prompt_len)),
        sampling_params=SamplingParams(max_tokens=256, ignore_eos=True),
    )
    return Request.from_engine_core_request(core, None)


def test_out_of_window_blocks_freed():
    m = KVCacheManager(
        num_blocks=64, block_size=4, enable_caching=False, sliding_window=16
    )
    req = make_request("a", 64)
    (blocks,) = m.allocate_slots(req, 64)  # one list a KV group
    assert len(blocks) == 16
    free_before = m.get_num_free_blocks()

    # Advance to computed=64, schedule one more token: queries at pos >= 64
    # need keys > 64 - 16 = 48 -> blocks for tokens < 49 (indices 0..11)
    # are dead.
    req.num_computed_tokens = 64
    new = m.allocate_slots(req, 1)
    assert new is not None
    req_blocks = m.req_to_blocks["a"]
    assert all(b.is_null for b in req_blocks[:12])
    assert not any(b.is_null for b in req_blocks[12:])
    assert m.get_num_free_blocks() >= free_before + 12 - 1


def test_window_bounds_memory_for_long_decode():
    """A windowed request decodes far past pool capacity without failing."""
    bs, window = 4, 16
    m = KVCacheManager(
        num_blocks=10, block_size=bs, enable_caching=False,
        sliding_window=window,
    )
    req = make_request("a", 8)
    assert m.allocate_slots(req, 8) is not None
    req.num_computed_tokens = 8
    # Decode 200 tokens one at a time: would need 52 blocks unwindowed.
    for step in range(200):
        got = m.allocate_slots(req, 1)
        assert got is not None, f"allocation failed at step {step}"
        req.num_computed_tokens += 1
    live = sum(1 for b in m.req_to_blocks["a"] if not b.is_null)
    assert live <= window // bs + 2


def test_full_attention_unaffected():
    m = KVCacheManager(num_blocks=16, block_size=4, enable_caching=False)
    req = make_request("a", 32)
    assert m.allocate_slots(req, 32) is not None
    req.num_computed_tokens = 32
    assert m.allocate_slots(req, 1) is not None
    assert not any(b.is_null for b in m.req_to_blocks["a"])


def make_hashed_request(rid: str, prompt, block_size: int) -> Request:
    from vllm_tpu.core.kv_cache_utils import make_block_hasher

    core = EngineCoreRequest(
        request_id=rid,
        prompt_token_ids=list(prompt),
        sampling_params=SamplingParams(max_tokens=256, ignore_eos=True),
    )
    return Request.from_engine_core_request(
        core, make_block_hasher(block_size)
    )


def test_window_aware_prefix_hit():
    """A windowed manager serves prefix hits as a cached suffix RUN
    covering the window, with null stand-ins before it (reference:
    SlidingWindowManager.find_longest_cache_hit)."""
    bs, window = 4, 16  # required run = ceil(15/4) = 4 blocks
    m = KVCacheManager(
        num_blocks=64, block_size=bs, enable_caching=True,
        sliding_window=window,
    )
    prompt = list(range(100, 165))  # 65 tokens -> 16 full blocks
    r1 = make_hashed_request("a", prompt, bs)
    assert m.allocate_slots(r1, 65) is not None  # registers 16 full blocks
    m.free(r1)

    r2 = make_hashed_request("b", prompt, bs)
    (hit_blocks,), hit_tokens = m.get_computed_blocks(r2)
    # Hit capped at num_tokens-1 -> 16 blocks / 64 tokens; only the last
    # `required` blocks are materialized, the prefix is null stand-ins.
    assert hit_tokens == 64
    assert len(hit_blocks) == 16
    assert all(b.is_null for b in hit_blocks[:12])
    assert not any(b.is_null for b in hit_blocks[12:])
    # The hit is usable: allocation on top of it succeeds.
    assert m.allocate_slots(
        r2, 1, new_computed_blocks=(hit_blocks,), num_new_computed_tokens=64
    ) is not None


def test_window_hit_survives_broken_prefix():
    """Evicting an early block must not kill the hit: the scan finds the
    last window-covering run; a break INSIDE the window region kills it
    down to the longest plain prefix run."""
    bs, window = 4, 16
    m = KVCacheManager(
        num_blocks=64, block_size=bs, enable_caching=True,
        sliding_window=window,
    )
    prompt = list(range(200, 265))
    r1 = make_hashed_request("a", prompt, bs)
    assert m.allocate_slots(r1, 65) is not None
    # Evict block 13 from the cache (inside the final window run).
    blk13 = m.req_to_blocks["a"][13]
    m.block_pool._maybe_evict_cached_block(blk13)
    m.free(r1)

    r2 = make_hashed_request("b", prompt, bs)
    (hit_blocks,), hit_tokens = m.get_computed_blocks(r2)
    # Runs: [0..13) cached, block 13 missing, [14..16) cached. The tail
    # run (2 blocks) is too short; the next run ends at block 13 ->
    # hit = 13 blocks = 52 tokens, last 4 real, 9 nulls.
    assert hit_tokens == 52
    assert len(hit_blocks) == 13
    assert all(b.is_null for b in hit_blocks[:9])
    assert not any(b.is_null for b in hit_blocks[9:])


def test_window_hit_plain_prefix_fallback():
    """A cached run anchored at block 0 but shorter than the window still
    hits (plain prefix semantics)."""
    bs, window = 4, 16
    m = KVCacheManager(
        num_blocks=64, block_size=bs, enable_caching=True,
        sliding_window=window,
    )
    prompt = list(range(300, 333))  # 33 tokens -> 8 full blocks
    r1 = make_hashed_request("a", prompt, bs)
    assert m.allocate_slots(r1, 33) is not None
    # Evict blocks 2..8 -> only blocks 0,1 cached (run of 2 < required 4).
    for i in range(2, 8):
        m.block_pool._maybe_evict_cached_block(m.req_to_blocks["a"][i])
    m.free(r1)

    r2 = make_hashed_request("b", prompt, bs)
    (hit_blocks,), hit_tokens = m.get_computed_blocks(r2)
    assert hit_tokens == 8
    assert len(hit_blocks) == 2
    assert not any(b.is_null for b in hit_blocks)


def test_window_freed_blocks_still_hittable():
    """Out-of-window freeing nulls a request's OWN table entries but the
    freed blocks stay registered until evicted — a second identical
    request still gets the window hit."""
    bs, window = 4, 16
    m = KVCacheManager(
        num_blocks=64, block_size=bs, enable_caching=True,
        sliding_window=window,
    )
    prompt = list(range(400, 465))
    r1 = make_hashed_request("a", prompt, bs)
    assert m.allocate_slots(r1, 65) is not None
    r1.num_computed_tokens = 65
    assert m.allocate_slots(r1, 1) is not None  # triggers window frees
    assert any(b.is_null for b in m.req_to_blocks["a"])  # frees happened
    m.free(r1)

    r2 = make_hashed_request("b", prompt, bs)
    _, hit_tokens = m.get_computed_blocks(r2)
    assert hit_tokens == 64


def test_windowed_scheduler_prefix_hit():
    """Scheduler-level: a windowed cache config serves the second
    identical prompt from cache (window-aware), scheduling only the
    remainder."""
    from tests.core.utils import create_request, create_scheduler, make_runner_output

    sched = create_scheduler(block_size=16, sliding_window=64)
    prompt = list(range(100, 228))  # 128 tokens = 8 blocks
    r1 = create_request(prompt_token_ids=prompt, max_tokens=2)
    sched.add_request(r1)
    out = sched.schedule()
    sched.update_from_output(out, make_runner_output(out, token_id=7))
    out = sched.schedule()
    sched.update_from_output(out, make_runner_output(out, token_id=8))
    assert not sched.has_unfinished_requests()

    r2 = create_request(prompt_token_ids=prompt, max_tokens=2)
    sched.add_request(r2)
    out2 = sched.schedule()
    # Hit capped at num_tokens-1 -> 7 blocks = 112 tokens.
    assert r2.num_cached_tokens == 112
    assert out2.num_scheduled_tokens[r2.request_id] == 128 - 112


def test_windowed_e2e_matches_big_pool(tmp_path_factory):
    """Greedy decode of a windowed model is identical whether or not the
    pool is tight enough to trigger out-of-window freeing."""
    import torch
    from transformers import MistralConfig, MistralForCausalLM

    from vllm_tpu import LLM, SamplingParams

    torch.manual_seed(0)
    cfg = MistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=512, sliding_window=32,
        tie_word_embeddings=False,
    )
    hf = MistralForCausalLM(cfg).to(torch.float32)
    path = str(tmp_path_factory.mktemp("tiny_mistral_win"))
    hf.save_pretrained(path, safe_serialization=True)

    def gen(num_blocks, repeat_long=False):
        llm = LLM(
            model=path, dtype="float32", max_model_len=256, block_size=16,
            num_gpu_blocks_override=num_blocks, max_num_seqs=2,
            max_num_batched_tokens=128,
        )
        rng = np.random.default_rng(0)
        prompts = [rng.integers(5, 120, size=12).tolist()]
        params = SamplingParams(
            temperature=0.0, max_tokens=96, ignore_eos=True
        )
        outs = llm.generate(
            [{"prompt_token_ids": p} for p in prompts], params
        )
        toks = [o.outputs[0].token_ids for o in outs]
        if repeat_long:
            # A 64-token prompt served twice: the repeat takes the
            # window-aware prefix-cache hit (cached run covering window
            # 32 + null stand-ins) and must decode identically.
            long_p = rng.integers(5, 120, size=64).tolist()
            p2 = SamplingParams(
                temperature=0.0, max_tokens=16, ignore_eos=True
            )
            cold = llm.generate([{"prompt_token_ids": long_p}], p2)
            hot = llm.generate([{"prompt_token_ids": long_p}], p2)
            assert (
                cold[0].outputs[0].token_ids == hot[0].outputs[0].token_ids
            )
            stats = (
                llm.llm_engine.engine_core.engine_core.scheduler
                .kv_cache_manager.prefix_cache_stats
            )
            assert stats.hits > 0  # the repeat really hit
        return toks

    # 5 blocks of 16 = 80 token slots < 12 + 96 tokens: only possible
    # because out-of-window blocks (window 32) are recycled.
    tight = gen(5)
    roomy = gen(64, repeat_long=True)
    assert tight == roomy
    assert len(tight[0]) == 96
