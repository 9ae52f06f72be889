"""Context parallelism wired end-to-end through the engine.

Reference analog: DCP — ``vllm/distributed/parallel_state.py:1608`` (_DCP
group), ``v1/worker/cp_utils.py:30-44`` (decode-LSE contract), and the
``cp_kv_cache_interleave_size`` block striping. TPU realization: the block
pool is color-striped (a request's k-th block comes from color k % cp =
the cp rank holding that page), the cache's block dim is GSPMD-sharded
over 'cp', and each layer's insert+attention runs in a partial-manual
shard_map with the 3-collective LSE merge.
"""

from __future__ import annotations

import numpy as np
import pytest

from vllm_tpu.core.block_pool import BlockPool, _count_for_color
from vllm_tpu.core.kv_cache_manager import KVCacheManager


# ----------------------------------------------------------------------
# Pool striping units
# ----------------------------------------------------------------------

def test_count_for_color():
    # 5 blocks starting at color 1 over 4 colors: colors 1,2,3,0,1.
    assert [_count_for_color(5, 1, c, 4) for c in range(4)] == [1, 2, 1, 1]
    assert _count_for_color(3, 0, 0, 1) == 3


def test_striped_pool_colors():
    pool = BlockPool(16, enable_caching=False, num_colors=4)
    # Each color's first id is a reserved null.
    for c in range(4):
        assert pool.blocks[c * 4].is_null
    assert pool.get_num_free_blocks() == 12
    blocks = pool.get_new_blocks(6, first_color=1)
    # Block k from color (1+k)%4, ids inside that color's range.
    for k, b in enumerate(blocks):
        assert pool.color_of(b.block_id) == (1 + k) % 4
    pool.free_blocks(blocks)
    assert pool.get_num_free_blocks() == 12


def test_striped_pool_exhaustion_is_per_color():
    pool = BlockPool(8, enable_caching=False, num_colors=2)
    # 3 free per color. 6 blocks starting at color 0 = 3+3: fits.
    assert pool.can_allocate(6, 0)
    # 7 would need 4 from color 0: must refuse even though 6 are free.
    assert not pool.can_allocate(7, 0)
    with pytest.raises(RuntimeError):
        pool.get_new_blocks(7, 0)


def test_striped_manager_positions():
    """The manager stripes by absolute context-block index across
    successive allocate_slots calls (chunked prefill + decode growth)."""
    from vllm_tpu.request import Request
    from vllm_tpu.sampling_params import SamplingParams

    mgr = KVCacheManager(
        num_blocks=32, block_size=4, enable_caching=False, num_stripes=2
    )
    req = Request(
        request_id="r0", prompt_token_ids=list(range(23)),
        sampling_params=SamplingParams(max_tokens=4),
    )
    first = mgr.allocate_slots(req, 10)  # blocks 0..2 (ceil(10/4))
    req.num_computed_tokens = 10
    second = mgr.allocate_slots(req, 13)  # blocks 3..5 (ceil(23/4)=6)
    ids = [b.block_id for b in first[0] + second[0]]
    for k, bid in enumerate(ids):
        assert mgr.block_pool.color_of(bid) == k % 2, (k, bid)


# ----------------------------------------------------------------------
# E2E: greedy parity cp=2 vs cp=1 through the LLM API on the CPU mesh
# ----------------------------------------------------------------------

def _generate(model_dir, prompts, max_tokens=8, **kw):
    from vllm_tpu import LLM, SamplingParams

    llm = LLM(
        model=model_dir, dtype="float32", max_model_len=128, block_size=16,
        num_gpu_blocks_override=64, max_num_seqs=8,
        max_num_batched_tokens=128, **kw,
    )
    params = SamplingParams(
        temperature=0.0, max_tokens=max_tokens, ignore_eos=True
    )
    outs = llm.generate([{"prompt_token_ids": p} for p in prompts], params)
    return [o.outputs[0].token_ids for o in outs]


@pytest.fixture(scope="module")
def tiny_llama(tmp_path_factory):
    from tests.models.utils import tiny_llama_dir

    return tiny_llama_dir(
        tmp_path_factory.mktemp("tiny_llama_cp"), num_key_value_heads=4
    )


@pytest.mark.parametrize("cp_kw", [
    dict(context_parallel_size=2),
    dict(context_parallel_size=2, tensor_parallel_size=2),
])
def test_llm_generate_cp_parity(tiny_llama, cp_kw):
    """Long multi-block contexts under cp=2 (and cp x tp) produce the
    same greedy tokens as the single-device engine."""
    rng = np.random.default_rng(9)
    # Contexts spanning several 16-token blocks so striping really spreads
    # pages over ranks (41 + 12 generated = 4 blocks).
    prompts = [rng.integers(10, 120, size=n).tolist() for n in (41, 7, 23)]
    ref = _generate(tiny_llama, prompts, max_tokens=12)
    got = _generate(tiny_llama, prompts, max_tokens=12, **cp_kw)
    assert got == ref


def test_llm_cp_prefix_cache_parity(tiny_llama):
    """A prefix-cache hit reuses striped blocks whose colors line up with
    positions by construction; the second request must match the first."""
    rng = np.random.default_rng(11)
    prefix = rng.integers(10, 120, size=37).tolist()
    from vllm_tpu import LLM, SamplingParams

    llm = LLM(
        model=tiny_llama, dtype="float32", max_model_len=128, block_size=16,
        num_gpu_blocks_override=64, max_num_seqs=8,
        max_num_batched_tokens=128, context_parallel_size=2,
        enable_prefix_caching=True,
    )
    params = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)
    first = llm.generate([{"prompt_token_ids": prefix}], params)
    second = llm.generate([{"prompt_token_ids": prefix}], params)
    assert (
        first[0].outputs[0].token_ids == second[0].outputs[0].token_ids
    )
    stats = (
        llm.llm_engine.engine_core.engine_core.scheduler
        .kv_cache_manager.prefix_cache_stats
    )
    assert stats.hits > 0  # the second request really hit the cache


def test_llm_cp_cascade_parity(tiny_llama):
    """Shared-prefix batch under cp=2: the striping-aware cascade path
    (num_common_prefix_blocks > 0 inside cp_write_and_attend) produces
    the same greedy tokens as the single-device engine."""
    rng = np.random.default_rng(13)
    shared = rng.integers(10, 120, size=37).tolist()
    prompts = [shared + rng.integers(10, 120, size=n).tolist()
               for n in (3, 9, 6)]
    ref = _generate(tiny_llama, prompts, max_tokens=10)
    got = _generate(
        tiny_llama, prompts, max_tokens=10, context_parallel_size=2,
        enable_prefix_caching=True,
    )
    assert got == ref
