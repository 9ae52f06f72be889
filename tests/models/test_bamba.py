"""Bamba hybrid (Mamba2 + attention) tests: HF greedy parity through the
engine, chunked prefill, multi-request slot stability, and the hybrid
cache geometry.

Reference analog: ``tests/models/language`` hybrid-model parity +
``v1/core`` hybrid KV coordination (``kv_cache_coordinator.py:392``).
"""

from __future__ import annotations

import numpy as np
import pytest


def tiny_bamba_config(**overrides):
    from transformers import BambaConfig

    kwargs = dict(
        vocab_size=128,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=4,
        attn_layer_indices=[1, 3],  # interleaved: mamba, attn, mamba, attn
        num_attention_heads=4,
        num_key_value_heads=2,
        mamba_n_heads=4,
        mamba_d_head=16,
        mamba_d_state=16,
        mamba_n_groups=1,
        mamba_d_conv=4,
        mamba_expand=2,
        mamba_chunk_size=8,
        tie_word_embeddings=False,
        max_position_embeddings=256,
    )
    kwargs.update(overrides)
    return BambaConfig(**kwargs)


@pytest.fixture(scope="module")
def tiny_bamba(tmp_path_factory):
    import torch
    from transformers import BambaForCausalLM

    torch.manual_seed(0)
    model = BambaForCausalLM(tiny_bamba_config()).to(torch.float32)
    path = tmp_path_factory.mktemp("tiny_bamba")
    model.save_pretrained(str(path), safe_serialization=True)
    return str(path)


def _hf_greedy(path, prompt, n):
    import torch
    from transformers import BambaForCausalLM

    model = BambaForCausalLM.from_pretrained(path).to(torch.float32).eval()
    ids = torch.tensor([prompt])
    with torch.no_grad():
        out = model.generate(
            ids, max_new_tokens=n, do_sample=False,
            pad_token_id=0,
        )
    return out[0, len(prompt):].tolist()


def _mk(path, **kw):
    from vllm_tpu import LLM

    kwargs = dict(
        model=path, dtype="float32", max_model_len=128, block_size=16,
        num_gpu_blocks_override=64, max_num_seqs=4,
        max_num_batched_tokens=128,
    )
    kwargs.update(kw)
    return LLM(**kwargs)


def test_bamba_hf_parity(tiny_bamba):
    from vllm_tpu import SamplingParams

    rng = np.random.default_rng(0)
    prompt = rng.integers(5, 120, size=21).tolist()
    want = _hf_greedy(tiny_bamba, prompt, 8)
    llm = _mk(tiny_bamba)
    got = llm.generate(
        [{"prompt_token_ids": prompt}],
        SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True),
    )[0].outputs[0].token_ids
    assert got == want


def test_bamba_chunked_prefill_parity(tiny_bamba):
    """Chunked prefill must thread SSM state between chunks."""
    from vllm_tpu import SamplingParams

    rng = np.random.default_rng(1)
    prompt = rng.integers(5, 120, size=50).tolist()
    want = _hf_greedy(tiny_bamba, prompt, 6)
    llm = _mk(tiny_bamba, max_num_batched_tokens=16)  # forces 4 chunks
    got = llm.generate(
        [{"prompt_token_ids": prompt}],
        SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True),
    )[0].outputs[0].token_ids
    assert got == want


def test_bamba_multi_request_slots(tiny_bamba):
    """Concurrent + sequential requests keep independent SSM state: batch
    results equal one-at-a-time results, and slots recycle correctly
    across generations."""
    from vllm_tpu import SamplingParams

    rng = np.random.default_rng(2)
    prompts = [
        {"prompt_token_ids": rng.integers(5, 120, size=n).tolist()}
        for n in (17, 9, 23)
    ]
    sp = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)
    llm = _mk(tiny_bamba)
    batch = [o.outputs[0].token_ids for o in llm.generate(prompts, sp)]
    solo = [
        llm.generate([p], sp)[0].outputs[0].token_ids for p in prompts
    ]
    assert batch == solo
    # Slots recycle: at most one outstanding (the final request's removal
    # rides the NEXT scheduler step, which hasn't run).
    runner = llm.llm_engine.engine_core.engine_core.executor.worker.runner
    assert len(runner._state_slot_free) >= 3
    assert len(runner._state_slot_of) <= 1


def test_bamba_async_pipeline_parity(tiny_bamba):
    """A row decoding steps ahead of its tokens reads and writes its SSM
    state slot in step order: the async engine serves what sync serves."""
    from vllm_tpu import SamplingParams

    rng = np.random.default_rng(4)
    prompts = [{"prompt_token_ids": rng.integers(5, 120, size=n).tolist()}
               for n in (13, 6)]
    sp = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)
    ref, got = (
        [o.outputs[0].token_ids
         for o in _mk(tiny_bamba, async_scheduling=mode).generate(prompts, sp)]
        for mode in (False, True)
    )
    assert got == ref


def test_bamba_preemption_storm(tiny_bamba):
    """Tiny KV pool forces preemption churn; hybrid state slots survive
    preempt/resume with greedy parity (fault-injection tier)."""
    from vllm_tpu import SamplingParams

    llm = _mk(
        tiny_bamba, block_size=4, num_gpu_blocks_override=12,
        max_model_len=64, max_num_batched_tokens=64,
    )
    rng = np.random.default_rng(5)
    prompts = [
        {"prompt_token_ids": rng.integers(5, 120, size=12).tolist()}
        for _ in range(6)
    ]
    sp = SamplingParams(temperature=0.0, max_tokens=20, ignore_eos=True)
    batch = [o.outputs[0].token_ids for o in llm.generate(prompts, sp)]
    solo = [llm.generate([p], sp)[0].outputs[0].token_ids for p in prompts]
    assert batch == solo
    stats = llm.llm_engine.engine_core.engine_core.scheduler
    assert stats._num_preempted_total > 0  # the storm actually happened


def test_bamba_cache_geometry(tiny_bamba):
    llm = _mk(tiny_bamba)
    runner = llm.llm_engine.engine_core.engine_core.executor.worker.runner
    kv = runner.kv_cache
    assert set(kv) == {"paged", "conv", "ssm"}
    assert kv["paged"].shape[0] == 2  # two attention layers
    assert kv["conv"].shape[:2] == (2, 5)  # two mamba layers, 4 slots
    assert kv["ssm"].shape[:2] == (2, 5)
    # Prefix caching is off for hybrids.
    core = llm.llm_engine.engine_core.engine_core
    assert not core.scheduler.cache_config.enable_prefix_caching


def test_bamba_profile_paths_release_state_slots(tiny_bamba):
    """profile_run / profile_step_memory / execute_dummy_batch admit
    __profile__ requests that take hybrid state slots; the cleanup must
    return them or real traffic hits an exhausted pool (ADVICE r3 #1)."""
    llm = _mk(tiny_bamba)
    runner = llm.llm_engine.engine_core.engine_core.executor.worker.runner
    total = len(runner._state_slot_free) + len(runner._state_slot_of)
    runner.profile_run()
    runner.execute_dummy_batch()
    runner.profile_step_memory()
    assert len(runner._state_slot_of) == 0
    assert len(runner._state_slot_free) == total
    # And the engine still serves max_num_seqs concurrent requests.
    from vllm_tpu import SamplingParams

    rng = np.random.default_rng(7)
    prompts = [
        {"prompt_token_ids": rng.integers(5, 120, size=9).tolist()}
        for _ in range(4)
    ]
    outs = llm.generate(
        prompts, SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True)
    )
    assert all(len(o.outputs[0].token_ids) == 4 for o in outs)


def test_bamba_preempted_requests_release_state_slots(tiny_bamba):
    """A preempted-and-waiting request must not hold its SSM slot: with
    slots == max_num_seqs, admission into capacity freed by preemption
    would otherwise pop from an empty pool (ADVICE r3 #3)."""
    from vllm_tpu import SamplingParams

    llm = _mk(
        tiny_bamba, block_size=4, num_gpu_blocks_override=10,
        max_model_len=64, max_num_batched_tokens=32, max_num_seqs=2,
    )
    rng = np.random.default_rng(8)
    prompts = [
        {"prompt_token_ids": rng.integers(5, 120, size=10).tolist()}
        for _ in range(4)
    ]
    sp = SamplingParams(temperature=0.0, max_tokens=16, ignore_eos=True)
    batch = [o.outputs[0].token_ids for o in llm.generate(prompts, sp)]
    solo = [llm.generate([p], sp)[0].outputs[0].token_ids for p in prompts]
    assert batch == solo
    sched = llm.llm_engine.engine_core.engine_core.scheduler
    assert sched._num_preempted_total > 0
