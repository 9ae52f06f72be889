"""Plugin entry-point loading + executor seam contract.

Reference analogs: ``vllm/plugins/`` (load_general_plugins) and the
``Executor.get_class`` / ``collective_rpc`` seam
(``vllm/v1/executor/abstract.py:37``).
"""

from __future__ import annotations

import numpy as np
import pytest


class _FakeEntryPoint:
    def __init__(self, name, hook):
        self.name = name
        self._hook = hook

    def load(self):
        return self._hook


def test_load_general_plugins(monkeypatch):
    import vllm_tpu.plugins as plugins

    calls = []

    def good():
        calls.append("good")

    def bad():
        raise RuntimeError("boom")

    fake = [_FakeEntryPoint("good", good), _FakeEntryPoint("bad", bad)]

    def fake_eps(group=None):
        assert group == plugins.PLUGIN_GROUP
        return fake

    import importlib.metadata

    monkeypatch.setattr(importlib.metadata, "entry_points", fake_eps)
    monkeypatch.setattr(plugins, "_loaded", False)
    loaded = plugins.load_general_plugins()
    # The good plugin ran; the bad one failed without raising.
    assert loaded == ["good"]
    assert calls == ["good"]
    # Idempotent per process.
    assert plugins.load_general_plugins() == []

    # Allow-list filtering.
    monkeypatch.setenv("VLLM_TPU_PLUGINS", "nope")
    assert plugins.load_general_plugins(force=True) == []
    monkeypatch.delenv("VLLM_TPU_PLUGINS")


def test_plugin_can_register_model(monkeypatch):
    """The canonical plugin action: out-of-tree architecture registration."""
    import vllm_tpu.plugins as plugins
    from vllm_tpu.models.registry import ModelRegistry, _REGISTRY

    def hook():
        ModelRegistry.register(
            "TestPluginArch", "vllm_tpu.models.llama", "LlamaForCausalLM"
        )

    def fake_eps(group=None):
        return [_FakeEntryPoint("arch", hook)]

    import importlib.metadata

    monkeypatch.setattr(importlib.metadata, "entry_points", fake_eps)
    monkeypatch.setattr(plugins, "_loaded", False)
    try:
        assert plugins.load_general_plugins() == ["arch"]
        assert "TestPluginArch" in ModelRegistry.get_supported_archs()
    finally:
        _REGISTRY.pop("TestPluginArch", None)


def test_executor_seam(tmp_path):
    """Executor contract: get_class selection, collective_rpc fan-out,
    dispatch/finalize round trip."""
    from tests.models.utils import tiny_llama_dir
    from vllm_tpu.engine.arg_utils import EngineArgs
    from vllm_tpu.engine.executor import Executor

    path = tiny_llama_dir(tmp_path / "ck")
    config = EngineArgs(
        model=path, dtype="float32", max_model_len=64, block_size=16,
        num_gpu_blocks_override=32, max_num_seqs=4,
        max_num_batched_tokens=64,
    ).create_engine_config().finalize()
    cls = Executor.get_class(config)
    ex = cls(config)
    try:
        num_blocks = ex.initialize()
        assert num_blocks == 32
        # collective_rpc returns one result per worker (uniproc: one).
        assert ex.collective_rpc("execute_dummy_batch") == [None]
        assert ex.max_concurrent_batches >= 1
        # dispatch/finalize round trip on a real scheduler output.
        from vllm_tpu.core.sched_output import NewRequestData, SchedulerOutput
        from vllm_tpu.sampling_params import SamplingParams

        so = SchedulerOutput(
            scheduled_new_reqs=[NewRequestData(
                req_id="r0", prompt_token_ids=[5, 9, 11],
                sampling_params=SamplingParams(max_tokens=4, temperature=0.0),
                block_ids=([1],), num_computed_tokens=0,
            )],
            num_scheduled_tokens={"r0": 3},
            total_num_scheduled_tokens=3,
        )
        out = ex.finalize(ex.dispatch(so))
        assert out.req_ids == ["r0"]
        assert len(out.sampled_token_ids[0]) == 1
    finally:
        ex.shutdown()


def test_batch_invariance_seeded_sampling(tmp_path):
    """Seeded sampling is batch-invariant too: per-request PRNG streams
    don't depend on batch composition."""
    from tests.models.utils import tiny_llama_dir
    from vllm_tpu import LLM, SamplingParams

    path = tiny_llama_dir(tmp_path / "ck")
    llm = LLM(
        model=path, dtype="float32", max_model_len=128, block_size=16,
        num_gpu_blocks_override=64, max_num_seqs=8,
        max_num_batched_tokens=128,
    )
    probe = {"prompt_token_ids": [7, 21, 3, 9, 40]}
    sp = SamplingParams(temperature=0.8, top_p=0.9, seed=1234,
                        max_tokens=8, ignore_eos=True)
    [solo] = llm.generate([probe], sp)
    rng = np.random.default_rng(1)
    others = [
        {"prompt_token_ids": rng.integers(5, 120, size=n).tolist()}
        for n in (9, 4)
    ]
    outs = llm.generate([others[0], probe, others[1]], sp)
    assert outs[1].outputs[0].token_ids == solo.outputs[0].token_ids
