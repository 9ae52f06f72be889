"""``metrics/roofline.py``: the roofline arithmetic the KV fabric's
fetch-or-recompute cost model prices a recompute with."""

from __future__ import annotations

import pytest


def test_roofline_model_math():
    from vllm_tpu.metrics.roofline import RooflineModel

    m = RooflineModel(weight_bytes=16_000_000_000,
                      active_params=8_000_000_000,
                      kv_tok_bytes=1024, device_kind="TPU v5e")
    # 2000 tok/s * 2 FLOPs/param * 8e9 params / 197e12 peak.
    assert m.mfu(2000.0) == pytest.approx(0.16244, abs=1e-4)
    assert m.mfu(0.0) == 0.0
    # One step reads all weights + ctx KV.
    assert m.hbm_bytes_per_step(1000) == 16_000_000_000 + 1024_000
    assert m.hbm_bw_util(30.0, 1000) == pytest.approx(
        (16_000_000_000 + 1024_000) * 30.0 / 819e9, rel=1e-6)
    # Round-trips the worker->engine RPC boundary.
    assert RooflineModel.from_dict(m.to_dict()) == m


def test_unknown_device_kind_has_no_peak():
    """A device that is not in the peak table has no peak: the math
    raises a KeyError naming the kind (never a share of some other
    chip's peak)."""
    from vllm_tpu.metrics.roofline import RooflineModel

    m = RooflineModel(weight_bytes=1_000, active_params=1_000,
                      kv_tok_bytes=8, device_kind="cpu")
    assert not m.has_peaks
    with pytest.raises(KeyError, match="cpu"):
        m.mfu(100.0)
    with pytest.raises(KeyError, match="cpu"):
        m.hbm_bw_util(10.0, 100)


def test_roofline_param_helpers():
    import numpy as np

    from vllm_tpu.metrics import roofline as rf

    params = {
        "w": np.zeros((4, 4), dtype=np.float32),  # 64 B, 16 params
        "q": np.zeros((8,), dtype=np.uint8),      # 8 B, 16 logical (int4)
    }
    assert rf.weight_bytes(params) == 64 + 8
    assert rf.logical_params(params) == 16 + 16
    assert rf.kv_bytes_per_token(2, 4, 64, 2) == 2 * 2 * 4 * 64 * 2


def test_worker_roofline_reaches_the_fabric_cost_model(tmp_path):
    """``Worker.roofline_info`` is handed to the KV fabric's cost model at
    engine start: the model's real bytes and shapes, and on a device with
    no published peak the arbiter's default, never another chip's."""
    import jax

    from tests.models.utils import tiny_llama_config, tiny_llama_dir
    from vllm_tpu import LLM
    from vllm_tpu.metrics import roofline as rf

    llm = LLM(
        model=tiny_llama_dir(tmp_path), dtype="float32", max_model_len=128,
        block_size=16, num_gpu_blocks_override=64, max_num_seqs=4,
        max_num_batched_tokens=128, kv_connector="fabric",
    )
    core = llm.llm_engine.engine_core.engine_core
    worker = core.executor.worker
    got = core.kv_connector.cost.roofline
    assert got == rf.RooflineModel.from_dict(worker.roofline_info())
    assert got.weight_bytes == rf.weight_bytes(worker.params) > 0
    hf = tiny_llama_config()
    assert got.kv_tok_bytes == rf.kv_bytes_per_token(
        hf.num_hidden_layers, hf.num_key_value_heads,
        hf.hidden_size // hf.num_attention_heads, 2)
    assert got.device_kind == jax.devices()[0].device_kind
    assert not got.has_peaks  # the CPU is in no peak table
    assert core.kv_connector.cost.stats()["has_roofline"]
