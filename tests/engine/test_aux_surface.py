"""Auxiliary surfaces: Anthropic Messages API, run-batch, profiler RPC,
NaN detection flag.
"""

from __future__ import annotations

import asyncio
import json

import pytest

from tests.models.utils import tiny_llama_dir_with_tokenizer
from vllm_tpu.engine.arg_utils import AsyncEngineArgs
from vllm_tpu.engine.async_llm import AsyncLLM


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    return tiny_llama_dir_with_tokenizer(tmp_path_factory.mktemp("tiny_aux"))


@pytest.fixture(scope="module")
def engine(ckpt):
    e = AsyncLLM.from_engine_args(
        AsyncEngineArgs(
            model=ckpt, dtype="float32", max_model_len=128, block_size=16,
            num_gpu_blocks_override=64, max_num_seqs=4,
            max_num_batched_tokens=128,
        )
    )
    yield e
    e.shutdown()


async def _client(engine):
    from aiohttp.test_utils import TestClient, TestServer

    from vllm_tpu.entrypoints.openai.api_server import build_app

    client = TestClient(TestServer(build_app(engine, "tiny")))
    await client.start_server()
    return client


def test_anthropic_messages(engine):
    async def run():
        client = await _client(engine)
        try:
            resp = await client.post("/v1/messages", json={
                "model": "tiny", "max_tokens": 6,
                "messages": [{"role": "user", "content": "ab"}],
            })
            assert resp.status == 200, await resp.text()
            body = await resp.json()
            assert body["type"] == "message"
            assert body["role"] == "assistant"
            assert body["content"][0]["type"] == "text"
            assert body["stop_reason"] in (
                "end_turn", "max_tokens", "stop_sequence"
            )
            assert body["usage"]["output_tokens"] >= 1
        finally:
            await client.close()

    asyncio.run(run())


def test_anthropic_messages_stream(engine):
    async def run():
        client = await _client(engine)
        try:
            resp = await client.post("/v1/messages", json={
                "model": "tiny", "max_tokens": 5, "stream": True,
                # Greedy: sampled with the default temperature and a seed
                # hashed from a random request id, about one run in a
                # hundred draws EOS first and streams no text at all.
                "temperature": 0,
                "messages": [{"role": "user", "content": "ab"}],
            })
            assert resp.status == 200
            text = (await resp.read()).decode()
            events = [
                line.split(": ", 1)[1]
                for line in text.splitlines()
                if line.startswith("event: ")
            ]
            assert events[0] == "message_start"
            assert "content_block_delta" in events
            assert events[-1] == "message_stop"
        finally:
            await client.close()

    asyncio.run(run())


def test_anthropic_validation(engine):
    async def run():
        client = await _client(engine)
        try:
            resp = await client.post("/v1/messages", json={
                "model": "tiny",
                "messages": [{"role": "user", "content": "x"}],
            })  # missing max_tokens
            assert resp.status == 400
        finally:
            await client.close()

    asyncio.run(run())


def test_run_batch(ckpt, tmp_path):
    from vllm_tpu.engine.arg_utils import EngineArgs
    from vllm_tpu.engine.llm_engine import LLMEngine
    from vllm_tpu.entrypoints.run_batch import run_batch

    inp = tmp_path / "in.jsonl"
    outp = tmp_path / "out.jsonl"
    lines = [
        {"custom_id": "c1", "method": "POST", "url": "/v1/completions",
         "body": {"prompt": "ab", "max_tokens": 4, "temperature": 0.0,
                  "ignore_eos": True}},
        {"custom_id": "c2", "method": "POST", "url": "/v1/chat/completions",
         "body": {"messages": [{"role": "user", "content": "hi"}],
                  "max_tokens": 4, "temperature": 0.0}},
        {"custom_id": "c3", "method": "POST", "url": "/v1/embeddings",
         "body": {"input": "ab"}},
        {"custom_id": "bad", "method": "POST", "url": "/v1/unknown",
         "body": {}},
    ]
    inp.write_text("\n".join(json.dumps(x) for x in lines))

    engine = LLMEngine.from_engine_args(
        EngineArgs(
            model=ckpt, dtype="float32", max_model_len=128, block_size=16,
            num_gpu_blocks_override=64, max_num_seqs=4,
            max_num_batched_tokens=128,
        )
    )
    try:
        stats = run_batch(engine, str(inp), str(outp), "tiny")
    finally:
        engine.shutdown()
    assert stats == {"total": 4, "succeeded": 3, "failed": 1}
    recs = [json.loads(x) for x in outp.read_text().splitlines()]
    by_id = {r["custom_id"]: r for r in recs}
    assert by_id["c1"]["response"]["body"]["object"] == "text_completion"
    assert by_id["c2"]["response"]["body"]["choices"][0]["message"]["role"] == "assistant"
    assert len(by_id["c3"]["response"]["body"]["data"][0]["embedding"]) == 64
    assert by_id["bad"]["error"]["code"] == 400


def test_profiler_rpc(ckpt, tmp_path):
    from vllm_tpu import LLM, SamplingParams

    llm = LLM(
        model=ckpt, dtype="float32", max_model_len=128, block_size=16,
        num_gpu_blocks_override=64, max_num_seqs=4,
        max_num_batched_tokens=128,
    )
    trace_dir = str(tmp_path / "trace")
    client = llm.llm_engine.engine_core
    assert client.start_profile(trace_dir)
    llm.generate(
        [{"prompt_token_ids": [5, 9]}],
        SamplingParams(temperature=0.0, max_tokens=2, ignore_eos=True),
    )
    assert client.stop_profile()
    import os

    assert any(os.scandir(trace_dir)), "no trace written"


def test_nan_check_flag(ckpt, monkeypatch):
    from vllm_tpu import LLM, SamplingParams, envs

    monkeypatch.setenv("VLLM_TPU_NAN_CHECK", "1")
    envs.refresh()
    try:
        llm = LLM(
            model=ckpt, dtype="float32", max_model_len=128, block_size=16,
            num_gpu_blocks_override=64, max_num_seqs=4,
            max_num_batched_tokens=128,
        )
        outs = llm.generate(
            [{"prompt_token_ids": [5, 9]}],
            SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True),
        )
        assert len(outs[0].outputs[0].token_ids) == 4
    finally:
        envs.refresh()


def test_serve_bench_qps_sweep(tmp_path):
    """vllm-tpu bench serve --qps-sweep: one engine, per-QPS stats."""
    import argparse
    import json

    from tests.models.utils import tiny_llama_dir
    from vllm_tpu.benchmarks.run import run_bench

    path = tiny_llama_dir(tmp_path / "ck")
    out = str(tmp_path / "sweep.json")
    args = argparse.Namespace(
        mode="serve", model=path, dtype="float32", max_model_len=128,
        block_size=16, num_gpu_blocks_override=64, max_num_seqs=8,
        max_num_batched_tokens=128, num_prompts=4, input_len=8,
        output_len=4, batch_size=2, qps=0.0, qps_sweep="50,0",
        json_out=out,
    )
    result = run_bench(args)
    assert result["mode"] == "serve_sweep"
    assert [p["qps"] for p in result["points"]] == [50.0, 0.0]
    for p in result["points"]:
        assert p["ttft_p50_s"] is not None
        assert p["request_throughput"] > 0
    assert json.load(open(out))["mode"] == "serve_sweep"


def test_usage_telemetry_opt_out(tmp_path, monkeypatch):
    import json
    import os

    from vllm_tpu.engine.arg_utils import EngineArgs
    from vllm_tpu.usage import record_usage

    from tests.models.utils import tiny_llama_config

    config = EngineArgs(
        model="dummy", load_format="dummy",
        hf_config=tiny_llama_config(architectures=["LlamaForCausalLM"]),
        dtype="float32",
    ).create_engine_config()
    stats = tmp_path / "usage.jsonl"
    monkeypatch.setenv("VLLM_TPU_USAGE_STATS_PATH", str(stats))
    # conftest opts the whole suite out; opt back in for this test.
    monkeypatch.setenv("VLLM_TPU_NO_USAGE_STATS", "0")
    from vllm_tpu import envs as _envs0

    _envs0._cache.pop("VLLM_TPU_NO_USAGE_STATS", None)
    record_usage(config, context="test")
    entry = json.loads(stats.read_text().strip())
    assert entry["architectures"] == ["LlamaForCausalLM"]
    assert entry["context"] == "test"
    assert "model" not in entry  # no paths/names recorded

    os.unlink(stats)
    monkeypatch.setenv("VLLM_TPU_NO_USAGE_STATS", "1")
    # envs are cached; clear so the opt-out is visible.
    from vllm_tpu import envs as _envs

    _envs._cache.pop("VLLM_TPU_NO_USAGE_STATS", None)
    record_usage(config, context="test")
    assert not stats.exists()
    monkeypatch.delenv("VLLM_TPU_NO_USAGE_STATS")
    _envs._cache.pop("VLLM_TPU_NO_USAGE_STATS", None)
