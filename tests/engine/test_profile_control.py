"""The one profiler control: ``/start_profile`` and ``/stop_profile`` arm a
call that the engine-loop thread executes between two steps, and the
engine's own spans land on the profiler's clock while a profile runs. Tiny
model, CPU."""

from __future__ import annotations

import asyncio
import glob
import os
import subprocess
import sys
import threading

import pytest

from tests.models.utils import tiny_llama_dir
from vllm_tpu import tracing
from vllm_tpu.engine.arg_utils import AsyncEngineArgs
from vllm_tpu.engine.async_llm import AsyncLLM

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

STEP_SPANS = {
    "vllm_tpu.engine.schedule", "vllm_tpu.runner.prepare",
    "vllm_tpu.runner.dispatch", "vllm_tpu.runner.fetch",
    "vllm_tpu.engine.update", "vllm_tpu.frontend.output",
}


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    model = tiny_llama_dir(tmp_path_factory.mktemp("tiny_llama_profile"))
    eng = AsyncLLM.from_engine_args(AsyncEngineArgs(
        model=model, dtype="float32", max_model_len=128, block_size=16,
        num_gpu_blocks_override=64, max_num_seqs=8,
        max_num_batched_tokens=128,
    ))
    yield eng
    if tracing.profiler_running():
        tracing.stop_profiler()
    eng.shutdown()


def _drive(engine, go):
    from aiohttp.test_utils import TestClient, TestServer

    from vllm_tpu.entrypoints.openai.api_server import build_app

    async def run():
        async with TestClient(TestServer(build_app(engine, "tiny"))) as client:
            return await go(client)

    return asyncio.run(run())


def _host_events(trace_dir) -> dict[str, set[str]]:
    """``line name -> event names`` of the trace's host plane."""
    from jax.profiler import ProfileData

    [path] = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True)
    out: dict[str, set[str]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out.setdefault(line.name, set()).update(
                    e.name for e in line.events)
    return out


def test_profile_over_http_runs_on_the_engine_thread_while_tokens_stream(
        engine, tmp_path):
    trace_dir = str(tmp_path / "trace")
    seen: dict = {}

    async def go(client):
        chunks = 0
        marks = {}

        async def stream():
            nonlocal chunks
            resp = await client.post("/v1/completions", json={
                "prompt": [2, 7, 1, 8], "max_tokens": 120, "temperature": 0,
                "stream": True, "ignore_eos": True})
            assert resp.status == 200
            async for line in resp.content:
                if line.startswith(b"data: {"):
                    chunks += 1

        task = asyncio.create_task(stream())
        while chunks < 3:
            await asyncio.sleep(0.005)
        resp = await client.post("/start_profile",
                                 json={"trace_dir": trace_dir})
        seen["start"] = (resp.status, await resp.json())
        marks["started"] = chunks
        # A second start while one runs is refused, and nothing breaks.
        resp = await client.post("/start_profile", json={})
        seen["second"] = (resp.status, await resp.json())
        while chunks < marks["started"] + 20 and not task.done():
            await asyncio.sleep(0.005)
        marks["before_stop"] = chunks
        await task
        await asyncio.sleep(0.25)  # idle: the loop waits for input
        resp = await client.post("/stop_profile")
        seen["stop"] = (resp.status, await resp.json())
        resp = await client.post("/stop_profile")
        seen["second_stop"] = resp.status
        return chunks, marks

    chunks, marks = _drive(engine, go)
    assert chunks == 120
    # Tokens kept streaming between the start's return and the stop.
    assert marks["before_stop"] > marks["started"]

    status, body = seen["start"]
    assert status == 200 and body["status"] == "profiling started"
    assert body["trace_dir"] == trace_dir
    status, body = seen["second"]
    assert status == 409 and "already running" in body["error"]
    status, body = seen["stop"]
    assert status == 200 and body["trace_dir"] == trace_dir
    assert body["stop_s"] >= 0 and body["traced_s"] > 0
    assert seen["second_stop"] == 409
    # Both ran on the engine-loop thread, never on this (the HTTP) thread.
    assert seen["start"][1]["thread"] == "engine-core" == body["stop_thread"]
    assert threading.current_thread().name != "engine-core"
    assert tracing.profiler_running() is None

    lines = _host_events(trace_dir)
    names = set().union(*lines.values())
    ours = {n for n in names if n.startswith("vllm_tpu.")}
    assert STEP_SPANS <= ours, STEP_SPANS - ours
    assert "vllm_tpu.engine.wait_input" in ours
    # The per-request span stays in the chrome trace alone.
    assert not any("detokenize" in n for n in ours)
    # One thread carries the step's spans: parent and child follow from
    # nesting there.
    assert any(STEP_SPANS <= evs for evs in lines.values())
    # The python tracer is off: no frame events ("$file.py:line func").
    assert not [n for n in names if n.startswith("$") or ".py:" in n]


def test_trace_span_with_both_sinks_off_imports_no_jax():
    code = f"""
import importlib.util, sys
spec = importlib.util.spec_from_file_location(
    "tracing", {os.path.join(REPO_ROOT, "vllm_tpu", "tracing.py")!r})
tracing = importlib.util.module_from_spec(spec)
spec.loader.exec_module(tracing)
for _ in range(3):
    with tracing.trace_span("schedule", tokens=4):
        pass
    with tracing.trace_span("detokenize", category="request"):
        pass
assert tracing.profiler_running() is None
assert "jax" not in sys.modules, "trace_span imported jax"
print("ok")
"""
    env = {k: v for k, v in os.environ.items() if k != "VLLM_TPU_TRACE_DIR"}
    out = subprocess.run([sys.executable, "-c", code], check=True, env=env,
                         capture_output=True, text=True, timeout=60)
    assert out.stdout.strip() == "ok"


def test_chrome_sink_keeps_its_names_while_a_profile_runs(tmp_path,
                                                          monkeypatch):
    """Both sinks on: the chrome trace gets the span under the name it
    always had, the profiler ``vllm_tpu.<category>.<name>``."""
    import importlib.util
    import json

    monkeypatch.setenv("VLLM_TPU_TRACE_DIR", str(tmp_path / "chrome"))
    spec = importlib.util.spec_from_file_location(
        "tracing_both", os.path.join(REPO_ROOT, "vllm_tpu", "tracing.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    info = mod.start_profiler(str(tmp_path / "prof"))
    try:
        assert info["thread"] == threading.current_thread().name
        with pytest.raises(mod.ProfilerBusyError):
            mod.start_profiler(str(tmp_path / "prof2"))
        with mod.trace_span("schedule", tokens=3, reqs=None):
            pass
        with mod.trace_span("detokenize", category="request", req_id="r"):
            pass
    finally:
        done = mod.stop_profiler()
    mod.close_trace()
    assert done["trace_dir"] == str(tmp_path / "prof") and done["stop_s"] >= 0
    with pytest.raises(mod.ProfilerBusyError):
        mod.stop_profiler()
    [path] = (tmp_path / "chrome").glob("trace-*.json")
    events = json.loads(path.read_text())
    assert [(e["name"], e["cat"]) for e in events] == [
        ("schedule", "engine"), ("detokenize", "request")]
    assert events[0]["args"] == {"tokens": 3}
    names = set().union(*_host_events(str(tmp_path / "prof")).values())
    assert "vllm_tpu.engine.schedule" in names
    assert not any("detokenize" in n for n in names)
