"""Trace-file lifecycle and cross-process trace-id propagation.

Covers the observability tentpole: per-process chrome-trace files are
strict JSON once the process exits (atexit terminator), async request
spans carry the frontend-assigned trace id across the ZMQ engine-core
process split, and ``tools/merge_traces.py`` fuses the per-process
files into one Perfetto timeline with a flow per request.
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
import time

import pytest

from tests.models.utils import tiny_llama_dir
from vllm_tpu import LLM, SamplingParams

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
TRACING_PY = os.path.join(REPO_ROOT, "vllm_tpu", "tracing.py")


def _load_merge_traces():
    spec = importlib.util.spec_from_file_location(
        "merge_traces", os.path.join(REPO_ROOT, "tools", "merge_traces.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fresh_tracing():
    """A private copy of the tracing module, so tests can exercise the
    open/close lifecycle without touching the process-wide instance."""
    spec = importlib.util.spec_from_file_location("tracing_fresh", TRACING_PY)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_file_strict_json_after_process_exit(tmp_path):
    """A process that exits normally leaves a strictly valid JSON array
    (the atexit close terminates it) — no trailing-comma repair needed."""
    code = f"""
import importlib.util
spec = importlib.util.spec_from_file_location("tracing", {TRACING_PY!r})
tracing = importlib.util.module_from_spec(spec)
spec.loader.exec_module(tracing)
with tracing.trace_span("work", category="engine", items=3):
    pass
tracing.trace_instant("request_arrival", req_id="r0", trace_id="abc123")
tracing.trace_async_begin("queue", "abc123", req_id="r0")
tracing.trace_async_end("queue", "abc123", req_id="r0")
"""
    env = dict(os.environ, VLLM_TPU_TRACE_DIR=str(tmp_path))
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=60)
    files = list(tmp_path.glob("trace-*.json"))
    assert len(files) == 1
    events = json.loads(files[0].read_text())  # strict parse, no repair
    assert [e["name"] for e in events] == [
        "work", "request_arrival", "queue", "queue"]
    assert [e["ph"] for e in events] == ["X", "i", "b", "e"]
    b, e = events[2], events[3]
    assert b["id"] == e["id"] == "abc123"
    assert b["args"]["trace_id"] == "abc123"
    assert e["ts"] >= b["ts"]


def test_close_trace_idempotent_drops_late_events(tmp_path, monkeypatch):
    monkeypatch.setenv("VLLM_TPU_TRACE_DIR", str(tmp_path))
    tracing = _fresh_tracing()
    tracing.trace_instant("one", req_id="a")
    tracing.close_trace()
    [path] = tmp_path.glob("trace-*.json")
    events = json.loads(path.read_text())
    assert len(events) == 1

    # Emissions after close are dropped, and closing again is a no-op.
    tracing.trace_instant("late", req_id="b")
    tracing.close_trace()
    assert json.loads(path.read_text()) == events


def test_close_trace_empty_file_is_valid(tmp_path, monkeypatch):
    monkeypatch.setenv("VLLM_TPU_TRACE_DIR", str(tmp_path))
    tracing = _fresh_tracing()
    assert tracing.trace_enabled()  # opens the file, writes no events
    tracing.close_trace()
    [path] = tmp_path.glob("trace-*.json")
    assert json.loads(path.read_text()) == []


def test_merge_repairs_unterminated_file(tmp_path):
    """A killed process leaves ``[...},`` with no terminator; the merge
    tool repairs it on read instead of dropping the file."""
    (tmp_path / "trace-1.json").write_text(
        '[\n{"name": "a", "ph": "i", "ts": 1, "pid": 1, "tid": 1,'
        ' "args": {}},\n')
    merge_traces = _load_merge_traces()
    merged = merge_traces.merge(str(tmp_path))
    names = [e["name"] for e in merged["traceEvents"]
             if e.get("ph") == "i"]
    assert names == ["a"]


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    return tiny_llama_dir(tmp_path_factory.mktemp("tiny_llama_trace"))


def test_trace_id_across_two_processes_and_merge(ckpt, tmp_path,
                                                 monkeypatch):
    """The acceptance path: run the frontend and a spawned ZMQ engine-core
    process with VLLM_TPU_TRACE_DIR set, then merge the two per-process
    trace files — one request's trace id must link spans from BOTH pids,
    and the merged object must be valid chrome-trace JSON with a flow."""
    import gc

    import vllm_tpu.tracing as tracing
    from vllm_tpu.engine.async_llm import AsyncLLM

    # An in-process engine loop that an earlier test of this worker left
    # running would write its ``wait_input`` spans (category ``engine``)
    # into this process's file, and the merge would name the frontend an
    # engine core.
    for obj in gc.get_objects():
        if type(obj) is AsyncLLM and getattr(obj, "_thread", None) is not None:
            obj.shutdown()

    monkeypatch.setenv("VLLM_TPU_TRACE_DIR", str(tmp_path))
    # The module caches the enabled decision; reset for this test.
    monkeypatch.setattr(tracing, "_enabled", None)
    monkeypatch.setattr(tracing, "_file", None)
    monkeypatch.setattr(tracing, "_wrote_any", False)

    llm = LLM(
        model=ckpt, dtype="float32", max_model_len=128, block_size=16,
        num_gpu_blocks_override=64, max_num_seqs=4,
        max_num_batched_tokens=128, distributed_executor_backend="mp",
    )
    try:
        llm.generate(
            [{"prompt_token_ids": [5, 9, 11]}],
            SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True),
        )
    finally:
        llm.llm_engine.shutdown()
    tracing.close_trace()  # terminate the frontend's file

    # The engine-core child closes its file via atexit on the shutdown
    # message; give it a moment to exit.
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        if len(list(tmp_path.glob("trace-*.json"))) >= 2:
            break
        time.sleep(0.2)
    files = list(tmp_path.glob("trace-*.json"))
    assert len(files) >= 2, f"expected a trace file per process: {files}"

    merge_traces = _load_merge_traces()
    merged = merge_traces.merge(str(tmp_path))
    events = merged["traceEvents"]
    json.loads(json.dumps(merged))  # round-trips as plain JSON

    # One request's trace id appears in events from both processes.
    pids_by_trace: dict[str, set] = {}
    for ev in events:
        tid = (ev.get("args") or {}).get("trace_id")
        if tid:
            pids_by_trace.setdefault(tid, set()).add(ev["pid"])
    cross = {t: p for t, p in pids_by_trace.items() if len(p) >= 2}
    assert cross, (
        f"no trace id spans multiple pids: "
        f"{{t: sorted(p) for t, p in pids_by_trace.items()}}")

    # The engine-side lifecycle spans carry the shared trace id...
    trace_id = next(iter(cross))
    span_names = {
        ev["name"] for ev in events
        if ev.get("ph") in ("b", "e")
        and ev.get("id2", {}).get("global") == trace_id
    }
    assert {"request", "queue", "prefill", "decode"} <= span_names
    # ...and the merge adds a flow arrow linking the processes.
    flows = [ev for ev in events if ev.get("cat") == "request_flow"]
    assert any(ev["ph"] == "s" for ev in flows)
    assert any(ev["ph"] == "f" for ev in flows)
    # Process metadata names both roles.
    roles = {
        ev["args"]["name"]
        for ev in events if ev.get("name") == "process_name"
    }
    assert any("engine-core" in r for r in roles)
    assert any("frontend" in r for r in roles)


def test_merge_cli(tmp_path):
    (tmp_path / "trace-7.json").write_text(
        '[\n{"name": "x", "cat": "engine", "ph": "i", "ts": 5, "pid": 7,'
        ' "tid": 1, "args": {"trace_id": "ff"}}\n]\n')
    out = tmp_path / "merged.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools",
                                      "merge_traces.py"),
         str(tmp_path), "-o", str(out)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    merged = json.loads(out.read_text())
    assert any(e.get("name") == "x" for e in merged["traceEvents"])
