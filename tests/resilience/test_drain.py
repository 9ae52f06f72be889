"""Graceful-drain e2e: SIGTERM a real HTTP server subprocess mid-stream.

The drain contract (README "Overload & lifecycle"): on SIGTERM the
listener stays up but admission closes — the in-flight stream runs to
completion, /ready flips 503, a new request gets a clean 503 +
Retry-After (not a connection error), and the process exits 0 within the
drain budget.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from tests.models.utils import tiny_llama_dir_with_tokenizer

pytestmark = pytest.mark.fault_injection

_SERVER = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("VLLM_TPU_PALLAS_INTERPRET", "1")
os.environ.setdefault("VLLM_TPU_NO_USAGE_STATS", "1")
import jax
jax.config.update("jax_platforms", "cpu")

from vllm_tpu.engine.arg_utils import AsyncEngineArgs
from vllm_tpu.entrypoints.openai.api_server import run_server

run_server(
    AsyncEngineArgs(
        model=sys.argv[1],
        dtype="float32",
        max_model_len=2048,
        block_size=16,
        num_gpu_blocks_override=160,
        max_num_seqs=4,
        max_num_batched_tokens=128,
        drain_timeout_s=30.0,
    ),
    host="127.0.0.1",
    port=int(sys.argv[2]),
)
"""


def _post(base, path, body, timeout=10.0):
    req = urllib.request.Request(
        base + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    return urllib.request.urlopen(req, timeout=timeout)


def _wait_ready(base, deadline):
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(base + "/ready", timeout=2) as r:
                if r.status == 200:
                    return
        except (urllib.error.URLError, ConnectionError, OSError):
            pass
        time.sleep(0.25)
    raise TimeoutError("server never became ready")


def test_sigterm_drains_gracefully(tmp_path_factory):
    # With-tokenizer checkpoint: deltas carry text, so the SSE stream
    # emits an event per token (the handler suppresses empty deltas).
    ckpt = tiny_llama_dir_with_tokenizer(
        tmp_path_factory.mktemp("tiny_llama_drain"))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    base = f"http://127.0.0.1:{port}"
    script = tmp_path_factory.mktemp("drain_server") / "server.py"
    script.write_text(_SERVER)

    # JAX_COMPILATION_CACHE_DIR, if set, rides along in os.environ; unset,
    # the child's worker uses the same fixed directory as this process.
    env = dict(os.environ, PYTHONPATH=os.getcwd())
    # A file, not a pipe nobody reads while the test runs (a full pipe
    # blocks the server in write()).
    log = open(script.with_name("server.log"), "w+")
    proc = subprocess.Popen(
        [sys.executable, str(script), ckpt, str(port)], env=env,
        stdout=log, stderr=subprocess.STDOUT, text=True,
    )
    try:
        _wait_ready(base, time.monotonic() + 180)

        # Long decode (~seconds): still in flight for every check below.
        stream = _post(base, "/v1/completions", {
            "model": "drain", "prompt": [3, 5, 7, 11],
            "max_tokens": 1200, "ignore_eos": True,
            "temperature": 0.0, "stream": True,
        }, timeout=240)
        first = stream.readline()  # blocks through first-step compile
        assert first.startswith(b"data: "), first

        proc.send_signal(signal.SIGTERM)

        # /ready flips 503 once the drain latch lands.
        deadline = time.monotonic() + 10
        ready_status = None
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(base + "/ready", timeout=2) as r:
                    ready_status = r.status
            except urllib.error.HTTPError as e:
                ready_status = e.code
                if e.code == 503:
                    assert json.loads(e.read())["draining"] is True
                    break
            time.sleep(0.1)
        assert ready_status == 503

        # New work is shed with a clean 503 + Retry-After — the listener
        # is still accepting, so this is an HTTP error, not ECONNREFUSED.
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            _post(base, "/v1/completions", {
                "model": "drain", "prompt": [2, 4],
                "max_tokens": 4, "temperature": 0.0,
            })
        shed = exc_info.value
        assert shed.code == 503
        assert shed.headers["Retry-After"]
        body = json.loads(shed.read())
        assert body["error"]["type"] == "service_unavailable_error"

        # The in-flight stream completes normally despite the SIGTERM.
        finish_reasons = []
        saw_done = False
        for raw in stream:
            line = raw.strip()
            if not line.startswith(b"data: "):
                continue
            payload = line[len(b"data: "):]
            if payload == b"[DONE]":
                saw_done = True
                break
            chunk = json.loads(payload)
            for choice in chunk.get("choices", []):
                if choice.get("finish_reason"):
                    finish_reasons.append(choice["finish_reason"])
        assert saw_done
        assert finish_reasons == ["length"]  # completed, not cut off

        # Exit 0 well inside the drain budget.
        assert proc.wait(timeout=30) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
        log.seek(0)
        out = log.read()
        log.close()
        if proc.returncode != 0:
            print(out[-4000:])
