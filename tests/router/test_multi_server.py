"""Multi-API-server topology e2e: one launcher subprocess, 4 frontend
shards behind a shared port, 2 shared DP engines, kv-event-fed routing.

One server boots for the whole module (boot dominates the cost); the
tests run in file order against it and cover the acceptance criteria of
the frontend-scale-out PR:

1. per-frontend identity: /health, /ready and /metrics are addressable
   on each shard's admin port with distinct ``api_server_index``;
2. prefix-affinity: >=90% of follow-up turns are prefix-routed, summed
   over every shard's ``vllm:dp_routing_decisions_total{kind="prefix"}``;
3. shard-scoped crash recovery: SIGKILLing one frontend loses only THAT
   shard's journaled in-flight requests, and its replacement (same shard
   index) reports them;
4. SIGTERM drains every frontend and the launcher exits 0.
"""

from __future__ import annotations

import json
import math
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from tests.models.utils import tiny_llama_dir
from vllm_tpu.router.topology import admin_port_for

pytestmark = pytest.mark.fault_injection

N_FRONTENDS = 4
N_ENGINES = 2
N_SESSIONS = 8
BLOCK = 16

# Spawned engine/frontend children re-import the main module, so the
# server script MUST gate its work behind __main__ (multiprocessing
# "spawn" bootstrapping requirement).
_SERVER = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("VLLM_TPU_PALLAS_INTERPRET", "1")
os.environ.setdefault("VLLM_TPU_NO_USAGE_STATS", "1")


def main():
    import jax
    jax.config.update("jax_platforms", "cpu")

    from vllm_tpu.engine.arg_utils import AsyncEngineArgs
    from vllm_tpu.entrypoints.openai.api_server import run_server

    run_server(
        AsyncEngineArgs(
            model=sys.argv[1],
            dtype="float32",
            max_model_len=256,
            block_size=16,
            num_gpu_blocks_override=96,
            max_num_seqs=4,
            max_num_batched_tokens=128,
            data_parallel_engines=2,
            api_server_count=4,
            drain_timeout_s=30.0,
            journal_dir=sys.argv[3],
        ),
        host="127.0.0.1",
        port=int(sys.argv[2]),
    )


if __name__ == "__main__":
    main()
"""


def _get(url: str, timeout: float = 5.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.load(r)


def _post(base: str, body: dict, timeout: float = 120.0):
    req = urllib.request.Request(
        base + "/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    return urllib.request.urlopen(req, timeout=timeout)


def _metric(port: int, name: str, label: str | None = None) -> float:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5) as r:
        total = 0.0
        for line in r.read().decode().splitlines():
            if line.startswith(name) and (label is None or label in line):
                total += float(line.rsplit(" ", 1)[1])
        return total


class _Topology:
    def __init__(self, proc: subprocess.Popen, port: int, journal: str):
        self.proc = proc
        self.port = port
        self.journal = journal
        self.base = f"http://127.0.0.1:{port}"

    def admin(self, k: int) -> str:
        return f"http://127.0.0.1:{admin_port_for(self.port, k)}"

    def sum_metric(self, name: str, label: str | None = None) -> float:
        return sum(
            _metric(admin_port_for(self.port, k), name, label)
            for k in range(N_FRONTENDS)
        )


@pytest.fixture(scope="module")
def topo(tmp_path_factory):
    ckpt = tiny_llama_dir(tmp_path_factory.mktemp("tiny_llama_topo"))
    journal = str(tmp_path_factory.mktemp("topo_journal"))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    script = tmp_path_factory.mktemp("topo_server") / "server.py"
    script.write_text(_SERVER)

    # JAX_COMPILATION_CACHE_DIR, if set, rides along in os.environ; unset,
    # the child's worker uses the same fixed directory as this process.
    env = dict(os.environ, PYTHONPATH=os.getcwd())
    # Own session: the launcher's frontends are non-daemon children, so
    # teardown must be able to kill the WHOLE tree (killpg).
    # Output goes to a file, not a pipe nobody reads while the tests run:
    # a full pipe (64 KiB; XLA:CPU logs two long lines per executable it
    # loads from the compilation cache) blocks every server process in
    # write() and the next request times out.
    log = open(script.with_name("server.log"), "w+")
    proc = subprocess.Popen(
        [sys.executable, str(script), ckpt, str(port), journal], env=env,
        stdout=log, stderr=subprocess.STDOUT, text=True,
        start_new_session=True,
    )
    t = _Topology(proc, port, journal)
    try:
        deadline = time.monotonic() + 240
        pending = set(range(N_FRONTENDS))
        while pending and time.monotonic() < deadline:
            if proc.poll() is not None:
                break
            for k in list(pending):
                try:
                    with urllib.request.urlopen(
                            t.admin(k) + "/ready", timeout=2) as r:
                        if r.status == 200:
                            pending.discard(k)
                except (urllib.error.URLError, ConnectionError, OSError):
                    pass
            time.sleep(0.5)
        if pending:
            raise TimeoutError(
                f"frontends {sorted(pending)} never became ready "
                f"(launcher exit={proc.poll()})")
        yield t
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, OSError):
            pass
        if proc.poll() is None:
            proc.wait(timeout=10)
        log.seek(0)
        out = log.read()
        log.close()
        if proc.returncode not in (0, -signal.SIGKILL.value):
            print(out[-6000:])


def test_per_frontend_identity(topo):
    """Every shard is individually addressable on its admin port and
    knows its own index; the shared port answers too."""
    indexes = set()
    pids = set()
    for k in range(N_FRONTENDS):
        health = _get(topo.admin(k) + "/health")
        assert health["status"] == "healthy"
        assert len(health["engines"]) == N_ENGINES
        indexes.add(health["api_server_index"])
        pids.add(health["pid"])
        assert health["routing"].keys() == {
            "prefix", "prefix_spill", "least_loaded", "round_robin"}
        port_k = admin_port_for(topo.port, k)
        assert _metric(port_k, "vllm:api_server_index") == float(k)
        assert _metric(port_k, "vllm:api_server_count") == float(N_FRONTENDS)
    assert indexes == set(range(N_FRONTENDS))
    assert len(pids) == N_FRONTENDS  # truly separate processes


def test_followup_turns_route_to_prefix_holder(topo):
    """The tentpole acceptance bar: with 4 frontends and dp=2, >=90% of
    follow-up turns land on the engine that holds the session's prefix,
    observed via the routing-decision counters summed across shards."""
    convos = [
        [(1009 * g + 7 * j) % 120 + 3 for j in range(BLOCK * 3)]
        for g in range(N_SESSIONS)
    ]
    for c in convos:
        with _post(topo.base, {"model": "topo", "prompt": c,
                               "max_tokens": 8, "temperature": 0.0}) as r:
            assert r.status == 200

    # Each turn-1 prompt caches 3 blocks on its engine; every frontend's
    # index must hear about ALL of them (kv events broadcast to every
    # shard) before turn-2 routing is deterministic.
    want = 3 * N_SESSIONS
    deadline = time.monotonic() + 30
    laggards = {}
    while time.monotonic() < deadline:
        laggards = {
            k: idx for k in range(N_FRONTENDS)
            if sum((idx := _get(topo.admin(k) + "/health")["prefix_index"])
                   ["engines"].values()) < want
        }
        if not laggards:
            break
        time.sleep(0.25)
    assert not laggards, f"prefix indexes never settled: {laggards}"

    before = topo.sum_metric(
        "vllm:dp_routing_decisions_total", 'kind="prefix"')
    # Turn 2 re-sends the whole conversation plus a fresh tail; each new
    # HTTP connection lands on a kernel-chosen frontend, so this also
    # exercises cross-shard index agreement.
    for g, c in enumerate(convos):
        turn2 = c + [(1009 * g + 13 + 7 * j) % 120 + 3 for j in range(16)]
        with _post(topo.base, {"model": "topo", "prompt": turn2,
                               "max_tokens": 8, "temperature": 0.0}) as r:
            assert r.status == 200
    prefix_routed = topo.sum_metric(
        "vllm:dp_routing_decisions_total", 'kind="prefix"') - before
    assert prefix_routed >= math.ceil(0.9 * N_SESSIONS), (
        f"only {prefix_routed}/{N_SESSIONS} follow-up turns prefix-routed")
    # The routed hits also feed the per-shard histogram.
    assert topo.sum_metric(
        "vllm:dp_prefix_hit_blocks_count") >= prefix_routed


def test_frontend_crash_replays_only_its_shard(topo):
    """SIGKILL frontend 0 with a journaled request in flight: the
    launcher respawns shard 0, whose replacement reports exactly its own
    shard's loss; the other shards' journals are untouched."""
    pid0 = _get(topo.admin(0) + "/health")["pid"]

    # A long stream admitted by shard 0 (admin port pins the frontend),
    # journaled in shard-0's journal dir. Don't wait for SSE data — the
    # first event only arrives after first-step compile; the on-disk
    # snapshot (written synchronously at admission, unlinked on finish)
    # is the reliable "in flight right now" signal.
    shard0 = os.path.join(topo.journal, "shard-0")
    stream = _post(topo.admin(0), {
        "model": "topo", "prompt": [3, 5, 7, 11],
        "max_tokens": 200, "ignore_eos": True,
        "temperature": 0.0, "stream": True,
    })
    deadline = time.monotonic() + 20
    while not os.listdir(shard0):
        assert time.monotonic() < deadline, "request never journaled"
        time.sleep(0.02)

    os.kill(pid0, signal.SIGKILL)
    try:
        stream.close()
    except Exception:
        pass

    # The launcher respawns the SAME shard index; its replacement scans
    # journal shard-0 and reports the orphaned request as lost.
    deadline = time.monotonic() + 120
    health = None
    while time.monotonic() < deadline:
        try:
            health = _get(topo.admin(0) + "/health")
            if health["pid"] != pid0 and health["status"] == "healthy":
                break
        except (urllib.error.URLError, ConnectionError, OSError):
            pass
        time.sleep(0.5)
    assert health is not None and health["pid"] != pid0, (
        "frontend 0 was never respawned")
    assert health["api_server_index"] == 0
    assert health["requests_lost_on_restart_total"] == 1
    # Sibling shards never saw the crash: their counters stay zero.
    for k in range(1, N_FRONTENDS):
        sibling = _get(topo.admin(k) + "/health")
        assert sibling["requests_lost_on_restart_total"] == 0
        assert sibling["pid"] != health["pid"]


def test_sigterm_drains_every_frontend_to_exit_zero(topo):
    topo.proc.send_signal(signal.SIGTERM)
    assert topo.proc.wait(timeout=90) == 0
