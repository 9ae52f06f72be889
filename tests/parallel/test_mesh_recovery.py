"""Mesh fault-tolerance e2e: kill a "host" mid-decode, survive.

The acceptance scenario on the tier-1 CPU rig: the engine (heartbeat
rank 0, in-process client) shares a 2-rank ring with a jax-free peer
subprocess standing in for the second host. SIGKILLing the peer
mid-decode must drive the full recovery story — the monitor classifies
host death after ``mesh_death_timeout_s``, the engine aborts the
in-flight step, runs the supervised shrink, and the journal replays the
interrupted request to completion with zero lost requests; ``/health``
reports ``mesh.state=degraded`` and ``vllm:mesh_recoveries_total``
increments. Respawning the peer grows the mesh back.

The failure path pins the never-half-meshed contract: when the
``worker.reinitialize_mesh`` failpoint makes recovery itself fail, the
engine must come out cleanly dead (EngineDeadError for all waiters),
not keep serving on a broken world.

MeshRecoveryManager decision/bookkeeping units ride along (no model).
"""

from __future__ import annotations

import asyncio
import socket
import time

import pytest

from tests.models.utils import tiny_llama_dir
from vllm_tpu.engine.arg_utils import AsyncEngineArgs
from vllm_tpu.engine.async_llm import AsyncLLM, EngineDeadError
from vllm_tpu.parallel.mesh_monitor import ENV_HB_ADDRS, MeshEvent
from vllm_tpu.resilience import failpoints as fp
from vllm_tpu.resilience.chaos import HeartbeatPeerManager
from vllm_tpu.resilience.mesh_recovery import (ENV_HB_RANK,
                                               MeshRecoveryManager)
from vllm_tpu.sampling_params import RequestOutputKind, SamplingParams

pytestmark = pytest.mark.fault_injection

INTERVAL = 0.1
# Twenty beats of room. The engine's monitor threads share the engine's
# interpreter: while a step program is traced and lowered at its first use
# they do not run, the peer's beats wait in the socket, and a timeout
# shorter than that stall reads a live peer as dead (0.6 s against a first
# use of 1.3 s under six xdist workers: a LOST and a REJOIN before the kill,
# and every count of this file off by one).
TIMEOUT = 2.0
# Seconds added to every step while a request must stay in flight through
# the whole death timeout and the recovery behind it.
STEP_DELAY = 0.08


@pytest.fixture(autouse=True)
def _disarm_failpoints():
    fp.deactivate()
    yield
    fp.deactivate()


def _free_udp_ports(n: int) -> list[int]:
    socks = []
    for _ in range(n):
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.bind(("127.0.0.1", 0))
        socks.append(s)
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def _wait_for(cond, timeout=60.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.05)
    pytest.fail(f"timed out waiting for {msg}")


# -- MeshRecoveryManager units (no model, no engine) --------------------


def _manager(monkeypatch, rank=0, n=2) -> MeshRecoveryManager:
    ports = _free_udp_ports(n)
    addrs = [("127.0.0.1", p) for p in ports]
    return MeshRecoveryManager(
        rank, addrs, heartbeat_interval_s=INTERVAL, death_timeout_s=TIMEOUT)


def test_from_env_unarmed_without_ring(monkeypatch):
    monkeypatch.delenv(ENV_HB_ADDRS, raising=False)
    assert MeshRecoveryManager.from_env() is None
    # A single address cannot form a ring: warn-and-ignore, not crash.
    monkeypatch.setenv(ENV_HB_ADDRS, "127.0.0.1:1")
    assert MeshRecoveryManager.from_env() is None


def test_from_env_rank_precedence(monkeypatch):
    ports = _free_udp_ports(2)
    monkeypatch.setenv(
        ENV_HB_ADDRS, ",".join(f"127.0.0.1:{p}" for p in ports))
    monkeypatch.setenv("VLLM_TPU_DIST_PROCESS_ID", "1")
    monkeypatch.delenv(ENV_HB_RANK, raising=False)
    mgr = MeshRecoveryManager.from_env()
    assert mgr is not None and mgr.rank == 1  # falls back to DIST id
    mgr.stop()
    monkeypatch.setenv(ENV_HB_RANK, "0")
    mgr = MeshRecoveryManager.from_env()
    assert mgr is not None and mgr.rank == 0  # explicit rank wins
    mgr.stop()


def test_poll_coalesces_and_prioritizes_shrink(monkeypatch):
    mgr = _manager(monkeypatch, n=3)
    assert mgr.poll() is None  # quiet ring -> no decision
    # A batch with both a loss and a rejoin must shrink (the grow is
    # picked up later): KV is invalid either way, but shrink cannot wait.
    mgr.monitor._events = [MeshEvent("rejoin", 2, 1),
                           MeshEvent("lost", 1, 2)]
    decision = mgr.poll()
    assert decision == {"action": "shrink", "lost": [1], "rejoined": [2],
                        "epoch": 2}
    assert mgr.rank_losses_total == 1
    # Rejoin-only batch -> grow.
    mgr.monitor._events = [MeshEvent("rejoin", 1, 3)]
    assert mgr.poll()["action"] == "grow"
    # Events landing while a recovery executes are deferred, not acted on.
    mgr.begin_recovery()
    mgr.monitor._events = [MeshEvent("lost", 2, 4)]
    assert mgr.poll() is None
    assert mgr.status()["state"] == "recovering"
    mgr.finish_recovery(ok=True)
    assert mgr.recoveries_total == 1
    assert len(mgr.status()["recovery_durations"]) == 1
    mgr.begin_recovery()
    mgr.finish_recovery(ok=False)  # failed recovery: no counter, no sample
    assert mgr.recoveries_total == 1
    assert len(mgr.status()["recovery_durations"]) == 1


def test_survivor_world_mapping(monkeypatch):
    mgr = _manager(monkeypatch, rank=1, n=3)
    # Not an explicit-coordinator launch -> nothing to re-mesh.
    monkeypatch.delenv("VLLM_TPU_DIST_COORDINATOR", raising=False)
    assert mgr.survivor_world() is None
    monkeypatch.setenv("VLLM_TPU_DIST_COORDINATOR", "10.0.0.1:1234")
    # Rank 2 lost, rank 0 (the coordinator host) survives: keep it.
    mgr.monitor._lost = {2}
    assert mgr.survivor_world() == ("10.0.0.1:1234", 2, 1)
    # Rank 0 lost: the lowest survivor (this rank) hosts the coordinator
    # on its heartbeat host + the original port; ranks compact to 0..n-1.
    mgr.monitor._lost = {0}
    host = mgr.monitor._addrs[1][0]
    assert mgr.survivor_world() == (f"{host}:1234", 2, 0)
    # This rank itself in the lost set (we are the partitioned one).
    mgr.monitor._lost = {1}
    assert mgr.survivor_world() is None


# -- e2e: host death mid-decode on the tier-1 CPU rig -------------------


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    return tiny_llama_dir(tmp_path_factory.mktemp("tiny_llama_mesh"))


@pytest.fixture(scope="module")
def hb_peers():
    """A 2-rank heartbeat ring: the engine is rank 0, a jax-free peer
    subprocess models the second host as rank 1."""
    import os

    ports = _free_udp_ports(2)
    spec = ",".join(f"127.0.0.1:{p}" for p in ports)
    old = {k: os.environ.get(k) for k in (ENV_HB_ADDRS, ENV_HB_RANK)}
    os.environ[ENV_HB_ADDRS] = spec
    os.environ[ENV_HB_RANK] = "0"
    peers = HeartbeatPeerManager(
        spec, [1], heartbeat_interval_s=INTERVAL, death_timeout_s=TIMEOUT)
    peers.start_all()
    peers.wait_up()
    yield peers
    peers.stop_all()
    for k, v in old.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


@pytest.fixture(scope="module")
def engine(ckpt, hb_peers):
    engine = AsyncLLM.from_engine_args(
        AsyncEngineArgs(
            model=ckpt, dtype="float32", max_model_len=128, block_size=16,
            num_gpu_blocks_override=64, max_num_seqs=4,
            max_num_batched_tokens=128, enable_engine_recovery=True,
            max_request_retries=2,
            mesh_death_timeout_s=TIMEOUT,
            mesh_heartbeat_interval_s=INTERVAL,
        )
    )
    # The first uses of the prefill and decode programs happen here, with
    # nothing armed, not inside the window a test times.
    _generate(engine, [5, 9, 11], 4, "warm")
    yield engine
    try:
        engine.shutdown()
    except Exception:
        pass


def _mesh(engine) -> dict:
    return engine.resilience_status()["mesh"]


def _generate(engine, prompt: list[int], max_tokens: int, req_id: str,
              on_tokens=None) -> list[int]:
    sp = SamplingParams(temperature=0.0, max_tokens=max_tokens,
                        ignore_eos=True,
                        output_kind=RequestOutputKind.DELTA)

    async def run():
        tokens = []
        async for out in engine.generate(
            {"prompt_token_ids": prompt}, sp, req_id
        ):
            tokens.extend(out.outputs[0].token_ids)
            if on_tokens is not None:
                on_tokens(tokens)
            if out.finished:
                assert out.outputs[0].finish_reason == "length"
        return tokens

    return asyncio.run(asyncio.wait_for(run(), timeout=240))


def _settled(engine, state: str) -> dict:
    """The mesh status once the monitor reads ``state`` and the busy loop
    has run the recovery for it: the monitor changes first, by up to a
    poll interval, so the status must read the same on two polls with no
    event left to drain and no recovery under way."""
    mgr = engine.engine_core.engine_core.mesh_recovery
    seen: list[int | None] = []

    def done():
        m = _mesh(engine)
        quiet = m["state"] == state and not mgr.monitor._events
        seen.append(m["recoveries_total"] if quiet else None)
        return len(seen) > 1 and seen[-1] is not None and seen[-1] == seen[-2]

    _wait_for(done, msg=f"mesh settled {state}")
    return _mesh(engine)


@pytest.fixture
def healthy(engine, hb_peers) -> dict:
    """Rank 1 up and the mesh whole, whatever the test before left."""
    hb_peers.respawn(1)
    return _settled(engine, "healthy")


@pytest.fixture
def degraded(engine, hb_peers) -> dict:
    """Rank 1 dead and the mesh shrunk, whatever the test before left."""
    hb_peers.kill(1)
    return _settled(engine, "degraded")


def test_host_death_mid_decode_shrinks_and_replays(engine, hb_peers,
                                                   healthy):
    before = engine.resilience_status()

    # Stretch every decode step so the death timeout elapses (and the
    # recovery runs) while the request is unambiguously in flight.
    fp.configure(f"model_runner.step=delay({STEP_DELAY})")
    killed = []

    def kill_after_three(tokens):
        if not killed and len(tokens) >= 3:
            killed.append(hb_peers.kill(1))

    tokens = _generate(engine, [5, 9, 11], 96, "mesh-crash-1",
                       on_tokens=kill_after_three)
    # Zero lost requests: the interrupted stream resumed from the journal
    # and delivered its full budget, no duplicates of the prefix.
    assert killed and len(tokens) == 96

    mesh = _mesh(engine)
    assert mesh["state"] == "degraded"
    assert mesh["size"] == 1 and mesh["lost_ranks"] == [1]
    assert mesh["rank_losses_total"] == healthy["rank_losses_total"] + 1
    assert mesh["recoveries_total"] == healthy["recoveries_total"] + 1
    status = engine.resilience_status()
    assert (status["requests_replayed_total"]
            == before["requests_replayed_total"] + 1)
    assert status["requests_failed_on_crash_total"] == 0
    assert not engine._dead and engine.is_ready()


def test_degraded_mesh_visible_in_health_and_metrics(engine, degraded):
    from aiohttp.test_utils import TestClient, TestServer

    from vllm_tpu.entrypoints.openai.api_server import build_app
    from vllm_tpu.metrics.prometheus import PrometheusRegistry

    losses = degraded["rank_losses_total"]
    recoveries = degraded["recoveries_total"]
    assert losses >= 1 and recoveries >= 1

    async def run():
        app = build_app(engine, "tiny", PrometheusRegistry(engine))
        async with TestClient(TestServer(app)) as client:
            resp = await client.get("/health")
            # Degraded capacity, but alive: liveness stays 200.
            assert resp.status == 200
            body = await resp.json()
            assert body["status"] == "degraded"
            assert body["mesh"]["state"] == "degraded"
            assert body["mesh"]["size"] == 1
            assert body["mesh"]["world_size"] == 2
            assert body["mesh"]["lost_ranks"] == [1]
            assert body["mesh"]["recoveries_total"] == recoveries

            text = await (await client.get("/metrics")).text()
            assert "vllm:mesh_size 1.0" in text
            assert f"vllm:mesh_rank_losses_total {float(losses)}" in text
            assert f"vllm:mesh_recoveries_total {float(recoveries)}" in text
            assert ("vllm:mesh_recovery_duration_seconds_count "
                    f"{recoveries}\n" in text)

    asyncio.run(asyncio.wait_for(run(), timeout=60))


def test_rejoin_grows_mesh_back_and_serves(engine, hb_peers, degraded):
    hb_peers.respawn(1)
    # The rejoin is noticed by the idle busy loop (no traffic needed) and
    # drives a grow recovery.
    mesh = _settled(engine, "healthy")
    assert mesh["lost_ranks"] == []
    assert mesh["recoveries_total"] == degraded["recoveries_total"] + 1
    assert mesh["rank_losses_total"] == degraded["rank_losses_total"]

    assert len(_generate(engine, [7, 3], 8, "after-rejoin")) == 8


# -- slow: 2-process jax.distributed mesh shrink (the real rig) ---------

_MULTIHOST_CHILD = r"""
import os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")

import numpy as np
import jax.numpy as jnp
from vllm_tpu.parallel import distributed as dist
from vllm_tpu.resilience.mesh_recovery import MeshRecoveryManager

rank = int(os.environ["VLLM_TPU_DIST_PROCESS_ID"])

dist.init_distributed()
assert jax.process_count() == 2 and len(jax.devices()) == 8

mgr = MeshRecoveryManager.from_env()
assert mgr is not None and mgr.rank == rank
mgr.start()

# A sharded computation over the full 8-device world stands in for the
# serving workload.
from transformers import LlamaConfig
from vllm_tpu.models.llama import LlamaForCausalLM
from vllm_tpu.parallel.mesh import build_mesh, named_shardings
from vllm_tpu.config import ParallelConfig

cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=1, num_attention_heads=8,
                  num_key_value_heads=8, max_position_embeddings=64,
                  tie_word_embeddings=False)
model = LlamaForCausalLM(cfg, dtype=jnp.float32)

def shard_dummy(mesh):
    with jax.default_device(jax.local_devices()[0]):
        host = jax.tree.map(
            np.asarray, model.init_dummy_params(jax.random.PRNGKey(0)))
    shardings = named_shardings(mesh, model.param_shardings())
    return jax.tree.map(
        lambda x, s: jax.make_array_from_callback(
            x.shape, s, lambda idx: x[idx]),
        host, shardings)

mesh = build_mesh(ParallelConfig(tensor_parallel_size=8))
params = shard_dummy(mesh)
print("WORLD2_OK", rank, flush=True)

if rank == 1:
    # The dying host: hard-exit mid-run, exactly like a SIGKILL.
    time.sleep(1.0)
    os._exit(137)

# Rank 0 is the survivor: wait for the monitor to classify host death,
# then run the same shrink sequence Worker.reinitialize_mesh drives —
# teardown, re-bootstrap the survivor world, rebuild the mesh at the
# reduced size, reload params over it, and compute.
deadline = time.monotonic() + 60.0
decision = None
while decision is None and time.monotonic() < deadline:
    decision = mgr.poll()
    time.sleep(0.05)
assert decision is not None and decision["action"] == "shrink", decision
assert decision["lost"] == [1], decision
mgr.begin_recovery()
world = mgr.survivor_world()
assert world is not None and world[1:] == (1, 0), world
# Drop every old-world reference BEFORE teardown (the production
# contract Worker.reinitialize_mesh follows): live Device/Array handles
# would keep the old coordination client alive against the new service.
del params, mesh
# force=True: the dead host can never join the shutdown barrier.
dist.shutdown_distributed(force=True)
dist.init_distributed(*world)
assert jax.process_count() == 1 and len(jax.devices()) == 4
mesh = build_mesh(ParallelConfig(tensor_parallel_size=4))
params = shard_dummy(mesh)
leaf = jax.tree_util.tree_leaves(params)[0]
assert np.isfinite(float(jnp.sum(leaf)))
mgr.finish_recovery(ok=True)
st = mgr.status()
assert st["state"] == "degraded" and st["recoveries_total"] == 1, st
mgr.stop()
dist.shutdown_distributed()
print("CHILD_OK", rank, flush=True)
"""


@pytest.mark.slow
def test_two_process_mesh_shrink_survives_dead_host(tmp_path):
    """The real rig: two jax.distributed processes, rank 1 hard-exits,
    rank 0's heartbeat monitor classifies host death and re-forms the
    world alone at half the devices. The in-process tests above keep this
    flow under the tier-1 gate; this one proves it cross-process."""
    import os
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord_port = s.getsockname()[1]
    hb_ports = _free_udp_ports(2)
    spec = ",".join(f"127.0.0.1:{p}" for p in hb_ports)
    script = tmp_path / "child.py"
    script.write_text(_MULTIHOST_CHILD)
    procs = []
    for i in range(2):
        env = dict(
            os.environ,
            VLLM_TPU_DIST_COORDINATOR=f"127.0.0.1:{coord_port}",
            VLLM_TPU_DIST_NUM_PROCESSES="2",
            VLLM_TPU_DIST_PROCESS_ID=str(i),
            VLLM_TPU_PALLAS_INTERPRET="1",
            PYTHONPATH=os.getcwd(),
        )
        env[ENV_HB_ADDRS] = spec
        env[ENV_HB_RANK] = str(i)
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        ))
    outs = [p.communicate(timeout=300)[0] for p in procs]
    assert procs[1].returncode == 137, outs[1][-2000:]  # died as planned
    assert "WORLD2_OK 1" in outs[1]
    assert procs[0].returncode == 0, outs[0][-3000:]
    assert "CHILD_OK 0" in outs[0]


# -- failure path: recovery fails -> cleanly dead, never half-meshed ----


def test_failed_recovery_kills_engine_cleanly(ckpt):
    import os

    ports = _free_udp_ports(2)
    spec = ",".join(f"127.0.0.1:{p}" for p in ports)
    old = {k: os.environ.get(k) for k in (ENV_HB_ADDRS, ENV_HB_RANK)}
    os.environ[ENV_HB_ADDRS] = spec
    os.environ[ENV_HB_RANK] = "0"
    peers = HeartbeatPeerManager(
        spec, [1], heartbeat_interval_s=INTERVAL, death_timeout_s=TIMEOUT)
    peers.start_all()
    peers.wait_up()
    engine = None
    try:
        engine = AsyncLLM.from_engine_args(
            AsyncEngineArgs(
                model=ckpt, dtype="float32", max_model_len=128,
                block_size=16, num_gpu_blocks_override=64, max_num_seqs=4,
                max_num_batched_tokens=128, enable_engine_recovery=True,
                mesh_death_timeout_s=TIMEOUT,
                mesh_heartbeat_interval_s=INTERVAL,
            )
        )
        assert _mesh(engine)["state"] == "healthy"
        # Recovery itself will fail at the worker re-mesh step.
        fp.configure("worker.reinitialize_mesh=raise")
        peers.kill(1)
        # The busy loop must let MeshRecoveryError unwind: process-level
        # death (here: engine marked dead), NOT a half-meshed engine that
        # keeps serving.
        _wait_for(lambda: engine._dead,
                  msg="engine cleanly dead after failed mesh recovery")
        assert not engine.is_ready()

        async def run():
            sp = SamplingParams(temperature=0.0, max_tokens=4)
            async for _ in engine.generate(
                {"prompt_token_ids": [1, 2]}, sp, "post-mortem"
            ):
                pass

        with pytest.raises(EngineDeadError):
            asyncio.run(asyncio.wait_for(run(), timeout=60))
    finally:
        fp.deactivate()
        peers.stop_all()
        if engine is not None:
            try:
                engine.shutdown()
            except Exception:
                pass
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
