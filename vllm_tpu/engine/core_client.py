"""EngineCore clients: in-process and multiprocess (ZMQ) variants.

Reference analog: ``vllm/v1/engine/core_client.py`` (InprocClient :274,
SyncMPClient :716, AsyncMPClient :887, DPLBAsyncMPClient :1317). One client
interface serves both the sync LLMEngine and the AsyncLLM thread loop:

- ``add_request`` / ``abort_requests`` feed work in;
- ``get_output(timeout)`` returns the next EngineCoreOutputs (None on
  timeout — MP mode blocks on the socket, in-proc mode runs a step);
- ``has_unfinished_requests`` is tracked client-side in MP mode (adds
  minus finish records) so the frontend never round-trips for it.

Engine death surfaces as EngineDeadError from any call — unless crash
recovery (``config.resilience_config.enable_recovery``) is on, in which
case the client respawns the dead engine-core process under the
supervisor's restart budget and raises EngineRestartedError carrying the
request ids that were in flight on it (the frontend replays or fails
those per-request; see ``vllm_tpu/resilience``). The single-engine
MPClient respawns *blocking* (there is nothing else to serve meanwhile);
the DP client respawns *non-blocking* and keeps routing to surviving
ranks (degraded mode) until the replacement reports READY.
"""

from __future__ import annotations

import atexit
import os
import pickle
import tempfile
import time
import uuid
from typing import Any

from vllm_tpu.config import EngineConfig
from vllm_tpu.core.sched_output import EngineCoreOutputs
from vllm_tpu.logger import init_logger
from vllm_tpu.request import EngineCoreRequest
from vllm_tpu.resilience import EngineRestartedError, EngineSupervisor
from vllm_tpu.resilience.failpoints import fail_point
from vllm_tpu.resilience.supervisor import COORDINATOR_ID
from vllm_tpu.tracing import trace_instant
from vllm_tpu.versioning import SchemaVersionError, check_schema

logger = init_logger(__name__)


class EngineDeadError(RuntimeError):
    """Reference analog: ``vllm/v1/engine/exceptions.py:9``."""


def make_client(config: EngineConfig):
    from vllm_tpu import envs
    from vllm_tpu.plugins import load_general_plugins
    from vllm_tpu.usage import record_usage

    # Every engine frontend (sync LLMEngine AND AsyncLLM) converges here.
    load_general_plugins()
    record_usage(config, context="engine")

    if config.parallel_config.data_parallel_engines > 1:
        return DPLBClient(config)
    mp = (
        envs.VLLM_TPU_ENABLE_MULTIPROCESSING
        or config.parallel_config.distributed_executor_backend == "mp"
    )
    return MPClient(config) if mp else InprocClient(config)


def _merge_numeric(acc: dict, snap: dict) -> dict:
    """Fold one engine's fabric snapshot into the pool aggregate: numeric
    leaves sum, dicts recurse, anything else keeps the first value seen
    (booleans are config echoes, not counters — excluded from summing)."""
    out = dict(acc)
    for k, v in snap.items():
        if isinstance(v, dict):
            # A leaf can be None on one engine and a dict on another
            # (e.g. cost.last_decision before that engine ever decided).
            prev = out.get(k)
            out[k] = _merge_numeric(prev if isinstance(prev, dict) else {}, v)
        elif isinstance(v, bool):
            out.setdefault(k, v)
        elif isinstance(v, (int, float)) and isinstance(
            out.get(k), (int, float)
        ):
            out[k] = out[k] + v
        else:
            out.setdefault(k, v)
    return out


def _apply_config_overrides(config: EngineConfig, overrides: dict) -> None:
    """Dotted-path overrides for an upgrade replacement's config, e.g.
    ``{"scheduler_config.max_num_seqs": 8}``. Unknown paths raise BEFORE
    any process is spawned — a knob that silently failed to apply would
    make the health gate vouch for the wrong config."""
    for path, value in overrides.items():
        obj: Any = config
        parts = str(path).split(".")
        for attr in parts[:-1]:
            if not hasattr(obj, attr):
                raise ValueError(f"unknown engine config path: {path!r}")
            obj = getattr(obj, attr)
        if not hasattr(obj, parts[-1]):
            raise ValueError(f"unknown engine config path: {path!r}")
        setattr(obj, parts[-1], value)


class InprocClient:
    """Direct in-process EngineCore (the default single-host path)."""

    def __init__(self, config: EngineConfig) -> None:
        from vllm_tpu.engine.engine_core import EngineCore

        self.engine_core = EngineCore(config)

    def add_request(self, req: EngineCoreRequest) -> None:
        self.engine_core.add_request(req)

    def abort_requests(self, request_ids: list[str]) -> None:
        self.engine_core.abort_requests(request_ids)

    def get_output(self, timeout: float | None = None) -> EngineCoreOutputs:
        return self.engine_core.step()

    def has_unfinished_requests(self) -> bool:
        return self.engine_core.has_unfinished_requests()

    def reset_prefix_cache(self) -> bool:
        return self.engine_core.reset_prefix_cache()

    def set_brownout_rung(self, rung: int) -> bool:
        return self.engine_core.set_brownout_rung(rung)

    def set_qos_enabled(self, enabled: bool) -> bool:
        return self.engine_core.set_qos_enabled(enabled)

    def set_config(self, updates: dict) -> dict:
        return self.engine_core.set_config(updates)

    def engine_versions(self) -> dict:
        return {"0": self.engine_core.version_status()}

    def sleep(self, level: int = 1) -> bool:
        return self.engine_core.sleep(level)

    def wake_up(self) -> bool:
        return self.engine_core.wake_up()

    def is_sleeping(self) -> bool:
        return self.engine_core.is_sleeping()

    def update_weights(self, path: str) -> bool:
        return self.engine_core.update_weights(path)

    def receive_weights(self, port: int, timeout: float = 300.0) -> int:
        return self.engine_core.receive_weights(port, timeout)

    def reinitialize_distributed(self, new_tp: int) -> bool:
        return self.engine_core.reinitialize_distributed(new_tp)

    def save_sharded_state(self, path: str) -> bool:
        return self.engine_core.save_sharded_state(path)

    def add_lora(self, name: str, path: str) -> bool:
        return self.engine_core.add_lora(name, path)

    def remove_lora(self, name: str) -> bool:
        return self.engine_core.remove_lora(name)

    def list_loras(self) -> list[str]:
        return self.engine_core.list_loras()

    def start_profile(self, trace_dir: str | None = None,
                      python_tracer_level: int = 0) -> dict:
        return self.engine_core.start_profile(trace_dir, python_tracer_level)

    def stop_profile(self) -> dict:
        return self.engine_core.stop_profile()

    def kv_fabric_status(self) -> dict:
        return self.engine_core.kv_fabric_status()

    @property
    def inflight(self) -> bool:
        return bool(self.engine_core._inflight)

    def engine_status(self) -> dict:
        return {"0": {"up": True, "restarts": 0}}

    def mesh_status(self) -> dict | None:
        return self.engine_core.mesh_status()

    def poll_mesh(self) -> None:
        """Drive mesh-membership recovery (no-op unless the heartbeat
        ring is armed). A shrink/grow surfaces as EngineRestartedError —
        the engine is ALIVE and recovered, but every interrupted request
        must go through the frontend's journal-replay path. Suspects are
        explicitly empty: a host death is not the requests' fault, so
        the quarantine must not strike them."""
        ev = self.engine_core.poll_mesh_recovery()
        if ev is not None and ev["lost_req_ids"]:
            raise EngineRestartedError(
                ev["lost_req_ids"], engine_id=0, reason=ev["reason"],
                suspect_req_ids=[])

    def is_ready(self) -> bool:
        return True

    def suspend_recovery(self) -> None:
        """No-op: the in-proc client has no respawn machinery."""

    def shutdown(self) -> None:
        self.engine_core.shutdown()


class _ZMQClientBase:
    """Shared socket plumbing for the MP clients.

    Subclass contract: set ``_serial``, ``_proc_mod``, ``_ctx``,
    ``_output`` (shared PULL), ``_procs`` (list of engine processes),
    ``_pending``, ``_dead``, ``_resilience``, ``_supervisor``,
    ``_started``; implement ``_utility`` (single-engine call vs
    broadcast), ``_on_finished`` (drop a finished request id),
    ``_respawn_engine`` (tear down + relaunch one engine, returning the
    request ids lost with it) and ``_on_engine_ready`` (a respawned
    engine reported READY).
    """

    # Shutdown/drain latch: once set, crash recovery is OFF — a death
    # observed while tearing down raises EngineDeadError instead of
    # respawning. Without it, shutdown could race a respawn back to life
    # against the ZMQ sockets being closed (satellite of ISSUE 3).
    _closing = False

    # Last mesh status pushed by an engine proc (MSG_MESH), keyed by
    # engine id; None until a mesh-monitoring engine reports.
    _mesh: dict[int, dict] | None = None

    def mesh_status(self) -> dict | None:
        if not self._mesh:
            return None
        # Single-engine deployments are the mesh case today; for DP just
        # surface engine 0's view (each rank monitors the same ring).
        return next(iter(self._mesh.values()))

    def poll_mesh(self) -> None:
        """MP mode: mesh recovery runs inside the engine proc's busy loop
        and arrives via MSG_MESH on the output socket — nothing to drive
        from the frontend."""

    def _on_mesh_msg(self, frames: list[bytes]) -> None:
        payload = self._serial.decode(frames[1])
        eid = int(payload.get("engine_id", 0))
        if self._mesh is None:
            self._mesh = {}
        self._mesh[eid] = payload.get("status") or {}
        lost = payload.get("lost_req_ids") or []
        if lost and not self._closing:
            # The engine survived and recovered (shrunk/regrown mesh) —
            # this is NOT a death, so no respawn: just hand the
            # interrupted requests to the journal-replay path. Empty
            # suspect set: a host death is not the requests' fault.
            raise EngineRestartedError(
                lost, engine_id=eid,
                reason=payload.get("reason", "mesh recovery"),
                suspect_req_ids=[])

    def suspend_recovery(self) -> None:
        """Permanently disable respawns on this client (graceful drain /
        shutdown). In-flight work keeps running on live engines; only the
        reaction to a *death* changes (EngineDeadError, fail-fast)."""
        self._closing = True

    def _recv(self, timeout_ms: int) -> list[bytes] | None:
        """One message, honoring death of any engine process."""
        # drop = pretend the poll timed out (frame lost in transit);
        # delay/raise model a slow or failing transport.
        if fail_point("core_client.recv") == "drop":
            return None
        deadline = timeout_ms
        step = 200
        while True:
            if self._output.poll(min(step, max(deadline, 0))):
                frames = self._output.recv_multipart()
                kind = frames[0]
                if kind == self._proc_mod.MSG_DEAD:
                    eid = int(frames[2]) if len(frames) > 2 else 0
                    # Optional fourth frame: request ids in flight at
                    # death (the quarantine suspect set).
                    suspects = None
                    if len(frames) > 3:
                        try:
                            suspects = self._serial.decode(frames[3])
                        except Exception:
                            suspects = None
                    self._handle_engine_death(
                        [eid], f"engine core died:\n{frames[1].decode()}",
                        suspects=suspects,
                    )
                    continue  # unreachable (death handler raises)
                if kind == self._proc_mod.MSG_MESH:
                    self._on_mesh_msg(frames)  # raises on a recovery
                    continue
                if kind == self._proc_mod.MSG_READY and self._started:
                    # A respawned engine finished re-initialization.
                    self._on_engine_ready(self._serial.decode(frames[1]))
                    continue
                return frames
            deadline -= step
            dead = self._dead_proc_ids()
            if dead:
                self._handle_engine_death(
                    dead, "an engine core process exited"
                )
            if deadline <= 0:
                return None

    def _dead_proc_ids(self) -> list[int]:
        """Engine slots whose process exited unexpectedly. The DP client
        overrides this to skip retired slots: an autoscale drain victim
        exits on purpose, and that exit must not read as a death."""
        return [i for i, p in enumerate(self._procs) if not p.is_alive()]

    def _check_alive(self) -> None:
        if self._dead:
            raise EngineDeadError("engine core process is not running")
        dead = self._dead_proc_ids()
        if dead:
            self._handle_engine_death(
                dead, "engine core process is not running"
            )

    def _handle_engine_death(self, engine_ids: list[int],
                             reason: str,
                             suspects: list[str] | None = None) -> None:
        """Dead engine(s) detected. Always raises: EngineDeadError when
        recovery is off / mid-init / budget-exhausted (reference
        semantics), EngineRestartedError (with the interrupted request
        ids) after a successful respawn kick-off.

        ``suspects`` is the batch that was on the device at death (from
        the MSG_DEAD suspect frame); None means the death carried no
        batch info (SIGKILL, proc-exit detection) and the conservative
        default — every lost request is a suspect — applies."""
        hang = "device hang" in reason
        if hang:
            # Distinct failure class from busy-loop heartbeat loss: the
            # step watchdog inside the engine proc fired and hard-exited.
            self.watchdog_trips = getattr(self, "watchdog_trips", 0) + 1
        if (
            not self._started
            or self._closing
            or not self._resilience.enable_recovery
        ):
            self._dead = True
            raise EngineDeadError(reason)
        lost: list[str] = []
        for eid in engine_ids:
            if not self._supervisor.may_restart(eid):
                self._supervisor.record_dead(eid)
                self._dead = True
                raise EngineDeadError(
                    f"{reason} (engine {eid} exhausted its "
                    f"{self._resilience.max_engine_restarts}-restart budget)"
                )
            n = self._supervisor.record_failure(eid)
            logger.error(
                "engine core %d died (%s); respawning (restart %d/%d)",
                eid, reason.splitlines()[0], n,
                self._resilience.max_engine_restarts,
            )
            lost.extend(self._respawn_engine(eid))
        raise EngineRestartedError(
            lost, engine_id=engine_ids[0], reason=reason.splitlines()[0],
            suspect_req_ids=suspects, hang=hang,
        )

    def _drain_stale_outputs(self, lost: set[str]) -> None:
        """Drop frames from a dead engine incarnation that would corrupt
        replayed streams: OUTPUT frames for interrupted requests get
        filtered (their requests are about to be re-admitted under the
        same ids), MSG_DEAD frames for the death being handled get
        dropped. Best-effort — frames still in the kernel buffer when
        this runs are caught by the req-id filter downstream only if
        another death occurs, so the respawn path drains *after* joining
        the dead process."""
        kept: list[list[bytes]] = []

        def filter_frames(frames: list[bytes]) -> list[bytes] | None:
            if frames[0] == self._proc_mod.MSG_DEAD:
                return None
            if frames[0] != self._proc_mod.MSG_OUTPUTS:
                return frames
            outs: EngineCoreOutputs = self._serial.decode(frames[1])
            filtered = [o for o in outs.outputs if o.req_id not in lost]
            if len(filtered) == len(outs.outputs):
                return frames
            if not filtered and outs.scheduler_stats is None:
                return None
            outs.outputs = filtered
            return [self._proc_mod.MSG_OUTPUTS, self._serial.encode(outs)]

        for frames in self._pending:
            f = filter_frames(frames)
            if f is not None:
                kept.append(f)
        while self._output.poll(0):
            f = filter_frames(self._output.recv_multipart())
            if f is not None:
                kept.append(f)
        self._pending = kept

    def _respawn_engine(self, engine_id: int) -> list[str]:
        raise NotImplementedError

    def _on_engine_ready(self, payload: dict) -> None:
        raise NotImplementedError

    def _has_live_requests(self) -> bool:
        return bool(self._live)

    def _engines_with_work(self) -> list[int]:
        return list(range(len(self._procs)))

    def _check_heartbeat(self) -> None:
        """Hang detection (opt-in): an engine that holds unfinished
        requests but has produced no frame for heartbeat_timeout_s is
        killed; the normal death path then recovers it."""
        hb = self._resilience.heartbeat_timeout_s
        if not hb or not self._has_live_requests():
            self._last_progress = time.monotonic()
            return
        if time.monotonic() - self._last_progress <= hb:
            return
        self._last_progress = time.monotonic()
        for eid in self._engines_with_work():
            p = self._procs[eid]
            if p.is_alive():
                logger.error(
                    "engine core %d heartbeat timeout (%.0fs with "
                    "unfinished requests and no output); killing it",
                    eid, hb,
                )
                p.terminate()

    def get_output(self, timeout: float | None = None) -> EngineCoreOutputs:
        """Next batch of outputs; empty EngineCoreOutputs on timeout."""
        self._check_alive()
        self._check_heartbeat()
        while True:
            if self._pending:
                frames = self._pending.pop(0)
            else:
                frames = self._recv(
                    timeout_ms=int(
                        (timeout if timeout is not None else 0.2) * 1000
                    )
                )
            if frames is None:
                return EngineCoreOutputs()
            if frames[0] == self._proc_mod.MSG_READY:
                # READY parked in _pending by a stale-frame drain.
                self._on_engine_ready(self._serial.decode(frames[1]))
                continue
            if frames[0] == self._proc_mod.MSG_UTILITY_REPLY:
                # Stray reply from an abandoned utility collection (a
                # peer death interrupted it mid-way — e.g. a weight
                # re-seed cut short by chaos): drop it rather than let
                # it crash the output-stream assert below.
                logger.debug(
                    "dropping stray utility reply: %s",
                    self._serial.decode(frames[1]),
                )
                continue
            break
        self._last_progress = time.monotonic()
        assert frames[0] == self._proc_mod.MSG_OUTPUTS, frames[0]
        outputs: EngineCoreOutputs = self._serial.decode(frames[1])
        for o in outputs.outputs:
            if o.finish_reason is not None:
                self._on_finished(o.req_id)
        return outputs

    def engine_status(self) -> dict:
        return self._supervisor.status()

    def is_ready(self) -> bool:
        return not self._dead and self._supervisor.all_up()

    def _collect_utility_replies(
        self, method: str, count: int, timeout_ms: int
    ) -> list[dict]:
        """Read ``count`` UTILITY_REPLY frames, buffering interleaved
        outputs. ALWAYS drains all ``count`` replies (stray replies left on
        the shared socket would crash the next get_output)."""
        replies: list[dict] = []
        for _ in range(100_000):
            if len(replies) == count:
                break
            frames = self._recv(timeout_ms=timeout_ms)
            if frames is None:
                break
            if frames[0] == self._proc_mod.MSG_UTILITY_REPLY:
                replies.append(self._serial.decode(frames[1]))
            else:
                self._pending.append(frames)
        if len(replies) != count:
            raise EngineDeadError(
                f"utility call {method}: {len(replies)}/{count} replies"
            )
        errors = [r["error"] for r in replies if "error" in r]
        if errors:
            raise RuntimeError(
                f"engine utility {method} failed: {'; '.join(errors)}"
            )
        return replies

    # -- engine-core utility surface (same signatures on every client) --

    def _utility(self, method: str, *args, timeout_ms: int = 600_000):
        raise NotImplementedError

    def _on_finished(self, req_id: str) -> None:
        raise NotImplementedError

    def _teardown(self, sockets: list) -> None:
        """Shared shutdown tail: SHUTDOWN + join/terminate every engine
        proc, close sockets, remove the ipc dir."""
        try:
            for sock, proc in zip(self._inputs, self._procs):
                if proc.is_alive():
                    sock.send_multipart([self._proc_mod.MSG_SHUTDOWN])
            for proc in self._procs:
                proc.join(timeout=5)
                if proc.is_alive():
                    proc.terminate()
                    proc.join(timeout=2)
        except Exception:
            pass
        finally:
            for sock in sockets:
                sock.close(linger=0)
            self._ctx.term()
            self._procs = []
            import shutil

            shutil.rmtree(self._run_dir, ignore_errors=True)

    def reset_prefix_cache(self) -> bool:
        return self._utility("reset_prefix_cache", timeout_ms=30_000)

    def set_brownout_rung(self, rung: int) -> bool:
        # DPLB's _utility broadcasts, so the rung reaches every engine
        # in the pool with one call.
        return self._utility("set_brownout_rung", rung, timeout_ms=30_000)

    def set_qos_enabled(self, enabled: bool) -> bool:
        return self._utility("set_qos_enabled", enabled, timeout_ms=30_000)

    def set_config(self, updates: dict) -> dict:
        # DPLB's _utility broadcasts: one call applies the vetted live
        # knobs pool-wide (every UP engine, gated newcomers included).
        return self._utility("set_config", updates, timeout_ms=60_000)

    def engine_versions(self) -> dict:
        """Per-engine /health ``version`` blocks, keyed by engine id."""
        return {"0": self._utility("version_status", timeout_ms=30_000)}

    def sleep(self, level: int = 1) -> bool:
        return self._utility("sleep", level)

    def wake_up(self) -> bool:
        return self._utility("wake_up")

    def is_sleeping(self) -> bool:
        return self._utility("is_sleeping", timeout_ms=30_000)

    def update_weights(self, path: str) -> bool:
        return self._utility("update_weights", path)

    def receive_weights(self, port: int, timeout: float = 300.0) -> int:
        return self._utility(
            "receive_weights", port, timeout,
            timeout_ms=int(timeout * 1000) + 30_000,
        )

    def reinitialize_distributed(self, new_tp: int) -> bool:
        # Weight resharding + runner rebuild + bucket recompiles.
        return self._utility(
            "reinitialize_distributed", new_tp, timeout_ms=600_000
        )

    def save_sharded_state(self, path: str) -> bool:
        return self._utility("save_sharded_state", path, timeout_ms=600_000)

    def add_lora(self, name: str, path: str) -> bool:
        return self._utility("add_lora", name, path)

    def remove_lora(self, name: str) -> bool:
        return self._utility("remove_lora", name, timeout_ms=30_000)

    def list_loras(self) -> list[str]:
        return self._utility("list_loras", timeout_ms=30_000)

    def start_profile(self, trace_dir: str | None = None,
                      python_tracer_level: int = 0) -> dict:
        return self._utility("start_profile", trace_dir, python_tracer_level,
                             timeout_ms=30_000)

    def stop_profile(self) -> dict:
        return self._utility("stop_profile", timeout_ms=60_000)

    def kv_fabric_status(self) -> dict:
        return self._utility("kv_fabric_status", timeout_ms=60_000)


class MPClient(_ZMQClientBase):
    """Engine core in a spawned process, msgpack over ipc ZMQ sockets."""

    def __init__(self, config: EngineConfig, ready_timeout_s: float = 600.0):
        import multiprocessing

        import zmq

        from vllm_tpu.engine import core_proc, serial_utils

        self._serial = serial_utils
        self._proc_mod = core_proc
        self._mp_ctx = multiprocessing.get_context("spawn")
        self._run_dir = run_dir = tempfile.mkdtemp(prefix="vllm-tpu-ipc-")
        suffix = uuid.uuid4().hex[:8]
        input_addr = f"ipc://{run_dir}/input-{suffix}.sock"
        output_addr = f"ipc://{run_dir}/output-{suffix}.sock"
        self._output_addr = output_addr

        self._resilience = config.resilience_config
        self._supervisor = EngineSupervisor(self._resilience, 1)
        self._started = False
        self._ready_timeout_s = ready_timeout_s
        # Same bytes respawn the engine with the same config.
        self._config_bytes = pickle.dumps(config)

        self._ctx = zmq.Context(1)
        self._output = self._ctx.socket(zmq.PULL)
        self._output.bind(output_addr)
        self._input = self._ctx.socket(zmq.PUSH)
        self._input.bind(input_addr)
        from vllm_tpu.tracing import startup

        # From the spawn to READY the engine's process keeps the start's
        # timeline, from its own creation on: not this process's time.
        startup.begin(None)
        self._proc = self._spawn_proc(input_addr)
        self._procs = [self._proc]
        self._inputs = [self._input]
        atexit.register(self.shutdown)

        self._dead = False
        # Live request ids (id-keyed so an abort racing an in-flight
        # engine-side finish record cannot double-count).
        self._live: set[str] = set()
        self._pending: list[list[bytes]] = []  # OUT frames read early
        self._last_progress = time.monotonic()
        # Block until the engine proc finishes init (model load + KV
        # sizing + warm-up can take minutes on first compile).
        frames = self._recv(timeout_ms=int(ready_timeout_s * 1000))
        startup.end()
        if frames is None or frames[0] != core_proc.MSG_READY:
            raise EngineDeadError(
                "engine core process failed to initialize"
            )
        ready = serial_utils.decode(frames[1])
        # Version handshake: an engine proc from a different schema
        # generation (rolling binary upgrade gone sideways, stale ipc
        # leftovers) must be refused at attach — one clean typed error
        # beats a misparsed frame three messages later.
        check_schema("ready", ready.get("schema"),
                     detail=f"engine proc pid {self._proc.pid}")
        config.cache_config.num_gpu_blocks = ready["num_gpu_blocks"]
        self._num_gpu_blocks = ready["num_gpu_blocks"]
        self._started = True
        logger.info(
            "engine core proc up (pid %s, %d KV blocks)",
            self._proc.pid, ready["num_gpu_blocks"],
        )

    def _spawn_proc(self, input_addr: str):
        proc = self._mp_ctx.Process(
            target=self._proc_mod.run_engine_core,
            args=(self._config_bytes, input_addr, self._output_addr),
            name="vllm-tpu-engine-core",
            daemon=True,
        )
        proc.start()
        return proc

    # -- crash recovery ------------------------------------------------

    def _respawn_engine(self, engine_id: int) -> list[str]:
        """Blocking respawn of THE engine: backoff, fresh input socket,
        relaunch, wait for READY (retrying under the restart budget if
        the replacement dies during init). Single-engine client — there
        is nothing else to serve while the engine is down, so blocking
        here is the right trade."""
        import zmq

        lost = sorted(self._live)
        self._live.clear()
        self._join_dead_proc()
        self._drain_stale_outputs(set(lost))
        while True:
            if self._closing:
                self._dead = True
                raise EngineDeadError(
                    "engine core died during shutdown/drain; not respawning"
                )
            time.sleep(self._supervisor.backoff_s(0))
            # Fresh input socket per attempt: the dead incarnation's
            # queued input frames must not reach the replacement, and a
            # terminated proc can leave the ipc file behind.
            self._input.close(linger=0)
            suffix = uuid.uuid4().hex[:8]
            input_addr = f"ipc://{self._run_dir}/input-{suffix}.sock"
            self._input = self._ctx.socket(zmq.PUSH)
            self._input.bind(input_addr)
            self._inputs = [self._input]
            self._proc = self._spawn_proc(input_addr)
            self._procs = [self._proc]
            timeout_s = (
                self._resilience.respawn_ready_timeout_s
                or self._ready_timeout_s
            )
            ready = self._await_ready(timeout_s)
            if ready is not None:
                break
            if not self._supervisor.may_restart(0):
                self._supervisor.record_dead(0)
                self._dead = True
                raise EngineDeadError(
                    "engine core failed to re-initialize and exhausted "
                    f"its {self._resilience.max_engine_restarts}-restart "
                    "budget"
                )
            n = self._supervisor.record_failure(0)
            logger.error(
                "respawned engine core died during init (restart %d/%d)",
                n, self._resilience.max_engine_restarts,
            )
            self._join_dead_proc()
        if ready["num_gpu_blocks"] != self._num_gpu_blocks:
            logger.warning(
                "respawned engine core sized %d KV blocks (was %d)",
                ready["num_gpu_blocks"], self._num_gpu_blocks,
            )
        self._supervisor.record_ready(0)
        self._last_progress = time.monotonic()
        logger.info(
            "engine core proc respawned (pid %s); %d in-flight requests "
            "interrupted", self._proc.pid, len(lost),
        )
        return lost

    def _join_dead_proc(self) -> None:
        self._proc.join(timeout=2)
        if self._proc.is_alive():
            self._proc.terminate()
            self._proc.join(timeout=2)

    def _await_ready(self, timeout_s: float) -> dict | None:
        """Wait for the respawned engine's READY, dropping stale frames
        from the previous incarnation. None = this incarnation failed."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self._closing:
                return None
            if self._output.poll(200):
                frames = self._output.recv_multipart()
                if frames[0] == self._proc_mod.MSG_READY:
                    return self._serial.decode(frames[1])
                continue  # stale OUT/UTILREP/DEAD from the old proc
            if not self._proc.is_alive():
                return None
        return None

    def _on_engine_ready(self, payload: dict) -> None:
        # The blocking respawn consumes READY itself; one arriving here
        # is a late duplicate — just mark the engine up.
        self._supervisor.record_ready(0)

    def _engines_with_work(self) -> list[int]:
        return [0] if self._live else []

    # ------------------------------------------------------------------

    def add_request(self, req: EngineCoreRequest) -> None:
        self._check_alive()
        # The trace id crosses the process boundary inside the encoded
        # request; this instant marks the frontend side of the hop.
        trace_instant(
            "request_send", req_id=req.request_id, trace_id=req.trace_id,
        )
        # drop = the ADD frame is lost in transit: the request stays live
        # client-side but never reaches the engine (a hang the deadline /
        # heartbeat machinery must catch).
        if fail_point("core_client.send",
                      lambda: f"req={req.request_id}") != "drop":
            self._input.send_multipart(
                [self._proc_mod.MSG_ADD, self._serial.encode(req)]
            )
        self._live.add(req.request_id)

    def abort_requests(self, request_ids: list[str]) -> None:
        if self._dead or not request_ids:
            return
        self._input.send_multipart(
            [self._proc_mod.MSG_ABORT, self._serial.encode(list(request_ids))]
        )
        # Aborted requests produce no further outputs.
        for rid in request_ids:
            self._live.discard(rid)

    def _on_finished(self, req_id: str) -> None:
        self._live.discard(req_id)

    def has_unfinished_requests(self) -> bool:
        return bool(self._live)

    def _utility(self, method: str, *args, timeout_ms: int = 600_000):
        """Blocking engine-core method call over the socket pair."""
        self._check_alive()
        self._input.send_multipart([
            self._proc_mod.MSG_UTILITY,
            method.encode(),
            self._serial.encode(list(args)),
        ])
        return self._collect_utility_replies(method, 1, timeout_ms)[0]["ok"]

    @property
    def inflight(self) -> bool:
        # The proc steps autonomously; treat unfinished work as in flight.
        return bool(self._live)

    def shutdown(self) -> None:
        # Halt the respawn path BEFORE touching sockets: a concurrent
        # _handle_engine_death observing the latch fails fast instead of
        # relaunching an engine against sockets mid-teardown.
        self._closing = True
        if getattr(self, "_proc", None) is None:
            return
        self._teardown([self._input, self._output])
        self._proc = None


class DPLBClient(_ZMQClientBase):
    """Data-parallel load-balancing client: N engine-core procs + a
    coordinator proc, least-loaded request routing.

    Reference analog: ``vllm/v1/engine/core_client.py:1317``
    (DPLBAsyncMPClient) + ``coordinator.py``. Each engine PUSHes outputs to
    one shared PULL socket (fan-in); requests are routed per-engine over
    dedicated PUSH sockets. Routing load is tracked client-side only (adds
    minus finishes per engine — exact, since every request passes through
    this client); coordinator snapshots feed the wave state and
    observability, not routing (they cover a subset of the same requests,
    so summing them in would double-count). The client also reports its
    total in-flight count to the coordinator so a request in flight to an
    engine keeps the wave open (the reference attaches wave numbers to
    requests for the same race).
    """

    def __init__(self, config: EngineConfig, ready_timeout_s: float = 600.0):
        import copy
        import multiprocessing

        import zmq

        from vllm_tpu.engine import coordinator, core_proc, serial_utils

        self._serial = serial_utils
        self._proc_mod = core_proc
        pc = config.parallel_config
        self._num_engines = n = pc.data_parallel_engines
        self._resilience = config.resilience_config
        self._supervisor = EngineSupervisor(self._resilience, n)
        self._started = False
        self._ready_timeout_s = ready_timeout_s
        self._run_dir = run_dir = tempfile.mkdtemp(prefix="vllm-tpu-dp-")
        suffix = uuid.uuid4().hex[:8]
        output_addr = f"ipc://{run_dir}/out-{suffix}.sock"
        report_addr = f"ipc://{run_dir}/rep-{suffix}.sock"
        pub_addr = f"ipc://{run_dir}/pub-{suffix}.sock"
        self._output_addr = output_addr

        self._ctx = zmq.Context(1)
        self._output = self._ctx.socket(zmq.PULL)
        self._output.bind(output_addr)
        self._sub = self._ctx.socket(zmq.SUB)
        self._sub.connect(pub_addr)
        self._sub.setsockopt(zmq.SUBSCRIBE, coordinator.TOPIC)
        self._report = self._ctx.socket(zmq.PUSH)
        self._report.connect(report_addr)
        # Bounded-blocking send: a silently dropped FINAL report (count 0)
        # would leave the coordinator's wave open forever with lockstep
        # engines dummy-stepping; 50 ms covers any transient HWM stall
        # without ever meaningfully stalling routing.
        self._report.setsockopt(zmq.SNDTIMEO, 50)

        self._mp_ctx = multiprocessing.get_context("spawn")
        self._coord_args = (report_addr, pub_addr, n)
        self._coord = self._spawn_coordinator()
        # Coordinator failover state: supervised under COORDINATOR_ID
        # (restart budget = max_coordinator_restarts, exponential
        # backoff), respawn timing is NON-blocking — `_coord_respawn_at`
        # holds the earliest next attempt so the busy loop never sleeps.
        self._coord_respawn_at: float | None = None
        self._coord_gave_up = False
        self._coord_epoch: str | None = None
        # Freshness of the last coordinator snapshot; routing degrades to
        # round-robin past coordinator_stale_after_s. Seeded to "fresh at
        # construction" — the first publish lands within the 1 Hz
        # heartbeat.
        self._snapshot_t = time.monotonic()
        self._routing_degraded = False
        self._rr = 0  # round-robin cursor for the degraded path

        # Each engine is a full single-engine config: the per-engine mesh
        # (tp/ep/...) stays as configured; DP fan-out happens here. On a
        # multi-chip TPU host each engine is pinned to a disjoint chip
        # subset (libtpu locks chips per process); multi-host DP instead
        # runs one engine per host with no pinning needed.
        chips_per_engine = pc.world_size
        pin_chips = (
            os.environ.get("JAX_PLATFORMS", "").lower() not in ("cpu",)
            and "TPU_VISIBLE_DEVICES" not in os.environ
        )
        self._inputs = []
        self._procs = []
        self._engine_cfg_bytes: list[bytes] = []
        self._engine_kwargs: list[dict] = []
        kv_endpoints: dict[int, str] = {}
        # Tiered KV fabric in a DP pool: each engine serves its host tier
        # on a pre-assigned loopback port and peers with every other
        # engine's, so a prefix demoted to any engine's host RAM is
        # fetchable pool-wide. Explicit binds/peers in config win.
        fabric_binds: list[str] | None = None
        if (
            config.cache_config.kv_connector == "fabric"
            and n > 1
            and not config.cache_config.kv_fabric_bind
        ):
            import socket as _socket

            picked = []
            for _ in range(n):
                s = _socket.socket()
                s.bind(("127.0.0.1", 0))
                picked.append(s)
            fabric_binds = [
                f"127.0.0.1:{s.getsockname()[1]}" for s in picked
            ]
            for s in picked:
                s.close()
        for eid in range(n):
            engine_config = copy.deepcopy(config)
            engine_config.parallel_config.data_parallel_engines = 1
            # Roles are a pool-level concept the client routes on; each
            # engine proc is a dp=1 pool and would fail the roles/pool
            # size validation in finalize().
            engine_config.parallel_config.engine_roles = None
            if fabric_binds is not None:
                engine_config.cache_config.kv_fabric_bind = (
                    fabric_binds[eid])
                engine_config.cache_config.kv_fabric_peers = [
                    b for i, b in enumerate(fabric_binds) if i != eid
                ]
            ep = engine_config.cache_config.kv_events_endpoint
            if ep and eid > 0:
                # Each engine binds its OWN endpoint; rank 0 keeps the
                # configured address for BOTH schemes (reference offsets
                # the port by DP rank): tcp ports increment, ipc paths
                # get a rank suffix.
                if ep.startswith("tcp://") and ":" in ep.rsplit("/", 1)[-1]:
                    host, port = ep.rsplit(":", 1)
                    engine_config.cache_config.kv_events_endpoint = (
                        f"{host}:{int(port) + eid}"
                    )
                else:
                    engine_config.cache_config.kv_events_endpoint = (
                        f"{ep}.dp{eid}"
                    )
            if engine_config.cache_config.kv_events_endpoint:
                kv_endpoints[eid] = (
                    engine_config.cache_config.kv_events_endpoint
                )
            input_addr = f"ipc://{run_dir}/in{eid}-{suffix}.sock"
            sock = self._ctx.socket(zmq.PUSH)
            sock.bind(input_addr)
            self._inputs.append(sock)
            extra_env = (
                {
                    "TPU_VISIBLE_DEVICES": ",".join(
                        str(c) for c in range(
                            eid * chips_per_engine,
                            (eid + 1) * chips_per_engine,
                        )
                    ),
                }
                if pin_chips
                else {}
            )
            self._engine_cfg_bytes.append(pickle.dumps(engine_config))
            self._engine_kwargs.append(dict(
                engine_id=eid,
                coord_report_addr=report_addr,
                coord_pub_addr=pub_addr,
                lockstep=pc.data_parallel_lockstep,
                extra_env=extra_env,
            ))
            self._procs.append(self._spawn_dp_engine(eid, input_addr))
        atexit.register(self.shutdown)

        # Prefix-cache-aware routing (opt-in via --kv-events-endpoint):
        # SUBscribe to every engine's block-lifecycle stream and place
        # requests on the engine already holding their longest prefix.
        self._prefix_router = None
        self._prefix_index = None
        self._kv_subscriber = None
        self._routing_stats = None
        if kv_endpoints:
            from vllm_tpu.router.policy import PrefixAwareRouter, RoutingStats
            from vllm_tpu.router.prefix_index import (
                KVEventSubscriber,
                PrefixCacheIndex,
            )

            self._prefix_index = PrefixCacheIndex()
            self._kv_subscriber = KVEventSubscriber(
                self._prefix_index, kv_endpoints
            )
            # With the tiered fabric, a spilled request's prefix is
            # fetchable from the owning peer — arm the spillover rung so
            # affinity yields to load balance under imbalance.
            self._prefix_router = PrefixAwareRouter(
                self._prefix_index, config.cache_config.block_size,
                spill_threshold=(
                    int(os.environ.get(
                        "VLLM_TPU_PREFIX_SPILL_THRESHOLD", "4"))
                    if config.cache_config.kv_connector == "fabric"
                    else None),
            )
            self._routing_stats = RoutingStats()

        # Disaggregated prefill/decode (vllm_tpu/disagg): parse the role
        # plan; build the handoff coordinator only when the topology can
        # actually hand off — dedicated capacity on both sides AND
        # auto-assigned fabric peer addresses to push KV over. Roles
        # without a coordinator still bias routing (the phase rung).
        self._role_plan = None
        self._disagg = None
        self._disagg_peer_addr: dict[int, str] = {}
        self._block_size = config.cache_config.block_size
        if pc.engine_roles:
            from vllm_tpu import envs
            from vllm_tpu.disagg import DisaggCoordinator, RolePlan

            self._role_plan = RolePlan.from_spec(pc.engine_roles, n)
            if (self._role_plan.active and fabric_binds is not None
                    and not envs.VLLM_TPU_DISABLE_DISAGG):
                self._disagg = DisaggCoordinator(
                    self._role_plan,
                    min_prompt_tokens=pc.disagg_min_prompt_tokens,
                    block_size=self._block_size,
                )
                self._disagg_peer_addr = dict(enumerate(fabric_binds))
            if self._routing_stats is None:
                # Phase-rung decisions must be countable even without
                # prefix-aware routing (no --kv-events-endpoint).
                from vllm_tpu.router.policy import RoutingStats

                self._routing_stats = RoutingStats()

        self._dead = False
        self._live: dict[str, int] = {}  # req_id -> engine_id
        # Exact per-engine in-flight (adds minus finishes seen here) —
        # the routing metric. Coordinator snapshots are kept for the wave
        # state and observability only: they cover a SUBSET of the same
        # requests, so summing them in would double-count.
        self._engine_inflight = [0] * n
        self._coord_loads = [0] * n
        # Last inflight count that failed to send (retried on later calls
        # so a dropped final 0 cannot wedge the wave open).
        self._report_unsent: int | None = None
        self._pending: list[list[bytes]] = []
        # Degraded-mode routing mask: False while a rank is respawning.
        self._engine_up = [True] * n
        # Elastic capacity (vllm_tpu/resilience/autoscale). Slots are
        # append-only: a scale-down retires its slot into ``_removed``
        # (the id is never reused), so every per-engine list stays
        # index-aligned forever. One scale event runs at a time
        # (``_scale_state``); all mutation happens on the frontend's
        # engine-loop thread — the same thread that owns add_request /
        # get_output — so none of this needs locking.
        self._draining: set[int] = set()  # victims finishing their work
        self._seeding: set[int] = set()   # newcomers awaiting weights
        self._removed: set[int] = set()   # retired slots (exited on purpose)
        # Rolling-upgrade health gate: engines that are UP (answer
        # utility probes, receive config broadcasts) but must not
        # receive routed traffic until the gate opens. Rollback retires
        # a gated slot with zero routed requests by construction.
        self._gating: set[int] = set()
        # Version-handshake rejections by kind (feeds the
        # vllm:schema_mismatch_total metric via version_status).
        self.schema_mismatch_total: dict[str, int] = {}
        self._scale_state: dict | None = None
        self._scale_log: list[dict] = []
        self._scale_events_pending: list[dict] = []
        self._drain_durations: list[float] = []
        self._fabric_binds = fabric_binds
        self._ipc_suffix = suffix
        self._pin_chips = pin_chips
        self._last_progress = time.monotonic()
        ready = 0
        blocks: list[int] = []
        deadline_ms = int(ready_timeout_s * 1000)
        while ready < n:
            frames = self._recv(timeout_ms=deadline_ms)
            if frames is None or frames[0] != core_proc.MSG_READY:
                raise EngineDeadError(
                    "DP engine core processes failed to initialize"
                )
            payload = serial_utils.decode(frames[1])
            check_schema(
                "ready", payload.get("schema"),
                detail=f"DP engine {payload.get('engine_id', '?')}")
            blocks.append(payload["num_gpu_blocks"])
            ready += 1
        config.cache_config.num_gpu_blocks = min(blocks)
        self._started = True
        logger.info(
            "%d DP engine cores up (KV blocks per engine: %s)", n, blocks
        )

    def _spawn_coordinator(self):
        from vllm_tpu.engine import coordinator

        proc = self._mp_ctx.Process(
            target=coordinator.run_coordinator,
            args=self._coord_args,
            name="vllm-tpu-dp-coordinator",
            daemon=True,
        )
        proc.start()
        return proc

    def _spawn_dp_engine(self, eid: int, input_addr: str,
                         cfg_bytes: bytes | None = None):
        proc = self._mp_ctx.Process(
            target=self._proc_mod.run_engine_core,
            args=(cfg_bytes if cfg_bytes is not None
                  else self._engine_cfg_bytes[eid], input_addr,
                  self._output_addr),
            kwargs=self._engine_kwargs[eid],
            name=f"vllm-tpu-engine-core-dp{eid}",
            daemon=True,
        )
        proc.start()
        return proc

    # -- crash recovery (degraded-mode serving) ------------------------

    def _respawn_engine(self, engine_id: int) -> list[str]:
        """NON-blocking respawn of one DP rank: the replacement process is
        launched immediately and re-initializes in the background (its
        READY arrives interleaved on the shared output socket), while
        routing excludes the rank — surviving ranks keep serving."""
        import zmq

        eid = engine_id
        st = getattr(self, "_scale_state", None)
        if st is not None and st.get("kind") == "up":
            if eid == st.get("eid"):
                # The seeding newcomer died (chaos mid-re-seed, dummy
                # boot crash): its slot's respawn config keeps the real
                # checkpoint load_format, so the relaunch below IS the
                # checkpoint-reload fallback — mark the event so the
                # replacement's READY joins it without a re-seed.
                st["fallback"] = True
                st["phase"] = "awaiting_fallback"
            elif (eid == st.get("donor")
                    and st.get("phase") == "reseeding"):
                # The re-seed donor died mid-push: the newcomer holds a
                # part-garbage tree. Reboot it from checkpoint; the
                # donor's own recovery proceeds normally below.
                self._abort_reseed(st)
        self._engine_up[eid] = False
        proc = self._procs[eid]
        proc.join(timeout=2)
        if proc.is_alive():
            proc.terminate()
            proc.join(timeout=2)
        lost = sorted(
            rid for rid, e in self._live.items() if e == eid
        )
        for rid in lost:
            del self._live[rid]
        self._engine_inflight[eid] = 0
        if getattr(self, "_disagg", None) is not None:
            # Handoff legs died with the engine: count them recomputed
            # and clear the records — the frontend's journal replay
            # resubmits each request under the same id on a clean slate
            # (prompt + tokens already streamed, budget decremented).
            self._disagg.note_engine_death(lost)
        if getattr(self, "_prefix_index", None) is not None:
            # The replacement boots with an empty prefix cache; waiting
            # for its seq-gap resync would mis-route in the meantime.
            self._prefix_index.drop_engine(eid)
        self._drain_stale_outputs(set(lost))
        # Zero the dead rank's load at the coordinator: a stale nonzero
        # load would hold the wave open with lockstep ranks
        # dummy-stepping until the replacement's first report.
        try:
            self._report.send(
                self._serial.encode({"engine_down": eid})
            )
        except Exception:
            pass
        # Bounded inline backoff (capped low — this blocks routing to the
        # surviving ranks too); the restart budget bounds crash loops.
        time.sleep(min(self._supervisor.backoff_s(eid), 2.0))
        self._inputs[eid].close(linger=0)
        suffix = uuid.uuid4().hex[:8]
        input_addr = f"ipc://{self._run_dir}/in{eid}-{suffix}.sock"
        sock = self._ctx.socket(zmq.PUSH)
        sock.bind(input_addr)
        self._inputs[eid] = sock
        self._procs[eid] = self._spawn_dp_engine(eid, input_addr)
        self._report_inflight()
        logger.info(
            "DP engine core %d respawning in background (pid %s); %d "
            "in-flight requests interrupted; serving degraded on %d/%d "
            "ranks", eid, self._procs[eid].pid, len(lost),
            sum(self._engine_up), self._num_engines,
        )
        return lost

    def _on_engine_ready(self, payload: dict) -> None:
        eid = int(payload.get("engine_id", 0))
        try:
            check_schema("ready", payload.get("schema"),
                         detail=f"DP engine {eid}")
        except SchemaVersionError as exc:
            # A respawned/newcomer engine speaking a different schema
            # must not rejoin the pool: count it, kill the proc, and let
            # the budget-bounded death path decide what happens next
            # (for an upgrade newcomer that means automatic rollback).
            counts = getattr(self, "schema_mismatch_total", None)
            if counts is not None:
                counts["ready"] = counts.get("ready", 0) + 1
            logger.error("%s; refusing attach and terminating the proc",
                         exc)
            proc = self._procs[eid]
            if proc.is_alive():
                proc.terminate()
            return
        if eid in getattr(self, "_seeding", ()):
            # Scale-up newcomer: NOT routable yet. A dummy-weights boot
            # waits for the peer re-seed (poll_scale drives it off the
            # phase latch); a checkpoint-fallback respawn joins at once
            # — it already holds real weights.
            st = self._scale_state
            if st is not None and st.get("eid") == eid:
                if st.get("fallback"):
                    self._finish_scale_up(
                        eid,
                        outcome=(st.get("ready_outcome")
                                 or "fallback_checkpoint"))
                else:
                    st["phase"] = "ready_for_reseed"
                    logger.info(
                        "scale-up: engine %d booted (dummy weights, %s "
                        "KV blocks); awaiting peer re-seed",
                        eid, payload.get("num_gpu_blocks", -1),
                    )
                return
            # No live event claims this seeding slot (the event was
            # abandoned): retire it rather than serve dummy weights.
            self._retire_slot(eid, outcome="orphaned")
            return
        self._engine_up[eid] = True
        self._supervisor.record_ready(eid)
        logger.info(
            "DP engine core %d recovered (%d KV blocks); %d/%d ranks up",
            eid, payload.get("num_gpu_blocks", -1),
            sum(self._engine_up), self._num_engines,
        )

    def _engines_with_work(self) -> list[int]:
        return [
            i for i, c in enumerate(self._engine_inflight)
            if c > 0 and self._engine_up[i]
        ]

    def _dead_proc_ids(self) -> list[int]:
        # Retired slots exited on purpose — not deaths.
        removed = getattr(self, "_removed", ())
        return [
            i for i, p in enumerate(self._procs)
            if i not in removed and not p.is_alive()
        ]

    def _handle_engine_death(self, engine_ids: list[int],
                             reason: str,
                             suspects: list[str] | None = None) -> None:
        """Route drain victims around the restart budget: a victim that
        dies mid-drain was leaving anyway, so its death must never
        consume restart budget — nor, budget-exhausted, kill the whole
        pool. Retire the slot and hand its in-flight requests straight
        to journal replay; any OTHER dead engine in the same batch takes
        the normal respawn path (its raise carries both lost sets).

        A routing-gated upgrade newcomer gets the same treatment with a
        different meaning: it serves no routed traffic, so its death
        retires the slot with zero lost requests and the rolling
        controller reads the removal as an automatic rollback — the old
        engine was never masked."""
        recovering = (self._started and not self._closing
                      and self._resilience.enable_recovery)
        victims = [
            e for e in engine_ids
            if e in getattr(self, "_draining", ())
        ] if recovering else []
        newcomers = [
            e for e in engine_ids
            if e in getattr(self, "_gating", ()) and e not in victims
        ] if recovering else []
        if not victims and not newcomers:
            return super()._handle_engine_death(
                engine_ids, reason, suspects)
        lost: list[str] = []
        for eid in victims:
            logger.warning(
                "engine %d died while draining (%s); finalizing its "
                "retirement instead of respawning",
                eid, reason.splitlines()[0],
            )
            lost.extend(self._retire_slot(eid, outcome="died_draining"))
        for eid in newcomers:
            logger.warning(
                "upgrade newcomer %d died before its gate opened (%s); "
                "retiring the slot — the old engine keeps serving",
                eid, reason.splitlines()[0],
            )
            lost.extend(
                self._retire_slot(eid, outcome="upgrade_newcomer_died"))
        handled = victims + newcomers
        rest = [e for e in engine_ids if e not in handled]
        if rest:
            try:
                super()._handle_engine_death(rest, reason, suspects)
            except EngineRestartedError as e:
                e.lost_req_ids = sorted({*e.lost_req_ids, *lost})
                raise
        raise EngineRestartedError(
            lost, engine_id=handled[0],
            reason=("engine died while draining (autoscale)" if victims
                    else "upgrade newcomer died before its gate opened"),
            suspect_req_ids=[],
        )

    # ------------------------------------------------------------------

    def _drain_loads(self) -> None:
        """Record coordinator snapshots (wave state / observability) and
        track their freshness + the coordinator's incarnation epoch."""
        while self._sub.poll(0):
            frames = self._sub.recv_multipart()
            state = self._serial.decode(frames[1])
            for eid_s, (w, r) in state["loads"].items():
                e = int(eid_s)
                if e < len(self._coord_loads):
                    self._coord_loads[e] = w + r
            self._snapshot_t = time.monotonic()
            self._supervisor.record_ready(COORDINATOR_ID)
            epoch = state.get("epoch")
            if epoch != self._coord_epoch:
                if self._coord_epoch is not None:
                    # A coordinator we did not respawn ourselves (or one
                    # whose READY beat our liveness check) came up fresh:
                    # re-seed its view of the client's in-flight count.
                    self._report_unsent = len(self._live)
                self._coord_epoch = epoch

    def _check_coordinator(self) -> None:
        """Coordinator failover. The coordinator is supervision, not the
        data path: if it dies, respawn it (a dead coordinator would
        otherwise silently freeze the wave state and leave lockstep ranks
        dummy-stepping forever) — under the supervisor's backoff schedule
        and max_coordinator_restarts budget, never blocking the busy loop
        (the next attempt time is latched in ``_coord_respawn_at``). Past
        the budget the client stops respawning and keeps serving on the
        stale-snapshot degraded path (round-robin routing)."""
        if self._closing or self._coord_gave_up:
            return
        if self._coord.is_alive():
            return
        now = time.monotonic()
        if self._coord_respawn_at is None:
            # First observation of this death: consume budget, schedule.
            if not self._supervisor.may_restart_coordinator():
                self._coord_gave_up = True
                logger.error(
                    "DP coordinator died (exit %s) and exhausted its "
                    "%d-restart budget; serving degraded (round-robin "
                    "routing, no wave lockstep)",
                    self._coord.exitcode,
                    self._resilience.max_coordinator_restarts,
                )
                return
            n = self._supervisor.record_failure(COORDINATOR_ID)
            backoff = self._supervisor.backoff_s(COORDINATOR_ID)
            self._coord_respawn_at = now + backoff
            logger.warning(
                "DP coordinator died (exit %s); respawn %d/%d in %.1fs",
                self._coord.exitcode, n,
                self._resilience.max_coordinator_restarts, backoff,
            )
        if now < self._coord_respawn_at:
            return
        self._coord_respawn_at = None
        self._coord = self._spawn_coordinator()
        # Re-seed the fresh coordinator's client view; engines re-report
        # on their own when they observe the new incarnation's epoch.
        self._report_unsent = len(self._live)
        logger.info(
            "DP coordinator respawned (pid %s, restart %d)",
            self._coord.pid, self._supervisor.restarts(COORDINATOR_ID),
        )

    def _snapshot_stale(self) -> bool:
        return (
            time.monotonic() - self._snapshot_t
            > self._resilience.coordinator_stale_after_s
        )

    def routing_status(self, drain: bool = False) -> dict | None:
        """Routing-decision counters + index health for /metrics and
        /health, or None when prefix-aware routing is not configured.
        ``drain=True`` (metrics renderer only) hands over the pending
        prefix-hit lengths for histogram observation."""
        if getattr(self, "_routing_stats", None) is None:
            return None
        status = self._routing_stats.snapshot(drain=drain)
        if getattr(self, "_prefix_index", None) is not None:
            status["index"] = self._prefix_index.status()
        return status

    def coordinator_status(self) -> dict:
        """JSON-shaped snapshot for /health /metrics (control-plane view:
        never part of data-plane readiness). routing_degraded is computed
        live — "a request arriving now would be round-robin routed" —
        not echoed from the last routing decision, so an outage is
        visible even on an idle frontend."""
        return {
            "up": self._coord.is_alive(),
            "restarts": self._supervisor.restarts(COORDINATOR_ID),
            "snapshot_age_s": time.monotonic() - self._snapshot_t,
            "routing_degraded": self._snapshot_stale(),
        }

    def _report_inflight(self) -> None:
        """Tell the coordinator how many requests this client has live, so
        requests in flight to an engine keep the wave open. A failed send
        (50 ms SNDTIMEO) is retried on later calls — dropping the final
        count-0 report would wedge the wave open with lockstep engines
        dummy-stepping forever."""
        self._report_unsent = len(self._live)
        self._flush_report()

    def _flush_report(self) -> None:
        # Liveness check runs unconditionally: coordinator death must be
        # noticed (and the respawn scheduled) even with nothing to send.
        self._check_coordinator()
        if self._report_unsent is None:
            return
        try:
            self._report.send(self._serial.encode(
                {"client_inflight": self._report_unsent}
            ))
            self._report_unsent = None
        except Exception:
            pass  # keep _report_unsent; retried on the next call

    def add_request(self, req: EngineCoreRequest) -> None:
        self._check_alive()
        self._drain_loads()
        # Degraded mode: route around ranks that are respawning, and
        # around autoscale drain victims (their in-flight work finishes
        # but no NEW work lands). If every rank is down (mass-crash
        # window), fall back — first to draining-but-alive ranks, then
        # to every non-retired slot: the bind side of the fresh input
        # socket buffers the add until the replacement connects, so
        # nothing is dropped.
        draining = getattr(self, "_draining", ())
        removed = getattr(self, "_removed", ())
        gating = getattr(self, "_gating", ())
        candidates = [
            i for i in range(self._num_engines)
            if self._engine_up[i] and i not in draining
            and i not in gating
        ] or [
            i for i in range(self._num_engines) if self._engine_up[i]
        ] or [
            i for i in range(self._num_engines) if i not in removed
        ]
        # Coordinator-snapshot freshness gates the routing policy: fresh
        # -> least-loaded on the client-side exact counters; stale (the
        # coordinator is gone or wedged past coordinator_stale_after_s)
        # -> round-robin. The exact counters are client-local and stay
        # correct without the coordinator, but a stale global view means
        # engine-side conditions (wave state, a rank quietly wedged) are
        # invisible — spreading uniformly is the conservative choice, and
        # the flip doubles as the degraded-routing signal for /metrics.
        stale = self._snapshot_stale()
        if stale != self._routing_degraded:
            self._routing_degraded = stale
            logger.warning(
                "coordinator snapshot %s; %s routing",
                "stale" if stale else "fresh again",
                "round-robin" if stale else "least-loaded",
            )
        # Disaggregated handoff: an eligible new request becomes a
        # max_tokens=1 prefill leg tagged with a decode peer's fabric
        # address; the finish interception in get_output migrates it.
        # A resume leg (pending handoff, resumed) routes as decode.
        phase_hint = None
        disagg = getattr(self, "_disagg", None)
        if disagg is not None:
            ph = disagg.pending(req.request_id)
            if ph is not None and ph.resumed:
                phase_hint = "decode"
            elif ph is None and disagg.eligible(req):
                req, phase_hint = self._disagg_begin(req)
        # Rung 0 (role-aware pools): narrow to the engines serving this
        # request's phase; long prompts land on prefill capacity, so
        # decode engines keep their batches dense.
        if getattr(self, "_role_plan", None) is not None:
            from vllm_tpu.router.policy import phase_rung

            candidates, pk = phase_rung(
                self._role_plan, req, candidates, self._block_size,
                phase=phase_hint,
            )
            if pk is not None and self._routing_stats is not None:
                self._routing_stats.note_phase(pk)
        # Routing ladder: prefix hit > least-loaded > round-robin. The
        # prefix index is fed DIRECTLY by engine kv_events (not via the
        # coordinator), so prefix placement stays valid even when the
        # load snapshot is stale.
        decision = None
        # getattr: unit tests build clients bare via __new__ without the
        # optional routing attributes (the FakeClient idiom).
        if getattr(self, "_prefix_router", None) is not None:
            decision = self._prefix_router.choose(
                req, candidates,
                {i: self._engine_inflight[i] for i in candidates},
            )
        if decision is not None:
            eid = decision.engine_id
        elif stale:
            eid = candidates[self._rr % len(candidates)]
            self._rr += 1
        else:
            eid = min(
                candidates,
                key=lambda i: self._engine_inflight[i],
            )
        if getattr(self, "_routing_stats", None) is not None:
            from vllm_tpu.router.policy import RoutingDecision

            self._routing_stats.note(
                decision if decision is not None else RoutingDecision(
                    eid, "round_robin" if stale else "least_loaded"
                )
            )
        self._live[req.request_id] = eid
        self._engine_inflight[eid] += 1
        if disagg is not None:
            ph = disagg.pending(req.request_id)
            if ph is not None and not ph.resumed:
                ph.record.from_engine = eid
        trace_instant(
            "request_send", req_id=req.request_id, trace_id=req.trace_id,
            engine_id=eid,
        )
        self._report_inflight()  # before the add: wave opens first
        if fail_point("core_client.send",
                      lambda: f"req={req.request_id}") != "drop":
            self._inputs[eid].send_multipart(
                [self._proc_mod.MSG_ADD, self._serial.encode(req)]
            )

    # -- disaggregated prefill/decode handoff --------------------------

    def _disagg_begin(self, req: EngineCoreRequest):
        """Prepare the prefill leg of a handoff: pick the decode target
        (least-loaded dedicated decode engine), reserve its host-tier
        budget, clamp the request to one token. Any obstacle — armed
        ``disagg.handoff`` failpoint, no decode capacity up, no peer
        address — leaves the request unmodified; it serves unified."""
        if fail_point("disagg.handoff",
                      lambda: f"req={req.request_id}") == "drop":
            return req, None
        disagg = self._disagg
        draining = getattr(self, "_draining", ())
        decode_up = [
            i for i in disagg.plan.decode_ids
            if self._engine_up[i] and i not in draining
        ]
        if not decode_up:
            return req, None
        to_engine = min(
            decode_up, key=lambda i: self._engine_inflight[i])
        push_addr = self._disagg_peer_addr.get(to_engine)
        if push_addr is None:
            return req, None
        # No point migrating a request onto the engine that prefilled
        # it: if the only up prefill-phase capacity IS the decode
        # target (the prefill side died), serve unified instead.
        prefill_up = [
            i for i in disagg.plan.candidates_for_phase("prefill")
            if self._engine_up[i] and i != to_engine
            and i not in draining
        ]
        if not prefill_up:
            return req, None
        leg = disagg.begin(
            req, from_engine=-1, to_engine=to_engine,
            push_addr=push_addr)
        # Reserve decode-side KV budget BEFORE the prefill leg is sent,
        # so a demotion burst on the decode engine can't strand the
        # half-shipped prefix. Best-effort: a failed reservation only
        # weakens eviction protection, never the handoff.
        try:
            self._utility_on(
                to_engine, "disagg_reserve", req.request_id,
                disagg.reserve_blocks_for(req), timeout_ms=10_000)
        except Exception as exc:
            logger.debug(
                "disagg reserve on engine %d failed (%s); pushing "
                "unreserved", to_engine, exc)
        return leg, "prefill"

    def _disagg_process(
        self, outputs: EngineCoreOutputs
    ) -> EngineCoreOutputs:
        """Migrate handoffs at the output seam: a clamped prefill leg's
        "length" finish is swallowed (its first token still streams) and
        the request re-adds on the decode target; the decode leg's first
        output classifies whether the pushed KV landed."""
        disagg = self._disagg
        resumes = []
        for o in outputs.outputs:
            ph = disagg.pending(o.req_id)
            if ph is None:
                continue
            if not ph.resumed:
                if o.finish_reason is None:
                    # A token can stream before the finish frame; bank
                    # it for the resume prompt.
                    ph.record.emitted_token_ids.extend(o.new_token_ids)
                    continue
                resume = disagg.note_prefill_finished(
                    o.req_id, list(o.new_token_ids), o.finish_reason)
                if resume is not None:
                    # One stream, two engines: the frontend must not see
                    # this leg boundary.
                    o.finish_reason = None
                    o.stop_reason = None
                    resumes.append(resume)
            else:
                if o.new_token_ids or o.finish_reason is not None:
                    disagg.note_decode_first_tokens(
                        o.req_id, o.num_cached_tokens)
                if o.finish_reason is not None:
                    disagg.note_finished(o.req_id)
        for r in resumes:
            self._disagg_resume(r)
        return outputs

    def _disagg_resume(self, req: EngineCoreRequest) -> None:
        """Send the decode leg straight to the engine the KV was pushed
        to (its host tier holds the prefix; the ladder would have to
        rediscover that over the wire). A dead target falls back to the
        normal ladder — any engine can serve it via peer fetch or plain
        recompute."""
        ph = self._disagg.pending(req.request_id)
        eid = ph.record.to_engine if ph is not None else None
        if (eid is None or not self._engine_up[eid]
                or eid in getattr(self, "_draining", ())):
            self.add_request(req)
            return
        self._live[req.request_id] = eid
        self._engine_inflight[eid] += 1
        trace_instant(
            "request_send", req_id=req.request_id,
            trace_id=req.trace_id, engine_id=eid,
        )
        self._report_inflight()
        if fail_point("core_client.send",
                      lambda: f"req={req.request_id}") != "drop":
            self._inputs[eid].send_multipart(
                [self._proc_mod.MSG_ADD, self._serial.encode(req)]
            )

    def disagg_status(self, drain: bool = False) -> dict | None:
        """Handoff-protocol snapshot for /metrics and /health, or None
        when the pool has no engine roles. Mirrors routing_status's
        drain contract: only the metrics renderer drains (durations
        must be observed exactly once by the histogram)."""
        disagg = getattr(self, "_disagg", None)
        if disagg is not None:
            return disagg.status(drain=drain)
        plan = getattr(self, "_role_plan", None)
        if plan is None:
            return None
        return {
            "active": False,
            "roles": list(plan.roles),
            "pending": 0,
            "outcomes": {},
            "durations_s": [],
        }

    # -- elastic capacity (autoscale execution layer) -------------------

    def _routable_ids(self) -> list[int]:
        """Engines a new request may land on right now."""
        return [
            i for i in range(self._num_engines)
            if self._engine_up[i] and i not in self._draining
            and i not in self._gating
        ]

    def _broadcast_best_effort(self, method: str, *args,
                               skip: int | None = None) -> None:
        """Fire ``method`` at every routable engine, swallowing per-
        engine failures: fabric peer-list edits are advisory — a missed
        removal only costs one failed fetch later."""
        for i in self._routable_ids():
            if i == skip:
                continue
            try:
                self._utility_on(i, method, *args, timeout_ms=30_000)
            except Exception as exc:
                logger.debug("%s on engine %d failed: %s",
                             method, i, exc)

    def _note_scale_event(self, direction: str, outcome: str,
                          duration_s: float,
                          reseed: str | None = None) -> None:
        ev: dict = {
            "direction": direction, "outcome": outcome,
            "duration_s": round(duration_s, 3),
        }
        if reseed is not None:
            ev["reseed"] = reseed
        self._scale_log.append(ev)
        self._scale_events_pending.append(ev)

    def _drain_scale_events(self) -> list[dict]:
        evs, self._scale_events_pending = self._scale_events_pending, []
        return evs

    def scale_up(self, checkpoint: str | None = None,
                 config_overrides: dict | None = None,
                 from_disk: bool = False,
                 gating: bool = False) -> int | None:
        """Begin adding one engine to the pool (non-blocking).

        The newcomer boots with ``load_format="dummy"`` — allocated,
        garbage weights, NO checkpoint read on the hot path — and stays
        masked from routing (``_seeding``) until :meth:`poll_scale`
        re-seeds its weights from a live peer over the streaming
        weight-transfer push. Its slot's respawn config keeps the real
        checkpoint ``load_format``, so any crash (or a failed re-seed)
        degrades to the existing recovery path: respawn from checkpoint.
        Returns the new engine id, or None when no event can start
        (one scale event at a time).

        Rolling-upgrade variants: ``checkpoint`` boots the replacement
        on *new* weights (forces a disk load — peers hold the old
        weights, so donor re-seed would defeat the upgrade);
        ``config_overrides`` applies dotted-path engine config changes
        (validated before spawn); ``from_disk`` skips the peer re-seed
        even without a new checkpoint; ``gating`` keeps the newcomer
        routing-masked after it joins — up and utility-reachable for
        health probes, but serving nothing until :meth:`open_gate`."""
        import copy
        import socket as _socket

        import zmq

        if (self._scale_state is not None or self._closing
                or self._dead or not self._started):
            return None
        if self._pin_chips:
            # Chip pinning partitions a fixed host inventory at launch;
            # there is no spare chip set to pin a newcomer to.
            logger.warning(
                "scale_up refused: engines are chip-pinned "
                "(fixed host chip inventory)")
            return None
        eid = len(self._procs)
        engine_config = pickle.loads(self._engine_cfg_bytes[0])
        if checkpoint is not None:
            engine_config.model_config.model = checkpoint
            from_disk = True
        if config_overrides:
            # Raises on an unknown path — before any slot state mutates
            # or any process spawns.
            _apply_config_overrides(engine_config, config_overrides)
        new_bind = None
        if self._fabric_binds is not None:
            s = _socket.socket()
            s.bind(("127.0.0.1", 0))
            new_bind = f"127.0.0.1:{s.getsockname()[1]}"
            s.close()
            self._fabric_binds.append(new_bind)
            engine_config.cache_config.kv_fabric_bind = new_bind
            engine_config.cache_config.kv_fabric_peers = [
                b for i, b in enumerate(self._fabric_binds)
                if i != eid and i not in self._removed
            ]
        # The kv-events subscriber set is fixed at construction: the
        # newcomer publishes no events (no prefix affinity) and serves
        # via the phase/load rungs instead.
        engine_config.cache_config.kv_events_endpoint = None
        self._engine_cfg_bytes.append(pickle.dumps(engine_config))
        self._engine_kwargs.append(dict(
            engine_id=eid,
            coord_report_addr=self._coord_args[0],
            coord_pub_addr=self._coord_args[1],
            lockstep=self._engine_kwargs[0]["lockstep"],
            extra_env={},
        ))
        boot_config = copy.deepcopy(engine_config)
        if not from_disk:
            boot_config.model_config.load_format = "dummy"
        input_addr = (
            f"ipc://{self._run_dir}/in{eid}-{self._ipc_suffix}.sock"
        )
        sock = self._ctx.socket(zmq.PUSH)
        sock.bind(input_addr)
        self._inputs.append(sock)
        self._engine_inflight.append(0)
        self._coord_loads.append(0)
        self._engine_up.append(False)
        self._seeding.add(eid)
        if gating:
            self._gating.add(eid)
        self._num_engines += 1
        self._procs.append(self._spawn_dp_engine(
            eid, input_addr, cfg_bytes=pickle.dumps(boot_config)))
        self._scale_state = {
            "kind": "up", "eid": eid, "phase": "spawning",
            "t0": time.monotonic(), "bind": new_bind, "donor": None,
            # from_disk boots real weights: its READY joins directly via
            # the fallback branch (no re-seed round-trip).
            "fallback": from_disk,
            "ready_outcome": "from_disk" if from_disk else None,
        }
        logger.info(
            "scale-up: engine %d spawning (pid %s, %s)%s",
            eid, self._procs[eid].pid,
            f"checkpoint {checkpoint}" if checkpoint is not None
            else ("disk load" if from_disk
                  else "dummy weights; peer re-seed to follow"),
            "; routing gated" if gating else "")
        return eid

    def scale_down(self, engine_id: int | None = None) -> int | None:
        """Begin a graceful drain of one engine (non-blocking). The
        victim is masked from routing immediately; :meth:`poll_scale`
        retires the slot once its in-flight requests finish (demoting
        its hot host-tier KV to peers first), or journal-replays the
        stragglers onto survivors past ``autoscale_drain_deadline_s``.
        Returns the victim id, or None when no event can start."""
        if (self._scale_state is not None or self._closing
                or self._dead or not self._started):
            return None
        cands = self._routable_ids()
        if engine_id is not None:
            if engine_id not in cands or len(cands) <= 1:
                return None
            victim = engine_id
        else:
            if len(cands) <= 1:
                return None
            # Highest id: keeps the dense low-id prefix (and with it
            # the original chip pinning / role layout) intact.
            victim = max(cands)
        self._draining.add(victim)
        self._scale_state = {
            "kind": "down", "eid": victim, "phase": "draining",
            "t0": time.monotonic(),
        }
        logger.info(
            "scale-down: engine %d draining (%d in flight, deadline "
            "%.0fs)", victim, self._engine_inflight[victim],
            self._resilience.autoscale_drain_deadline_s)
        return victim

    def rebalance_role(self, engine_id: int, role: str) -> bool:
        """Convert one engine's role (prefill/decode/unified) via a
        short drain: the engine is masked from routing until its
        current work finishes, then the role plan flips. No process
        restart — roles are a client-side routing concept (every engine
        proc runs role-free)."""
        if role not in ("prefill", "decode", "unified"):
            raise ValueError(f"unknown engine role: {role}")
        if (self._scale_state is not None or self._closing
                or self._dead or not self._started
                or getattr(self, "_role_plan", None) is None
                or engine_id not in self._routable_ids()
                or self._role_plan.roles[engine_id] == role):
            return False
        self._draining.add(engine_id)
        self._scale_state = {
            "kind": "rebalance", "eid": engine_id, "phase": "draining",
            "t0": time.monotonic(), "role": role,
        }
        logger.info(
            "rebalance: engine %d draining for re-role %s -> %s",
            engine_id, self._role_plan.roles[engine_id], role)
        return True

    def poll_scale(self) -> list[dict]:
        """Advance the in-flight scale event (if any) one step and hand
        back completed-event records for the controller's counters.
        Called from the frontend's engine loop — the thread that owns
        add_request/get_output, so no locking. The re-seed round-trip
        is the one blocking stretch: weights stream peer-to-peer
        (seconds), never from a checkpoint."""
        st = self._scale_state
        if st is None or self._closing or self._dead:
            return self._drain_scale_events()
        now = time.monotonic()
        if st["kind"] == "up":
            eid = st["eid"]
            if st["phase"] == "ready_for_reseed":
                self._start_reseed(st)
            elif (now - st["t0"]
                    > self._resilience.autoscale_reseed_timeout_s
                    and st["phase"] in ("spawning", "awaiting_fallback")):
                # Newcomer never became seedable (wedged boot, repeated
                # fallback crashes): give the slot up.
                logger.error(
                    "scale-up of engine %d timed out after %.0fs; "
                    "retiring the slot", eid, now - st["t0"])
                self._retire_slot(eid, outcome="timeout")
        elif st["kind"] == "down":
            self._drain_to_retire(st["eid"], st["t0"])
        elif st["kind"] == "rebalance":
            eid = st["eid"]
            deadline = (now - st["t0"]
                        > self._resilience.autoscale_drain_deadline_s)
            if self._engine_inflight[eid] == 0 or deadline:
                # A role flip needs no empty engine, just a quiet one;
                # past the deadline flip anyway — the phase rung only
                # steers NEW requests, running work is unaffected.
                self._role_plan.roles[eid] = st["role"]
                self._role_plan.__post_init__()
                self._draining.discard(eid)
                self._note_scale_event(
                    "rebalance",
                    "deadline_flip" if deadline else "ok",
                    now - st["t0"])
                self._scale_state = None
                logger.info("engine %d re-roled to %s", eid, st["role"])
        return self._drain_scale_events()

    def _drain_to_retire(self, eid: int, started_t: float,
                         outcome: str = "drained") -> list[str] | None:
        """THE drain-to-retire sequence, shared by every path that ends
        an engine's service on purpose — autoscale scale-down, the
        rolling upgrade's victim drain, and the frontend's SIGTERM drain
        (which retires slots through scale_down + this poll).

        Returns the lost request ids when the slot retired on this call
        (empty on a graceful finish), or None while the drain is still
        in progress. A graceful finish first demotes the victim's hot
        host-tier KV to surviving peers (best-effort). Past
        ``autoscale_drain_deadline_s`` the slot is retired anyway and
        the stragglers journal-replay onto survivors via the raised
        EngineRestartedError — zero lost requests, the same path a crash
        takes, minus the crash."""
        if self._engine_inflight[eid] == 0:
            if self._fabric_binds is not None:
                try:
                    shipped = self._utility_on(
                        eid, "kv_fabric_drain", timeout_ms=60_000)
                    logger.info(
                        "engine %d demoted %s host-tier blocks to "
                        "peers before exit", eid, shipped)
                except Exception as exc:
                    logger.warning(
                        "kv drain on engine %d failed (%s); its "
                        "host tier is lost (recompute covers it)",
                        eid, exc)
            return self._retire_slot(eid, outcome=outcome)
        if (time.monotonic() - started_t
                > self._resilience.autoscale_drain_deadline_s):
            lost = self._retire_slot(eid, outcome="deadline_replay")
            raise EngineRestartedError(
                lost, engine_id=eid,
                reason="drain deadline; replaying stragglers on "
                       "survivors",
                suspect_req_ids=[],
            )
        return None

    def _start_reseed(self, st: dict) -> None:
        """Blocking peer re-seed: the newcomer listens, the least-loaded
        live peer pushes its full param tree over the streaming weight-
        transfer path. On failure the newcomer reboots from its
        checkpoint config — the pool never admits dummy weights."""
        import socket as _socket

        eid = st["eid"]
        donors = [i for i in self._routable_ids() if i != eid]
        if not donors:
            # Nobody to seed from (mass-crash window): checkpoint it.
            self._abort_reseed(st)
            return
        donor = min(donors, key=lambda i: self._engine_inflight[i])
        s = _socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        timeout = self._resilience.autoscale_reseed_timeout_s
        st["phase"] = "reseeding"
        st["donor"] = donor
        logger.info(
            "re-seeding engine %d from peer %d (port %d)",
            eid, donor, port)
        # Receiver first (it binds the listener), pusher second; the
        # pusher's connect loop absorbs the bind race. Raw sends — the
        # newcomer is not "up" so _utility_on would refuse it.
        for target, method, args in (
            (eid, "receive_weights", [port, timeout]),
            (donor, "push_weights_to", ["127.0.0.1", port, timeout]),
        ):
            self._inputs[target].send_multipart([
                self._proc_mod.MSG_UTILITY,
                method.encode(),
                self._serial.encode(args),
            ])
        try:
            self._collect_utility_replies(
                "weight_reseed", 2, int(timeout * 1000) + 30_000)
        except EngineRestartedError:
            raise  # a peer died; _respawn_engine arranged the fallback
        except Exception as exc:
            logger.warning(
                "peer re-seed of engine %d failed (%s); rebooting it "
                "from checkpoint", eid, exc)
            self._abort_reseed(st)
            return
        self._finish_scale_up(eid, outcome="reseeded")

    def _abort_reseed(self, st: dict) -> None:
        """Re-seed cannot complete (donor died mid-push, reseed error,
        no donors): reboot the newcomer from its slot config — which
        keeps the real checkpoint load_format — and let its READY join
        the pool via the fallback branch of _on_engine_ready."""
        import zmq

        nid = st["eid"]
        proc = self._procs[nid]
        if proc.is_alive():
            proc.terminate()
            proc.join(timeout=2)
        self._inputs[nid].close(linger=0)
        suffix = uuid.uuid4().hex[:8]
        input_addr = f"ipc://{self._run_dir}/in{nid}-{suffix}.sock"
        sock = self._ctx.socket(zmq.PUSH)
        sock.bind(input_addr)
        self._inputs[nid] = sock
        self._procs[nid] = self._spawn_dp_engine(nid, input_addr)
        st["fallback"] = True
        st["phase"] = "awaiting_fallback"
        st["t0"] = time.monotonic()  # fresh budget for the reload
        logger.warning(
            "engine %d rebooting from checkpoint (re-seed fallback)",
            nid)

    def _finish_scale_up(self, eid: int, outcome: str) -> None:
        """Join a seeded (or checkpoint-reloaded) newcomer: survivors
        learn its fabric tier, the role plan grows, routing unmasks."""
        st = self._scale_state
        bind = st.get("bind") if st is not None else None
        if getattr(self, "_role_plan", None) is not None:
            while len(self._role_plan.roles) <= eid:
                self._role_plan.roles.append("unified")
            self._role_plan.__post_init__()
        if bind:
            # Survivors learn the newcomer's host tier (the newcomer
            # already has the full peer list baked into its config).
            self._broadcast_best_effort(
                "kv_fabric_add_peer", bind, skip=eid)
            if getattr(self, "_disagg", None) is not None:
                self._disagg_peer_addr[eid] = bind
        self._seeding.discard(eid)
        self._engine_up[eid] = True
        self._supervisor.record_ready(eid)
        dur = time.monotonic() - st["t0"] if st is not None else 0.0
        self._note_scale_event(
            "up", outcome, dur,
            reseed="ok" if outcome == "reseeded" else "fallback")
        self._scale_state = None
        self._report_inflight()
        logger.info(
            "scale-up complete: engine %d joined (%s, %.1fs); pool now "
            "%d routable", eid, outcome, dur, len(self._routable_ids()))

    def _retire_slot(self, eid: int, outcome: str) -> list[str]:
        """Retire one engine slot for good. Terminal: the id is never
        reused, per-engine lists keep their length (index alignment),
        and the slot is masked everywhere via ``_removed``. Returns the
        request ids still live on the slot (non-empty only on a forced
        or chaos retirement) for journal replay."""
        st = self._scale_state
        # BEFORE any proc poke: the victim's exit must not read as a
        # death to the liveness checks.
        self._removed.add(eid)
        self._draining.discard(eid)
        self._seeding.discard(eid)
        self._gating.discard(eid)
        self._engine_up[eid] = False
        proc = self._procs[eid]
        if proc.is_alive():
            # Clean shutdown first; terminate as the backstop.
            try:
                self._inputs[eid].send_multipart(
                    [self._proc_mod.MSG_SHUTDOWN])
            except Exception:
                pass
            proc.join(timeout=10)
            if proc.is_alive():
                proc.terminate()
                proc.join(timeout=2)
        else:
            proc.join(timeout=2)
        lost = sorted(r for r, e in self._live.items() if e == eid)
        for rid in lost:
            del self._live[rid]
        self._engine_inflight[eid] = 0
        if getattr(self, "_disagg", None) is not None:
            self._disagg.note_engine_death(lost)
        if getattr(self, "_prefix_index", None) is not None:
            self._prefix_index.drop_engine(eid)
        self._drain_stale_outputs(set(lost))
        self._disagg_peer_addr.pop(eid, None)
        # Forget the slot entirely: readiness must not wait on a rank
        # that left on purpose.
        self._supervisor.remove(eid)
        try:
            self._report.send(
                self._serial.encode({"engine_down": eid}))
        except Exception:
            pass
        # Survivors forget the retired peer's fabric tier.
        if (self._fabric_binds is not None
                and eid < len(self._fabric_binds)):
            self._broadcast_best_effort(
                "kv_fabric_remove_peer", self._fabric_binds[eid])
        if st is not None and st.get("eid") == eid:
            dur = time.monotonic() - st["t0"]
            if st["kind"] == "down":
                self._drain_durations.append(dur)
            self._note_scale_event(st["kind"], outcome, dur)
            self._scale_state = None
        self._report_inflight()
        logger.info(
            "engine %d retired (%s); pool now %d routable",
            eid, outcome, len(self._routable_ids()))
        return lost

    def pool_status(self, drain: bool = False) -> dict:
        """Elastic-capacity snapshot for /health and /metrics.
        ``drain=True`` (metrics renderer only) hands over the pending
        drain durations for exactly-once histogram observation."""
        st = self._scale_state
        durations = list(self._drain_durations)
        if drain:
            self._drain_durations = []
        return {
            "size": self._num_engines,
            "actual": len(self._routable_ids()),
            "draining": sorted(self._draining),
            "seeding": sorted(self._seeding),
            "gating": sorted(self._gating),
            "removed": sorted(self._removed),
            "scale_event": (
                {
                    "kind": st["kind"], "engine": st["eid"],
                    "phase": st["phase"],
                    "age_s": round(time.monotonic() - st["t0"], 3),
                }
                if st is not None else None
            ),
            "events": list(self._scale_log)[-20:],
            "drain_durations_s": durations,
        }

    # -- rolling-upgrade primitives (resilience/rolling.py executor) ----

    def slot_state(self, eid: int) -> str:
        """"up" | "removed" | "pending" — the upgrade driver's view of
        one slot. "pending" covers spawning/booting/seeding; a retired
        slot is "removed" forever (ids are never reused)."""
        if eid in self._removed:
            return "removed"
        if 0 <= eid < len(self._engine_up) and self._engine_up[eid]:
            return "up"
        return "pending"

    def open_gate(self, eid: int) -> bool:
        """Shift routing onto a gated newcomer: the health gate passed,
        new requests may land on it from the next add_request."""
        if eid not in self._gating:
            return False
        self._gating.discard(eid)
        logger.info(
            "upgrade: routing gate opened for engine %d; pool now %d "
            "routable", eid, len(self._routable_ids()))
        return True

    def retire_engine(self, eid: int,
                      outcome: str = "upgrade_rolled_back") -> list[str]:
        """Roll back / abort: retire one slot outright. For a gated
        newcomer the returned lost list is empty by construction — it
        never received routed traffic — which is exactly the
        "pool byte-identical to pre-upgrade" guarantee."""
        if eid in self._removed:
            return []
        return self._retire_slot(eid, outcome=outcome)

    def probe_engine(self, eid: int, n_tokens: int = 4) -> list[int]:
        """One health-gate probe: a tiny deterministic generation run
        end-to-end inside the gated newcomer (EngineCore.probe). Raises
        on any failure — the raise IS the gate-fail signal. The generous
        timeout covers a first-token compile on a cold cache."""
        return self._utility_on(
            eid, "probe", n_tokens, timeout_ms=600_000)

    def engine_versions(self) -> dict:
        """Per-engine /health ``version`` blocks keyed by engine id
        (package + schema version, config hash, weights fingerprint) —
        a mixed-version pool at a glance, plus this client's schema-
        handshake rejection counts."""
        self._check_alive()
        up = [
            i for i in range(self._num_engines) if self._engine_up[i]
        ]
        if not up:
            return {}
        for eid in up:
            self._inputs[eid].send_multipart([
                self._proc_mod.MSG_UTILITY,
                b"version_status",
                self._serial.encode([]),
            ])
        replies = self._collect_utility_replies(
            "version_status", len(up), 30_000)
        return {
            str(r.get("engine_id", i)): r["ok"]
            for i, r in enumerate(replies) if r.get("ok")
        }

    # ------------------------------------------------------------------

    def _utility_on(
        self, eid: int, method: str, *args, timeout_ms: int = 30_000
    ):
        """Targeted utility call to ONE engine (``_utility``
        broadcasts); used for decode-side handoff reservations."""
        self._check_alive()
        if not self._engine_up[eid]:
            raise RuntimeError(
                f"utility {method}: engine {eid} is restarting")
        self._inputs[eid].send_multipart([
            self._proc_mod.MSG_UTILITY,
            method.encode(),
            self._serial.encode(list(args)),
        ])
        return self._collect_utility_replies(method, 1, timeout_ms)[0]["ok"]

    # ------------------------------------------------------------------

    def abort_requests(self, request_ids: list[str]) -> None:
        if self._dead or not request_ids:
            return
        if getattr(self, "_disagg", None) is not None:
            # A frontend abort (client cancel, stop string detected
            # frontend-side) can land mid-handoff; drop the pending
            # record so the resume leg is never sent.
            for rid in request_ids:
                self._disagg.note_abort(rid)
        by_engine: dict[int, list[str]] = {}
        for rid in request_ids:
            eid = self._live.pop(rid, None)
            if eid is not None:
                self._engine_inflight[eid] -= 1
                by_engine.setdefault(eid, []).append(rid)
        for eid, rids in by_engine.items():
            self._inputs[eid].send_multipart(
                [self._proc_mod.MSG_ABORT, self._serial.encode(rids)]
            )
        self._report_inflight()

    def _on_finished(self, req_id: str) -> None:
        eid = self._live.pop(req_id, None)
        if eid is not None:
            self._engine_inflight[eid] -= 1
            self._report_inflight()

    def get_output(self, timeout: float | None = None) -> EngineCoreOutputs:
        self._drain_loads()  # keep snapshot freshness current when idle
        self._flush_report()  # retry a dropped inflight report
        outputs = super().get_output(timeout)
        if getattr(self, "_disagg", None) is not None and outputs.outputs:
            outputs = self._disagg_process(outputs)
        return outputs

    def has_unfinished_requests(self) -> bool:
        self._flush_report()  # retry a dropped inflight report
        return bool(self._live)

    def _utility(self, method: str, *args, timeout_ms: int = 600_000):
        """Broadcast to all UP engines; returns the lowest engine id's
        result. All replies are drained even on error (stray replies on
        the shared socket would corrupt the output stream). Ranks mid-
        respawn are skipped — they rebuild their state from config on
        READY and cannot answer."""
        self._check_alive()
        up = [
            i for i in range(self._num_engines) if self._engine_up[i]
        ]
        if not up:
            raise RuntimeError(
                f"utility {method}: no engine cores available "
                "(all ranks restarting)"
            )
        for eid in up:
            self._inputs[eid].send_multipart([
                self._proc_mod.MSG_UTILITY,
                method.encode(),
                self._serial.encode(list(args)),
            ])
        replies = self._collect_utility_replies(
            method, len(up), timeout_ms
        )
        replies.sort(key=lambda r: r.get("engine_id", 0))
        return replies[0]["ok"]

    def kv_fabric_status(self) -> dict:
        """Pool-wide fabric snapshot: broadcast to every UP engine and
        merge numeric leaves (counter sums, tier-occupancy totals), with
        the per-engine snapshots preserved under "engines"."""
        self._check_alive()
        up = [
            i for i in range(self._num_engines) if self._engine_up[i]
        ]
        if not up:
            return {}
        for eid in up:
            self._inputs[eid].send_multipart([
                self._proc_mod.MSG_UTILITY,
                b"kv_fabric_status",
                self._serial.encode([]),
            ])
        replies = self._collect_utility_replies(
            "kv_fabric_status", len(up), 60_000
        )
        replies.sort(key=lambda r: r.get("engine_id", 0))
        per_engine = {
            r.get("engine_id", i): r["ok"]
            for i, r in enumerate(replies) if r.get("ok")
        }
        merged: dict = {}
        for snap in per_engine.values():
            merged = _merge_numeric(merged, snap)
        merged["engines"] = {str(k): v for k, v in per_engine.items()}
        return merged

    @property
    def inflight(self) -> bool:
        return bool(self._live)

    def shutdown(self) -> None:
        # Respawn latch first (engines AND coordinator): teardown must
        # never race a background respawn back to life.
        self._closing = True
        if not getattr(self, "_procs", None):
            return
        if getattr(self, "_kv_subscriber", None) is not None:
            try:
                self._kv_subscriber.close()
            except Exception:
                pass
            self._kv_subscriber = None
        try:
            if self._coord.is_alive():
                self._coord.terminate()
                self._coord.join(timeout=2)
        except Exception:
            pass
        self._teardown(
            [*self._inputs, self._output, self._sub, self._report]
        )
