"""EngineCoreProc: the engine core in its own OS process, driven over ZMQ.

Reference analog: ``vllm/v1/engine/core.py:806`` (EngineCoreProc,
run_busy_loop :1164, engine-dead propagation :1358). The process owns the
TPU (jax initializes here, never in the frontend); the frontend talks
msgpack over a pair of ipc sockets. One loop thread serves both sockets:
it drains the input socket (blocking with a timeout when idle, non-blocking
while requests are in flight), steps the core, and pushes outputs.
"""

from __future__ import annotations

import pickle
import traceback

from vllm_tpu.logger import init_logger
from vllm_tpu.tracing import trace_span

# Wire message types (frame 0).
MSG_ADD = b"ADD"
MSG_ABORT = b"ABORT"
MSG_SHUTDOWN = b"SHUTDOWN"
MSG_UTILITY = b"UTIL"
MSG_READY = b"READY"
MSG_OUTPUTS = b"OUT"
MSG_DEAD = b"DEAD"
MSG_UTILITY_REPLY = b"UTILREP"
# Mesh membership/recovery report (multi-host fault tolerance): payload is
# {"status": <mesh status dict>, "lost_req_ids": [...], "reason": str,
# "engine_id": int}. A non-empty lost_req_ids means the engine JUST
# recovered from a mesh shrink/grow and those requests need journal
# replay — the engine itself is alive (no respawn).
MSG_MESH = b"MESH"


def run_engine_core(config_bytes: bytes, input_addr: str,
                    output_addr: "str | list[str]", engine_id: int = 0,
                    coord_report_addr: str | None = None,
                    coord_pub_addr: str | None = None,
                    lockstep: bool = False,
                    extra_env: dict[str, str] | None = None,
                    bind_input: bool = False) -> None:
    """Process entry point (spawn target).

    With ``coord_*`` addresses set this is the DP variant (reference
    ``DPEngineCoreProc``, ``core.py:1622``): the proc reports its load to
    the coordinator after every iteration and, when ``lockstep`` is on,
    runs dummy batches while other DP ranks still have work in the wave.

    Multi-API-server topology (reference: many API servers, one engine
    pool): ``output_addr`` may be a LIST of per-frontend addresses — the
    engine opens one PUSH per frontend and routes each request's outputs
    back to output socket ``request.client_index``; READY/DEAD broadcast
    to every frontend. ``bind_input=True`` flips the input topology: the
    engine BINDS its PULL socket and the N frontends connect PUSH — so
    frontends can come and go (crash/respawn) without the engine caring.
    """
    import os

    # Per-engine device assignment (DP on one multi-chip host: each engine
    # owns a disjoint chip subset) must land before any backend init.
    for k, v in (extra_env or {}).items():
        os.environ[k] = v

    # Honor the parent's platform selection BEFORE any backend init, also
    # where jax was imported before the variable was set (test rigs force
    # the CPU backend this way).
    if os.environ.get("JAX_PLATFORMS"):
        import jax

        jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])

    import zmq

    from vllm_tpu.engine import serial_utils
    from vllm_tpu.engine.engine_core import EngineCore
    from vllm_tpu.plugins import load_general_plugins
    from vllm_tpu.resilience.failpoints import fail_point

    # Spawned interpreters don't inherit the frontend's plugin state:
    # out-of-tree registrations must happen where the model is built.
    load_general_plugins()

    logger = init_logger("vllm_tpu.engine.core_proc")
    ctx = zmq.Context(1)
    inp = ctx.socket(zmq.PULL)
    if bind_input:
        # Engine owns the input endpoint; unlink a stale ipc file left
        # by an uncleanly-killed predecessor (same hygiene as the
        # coordinator and KVEventPublisher).
        if input_addr.startswith("ipc://"):
            try:
                os.unlink(input_addr[len("ipc://"):])
            except OSError:
                pass
        inp.bind(input_addr)
    else:
        inp.connect(input_addr)
    output_addrs = (
        [output_addr] if isinstance(output_addr, str) else list(output_addr)
    )
    outs = []
    for addr in output_addrs:
        sock = ctx.socket(zmq.PUSH)
        sock.connect(addr)
        outs.append(sock)
    out = outs[0]
    # request_id -> frontend output index, for multi-frontend routing.
    req_client: dict[str, int] = {}

    def out_for(req_id: str):
        return outs[req_client.get(req_id, 0) % len(outs)]

    # DP coordinator plumbing (absent for the single-engine path).
    coord_push = coord_sub = None
    if coord_report_addr is not None:
        from vllm_tpu.engine.coordinator import TOPIC

        coord_push = ctx.socket(zmq.PUSH)
        coord_push.connect(coord_report_addr)
        coord_sub = ctx.socket(zmq.SUB)
        coord_sub.connect(coord_pub_addr)
        coord_sub.setsockopt(zmq.SUBSCRIBE, TOPIC)
    last_load: tuple[int, int] | None = None
    global_unfinished = False
    coord_epoch: str | None = None

    def report_load() -> None:
        nonlocal last_load
        if coord_push is None:
            return
        load = core.get_load()
        if load != last_load:
            if fail_point("coordinator.report",
                          lambda: f"engine={engine_id}") == "drop":
                return  # last_load untouched -> retried next iteration
            coord_push.send(serial_utils.encode({
                "engine_id": engine_id,
                "waiting": load[0],
                "running": load[1],
            }))
            last_load = load

    def drain_coordinator() -> None:
        nonlocal global_unfinished, last_load, coord_epoch
        if coord_sub is None:
            return
        while coord_sub.poll(0):
            frames = coord_sub.recv_multipart()
            state = serial_utils.decode(frames[1])
            global_unfinished = bool(state["global_unfinished"])
            epoch = state.get("epoch")
            if epoch != coord_epoch:
                # New coordinator incarnation: it booted with zeroed
                # loads, and change-driven reporting would never resend
                # a steady load. Forget last_load so the next
                # report_load() re-reports unconditionally.
                if coord_epoch is not None:
                    last_load = None
                coord_epoch = epoch

    def send_dead(reason: str, suspects: list[str]) -> None:
        # Third frame identifies WHICH engine died so the DP client's
        # supervisor respawns the right rank; fourth carries the request
        # ids that were in flight at death — the quarantine manager's
        # suspect set for poison-request bisection. Every frontend gets
        # the notice: each must stop routing to this rank.
        for sock in outs:
            sock.send_multipart([
                MSG_DEAD,
                reason.encode(),
                str(engine_id).encode(),
                serial_utils.encode(suspects),
            ])

    def install_watchdog_escalation(engine_core) -> None:
        """Make a step-watchdog trip look like an engine crash.

        The watchdog thread can't reuse ``out`` (ZMQ sockets are not
        thread-safe) so it opens its own PUSH socket for the one dying
        message, then hard-exits: the busy loop is wedged inside the
        device step and will never unwind through the normal exception
        path.
        """
        runner = getattr(
            getattr(engine_core.executor, "worker", None), "runner", None
        )
        watchdog = getattr(runner, "watchdog", None)
        if watchdog is None:
            return

        def escalate(req_ids: list[str], elapsed: float) -> None:
            try:
                suspects = engine_core.suspect_req_ids() or list(req_ids)
            except Exception:
                suspects = list(req_ids)
            try:
                for addr in output_addrs:
                    death = ctx.socket(zmq.PUSH)
                    death.connect(addr)
                    death.send_multipart([
                        MSG_DEAD,
                        (f"device hang: step exceeded "
                         f"{watchdog.timeout_s:.1f}s watchdog deadline "
                         f"(elapsed {elapsed:.1f}s)").encode(),
                        str(engine_id).encode(),
                        serial_utils.encode(suspects),
                    ])
                    death.close(linger=1000)
            except Exception:
                logger.exception("watchdog escalation send failed")
            os._exit(1)

        watchdog.on_trip = escalate

    core = None
    try:
        config = pickle.loads(config_bytes)
        # The start's timeline of THIS process: `import` is its creation
        # to here (the interpreter, the modules above, the sockets), then
        # the engine's phases; READY ends it. The phases cross to the
        # front end with the first stats snapshot.
        from vllm_tpu.tracing import startup

        startup.imported()
        core = EngineCore(config)
        startup.ready()
        install_watchdog_escalation(core)
        from vllm_tpu.versioning import SCHEMA_VERSION
        for sock in outs:
            sock.send_multipart([
                MSG_READY,
                serial_utils.encode(
                    {"num_gpu_blocks": config.cache_config.num_gpu_blocks,
                     "engine_id": engine_id,
                     # Wire handshake: a frontend from a different
                     # schema generation must refuse the attach instead
                     # of misparsing frames later (rolling binary
                     # upgrades make mixed pools a planned state).
                     "schema": SCHEMA_VERSION}
                ),
            ])

        def send_mesh(lost_req_ids: list[str], reason: str) -> None:
            status = core.mesh_status()
            for sock in outs:
                sock.send_multipart([
                    MSG_MESH,
                    serial_utils.encode({
                        "status": status,
                        "lost_req_ids": lost_req_ids,
                        "reason": reason,
                        "engine_id": engine_id,
                    }),
                ])

        def poll_input(timeout_ms: int) -> bool:
            if not timeout_ms:
                return bool(inp.poll(0))
            # The idle wait for input, a span of a running profile.
            with trace_span("wait_input"):
                return bool(inp.poll(timeout_ms))

        last_mesh_epoch = None
        if core.mesh_recovery is not None:
            # Initial report so frontends render /health mesh state
            # before any membership change.
            send_mesh([], "mesh monitoring armed")
            last_mesh_epoch = core.mesh_recovery.monitor.epoch

        def poll_mesh() -> None:
            nonlocal last_mesh_epoch
            if core.mesh_recovery is None:
                return
            # Recovery (shrink/grow + request replay hand-off)...
            ev = core.poll_mesh_recovery()
            if ev is not None:
                send_mesh(ev["lost_req_ids"], ev["reason"])
                last_mesh_epoch = ev["status"]["epoch"]
                return
            # ...and plain status refreshes (epoch moved without a
            # recovery decision, e.g. a rejoin observed mid-recovery).
            epoch = core.mesh_recovery.monitor.epoch
            if epoch != last_mesh_epoch:
                send_mesh([], "mesh membership changed")
                last_mesh_epoch = epoch

        while True:
            busy = core.has_unfinished_requests()
            # Idle: block on input (bounded so shutdown stays responsive).
            # Mid-wave idle ranks poll non-blocking: they must keep pace
            # with the busy ranks' step rate, not the 5 Hz idle tick.
            timeout = 0 if busy or (lockstep and global_unfinished) else 200
            while poll_input(timeout):
                frames = inp.recv_multipart()
                kind = frames[0]
                if kind == MSG_ADD:
                    req = serial_utils.decode(frames[1])
                    if len(outs) > 1:
                        req_client[req.request_id] = int(
                            getattr(req, "client_index", 0))
                    try:
                        core.add_request(req)
                    except Exception as e:
                        # Reject THIS request; the engine keeps serving.
                        logger.error(
                            "add_request %s failed: %s", req.request_id, e
                        )
                        from vllm_tpu.core.sched_output import (
                            EngineCoreOutput,
                            EngineCoreOutputs,
                        )

                        out_for(req.request_id).send_multipart([
                            MSG_OUTPUTS,
                            serial_utils.encode(EngineCoreOutputs(
                                outputs=[EngineCoreOutput(
                                    req_id=req.request_id,
                                    new_token_ids=[],
                                    finish_reason="abort",
                                )],
                            )),
                        ])
                        req_client.pop(req.request_id, None)
                elif kind == MSG_ABORT:
                    abort_ids = serial_utils.decode(frames[1])
                    core.abort_requests(abort_ids)
                    for rid in abort_ids:
                        req_client.pop(rid, None)
                elif kind == MSG_UTILITY:
                    method = frames[1].decode()
                    args = (
                        serial_utils.decode(frames[2])
                        if len(frames) > 2
                        else []
                    )
                    # Optional 4th frame: which frontend asked — the
                    # reply must land on ITS output socket (older
                    # 3-frame clients implicitly mean frontend 0).
                    reply_to = (
                        int(frames[3]) % len(outs) if len(frames) > 3
                        else 0
                    )
                    # A failing utility (e.g. sleep with active requests,
                    # bad reload path) fails the CALL, not the engine.
                    try:
                        result = {"ok": getattr(core, method)(*args),
                                  "engine_id": engine_id}
                    except Exception as e:
                        logger.error("utility %s failed: %s", method, e)
                        result = {"error": f"{type(e).__name__}: {e}",
                                  "engine_id": engine_id}
                    outs[reply_to].send_multipart([
                        MSG_UTILITY_REPLY, serial_utils.encode(result)
                    ])
                elif kind == MSG_SHUTDOWN:
                    return
                timeout = 0
            drain_coordinator()
            # Mesh membership: notice host death/rejoin and run the
            # supervised shrink/grow BEFORE stepping — a step dispatched
            # onto a mesh with a dead host wedges in the collective. A
            # failed recovery raises (MeshRecoveryError) and unwinds
            # through the generic death path below: cleanly dead, never
            # half-meshed.
            poll_mesh()
            # Report BEFORE stepping: step() can block inside a cross-rank
            # collective, and idle ranks only join once the coordinator has
            # seen this rank's load (reference: DPEngineCoreProc reports at
            # the top of the busy loop).
            report_load()
            if not core.has_unfinished_requests():
                # Saves queued at the finish of the last running request
                # must not wait for the next request's step: peers query
                # this engine's host tier through the KV fabric.
                core.flush_kv_saves()
                if lockstep and global_unfinished:
                    # Other DP ranks are mid-wave: keep collectives alive.
                    core.execute_dummy_batch()
                continue
            outputs = core.step()
            report_load()
            if not outputs.outputs:
                pass
            elif len(outs) == 1:
                out.send_multipart(
                    [MSG_OUTPUTS, serial_utils.encode(outputs)]
                )
            else:
                # Multi-frontend: split the step's outputs by owning
                # frontend; each non-empty slice rides its own socket
                # with the step's scheduler_stats attached (every
                # frontend's metrics see engine-level stats).
                by_client: dict[int, list] = {}
                for o in outputs.outputs:
                    idx = req_client.get(o.req_id, 0) % len(outs)
                    by_client.setdefault(idx, []).append(o)
                    if o.finish_reason is not None:
                        req_client.pop(o.req_id, None)
                from vllm_tpu.core.sched_output import EngineCoreOutputs

                for idx, slice_outs in by_client.items():
                    outs[idx].send_multipart([
                        MSG_OUTPUTS,
                        serial_utils.encode(EngineCoreOutputs(
                            outputs=slice_outs,
                            scheduler_stats=outputs.scheduler_stats,
                            timestamp=outputs.timestamp,
                        )),
                    ])
    except Exception:
        tb = traceback.format_exc()
        logger.error("engine core proc died:\n%s", tb)
        try:
            suspects: list[str] = []
            if core is not None:
                try:
                    suspects = core.suspect_req_ids()
                except Exception:
                    suspects = []
            send_dead(tb, suspects)
        except Exception:
            pass
    finally:
        if core is not None:
            core.shutdown()
        inp.close(linger=0)
        for sock in outs:
            sock.close(linger=0)
        if bind_input and input_addr.startswith("ipc://"):
            try:
                os.unlink(input_addr[len("ipc://"):])
            except OSError:
                pass
        if coord_push is not None:
            coord_push.close(linger=0)
            coord_sub.close(linger=0)
        ctx.term()
