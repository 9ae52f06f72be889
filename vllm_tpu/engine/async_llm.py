"""AsyncLLM: per-request async-generator API for serving.

Reference analog: ``vllm/v1/engine/async_llm.py:70`` (generate :524,
_run_output_handler :637). The reference splits frontend and engine core
into separate processes over ZMQ; here the engine core runs in a background
*thread* — the jitted TPU step releases the GIL while the device works, so
the asyncio event loop stays responsive without a process hop (the reference
needs the split because its scheduler hot loop is GIL-bound CPU work
feeding many GPU worker processes). A ZMQ proc split can layer on top for
DP; the AsyncLLM surface is identical either way.
"""

from __future__ import annotations

import asyncio
import os
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Any, AsyncGenerator

from vllm_tpu.config import EngineConfig
from vllm_tpu.core.sched_output import EngineCoreOutput
from vllm_tpu.engine.core_client import make_client
from vllm_tpu.engine.input_processor import InputProcessor, PromptType
from vllm_tpu.engine.output_processor import OutputProcessor
from vllm_tpu.logger import init_logger
from vllm_tpu.outputs import RequestOutput
from vllm_tpu.resilience import (
    TIMEOUT_FINISH_REASON,
    AdmissionController,
    EngineRestartedError,
    LiveConfigError,
    QuarantineManager,
    RequestFailedOnCrashError,
    RequestJournal,
    SlowClientError,
    live_config_keys,
    make_shed_error,
    vet_live_config,
)
from vllm_tpu.sampling_params import RequestOutputKind, SamplingParams
from vllm_tpu.tracing import trace_span

logger = init_logger(__name__)

# One EngineDeadError across the stack (reference:
# ``vllm/v1/engine/exceptions.py:9``) — a caller's `except EngineDeadError`
# must catch regardless of whether the death surfaced client- or
# engine-side.
from vllm_tpu.engine.core_client import EngineDeadError  # noqa: E402,F401


class AsyncStream:
    """Thread-safe per-request output stream with an optional buffer bound.

    Reference analog: ``RequestOutputCollector`` (async_llm.py). The engine
    thread calls ``put_nowait`` (the OutputProcessor treats it like a queue);
    delivery hops onto the consumer's event loop via call_soon_threadsafe so
    the awaiting generator wakes up.

    Slow-client backpressure: with ``maxsize > 0``, a consumer that stops
    reading cannot buffer output without limit. On overflow the stream
    either discards the oldest undelivered output (``drop_oldest`` — the
    next delivered output carries ``num_dropped_outputs``; CUMULATIVE and
    FINAL_ONLY consumers lose nothing since later outputs supersede) or
    delivers :class:`SlowClientError` and reports the request for abort
    (``abort`` policy). Terminal items (exceptions, finished outputs) are
    never dropped and are appended even over the bound — a stream always
    terminates.
    """

    def __init__(
        self,
        loop: asyncio.AbstractEventLoop,
        maxsize: int = 0,
        overflow_policy: str = "drop_oldest",
        request_id: str | None = None,
        on_drop: Any | None = None,
        on_slow_client: Any | None = None,
    ) -> None:
        self._loop = loop
        self._maxsize = maxsize
        self._policy = overflow_policy
        self._request_id = request_id
        self._on_drop = on_drop  # callable(n) — drop accounting
        self._on_slow_client = on_slow_client  # callable(request_id)
        # Consumed and mutated only on the event-loop thread (put_nowait
        # trampolines through call_soon_threadsafe).
        self._items: deque = deque()
        self._ready = asyncio.Event()
        self._aborted = False
        self._undelivered_drops = 0
        self.dropped_total = 0

    @staticmethod
    def _is_terminal(item: Any) -> bool:
        return isinstance(item, Exception) or bool(
            getattr(item, "finished", False))

    def put_nowait(self, item: Any) -> None:
        if self._loop.is_closed():  # pragma: no cover - shutdown race
            return
        self._loop.call_soon_threadsafe(self._put, item)

    def _put(self, item: Any) -> None:
        # Event-loop thread only.
        if self._aborted:
            return
        if (
            self._maxsize
            and len(self._items) >= self._maxsize
            and not self._is_terminal(item)
        ):
            if self._policy == "abort":
                self._aborted = True
                self._items.append(
                    SlowClientError(self._request_id or "?",
                                    len(self._items)))
                self._ready.set()
                if self._on_slow_client is not None:
                    self._on_slow_client(self._request_id)
                return
            # drop_oldest: the front of the deque is never terminal (a
            # terminal item ends the stream, nothing is put after it).
            self._items.popleft()
            self.dropped_total += 1
            self._undelivered_drops += 1
            if self._on_drop is not None:
                self._on_drop(1)
        self._items.append(item)
        self._ready.set()

    async def get(self) -> Any:
        while not self._items:
            self._ready.clear()
            await self._ready.wait()
        item = self._items.popleft()
        if self._undelivered_drops and not isinstance(item, Exception):
            # Surface the gap to delta consumers; cumulative consumers can
            # ignore it (their next output already contains everything).
            item.num_dropped_outputs = self._undelivered_drops
            self._undelivered_drops = 0
        return item


class AsyncLLM:
    # Class-level QoS defaults so harnesses that assemble an engine via
    # __new__ around a fake client (the recovery/chaos/quarantine unit
    # rigs) get a working no-brownout configuration without tracking
    # every new attribute.
    _brownout = None
    _brownout_next_t = 0.0
    _brownout_push_t = 0.0
    _qos_enabled = True
    # Rolling-upgrade defaults for the same __new__-built rigs.
    _rolling = None
    _rolling_pending_down = None
    _engine_versions = None
    _versions_next_t = 0.0

    def __init__(self, config: EngineConfig, start: bool = True,
                 client: Any | None = None) -> None:
        self.config = config = config.finalize()
        self.resilience = config.resilience_config
        self.lifecycle = config.lifecycle_config
        # Overload protection: bounded admission + drain latch + shed
        # accounting (vllm_tpu/resilience/lifecycle).
        self.admission = AdmissionController(self.lifecycle)
        # Crash-recovery journal: every admitted request's prompt, params
        # and emitted tokens, so requests in flight on a crashed engine
        # core can be resumed on its replacement (vllm_tpu/resilience).
        # journal_dir alone also creates one (persistence needs entries).
        self.journal = (
            RequestJournal(persist_dir=self.resilience.journal_dir)
            if self.resilience.enable_recovery
            or self.resilience.journal_dir is not None
            else None
        )
        # Poison-request bisection & quarantine: strike accounting over
        # crash suspect sets; a request repeatedly implicated in engine
        # deaths is dead-lettered instead of crash-looping the engine
        # (vllm_tpu/resilience/quarantine).
        self.quarantine = (
            QuarantineManager(
                max_suspect_strikes=self.resilience.max_suspect_strikes,
                probation_cap=self.resilience.quarantine_probation_cap,
                persist_dir=self.resilience.journal_dir,
                on_release=self._release_held_requests,
            )
            if self.resilience.enable_recovery
            else None
        )
        # ``client`` injects a pre-built engine client (the multi-API-
        # server topology's SharedDPClient, which talks to an engine
        # pool owned by the launcher, not by this process).
        self.engine_core = client if client is not None else (
            make_client(config))
        self.input_processor = InputProcessor(config)
        # SLO scoreboard: optional request-trace capture + live per-class
        # attainment targets (vllm_tpu/metrics/reqtrace, metrics/goodput).
        # Both default off, leaving the output processor's per-request
        # path untouched.
        obs = config.observability_config
        self.reqtrace = None
        if obs.request_trace_dir:
            from vllm_tpu.metrics.reqtrace import RequestTraceRecorder

            self.reqtrace = RequestTraceRecorder(obs.request_trace_dir)
        slo_targets = None
        if obs.slo_targets:
            from vllm_tpu.metrics.goodput import parse_slo_spec

            slo_targets = parse_slo_spec(obs.slo_targets)
        self.output_processor = OutputProcessor(
            self.input_processor.tokenizer, journal=self.journal,
            on_request_closed=self._on_request_closed,
            reqtrace=self.reqtrace, slo_targets=slo_targets,
        )
        self.stat_loggers: list[Any] = []

        self._input_queue: queue.Queue = queue.Queue()
        self._loop: asyncio.AbstractEventLoop | None = None
        self._dead = False
        self._shutdown = threading.Event()
        self._thread: threading.Thread | None = None
        # Lifecycle counters (ints under the GIL; read by /metrics).
        self.timeouts_total: dict[str, int] = {}
        self.stream_drops_total = 0
        self.slow_client_aborts_total = 0
        # Journal replays skipped because the client aborted the request
        # between the crash and its re-admission (satellite fix: a stale
        # replay would generate for a consumer that already left).
        self.replays_dropped_aborted_total = 0
        self._last_deadline_sweep = 0.0
        # Elastic capacity (vllm_tpu/resilience/autoscale): the
        # controller decides, the DP client executes (spawn + peer
        # weight re-seed up, graceful drain down). Armed only for a DP
        # pool with --autoscale; VLLM_TPU_DISABLE_AUTOSCALE is the
        # escape hatch that severs the decision loop while keeping
        # manual scale_up()/scale_down() available.
        self._autoscale = None
        self._autoscale_next_t = 0.0
        self._autoscale_occ: float | None = None
        self._autoscale_occ_t = 0.0
        rc = self.resilience
        if rc.autoscale and hasattr(self.engine_core, "scale_up"):
            from vllm_tpu import envs

            if envs.VLLM_TPU_DISABLE_AUTOSCALE:
                logger.warning(
                    "autoscale configured but disabled via "
                    "VLLM_TPU_DISABLE_AUTOSCALE")
            else:
                from vllm_tpu.resilience import AutoscaleController

                n0 = config.parallel_config.data_parallel_engines
                self._autoscale = AutoscaleController(
                    min_engines=rc.autoscale_min_engines,
                    max_engines=rc.autoscale_max_engines or n0,
                    up_queue_depth=rc.autoscale_up_queue_depth,
                    down_queue_depth=rc.autoscale_down_queue_depth,
                    slo_floor=rc.autoscale_slo_floor,
                    occupancy_high=rc.autoscale_occupancy_high,
                    hold_s=rc.autoscale_hold_s,
                    cooldown_s=rc.autoscale_cooldown_s,
                )
        # QoS brownout ladder (vllm_tpu/resilience/qos): the controller
        # decides the rung from the same pressure signals the autoscaler
        # watches but on a millisecond cadence; the rung is pushed to
        # every engine core (spec suspension / chunk shrink / pressure
        # preemption) and enforced frontend-side (rung-3 batch-class
        # sheds). VLLM_TPU_DISABLE_QOS is the escape hatch that turns
        # off the ladder, WFQ admission, and pressure preemption at
        # once; set_qos(False) is the live FIFO-vs-QoS A/B toggle.
        self._brownout = None
        self._brownout_next_t = 0.0
        self._brownout_push_t = 0.0
        self._qos_enabled = True
        from vllm_tpu import envs

        if envs.VLLM_TPU_DISABLE_QOS:
            self._qos_enabled = False
            self.admission.wfq_enabled = False
            if self.lifecycle.brownout:
                logger.warning(
                    "brownout configured but disabled via "
                    "VLLM_TPU_DISABLE_QOS")
        elif self.lifecycle.brownout:
            from vllm_tpu.resilience import BrownoutController

            self._brownout = BrownoutController(
                self.lifecycle.make_brownout_config())
        # Zero-downtime operations (vllm_tpu/resilience/rolling): the
        # rolling-upgrade controller sequences the pool one slot at a
        # time; the busy loop executes its commands against the DP
        # client's upgrade primitives. Armed for any engine-pool
        # client; VLLM_TPU_DISABLE_ROLLING severs the driver (POST
        # /admin/upgrade refuses) while the manual primitives and the
        # live-config set_config RPC stay available.
        self._rolling = None
        self._rolling_pending_down = None
        # Per-engine /health version blocks, refreshed on the engine
        # loop (the client's utility sockets are single-threaded).
        self._engine_versions = None
        self._versions_next_t = 0.0
        self.config_reloads_total: dict[str, int] = {}
        if hasattr(self.engine_core, "scale_up"):
            if envs.VLLM_TPU_DISABLE_ROLLING:
                logger.warning(
                    "rolling upgrades disabled via "
                    "VLLM_TPU_DISABLE_ROLLING")
            else:
                from vllm_tpu.resilience import RollingUpgradeController

                self._rolling = RollingUpgradeController(
                    gate_requests=rc.upgrade_gate_requests,
                    gate_timeout_s=rc.upgrade_gate_timeout_s,
                    slo_floor=rc.upgrade_slo_floor,
                )
        if start:
            self.start()

    @classmethod
    def from_engine_args(cls, engine_args: Any) -> "AsyncLLM":
        return cls(engine_args.create_engine_config())

    @property
    def tokenizer(self):
        return self.input_processor.tokenizer

    def start(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._busy_loop, name="engine-core", daemon=True
            )
            self._thread.start()

    # ------------------------------------------------------------------
    # Client side (event loop)
    # ------------------------------------------------------------------

    async def generate(
        self,
        prompt: PromptType,
        sampling_params: SamplingParams,
        request_id: str,
        priority: int = 0,
        pooling_params=None,
    ) -> AsyncGenerator[RequestOutput, None]:
        """Feed a request and yield RequestOutputs as tokens arrive.

        Raises :class:`RequestShedError` when admission control rejects
        the request (saturated or draining) — nothing is queued in that
        case, and the shed is counted in
        ``vllm:requests_shed_total{reason=...}``.
        """
        if self._dead:
            raise EngineDeadError("engine core died")
        self._loop = asyncio.get_running_loop()
        # Request-level priority (SamplingParams.priority, fed by the
        # body or the X-Priority header) wins over the call-site default.
        # Lower = more urgent; 0 = interactive.
        if sampling_params.priority is not None:
            priority = sampling_params.priority
        core_req = self.input_processor.process(
            request_id, prompt, sampling_params, priority=priority,
            pooling_params=pooling_params,
        )
        tenant_id = sampling_params.tenant_id
        # Brownout rung 3+: shed batch-class work before reserving
        # capacity, with a Retry-After scaled by the rung. Interactive
        # requests (priority 0, non-shed SLO class) pass through to the
        # normal admission check.
        ctrl = self._brownout
        if (
            ctrl is not None and self._qos_enabled and ctrl.rung >= 3
            and self._is_batch_class(priority, sampling_params)
        ):
            self.admission.count_shed("brownout", tenant_id)
            raise make_shed_error(
                "brownout", self.lifecycle,
                retry_after_s=ctrl.retry_after_s(
                    self.lifecycle.retry_after_s),
            )
        # Admission AFTER input processing: a malformed request is a 400,
        # not a shed; capacity is reserved only for well-formed work.
        shed_reason = self.admission.try_admit(
            request_id, len(core_req.prompt_token_ids),
            tenant_id=tenant_id,
        )
        if shed_reason is not None:
            raise make_shed_error(shed_reason, self.lifecycle)
        lc = self.lifecycle
        out_q = AsyncStream(
            asyncio.get_running_loop(),
            maxsize=lc.stream_buffer_size,
            overflow_policy=lc.stream_overflow_policy,
            request_id=request_id,
            on_drop=self._note_stream_drop,
            on_slow_client=self._abort_slow_client,
        )
        state = self.output_processor.add_request(
            request_id,
            getattr(core_req, "prompt_text", None),
            core_req.prompt_token_ids,
            core_req.sampling_params,
            core_req.arrival_time,
            queue=out_q,
            trace_id=core_req.trace_id,
        )
        # Deadline resolution: per-request override > server default;
        # enforced by the engine-thread sweep (_expire_deadlines).
        now = time.monotonic()
        deadline_s = sampling_params.deadline_s or lc.default_deadline_s
        if deadline_s:
            state.deadline_t = now + deadline_s
        if lc.ttft_timeout_s:
            state.ttft_deadline_t = now + lc.ttft_timeout_s
        if self.journal is not None:
            self.journal.record_admitted(core_req)
        self._input_queue.put(("add", core_req))
        finished = False
        try:
            while True:
                item = await out_q.get()
                if isinstance(item, Exception):
                    raise item
                yield item
                if item.finished:
                    finished = True
                    return
        finally:
            # Generator dropped early (client disconnect) -> abort.
            if not finished:
                self._abort_requests([request_id])

    async def abort(self, request_id: str) -> None:
        self._abort_requests([request_id])

    def _abort_requests(self, request_ids: list[str]) -> None:
        """Frontend-side cleanup always runs; the engine-side abort is
        only enqueued while the engine is alive — a dead engine has no
        request state to abort, and piling aborts onto its queue would
        never drain."""
        self.output_processor.abort_requests(request_ids)
        if not self._dead:
            self._input_queue.put(("abort", request_ids))

    def _on_request_closed(self, request_id: str) -> None:
        """OutputProcessor callback: the request reached a terminal state
        (final output delivered or aborted). Frees its admission slot and
        clears its quarantine strikes — a request that terminated cleanly
        cannot be the deterministic poison."""
        self.admission.release(request_id)
        if self.quarantine is not None:
            self.quarantine.note_terminal(request_id)

    def _release_held_requests(self, req_ids: list[str]) -> None:
        """Quarantine callback: the bisection probe resolved, the held
        half may re-admit. May fire on any thread (terminal notifications
        come from both the busy loop and the event loop), so only enqueue
        — the busy loop replays them with full journal checks."""
        self._input_queue.put(("release", list(req_ids)))

    # -- slow-client backpressure (callbacks from AsyncStream) ---------

    def _note_stream_drop(self, n: int) -> None:
        self.stream_drops_total += n

    def _abort_slow_client(self, request_id: str) -> None:
        # Runs on the event-loop thread (AsyncStream._put). The stream has
        # already delivered SlowClientError to the consumer; kill the
        # request everywhere else.
        self.slow_client_aborts_total += 1
        logger.warning(
            "aborting request %s: output stream overflowed (slow client)",
            request_id,
        )
        self._abort_requests([request_id])

    # ------------------------------------------------------------------
    # Engine side (background thread)
    # ------------------------------------------------------------------

    def _busy_loop(self) -> None:
        try:
            stalled = False
            while not self._shutdown.is_set():
                try:
                    stalled = self._step_once(stalled)
                except EngineRestartedError as e:
                    # An engine core crashed and the client respawned it
                    # (or is respawning it, DP): replay/fail the
                    # interrupted requests and keep serving — crash
                    # recovery must never take down the whole frontend.
                    self._recover_requests(e)
                    stalled = False
        except Exception as e:  # permanent engine death -> fail all waiters
            logger.exception("engine core loop died: %s", e)
            self._dead = True
            err = EngineDeadError(f"engine core died: {e!r}")
            for state in list(self.output_processor.request_states.values()):
                self.admission.release(state.request_id)
                if state.queue is not None:
                    state.queue.put_nowait(err)

    def _step_once(self, stalled: bool) -> bool:
        # `stalled`: unfinished requests exist but the last step()
        # dispatched nothing and produced nothing (e.g. a prompt
        # whose KV footprint can't be allocated yet). Block on the
        # input queue with a timeout instead of hot-spinning.
        self._drain_input_queue(
            block=stalled
            or not self.engine_core.has_unfinished_requests()
        )
        if self._shutdown.is_set():
            return stalled
        # Deadline/TTFT sweep runs even when the engine is idle or
        # stalled — a request stuck queued is exactly the one a TTFT
        # timeout exists for.
        self._expire_deadlines()
        # Mesh-membership poll (in-proc client only; MP engines poll in
        # their own busy loop and report over MSG_MESH). Runs even when
        # idle: /health must reflect a host death with no traffic, and a
        # rejoin must grow the mesh back. Raises EngineRestartedError on
        # a shrink/grow so the interrupted requests journal-replay.
        poll_mesh = getattr(self.engine_core, "poll_mesh", None)
        if poll_mesh is not None:
            poll_mesh()
        # Elastic-capacity tick (DP pool only): advance any in-flight
        # scale event and run the controller. Runs even when idle — a
        # drained-quiet pool is exactly when scale-down fires. May raise
        # EngineRestartedError (drain deadline replays stragglers onto
        # survivors) — recovered by the busy loop like any crash.
        if getattr(self.engine_core, "poll_scale", None) is not None:
            self.poll_autoscale()
        # Rolling-upgrade tick: observe slot state the scale machinery
        # advanced, execute the controller's next command, and keep the
        # per-engine /health version cache fresh. Runs even when idle —
        # upgrades of a quiet pool must still progress.
        if hasattr(self.engine_core, "engine_versions"):
            self.poll_versions()
        if self._rolling is not None:
            self.poll_upgrade()
        # Brownout tick: runs even when idle so the ladder de-escalates
        # once pressure clears (rung 0 must be reachable with no traffic).
        if self._brownout is not None and self._qos_enabled:
            self.poll_brownout()
        if not self.engine_core.has_unfinished_requests():
            return stalled
        outputs = self.engine_core.get_output(timeout=0.2)
        stalled = not outputs.outputs and not self.engine_core.inflight
        stats = outputs.scheduler_stats
        if stats is not None and stats.preempted_req_ids:
            # A preempt/resume cycle consumes scheduler capacity twice:
            # re-charge the tenant's WFQ virtual-time debt per preempted
            # request. The token reservation is untouched, so the
            # admission release stays exactly-once.
            for rid in stats.preempted_req_ids:
                self.admission.note_requeue(rid)
        # process_outputs delivers straight into each request's
        # AsyncStream (thread-safe); nothing to re-publish here.
        processed = self.output_processor.process_outputs(
            outputs.outputs
        )
        if processed.reqs_to_abort:
            self.engine_core.abort_requests(processed.reqs_to_abort)
        for logger_ in self.stat_loggers:
            logger_.record(
                scheduler_stats=outputs.scheduler_stats,
                iteration_stats=processed.iteration_stats,
            )
        return stalled

    def _expire_deadlines(self) -> None:
        """Engine-thread sweep: requests past their deadline (or TTFT
        cutoff while still waiting for a first token) are aborted
        engine-side and finished with ``finish_reason="timeout"`` —
        never silently hung. Throttled; runs even when the engine is
        idle (the busy loop ticks ~10Hz via the input-queue timeout)."""
        now = time.monotonic()
        if now - self._last_deadline_sweep < 0.05:
            return
        self._last_deadline_sweep = now
        expired: list[tuple[str, str]] = []
        for rid, state in list(self.output_processor.request_states.items()):
            if state.deadline_t is not None and now >= state.deadline_t:
                expired.append((rid, "deadline"))
            elif (
                state.ttft_deadline_t is not None
                and state.metrics.first_token_time is None
                and now >= state.ttft_deadline_t
            ):
                expired.append((rid, "ttft"))
        if not expired:
            return
        rids = [rid for rid, _ in expired]
        logger.warning("expiring %d request(s) past deadline: %s",
                       len(rids), rids)
        # Engine-side abort first (frees KV blocks / scheduler slots); if
        # it raises EngineRestartedError the sweep retries next tick —
        # counters and finishes below must not run twice.
        self.engine_core.abort_requests(rids)
        for _, kind in expired:
            self.timeouts_total[kind] = self.timeouts_total.get(kind, 0) + 1
        # Finish through the normal output path (same as crash recovery)
        # so stats, journal, tracing, and admission release all fire.
        processed = self.output_processor.process_outputs([
            EngineCoreOutput(
                req_id=rid, new_token_ids=[],
                finish_reason=TIMEOUT_FINISH_REASON,
            )
            for rid in rids
        ])
        for logger_ in self.stat_loggers:
            logger_.record(
                scheduler_stats=None,
                iteration_stats=processed.iteration_stats,
            )

    def _recover_requests(self, err: EngineRestartedError) -> None:
        """Requests lost with a crashed engine are replayed from the
        journal (resuming from the tokens already delivered), parked or
        dead-lettered by the quarantine bisection, or failed with a
        per-request error — never silently hung."""
        logger.warning(
            "engine core %d restarted (%s); recovering %d in-flight "
            "requests", err.engine_id,
            "device hang" if err.hang else "crash",
            len(err.lost_req_ids),
        )
        dispositions: dict[str, str] = {}
        if self.quarantine is not None and err.lost_req_ids:
            dispositions = self.quarantine.on_crash(
                err.lost_req_ids, err.suspect_req_ids
            )
        for rid in err.lost_req_ids:
            disposition = dispositions.get(rid, "replay")
            state = self.output_processor.request_states.get(rid)
            if state is None:
                # Aborted/finished while the crash was being handled.
                if self.journal is not None:
                    self.journal.discard(rid)
                if self.quarantine is not None:
                    self.quarantine.note_terminal(rid)
                self.replays_dropped_aborted_total += 1
                continue
            if disposition == "deadletter":
                entry = (
                    self.journal.get(rid)
                    if self.journal is not None else None
                )
                rec = self.quarantine.note_deadlettered(
                    rid, entry, str(err))
                self._fail_request(
                    rid, state,
                    (entry.retries + 1) if entry is not None else 1,
                    f"quarantined as poison request after "
                    f"{rec['strikes']} crash strike(s); dead-lettered",
                )
                continue
            if disposition == "hold":
                # Parked: journal entry and stream stay open; re-admitted
                # via _release_held_requests when the probe resolves.
                continue
            # Bisection-probe replays bypass the generic retry budget —
            # the strike cap bounds them instead (a poison request must
            # stay replayable long enough to be isolated). Ordinary
            # one-strike suspects still spend from max_request_retries.
            self._replay_or_fail(
                rid, state,
                bypass_retry_budget=(
                    self.quarantine is not None
                    and self.quarantine.is_probing(rid)
                ),
            )

    def _replay_or_fail(self, rid: str, state,
                        bypass_retry_budget: bool = False) -> None:
        entry = (
            self.journal.get(rid) if self.journal is not None else None
        )
        if entry is None:
            self._fail_request(rid, state, 1, "no journal entry")
            return
        remaining = entry.remaining_tokens
        if remaining is not None and remaining <= 0:
            # Full budget already delivered: close the stream out as
            # a normal length finish instead of replaying a request
            # that has nothing left to generate.
            self.output_processor.process_outputs([
                EngineCoreOutput(
                    req_id=rid, new_token_ids=[],
                    finish_reason="length",
                )
            ])
        elif not entry.replayable:
            self._fail_request(
                rid, state, entry.retries + 1,
                "structured-output requests cannot be resumed",
            )
        elif (
            entry.retries >= self.resilience.max_request_retries
            and not bypass_retry_budget
        ):
            self._fail_request(
                rid, state, entry.retries + 1,
                "crash-replay budget exhausted",
            )
        else:
            self.journal.note_replayed(rid)
            logger.info(
                "replaying request %s onto recovered engine "
                "(attempt %d/%d, resuming after %d emitted tokens)",
                rid, entry.retries,
                self.resilience.max_request_retries,
                len(entry.emitted_token_ids),
            )
            # "replay" (not "add"): re-checked against the live request
            # set at drain time — an abort landing between here and the
            # actual add must not resurrect the request engine-side.
            self._input_queue.put(
                ("replay", (rid, entry.make_resume_request())))

    def _fail_request(self, rid: str, state, attempts: int,
                      detail: str) -> None:
        if self.journal is not None:
            self.journal.note_failed(rid)
        self.output_processor.request_states.pop(rid, None)
        self._on_request_closed(rid)
        err = RequestFailedOnCrashError(rid, attempts, detail)
        logger.error("%s", err)
        if state.queue is not None:
            state.queue.put_nowait(err)

    def _drain_input_queue(self, block: bool) -> None:
        try:
            if block:
                # Nothing runs (or nothing can): the loop waits for input.
                with trace_span("wait_input"):
                    op, payload = self._input_queue.get(timeout=0.1)
            else:
                op, payload = self._input_queue.get_nowait()
        except queue.Empty:
            return
        while True:
            try:
                if op == "add":
                    self.engine_core.add_request(payload)
                elif op == "replay":
                    # Journal replay of a crash-interrupted request. The
                    # client may have aborted it between the crash and
                    # this drain (the abort already tore down its state)
                    # — re-admitting would create a consumer-less ghost
                    # request, so drop the replay and count it.
                    rid, req = payload
                    if rid in self.output_processor.request_states:
                        self.engine_core.add_request(req)
                    else:
                        self.replays_dropped_aborted_total += 1
                        if self.journal is not None:
                            self.journal.discard(rid)
                        if self.quarantine is not None:
                            self.quarantine.note_terminal(rid)
                        logger.info(
                            "dropping journal replay of %s: aborted "
                            "before re-admission", rid)
                elif op == "release":
                    # Quarantine released held suspects: replay them with
                    # the full journal checks, on this thread.
                    for rid in payload:
                        state = (
                            self.output_processor.request_states.get(rid))
                        if state is None:
                            self.replays_dropped_aborted_total += 1
                            if self.journal is not None:
                                self.journal.discard(rid)
                            if self.quarantine is not None:
                                self.quarantine.note_terminal(rid)
                            continue
                        self._replay_or_fail(
                            rid, state, bypass_retry_budget=True)
                elif op == "abort":
                    self.engine_core.abort_requests(payload)
                elif op == "call":
                    # A call of the engine client made for another
                    # thread (live-config push, profiler start/stop): the
                    # client's utility sockets and the engine's step
                    # belong to this thread; the API handler waits on
                    # the future. An engine death mid-broadcast must
                    # still reach the busy loop's recovery path.
                    method, args, fut = payload
                    if fut.set_running_or_notify_cancel():
                        try:
                            fut.set_result(
                                getattr(self.engine_core, method)(*args))
                        except EngineRestartedError as e:
                            fut.set_exception(e)
                            raise
                        except BaseException as e:
                            fut.set_exception(e)
                elif op == "finish":
                    # Drain stragglers: abort engine-side, then close the
                    # streams with a final output ON THIS THREAD (racing
                    # process_outputs from another thread would corrupt
                    # per-request state).
                    rids, reason = payload
                    rids = [
                        r for r in rids
                        if r in self.output_processor.request_states
                    ]
                    if rids:
                        self.engine_core.abort_requests(rids)
                        self.output_processor.process_outputs([
                            EngineCoreOutput(
                                req_id=r, new_token_ids=[],
                                finish_reason=reason,
                            )
                            for r in rids
                        ])
            except EngineRestartedError:
                # The op raced the crash. Aborts are moot (the request
                # state died with the engine); an add must not be lost —
                # requeue it, then let the busy loop recover the rest.
                # A drain "finish" hasn't closed its streams yet: requeue.
                # A "replay"/"release" hadn't reached the engine, so its
                # request is not in the crash's lost set: requeue too.
                if op in ("add", "finish", "replay", "release"):
                    self._input_queue.put((op, payload))
                raise
            try:
                op, payload = self._input_queue.get_nowait()
            except queue.Empty:
                return

    # ------------------------------------------------------------------
    # Graceful drain
    # ------------------------------------------------------------------

    @property
    def num_inflight(self) -> int:
        return len(self.output_processor.request_states)

    def check_admission(self) -> None:
        """Cheap pre-check (no reservation) for streaming handlers that
        must reject BEFORE committing to an SSE response. Raises
        RequestShedError; the authoritative check is in generate()."""
        reason = self.admission.precheck()
        if reason is not None:
            raise make_shed_error(reason, self.lifecycle)

    def start_drain(self) -> None:
        """Stop admitting work: /ready flips 503, new requests shed with
        reason="draining", supervisor respawns are suspended (a drain
        must never race a respawn back to life). In-flight requests keep
        running; use drain() to wait them out."""
        if self.admission.draining:
            return
        logger.info("drain started: admission closed, respawns suspended")
        self.admission.start_drain()
        if hasattr(self.engine_core, "suspend_recovery"):
            self.engine_core.suspend_recovery()

    async def drain(self, timeout_s: float | None = None) -> None:
        """Graceful drain: stop admission, let in-flight requests finish
        under the drain budget, then abort stragglers (their streams get
        a final finish_reason="timeout" output — closed, not hung)."""
        self.start_drain()
        if timeout_s is None:
            timeout_s = self.lifecycle.drain_timeout_s
        deadline = time.monotonic() + timeout_s
        while self.num_inflight and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        if self.num_inflight:
            rids = list(self.output_processor.request_states)
            logger.warning(
                "drain budget (%.1fs) exhausted: aborting %d straggler(s)",
                timeout_s, len(rids),
            )
            self._input_queue.put(
                ("finish", (rids, TIMEOUT_FINISH_REASON)))
            grace = time.monotonic() + 5.0
            while self.num_inflight and time.monotonic() < grace:
                await asyncio.sleep(0.05)
        logger.info("drain complete (%d request(s) still open)",
                    self.num_inflight)

    # ------------------------------------------------------------------

    def lifecycle_status(self) -> dict:
        """JSON-shaped overload/lifecycle snapshot (feeds /metrics,
        /ready, and /debug/requests)."""
        status = self.admission.status()
        status.update(
            timeouts=dict(self.timeouts_total),
            stream_outputs_dropped_total=self.stream_drops_total,
            slow_client_aborts_total=self.slow_client_aborts_total,
        )
        return status

    def poll_autoscale(self) -> None:
        """Elastic-capacity tick (engine-loop thread): advance the DP
        client's in-flight scale event, feed completed-event records to
        the controller's counters, sample the traffic signals at
        ``autoscale_interval_s``, and execute the controller's decision.
        A drain past its deadline raises EngineRestartedError from the
        client — the busy loop then journal-replays the stragglers onto
        the surviving engines, exactly like a crash minus the crash."""
        client = self.engine_core
        events = client.poll_scale()
        ctrl = getattr(self, "_autoscale", None)
        if ctrl is None:
            return
        for ev in events:
            ctrl.note_scale_finished(ev["direction"], ev["outcome"])
            if ev.get("reseed"):
                ctrl.note_reseed(ev["reseed"])
        now = time.monotonic()
        if now < self._autoscale_next_t:
            return
        self._autoscale_next_t = now + self.resilience.autoscale_interval_s
        pool = client.pool_status()
        actual = pool["actual"]
        if actual <= 0:
            return
        # Waiting+running per routable engine: every open request state
        # is either queued client-side or in flight on an engine.
        depth = len(self.output_processor.request_states) / actual
        slo = None
        snap = self.output_processor.slo_attainment_snapshot()
        if snap:
            slo = min(v["attainment"] for v in snap.values())
        ctrl.observe(depth, slo, self._sample_occupancy(now))
        rolling = getattr(self, "_rolling", None)
        if rolling is not None and rolling.active:
            # A rolling upgrade owns the scale machinery: the
            # autoscaler keeps observing (its windows stay warm) but
            # must not race spawn/drain decisions into the cycle.
            return
        if ctrl.busy is not None or pool["scale_event"] is not None:
            return
        decision = ctrl.decide(actual)
        if decision == "up":
            if client.scale_up() is not None:
                ctrl.note_scale_started("up")
        elif decision == "down":
            if client.scale_down() is not None:
                ctrl.note_scale_started("down")

    def _sample_occupancy(self, now: float) -> float | None:
        """Worst kv-fabric tier occupancy across the pool, sampled at a
        slower cadence than the controller tick (the status call is a
        pool-wide utility broadcast) and cached for /health. None when
        no fabric is configured."""
        if self.config.cache_config.kv_connector != "fabric":
            return None
        if (now - self._autoscale_occ_t
                < 5 * self.resilience.autoscale_interval_s):
            return self._autoscale_occ
        self._autoscale_occ_t = now
        try:
            snap = self.engine_core.kv_fabric_status() or {}
        except Exception:
            return self._autoscale_occ
        # Pool-merged snapshots carry per-engine views under "engines";
        # a single-engine client returns one flat snapshot.
        engines = snap.get("engines")
        if not isinstance(engines, dict):
            engines = {"0": snap}
        worst: float | None = None
        for eng in engines.values():
            if not isinstance(eng, dict):
                continue
            for frac in (eng.get("tier_occupancy") or {}).values():
                if isinstance(frac, (int, float)):
                    worst = frac if worst is None else max(worst, frac)
        self._autoscale_occ = worst
        return worst

    # -- zero-downtime operations: rolling upgrade + live config -------

    def poll_versions(self) -> None:
        """Refresh the per-engine version cache (engine-loop thread —
        the client's utility sockets are not shareable with the event
        loop). Fast cadence while an upgrade is in flight so /health
        shows the new weights fingerprint as soon as the swap lands."""
        now = time.monotonic()
        if now < self._versions_next_t:
            return
        rolling = getattr(self, "_rolling", None)
        active = rolling is not None and rolling.active
        self._versions_next_t = now + (1.0 if active else 15.0)
        try:
            self._engine_versions = self.engine_core.engine_versions()
        except EngineRestartedError:
            raise
        except Exception:
            logger.debug("engine version refresh failed", exc_info=True)

    def poll_upgrade(self) -> None:
        """Rolling-upgrade tick (engine-loop thread): report slot state
        back to the controller, then execute its next command against
        the DP client's upgrade primitives. The controller is pure;
        every process-touching step happens here, on the one thread
        that owns the client."""
        ctrl = self._rolling
        if ctrl is None or not ctrl.active:
            return
        client = self.engine_core
        snap = ctrl.snapshot()
        newcomer, victim, phase = (
            snap["newcomer"], snap["victim"], snap["phase"])
        if newcomer is not None and phase in (
                "booting", "gating", "rolling_back"):
            state = client.slot_state(newcomer)
            if state == "up" and phase == "booting":
                logger.info(
                    "upgrade: engine %d is up (gated); health gate "
                    "opens (%d probe(s) required)", newcomer,
                    ctrl.gate_requests)
                ctrl.note_newcomer_up()
            elif state == "removed":
                # The death path already retired the slot; the gated
                # newcomer never received routed traffic, so this is an
                # automatic rollback by construction.
                ctrl.note_newcomer_dead()
                logger.warning(
                    "upgrade: newcomer %d died before its gate opened; "
                    "victim %d keeps serving (outcome=%s)",
                    newcomer, victim, ctrl.last_outcome)
        elif phase == "draining" and victim is not None:
            if client.slot_state(victim) == "removed":
                self._rolling_pending_down = None
                ctrl.note_victim_retired()
                self._versions_next_t = 0.0  # new fingerprint is live
            elif self._rolling_pending_down is not None:
                # scale_down was refused (a prior scale event was still
                # settling): retry until the latch frees.
                if client.scale_down(
                        engine_id=self._rolling_pending_down) is not None:
                    self._rolling_pending_down = None
        if not ctrl.active:
            return
        slo = None
        slo_snap = self.output_processor.slo_attainment_snapshot()
        if slo_snap:
            slo = min(v["attainment"] for v in slo_snap.values())
        action = ctrl.next_action(slo)
        if action is None:
            return
        op = action["op"]
        if op == "spawn":
            eid = None
            try:
                eid = client.scale_up(
                    checkpoint=action["checkpoint"],
                    config_overrides=action["config"],
                    gating=True,
                )
            except EngineRestartedError:
                raise
            except Exception:
                logger.exception(
                    "upgrade: spawn of the replacement for slot %s "
                    "failed; aborting the cycle", action["victim"])
                ctrl.request_abort()
            ctrl.note_spawned(eid)
            if eid is not None:
                logger.info(
                    "upgrade: engine %d booting as gated replacement "
                    "for %s", eid, action["victim"])
        elif op == "probe":
            try:
                client.probe_engine(action["newcomer"])
                ctrl.note_probe(True)
            except EngineRestartedError:
                # The probe raced an engine death elsewhere; its result
                # is unknowable — neither a pass nor a gate failure.
                ctrl.note_probe_interrupted()
                raise
            except Exception as e:
                logger.warning(
                    "upgrade: health probe failed on engine %s: %s",
                    action["newcomer"], e)
                ctrl.note_probe(False)
        elif op == "promote":
            client.open_gate(action["newcomer"])
            logger.info(
                "upgrade: gate passed on engine %s; draining victim %s",
                action["newcomer"], action["victim"])
            if client.scale_down(engine_id=action["victim"]) is None:
                self._rolling_pending_down = action["victim"]
        elif op == "rollback":
            lost = client.retire_engine(action["newcomer"])
            if lost:  # a gated slot holds no routed traffic
                logger.error(
                    "upgrade rollback of engine %s lost %d request(s)",
                    action["newcomer"], len(lost))
            ctrl.note_rolled_back()
            logger.warning(
                "upgrade: rolled back engine %s (%s); victim %s keeps "
                "serving", action["newcomer"],
                ctrl.snapshot().get("fail_reason") or "gate failed",
                action["victim"])

    def start_upgrade(self, checkpoint: str | None = None,
                      config: dict | None = None,
                      slots: list[int] | None = None,
                      gate_requests: int | None = None,
                      slo_floor: float | None = None) -> dict:
        """Arm a rolling-upgrade cycle (POST /admin/upgrade). The
        checkpoint path and config overrides are validated up front — a
        cycle that cannot possibly succeed is refused at the API, not
        rolled back one engine boot later. Raises ValueError on bad
        input or when a cycle is already in flight."""
        ctrl = self._rolling
        if ctrl is None:
            from vllm_tpu import envs

            raise ValueError(
                "rolling upgrades unavailable: "
                + ("disabled via VLLM_TPU_DISABLE_ROLLING"
                   if envs.VLLM_TPU_DISABLE_ROLLING
                   else "requires a data-parallel engine pool"))
        if checkpoint is None and not config:
            raise ValueError(
                "nothing to upgrade: provide a new checkpoint and/or "
                "config overrides")
        if checkpoint is not None and not os.path.exists(checkpoint):
            raise ValueError(
                f"upgrade checkpoint not found: {checkpoint}")
        if config:
            import copy

            from vllm_tpu.engine.core_client import (
                _apply_config_overrides)

            # Dry-run against a copy of our own config: unknown dotted
            # paths are a 400 here, not a failed boot mid-cycle.
            _apply_config_overrides(copy.deepcopy(self.config), config)
        # Per-cycle gate overrides (the CLI's --upgrade-gate-requests /
        # --upgrade-slo-floor); the server defaults stay for the next
        # cycle only if never overridden.
        if gate_requests is not None:
            if int(gate_requests) < 1:
                raise ValueError(
                    f"gate_requests must be >= 1, got {gate_requests}")
            ctrl.gate_requests = int(gate_requests)
        if slo_floor is not None:
            if not (0.0 <= float(slo_floor) <= 1.0):
                raise ValueError(
                    f"slo_floor must be in [0, 1], got {slo_floor}")
            ctrl.slo_floor = float(slo_floor)
        if slots is None:
            pool = self.engine_core.pool_status()
            busy = (set(pool["draining"]) | set(pool["seeding"])
                    | set(pool["gating"]) | set(pool["removed"]))
            slots = [i for i in range(pool["size"]) if i not in busy]
        if not ctrl.start(slots, checkpoint=checkpoint, config=config):
            raise ValueError(
                "an upgrade cycle is already in flight (one at a "
                "time); abort it first" if ctrl.active
                else "no slots to upgrade")
        self._rolling_pending_down = None
        logger.info(
            "rolling upgrade started over slots %s (checkpoint=%s, "
            "config=%s)", slots, checkpoint, config)
        return {"started": True, **ctrl.snapshot()}

    def abort_upgrade(self) -> dict:
        """Abort the in-flight cycle at the next safe point: a gated
        newcomer rolls back; a slot already past promotion finishes its
        drain before the cycle stops."""
        ctrl = self._rolling
        accepted = ctrl.request_abort() if ctrl is not None else False
        status = ctrl.snapshot() if ctrl is not None else {}
        return {"abort_requested": accepted, **status}

    def upgrade_status(self) -> dict | None:
        """Rolling-upgrade snapshot for /health and /metrics, or None
        when the client has no engine pool (nothing to roll)."""
        if not hasattr(self.engine_core, "scale_up"):
            return None
        ctrl = getattr(self, "_rolling", None)
        return {
            "enabled": ctrl is not None,
            "controller": ctrl.snapshot() if ctrl is not None else None,
            "live_config_keys": live_config_keys(),
            "config_reloads_total": dict(
                getattr(self, "config_reloads_total", None) or {}),
        }

    def version_status(self) -> dict:
        """/health ``version`` block: this frontend's package/schema/
        config identity, the cached per-engine blocks (refreshed on the
        engine-loop thread), and schema-mismatch rejection counts."""
        from vllm_tpu import versioning
        from vllm_tpu.versioning import version_block

        # check_schema() rejections anywhere in this process — READY
        # handshakes (attach + respawn), handoff/trace decodes — plus
        # the journal scan's inline stamp comparison.
        mismatches = dict(versioning.mismatch_total)
        journal = getattr(self, "journal", None)
        journal_mm = getattr(journal, "schema_mismatch_total", 0)
        if journal_mm:
            mismatches["journal"] = (
                mismatches.get("journal", 0) + journal_mm)
        config = getattr(self, "config", None)
        return {
            "frontend": version_block(
                config,
                config.model_config.model if config is not None
                else None),
            "engines": dict(
                getattr(self, "_engine_versions", None) or {}),
            "schema_mismatch_total": mismatches,
        }

    def set_live_config(self, updates: dict,
                        timeout_s: float = 30.0) -> dict:
        """Apply a vetted live-config update pool-wide without restart
        (POST /admin/config). Frontend-scope knobs apply in this
        process; engine-scope knobs broadcast over the ``set_config``
        utility RPC, marshalled onto the engine-loop thread (which owns
        the client sockets). Raises :class:`LiveConfigError` — the
        whole request is rejected — on any unknown key or out-of-range
        value."""
        try:
            frontend, engine = vet_live_config(updates)
        except LiveConfigError:
            self._count_config_reload("rejected")
            raise
        applied: list[str] = []
        inert: list[str] = []
        for key, value in frontend.items():
            (applied if self._apply_frontend_config(key, value)
             else inert).append(key)
        if engine:
            try:
                result = self._engine_set_config(engine, timeout_s)
            except Exception as e:
                self._count_config_reload("error")
                raise RuntimeError(
                    f"engine config push failed: {e}") from e
            applied += [f"{k} (engines)"
                        for k in result.get("applied", ())]
            inert += [f"{k} (engines)" for k in result.get("inert", ())]
        self._count_config_reload("ok")
        logger.info("live config applied: %s%s", applied,
                    f" (inert: {inert})" if inert else "")
        return {"applied": applied, "inert": inert}

    def _count_config_reload(self, outcome: str) -> None:
        counts = getattr(self, "config_reloads_total", None)
        if counts is None:
            counts = self.config_reloads_total = {}
        counts[outcome] = counts.get(outcome, 0) + 1

    def _apply_frontend_config(self, key: str, value: Any) -> bool:
        """One frontend-scope knob; returns False when the owning
        subsystem is not armed (the knob is inert, not an error)."""
        if key == "tenant_weights":
            from vllm_tpu.resilience.qos import parse_tenant_weights

            self.admission.fair_queue.set_weights(
                parse_tenant_weights(value))
            self.lifecycle.tenant_weights = value
            return True
        if key.startswith("brownout_"):
            ctrl = self._brownout
            if ctrl is None:
                return False
            setattr(ctrl.config, key[len("brownout_"):], value)
            return True
        if key.startswith("autoscale_"):
            ctrl = getattr(self, "_autoscale", None)
            if ctrl is None:
                return False
            setattr(ctrl, key[len("autoscale_"):], value)
            return True
        return False

    def _engine_set_config(self, updates: dict,
                           timeout_s: float) -> dict:
        if not hasattr(self.engine_core, "set_config"):
            return {"applied": [], "inert": sorted(updates)}
        return self.call_on_engine_thread(
            "set_config", updates).result(timeout=timeout_s)

    def call_on_engine_thread(self, method: str, *args) -> Future:
        """Queue ``engine_core.<method>(*args)`` for the engine-loop
        thread, which runs it between two steps, and return the future of
        its result. That thread owns the client's utility sockets and is
        the only one that may step the engine or run the profiler
        (``/start_profile`` and ``/stop_profile`` ARM their call here).
        With no loop running (tests, a sync embedding) the call is made
        at once, on the caller's thread."""
        fut: Future = Future()
        if self._thread is None or not self._thread.is_alive():
            try:
                fut.set_result(getattr(self.engine_core, method)(*args))
            except Exception as e:
                fut.set_exception(e)
        else:
            self._input_queue.put(("call", (method, args, fut)))
        return fut


    # -- QoS: brownout ladder + FIFO-vs-QoS A/B ------------------------

    def _is_batch_class(self, priority: int, params: SamplingParams) -> bool:
        """Whether a request is sheddable batch-class work under the
        brownout ladder: any priority > 0, or an SLO class listed in
        --brownout-shed-classes."""
        if priority and priority > 0:
            return True
        ctrl = self._brownout
        if ctrl is None or not params.slo_class:
            return False
        return params.slo_class in ctrl.config.shed_class_set()

    def poll_brownout(self) -> None:
        """Brownout-ladder tick (engine-loop thread): sample admission
        occupancy, per-engine queue depth, and worst-class SLO
        attainment; advance the ladder; push rung changes to every
        engine core. Throttled by --brownout-interval-s. The rung is
        re-pushed every second while elevated so an engine respawned
        mid-brownout (fresh scheduler at rung 0) converges back."""
        ctrl = self._brownout
        if ctrl is None:
            return
        now = time.monotonic()
        if now < self._brownout_next_t:
            return
        self._brownout_next_t = now + ctrl.config.interval_s
        lc = self.lifecycle
        inflight = len(self.output_processor.request_states)
        # Occupancy = how full the admission envelope is (whichever of
        # the request / prompt-token caps is more saturated). With no
        # caps configured this stays 0 and queue depth alone drives the
        # ladder.
        occ = 0.0
        if lc.max_inflight_requests:
            occ = inflight / lc.max_inflight_requests
        if lc.max_queued_prompt_tokens:
            occ = max(
                occ,
                self.admission.inflight_prompt_tokens
                / lc.max_queued_prompt_tokens,
            )
        engines = 1
        if hasattr(self.engine_core, "pool_status"):
            try:
                engines = max(
                    1, self.engine_core.pool_status().get("actual", 1))
            except Exception:
                engines = 1
        slo = None
        snap = self.output_processor.slo_attainment_snapshot()
        if snap:
            slo = min(v["attainment"] for v in snap.values())
        prev = ctrl.rung
        rung = ctrl.observe(
            occupancy=occ, queue_depth=inflight / engines,
            slo_attainment=slo, now=now,
        )
        if rung == prev and not (
            rung > 0 and now - self._brownout_push_t >= 1.0
        ):
            return
        if rung != prev:
            from vllm_tpu.resilience.qos import RUNG_ACTIONS

            logger.warning(
                "brownout rung %d -> %d (%s; occ=%.2f, depth=%.1f, "
                "slo=%s)", prev, rung,
                RUNG_ACTIONS.get(rung, "?"), occ, inflight / engines,
                "n/a" if slo is None else f"{slo:.2f}")
        self._brownout_push_t = now
        try:
            self.engine_core.set_brownout_rung(rung)
        except EngineRestartedError:
            raise
        except Exception:
            logger.exception("failed to push brownout rung to engines")

    def set_qos(self, enabled: bool) -> bool:
        """Live FIFO-vs-QoS A/B toggle (bench trace): flips WFQ
        admission, the brownout ladder's enforcement, and the
        engine-side QoS actions (spec suspension, chunk shrink, pressure
        preemption) in one switch. Returns the new state."""
        enabled = bool(enabled)
        self._qos_enabled = enabled
        self.admission.wfq_enabled = enabled
        try:
            if hasattr(self.engine_core, "set_qos_enabled"):
                self.engine_core.set_qos_enabled(enabled)
            if (enabled and self._brownout is not None
                    and self._brownout.rung > 0
                    and hasattr(self.engine_core, "set_brownout_rung")):
                self.engine_core.set_brownout_rung(self._brownout.rung)
        except Exception:
            logger.exception("failed to push QoS toggle to engines")
        return enabled

    def qos_status(self) -> dict:
        """QoS snapshot (WFQ state, per-tenant shed accounting, brownout
        ladder, preemption knobs) for /health and /metrics."""
        adm = self.admission.status()
        ctrl = self._brownout
        sc = self.config.scheduler_config
        return {
            "enabled": self._qos_enabled,
            "wfq_enabled": adm["wfq_enabled"],
            "wfq": adm["wfq"],
            "shed_by_tenant": adm["shed_by_tenant"],
            "brownout": ctrl.snapshot() if ctrl is not None else None,
            "pressure_preemption_s": sc.pressure_preemption_s,
            "max_preemptions_per_step": sc.max_preemptions_per_step,
            "max_preemptions_per_request": sc.max_preemptions_per_request,
        }

    def autoscale_status(self, drain: bool = False) -> dict | None:
        """Elastic-capacity snapshot (pool membership + controller) for
        /health and /metrics, or None when the client has no engine
        pool. ``drain=True`` (metrics renderer only) takes ownership of
        the pending drain-duration observations."""
        client = self.engine_core
        if not hasattr(client, "pool_status"):
            return None
        ctrl = getattr(self, "_autoscale", None)
        status: dict = {
            "enabled": ctrl is not None,
            "pool": client.pool_status(drain=drain),
        }
        if ctrl is not None:
            status["controller"] = ctrl.snapshot()
            status["kv_occupancy"] = getattr(self, "_autoscale_occ", None)
        return status

    def resilience_status(self) -> dict:
        """JSON-shaped liveness/restart snapshot (feeds /health and the
        resilience metrics)."""
        client = self.engine_core
        engines = (
            client.engine_status()
            if hasattr(client, "engine_status") else {}
        )
        # DP deployments expose the coordinator as a separate
        # control-plane entry (never folded into engine readiness).
        coordinator = (
            client.coordinator_status()
            if hasattr(client, "coordinator_status") else None
        )
        return {
            "engine_dead": self._dead,
            "recovery_enabled": self.resilience.enable_recovery,
            "engines": engines,
            "coordinator": coordinator,
            "requests_replayed_total": (
                self.journal.requests_replayed_total
                if self.journal is not None else 0
            ),
            "requests_failed_on_crash_total": (
                self.journal.requests_failed_on_crash_total
                if self.journal is not None else 0
            ),
            "requests_lost_on_restart_total": (
                self.journal.requests_lost_on_restart_total
                if self.journal is not None else 0
            ),
            # Step-watchdog trips observed client-side (MP engines that
            # hard-exited on a wedged device step).
            "step_watchdog_trips_total": getattr(
                self.engine_core, "watchdog_trips", 0),
            "replays_dropped_aborted_total": (
                self.replays_dropped_aborted_total),
            "requests_quarantined_total": (
                self.quarantine.requests_quarantined_total
                if self.quarantine is not None else 0
            ),
            "quarantine": (
                self.quarantine.status()
                if self.quarantine is not None else None
            ),
            # Multi-host mesh membership/recovery (None unless the
            # heartbeat ring is armed via VLLM_TPU_MESH_HB_ADDRS).
            "mesh": (
                client.mesh_status()
                if hasattr(client, "mesh_status") else None
            ),
        }

    def routing_status(self, drain: bool = False) -> dict | None:
        """DP routing-decision counters (prefix / least-loaded /
        round-robin) + prefix-index health, or None when the client does
        not do prefix-aware routing. Feeds /metrics (drain=True: takes
        ownership of pending prefix-hit lengths) and /health."""
        client = self.engine_core
        if hasattr(client, "routing_status"):
            return client.routing_status(drain=drain)
        return None

    def kv_fabric_status(self) -> dict:
        """Tiered-KV-fabric snapshot (per-tier occupancy, fetch
        outcomes, demotions, transferred bytes) — pool-merged under the
        DP client; {} when no fabric connector is configured."""
        client = self.engine_core
        if hasattr(client, "kv_fabric_status"):
            return client.kv_fabric_status()
        return {}

    def disagg_status(self, drain: bool = False) -> dict | None:
        """Disaggregated prefill/decode handoff snapshot (roles, pending
        handoffs, outcome counters, drained durations), or None when the
        pool has no engine roles. Feeds /metrics (drain=True takes
        ownership of pending handoff durations) and /health."""
        client = self.engine_core
        if hasattr(client, "disagg_status"):
            return client.disagg_status(drain=drain)
        return None

    def debug_deadletter(self) -> dict:
        """Dead-letter introspection (/debug/deadletter): quarantined
        poison requests with their strike history."""
        if self.quarantine is None:
            return {"enabled": False, "records": []}
        return {
            "enabled": True,
            "records": self.quarantine.deadletter.list(),
            "quarantine": self.quarantine.status(),
        }

    def debug_requests(self) -> dict:
        """Live request introspection (/debug/requests): in-flight
        requests (state, age, tokens emitted, KV blocks held) plus the
        bounded ring of recently finished requests with their per-phase
        timing breakdown."""
        snapshot = self.output_processor.debug_snapshot()
        slo = self.slo_status()
        if slo is not None:
            snapshot["slo"] = slo
        return snapshot

    def slo_status(self) -> dict | None:
        """SLO scoreboard snapshot: per-class sliding-window attainment
        (when targets are configured) and trace-capture counters (when
        recording). None when both are off — the scoreboard then has no
        live state to report."""
        op = self.output_processor
        reqtrace = getattr(self, "reqtrace", None)
        if reqtrace is None and not op.slo_targets:
            return None
        status: dict = {
            "targets": op.slo_targets or None,
            "attainment": op.slo_attainment_snapshot(),
        }
        if reqtrace is not None:
            status["trace"] = reqtrace.status()
        return status

    def is_ready(self) -> bool:
        """All engines initialized and up (readiness, distinct from
        liveness: a respawning rank makes the server NOT ready while
        /health still reports it serving degraded). A draining server is
        NOT ready: the load balancer must stop routing to it while
        in-flight requests run out."""
        if self._dead or self.admission.draining:
            return False
        client = self.engine_core
        return client.is_ready() if hasattr(client, "is_ready") else True

    def shutdown(self) -> None:
        # Ordering matters: suspend respawns FIRST, so the busy loop (or
        # a ZMQ input thread) observing a dead engine while we tear down
        # cannot race a respawn back to life against closing sockets.
        if hasattr(self.engine_core, "suspend_recovery"):
            self.engine_core.suspend_recovery()
        self._shutdown.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        self.engine_core.shutdown()
        # getattr: resilience tests build AsyncLLM via __new__, skipping
        # __init__ (and with it the recorder wiring).
        reqtrace = getattr(self, "reqtrace", None)
        if reqtrace is not None:
            reqtrace.close()
