"""EngineCore: the schedule -> execute -> update inner loop.

Reference analog: ``vllm/v1/engine/core.py:91`` (step :402). The process
wrapper (ZMQ busy loop) lives in ``engine/core_proc.py``; this class is the
in-proc core both paths share.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Iterable

from vllm_tpu.config import EngineConfig
from vllm_tpu.core.kv_cache_utils import make_block_hasher
from vllm_tpu.core.sched_output import STEP_PHASES, EngineCoreOutputs
from vllm_tpu.core.scheduler import Scheduler
from vllm_tpu.engine.executor import Executor
from vllm_tpu.logger import init_logger
from vllm_tpu.tracing import (
    compiles,
    startup,
    trace_async_begin,
    trace_async_end,
    trace_enabled,
    trace_instant,
    trace_span,
)
from vllm_tpu.request import EngineCoreRequest, Request, RequestStatus
from vllm_tpu.resilience.failpoints import fail_point

logger = init_logger(__name__)


class EngineCore:
    def __init__(self, config: EngineConfig, executor_class: type[Executor] | None = None) -> None:
        # `engine` on the start's timeline: all of this constructor less
        # the worker's phases inside `executor.initialize()`.
        with startup.phase("engine") as note:
            self._init(config, executor_class)
            note.append(f"{config.cache_config.num_gpu_blocks} KV blocks")

    def _init(self, config: EngineConfig, executor_class: type[Executor] | None) -> None:
        self.config = config.finalize().resolve_generation()
        executor_class = executor_class or Executor.get_class(config)
        self.executor = executor_class(config)
        num_blocks = self.executor.initialize()
        config.cache_config.num_gpu_blocks = num_blocks

        # Async (lag-1 pipelined) scheduling hides the device->host fetch
        # behind the next step's compute. Spec decode needs draft tokens on
        # the host between steps, so it forces the sync scheduler.
        self.async_scheduling = (
            config.scheduler_config.async_scheduling
            and not config.speculative_config.enabled
        )
        scheduler_cls: type[Scheduler] = Scheduler
        if self.async_scheduling:
            from vllm_tpu.core.async_scheduler import AsyncScheduler

            scheduler_cls = AsyncScheduler
        self._inflight: deque = deque()
        # The step currently inside executor.dispatch()/finalize() — not
        # (or no longer) tracked by _inflight, but very much on the
        # device. suspect_req_ids() must see it: a crash that unwinds
        # out of dispatch would otherwise blame only the PREVIOUS
        # pipelined batch and the quarantine would strike innocents
        # while the poison batch goes unrecorded.
        self._executing: SchedulerOutput | None = None
        # Cumulative seconds blocked fetching device results (lag-pipeline
        # stall; exported via SchedulerStats.pipeline_stall_s).
        self._stall_s = 0.0
        # Per-phase step durations accumulated since the last stats
        # snapshot (drained into SchedulerStats by _attach_engine_stats).
        self._phase_times: dict[str, list[float]] = {
            phase: [] for phase in STEP_PHASES
        }
        # Last dispatched batch occupancy + step-completion timestamps
        # (step-interval gauge).
        self._last_batch: tuple[int, int] = (0, 0)
        self._last_step_end: float | None = None
        self._step_interval_s = 0.0
        # What of the start and of compiling has reached a stats snapshot:
        # the start's phases go once, the compile counters when they moved.
        self._startup_sent = False
        self._compile_mark: tuple[int, int] | None = None
        # Request lifecycle phase per in-flight request, keyed by req id:
        # (trace_id, "queue" | "prefill" | "decode"). Only populated while
        # tracing is enabled — the async b/e span bookkeeping is pure
        # overhead otherwise.
        self._req_trace_phase: dict[str, tuple[str, str]] = {}
        # Outputs finalized outside step() (elastic-resize drain) waiting
        # for the next step() call to deliver them.
        self._drained_outputs: deque = deque()
        self._max_inflight = (
            min(
                config.scheduler_config.async_pipeline_depth,
                self.executor.max_concurrent_batches,
            )
            if self.async_scheduling
            else 1
        )

        from vllm_tpu.kv_connector import make_kv_connector

        self.kv_connector = make_kv_connector(
            config.cache_config.kv_connector,
            config.cache_config.kv_connector_cache_gb,
            config.cache_config.kv_connector_url,
            quant=config.cache_config.kv_fabric_quant,
            bind=config.cache_config.kv_fabric_bind,
            peers=config.cache_config.kv_fabric_peer_list,
            link_gbps=config.cache_config.kv_fabric_link_gbps,
        )
        if (
            self.kv_connector is not None
            and not config.cache_config.enable_prefix_caching
        ):
            logger.warning(
                "kv connector disabled: requires prefix caching (content "
                "hashes)"
            )
            self.kv_connector = None
        self.structured_output_manager = self._make_structured_output_manager()
        self.scheduler = scheduler_cls(
            config.scheduler_config,
            config.cache_config,
            structured_output_manager=self.structured_output_manager,
            kv_connector=self.kv_connector,
        )
        # The runner gathers grammar bitmasks from a device-resident table
        # it syncs from the manager (in-proc share; becomes an RPC-shipped
        # table under a future proc split).
        self.executor.collective_rpc(
            "set_structured_output_manager", self.structured_output_manager
        )
        self._device_block_bytes = 0
        if self.kv_connector is not None:
            self.executor.collective_rpc("set_kv_connector", self.kv_connector)
            try:
                self._device_block_bytes = int(self.executor.collective_rpc(
                    "kv_cache_block_bytes")[0])
            except Exception:
                pass  # byte gauge reads 0 for the device tier
            if hasattr(self.kv_connector, "set_roofline"):
                # Hand the fabric's cost model the worker's measured
                # RooflineModel: the fetch-vs-recompute arbiter prices
                # recompute by what the device can do.
                try:
                    from vllm_tpu.metrics.roofline import RooflineModel

                    info = self.executor.collective_rpc("roofline_info")[0]
                    if info:
                        self.kv_connector.set_roofline(
                            RooflineModel.from_dict(info))
                except Exception as exc:
                    logger.warning(
                        "kv fabric: roofline unavailable (%s); cost model "
                        "uses defaults", exc)
            if hasattr(self.kv_connector, "note_device_eviction"):
                # Demotion hook: HBM prefix-cache evictions are reported
                # as device-tier demotions.
                self.scheduler.kv_cache_manager.block_pool.demote_sink = (
                    self.kv_connector.note_device_eviction)
        self._block_hasher = (
            make_block_hasher(config.cache_config.block_size)
            if config.cache_config.enable_prefix_caching
            else None
        )
        self._lora_names: set[str] = set()
        # Multi-host mesh fault tolerance: armed only when the launcher
        # provides a heartbeat ring (VLLM_TPU_MESH_HB_ADDRS); None on
        # single-host deployments — zero overhead.
        from vllm_tpu.resilience.mesh_recovery import MeshRecoveryManager

        self.mesh_recovery = MeshRecoveryManager.from_env(
            getattr(config, "resilience_config", None))
        if self.mesh_recovery is not None:
            self.mesh_recovery.start()

    def _make_structured_output_manager(self):
        from vllm_tpu.engine.input_processor import get_tokenizer
        from vllm_tpu.structured_output import StructuredOutputManager

        model_config = self.config.model_config

        def tokenizer_factory():
            try:
                return get_tokenizer(model_config)
            except Exception:
                return None

        return StructuredOutputManager(tokenizer_factory)

    # ------------------------------------------------------------------

    def add_request(self, request: EngineCoreRequest) -> None:
        if request.lora_name is not None and (
            request.lora_name not in self._lora_names
        ):
            raise ValueError(
                f"unknown LoRA adapter {request.lora_name!r}; "
                f"loaded: {sorted(self._lora_names)}"
            )
        req = Request.from_engine_core_request(request, self._block_hasher)
        trace_instant(
            "request_arrival", req_id=request.request_id,
            trace_id=request.trace_id,
            prompt_tokens=len(request.prompt_token_ids),
        )
        if trace_enabled() and request.trace_id is not None:
            self._req_trace_phase[request.request_id] = (
                request.trace_id, "queue"
            )
            trace_async_begin(
                "queue", request.trace_id, req_id=request.request_id
            )
        self.scheduler.add_request(req)

    def abort_requests(self, request_ids: Iterable[str]) -> None:
        for rid in request_ids:
            entry = self._req_trace_phase.pop(rid, None)
            if entry is not None:
                trace_async_end(entry[1], entry[0], req_id=rid)
        self.scheduler.finish_requests(request_ids, RequestStatus.FINISHED_ABORTED)

    def has_unfinished_requests(self) -> bool:
        return bool(self._inflight) or self.scheduler.has_unfinished_requests()

    def get_load(self) -> tuple[int, int]:
        """(num_waiting, num_running) for the DP coordinator.
        Reference analog: SchedulerStats counts in EngineCoreOutputs."""
        return (
            len(self.scheduler.waiting),
            len(self.scheduler.running) + len(self._inflight),
        )

    def execute_dummy_batch(self) -> None:
        """One no-request device step, so idle DP ranks keep participating
        in cross-rank collectives during a wave (reference: ``core.py:731``
        ``execute_dummy_batch``)."""
        self.executor.collective_rpc("execute_dummy_batch")

    def step(self) -> EngineCoreOutputs:
        """One engine iteration.

        Sync mode: schedule -> execute -> update (reference ``core.py:402``).
        Async mode: keep up to 2 steps in flight — dispatch step N+1 before
        fetching step N's tokens, so the host->device->host turnaround of a
        step overlaps the next step's compute (reference
        ``step_with_batch_queue`` core.py:443 + AsyncScheduler).
        """
        if self._drained_outputs:
            # Tokens finalized during an elastic-resize drain: deliver
            # before any new work.
            return self._drained_outputs.popleft()
        # Persist freed requests' blocks BEFORE any new scheduling can
        # hand those blocks to someone else (in-flight steps were
        # scheduled before the free, so the payload is still intact).
        self.flush_kv_saves()
        while (
            len(self._inflight) < self._max_inflight
            and self.scheduler.has_unfinished_requests()
        ):
            fail_point("engine_core.step.schedule")
            t0 = time.monotonic()
            with trace_span("schedule"):
                scheduler_output = self.scheduler.schedule()
            self._phase_times["schedule"].append(time.monotonic() - t0)
            if scheduler_output.total_num_scheduled_tokens == 0:
                # Not dispatched: hand the drained finished ids (and any
                # encoder-cache frees) back so the runner still gets them
                # on the next dispatched step.
                self.scheduler.finished_req_ids |= scheduler_output.finished_req_ids
                self.scheduler._pending_preempted |= (
                    scheduler_output.preempted_req_ids
                )
                self.scheduler._pending_encoder_frees = (
                    scheduler_output.free_encoder_input_ids
                    + self.scheduler._pending_encoder_frees
                )
                break
            if self._req_trace_phase:
                # Newly scheduled requests leave the queue and enter
                # prefill (resumed-from-preemption requests live in the
                # cached set and keep their current phase).
                for nrd in scheduler_output.scheduled_new_reqs:
                    entry = self._req_trace_phase.get(nrd.req_id)
                    if entry is not None and entry[1] == "queue":
                        trace_async_end("queue", entry[0], req_id=nrd.req_id)
                        trace_async_begin(
                            "prefill", entry[0], req_id=nrd.req_id,
                            prompt_tokens=len(nrd.prompt_token_ids),
                        )
                        self._req_trace_phase[nrd.req_id] = (
                            entry[0], "prefill"
                        )
            fail_point(
                "engine_core.step.dispatch",
                lambda: f"tokens="
                f"{scheduler_output.total_num_scheduled_tokens}",
            )
            t0 = time.monotonic()
            # Track the batch across the dispatch call: if it raises (or
            # wedges under the step watchdog), suspect_req_ids() must
            # report THIS batch, which _inflight does not know yet.
            self._executing = scheduler_output
            with trace_span(
                "dispatch",
                tokens=scheduler_output.total_num_scheduled_tokens,
                reqs=scheduler_output.num_reqs,
            ):
                handle = self.executor.dispatch(scheduler_output)
            self._phase_times["dispatch"].append(time.monotonic() - t0)
            self._note_runner_phase("prepare", handle, "prep_s")
            self._last_batch = (
                scheduler_output.total_num_scheduled_tokens,
                scheduler_output.num_reqs,
            )
            self._inflight.append((scheduler_output, handle))
            self._executing = None
        if not self._inflight:
            failed = self.scheduler.drain_failed()
            return failed if failed is not None else EngineCoreOutputs()
        # Peek, finalize, then pop: a crash inside finalize() must still
        # attribute THIS batch (suspect_req_ids walks _inflight).
        scheduler_output, handle = self._inflight[0]
        fail_point("engine_core.step.finalize")
        with trace_span("finalize"):
            t0 = time.monotonic()
            runner_output = self.executor.finalize(handle)
            # Time blocked on the device fetch: ~0 when the lag-N overlap
            # is winning, the whole device step when it is not.
            stall = time.monotonic() - t0
            self._stall_s += stall
        self._inflight.popleft()
        self._phase_times["finalize"].append(stall)
        self._note_runner_phase("fetch", handle, "wait_s")
        t0 = time.monotonic()
        with trace_span("update"):
            outputs = self.scheduler.update_from_output(
                scheduler_output, runner_output
            )
        self._phase_times["update"].append(time.monotonic() - t0)
        # Disaggregated handoffs flush IN this step, not at the top of
        # the next one: the decode engine is stalled on the push, so its
        # latency is on the request's critical path — unlike ordinary
        # cold saves, which can wait out a sustained-load streak.
        self._flush_handoff_pushes()
        now = time.monotonic()
        if self._last_step_end is not None:
            self._step_interval_s = now - self._last_step_end
        self._last_step_end = now
        self._attach_engine_stats(outputs)
        for o in outputs.outputs:
            if self._req_trace_phase:
                self._trace_request_progress(o)
            if o.finish_reason is not None:
                trace_instant(
                    "request_finish", req_id=o.req_id,
                    finish_reason=str(o.finish_reason),
                )
        return outputs

    def _note_runner_phase(self, phase: str, handle, key: str) -> None:
        """The runner's own timing of this step, left on its handle
        (``prep_s`` inside dispatch, ``wait_s`` inside finalize), as a
        sample of ``phase``."""
        seconds = getattr(handle, key, None)
        if seconds is not None:
            self._phase_times[phase].append(seconds)

    def _trace_request_progress(self, o) -> None:
        """Advance a request's async lifecycle span on its outputs: first
        token closes prefill and opens decode; a finish closes whatever
        phase the request was in."""
        entry = self._req_trace_phase.get(o.req_id)
        if entry is None:
            return
        trace_id, phase = entry
        if o.new_token_ids and phase == "prefill":
            trace_async_end("prefill", trace_id, req_id=o.req_id)
            trace_async_begin("decode", trace_id, req_id=o.req_id)
            phase = "decode"
            self._req_trace_phase[o.req_id] = (trace_id, phase)
        if o.finish_reason is not None:
            trace_async_end(
                phase, trace_id, req_id=o.req_id,
                finish_reason=str(o.finish_reason),
            )
            del self._req_trace_phase[o.req_id]

    def _attach_engine_stats(self, outputs: EngineCoreOutputs) -> None:
        """Fold engine/worker-side counters into the step's stats snapshot
        (bucket compile/hit counts of the jitted-step cache, pipeline
        stall time). Reference analog: the compile/stall observability of
        ``vllm/v1/metrics`` around CUDA-graph capture."""
        stats = outputs.scheduler_stats
        if stats is None:
            return
        stats.pipeline_stall_s = self._stall_s
        if not self._startup_sent:
            self._startup_sent = True
            stats.startup = startup.snapshot()
        # Drain the per-phase step durations accumulated since the last
        # snapshot into this one (exactly-once export).
        for phase in STEP_PHASES:
            setattr(stats, f"step_{phase}_times", self._phase_times[phase])
        self._phase_times = {phase: [] for phase in STEP_PHASES}
        stats.batch_num_tokens, stats.batch_num_reqs = self._last_batch
        budget = self.config.scheduler_config.max_num_batched_tokens
        stats.batch_occupancy = (
            stats.batch_num_tokens / budget if budget else 0.0
        )
        stats.step_interval_s = self._step_interval_s
        runner = getattr(
            getattr(self.executor, "worker", None), "runner", None
        )
        if runner is not None:
            stats.bucket_compiles = getattr(runner, "bucket_compiles", 0)
            stats.bucket_hits = getattr(runner, "bucket_hits", 0)
            stats.step_launches = getattr(runner, "step_launches", 0)
            stats.decode_only_launches = getattr(
                runner, "decode_only_launches", 0
            )
            stats.launch_sampled_tokens = getattr(
                runner, "launch_sampled_tokens", 0
            )
            for name in ("steps_by_kind", "step_tokens_by_kind",
                         "attn_context_tokens_by_kind", "attn_ragged_pairs",
                         "attn_decode_tiles", "moe_gmm_visits",
                         "diffusion_head_positions",
                         "diffusion_head_steps_by_rung"):
                setattr(stats, name, dict(getattr(runner, name, {})))
            stats.diffusion_passes_by_kind = dict(
                getattr(runner, "diffusion_passes_by_kind", {}))
            for name in ("diffusion_committed_tokens",
                         "diffusion_stores_fused", "moe_assignments",
                         "moe_experts_hit", "moe_pad_pairs_skipped",
                         "linear_attn_state_rows",
                         "window_attn_rows", "window_attn_pairs",
                         "attn_sink_rows"):
                setattr(stats, name, getattr(runner, name, 0))
            stats.linear_attn_tokens_by_kind = dict(
                getattr(runner, "linear_attn_tokens_by_kind", {}))
            stats.state_slots_in_use = len(
                getattr(runner, "_state_slot_of", ()))
            stats.step_first_use_s = getattr(runner, "first_use_s", 0.0)
            # Compiling moves these and nothing else does: one compare a
            # step, the copies only after a first use or a compile.
            mark = (compiles.heard, stats.bucket_compiles)
            if mark != self._compile_mark:
                self._compile_mark = mark
                stats.compile_seconds = dict(compiles.seconds)
                stats.compile_cache = dict(compiles.cache)
                stats.step_first_use_stage_s = dict(
                    getattr(runner, "first_use_stage_s", {}))
            stats.prep_fallback_rows = getattr(
                runner, "prep_fallback_rows", 0
            )
            stats.sampler_kernel_launches = getattr(
                runner, "sampler_kernel_launches", 0
            )
            stats.sampler_fallback_rows = getattr(
                runner, "sampler_fallback_rows", 0
            )
            stats.numeric_guard_trips = dict(
                getattr(runner, "numeric_guard_trips", {})
            )
            watchdog = getattr(runner, "watchdog", None)
            if watchdog is not None:
                stats.step_watchdog_trips = watchdog.trips
        if self.kv_connector is not None and hasattr(
            self.kv_connector, "fabric_stats"
        ):
            stats.kv_fabric = self.kv_fabric_status()

    def flush_kv_saves(self) -> None:
        """Ship pending request-finish KV saves to the worker connector.

        Called at the top of every step, and by the engine-core proc's
        idle branch: a block demoted at the finish of the LAST running
        request must still reach the host tier promptly — peer engines
        query it over the fabric — not wait for this engine's next
        request to trigger a step."""
        if self.kv_connector is not None:
            saves = self.scheduler.take_pending_kv_saves()
            if saves:
                self.executor.collective_rpc("kv_connector_save", saves)

    def _flush_handoff_pushes(self) -> None:
        """Ship this step's finished-handoff KV to decode peers. Hoists
        the save flush so every pushed key is host-tier-resident first
        (take_pending_kv_saves covers the same finishes)."""
        if self.kv_connector is None:
            return
        handoffs = self.scheduler.take_pending_handoffs()
        if not handoffs:
            return
        self.flush_kv_saves()
        for req_id, url, keys in handoffs:
            self.executor.collective_rpc(
                "kv_connector_push", req_id, url, keys)

    def disagg_reserve(self, req_id: str, n_blocks: int) -> int:
        """Decode-side handoff admission (client utility RPC): reserve
        host-tier bytes for an incoming push."""
        if self.kv_connector is None:
            return 0
        res = self.executor.collective_rpc(
            "kv_connector_reserve", req_id, n_blocks)
        return int(res[0]) if res else 0

    def kv_fabric_status(self) -> dict:
        """Tiered-fabric snapshot (tier occupancy in blocks AND bytes,
        fetch/push outcomes, demotions, transferred bytes) with the
        device tier folded in from the block pool's resident-hash map."""
        if self.kv_connector is None or not hasattr(
            self.kv_connector, "fabric_stats"
        ):
            return {}
        snap = self.kv_connector.fabric_stats()
        pool = self.scheduler.kv_cache_manager.block_pool
        n_device = len(pool.cached_block_hash_to_block)
        snap["tier_blocks"]["device"] = n_device
        if "tier_bytes" in snap:
            # Device blocks live unquantized at the cache dtype; size
            # them from the fabric's encoded-block EWMA is wrong, so use
            # the runner-reported per-block byte size when known.
            snap["tier_bytes"]["device"] = n_device * getattr(
                self, "_device_block_bytes", 0)
        return snap

    def suspect_req_ids(self) -> list[str]:
        """Request ids that were scheduled on the device when this call
        runs — the suspect set attached to a crash/hang notification so
        the frontend's quarantine can attribute the death to the batch
        that was executing, not every journaled request. The batch whose
        dispatch is unwinding (``_executing``) comes first: it is the
        most likely culprit and is NOT in ``_inflight`` yet."""
        ids: list[str] = []
        executing = self._executing
        if executing is not None:
            ids.extend(executing.num_scheduled_tokens.keys())
        for scheduler_output, _handle in self._inflight:
            ids.extend(scheduler_output.num_scheduled_tokens.keys())
        # A crash outside any dispatch/finalize (scheduler bug, stats
        # path) leaves both empty; fall back to the running batch.
        if not ids:
            ids = [r.request_id for r in self.scheduler.running]
        seen: set[str] = set()
        return [r for r in ids if not (r in seen or seen.add(r))]

    def reset_prefix_cache(self) -> bool:
        ok = self.scheduler.kv_cache_manager.reset_prefix_cache()
        # Publish the clear even on an idle engine (no schedule() to ride):
        # subscribed routers must not keep a stale resident-blocks view.
        if self.scheduler.kv_event_publisher is not None:
            self.scheduler.kv_event_publisher.flush()
        return ok

    def set_brownout_rung(self, rung: int) -> bool:
        """Apply a brownout-ladder rung pushed by the frontend QoS
        controller (resilience/qos.py). The scheduler acts on it from
        the next schedule(): >= 1 suspends speculation, >= 2 shrinks
        prefill chunks, >= 4 preempts batch-class decodes."""
        self.scheduler.brownout_rung = max(0, int(rung))
        return True

    def set_qos_enabled(self, enabled: bool) -> bool:
        """Live FIFO-vs-QoS A/B switch (the trace bench flips it): off
        disables pressure preemption and zeroes the brownout rung;
        VLLM_TPU_DISABLE_QOS is the env spelling of the same switch."""
        self.scheduler.disable_qos = not enabled
        if not enabled:
            self.scheduler.brownout_rung = 0
        return True

    def set_config(self, updates: dict) -> dict:
        """Live-config RPC (resilience/rolling.py): apply engine-scope
        knobs from the vetted live-updatable set without a restart. The
        scheduler re-reads its config fields every schedule(), so a
        plain field write takes effect on the next step. Keys are vetted
        frontend-side (vet_live_config); an unknown key arriving here
        anyway is a bug and raises (the utility reply carries it back as
        a loud typed error, never a silent no-op). Returns
        ``{"applied": [...], "inert": [...]}`` — "inert" keys were
        accepted but target a subsystem this engine doesn't run (e.g.
        adaptive-spec watermarks without --spec-adaptive)."""
        applied: list[str] = []
        inert: list[str] = []
        sched = self.scheduler
        for key, value in updates.items():
            if key == "long_prefill_token_threshold":
                sched.config.long_prefill_token_threshold = int(value)
            elif key == "pressure_preemption_s":
                sched.config.pressure_preemption_s = float(value)
            elif key == "max_preemptions_per_step":
                sched.config.max_preemptions_per_step = int(value)
            elif key in ("spec_adaptive_high_watermark",
                         "spec_adaptive_low_watermark"):
                adaptive = getattr(sched, "adaptive_spec", None)
                if adaptive is None:
                    inert.append(key)
                    continue
                attr = ("high_watermark" if key.endswith("high_watermark")
                        else "low_watermark")
                setattr(adaptive, attr, float(value))
            else:
                raise ValueError(
                    f"set_config: {key!r} is not an engine-scope "
                    f"live-updatable knob")
            applied.append(key)
        return {"applied": applied, "inert": inert}

    def probe(self, n_tokens: int = 4,
              prompt_token_ids: list[int] | None = None) -> list[int]:
        """Health-gate probe (resilience/rolling.py): run one tiny
        self-contained generation through the full schedule -> execute ->
        update path and return the sampled token ids. The rolling
        upgrade gates a routing-masked newcomer on N of these
        succeeding; greedy + ignore_eos makes the result deterministic
        for a given checkpoint, so the driver can additionally compare
        probe outputs across engines. Raises on any failure — a probe
        that can't produce tokens IS the gate signal."""
        from vllm_tpu.sampling_params import SamplingParams

        self._probe_seq = getattr(self, "_probe_seq", 0) + 1
        rid = f"_probe-{self._probe_seq}"
        self.add_request(EngineCoreRequest(
            request_id=rid,
            prompt_token_ids=list(prompt_token_ids or (1, 2, 3, 4)),
            sampling_params=SamplingParams(
                temperature=0.0, max_tokens=max(1, int(n_tokens)),
                ignore_eos=True),
        ))
        tokens: list[int] = []
        for _ in range(512):
            outputs = self.step()
            for out in outputs.outputs:
                if out.req_id != rid:
                    continue
                tokens.extend(out.new_token_ids)
                if out.finish_reason is not None:
                    if out.finish_reason == "error":
                        raise RuntimeError(
                            f"probe request failed: {out.stop_reason!r}")
                    if not tokens:
                        raise RuntimeError(
                            "probe finished without emitting tokens")
                    return tokens
        self.abort_requests([rid])
        raise RuntimeError(
            f"probe did not finish within the step budget "
            f"({len(tokens)}/{n_tokens} tokens)")

    def version_status(self) -> dict:
        """The /health ``version`` block for this engine (utility RPC):
        package + schema version, config hash, checkpoint path and its
        mtime-derived weights fingerprint. update_weights() changes the
        fingerprint the next time this is asked — the upgrade e2e
        asserts the newcomer's differs from the victim's."""
        from vllm_tpu.versioning import version_block

        return version_block(
            config=self.config,
            model_path=self.config.model_config.model,
        )

    # ------------------------------------------------------------------
    # Sleep / wake / weight reload (reference: core.py:673 sleep, :711
    # wake_up; gpu_worker.py:978 update_weights)
    # ------------------------------------------------------------------

    def sleep(self, level: int = 1) -> bool:
        assert not self.scheduler.has_unfinished_requests(), (
            "cannot sleep with unfinished requests"
        )
        # Drain in-flight steps scheduled past the last finish (their
        # outputs are stale and identity-guarded away).
        while self._inflight:
            self.step()
        # The KV cache is discarded; any cached prefixes are invalid (the
        # method also publishes the clear — a sleeping engine runs no
        # schedule() to ride).
        self.reset_prefix_cache()
        self.executor.collective_rpc("sleep", level)
        self._asleep = True
        return True

    def wake_up(self) -> bool:
        self.executor.collective_rpc("wake_up")
        self._asleep = False
        return True

    def is_sleeping(self) -> bool:
        return getattr(self, "_asleep", False)

    def save_sharded_state(self, path: str) -> bool:
        """Dump the assembled weights for fast reload (reference:
        ``save_sharded_state`` gpu_worker.py:939)."""
        self.executor.collective_rpc("save_sharded_state", path)
        return True

    def reinitialize_distributed(self, new_tp: int) -> bool:
        """Elastic EP: resize the tp/ep world at runtime (reference:
        ``EngineCore.reinitialize_distributed`` core.py:1865 +
        ``vllm/distributed/elastic_ep/``).

        Serving pauses for the re-mesh: in-flight steps drain (their
        executables belong to the old mesh), running requests are
        preempted (KV content does not survive the resize), the prefix
        cache resets, and the worker reshards weights over the new mesh
        and rebuilds its runner. Preempted requests resume from their
        token ids on the next step — nothing is aborted.
        """
        assert not getattr(self, "_asleep", False), (
            "cannot resize a sleeping engine; wake_up first"
        )
        # Validate constraints BEFORE the destructive drain/preempt/reset:
        # a rejected resize must not pay preemption or lose the prefix
        # cache (ADVICE r4 #1).
        self.executor.collective_rpc("validate_parallel_resize", new_tp)
        # Drain in-flight handles WITHOUT scheduling new work (step()
        # would keep refilling the pipeline while requests are active
        # and never converge). Outputs produced here are buffered and
        # returned by the next step() calls — tokens must not be lost.
        while self._inflight:
            scheduler_output, handle = self._inflight.popleft()
            runner_output = self.executor.finalize(handle)
            outputs = self.scheduler.update_from_output(
                scheduler_output, runner_output
            )
            if outputs.outputs:
                self._drained_outputs.append(outputs)
        if self.kv_connector is not None:
            # Pending external saves read KV payloads by block id — they
            # must flush BEFORE the re-mesh discards the cache content.
            saves = self.scheduler.take_pending_kv_saves()
            if saves:
                self.executor.collective_rpc("kv_connector_save", saves)
        sched = self.scheduler
        # Reversed so the per-victim prepend restores FCFS order in the
        # waiting queue.
        for request in reversed(sched.running):
            sched._preempt(request)
        sched.running.clear()
        self.reset_prefix_cache()
        self.executor.collective_rpc("reinitialize_parallel", new_tp)
        return True

    # ------------------------------------------------------------------
    # Multi-host mesh fault tolerance (host death -> supervised shrink)
    # ------------------------------------------------------------------

    def mesh_status(self) -> dict | None:
        """Mesh membership/recovery status for /health, or None when mesh
        monitoring is not armed."""
        if self.mesh_recovery is None:
            return None
        return self.mesh_recovery.status()

    def poll_mesh_recovery(self) -> dict | None:
        """Busy-loop hook: notice membership changes and drive recovery.

        Returns None when nothing happened, else a recovery report
        ``{"lost_req_ids", "reason", "status"}`` the client layer turns
        into an EngineRestartedError so the frontend journal-replays the
        interrupted requests. A recovery that FAILS raises
        MeshRecoveryError — the busy loop must let it unwind so the
        process dies cleanly (never serve half-meshed).
        """
        if self.mesh_recovery is None:
            return None
        decision = self.mesh_recovery.poll()
        if decision is None:
            return None
        return self._recover_mesh(decision)

    def _recover_mesh(self, decision: dict) -> dict:
        from vllm_tpu.resilience.mesh_recovery import MeshRecoveryError

        action = decision["action"]
        logger.warning("mesh %s: lost=%s rejoined=%s epoch=%d — starting "
                       "supervised recovery", action, decision["lost"],
                       decision["rejoined"], decision["epoch"])
        self.mesh_recovery.begin_recovery()
        try:
            # Every unfinished request is interrupted: the in-flight
            # steps' device arrays span the dead world (shrink) or the
            # stale one (grow), and KV content does not survive the
            # re-mesh either way. Collect BEFORE aborting.
            lost_req_ids: list[str] = []
            for scheduler_output, _handle in self._inflight:
                lost_req_ids.extend(
                    scheduler_output.num_scheduled_tokens.keys())
            if self._executing is not None:
                lost_req_ids.extend(
                    self._executing.num_scheduled_tokens.keys())
            lost_req_ids.extend(
                r.request_id for r in self.scheduler.running)
            lost_req_ids.extend(
                r.request_id for r in self.scheduler.waiting)
            lost_req_ids = list(dict.fromkeys(lost_req_ids))
            # DISCARD in-flight handles without finalizing: a finalize is
            # a device sync that can hang forever on a collective whose
            # peer is dead. The arrays are garbage now anyway.
            self._inflight.clear()
            self._executing = None
            self._drained_outputs.clear()
            self.abort_requests(lost_req_ids)
            self.reset_prefix_cache()
            # Re-bootstrap the surviving hosts at the new world size and
            # reshard/reload weights over the shrunken (or regrown) mesh.
            world = self.mesh_recovery.survivor_world()
            self.executor.collective_rpc(
                "reinitialize_mesh",
                *(world if world is not None else (None, None, None)))
        except Exception as exc:
            self.mesh_recovery.finish_recovery(ok=False)
            raise MeshRecoveryError(
                f"mesh {action} recovery failed: {exc}") from exc
        self.mesh_recovery.finish_recovery(ok=True)
        return {
            "lost_req_ids": lost_req_ids,
            "reason": (f"mesh {action}: lost ranks "
                       f"{decision['lost'] or decision['rejoined']}"),
            "status": self.mesh_recovery.status(),
        }

    def update_weights(self, path: str) -> bool:
        assert not self.scheduler.has_unfinished_requests(), (
            "cannot swap weights with unfinished requests"
        )
        while self._inflight:
            self.step()
        self.executor.collective_rpc("update_weights", path)
        # version_status()'s weights fingerprint must track what is
        # actually resident, not what the engine booted with.
        self.config.model_config.model = path
        return True

    def receive_weights(self, port: int, timeout: float = 300.0) -> int:
        """Disk-free RL weight push: listen on ``port`` for one streamed
        transfer and apply it in place (reference:
        ``distributed/weight_transfer/`` collective push)."""
        if port <= 0:
            # The blocking utility RPC cannot hand an OS-chosen ephemeral
            # port back to the trainer; require an explicit one.
            raise ValueError(
                "receive_weights needs an explicit port (port=0 would "
                "bind an undiscoverable ephemeral port)"
            )
        assert not self.scheduler.has_unfinished_requests(), (
            "cannot swap weights with unfinished requests"
        )
        while self._inflight:
            self.step()
        [n] = self.executor.collective_rpc("receive_weights", port, timeout)
        return n

    def push_weights_to(self, host: str, port: int,
                        timeout: float = 300.0) -> int:
        """Elastic scale-up re-seed, donor side: stream this engine's
        resident weights to a newcomer listening on ``host:port`` over
        the weight-transfer push path. Unlike :meth:`receive_weights`
        this does NOT require a quiesced engine — params are immutable
        device arrays, so a serving peer can donate (the utility RPC
        stalls its step loop for the transfer, which is why the client
        picks the least-loaded donor)."""
        [n] = self.executor.collective_rpc(
            "push_weights_to", host, port, timeout)
        return n

    # -- live fabric peer membership (elastic capacity) ----------------

    def kv_fabric_add_peer(self, url: str) -> bool:
        """Admit a scaled-up engine's fabric server to the peer list."""
        if self.kv_connector is None or not hasattr(
            self.kv_connector, "add_peer"
        ):
            return False
        self.kv_connector.add_peer(url)
        return True

    def kv_fabric_remove_peer(self, url: str) -> bool:
        """Retire a drained engine's fabric server from the peer list."""
        if self.kv_connector is None or not hasattr(
            self.kv_connector, "remove_peer"
        ):
            return False
        self.kv_connector.remove_peer(url)
        return True

    def kv_fabric_drain(self) -> int:
        """Scale-down demotion: flush pending saves, then ship this
        engine's host-tier KV to surviving peers. Returns the number of
        blocks shipped (0 when no fabric / no peers — best-effort, the
        fabric is a cache)."""
        if self.kv_connector is None or not hasattr(
            self.kv_connector, "drain_host_to_peers"
        ):
            return 0
        self.flush_kv_saves()
        return int(self.kv_connector.drain_host_to_peers())

    def add_lora(self, name: str, path: str) -> bool:
        ok = self.executor.collective_rpc("add_lora", name, path)[0]
        if ok:
            self._lora_names.add(name)
        return ok

    def remove_lora(self, name: str) -> bool:
        ok = self.executor.collective_rpc("remove_lora", name)[0]
        self._lora_names.discard(name)
        return ok

    def list_loras(self) -> list[str]:
        return self.executor.collective_rpc("list_loras")[0]

    def start_profile(self, trace_dir: str | None = None,
                      python_tracer_level: int = 0) -> dict:
        """Start a JAX profile (and with it the engine's own spans on the
        profile's clock). With ``stop_profile`` the one way to profile:
        HTTP ``/start_profile`` and the multiprocess utility call come
        through here, and only on the thread that calls ``step()``,
        between two steps. Raises ``ProfilerBusyError`` while a profile
        runs."""
        return self.executor.collective_rpc(
            "start_profile", trace_dir, python_tracer_level)[0]

    def stop_profile(self) -> dict:
        """Stop the running profile: its directory, the seconds traced and
        the seconds the stop itself took."""
        return self.executor.collective_rpc("stop_profile")[0]

    def shutdown(self) -> None:
        if self.mesh_recovery is not None:
            self.mesh_recovery.stop()
        if self.structured_output_manager is not None:
            self.structured_output_manager.shutdown()
        if self.scheduler.kv_event_publisher is not None:
            self.scheduler.kv_event_publisher.flush()
            self.scheduler.kv_event_publisher.close()
        self.executor.shutdown()
