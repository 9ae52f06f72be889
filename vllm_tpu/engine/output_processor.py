"""Engine-core outputs -> user-facing RequestOutputs.

Reference analog: ``vllm/v1/engine/output_processor.py:413`` — per-request
frontend state (detokenizer, logprobs assembly, metrics), stop-string
aborts flowing back to the engine core.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any

from vllm_tpu.core.sched_output import EngineCoreOutput
from vllm_tpu.engine.detokenizer import IncrementalDetokenizer
from vllm_tpu.outputs import (
    CompletionOutput,
    Logprob,
    RequestMetrics,
    RequestOutput,
)
from vllm_tpu.sampling_params import RequestOutputKind, SamplingParams
from vllm_tpu.tracing import trace_async_begin, trace_async_end, trace_span


class RequestState:
    def __init__(
        self,
        request_id: str,
        prompt_text: str | None,
        prompt_token_ids: list[int],
        params: SamplingParams,
        tokenizer: Any,
        arrival_time: float,
        queue: Any | None = None,
        trace_id: str | None = None,
    ) -> None:
        self.request_id = request_id
        self.prompt_text = prompt_text
        self.prompt_token_ids = prompt_token_ids
        self.params = params
        # Effective SLO class label for per-class telemetry (unlabeled
        # requests land in DEFAULT_SLO_CLASS so classes partition
        # traffic). A reference to an existing string — no allocation.
        from vllm_tpu.metrics.stats import DEFAULT_SLO_CLASS

        self.slo_label = params.slo_class or DEFAULT_SLO_CLASS
        # Per-request ITL samples (ms), kept ONLY when the processor
        # needs per-request verdicts (trace recording or configured SLO
        # targets); None otherwise so the default hot path allocates
        # nothing per token.
        self.itl_track: list[float] | None = None
        self.detokenizer = IncrementalDetokenizer(
            tokenizer if params.detokenize else None, prompt_token_ids, params
        )
        self.metrics = RequestMetrics(arrival_time=arrival_time)
        self.last_token_time = arrival_time
        self.logprobs: list[dict[int, Logprob]] = []
        # Generation by diffusion: the block pass that committed each
        # output token (None until the engine reports one).
        self.commit_passes: list[int] | None = None
        # Observability: trace correlation id + per-request timing fields
        # folded into a RequestTimings record at finish (/debug/requests).
        self.trace_id = trace_id
        self.queue_time: float | None = None  # engine-reported
        self.detokenize_s = 0.0
        self.kv_blocks_held = 0  # last engine-reported
        self.peak_kv_blocks = 0
        self.num_output_tokens = 0
        self.num_cached_tokens = 0
        # Prompt logprobs: None for position 0, then one dict per prompt
        # token (assembled across prefill chunks).
        self.prompt_logprobs: list | None = (
            [None] if params.prompt_logprobs is not None else None
        )
        self.num_sent_chars = 0
        self.queue = queue  # per-request asyncio queue (streaming mode)
        # Lifecycle hardening (vllm_tpu/resilience/lifecycle): absolute
        # monotonic deadline (None = none) and the TTFT cutoff, both set
        # by AsyncLLM at admission and swept on the engine thread.
        self.deadline_t: float | None = None
        self.ttft_deadline_t: float | None = None

    def make_request_output(
        self, new_token_ids: list[int], finish_reason: str | None, stop_reason
    ) -> RequestOutput | None:
        kind = self.params.output_kind
        finished = finish_reason is not None
        if kind == RequestOutputKind.FINAL_ONLY and not finished:
            return None

        delta = kind == RequestOutputKind.DELTA
        text, self.num_sent_chars = self.detokenizer.get_next_output_text(
            finished, delta, self.num_sent_chars
        )
        passes = self.commit_passes
        if delta:
            token_ids = new_token_ids
            logprobs = self.logprobs[-len(new_token_ids) :] if self.params.logprobs else None
            if passes is not None:
                passes = passes[len(passes) - len(new_token_ids):]
        else:
            token_ids = self.detokenizer.output_token_ids
            logprobs = self.logprobs if self.params.logprobs else None

        completion = CompletionOutput(
            index=0,
            text=text,
            token_ids=token_ids,
            logprobs=logprobs,
            finish_reason=finish_reason,
            stop_reason=stop_reason,
            commit_passes=passes,
        )
        return RequestOutput(
            request_id=self.request_id,
            prompt=self.prompt_text,
            prompt_token_ids=self.prompt_token_ids,
            outputs=[completion],
            finished=finished,
            prompt_logprobs=self.prompt_logprobs,
            metrics=self.metrics,
        )


@dataclass
class ProcessedOutputs:
    request_outputs: list[RequestOutput] = field(default_factory=list)
    reqs_to_abort: list[str] = field(default_factory=list)
    iteration_stats: Any = None


class OutputProcessor:
    # Recently finished requests kept for /debug/requests introspection.
    FINISHED_RING_SIZE = 128
    # Sliding window of per-request SLO verdicts feeding the
    # vllm:slo_attainment{slo_class} gauge.
    SLO_WINDOW_SIZE = 512

    def __init__(self, tokenizer: Any | None = None,
                 journal: Any | None = None,
                 on_request_closed: Any | None = None,
                 reqtrace: Any | None = None,
                 slo_targets: dict | None = None) -> None:
        self.tokenizer = tokenizer
        self.request_states: dict[str, RequestState] = {}
        # Request-trace recorder (vllm_tpu/metrics/reqtrace); None keeps
        # the capture path entirely out of the per-request flow.
        self.reqtrace = reqtrace
        # Parsed per-class SLO targets ({class: {"ttft_ms", "itl_ms"}})
        # for the live attainment gauge; {} / None disables it.
        self.slo_targets = slo_targets or {}
        # (slo_class, met: bool) verdicts for recently finished requests.
        self.slo_window: deque = deque(maxlen=self.SLO_WINDOW_SIZE)
        # Whether finish-time verdicts need per-request ITL samples.
        self._track_itls = reqtrace is not None or bool(self.slo_targets)
        # Lifecycle hook: called with the request_id whenever a request's
        # frontend state is removed (finish, abort, crash-fail) — the
        # AdmissionController releases its capacity reservation here.
        # Must be idempotent: a request can be aborted twice.
        self.on_request_closed = on_request_closed
        # Optional crash-recovery journal (vllm_tpu/resilience): emitted
        # tokens are recorded here as they are processed, so a request
        # interrupted by an engine crash can resume from exactly what the
        # client has already seen.
        self.journal = journal
        # Bounded ring of RequestTimings for recently finished requests
        # (the live-introspection "where did request X spend its time"
        # view; appended engine-thread-side, snapshotted via
        # debug_snapshot()).
        self.finished_timings: deque = deque(maxlen=self.FINISHED_RING_SIZE)

    def add_request(
        self,
        request_id: str,
        prompt_text: str | None,
        prompt_token_ids: list[int],
        params: SamplingParams,
        arrival_time: float,
        queue: Any | None = None,
        trace_id: str | None = None,
    ) -> RequestState:
        state = RequestState(
            request_id,
            prompt_text,
            prompt_token_ids,
            params,
            self.tokenizer,
            arrival_time,
            queue,
            trace_id=trace_id,
        )
        if self._track_itls:
            state.itl_track = []
        self.request_states[request_id] = state
        # Frontend-side end-to-end request span: opened at admission,
        # closed when the final output is processed (its engine-side
        # children — queue/prefill/decode — share the trace id).
        trace_async_begin("request", trace_id, req_id=request_id)
        return state

    def abort_requests(self, request_ids) -> None:
        for rid in request_ids:
            state = self.request_states.pop(rid, None)
            if state is not None:
                trace_async_end(
                    "request", state.trace_id, req_id=rid,
                    finish_reason="abort",
                )
                self._record_finished(state, time.monotonic(), "abort")
            if self.journal is not None:
                self.journal.discard(rid)
            if self.on_request_closed is not None:
                self.on_request_closed(rid)

    def get_num_unfinished_requests(self) -> int:
        return len(self.request_states)

    def process_outputs(
        self,
        engine_core_outputs: list[EngineCoreOutput],
        logprobs_lists=None,
    ) -> ProcessedOutputs:
        """Detokenise and deliver one step's outputs. Timed as the
        ``output`` phase of ``vllm:engine_step_duration_seconds`` and, in a
        running profile, ONE span for the step (the per-request
        ``detokenize`` spans are the chrome trace's alone)."""
        t0 = time.perf_counter()
        with trace_span("output", "frontend", reqs=len(engine_core_outputs)):
            result = self._process_outputs(engine_core_outputs, logprobs_lists)
        result.iteration_stats.output_s = time.perf_counter() - t0
        return result

    def _process_outputs(
        self,
        engine_core_outputs: list[EngineCoreOutput],
        logprobs_lists=None,
    ) -> ProcessedOutputs:
        from vllm_tpu.metrics.stats import IterationStats

        result = ProcessedOutputs()
        stats = result.iteration_stats = IterationStats()
        now = time.monotonic()
        for eco in engine_core_outputs:
            state = self.request_states.get(eco.req_id)
            if state is None:
                continue  # aborted earlier

            if self.journal is not None and eco.new_token_ids:
                self.journal.record_tokens(eco.req_id, eco.new_token_ids)

            if eco.queue_time is not None:
                state.queue_time = eco.queue_time
            if eco.kv_blocks_held:
                state.kv_blocks_held = eco.kv_blocks_held
                state.peak_kv_blocks = max(
                    state.peak_kv_blocks, eco.kv_blocks_held
                )
            if eco.num_cached_tokens:
                state.num_cached_tokens = eco.num_cached_tokens

            if eco.new_token_ids:
                state.num_output_tokens += len(eco.new_token_ids)
                stats.num_generation_tokens += len(eco.new_token_ids)
                if state.metrics.first_token_time is None:
                    state.metrics.first_token_time = now
                    stats.num_prompt_tokens += len(state.prompt_token_ids)
                    ttft = now - state.metrics.arrival_time
                    stats.ttfts.append(ttft)
                    stats.ttfts_by_class.append((state.slo_label, ttft))
                else:
                    itl = now - state.last_token_time
                    stats.inter_token_latencies.append(itl)
                    stats.itls_by_class.append((state.slo_label, itl))
                    if state.itl_track is not None:
                        state.itl_track.append(itl * 1000.0)
                state.last_token_time = now

            t_detok = time.perf_counter()
            with trace_span(
                "detokenize", category="request", req_id=eco.req_id,
                trace_id=state.trace_id,
            ):
                stop_str = state.detokenizer.update(eco.new_token_ids)
            state.detokenize_s += time.perf_counter() - t_detok
            finish_reason = eco.finish_reason
            stop_reason = eco.stop_reason
            if stop_str is not None and finish_reason is None:
                # Stop string hit client-side: finish here, abort engine-side.
                finish_reason = "stop"
                stop_reason = stop_str
                result.reqs_to_abort.append(eco.req_id)

            if eco.new_logprobs is not None:
                self._append_logprobs(state, eco)
            if eco.commit_passes is not None:
                state.commit_passes = (
                    (state.commit_passes or []) + eco.commit_passes)
            if (
                eco.prompt_logprobs_delta is not None
                and state.prompt_logprobs is not None
            ):
                self._append_prompt_logprobs(state, eco.prompt_logprobs_delta)

            if finish_reason is not None:
                state.metrics.finished_time = now
                stats.e2e_latencies.append(now - state.metrics.arrival_time)
                stats.finished_reasons.append(str(finish_reason))
                trace_async_end(
                    "request", state.trace_id, req_id=eco.req_id,
                    finish_reason=str(finish_reason),
                )
                self._record_finished(state, now, str(finish_reason))
                # Pop BEFORE delivering the final output: once the client
                # sees `finished` it may re-use the request id; popping
                # after delivery could delete the successor's state.
                self.request_states.pop(eco.req_id, None)
                if self.journal is not None:
                    self.journal.record_finished(eco.req_id)
                if self.on_request_closed is not None:
                    self.on_request_closed(eco.req_id)

            out = state.make_request_output(
                eco.new_token_ids, finish_reason, stop_reason
            )
            if out is not None and eco.pooled is not None:
                out.pooled = eco.pooled
            if out is not None and eco.num_cached_tokens:
                out.num_cached_tokens = eco.num_cached_tokens
            if out is not None:
                if state.queue is not None:
                    state.queue.put_nowait(out)
                else:
                    result.request_outputs.append(out)
        return result

    # -- live introspection (/debug/requests) --------------------------

    def _record_finished(self, state: RequestState, now: float,
                         finish_reason: str) -> None:
        """Fold a finished request's state into a RequestTimings record
        and push it onto the bounded recently-finished ring."""
        from vllm_tpu.metrics.stats import RequestTimings

        m = state.metrics
        queue_s = state.queue_time
        prefill_s = decode_s = None
        if m.first_token_time is not None:
            prefill_s = m.first_token_time - m.arrival_time
            if queue_s is not None:
                prefill_s = max(0.0, prefill_s - queue_s)
            decode_s = max(0.0, state.last_token_time - m.first_token_time)
        timings = RequestTimings(
            request_id=state.request_id,
            trace_id=state.trace_id,
            slo_class=state.params.slo_class,
            tenant_id=state.params.tenant_id,
            arrival_time=m.arrival_time,
            finished_time=now,
            finish_reason=finish_reason,
            num_prompt_tokens=len(state.prompt_token_ids),
            num_output_tokens=state.num_output_tokens,
            num_cached_tokens=state.num_cached_tokens,
            peak_kv_blocks=state.peak_kv_blocks,
            queue_s=queue_s,
            prefill_s=prefill_s,
            decode_s=decode_s,
            detokenize_s=state.detokenize_s,
            e2e_s=max(0.0, now - m.arrival_time),
        )
        self.finished_timings.append(timings)
        ttft_ms = m.ttft * 1000.0 if m.ttft is not None else None
        if self.slo_targets:
            from vllm_tpu.metrics.goodput import request_meets_slo

            met = request_meets_slo(
                ttft_ms, state.itl_track or [],
                self.slo_targets.get(state.slo_label),
            )
            if met is not None:
                self.slo_window.append((state.slo_label, met))
        if self.reqtrace is not None:
            self.reqtrace.record_request(
                timings, state.params, ttft_ms=ttft_ms,
                itls_ms=state.itl_track,
            )

    def debug_snapshot(self) -> dict:
        """In-flight + recently-finished request views (JSON-shaped; the
        /debug/requests endpoint body). Safe to call from any thread: it
        only reads, and iterates over list() copies of the shared dict."""
        now = time.monotonic()
        in_flight = []
        for state in list(self.request_states.values()):
            m = state.metrics
            if m.first_token_time is not None:
                phase = "decode"
            elif state.queue_time is not None:
                phase = "prefill"
            else:
                phase = "queued"
            in_flight.append({
                "request_id": state.request_id,
                "trace_id": state.trace_id,
                "slo_class": state.params.slo_class,
                "tenant_id": state.params.tenant_id,
                "state": phase,
                "age_s": max(0.0, now - m.arrival_time),
                "num_prompt_tokens": len(state.prompt_token_ids),
                "tokens_emitted": state.num_output_tokens,
                "kv_blocks_held": state.kv_blocks_held,
                "queue_s": state.queue_time,
                "ttft_s": m.ttft,
                "deadline_remaining_s": (
                    state.deadline_t - now
                    if state.deadline_t is not None else None
                ),
            })
        recent = [
            t.as_dict() for t in reversed(list(self.finished_timings))
        ]
        return {
            "num_in_flight": len(in_flight),
            "in_flight": in_flight,
            "recently_finished": recent,
        }

    def slo_attainment_snapshot(self) -> dict[str, dict]:
        """Per-class attainment over the sliding verdict window:
        ``{class: {"attainment": fraction, "window": n}}``. Empty when
        no SLO targets are configured (the gauge then has nothing to
        say). Thread-safe: iterates a list() copy of the deque."""
        counts: dict[str, list[int]] = {}
        for cls, met in list(self.slo_window):
            met_n, total = counts.setdefault(cls, [0, 0])
            counts[cls] = [met_n + int(met), total + 1]
        return {
            cls: {"attainment": round(met_n / total, 4), "window": total}
            for cls, (met_n, total) in sorted(counts.items())
        }

    def _append_prompt_logprobs(self, state: RequestState, delta) -> None:
        """delta = (chunk_start, entries); entries cover prompt tokens
        chunk_start+1 .. chunk_start+len (position 0 has no predictor).

        Placement is by absolute position, not append: a preempted request
        re-runs prefill from 0 and re-emits chunks already delivered, and
        those must overwrite, not duplicate."""
        chunk_start, entries = delta
        for j, entry in enumerate(entries):
            idx = chunk_start + 1 + j
            topk_ids, topk_vals, tok, tok_lp, tok_rank = entry
            d: dict[int, Logprob] = {}
            k = state.params.prompt_logprobs or 0
            for rank, (tid, lp) in enumerate(zip(topk_ids[:k], topk_vals[:k])):
                d[int(tid)] = Logprob(logprob=float(lp), rank=rank + 1)
            if tok not in d:
                d[int(tok)] = Logprob(
                    logprob=float(tok_lp), rank=int(tok_rank) + 1
                )
            if self.tokenizer is not None and state.params.detokenize:
                for tid, lp in d.items():
                    lp.decoded_token = self.tokenizer.decode([tid])
            while len(state.prompt_logprobs) <= idx:
                state.prompt_logprobs.append(None)
            state.prompt_logprobs[idx] = d

    def _append_logprobs(self, state: RequestState, eco: EngineCoreOutput) -> None:
        """eco.new_logprobs: one (topk_ids, topk_vals, sampled_token_id,
        sampled_lp, sampled_rank) tuple per new token."""
        for entry in eco.new_logprobs:
            topk_ids, topk_vals, sampled_tok, sampled_lp, sampled_rank = entry
            d: dict[int, Logprob] = {}
            k = state.params.logprobs or 0
            for rank, (tid, lp) in enumerate(zip(topk_ids[:k], topk_vals[:k])):
                d[int(tid)] = Logprob(logprob=float(lp), rank=rank + 1)
            if sampled_tok not in d:
                d[int(sampled_tok)] = Logprob(
                    logprob=float(sampled_lp), rank=int(sampled_rank) + 1
                )
            if self.tokenizer is not None and state.params.detokenize:
                for tid, lp in d.items():
                    lp.decoded_token = self.tokenizer.decode([tid])
            state.logprobs.append(d)
