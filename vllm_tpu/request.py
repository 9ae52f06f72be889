"""Engine-internal request state machine.

Reference analog: ``vllm/v1/request.py`` — status enum, computed-token
tracking, spec-token buffers. Device-agnostic by design.
"""

from __future__ import annotations

import enum
import time
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Any

from vllm_tpu.sampling_params import SamplingParams

if TYPE_CHECKING:
    from vllm_tpu.core.kv_cache_utils import BlockHash


class RequestStatus(enum.IntEnum):
    WAITING = 0
    RUNNING = 1
    PREEMPTED = 2
    FINISHED_STOPPED = 3
    FINISHED_LENGTH_CAPPED = 4
    FINISHED_ABORTED = 5
    FINISHED_IGNORED = 6
    # Terminal execution error contained to this request (numeric-guard
    # trip: NaN/Inf logits or an out-of-range sampled token) — the engine
    # keeps serving everything else.
    FINISHED_ERROR = 7

    @staticmethod
    def is_finished(status: "RequestStatus") -> bool:
        return status >= RequestStatus.FINISHED_STOPPED


_FINISH_REASON = {
    RequestStatus.FINISHED_STOPPED: "stop",
    RequestStatus.FINISHED_LENGTH_CAPPED: "length",
    RequestStatus.FINISHED_ABORTED: "abort",
    RequestStatus.FINISHED_IGNORED: "length",
    RequestStatus.FINISHED_ERROR: "error",
}


@dataclass
class EngineCoreRequest:
    """Wire format frontend -> engine core (reference: v1/engine/__init__.py)."""

    request_id: str
    prompt_token_ids: list[int]
    sampling_params: SamplingParams
    arrival_time: float = field(default_factory=time.monotonic)
    eos_token_id: int | None = None
    priority: int = 0
    lora_name: str | None = None
    # Multimodal placeholders (feature ring 1).
    mm_inputs: list[Any] | None = None
    # Pooling/embedding request (None = generation).
    pooling_params: Any = None
    # Frontend-assigned trace correlation id: spans emitted for this
    # request in ANY process (frontend, engine core, worker) carry it, so
    # per-process chrome-trace files fuse into one per-request flow.
    trace_id: str | None = None
    # Which frontend (API-server shard) submitted this request. Engines
    # with multiple output sockets route this request's outputs back to
    # output socket [client_index]; single-frontend topologies leave 0.
    client_index: int = 0
    # Disaggregated prefill: fabric peer address ("host:port") of the
    # decode engine this request's prompt KV must be pushed to when the
    # request finishes. None = no handoff (the overwhelmingly common
    # case). Optional field: wire-safe against old peers (serial_utils
    # filters unknown dataclass kwargs at decode).
    disagg_push_to: str | None = None


class Request:
    """Scheduler-side request state."""

    def __init__(
        self,
        request_id: str,
        prompt_token_ids: list[int],
        sampling_params: SamplingParams,
        eos_token_id: int | None = None,
        arrival_time: float | None = None,
        priority: int = 0,
        lora_name: str | None = None,
        block_hasher: Any = None,
        pooling_params: Any = None,
        mm_inputs: list[Any] | None = None,
        trace_id: str | None = None,
        disagg_push_to: str | None = None,
    ) -> None:
        self.request_id = request_id
        self.trace_id = trace_id
        self.prompt_token_ids = prompt_token_ids
        self.sampling_params = sampling_params
        self.eos_token_id = eos_token_id
        self.arrival_time = arrival_time if arrival_time is not None else time.monotonic()
        self.priority = priority
        self.lora_name = lora_name
        self.pooling_params = pooling_params
        self.mm_inputs = mm_inputs or []
        self.disagg_push_to = disagg_push_to

        self.status = RequestStatus.WAITING
        self.stop_reason: int | str | None = None

        # prompt + generated tokens, grown in place.
        self._all_token_ids: list[int] = list(prompt_token_ids)
        self.num_prompt_tokens = len(prompt_token_ids)
        # Tokens whose KV is computed and resident in the cache.
        self.num_computed_tokens = 0
        # Prefix-cache hit length at first schedule (stats).
        self.num_cached_tokens = -1
        # Waiting->running delay, set at first schedule (rides the first
        # EngineCoreOutput so the frontend's RequestTimings has it).
        self.queue_time: float | None = None
        # Draft tokens proposed for this request, verified next step.
        self.spec_token_ids: list[int] = []
        # Async scheduling: sampling steps dispatched but whose output token
        # has not yet been materialized host-side (reference:
        # v1/core/sched/async_scheduler.py num_output_placeholders).
        self.num_output_placeholders = 0
        # Sampling STEPS in flight (placeholders counts TOKENS; a block
        # pass under generation by diffusion is a step with none).
        self.num_inflight_steps = 0
        # Number of scheduler preemptions (stats).
        self.num_preemptions = 0
        # Set after a FAILED external KV load: the rescheduled request
        # recomputes instead of re-querying the store (a store that still
        # advertises the keys but cannot serve them would otherwise loop
        # the request forever).
        self.skip_external_kv = False
        # Transient: in-flight step outputs from before an invalid-load
        # preemption are garbage; they drain placeholders without
        # materializing tokens, then the flag clears and resume proceeds.
        self.dropping_invalid = False
        # Structured output: compiled-grammar future + current DFA state
        # (managed by StructuredOutputManager; -1 = dead).
        self.grammar_future: Any = None
        self.fsm_state = 0

        # Generation by diffusion over blocks (models/generation.py; unused
        # by an autoregressive model). The HOST's view of the open block,
        # which lags the device by the steps in flight: its first absolute
        # position (-1 = none yet), and per position the token, whether it
        # is committed, the block pass that committed it (-1 = a prompt
        # token) and that pass's logprob entry. Kept across a preemption:
        # committed tokens never change.
        self.blk_start = -1
        self.blk_tokens: list[int] = []
        self.blk_committed: list[bool] = []
        self.blk_commit_pass: list[int] = []
        self.blk_logprobs: list[Any] = []
        # Schedule-time view: whether a block is open (else the request is
        # in prefill), how many of its positions are committed once every
        # pass in flight has landed, and the block passes scheduled so far
        # (the PRNG counter of a pass, and the order commits are reported
        # in; a pass that stores a block and denoises the next takes two).
        self.blk_open = False
        self.blk_sched_committed = 0
        self.blk_passes = 0

        # Content-addressed block hashes for prefix caching; maintained
        # incrementally as tokens append (reference: kv_cache_utils
        # get_request_block_hasher).
        self.block_hashes: list["BlockHash"] = []
        self._block_hasher = block_hasher
        if block_hasher is not None:
            self.block_hashes = block_hasher(self)

    @classmethod
    def from_engine_core_request(
        cls, req: EngineCoreRequest, block_hasher: Any = None
    ) -> "Request":
        return cls(
            request_id=req.request_id,
            prompt_token_ids=req.prompt_token_ids,
            sampling_params=req.sampling_params,
            eos_token_id=req.eos_token_id,
            pooling_params=req.pooling_params,
            arrival_time=req.arrival_time,
            priority=req.priority,
            lora_name=req.lora_name,
            block_hasher=block_hasher,
            mm_inputs=req.mm_inputs,
            trace_id=req.trace_id,
            disagg_push_to=getattr(req, "disagg_push_to", None),
        )

    # ------------------------------------------------------------------
    # Token accessors
    # ------------------------------------------------------------------

    @property
    def all_token_ids(self) -> list[int]:
        return self._all_token_ids

    @property
    def num_tokens(self) -> int:
        return len(self._all_token_ids)

    @property
    def num_output_tokens(self) -> int:
        return len(self._all_token_ids) - self.num_prompt_tokens

    @property
    def output_token_ids(self) -> list[int]:
        return self._all_token_ids[self.num_prompt_tokens :]

    @property
    def num_tokens_with_spec(self) -> int:
        return len(self._all_token_ids) + len(self.spec_token_ids)

    def append_output_token_ids(self, token_ids: int | list[int]) -> None:
        if isinstance(token_ids, int):
            self._all_token_ids.append(token_ids)
        else:
            self._all_token_ids.extend(token_ids)
        if self._block_hasher is not None:
            new_hashes = self._block_hasher(self)
            self.block_hashes.extend(new_hashes)

    # ------------------------------------------------------------------
    # Lifecycle
    # ------------------------------------------------------------------

    @property
    def is_finished(self) -> bool:
        return RequestStatus.is_finished(self.status)

    def get_finished_reason(self) -> str | None:
        return _FINISH_REASON.get(self.status)

    @property
    def max_tokens(self) -> int:
        mt = self.sampling_params.max_tokens
        assert mt is not None
        return mt

    @property
    def use_structured_output(self) -> bool:
        so = self.sampling_params.structured_outputs
        return so is not None and so.is_set

    def __repr__(self) -> str:
        return (
            f"Request(id={self.request_id}, status={self.status.name}, "
            f"tokens={self.num_tokens}, computed={self.num_computed_tokens})"
        )
