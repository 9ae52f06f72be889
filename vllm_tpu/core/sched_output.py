"""Wire formats between scheduler and model runner.

Reference analogs: ``vllm/v1/core/sched/output.py`` (SchedulerOutput) and
``vllm/v1/outputs.py`` (ModelRunnerOutput, EngineCoreOutputs).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

from vllm_tpu.sampling_params import SamplingParams


@dataclass
class NewRequestData:
    """Everything the runner needs to admit a request it has never seen."""

    req_id: str
    prompt_token_ids: list[int]
    sampling_params: SamplingParams
    # The request's block table: one list a KV group (a uniform model
    # has one group).
    block_ids: tuple[list[int], ...]
    num_computed_tokens: int
    lora_name: str | None = None
    mm_inputs: list[Any] | None = None
    eos_token_id: int | None = None
    pooling_params: Any = None


@dataclass
class CachedRequestData:
    """Delta for requests the runner already tracks (SoA layout like the
    reference's CachedRequestData)."""

    req_ids: list[str] = field(default_factory=list)
    resumed_from_preemption: list[bool] = field(default_factory=list)
    # All token ids, only populated for resumed requests (the runner's copy
    # went stale across preemption); None otherwise.
    resumed_req_token_ids: list[list[int] | None] = field(default_factory=list)
    # A request's new blocks (all of them when resumed), in the form of
    # ``NewRequestData.block_ids``; ``()`` where it got none.
    new_block_ids: list[tuple[list[int], ...]] = field(default_factory=list)
    num_computed_tokens: list[int] = field(default_factory=list)

    @property
    def num_reqs(self) -> int:
        return len(self.req_ids)


@dataclass
class BlockPass:
    """One pass over a request's open block (generation by diffusion over
    blocks). ``store`` = the block is clean: run it once more and keep its
    K/V. Alone that is a row of ``B`` positions that samples nothing; with
    ``denoise_next`` the row is ``2B`` positions, the clean block and then
    the next block, all masks, whose first denoising pass this is too: one
    pass of the model that takes TWO numbers, ``seq`` for the store and
    ``seq + 1`` for what it commits. Without ``store`` a denoising pass:
    every uncommitted position is fed the mask token and sampled, and the
    row's rule commits some. ``tokens`` / ``committed`` are the host's view
    of the block the row STARTS with, or None when a pass of this request
    is still in flight and the device's own copy (the previous step's
    output) is the one to read."""

    store: bool
    seq: int  # block passes of this request before this one
    tokens: list[int] | None = None
    committed: list[bool] | None = None
    denoise_next: bool = False

    @property
    def denoises(self) -> bool:
        return self.denoise_next or not self.store

    @property
    def commit_seq(self) -> int:
        """The number its commits are reported under."""
        return self.seq + 1 if self.denoise_next else self.seq


@dataclass
class SchedulerOutput:
    scheduled_new_reqs: list[NewRequestData] = field(default_factory=list)
    scheduled_cached_reqs: CachedRequestData = field(default_factory=CachedRequestData)
    # req_id -> tokens to run this step (includes spec tokens being verified).
    num_scheduled_tokens: dict[str, int] = field(default_factory=dict)
    total_num_scheduled_tokens: int = 0
    # req_id -> draft token ids scheduled for verification this step.
    scheduled_spec_decode_tokens: dict[str, list[int]] = field(default_factory=dict)
    # Requests that finished/aborted since the last step (runner state cleanup).
    finished_req_ids: set[str] = field(default_factory=set)
    # Requests preempted this step and NOT resumed within it: the runner
    # must release per-request device state (hybrid SSM slots) — a
    # preempted request recomputes from position 0 with zero state on
    # resume, so holding the slot while it waits both leaks capacity and
    # can exhaust the slot pool (running + preempted > max_num_seqs).
    preempted_req_ids: set[str] = field(default_factory=set)
    # Adaptive speculation: when True the occupancy gate has suspended
    # drafting batch-wide — the runner skips proposer work entirely this
    # step; spec_draft_budgets carries each scheduled request's current
    # draft budget (tokens for chains, tree-node prefix count for trees)
    # so next-step proposals are clipped at the source. Empty dict =
    # controller off (static drafting).
    spec_suspended: bool = False
    spec_draft_budgets: dict[str, int] = field(default_factory=dict)
    # KV connector: req_id -> (device block ids, content keys) to LOAD
    # into the cache before this step runs (saves flow separately via an
    # eager engine->worker RPC at free time).
    kv_connector_load: dict[str, tuple] = field(default_factory=dict)
    # Structured output: req_id -> row index into the grammar bitmask.
    structured_output_request_ids: dict[str, int] = field(default_factory=dict)
    grammar_bitmask: Any = None
    # Multimodal: req_id -> mm-input indexes whose encoder must run this
    # step (budget already reserved), and encoder-cache entries the worker
    # should drop (spans fully computed / request gone).
    scheduled_encoder_inputs: dict[str, list[int]] = field(default_factory=dict)
    free_encoder_input_ids: list[tuple[str, int]] = field(default_factory=list)
    # Generation by diffusion over blocks: req_id -> BlockPass for every
    # row of this step that runs an open block (rows without an entry are
    # prefill chunks, which end on a multiple of the block length).
    block_passes: dict[str, "BlockPass"] = field(default_factory=dict)
    # In-proc identity of each scheduled Request at schedule time. Async
    # scheduling leaves steps in flight after a request finishes; if a NEW
    # request reuses the id before the stale step drains, update_from_output
    # must not attribute the stale output to it. (Scheduler-local; never
    # crosses the wire — update runs in the scheduler's process.)
    req_refs: dict[str, Any] = field(default_factory=dict)

    @property
    def num_reqs(self) -> int:
        return len(self.scheduled_new_reqs) + self.scheduled_cached_reqs.num_reqs


@dataclass
class LogprobsLists:
    """Flat logprobs for sampled tokens (reference: v1/outputs.py)."""

    logprob_token_ids: list[list[int]]  # per request row: top-k token ids
    logprobs: list[list[float]]  # per request row: top-k logprobs
    sampled_token_ranks: list[int]
    sampled_logprobs: list[float]


@dataclass
class ModelRunnerOutput:
    req_ids: list[str] = field(default_factory=list)
    # Per request: tokens sampled this step ([] => no sample, e.g. partial
    # prefill; >1 with spec decode).
    sampled_token_ids: list[list[int]] = field(default_factory=list)
    logprobs: LogprobsLists | None = None
    # req_id -> per-position top-logprobs for prompt tokens.
    prompt_logprobs: dict[str, Any] = field(default_factory=dict)
    # Draft tokens proposed this step for next-step verification.
    draft_token_ids: dict[str, list[int]] = field(default_factory=dict)
    # Pooling-model outputs keyed by req_id.
    pooler_outputs: dict[str, Any] = field(default_factory=dict)
    # Requests whose external KV load failed: outputs are garbage, the
    # scheduler reschedules them for recompute (reference: invalid-block
    # recovery, scheduler.py:2123/2226).
    invalid_req_ids: set[str] = field(default_factory=set)
    # Generation by diffusion over blocks: req_id -> (tokens [B],
    # committed-after-the-pass [B], per-position logprob entries or None)
    # for every block row of the step; such rows carry no sampled_token_ids.
    block_results: dict[str, tuple] = field(default_factory=dict)
    # Requests whose numeric-integrity guard tripped this step (NaN/Inf
    # logits or out-of-range sampled token): terminal per-request error
    # (finish_reason="error"), never an engine failure.
    numeric_error_req_ids: set[str] = field(default_factory=set)


EMPTY_MODEL_RUNNER_OUTPUT = ModelRunnerOutput()


@dataclass
class EngineCoreOutput:
    req_id: str
    new_token_ids: list[int]
    finish_reason: str | None = None
    stop_reason: int | str | None = None
    new_logprobs: Any = None
    num_cached_tokens: int = 0
    events: list[Any] | None = None
    # Pooling/embedding result (final chunk of a pooling request).
    pooled: list[float] | None = None
    # Prompt logprobs covered by this step's chunk:
    # (chunk_start, [(topk_ids, topk_vals, token, token_lp, rank), ...]).
    prompt_logprobs_delta: Any = None
    # Observability (feeds the frontend's per-request RequestTimings and
    # /debug/requests): waiting->running delay measured at first schedule,
    # and the KV blocks currently held engine-side for this request.
    queue_time: float | None = None
    kv_blocks_held: int = 0
    # Generation by diffusion over blocks: for each of new_token_ids the
    # request's block pass (counted from 0; a block's store included,
    # whether it ran alone or rode with the next block's first pass) that
    # committed it. None for an autoregressive model.
    commit_passes: list[int] | None = None


# The phases of vllm:engine_step_duration_seconds that EngineCore times,
# in the order of a step; "output" (the frontend's) is IterationStats'.
STEP_PHASES = ("schedule", "dispatch", "prepare", "finalize", "fetch",
               "update")


@dataclass
class SchedulerStats:
    """Per-step snapshot (reference: v1/metrics/stats.py)."""

    num_running_reqs: int = 0
    num_waiting_reqs: int = 0
    kv_cache_usage: float = 0.0
    # KV groups over the one pool (``core/kv_cache_manager.py``): pages
    # each group's requests hold now, and cumulative since engine start
    # the pages each group took from the pool and the pages the window
    # groups gave back before their request ended.
    kv_group_pages_in_use: list[int] = field(default_factory=list)
    kv_pages_allocated: list[int] = field(default_factory=list)
    kv_window_pages_freed: int = 0
    # Fixed at start, a KV group (empty for a uniform model): the tokens a
    # page of the group holds, and the bytes one token costs the group's
    # layers as the model's config states them ("published") and as the
    # cache holds them ("held": lane padding, and a short group's places
    # no layer writes).
    kv_group_page_tokens: list[int] = field(default_factory=list)
    kv_token_bytes: list[dict[str, float]] = field(default_factory=list)
    # Pages held ("held") and pages of the pool ("pool"), each summed over
    # the scheduler's steps so far: held over pool between two scrapes is
    # the mean share of the pool in use.
    kv_page_steps: dict[str, int] = field(default_factory=dict)
    prefix_cache_queries: int = 0
    prefix_cache_hits: int = 0
    num_preempted_reqs: int = 0  # cumulative since engine start
    # Spec decode (cumulative): proposed draft tokens and accepted ones.
    spec_num_draft_tokens: int = 0
    spec_num_accepted_tokens: int = 0
    # Per-step (drained each snapshot): waiting->running queue delays of
    # requests first scheduled this step; per-request generated-token run
    # lengths of spec verification steps (accepted + bonus).
    queue_times: list[float] = field(default_factory=list)
    spec_accept_lengths: list[int] = field(default_factory=list)
    # Adaptive speculation: realized per-request draft lengths of spec
    # verification steps (drained each snapshot — feeds the
    # vllm:spec_decode_draft_len histogram; populated with or without
    # the adaptive controller), the controller's global acceptance-rate
    # EMA (None = no controller or no observations yet), whether the
    # occupancy gate currently suspends drafting, and the cumulative
    # suspension count.
    spec_draft_lens: list[int] = field(default_factory=list)
    spec_acceptance_rate_ema: float | None = None
    spec_suspended: bool = False
    spec_suspensions: int = 0
    # Worker/engine-side cumulative counters attached by EngineCore:
    # bucket-compile vs bucket-hit counts of the jitted step cache, and
    # time the lag-N pipeline spent blocked fetching device results.
    bucket_compiles: int = 0
    bucket_hits: int = 0
    pipeline_stall_s: float = 0.0
    # Numeric-guard trips (cumulative, by kind: "nan" / "sampled") and
    # step-watchdog trips, attached by EngineCore from the runner.
    numeric_guard_trips: dict[str, int] = field(default_factory=dict)
    step_watchdog_trips: int = 0
    # Decode-path observability (cumulative, attached by EngineCore from
    # the runner): jitted-step launches, launches whose batch was
    # decode-only (one token per row — sequence-pipelined kernel shape),
    # tokens sampled across launches (several a row under speculation
    # and diffusion), and step-input rows assembled by the Python loop
    # instead of the native fill.
    step_launches: int = 0
    decode_only_launches: int = 0
    launch_sampled_tokens: int = 0
    # By kind of step ("decode" = the decode-only step that runs the
    # decode attention kernel, "mixed" = every other), cumulative, counted
    # where the runner builds the step: steps, scheduled tokens, and
    # context tokens attended (sum over the step's rows of the positions
    # each row attends to). Seconds spent in first uses of a step program
    # (beside bucket_compiles, their count).
    steps_by_kind: dict[str, int] = field(default_factory=dict)
    step_tokens_by_kind: dict[str, int] = field(default_factory=dict)
    attn_context_tokens_by_kind: dict[str, int] = field(default_factory=dict)
    # (query token, key) pairs of the ragged attention kernel's calls,
    # "live" (the mask lets them through) and "computed" (its tiles work
    # them out), a q head a layer, cumulative, from the runner.
    attn_ragged_pairs: dict[str, int] = field(default_factory=dict)
    # Tiles of the decode attention kernel, "run" (its walk) and "least"
    # (what the rows' keys need), one call a decode-only step that takes
    # it, cumulative, from the runner.
    attn_decode_tiles: dict[str, float] = field(default_factory=dict)
    step_first_use_s: float = 0.0
    # The same seconds by stage ("trace", "lower", "backend", "run"), and
    # the process's compile counters from JAX's own events (seconds by
    # stage, persistent-cache results): cumulative, and attached only to
    # the snapshot after they moved (None otherwise). The start's phases
    # (``tracing.StartupTimeline.snapshot``) ride the first snapshot alone.
    step_first_use_stage_s: dict[str, float] | None = None
    compile_seconds: dict[str, float] | None = None
    compile_cache: dict[str, int] | None = None
    startup: dict | None = None
    # Generation by diffusion over blocks (cumulative, from the runner):
    # row-passes by kind (a pass that stores a clean block and denoises
    # the next counts once, as "denoise"), positions committed by
    # denoising passes, and the stores that rode with a denoising pass.
    # The head of a block step runs over the least rung (a quarter, a half
    # or all of the request bucket) that holds the live rows: positions
    # {"live", "computed"}, and steps by rung.
    # Mixture of experts (cumulative, from the runner): token-expert pairs
    # over live tokens, experts that got a live token, and the pad tokens'
    # pairs that were kept out of the grouped matmul, all summed over
    # layers and steps; and the grouped matmul's visits, "first" (waits
    # for its expert's matrix) and "resident" (runs on the one held).
    diffusion_passes_by_kind: dict[str, int] = field(default_factory=dict)
    diffusion_committed_tokens: int = 0
    diffusion_stores_fused: int = 0
    diffusion_head_positions: dict[str, int] = field(default_factory=dict)
    diffusion_head_steps_by_rung: dict[str, int] = field(default_factory=dict)
    moe_assignments: int = 0
    moe_experts_hit: int = 0
    moe_pad_pairs_skipped: int = 0
    moe_gmm_visits: dict[str, int] = field(default_factory=dict)
    # Linear attention over state slots (cumulative, from the runner, a
    # layer of that kind): rows whose state a step read and wrote, tokens
    # by the form that took them; and the slots held now.
    linear_attn_state_rows: int = 0
    linear_attn_tokens_by_kind: dict[str, int] = field(default_factory=dict)
    state_slots_in_use: int = 0
    # Window attention layers beside full ones (cumulative, from the
    # runner, a layer of that kind): rows a step, and the (query token,
    # key) pairs the window's mask lets through, a q head.
    window_attn_rows: int = 0
    window_attn_pairs: int = 0
    # Rows x layers a step ran with a sink logit inside the softmax
    # (cumulative, from the runner).
    attn_sink_rows: int = 0
    prep_fallback_rows: int = 0
    # Sampling-epilogue routing: in-jit sample() calls routed to the
    # fused sort-free kernel vs sampling rows that fell back to the XLA
    # reference path (all-greedy launches count as neither).
    sampler_kernel_launches: int = 0
    sampler_fallback_rows: int = 0
    # Engine-step phase durations (drained each snapshot, seconds), one
    # list a phase of STEP_PHASES — attached by EngineCore from its
    # schedule/dispatch/finalize/update sites and from the runner's
    # always-on step timing (prepare inside dispatch, fetch inside
    # finalize); feed the vllm:engine_step_duration_seconds family.
    step_schedule_times: list[float] = field(default_factory=list)
    step_dispatch_times: list[float] = field(default_factory=list)
    step_prepare_times: list[float] = field(default_factory=list)
    step_finalize_times: list[float] = field(default_factory=list)
    step_fetch_times: list[float] = field(default_factory=list)
    step_update_times: list[float] = field(default_factory=list)
    # Last dispatched batch occupancy (tokens, requests, and the fraction
    # of the token budget used) + wall time between step completions.
    batch_num_tokens: int = 0
    batch_num_reqs: int = 0
    batch_occupancy: float = 0.0
    step_interval_s: float = 0.0
    # Tiered KV fabric snapshot (attached by EngineCore when the fabric
    # connector is active): per-tier resident blocks, cumulative fetch
    # outcomes / demotions / transferred bytes. None = fabric off.
    kv_fabric: dict | None = None
    # QoS (resilience/qos.py): request ids preempted since the last
    # snapshot (drained — the frontend re-charges each one's tenant WFQ
    # debt on requeue), the cumulative pressure-preemption count, and
    # the brownout rung the scheduler is currently acting on (echo of
    # the rung the frontend ladder pushed; 0 when QoS is disabled).
    preempted_req_ids: list[str] = field(default_factory=list)
    pressure_preemptions: int = 0
    brownout_rung: int = 0


@dataclass
class EngineCoreOutputs:
    outputs: list[EngineCoreOutput] = field(default_factory=list)
    scheduler_stats: SchedulerStats | None = None
    timestamp: float = 0.0
