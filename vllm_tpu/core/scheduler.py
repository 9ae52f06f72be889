"""Token-budget continuous-batching scheduler.

Reference analog: ``vllm/v1/core/sched/scheduler.py`` (schedule :352,
update_from_output :1290). Semantics ported faithfully — they are
device-independent and battle-tested:

- ONE token budget per step covering prefill and decode uniformly; a
  request's step size is ``num_tokens_with_spec - num_computed_tokens``
  capped by the remaining budget (chunked prefill falls out of the cap).
- Running requests are served before waiting ones; allocation failure
  preempts the lowest-priority running request (the list tail) and retries.
- Waiting requests enter only while budget and max_num_seqs allow; a new
  request's cached prefix is discovered here (prefix cache lookup).
- ``update_from_output`` advances computed-token counts, applies spec-decode
  accept/reject, performs stop checks, frees finished requests, and emits
  per-request EngineCoreOutputs.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Iterable

import vllm_tpu.envs as envs
from vllm_tpu.config import CacheConfig, SchedulerConfig
from vllm_tpu.core.kv_cache_manager import KVCacheManager
from vllm_tpu.core.sched_output import (
    BlockPass,
    CachedRequestData,
    EngineCoreOutput,
    EngineCoreOutputs,
    ModelRunnerOutput,
    NewRequestData,
    SchedulerOutput,
    SchedulerStats,
)
from vllm_tpu.logger import init_logger
from vllm_tpu.request import Request, RequestStatus

logger = init_logger(__name__)


def _needs_logits_processors(p) -> bool:
    return bool(
        p.logit_bias or p.bad_words or p.bad_words_token_ids
        or p.allowed_token_ids is not None or p.min_tokens
    )


class RequestQueue:
    """FCFS by default; priority policy orders by (priority, arrival).

    Reference: ``vllm/v1/core/sched/request_queue.py``.
    """

    def __init__(self, policy: str = "fcfs") -> None:
        self.policy = policy
        self._q: deque[Request] = deque()

    def add(self, request: Request) -> None:
        if self.policy == "priority":
            # Insertion sort keeps the deque ordered; queues are short
            # relative to step cost.
            key = (request.priority, request.arrival_time)
            for i, r in enumerate(self._q):
                if key < (r.priority, r.arrival_time):
                    self._q.insert(i, request)
                    return
        self._q.append(request)

    def prepend(self, request: Request) -> None:
        """Resumed-preempted requests go to the head (FCFS) or re-sort."""
        if self.policy == "priority":
            self.add(request)
        else:
            self._q.appendleft(request)

    def peek(self) -> Request:
        return self._q[0]

    def popleft(self) -> Request:
        return self._q.popleft()

    def remove(self, request: Request) -> None:
        self._q.remove(request)

    def __len__(self) -> int:
        return len(self._q)

    def __bool__(self) -> bool:
        return bool(self._q)

    def __iter__(self):
        return iter(self._q)


def _block_ids(blocks: tuple[list, ...]) -> tuple[list[int], ...]:
    """The ids of what ``KVCacheManager.allocate_slots`` returned, a list a
    KV group; ``()`` where no group grew, so that the runner can skip the
    row (groups of one page size grow together; a group of more tokens a
    page grows on fewer steps, and its list is then empty)."""
    if not any(blocks):
        return ()
    return tuple([b.block_id for b in group] for group in blocks)


class Scheduler:
    # Async subclass advances computed counts at schedule time instead of
    # update time (lag-1 pipelining); several accounting paths branch on it.
    async_scheduling = False

    def __init__(
        self,
        scheduler_config: SchedulerConfig,
        cache_config: CacheConfig,
        structured_output_manager=None,
        kv_connector=None,
    ) -> None:
        self.config = scheduler_config
        self.cache_config = cache_config
        assert cache_config.num_gpu_blocks is not None, (
            "CacheConfig.num_gpu_blocks must be set before Scheduler init"
        )
        # KV-cache event publishing (reference: distributed/kv_events.py):
        # block store/evict/clear notifications for cache-aware routers,
        # batched and PUBlished once per schedule().
        self.kv_event_publisher = None
        if cache_config.kv_events_endpoint:
            from vllm_tpu.core.kv_events import KVEventPublisher

            self.kv_event_publisher = KVEventPublisher(
                cache_config.kv_events_endpoint, cache_config.block_size
            )
        self.kv_cache_manager = KVCacheManager(
            num_blocks=cache_config.num_gpu_blocks,
            block_size=cache_config.block_size,
            enable_caching=cache_config.enable_prefix_caching,
            sliding_window=cache_config.sliding_window,
            kv_cache_groups=cache_config.kv_cache_groups,
            num_stripes=cache_config.num_kv_stripes,
            event_sink=(
                self.kv_event_publisher.record
                if self.kv_event_publisher
                else None
            ),
        )
        self.block_size = cache_config.block_size
        self.structured_output_manager = structured_output_manager
        if kv_connector is not None and self.kv_cache_manager.num_groups > 1:
            raise NotImplementedError(
                "an external KV tier with several KV groups: its keys name "
                "ONE page a block of tokens"
            )
        self.kv_connector = kv_connector
        # (block_ids, keys) save records awaiting shipment to the runner.
        self._pending_kv_saves: list[tuple] = []
        # Disaggregated handoffs: (req_id, peer_url, keys) for finished
        # requests whose prompt-prefix KV must be pushed to a decode
        # engine. Drained by the engine core in the SAME step the
        # request finishes (take_pending_handoffs) — handoff latency is
        # on the request's critical path, unlike ordinary cold saves.
        self._pending_handoff_pushes: list[tuple] = []

        from vllm_tpu.core.encoder_cache_manager import EncoderCacheManager

        self.encoder_cache_manager = EncoderCacheManager(
            scheduler_config.encoder_cache_budget
        )
        # Worker-side encoder-cache entries to drop, shipped on the next
        # SchedulerOutput.
        self._pending_encoder_frees: list[tuple[str, int]] = []

        self.requests: dict[str, Request] = {}
        self.waiting = RequestQueue(scheduler_config.policy)
        self.running: list[Request] = []
        # Requests finished since the last schedule() — the runner drops
        # their persistent-batch state on the next step.
        self.finished_req_ids: set[str] = set()
        # Cumulative preemption count (loggers export deltas; a per-step
        # counter would lose events when async lag-1 runs two schedule()
        # calls between logger updates).
        self._num_preempted_total = 0
        self._kv_page_steps = {"held": 0, "pool": 0}
        groups = cache_config.kv_cache_groups or []
        self._kv_group_page_tokens = [g.block_size for g in groups]
        self._kv_token_bytes = [
            {"published": float(len(g.layer_names)
                                * g.kv_cache_spec.published_token_bytes),
             "held": g.page_size_bytes / g.block_size}
            for g in groups
        ]
        # Preempted ids from a schedule() whose output was never
        # dispatched (zero scheduled tokens): re-delivered on the next
        # dispatched step so the runner still releases per-request state.
        self._pending_preempted: set[str] = set()
        self._num_invalid_loads = 0
        # Cumulative spec-decode accounting (acceptance-rate metric).
        self._spec_num_draft_tokens = 0
        self._spec_num_accepted_tokens = 0
        # Per-step observability (drained by make_stats): queue delays of
        # requests first scheduled this step; spec verification
        # generated-run lengths (accepted + bonus) per request per step.
        self._queue_times: list[float] = []
        self._spec_accept_lengths: list[int] = []
        # Requests failed engine-side (e.g. grammar compile error) awaiting
        # an output record to the frontend.
        self._failed_requests: list[Request] = []
        # Request ids of the last non-empty (dispatched) step: the runner's
        # device-side token feedback reads the immediately previous step's
        # sampled array, so a request with in-flight tokens that MISSED that
        # step (depth cap, budget) must wait for host materialization.
        self._last_step_req_ids: set[str] = set()
        # Adaptive speculation: acceptance-driven draft budgets + the
        # occupancy-gated shutoff (spec_decode/adaptive.py). The
        # controller is a pure host-side state machine the scheduler
        # consults at schedule time and feeds from verification results;
        # VLLM_TPU_DISABLE_ADAPTIVE_SPEC in the environment is its
        # escape hatch.
        self.adaptive_spec = None
        if (
            scheduler_config.spec_adaptive
            and scheduler_config.spec_num_speculative_tokens > 0
        ):
            from vllm_tpu.spec_decode.adaptive import AdaptiveSpecController

            tree = None
            if scheduler_config.spec_tree_spec:
                from vllm_tpu.spec_decode.tree import build_tree

                tree = build_tree(scheduler_config.spec_tree_spec)
            self.adaptive_spec = AdaptiveSpecController(
                scheduler_config.spec_num_speculative_tokens,
                high_watermark=(
                    scheduler_config.spec_adaptive_high_watermark
                ),
                low_watermark=scheduler_config.spec_adaptive_low_watermark,
                ema_half_life_s=(
                    scheduler_config.spec_adaptive_ema_half_life_s
                ),
                tree=tree,
            )
        # Realized per-request draft lengths of spec verification steps
        # (drained by make_stats — feeds vllm:spec_decode_draft_len).
        self._spec_draft_lens: list[int] = []
        # QoS (resilience/qos.py): the brownout rung pushed live by the
        # frontend ladder (0 = normal; >= 1 suspends speculation, >= 2
        # shrinks prefill chunks, >= 4 preempts batch-class decodes) and
        # the no-restart FIFO-vs-QoS A/B switch (the trace bench flips
        # it; VLLM_TPU_DISABLE_QOS is the env spelling).
        self.brownout_rung = 0
        self.disable_qos = False
        # Pressure-preemption accounting: cumulative count, plus every
        # preempted request id since the last stats snapshot (drained by
        # make_stats — the frontend re-charges the tenant's WFQ debt on
        # requeue from this list, so preempt/resume can't double-spend
        # an admission allocation).
        self._pressure_preemptions_total = 0
        self._preempted_rids: list[str] = []
        # Generation by diffusion over blocks (models/generation.py):
        # positions a block, 0 for an autoregressive model.
        self._block_len = scheduler_config.block_length

    # ------------------------------------------------------------------
    # Generation by diffusion over blocks
    # ------------------------------------------------------------------

    def _blk_open(self, request: Request, start: int) -> None:
        """Open the block at ``start`` (the prefill has stored everything
        below it): the prompt's last ``P mod B`` tokens, committed, then
        masks. A request resumed after a preemption reopens the block it
        held, with the tokens it had committed out of order."""
        if request.blk_start != start or not request.blk_tokens:
            self._blk_reset(request, start, request.all_token_ids[start:])
        request.blk_open = True
        request.blk_sched_committed = sum(request.blk_committed)

    def _blk_reset(self, request: Request, start: int, known=()) -> None:
        """The host's view of the block at ``start``: ``known`` tokens,
        committed, then masks."""
        pad = self._block_len - len(known)
        request.blk_start = start
        request.blk_tokens = list(known) + [0] * pad
        request.blk_committed = [True] * len(known) + [False] * pad
        request.blk_commit_pass = [-1] * self._block_len
        request.blk_logprobs = [None] * self._block_len

    def _blk_plan(self, request: Request, computed: int, budget: int):
        """What a request that generates by diffusion runs next, given
        ``computed`` stored tokens and ``budget`` tokens of the step:
        ``(tokens, None)`` for a prefill chunk, which ends on a multiple
        of the block length, ``(B, BlockPass)`` for a pass over its open
        block, ``(2B, BlockPass)`` where the block is clean and the step
        and the model's length have room for the next block behind it
        (its store rides with the next block's first denoising pass), or
        None when the step has no room for a block."""
        blen = self._block_len
        if not request.blk_open:
            remaining = request.num_tokens // blen * blen - computed
            if remaining > 0:
                n = min(remaining, budget)
                cap = self._effective_chunk_threshold()
                if cap > 0:
                    n = min(n, cap)
                n = min(n, self.config.max_model_len - computed) // blen * blen
                return (n, None) if n > 0 else None
            self._blk_open(request, computed)
        if budget < blen or computed + blen > self.config.max_model_len:
            return None
        store = request.blk_sched_committed >= blen
        fused = (store and budget >= 2 * blen
                 and computed + 2 * blen <= self.config.max_model_len)
        # A pass of this request still in flight: the device's own copy of
        # the block (the previous step's output) is ahead of the host's.
        on_device = request.num_inflight_steps > 0
        return (2 * blen if fused else blen), BlockPass(
            store=store,
            seq=request.blk_passes,
            tokens=None if on_device else list(request.blk_tokens),
            committed=None if on_device else list(request.blk_committed),
            denoise_next=fused,
        )

    def _blk_after_schedule(self, request: Request, n: int, bp,
                            block_passes: dict) -> None:
        """Schedule-time bookkeeping of a diffusion request, in the sync
        and the async scheduler alike: what is STORED advances here, by a
        prefill chunk or by a pass that stores its clean block, alone or
        with the next block's first denoising pass behind it; a denoising
        pass stores nothing."""
        blen = self._block_len
        if bp is None:
            request.num_computed_tokens += n
            return
        block_passes[request.request_id] = bp
        # A pass that stores AND denoises takes two numbers, the store's
        # and its commits': pass numbers stay those of one pass a step.
        request.blk_passes += 2 if bp.denoise_next else 1
        request.num_inflight_steps += 1
        if bp.store:
            request.num_computed_tokens += blen
            request.blk_sched_committed = 0
        if bp.denoises:
            # What the sequential and the static rule commit, and the
            # least the dynamic rule does (a dynamic row is never
            # scheduled ahead of its result; update_from_output resets
            # this from what came back).
            request.blk_sched_committed = min(
                blen,
                request.blk_sched_committed
                + blen // request.sampling_params.denoising_steps,
            )

    def _blk_update(self, request: Request, req_id: str, bp, result,
                    outputs: list) -> None:
        """Fold one block pass's result into the host's view of the
        block, and stream every token that it and all before it are
        committed."""
        blen = self._block_len
        request.num_inflight_steps = max(0, request.num_inflight_steps - 1)
        new_token_ids: list[int] = []
        new_logprobs: list = []
        passes: list[int] = []
        stopped = False
        if bp.store:
            # The clean block is stored; the next opens, all masks. Here
            # and not at schedule time: the passes that completed the
            # clean block may have been in flight then, and land first.
            self._blk_reset(request, request.blk_start + blen)
        if bp.denoises and result is not None:
            tokens, committed, lps = result
            for j in range(blen):
                if committed[j] and not request.blk_committed[j]:
                    request.blk_committed[j] = True
                    request.blk_tokens[j] = int(tokens[j])
                    request.blk_commit_pass[j] = bp.commit_seq
                    request.blk_logprobs[j] = lps[j] if lps else None
            while not stopped:
                j = request.num_tokens - request.blk_start
                if not 0 <= j < blen or not request.blk_committed[j]:
                    break
                tok = request.blk_tokens[j]
                request.append_output_token_ids(tok)
                new_token_ids.append(tok)
                passes.append(request.blk_commit_pass[j])
                if request.blk_logprobs[j] is not None:
                    ids, vals, lp, rank = request.blk_logprobs[j]
                    new_logprobs.append((ids, vals, tok, lp, rank))
                stopped = self._check_stop(request)
        if request.blk_open and request.num_inflight_steps == 0:
            request.blk_sched_committed = sum(request.blk_committed)
        if stopped:
            if request in self.running:
                self.running.remove(request)
            else:
                self.waiting.remove(request)
            self._free_request(request)
        if new_token_ids or stopped:
            outputs.append(
                EngineCoreOutput(
                    req_id=req_id,
                    new_token_ids=new_token_ids,
                    finish_reason=request.get_finished_reason(),
                    stop_reason=request.stop_reason,
                    new_logprobs=new_logprobs or None,
                    num_cached_tokens=max(request.num_cached_tokens, 0),
                    queue_time=request.queue_time,
                    kv_blocks_held=self.kv_cache_manager.num_pages_held(
                        req_id),
                    commit_passes=passes,
                )
            )

    # ------------------------------------------------------------------
    # Request lifecycle
    # ------------------------------------------------------------------

    def add_request(self, request: Request) -> None:
        self.requests[request.request_id] = request
        request.status = RequestStatus.WAITING
        self.waiting.add(request)

    def finish_requests(
        self, request_ids: str | Iterable[str], status: RequestStatus
    ) -> list[Request]:
        """External finish (abort, stop-string hit detected by the frontend
        detokenizer). Reference: ``scheduler.py finish_requests``."""
        if isinstance(request_ids, str):
            request_ids = (request_ids,)
        finished = []
        for req_id in request_ids:
            request = self.requests.get(req_id)
            if request is None or request.is_finished:
                continue
            if request.status == RequestStatus.RUNNING:
                self.running.remove(request)
            elif request.status in (
                RequestStatus.WAITING,
                RequestStatus.PREEMPTED,  # preempted requests sit in waiting
            ):
                self.waiting.remove(request)
            request.status = status
            self._free_request(request)
            finished.append(request)
        return finished

    def take_pending_kv_saves(self) -> list[tuple]:
        out = self._pending_kv_saves
        self._pending_kv_saves = []
        return out

    def take_pending_handoffs(self) -> list[tuple]:
        out = self._pending_handoff_pushes
        self._pending_handoff_pushes = []
        return out

    def _free_request(self, request: Request) -> None:
        if self.adaptive_spec is not None:
            self.adaptive_spec.forget(request.request_id)
        self._free_encoder_for_request(request)
        if (
            self.kv_connector is not None
            and request.block_hashes
            and request.pooling_params is None
            and not request.mm_inputs  # hashes don't cover image content
        ):
            # One group: ``__init__`` refuses an external tier with more.
            (block_ids,) = self.kv_cache_manager.get_block_ids(
                request.request_id
            )
            # Only blocks whose KV was actually computed (an abort can
            # leave allocated-but-unwritten blocks behind hashed slots).
            confirmed_blocks = max(
                0,
                request.num_computed_tokens
                - request.num_output_placeholders,
            ) // self.block_size
            idxs = self.kv_connector.request_finished(request.block_hashes)
            save = [
                (block_ids[i], request.block_hashes[i])
                for i in idxs
                if i < min(len(block_ids), confirmed_blocks)
                and block_ids[i] != 0
            ]
            if save:
                self._pending_kv_saves.extend(save)
            if (request.disagg_push_to
                    and request.status != RequestStatus.FINISHED_ABORTED):
                # Handoff: push the FULL confirmed prefix to the decode
                # peer (not just host-tier misses — the peer has none of
                # it). The engine core flushes saves first, so every key
                # here is host-tier-resident by push time.
                n = min(len(block_ids), confirmed_blocks)
                keys = [
                    request.block_hashes[i]
                    for i in range(n) if block_ids[i] != 0
                ]
                if keys:
                    self._pending_handoff_pushes.append(
                        (request.request_id, request.disagg_push_to, keys))
        self.kv_cache_manager.free(request)
        self.finished_req_ids.add(request.request_id)
        del self.requests[request.request_id]
        if request.use_structured_output and self.structured_output_manager:
            self.structured_output_manager.release(request)

    def has_unfinished_requests(self) -> bool:
        return bool(self.running) or bool(self.waiting)

    def get_num_unfinished_requests(self) -> int:
        return len(self.running) + len(self.waiting)

    # ------------------------------------------------------------------
    # schedule()
    # ------------------------------------------------------------------

    def _adaptive_spec_on(self) -> bool:
        """Controller present and not disabled by the env."""
        return (
            self.adaptive_spec is not None
            and not envs.VLLM_TPU_DISABLE_ADAPTIVE_SPEC
        )

    def _qos_on(self) -> bool:
        """QoS actions (brownout rungs, pressure preemption) enabled."""
        return not self.disable_qos and not envs.VLLM_TPU_DISABLE_QOS

    def _effective_chunk_threshold(self) -> int:
        """Long-prefill chunk cap, shrunk under brownout rung 2+ so a
        batch prompt can't monopolize a step while interactive requests
        wait on TTFT. Quarter of the configured cap (or of the step
        budget when no cap is set), floored at 128 tokens."""
        base = self.config.long_prefill_token_threshold
        if self.brownout_rung >= 2 and self._qos_on():
            cap = max(
                128,
                (base if base > 0
                 else self.config.max_num_batched_tokens) // 4,
            )
            return cap if base <= 0 else min(base, cap)
        return base

    def schedule(self) -> SchedulerOutput:
        token_budget = self.config.max_num_batched_tokens
        num_scheduled_tokens: dict[str, int] = {}
        scheduled_spec_tokens: dict[str, list[int]] = {}
        block_passes: dict[str, BlockPass] = {}
        enc_sched: dict[str, list[int]] = {}
        scheduled_new_reqs: list[NewRequestData] = []
        cached = CachedRequestData()
        # Blocks allocated this step per running request (delta to runner).
        new_blocks_per_req: dict[str, list[int]] = {}
        preempted_in_step: set[str] = set()

        # Start index (pre-step num_computed) per request; async scheduling
        # advances num_computed_tokens at schedule time, so phase 3 must use
        # these captured values, not the live counter.
        starts: dict[str, int] = {}
        kv_connector_load: dict[str, tuple] = {}

        # QoS pressure preemption, before any scheduling decisions: the
        # freed request slots are admittable in this same step's phase 2.
        preempted_in_step |= self._pressure_preempt()

        # Spec-decode steps disable logprobs for the whole batch (the
        # runner's per-token logprob contract is single-token), so while ANY
        # request wants logprobs, drop pending drafts at the authoritative
        # point — schedule time — rather than trusting the runner's
        # finalize-time view, which races with request admission.
        if any(r.spec_token_ids for r in self.running) and any(
            r.sampling_params.prompt_logprobs is not None
            or r.sampling_params.logprobs is not None
            or r.use_structured_output
            or r.pooling_params is not None
            or _needs_logits_processors(r.sampling_params)
            for r in (*self.running, *self.waiting)
        ):
            # (Also incompatible with structured output and logits
            # processors: the rejection sampler applies neither grammar
            # masks nor bias/ban adjustments.)
            for r in self.running:
                r.spec_token_ids = []

        # Adaptive speculation: clip each request's pending drafts to its
        # acceptance-ratcheted budget (0 while the occupancy gate holds).
        # Proposal-side only — verification semantics are untouched, so
        # accepted text is identical to static drafting. For trees the
        # budget counts breadth-first node-prefix positions (any depth
        # cutoff is a contiguous prefix of the window layout).
        adaptive_on = self._adaptive_spec_on()
        if adaptive_on:
            for r in self.running:
                if not r.spec_token_ids:
                    continue
                budget = self.adaptive_spec.draft_budget(r.request_id)
                if budget <= 0:
                    r.spec_token_ids = []
                elif budget < len(r.spec_token_ids):
                    r.spec_token_ids = r.spec_token_ids[:budget]

        # Brownout rung 1+: suspend speculation pool-wide. Drafts are a
        # throughput hedge; under pressure their verify positions go to
        # guaranteed tokens instead (acts immediately, unlike the
        # adaptive controller's EMA-gated shutoff).
        if self.brownout_rung >= 1 and self._qos_on():
            for r in self.running:
                if r.spec_token_ids:
                    r.spec_token_ids = []

        # Phase 1: running requests, in order (decode + in-flight prefills).
        req_index = 0
        while req_index < len(self.running) and token_budget > 0:
            request = self.running[req_index]
            # Pipeline bound: each in-flight step feeds its input token
            # device-side from the immediately previous step's sampled
            # array, so chaining is exact at any depth. Penalty-bearing
            # requests cap at 2 — the in-jit token-count correction covers
            # exactly one not-yet-materialized token.
            p = request.sampling_params
            if request.use_structured_output or (
                p.bad_words_token_ids
                and any(len(seq) > 1 for seq in p.bad_words_token_ids)
            ):
                # The next step's grammar bitmask / bad-words suffix match
                # depends on the in-flight token — no scheduling ahead.
                depth_cap = 1
            elif (p.presence_penalty or p.frequency_penalty
                  or p.repetition_penalty != 1.0):
                depth_cap = 2
            elif (self._block_len
                  and p.remasking == "low_confidence_dynamic"):
                # How many positions the pass in flight commits is data:
                # whether the next pass denoises or stores is not known
                # until it lands.
                depth_cap = 1
            else:
                depth_cap = self.config.async_pipeline_depth
            if request.num_inflight_steps >= depth_cap:
                req_index += 1
                continue
            # In-flight tokens are only recoverable device-side from the
            # immediately previous dispatched step; a request that skipped
            # it waits until its tokens materialize host-side.
            if (
                (request.num_output_placeholders > 0
                 or (self._block_len and request.num_inflight_steps > 0))
                and request.request_id not in self._last_step_req_ids
            ):
                req_index += 1
                continue
            blk_pass = None
            if self._block_len:
                plan = self._blk_plan(
                    request, request.num_computed_tokens, token_budget
                )
                if plan is None:
                    req_index += 1
                    continue
                num_new_tokens, blk_pass = plan
            else:
                # num_output_placeholders is 0 in sync mode; in async mode
                # it lets a decode whose last token is still in flight be
                # scheduled one position ahead (the runner feeds the token
                # on device).
                num_new_tokens = (
                    request.num_tokens_with_spec
                    + request.num_output_placeholders
                    - request.num_computed_tokens
                )
                chunk_cap = self._effective_chunk_threshold()
                if chunk_cap > 0:
                    num_new_tokens = min(num_new_tokens, chunk_cap)
                num_new_tokens = min(num_new_tokens, token_budget)
                num_new_tokens = min(
                    num_new_tokens,
                    self.config.max_model_len - request.num_computed_tokens,
                )
            # Encoder gate: reserve encoder-cache space for any image span
            # this chunk covers; trims the chunk when the budget is full
            # (reference: _try_schedule_encoder_inputs).
            num_new_tokens, enc_new = self._try_schedule_encoder(
                request, request.num_computed_tokens, num_new_tokens
            )
            if num_new_tokens <= 0:
                self._rollback_encoder(request, enc_new)
                req_index += 1
                continue
            if self.config.spec_all_or_nothing and request.spec_token_ids:
                # A truncated draft TREE is unverifiable (children would
                # be cut mid-topology): drop the drafts BEFORE allocation
                # so no blocks are allocated — or victims preempted — for
                # tokens the step will not run.
                num_spec_fit = (
                    request.num_computed_tokens + num_new_tokens
                    - request.num_tokens
                )
                if 0 < num_spec_fit < len(request.spec_token_ids):
                    num_new_tokens -= num_spec_fit

            # Allocate, preempting the tail of `running` on failure.
            while True:
                new_blocks = self.kv_cache_manager.allocate_slots(
                    request, num_new_tokens,
                    num_lookahead_tokens=self.config.num_lookahead_tokens,
                    num_stored_tokens=self._blk_stored(request, blk_pass),
                )
                if new_blocks is not None:
                    break
                if not self.running:
                    break
                victim = self.running.pop()
                self._preempt(victim)
                preempted_in_step.add(victim.request_id)
                if victim is request:
                    new_blocks = None
                    break
            if new_blocks is None:
                # The request itself was preempted; scheduling continues with
                # whatever remains.
                self._rollback_encoder(request, enc_new)
                break

            # Trim speculative tokens that no longer fit the scheduled
            # window (all-or-nothing tree trims happened pre-allocation).
            if request.spec_token_ids:
                num_scheduled_spec = (
                    request.num_computed_tokens + num_new_tokens - request.num_tokens
                )
                if num_scheduled_spec > 0:
                    scheduled_spec_tokens[request.request_id] = (
                        request.spec_token_ids[:num_scheduled_spec]
                    )

            num_scheduled_tokens[request.request_id] = num_new_tokens
            token_budget -= num_new_tokens
            new_blocks_per_req[request.request_id] = _block_ids(new_blocks)
            if enc_new:
                enc_sched.setdefault(request.request_id, []).extend(enc_new)
            starts[request.request_id] = request.num_computed_tokens
            if self._block_len:
                self._blk_after_schedule(
                    request, num_new_tokens, blk_pass, block_passes
                )
            else:
                self._after_schedule(request, num_new_tokens)
            req_index += 1

        # Phase 2: admit waiting requests.
        while (
            self.waiting
            and token_budget > 0
            and len(self.running) < self.config.max_num_seqs
        ):
            request = self.waiting.peek()

            # Async scheduling: a preempted request with an in-flight output
            # token must wait for it to materialize before re-prefilling —
            # and an invalid-load recompute must wait for ALL its garbage
            # in-flight outputs to drain (a resumed step's legit output
            # would otherwise be indistinguishable from them).
            if (
                request.num_output_placeholders > 0
                or request.dropping_invalid
                or (self._block_len and request.num_inflight_steps > 0)
            ):
                break

            # Structured-output grammar still compiling -> leave in queue.
            if request.use_structured_output and self.structured_output_manager:
                try:
                    ready = self.structured_output_manager.is_ready(request)
                except Exception as e:
                    # Grammar failed to compile: fail this request, don't
                    # kill the engine loop.
                    logger.error(
                        "grammar compile failed for %s: %s",
                        request.request_id, e,
                    )
                    self.waiting.popleft()
                    request.status = RequestStatus.FINISHED_ABORTED
                    self._free_request(request)
                    # Surface the failure to the frontend on the next
                    # update (otherwise the client would hang forever).
                    self._failed_requests.append(request)
                    continue
                if not ready:
                    break

            # Prefix-cache hit discovery (only before first schedule;
            # resumed-preempted requests keep their progress at 0 and may
            # re-hit the cache too).
            is_mean_pooling = (
                request.pooling_params is not None
                and request.pooling_params.pooling_type == "mean"
            )
            # Mean pooling averages the hidden states of the tokens that
            # actually run through the model this step — a prefix-cache hit
            # or a split prompt would silently average a suffix only.
            # Prompt logprobs likewise need logits for EVERY prompt
            # position, so cache hits must not skip prefill compute
            # (reference: prompt_logprobs forces recompute of cached
            # tokens).
            wants_prompt_lp = (
                request.sampling_params is not None
                and request.sampling_params.prompt_logprobs is not None
            )
            # Multimodal prompts are excluded from prefix caching: block
            # hashes cover token ids only, and placeholder ids are
            # identical across different images (hashing mm content into
            # the blocks is the fix — future work).
            new_computed_blocks, num_new_computed_tokens = (
                self.kv_cache_manager.get_computed_blocks(request)
                if request.num_computed_tokens == 0
                and not is_mean_pooling
                and not wants_prompt_lp
                and not request.mm_inputs
                else (None, 0)
            )
            # External KV tier: whole blocks beyond the device hit.
            num_external_tokens = 0
            if (
                self.kv_connector is not None
                and request.num_computed_tokens == 0
                and not request.skip_external_kv
                and request.block_hashes
                # External hits skip compute too: same exclusions as the
                # device prefix-cache path above.
                and not wants_prompt_lp
                and not is_mean_pooling
                and not request.mm_inputs
            ):
                num_external_tokens = (
                    self.kv_connector.get_num_new_matched_tokens(
                        request.block_hashes, num_new_computed_tokens,
                        self.block_size,
                    )
                )
                # Leave at least one token to schedule.
                cap = request.num_tokens - 1 - num_new_computed_tokens
                num_external_tokens = max(
                    0,
                    min(num_external_tokens, cap)
                    // self.block_size * self.block_size,
                )
                num_new_computed_tokens += num_external_tokens

            blk_pass = None
            if self._block_len:
                request.blk_open = False  # a waiting request holds no K/V
                plan = self._blk_plan(
                    request,
                    request.num_computed_tokens + num_new_computed_tokens,
                    token_budget,
                )
                if plan is None:
                    break  # no room for a whole block in this step
                num_new_tokens, blk_pass = plan
            else:
                num_new_tokens = (
                    request.num_tokens
                    - request.num_computed_tokens
                    - num_new_computed_tokens
                )
                chunk_cap = self._effective_chunk_threshold()
                if chunk_cap > 0:
                    num_new_tokens = min(num_new_tokens, chunk_cap)
                num_new_tokens = min(num_new_tokens, token_budget)
            assert num_new_tokens > 0
            # Encoder gate (see phase 1). The window starts after any
            # device-cache / external-tier hit.
            num_new_tokens, enc_new = self._try_schedule_encoder(
                request,
                request.num_computed_tokens + num_new_computed_tokens,
                num_new_tokens,
            )
            if num_new_tokens <= 0:
                self._rollback_encoder(request, enc_new)
                break  # encoder budget exhausted; wait for frees
            if is_mean_pooling and num_new_tokens < (
                request.num_tokens - request.num_computed_tokens
            ):
                self._rollback_encoder(request, enc_new)
                break  # wait for a step with budget for the whole prompt

            if num_external_tokens:
                # Hold back prefix-cache registration from the start of
                # the externally-loaded span until update_from_output
                # confirms the load (garbage otherwise; a one-shot hold
                # would be lifted by the NEXT schedule's allocate, which
                # under async lag-1 runs before the failure is known).
                self.kv_cache_manager.defer_caching_from(
                    request.request_id,
                    request.num_computed_tokens
                    + num_new_computed_tokens
                    - num_external_tokens,
                )
            new_blocks = self.kv_cache_manager.allocate_slots(
                request,
                num_new_tokens,
                new_computed_blocks=new_computed_blocks,
                num_new_computed_tokens=num_new_computed_tokens,
                num_lookahead_tokens=self.config.num_lookahead_tokens,
                num_stored_tokens=self._blk_stored(
                    request, blk_pass, num_new_computed_tokens
                ),
            )
            if new_blocks is None:
                if num_external_tokens:
                    self.kv_cache_manager.confirm_external_load(
                        request.request_id
                    )
                self._rollback_encoder(request, enc_new)
                break  # out of KV space; don't preempt running for waiting

            if num_external_tokens:
                # The blocks covering the external span (right after the
                # device-cache hit) must be filled by the runner before
                # this step runs.
                req_blocks = self.kv_cache_manager.req_to_blocks[
                    request.request_id
                ]
                dev_blocks = (
                    num_new_computed_tokens - num_external_tokens
                ) // self.block_size
                ext_blocks = num_external_tokens // self.block_size
                load_ids = [
                    b.block_id
                    for b in req_blocks[dev_blocks : dev_blocks + ext_blocks]
                ]
                keys = list(
                    request.block_hashes[dev_blocks : dev_blocks + ext_blocks]
                )
                kv_connector_load[request.request_id] = (load_ids, keys)

            self.waiting.popleft()
            resumed = request.status == RequestStatus.PREEMPTED
            if not resumed:
                # First scheduling: queue delay = arrival -> now
                # (reference: request queue_time metric,
                # vllm/v1/metrics/loggers.py request_queue_time_seconds).
                request.queue_time = max(
                    0.0, time.monotonic() - request.arrival_time
                )
                self._queue_times.append(request.queue_time)
            request.status = RequestStatus.RUNNING
            self.running.append(request)
            if request.num_cached_tokens < 0:
                request.num_cached_tokens = num_new_computed_tokens
            request.num_computed_tokens += num_new_computed_tokens

            all_block_ids = self.kv_cache_manager.get_block_ids(request.request_id)
            if resumed or request.request_id in preempted_in_step:
                cached.req_ids.append(request.request_id)
                cached.resumed_from_preemption.append(True)
                cached.resumed_req_token_ids.append(list(request.all_token_ids))
                cached.new_block_ids.append(all_block_ids)
                cached.num_computed_tokens.append(request.num_computed_tokens)
                preempted_in_step.discard(request.request_id)
            else:
                scheduled_new_reqs.append(
                    NewRequestData(
                        req_id=request.request_id,
                        prompt_token_ids=request.prompt_token_ids,
                        sampling_params=request.sampling_params,
                        block_ids=all_block_ids,
                        num_computed_tokens=request.num_computed_tokens,
                        lora_name=request.lora_name,
                        mm_inputs=request.mm_inputs or None,
                        eos_token_id=request.eos_token_id,
                        pooling_params=request.pooling_params,
                    )
                )
            num_scheduled_tokens[request.request_id] = num_new_tokens
            token_budget -= num_new_tokens
            if enc_new:
                enc_sched.setdefault(request.request_id, []).extend(enc_new)
            starts[request.request_id] = request.num_computed_tokens
            if self._block_len:
                self._blk_after_schedule(
                    request, num_new_tokens, blk_pass, block_passes
                )
            else:
                self._after_schedule(request, num_new_tokens)

        # Phase 3: cached-request records for already-running requests.
        for request in self.running:
            req_id = request.request_id
            if req_id not in num_scheduled_tokens or req_id in (
                r.req_id for r in scheduled_new_reqs
            ):
                continue
            if req_id in cached.req_ids:
                continue  # resumed this step, already recorded
            cached.req_ids.append(req_id)
            cached.resumed_from_preemption.append(False)
            cached.resumed_req_token_ids.append(None)
            cached.new_block_ids.append(new_blocks_per_req.get(req_id, ()))
            cached.num_computed_tokens.append(
                starts.get(req_id, request.num_computed_tokens)
            )

        # Structured output: ship each constrained request's current
        # device-mask-table row (the runner gathers the bitmask on device).
        structured_rows: dict[str, int] = {}
        if self.structured_output_manager is not None:
            for rid in num_scheduled_tokens:
                req = self.requests[rid]
                if req.use_structured_output:
                    structured_rows[rid] = (
                        self.structured_output_manager.state_row(req)
                    )

        total = sum(num_scheduled_tokens.values())
        # Adaptive speculation: feed the occupancy gate from this step's
        # realized token-budget fill (same definition as the
        # vllm:engine_batch_occupancy gauge) and ship the verdicts — the
        # runner skips proposer work under suspension and clips next-step
        # proposals to the per-request budgets.
        spec_suspended = False
        spec_budgets: dict[str, int] = {}
        if adaptive_on:
            if total > 0:
                self.adaptive_spec.observe_occupancy(
                    total / self.config.max_num_batched_tokens
                )
            spec_suspended = self.adaptive_spec.suspended
            if not spec_suspended:
                spec_budgets = {
                    rid: self.adaptive_spec.draft_budget(rid)
                    for rid in num_scheduled_tokens
                }
        output = SchedulerOutput(
            spec_suspended=spec_suspended,
            spec_draft_budgets=spec_budgets,
            kv_connector_load=kv_connector_load,
            scheduled_new_reqs=scheduled_new_reqs,
            scheduled_cached_reqs=cached,
            num_scheduled_tokens=num_scheduled_tokens,
            total_num_scheduled_tokens=total,
            scheduled_spec_decode_tokens=scheduled_spec_tokens,
            block_passes=block_passes,
            structured_output_request_ids=structured_rows,
            scheduled_encoder_inputs=enc_sched,
            free_encoder_input_ids=self._take_encoder_frees(),
            finished_req_ids=self.finished_req_ids,
            # Victims preempted this step and not resumed within it (the
            # same-step-resume case went through resumed_from_preemption),
            # plus any carried over from undispatched schedules.
            preempted_req_ids=self._pending_preempted | preempted_in_step,
            req_refs={
                rid: self.requests[rid] for rid in num_scheduled_tokens
            },
        )
        self.finished_req_ids = set()
        self._pending_preempted = set()
        if total > 0:
            self._last_step_req_ids = set(num_scheduled_tokens)
        if self.kv_event_publisher is not None:
            self.kv_event_publisher.flush()
        return output

    # ------------------------------------------------------------------
    # Multimodal encoder scheduling
    # ------------------------------------------------------------------

    def _try_schedule_encoder(
        self, request: Request, start: int, num_new: int
    ) -> tuple[int, list[int]]:
        """Reserve encoder-cache budget for image spans intersecting
        [start, start+num_new). When the budget cannot hold a span's
        output, the chunk is trimmed to end just before that span.
        Returns (trimmed num_new, tentatively allocated input indexes) —
        the caller commits them only if the request is actually scheduled.
        """
        if not request.mm_inputs:
            return num_new, []
        rid = request.request_id
        allocated: list[int] = []
        for i, mm in enumerate(request.mm_inputs):
            off, n = mm.offset, mm.num_tokens
            if off + n <= start:
                continue  # fully computed in earlier chunks
            if off >= start + num_new:
                break
            if self.encoder_cache_manager.has(rid, i):
                continue
            if not self.encoder_cache_manager.can_allocate(n):
                num_new = max(0, off - start)
                break
            self.encoder_cache_manager.allocate(rid, i, n)
            allocated.append(i)
        # Drop reservations that fell outside the trimmed window.
        keep: list[int] = []
        for i in allocated:
            mm = request.mm_inputs[i]
            if mm.offset < start + num_new and mm.offset + mm.num_tokens > start:
                keep.append(i)
            else:
                self.encoder_cache_manager.free_input(rid, i)
        return num_new, keep

    def _rollback_encoder(self, request: Request, idxs: list[int]) -> None:
        for i in idxs:
            self.encoder_cache_manager.free_input(request.request_id, i)

    def _free_encoder_for_request(self, request: Request) -> None:
        freed = self.encoder_cache_manager.free_request(request.request_id)
        self._pending_encoder_frees.extend(freed)

    def _take_encoder_frees(self) -> list[tuple[str, int]]:
        out = self._pending_encoder_frees
        self._pending_encoder_frees = []
        return out

    def _blk_stored(self, request: Request, bp, hit: int = 0) -> int | None:
        """How many of the request's tokens hold their final K/V once the
        step has run (``KVCacheManager.allocate_slots``): a denoising pass
        stores nothing, and of the ``2B`` positions of a pass that stores
        a clean block and denoises the next only the first ``B`` are
        final, so the open block's page stays out of the prefix cache
        until its last block is stored."""
        if bp is None:
            return None
        stored = request.num_computed_tokens + hit
        return stored + self._block_len if bp.store else stored

    def _after_schedule(self, request: Request, num_new_tokens: int) -> None:
        """Hook run right after a request is scheduled this step. The async
        scheduler advances num_computed_tokens here (reference:
        ``_update_after_schedule``); the sync scheduler advances in
        update_from_output."""

    def _drain_invalid(
        self, request: Request, runner_output, req_index: int
    ) -> None:
        """Consume an invalid-epoch step's placeholders without appending
        its garbage tokens; resume waits until the count drains to 0."""
        drained = len(runner_output.sampled_token_ids[req_index])
        request.num_output_placeholders = max(
            0, request.num_output_placeholders - drained
        )
        request.num_inflight_steps = max(0, request.num_inflight_steps - 1)
        if (
            request.num_output_placeholders == 0
            and request.num_inflight_steps == 0
        ):
            request.dropping_invalid = False

    def _pressure_preempt(self) -> set[str]:
        """Load-based priority preemption (the scheduler half of the QoS
        layer). Two triggers, both bounded by max_preemptions_per_step
        and the per-victim preemption cap (so nothing starves):

        - A strictly higher-priority request has waited past the
          pressure budget (half its TTFT budget by default) while the
          step is out of request slots: preempt the lowest-priority
          running decode so phase 2 can admit it this step.
        - Brownout rung 4: preempt batch-class (priority > 0) decodes
          on pressure alone so interactive requests recover; an
          interactive (priority 0) request is NEVER a rung-4 victim.

        Victims resume token-identically via the normal PREEMPTED path
        and are journal-backed frontend-side like any preemption."""
        if not self._qos_on():
            return set()
        rung4 = self.brownout_rung >= 4
        budget_s = self.config.pressure_preemption_s
        max_step = self.config.max_preemptions_per_step
        if max_step <= 0 or (not rung4 and budget_s <= 0):
            return set()
        now = time.monotonic()
        preempted: set[str] = set()

        def victim_ok(r: Request) -> bool:
            return (
                r.pooling_params is None
                # Decode phase only: a prefill victim would just re-run
                # the same prefill, freeing nothing durable.
                and (r.num_output_tokens > 0
                     or r.num_output_placeholders > 0)
                and r.num_preemptions
                < self.config.max_preemptions_per_request
            )

        while len(preempted) < max_step:
            victim = None
            slots_full = len(self.running) >= self.config.max_num_seqs
            if self.waiting and slots_full:
                head = self.waiting.peek()
                triggered = rung4 or (
                    budget_s > 0
                    and head.status == RequestStatus.WAITING
                    and now - head.arrival_time >= budget_s
                )
                if triggered:
                    candidates = [
                        r for r in self.running
                        if r.priority > head.priority and victim_ok(r)
                        and (not rung4 or r.priority > 0)
                    ]
                    if candidates:
                        victim = max(
                            candidates,
                            key=lambda r: (r.priority, r.arrival_time),
                        )
            elif rung4 and any(r.priority == 0 for r in self.running):
                # Rung 4 without queue pressure: shed batch-class decodes
                # from the batch so interactive ITL recovers.
                candidates = [
                    r for r in self.running
                    if r.priority > 0 and victim_ok(r)
                ]
                if candidates:
                    victim = max(
                        candidates,
                        key=lambda r: (r.priority, r.arrival_time),
                    )
            if victim is None:
                break
            self.running.remove(victim)
            self._preempt(victim, to_tail=True)
            self._pressure_preemptions_total += 1
            preempted.add(victim.request_id)
        return preempted

    def _preempt(self, request: Request, *, to_tail: bool = False) -> None:
        self.kv_cache_manager.free(request)
        # Encoder outputs are tied to computed positions; a resume restarts
        # prefill from 0 and re-encodes.
        self._free_encoder_for_request(request)
        request.status = RequestStatus.PREEMPTED
        request.num_computed_tokens = 0
        # num_output_placeholders is intentionally preserved: an in-flight
        # sampled token still materializes via update_from_output, and the
        # resume guard below waits for it (else the resumed prefill would
        # re-sample an already-sampled position).
        request.num_preemptions += 1
        request.spec_token_ids = []
        # Generation by diffusion: the block closes (its K/V are gone) and
        # is reopened after the new prefill; what the host knows of it, the
        # tokens committed out of order included, stays.
        request.blk_open = False
        request.blk_sched_committed = 0
        self._num_preempted_total += 1
        self._preempted_rids.append(request.request_id)
        if to_tail:
            # Pressure/rung-4 victims re-queue at the tail (re-sorted by
            # priority under the priority policy): the higher-priority
            # request they yielded to must admit first, not the victim.
            self.waiting.add(request)
        else:
            self.waiting.prepend(request)

    # ------------------------------------------------------------------
    # update_from_output()
    # ------------------------------------------------------------------

    def update_from_output(
        self,
        scheduler_output: SchedulerOutput,
        runner_output: ModelRunnerOutput,
    ) -> EngineCoreOutputs:
        outputs: list[EngineCoreOutput] = []
        spec_scheduled = scheduler_output.scheduled_spec_decode_tokens

        for req_index, req_id in enumerate(runner_output.req_ids):
            request = self.requests.get(req_id)
            if request is None or (
                scheduler_output.req_refs
                and scheduler_output.req_refs.get(req_id) is not request
            ):
                # Finished externally between schedule and update, or the id
                # was reused by a new request while this step was in flight.
                continue
            num_tokens_scheduled = scheduler_output.num_scheduled_tokens.get(req_id)
            if num_tokens_scheduled is None:
                continue
            if req_id in runner_output.invalid_req_ids:
                # External KV load failed: this step's output for the
                # request is garbage. Reschedule via the preemption path
                # (blocks freed, recompute from 0) — the failure stays
                # request-scoped. Reference: _handle_invalid_blocks,
                # scheduler.py:2226.
                self._num_invalid_loads += 1
                logger.warning(
                    "rescheduling %s after failed external KV load",
                    req_id,
                )
                request.skip_external_kv = True
                request.dropping_invalid = True
                # The scheduling-time cache-hit account included the
                # blocks whose load just failed; re-account on the
                # reschedule so telemetry (and the disagg handoff
                # classifier) see what was actually served from cache.
                request.num_cached_tokens = -1
                # Belt-and-braces: registration of the external span was
                # deferred, but evict anything this request did register.
                self.kv_cache_manager.invalidate_cached_blocks(request)
                if request.status == RequestStatus.RUNNING:
                    if request in self.running:
                        self.running.remove(request)
                    self._preempt(request)
                # else: already preempted (block-pressure victim between
                # dispatch and update) — it sits in waiting once; a second
                # _preempt would double-insert it.
                self._drain_invalid(request, runner_output, req_index)
                continue
            if request.dropping_invalid:
                # In-flight output from before an invalid-load preemption:
                # drain its placeholders without materializing tokens.
                self._drain_invalid(request, runner_output, req_index)
                continue
            if req_id in runner_output.numeric_error_req_ids:
                # Numeric guard tripped on this request's row (NaN/Inf
                # logits or out-of-range sampled token): terminal
                # per-request error — the batch's other rows and the
                # engine itself keep going.
                request.status = RequestStatus.FINISHED_ERROR
                if request in self.running:
                    self.running.remove(request)
                elif request in self.waiting:
                    self.waiting.remove(request)
                self._free_request(request)
                outputs.append(
                    EngineCoreOutput(
                        req_id=req_id,
                        new_token_ids=[],
                        finish_reason=request.get_finished_reason(),
                    )
                )
                continue
            if req_id in scheduler_output.kv_connector_load:
                # The step that performed this request's external KV load
                # finalized clean: its span is trustworthy, lift the
                # prefix-cache registration hold (the next allocate
                # catches registration up).
                self.kv_cache_manager.confirm_external_load(req_id)

            if self._block_len and request.pooling_params is None:
                bp = scheduler_output.block_passes.get(req_id)
                if bp is not None:
                    self._blk_update(
                        request, req_id, bp,
                        runner_output.block_results.get(req_id), outputs,
                    )
                continue

            generated = runner_output.sampled_token_ids[req_index]
            scheduled_spec = spec_scheduled.get(req_id, [])

            if request.pooling_params is not None:
                # Pooling request: no tokens are ever emitted; it finishes
                # when the final chunk's pooled vector arrives.
                if not self.async_scheduling:
                    request.num_computed_tokens += num_tokens_scheduled
                request.num_output_placeholders = 0
                pooled = runner_output.pooler_outputs.get(req_id)
                if pooled is not None:
                    request.status = RequestStatus.FINISHED_STOPPED
                    if request in self.running:
                        self.running.remove(request)
                    else:
                        self.waiting.remove(request)
                    self._free_request(request)
                    outputs.append(
                        EngineCoreOutput(
                            req_id=req_id,
                            new_token_ids=[],
                            finish_reason=request.get_finished_reason(),
                            pooled=pooled,
                        )
                    )
                continue

            if not self.async_scheduling:
                request.num_computed_tokens += num_tokens_scheduled
            elif generated:
                request.num_output_placeholders = max(
                    0, request.num_output_placeholders - len(generated)
                )
                request.num_inflight_steps = max(
                    0, request.num_inflight_steps - 1
                )
            if scheduled_spec:
                # Tree mode schedules num_nodes drafts but can accept at
                # most tree-depth of them; cap the denominator so the
                # acceptance rate stays comparable with chain mode.
                cap = self.config.spec_max_accept_per_step
                self._spec_num_draft_tokens += (
                    min(len(scheduled_spec), cap) if cap
                    else len(scheduled_spec)
                )
                self._spec_num_accepted_tokens += max(0, len(generated) - 1)
                self._spec_accept_lengths.append(len(generated))
                self._spec_draft_lens.append(len(scheduled_spec))
                if self.adaptive_spec is not None:
                    # Feed the controller even while the A/B switch holds
                    # it out of the decision path: the EMAs stay warm so
                    # re-enabling adapts from live evidence, not a reset.
                    self.adaptive_spec.observe(
                        req_id, len(scheduled_spec),
                        max(0, len(generated) - 1),
                    )
                # Verification: len(generated) = accepted drafts + 1 bonus.
                # Rejected draft positions hold garbage KV; roll computed
                # count back so they are recomputed (reference:
                # scheduler.py:1290 spec-token accounting).
                num_rejected = len(scheduled_spec) + 1 - len(generated)
                assert num_rejected >= 0
                request.num_computed_tokens -= num_rejected
            request.spec_token_ids = []

            new_token_ids: list[int] = []
            stopped = False
            structured = (
                request.use_structured_output
                and self.structured_output_manager is not None
            )
            for tok in generated:
                request.append_output_token_ids(tok)
                new_token_ids.append(tok)
                if structured:
                    self.structured_output_manager.advance(request, tok)
                    if request.fsm_state < 0:
                        # Grammar cannot continue (e.g. complete and only
                        # EOS remained): terminate.
                        request.status = RequestStatus.FINISHED_STOPPED
                        stopped = True
                        break
                stopped = self._check_stop(request)
                if stopped:
                    break

            if req_id in runner_output.draft_token_ids:
                request.spec_token_ids = runner_output.draft_token_ids[req_id]

            prompt_lp_delta = runner_output.prompt_logprobs.get(req_id)

            if stopped:
                # Async scheduling: the request may have been preempted
                # between this step's dispatch and now (it sits in waiting).
                if request in self.running:
                    self.running.remove(request)
                else:
                    self.waiting.remove(request)
                self._free_request(request)

            if new_token_ids or stopped or prompt_lp_delta is not None:
                new_logprobs = None
                lp = runner_output.logprobs
                if (
                    lp is not None
                    and request.sampling_params.logprobs is not None
                    # Runner emits one logprob row per request per step; spec
                    # decode (N>1 tokens) must extend the runner contract to
                    # per-token rows before logprobs can combine with it.
                    and len(new_token_ids) == 1
                    and req_index < len(lp.sampled_token_ranks)
                ):
                    new_logprobs = [
                        (
                            lp.logprob_token_ids[req_index],
                            lp.logprobs[req_index],
                            new_token_ids[0],
                            lp.sampled_logprobs[req_index],
                            lp.sampled_token_ranks[req_index],
                        )
                    ]
                outputs.append(
                    EngineCoreOutput(
                        req_id=req_id,
                        new_token_ids=new_token_ids,
                        finish_reason=request.get_finished_reason(),
                        stop_reason=request.stop_reason,
                        new_logprobs=new_logprobs,
                        prompt_logprobs_delta=prompt_lp_delta,
                        num_cached_tokens=max(request.num_cached_tokens, 0),
                        queue_time=request.queue_time,
                        kv_blocks_held=(
                            self.kv_cache_manager.num_pages_held(req_id)
                        ),
                    )
                )

        # Encoder-cache eviction: spans whose every placeholder position is
        # now computed no longer need their encoder output.
        for req_id in scheduler_output.num_scheduled_tokens:
            request = self.requests.get(req_id)
            if request is None or not request.mm_inputs:
                continue
            done_to = request.num_computed_tokens
            for i, mm in enumerate(request.mm_inputs):
                if (
                    mm.offset + mm.num_tokens <= done_to
                    and self.encoder_cache_manager.free_input(req_id, i)
                ):
                    self._pending_encoder_frees.append((req_id, i))

        # Surface engine-side failures (e.g. grammar compile errors) so the
        # frontend releases the waiting client.
        self._drain_failed_into(outputs)

        return EngineCoreOutputs(
            outputs=outputs,
            scheduler_stats=self.make_stats(),
            timestamp=time.monotonic(),
        )

    def _drain_failed_into(self, outputs: list[EngineCoreOutput]) -> None:
        for request in self._failed_requests:
            outputs.append(
                EngineCoreOutput(
                    req_id=request.request_id,
                    new_token_ids=[],
                    finish_reason=request.get_finished_reason(),
                    stop_reason=request.stop_reason,
                )
            )
        self._failed_requests = []

    def drain_failed(self) -> EngineCoreOutputs | None:
        """Failure records when no step is running to carry them
        (e.g. the failed request was the only one)."""
        if not self._failed_requests:
            return None
        outputs: list[EngineCoreOutput] = []
        self._drain_failed_into(outputs)
        return EngineCoreOutputs(
            outputs=outputs,
            scheduler_stats=self.make_stats(),
            timestamp=time.monotonic(),
        )

    def _check_stop(self, request: Request) -> bool:
        """Stop conditions checked engine-side (stop *strings* are checked in
        the frontend detokenizer). Reference: ``vllm/v1/core/sched/utils.py
        check_stop``."""
        params = request.sampling_params
        if (
            request.num_tokens >= self.config.max_model_len
            or request.num_output_tokens >= request.max_tokens
        ):
            request.status = RequestStatus.FINISHED_LENGTH_CAPPED
            return True
        if request.num_output_tokens < params.min_tokens:
            return False
        last = request.all_token_ids[-1]
        if not params.ignore_eos and last == request.eos_token_id:
            request.status = RequestStatus.FINISHED_STOPPED
            return True
        if last in params.all_stop_token_ids:
            request.status = RequestStatus.FINISHED_STOPPED
            request.stop_reason = last
            return True
        return False

    # ------------------------------------------------------------------
    # Stats
    # ------------------------------------------------------------------

    def make_stats(self) -> SchedulerStats:
        stats = self.kv_cache_manager.prefix_cache_stats
        queue_times, self._queue_times = self._queue_times, []
        accept_lengths, self._spec_accept_lengths = (
            self._spec_accept_lengths, []
        )
        draft_lens, self._spec_draft_lens = self._spec_draft_lens, []
        preempted_rids, self._preempted_rids = self._preempted_rids, []
        ctl = self.adaptive_spec
        # Pages held and pages there are, summed over these snapshots (one
        # a step): their ratio over a stretch of time is the mean share of
        # the pool in use there.
        in_use = self.kv_cache_manager.pages_in_use_by_group()
        pool = self.kv_cache_manager.block_pool
        self._kv_page_steps["held"] += sum(in_use)
        self._kv_page_steps["pool"] += pool.num_blocks - pool.num_colors
        return SchedulerStats(
            num_running_reqs=len(self.running),
            num_waiting_reqs=len(self.waiting),
            kv_cache_usage=self.kv_cache_manager.usage,
            kv_group_pages_in_use=in_use,
            kv_pages_allocated=(
                self.kv_cache_manager.pages_allocated_by_group()),
            kv_window_pages_freed=self.kv_cache_manager.window_pages_freed,
            kv_group_page_tokens=self._kv_group_page_tokens,
            kv_token_bytes=self._kv_token_bytes,
            kv_page_steps=dict(self._kv_page_steps),
            prefix_cache_queries=stats.queries,
            prefix_cache_hits=stats.hits,
            num_preempted_reqs=self._num_preempted_total,
            spec_num_draft_tokens=self._spec_num_draft_tokens,
            spec_num_accepted_tokens=self._spec_num_accepted_tokens,
            queue_times=queue_times,
            spec_accept_lengths=accept_lengths,
            spec_draft_lens=draft_lens,
            spec_acceptance_rate_ema=(
                ctl.acceptance_rate() if ctl is not None else None
            ),
            spec_suspended=(
                self._adaptive_spec_on() and ctl.suspended
            ),
            spec_suspensions=(
                ctl.suspensions_total if ctl is not None else 0
            ),
            preempted_req_ids=preempted_rids,
            pressure_preemptions=self._pressure_preemptions_total,
            brownout_rung=self.brownout_rung if self._qos_on() else 0,
        )
