"""TPU model runner: persistent-jit step over bucketed ragged batches.

Reference analog: ``vllm/v1/worker/gpu_model_runner.py`` (7.1k LoC of CUDA
graph + torch.compile machinery). The TPU design collapses most of it
(SURVEY.md §7): ONE jitted step function per (tokens, reqs, blocks) bucket
replaces CUDA-graph capture/dispatch; XLA recompiles per bucket and caches.
Host work per step is pure vectorized numpy (single host core).

Step dataflow:
  host: scheduler output -> persistent InputBatch diff -> flat padded arrays
  device (jit): embed -> L x (norm/qkv/rope/KV-insert/paged-attn/mlp)
                -> gather last-token hidden -> logits -> sample
  host: fetch sampled ids -> ModelRunnerOutput
"""

from __future__ import annotations

import bisect
import functools
import time
from contextlib import nullcontext
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from vllm_tpu.config import EngineConfig
from vllm_tpu.core.sched_output import (
    ModelRunnerOutput,
    SchedulerOutput,
)
from vllm_tpu.logger import init_logger
from vllm_tpu.ops.attention import (
    AttentionMetadata,
    decode_kernel_tiling,
    ragged_kernel_tiling,
)
from vllm_tpu.ops.gmm_kernel import count_visits as count_gmm_visits
from vllm_tpu.ops.mla_kernel import count_latent_pairs, latent_blocks
from vllm_tpu.ops.rpa_decode_kernel import decode_row_order, decode_tile_counts
from vllm_tpu.ops.rpa_kernel import count_ragged_pairs
from vllm_tpu.resilience.failpoints import fail_point
from vllm_tpu.tracing import compiles, trace_span
from vllm_tpu.sample.sampler import (
    SamplingMetadata,
    dispatch_sample,
    sample,
    sampler_kernel_eligible,
)
from vllm_tpu.worker.input_batch import InputBatch

logger = init_logger(__name__)


class StepHandle:
    """A dispatched-but-not-fetched step (device arrays + row bookkeeping)."""

    def __init__(self, req_order=None, do_sample=None, sampled=None, lp=None,
                 row_states=None, empty: bool = False,
                 spec: bool = False) -> None:
        self.req_order = req_order or []
        self.do_sample = do_sample
        self.sampled = sampled  # [R] ids, or (out_tokens [R,S+1], num_out [R])
        self.lp = lp
        self.spec = spec
        # CachedRequestState identities at dispatch time: finalize only folds
        # a token into a row still owned by the same request instance (the
        # id may have been reused while this step was in flight).
        self.row_states = row_states or []
        self.empty = empty
        # Adaptive speculation verdicts for THIS step (from the
        # SchedulerOutput): suspended = skip all proposer work at
        # finalize; budgets clip next-step proposals per request.
        self.spec_suspended = False
        self.spec_draft_budgets: dict[str, int] = {}
        self.drafts = None  # EAGLE proposals [R, K] (device array)
        self.pooled = None  # (last [R, D], mean [R, D]) pooling outputs
        self.nan_count = None  # device scalar when VLLM_TPU_NAN_CHECK
        self.prompt_lp = None  # (vals, ids, tok_lp, rank) over [T]
        self.prompt_rows = None  # [(row_i, offset, start, n, prompt_len)]
        self.moe_counts = None  # [L, E] live tokens routed to each expert
        # Numeric integrity guard (opt-in): per-row "logits not finite"
        # device bool [r_pad]; forced_nan simulates a fully poisoned
        # logits tensor (model_runner.step failpoint, action `nan`).
        self.row_bad = None
        self.forced_nan = False
        # Requests whose external KV load failed this step: their outputs
        # are garbage and the scheduler must reschedule them (reference:
        # invalid-block recovery, scheduler.py:2123).
        self.failed_loads: set[str] = set()
        # The runner's timing of this step: host preparation of its inputs
        # (dispatch) and the wait for its results (finalize), in seconds.
        self.prep_s: float | None = None
        self.wait_s: float | None = None
        # The step's block passes (generation by diffusion; None for an
        # autoregressive model).
        self.block_passes = None


def window_live_pairs(q_lens, kv_lens, window: int) -> int:
    """(query token, key) pairs a causal mask with ``i - j < window`` lets
    through over these rows: a query at position ``p`` sees ``min(p + 1,
    window)`` keys, and a row's queries are its last ``q_len`` positions."""
    q_lens = np.asarray(q_lens, np.int64)
    kv_lens = np.asarray(kv_lens, np.int64)
    first = kv_lens - q_lens  # position of a row's first query
    # Queries under the window see p + 1 keys, the others ``window``.
    ramp = np.clip(window - 1 - first, 0, q_lens)  # queries with p + 1 < window
    ramp_sum = ramp * first + ramp * (ramp + 1) // 2
    return int(np.sum(ramp_sum + (q_lens - ramp) * window))


def head_rungs(r_pad: int) -> list[int]:
    """The rows the block epilogue's head may run over under generation by
    diffusion: a quarter, a half or all of the request bucket. A step
    takes the least rung that holds its live rows, which come first."""
    return sorted({r for r in (r_pad // 4, r_pad // 2, r_pad) if r})


def head_rung(rungs: list[int], live):
    """The place in ``rungs`` of the least that holds ``live`` rows: an int
    on the host, a traced scalar in the step program."""
    return sum((live > r) * 1 for r in rungs[:-1])


def _bucket(value: int, buckets: list[int]) -> int:
    i = bisect.bisect_left(buckets, value)
    if i == len(buckets):
        raise ValueError(f"{value} exceeds largest bucket {buckets[-1]}")
    return buckets[i]


class ModelRunner:
    def __init__(
        self,
        config: EngineConfig,
        model: Any,
        params: Any,
        num_kv_blocks: int,
        mesh: Any | None = None,
        draft_model: Any | None = None,
        draft_params: Any | None = None,
    ) -> None:
        self.config = config
        self.model = model
        self.params = params
        self.mesh = mesh
        self.tp_size = config.parallel_config.tensor_parallel_size
        sched = config.scheduler_config
        cache = config.cache_config
        self.block_size = cache.block_size
        # Kernel escape hatches, fixed for the runner's life by the
        # scheduler config. enable_sampler_kernel is a static argument
        # of the jitted step; enable_decode_attention gates the static
        # decode_only flag host-side.
        self.enable_decode_attention = sched.enable_decode_attention
        self.enable_sampler_kernel = sched.enable_sampler_kernel

        self.max_blocks_per_req = -(-sched.max_model_len // cache.block_size)
        # Device-resident empty placeholders (avoid a per-step 0-byte upload;
        # each device_put is a host-to-device round trip).
        self._empty_penalty = (
            jnp.zeros((0, 0), jnp.int32),
            jnp.zeros((0, 0), bool),
        )
        # KV groups over the one page pool (the worker grouped the layers'
        # specs at load): a block table and a slot mapping a group into the
        # step program, stacked on a leading axis where there are several.
        self.kv_groups = cache.kv_cache_groups or [None]
        self.num_kv_groups = len(self.kv_groups)
        # Tokens a page of each group holds (the engine's, for a uniform
        # model), and the group whose tables are longest: the step
        # program's padded blocks a request follow its counts.
        self.group_block_sizes = [
            g.block_size if g else cache.block_size for g in self.kv_groups]
        self._longest_tables = self.group_block_sizes.index(
            min(self.group_block_sizes))
        if self.num_kv_groups > 1 and (
            config.speculative_config.enabled
            or sched.enable_cascade_attention
        ):
            raise NotImplementedError(
                "several KV groups with speculative decoding or cascade "
                "attention: those paths derive slots from ONE block table "
                "inside the step program"
            )
        self.input_batch = InputBatch(
            max_num_reqs=sched.max_num_seqs,
            max_model_len=sched.max_model_len,
            max_blocks_per_req=self.max_blocks_per_req,
            num_kv_groups=self.num_kv_groups,
        )

        comp = config.compilation_config
        self.token_buckets = comp.token_buckets
        self.request_buckets = comp.request_buckets
        self.block_buckets = comp.page_buckets(self.max_blocks_per_req)
        # Async-scheduling state: the previous dispatched step's sampled
        # device array + its request->row mapping (token feedback source).
        # _last_sampled is kept padded to the LARGEST request bucket so the
        # jitted step sees one prev_sampled shape (else every bucket
        # transition would recompile: current-bucket x previous-bucket).
        self._last_sampled = None
        self._host_params = None
        self._host_draft = None
        self._max_pipeline_depth = sched.async_pipeline_depth
        # Sparse logits-processor entry-count buckets (static trace dims).
        self._adj_buckets = [4, 16, 64, 512]
        self._max_r = self.request_buckets[-1]
        self._zero_sampled = jnp.zeros(self._max_r, jnp.int32)
        self._prev_rows: dict[str, int] = {}
        # Generation by diffusion over blocks (models/generation.py): rows
        # of block_len tokens with a committed flag a position (of twice
        # block_len where a clean block's store rides with the next
        # block's first denoising pass). The tokens and flags one pass
        # leaves are the next pass's input, so they ride the feedback slot
        # the sampled tokens ride: _last_sampled is then
        # [max_r, 2 * block_len], tokens beside flags, by the previous
        # step's rows; after a pass that stored a block they are the NEXT
        # block's.
        gen = config.model_config.generation or {}
        self.block_len = int(gen.get("block_length", 0))
        self.mask_token_id = int(gen.get("mask_token_id", 0))
        if self.block_len:
            self._zero_sampled = jnp.zeros(
                (self._max_r, 2 * self.block_len), jnp.int32
            )
        # Structured output: device-resident packed-bitmask table, one row
        # per (grammar, state); row 0 = all-ones (unconstrained). Synced
        # from the StructuredOutputManager when new grammars compile; a
        # step ships only per-row state indices (see _prepare_inputs).
        self.structured_output_manager: Any = None
        self._grammar_version = -1
        self._mask_w = -(-model.vocab_size // 32)
        self._mask_table = None  # jnp [manager.table_rows, W] uint32

        # Speculative decoding: ngram drafting is pure host logic; EAGLE
        # drafting runs INSIDE the jitted step (draft prefill over the same
        # ragged batch + a greedy chain); the verification
        # rejection-sampler runs in-jit for both.
        spec = config.speculative_config
        self.num_spec = spec.num_speculative_tokens if spec.enabled else 0
        # Tree verification: static topology; num_spec is the NODE count.
        self.tree = None
        if spec.enabled and spec.spec_tree is not None:
            from vllm_tpu.spec_decode.tree import build_tree

            self.tree = build_tree(spec.spec_tree)
            assert self.num_spec == self.tree.num_nodes
            if (
                getattr(model, "sliding_window", None) is not None
                # Gemma-class models keep the cache-level window None but
                # pass real per-layer windows into the attention op.
                or getattr(model, "window", None) is not None
                or hasattr(model, "_layer_window")
            ):
                raise ValueError(
                    "tree spec verification with sliding-window attention "
                    "is not supported (the window floor is undefined for "
                    "tree positions)"
                )
        self.proposer = None
        self.draft_model = None
        self.draft_params = None
        self.draft_kv = None
        self.medusa = None
        self.medusa_params = None
        self._in_jit_drafts = self._eagle_drafts
        if spec.enabled and spec.method == "ngram":
            from vllm_tpu.spec_decode.ngram_proposer import NgramProposer

            self.proposer = NgramProposer(
                spec.prompt_lookup_min, spec.prompt_lookup_max,
                spec.num_speculative_tokens,
            )
        elif spec.enabled and spec.method == "suffix":
            from vllm_tpu.spec_decode.suffix_proposer import SuffixProposer

            self.proposer = SuffixProposer(spec.num_speculative_tokens)
        elif spec.enabled and spec.method == "medusa":
            from vllm_tpu.spec_decode.medusa import MedusaHeads

            self.medusa = MedusaHeads(
                # Tree mode: one head per DEPTH level, not per node.
                self.tree.num_levels if self.tree else
                spec.num_speculative_tokens,
                model.hidden_size, model.vocab_size, model.dtype,
            )
            if spec.model:
                self.medusa_params = self.medusa.load_params(spec.model)
            else:
                assert config.model_config.load_format == "dummy", (
                    "medusa spec decode needs speculative_config.model"
                )
                self.medusa_params = self.medusa.init_dummy_params(
                    jax.random.PRNGKey(config.model_config.seed + 2)
                )
            # Heads ride the params tree so they flow through the jit (a
            # captured array would be folded into the executable).
            self.params = {**self.params, "medusa": self.medusa_params}
        elif spec.enabled and spec.method in ("eagle", "eagle3", "draft_model"):
            assert draft_model is not None and draft_params is not None, (
                f"{spec.method} spec decode needs a loaded draft model"
            )
            self.draft_model = draft_model
            self.draft_params = draft_params
            if spec.method == "draft_model":
                self._in_jit_drafts = self._draft_lm_drafts
            if spec.method == "eagle3":
                # Target captures three intermediate hiddens for the
                # draft's fused conditioning.
                self.model.aux_hidden_layers = draft_model.default_aux_layers(
                    self.model.num_layers
                )
        # DP-pool suffix-corpus share (adaptive speculation): built
        # lazily once the kv-fabric connector attaches (its peer wiring
        # is the transport). None until then; "dead" stops re-probing
        # after a build failure.
        self._suffix_share = None
        self._suffix_share_dead = False

        # A model that can count the live tokens routed to each expert
        # (models/mixtral.py) returns the [L, E] counts as a third output
        # of apply(): EPLB's load statistics, vllm:moe_experts_hit_total.
        self._moe_counts = getattr(model, "supports_eplb", False) or getattr(
            model, "returns_expert_counts", False
        )
        if self._moe_counts:
            model.return_expert_counts = True
        # An expert share (the model holds ``num_experts`` of a wider
        # router): the pairs routed HERE are data, so the device counts
        # them; the host's product below counts every pair of a model
        # that holds them all.
        self._moe_share = getattr(model, "first_expert", None) is not None
        # EPLB: logical->physical expert indirection + load accumulator.
        self._eplb = getattr(model, "enable_eplb", False)
        self.eplb_state = None
        if self._eplb:
            from vllm_tpu.parallel.eplb import EplbState, identity_l2p

            pc = config.parallel_config
            groups = pc.eplb_num_groups or (
                pc.tensor_parallel_size
                if pc.enable_expert_parallel
                else max(pc.expert_parallel_size, 1)
            )
            if model.num_experts % groups:
                raise ValueError(
                    f"eplb groups ({groups}) must divide num_experts "
                    f"({model.num_experts})"
                )
            window = pc.eplb_window
            if groups == 1:
                # One group = nothing to balance: keep the statistics
                # (metrics) but never pay the weight shuffle.
                logger.warning(
                    "EPLB enabled with a single expert group; collecting "
                    "load stats only (no rebalancing)"
                )
                window = 0
            self.eplb_state = EplbState(
                model.num_layers, model.num_experts, groups,
                window=window,
            )
            if "eplb_l2p" not in self.params["layers"]:
                # Checkpoint loads have no map leaf (dummy init does).
                ident = identity_l2p(
                    model.num_layers, model.num_experts
                )
                self.params = {
                    **self.params,
                    "layers": {**self.params["layers"], "eplb_l2p": ident},
                }

        self.kv_connector = None
        self._kv_load_fn = jax.jit(
            lambda kv, ids, vals: kv.at[:, ids].set(vals),
            donate_argnums=(0,),
        )
        self.lora_manager = None
        if config.lora_config.enable_lora:
            from vllm_tpu.lora.manager import LoRAManager

            if not getattr(model, "supports_lora", False):
                raise ValueError(
                    f"{type(model).__name__} does not support LoRA serving"
                )
            model.enable_lora = True
            self.lora_manager = LoRAManager(
                model, self.params, config.lora_config.max_loras,
                config.lora_config.max_lora_rank,
            )

        self.num_kv_blocks = num_kv_blocks
        self.kv_cache = self._alloc_kv_cache()

        if self.draft_model is not None:
            self.draft_kv = self._alloc_draft_kv()

        # kv_cache (arg 1) and the draft KV (arg 2, when present) are
        # donated back as outputs (in-place reuse). The function's name is
        # the name of the program in a profiler trace (jit_vllm_tpu_step),
        # whatever the method behind it is called.
        def vllm_tpu_step(*args, **flags):
            return self._step(*args, **flags)

        self._step_fn = jax.jit(
            vllm_tpu_step,
            static_argnames=(
                "t_pad",
                "r_pad",
                "b_pad",
                "needs_penalties",
                "needs_top_k",
                "needs_top_p_min_p",
                "needs_gumbel",
                "needs_grammar",
                "needs_pooling",
                "num_logprobs",
                "num_prompt_logprobs",
                "num_spec",
                "num_adj",
                "num_allow",
                "cascade_blocks",
                "has_state_slots",
                "decode_only",
                "enable_sampler_kernel",
                "block_len",
            ),
            donate_argnums=(1, 2) if self.draft_model is not None else (1,),
        )
        from vllm_tpu import envs

        # Per-request state slots: hybrid attention+SSM Mamba state
        # (reference: HybridKVCacheCoordinator per-type groups) and
        # encoder-decoder cross-attention KV (reference:
        # CrossAttentionManager) share the slot lifecycle.
        self.is_encdec = getattr(model, "is_encoder_decoder", False)
        self._is_hybrid = (
            getattr(model, "is_hybrid_ssm", False) or self.is_encdec
        )
        self._state_slot_free = list(range(sched.max_num_seqs - 1, -1, -1))
        self._state_slot_of: dict[str, int] = {}

        # Multimodal: device-side encoder-output cache keyed by
        # (req_id, mm_input_index); budget enforced scheduler-side.
        self.is_mm = getattr(self.model, "is_multimodal", False)
        self._mm_cache: dict[tuple[str, int], jax.Array] = {}
        if self.is_mm:
            self._encode_fn = jax.jit(self.model.encode_images)
            if hasattr(self.model, "encode_videos"):
                self._encode_video_fn = jax.jit(self.model.encode_videos)
        elif self.is_encdec:
            # Encoder forward + cross-KV projection, slot write donated
            # in place (runs once per request, outside the step jit).
            def _encode_and_store(kv_cache, params, enc_ids, enc_len, slot):
                block = self.model.encode_cross(params, enc_ids, enc_len)
                return {
                    **kv_cache,
                    "cross": kv_cache["cross"].at[:, slot].set(block),
                    "cross_len": kv_cache["cross_len"].at[slot].set(enc_len),
                }

            self._encode_fn = jax.jit(_encode_and_store, donate_argnums=(0,))
        else:
            self._encode_fn = None

        self._nan_check = envs.VLLM_TPU_NAN_CHECK
        # Execution-layer fault containment (resilience config / env):
        # per-row isfinite guard on the step logits (rides the existing
        # device-feedback fetch) + host-side sampled-token range check. A
        # trip fails only the afflicted requests, never the engine.
        rc = getattr(config, "resilience_config", None)
        self._guard_numerics = bool(
            getattr(rc, "numeric_guard", False) or envs.VLLM_TPU_NUMERIC_GUARD
        )
        self.numeric_guard_trips: dict[str, int] = {}
        # Step watchdog: a dispatched step (device enqueue + finalize
        # fetch) exceeding the deadline is a device hang — the busy loop
        # is alive but the accelerator is wedged. core_proc overrides
        # watchdog.on_trip to escalate to a supervised engine restart.
        self.watchdog = None
        watchdog_s = float(getattr(rc, "step_watchdog_s", 0.0) or 0.0)
        if watchdog_s > 0:
            from vllm_tpu.worker.watchdog import StepWatchdog

            self.watchdog = StepWatchdog(watchdog_s)
        # Native (C++) step-input assembly; None -> python loop.
        self._native_prep = None
        if not envs.VLLM_TPU_DISABLE_NATIVE_PREP:
            from vllm_tpu.native import get_host_prep

            self._native_prep = get_host_prep()
        # Bucket-cache counters (exported via SchedulerStats).
        self._seen_buckets: set[tuple] = set()
        self.bucket_compiles = 0
        self.bucket_hits = 0
        # Rows assembled by the Python loop instead of the native fill
        # (native unavailable/disabled, or draft-row patch-up on spec
        # batches). Exported via SchedulerStats -> prometheus.
        self.prep_fallback_rows = 0
        # Decode-path observability: jitted-step launches, launches whose
        # batch was decode-only (eligible for the sequence-pipelined
        # kernel), and tokens sampled (several a row a launch under
        # speculation and diffusion). Exported via SchedulerStats.
        self.step_launches = 0
        self.decode_only_launches = 0
        self.launch_sampled_tokens = 0
        # Sampling-epilogue routing: in-jit sample() calls routed to the
        # fused sort-free kernel vs sampling rows that fell back to the
        # XLA reference (greedy-only launches count as neither).
        self.sampler_kernel_launches = 0
        self.sampler_fallback_rows = 0
        # Step-time breakdown, always on and cumulative: host preparation
        # of the step's inputs, the call that enqueues the jitted step, and
        # the wait for its results in finalize (the host waiting for the
        # device). EngineCore exports each step's share as the prepare /
        # fetch phases of vllm:engine_step_duration_seconds.
        self.timing = {"prep_s": 0.0, "dispatch_s": 0.0, "wait_s": 0.0,
                       "steps": 0}
        # By kind of step, cumulative ("decode" = the decode-only step
        # that runs the decode attention kernel, "mixed" = every other):
        # steps, scheduled tokens, and context tokens attended (the sum
        # over the step's rows of the positions each row attends to).
        self.steps_by_kind = {"decode": 0, "mixed": 0}
        self.step_tokens_by_kind = {"decode": 0, "mixed": 0}
        self.attn_context_tokens_by_kind = {"decode": 0, "mixed": 0}
        # (query token, key) pairs of the ragged attention kernel's calls,
        # cumulative, a q head a layer: those the mask lets through, and
        # those its tiles compute for them (``count_ragged_pairs`` mirrors
        # its choice of rung and of key extent). Steps that run it only.
        self.attn_ragged_pairs = {"live": 0, "computed": 0}
        self._ragged_tilings: dict[tuple[int, int], Any] = {}
        # Tiles of the decode attention kernel, cumulative, one call (a
        # layer's, on one heads block) a decode-only step that takes it:
        # those its walk runs, and the least the rows' keys need
        # (``decode_tile_counts``). run / least is what the rows of a
        # block that end apart cost.
        self.attn_decode_tiles = {"run": 0, "least": 0.0}
        self._decode_tilings: dict[tuple[int, int], Any] = {}
        # Generation by diffusion over blocks, cumulative: row-passes by
        # kind, and the positions denoising passes committed. Mixture of
        # experts, cumulative: token-expert pairs over live tokens, and
        # experts that got a live token, both summed over layers.
        # A pass that stores a clean block AND denoises the next is one
        # pass of the model and counts once, as "denoise"; "store" counts
        # the lone store passes, diffusion_stores_fused the stores that
        # rode along.
        self.diffusion_passes_by_kind = {"denoise": 0, "store": 0}
        self.diffusion_stores_fused = 0
        self.diffusion_committed_tokens = 0
        # The head of a block step: the positions of its live rows, those
        # of the rung it ran over (head_rungs), and the steps a rung.
        self.diffusion_head_positions = {"live": 0, "computed": 0}
        self.diffusion_head_steps_by_rung = {}
        self.moe_assignments = 0
        self.moe_experts_hit = 0
        self.moe_pad_pairs_skipped = 0
        # Visits of the grouped matmul (ops/gmm_kernel.py), cumulative, a
        # (group, row tile) pair a column tile a call: "first" waits for
        # its expert's matrix and starts the copy of one further on,
        # "resident" runs on the matrix its group's first visit brought.
        self.moe_gmm_visits = {"first": 0, "resident": 0}
        # Linear attention over per-request state slots, cumulative, a
        # layer of that kind: rows whose state a step reads and writes,
        # and tokens by the form that takes them ("decode" = a row's one
        # token, "chunk" = a row of several).
        self.linear_attn_state_rows = 0
        # Window attention layers beside full ones, cumulative: rows a
        # step times the layers of that kind, and the (query token, key)
        # pairs their mask lets through, a q head a layer.
        self.window_attn_rows = 0
        self.window_attn_pairs = 0
        # Rows a step ran through attention with a sink logit a head,
        # summed over the layers that have one.
        self.attn_sink_rows = 0
        self.linear_attn_tokens_by_kind = {"chunk": 0, "decode": 0}
        # Seconds spent in first uses of a step program (tracing, lowering
        # and compiling or loading it), beside bucket_compiles, their
        # count; the key of the first use now due, set by _prepare_inputs.
        self.first_use_s = 0.0
        self._first_use_key: tuple | None = None
        # The same seconds by stage: Python tracing, lowering, the backend
        # (XLA's compile or the load from the persistent cache), from
        # JAX's own events read before and after the call, and "run", the
        # rest of the call. They sum to first_use_s.
        self.first_use_stage_s = {
            "trace": 0.0, "lower": 0.0, "backend": 0.0, "run": 0.0}

    # ------------------------------------------------------------------
    # Jitted step
    # ------------------------------------------------------------------

    def _unpack(self, ibuf, fbuf, counts, prompt_mask, t, r, b, num_spec=0,
                num_adj=0, num_allow=0, num_prompt_logprobs=0,
                cascade_blocks=0, has_state_slots=0, decode_only=False,
                block_len=0):
        """Split the two packed host buffers back into metadata pytrees.

        One contiguous i32 upload + one f32 upload per step instead of ~12
        separate device_puts — host->device latency (not bandwidth) is the
        cost on TPU hosts, so transfers are batched. Slices are static; XLA
        folds them into the consumers.
        """
        o = 0

        def take(n):
            nonlocal o
            out = ibuf[o : o + n]
            o += n
            return out

        token_ids = take(t)
        s = num_spec
        # Further KV groups' slot mappings and block tables ride the END
        # of the buffer, so that a uniform model's layout is what it was;
        # the model's layers pick their group's row of both.
        more = self.num_kv_groups - 1
        if more:
            tail = ibuf[ibuf.shape[0] - more * (t + r * b):]
            group_slots = tail[: more * t].reshape(more, t)
            group_tables = tail[more * t:].reshape(more, r, b)
            stacked = lambda first, rest: jnp.concatenate([first[None], rest])
        else:
            group_slots = group_tables = None
            stacked = lambda first, rest: first
        md = AttentionMetadata(
            positions=take(t),
            slot_mapping=stacked(take(t), group_slots),
            token_req_idx=take(t),
            seq_lens=take(r),
            query_start_loc=take(r + 1),
            logits_indices=take(r),
            num_seqs=take(1),
            block_tables=stacked(take(r * b).reshape(r, b), group_tables),
            num_common_prefix_blocks=cascade_blocks,
            decode_only=bool(decode_only),
            tp_mesh=self.mesh,
            block_len=max(block_len, 1),
        )
        if decode_only:
            # Once a step, for every layer's call of the decode kernel.
            md.decode_order = decode_row_order(md.seq_lens, md.num_seqs)
        top_k = take(r)
        prng_keys = jax.lax.bitcast_convert_type(
            take(2 * r).reshape(r, 2), jnp.uint32
        )
        # Async scheduling: per-row index into the previous step's sampled
        # array for rows whose input token is still in flight (-1 = none).
        feedback = take(r)
        # Structured output: per-row index into the device mask table
        # (0 = unconstrained row).
        grammar_rows = take(r)
        # Logits processors: sparse per-row (token id, value) adjustments
        # (logit_bias, banned bad-words continuations, min-tokens EOS
        # suppression; padding id = vocab size -> dropped by the scatter)
        # and per-row allowed-token whitelists.
        adj_ids = take(r * num_adj).reshape(r, num_adj) if num_adj else None
        allow_ids = (
            take(r * num_allow).reshape(r, num_allow) if num_allow else None
        )
        allow_active = take(r) if num_allow else None
        # EAGLE: per-row next KNOWN token for the draft's shifted input at
        # the anchor position (-1 = use the freshly emitted token).
        draft_next = take(r) if self.draft_model is not None else None
        # LoRA: adapter slot per token (0 = none).
        token_lora = take(t) if self.lora_manager is not None else None
        # Prompt logprobs: the TRUE successor token per position (a
        # chunk's last position's successor is not in this buffer).
        plp_next = take(t) if num_prompt_logprobs else None
        spec = None
        if s > 0:
            spec = dict(
                num_draft=take(r),
                draft_ids=take(r * s).reshape(r, s),
                sample_pos=take(r * (s + 1)).reshape(r, s + 1),
            )
        if has_state_slots:
            # Hybrid attention+SSM: per-request Mamba state slot.
            md.state_slots = take(r)
        blk = None
        if block_len:
            # Generation by diffusion over blocks, per row: the kind of
            # pass (0 = a prefill chunk, 1 = denoise, 2 = store, 3 = a
            # row of two blocks, the first stored and the second
            # denoised), the commit rule and how many positions a pass
            # commits, the host's committed flags of the block the row
            # starts with (read where the row has no feedback), and the
            # dynamic rule's threshold (last in the f32 buffer).
            blk = (
                take(r), take(r), take(r),
                take(r * block_len).reshape(r, block_len),
                fbuf[fbuf.shape[0] - r :],
            )
        adj_vals = (
            fbuf[6 * r : 6 * r + r * num_adj].reshape(r, num_adj)
            if num_adj
            else None
        )
        sampling = SamplingMetadata(
            temperature=fbuf[0:r],
            top_p=fbuf[r : 2 * r],
            min_p=fbuf[2 * r : 3 * r],
            presence_penalty=fbuf[3 * r : 4 * r],
            frequency_penalty=fbuf[4 * r : 5 * r],
            repetition_penalty=fbuf[5 * r : 6 * r],
            top_k=top_k,
            prng_keys=prng_keys,
            output_token_counts=counts,
            prompt_token_mask=prompt_mask,
        )
        logit_adjust = (adj_ids, adj_vals, allow_ids, allow_active)
        return (token_ids, md, sampling, feedback, grammar_rows, logit_adjust,
                draft_next, token_lora, plp_next, spec, blk)

    @functools.cached_property
    def _attn_call_geometry(self):
        """(q heads, KV heads, head_dim, tokens a page) of the model's plain
        attention call and whether its pages are the split layout, for the
        counts of the attention kernels' work; None where the model has no
        such call (a cache of its own making)."""
        m = self.model
        # A model whose pages are the split layout names the call the
        # counts follow (its full layers'): (q heads, KV heads, the key's
        # stored lanes, tokens a page of that group).
        split = getattr(m, "ragged_kernel_geometry", None)
        if split:
            return split(self.block_size), True
        # (a cache of the model's own making counts where the model says
        # that its pages are plain K/V under ``paged_attention``, beside
        # whatever else it keeps: ``plain_kv_pages``)
        plain = all(hasattr(m, a) for a in
                    ("num_heads", "num_kv_heads", "head_dim")) and (
            getattr(m, "plain_kv_pages", False) or not (
                hasattr(m, "kv_cache_shape") or hasattr(m, "alloc_kv_cache")))
        if not plain:
            return None
        return (m.num_heads, m.num_kv_heads, m.head_dim, self.block_size), False

    @functools.cached_property
    def _gmm_column_tiles(self) -> int:
        """Column tiles of a sparse layer's grouped matmuls where they run
        the repo's kernel (one chip, on a TPU), else 0."""
        from vllm_tpu.layers import moe

        m = self.model
        if not (moe._grouped_is_the_path() and hasattr(m, "moe_intermediate")):
            return 0
        return moe.gmm_column_tiles(m.hidden_size, m.moe_intermediate)

    def _count_ragged_pairs(self, q_lens, kv_lens, t_pad: int, b_pad: int):
        """Add a step's pairs to ``attn_ragged_pairs`` where its attention
        runs the ragged kernel (a model with plain K/V heads, on the chip)."""
        block_len = max(self.block_len, 1)
        key = (t_pad, b_pad)
        if key not in self._ragged_tilings:
            geometry, split = self._attn_call_geometry or (None, False)
            self._ragged_tilings[key] = geometry and (ragged_kernel_tiling(
                *geometry, self.model.dtype, self._kv_dtype(), t_pad,
                b_pad, block_len=block_len, tp_size=self.tp_size, split=split,
            ), geometry[3])
        tiling, page_size = self._ragged_tilings[key] or (None, 0)
        if tiling:
            live, computed = count_ragged_pairs(
                q_lens, kv_lens, tiling, page_size=page_size,
                block_len=block_len,
            )
            self.attn_ragged_pairs["live"] += live
            self.attn_ragged_pairs["computed"] += computed

    def _count_decode_tiles(self, kv_lens, r_pad: int, b_pad: int):
        """Add a decode-only step's tiles to ``attn_decode_tiles`` where its
        plain attention call runs the decode kernel: those one call runs
        on a heads block, and the least its rows' keys need."""
        key = (r_pad, b_pad)
        if key not in self._decode_tilings:
            blocks = None
            # (a call that states a window is the ragged kernel's)
            if self._attn_call_geometry and getattr(
                    self.model, "sliding_window", None) is None:
                (_, _, head_dim, page_size), split = self._attn_call_geometry
                blocks = decode_kernel_tiling(
                    head_dim, page_size, r_pad, b_pad, split=split)
            self._decode_tilings[key] = blocks and dict(
                page_size=page_size, num_seqs_per_block=blocks[0],
                num_kv_pages_per_block=blocks[1])
        blocks = self._decode_tilings[key]
        if blocks:
            run, least = decode_tile_counts(kv_lens, len(kv_lens), **blocks)
            self.attn_decode_tiles["run"] += run
            self.attn_decode_tiles["least"] += least

    def _build_tree_metadata(self, md, spec, t_pad: int, r_pad: int):
        """In-jit tree-verify views (host prep stays the chain layout).

        The step's token stream holds per-tree-row windows of
        ``[root, node_1..node_N]`` at consecutive slots. Three rewrites:

        1. positions: node tokens move to ``root_pos + depth`` (RoPE and
           downstream causality see tree coordinates).
        2. ``tree_paged``: a pseudo-sequence split for the paged-context
           part — non-tree rows keep their chunk as one sequence; a tree
           row becomes a prefix sequence ``[chunk_start..root]`` (kv_len
           ``root_pos+1`` — true causal for the prefix, root sees itself
           via its canonical slot) plus one single-query sequence per
           node with the same kv bound, so nodes see context + root but
           never sibling slots. Node pseudo-positions are capped at
           ``root_pos`` for the reference path's position mask.
        3. ``tree_mask``: node-vs-node ancestor mask (root excluded —
           covered by the paged part).
        """
        import dataclasses

        import numpy as np

        tree = self.tree
        s = tree.num_nodes
        t = t_pad
        base_idx = spec["sample_pos"][:, 0]  # [R] stream idx of the root
        # Per-row node count: s for a full tree, fewer when the adaptive
        # controller prunes to a breadth-first level prefix (a prefix is
        # a valid subtree — every node's parent precedes it, so the
        # window layout, ancestor mask, and KV consolidation all hold
        # with the per-row bound below).
        num_draft = spec["num_draft"]  # [R]
        active = num_draft > 0  # [R] row has a (possibly pruned) tree
        row = jnp.clip(md.token_req_idx, 0, r_pad - 1)  # [T]
        tok = jnp.arange(t, dtype=jnp.int32)
        t_live = md.query_start_loc[jnp.clip(md.num_seqs[0], 0, r_pad)]
        live = tok < t_live
        off = tok - base_idx[row]
        in_nodes = (
            active[row] & (off >= 1) & (off <= num_draft[row]) & live
        )

        depth_nodes = jnp.asarray(np.asarray(tree.depth[1:], np.int32))
        off_n = jnp.clip(off - 1, 0, s - 1)
        pos0 = md.positions
        root_pos = pos0[jnp.clip(base_idx, 0, t - 1)]  # [R]
        positions = jnp.where(
            in_nodes, root_pos[row] + depth_nodes[off_n], pos0
        )

        # Pseudo-sequence split.
        starts = ((tok == md.query_start_loc[row]) | in_nodes) & live
        pid = jnp.cumsum(starts.astype(jnp.int32)) - 1  # [T]
        n_pseudo = jnp.sum(starts.astype(jnp.int32))
        idx = jnp.where(starts, pid, t)  # OOB rows dropped
        cu = jnp.full((t + 1,), t_live, jnp.int32).at[idx].set(
            tok, mode="drop"
        )
        rows_ps = jnp.zeros((t,), jnp.int32).at[idx].set(row, mode="drop")
        kv_val = jnp.where(
            active[row], root_pos[row] + 1, md.seq_lens[row]
        )
        kv_ps = jnp.zeros((t,), jnp.int32).at[idx].set(kv_val, mode="drop")
        paged = dataclasses.replace(
            md,
            positions=jnp.where(in_nodes, root_pos[row], pos0),
            block_tables=md.block_tables[rows_ps],
            seq_lens=kv_ps,
            query_start_loc=cu,
            token_req_idx=jnp.clip(pid, 0, t - 1),
            num_seqs=n_pseudo.reshape(1),
            num_common_prefix_blocks=0,
            state_slots=None,
        )

        node_mask = jnp.asarray(tree.ancestor_mask()[1:, 1:])  # [s, s]
        tmask = jnp.where(
            in_nodes[:, None], node_mask[off_n], False
        )  # [T, s]
        window_start = base_idx[row] + 1
        return dataclasses.replace(
            md, positions=positions, tree_mask=tmask,
            tree_window_start=window_start, tree_paged=paged,
        ), active

    def _consolidate_tree_kv(
        self, kv_cache, slot_mapping, base_idx, kv_src, num_out, active
    ):
        """Copy accepted nodes' KV rows to canonical slots.

        An accepted node's cache rows are valid as-is (its K/V were
        computed over exactly its ancestor chain); only their SLOTS are
        window-ordered. The accepted path's depth-d node moves from slot
        ``slot_mapping[base + kv_src[d-1]]`` to
        ``slot_mapping[base + d]`` (same index when the tree degenerates
        to a chain — the scatter is then a no-op write)."""
        nl, nb, bs, rows, lanes = kv_cache.shape
        depth = self.tree.num_levels
        t = slot_mapping.shape[0]
        d_arr = jnp.arange(depth, dtype=jnp.int32)[None, :]
        src_slots = slot_mapping[
            jnp.clip(base_idx[:, None] + kv_src, 0, t - 1)
        ]  # [R, D]
        dst_slots = slot_mapping[
            jnp.clip(base_idx[:, None] + 1 + d_arr, 0, t - 1)
        ]
        valid = (d_arr < (num_out[:, None] - 1)) & active[:, None]
        flat = kv_cache.reshape(nl * nb * bs, rows, lanes)
        lidx = (
            jnp.arange(nl, dtype=jnp.int32)[:, None, None] * (nb * bs)
        )  # [L, 1, 1]
        gathered = flat[lidx + src_slots[None]]  # [L, R, D, rows, lanes]
        dst = jnp.where(
            valid[None], lidx + dst_slots[None], nl * nb * bs
        )
        flat = flat.at[dst].set(gathered, mode="drop")
        return flat.reshape(nl, nb, bs, rows, lanes)

    def _step(
        self,
        params,
        kv_cache,
        draft_kv,
        ibuf,
        fbuf,
        counts,
        prompt_mask,
        prev_sampled,
        mask_table,
        mm_embeds=None,  # [T, D] encoder-output overlay (multimodal)
        mm_mask=None,  # [T] bool, True at overlaid positions
        mrope_positions=None,  # [3, T] i32 (Qwen2-VL m-rope streams)
        *,
        t_pad: int,
        r_pad: int,
        b_pad: int,
        needs_penalties: bool,
        needs_top_k: bool,
        needs_top_p_min_p: bool,
        needs_gumbel: bool,
        needs_grammar: bool,
        needs_pooling: bool = False,
        num_logprobs: int = 0,
        num_prompt_logprobs: int = 0,
        num_spec: int = 0,
        num_adj: int = 0,
        num_allow: int = 0,
        cascade_blocks: int = 0,
        has_state_slots: int = 0,
        decode_only: bool = False,
        enable_sampler_kernel: bool = True,
        block_len: int = 0,
    ):
        (token_ids, md, sampling, feedback, grammar_rows, logit_adjust,
         draft_next, token_lora, plp_next, spec, blk) = self._unpack(
            ibuf, fbuf, counts, prompt_mask, t_pad, r_pad, b_pad, num_spec,
            num_adj, num_allow, num_prompt_logprobs, cascade_blocks,
            has_state_slots, decode_only, block_len,
        )
        # Device-side token feedback (async scheduling): a decode row whose
        # input token was sampled by the still-in-flight previous step reads
        # it straight from that step's device output — the host never waits.
        needs_fb = feedback >= 0
        prev_tok = prev_sampled[jnp.clip(feedback, 0, prev_sampled.shape[0] - 1)]
        if block_len:
            # The same for a block row: the tokens and committed flags its
            # previous pass left, still in flight, are this pass's input.
            # A committed position feeds its token, every other the mask
            # token's embedding. They are the block the row STARTS with;
            # the head, the sampler and the rule run over the row's LAST
            # block, which is the same one but in a row of two blocks
            # (kind 3): there the first is clean and stored, and the
            # second, which the host filled with mask tokens, has no
            # position committed yet.
            kind = blk[0]
            lanes = jnp.arange(block_len, dtype=jnp.int32)[None, :]
            blk_idx = jnp.where(
                (kind > 0)[:, None], md.query_start_loc[:-1, None] + lanes,
                t_pad,
            )  # [r, B] into [T]; out of range where the row runs no block
            blk_tok = jnp.where(
                needs_fb[:, None], prev_tok[:, :block_len],
                token_ids[jnp.clip(blk_idx, 0, t_pad - 1)],
            )
            blk_com = jnp.where(
                needs_fb[:, None], prev_tok[:, block_len:], blk[3]
            ) > 0
            token_ids = token_ids.at[blk_idx.reshape(-1)].set(
                jnp.where(blk_com, blk_tok, self.mask_token_id).reshape(-1),
                mode="drop",
            )
            blk_com = blk_com & (kind != 3)[:, None]
            flat_idx = jnp.clip(
                md.query_start_loc[1:, None] - block_len + lanes, 0, t_pad - 1
            )
        else:
            last_pos = jnp.maximum(md.query_start_loc[1:] - 1, 0)  # [r]
            # Rows without feedback scatter out of bounds (dropped) so
            # padded rows sharing a last_pos cannot clobber a live row's
            # fed token.
            idx = jnp.where(needs_fb, last_pos, t_pad)
            token_ids = token_ids.at[idx].set(prev_tok, mode="drop")
        if needs_penalties:
            # The fed in-flight token isn't in the host-built counts yet;
            # add it here so async penalties match sync semantics.
            from dataclasses import replace as _replace

            counts2 = sampling.output_token_counts.at[
                jnp.arange(r_pad), prev_tok
            ].add(needs_fb.astype(jnp.int32))
            sampling = _replace(sampling, output_token_counts=counts2)
        tree_active = None
        if num_spec > 0 and self.tree is not None:
            md, tree_active = self._build_tree_metadata(
                md, spec, t_pad, r_pad
            )
        mm_kw = {}
        if mm_embeds is not None:
            mm_kw["mm_embeds"] = mm_embeds
            mm_kw["mm_mask"] = mm_mask
        if mrope_positions is not None:
            mm_kw["mrope_positions"] = mrope_positions
        moe_counts = None
        out = self.model.apply(
            params, kv_cache, token_ids, md, token_lora_slot=token_lora,
            **mm_kw,
        )
        aux_h = None
        if self._moe_counts:
            hidden, kv_cache, moe_counts = out  # counts [L, E]
        elif getattr(self.model, "aux_hidden_layers", None) is not None:
            hidden, kv_cache, aux_h = out  # EAGLE-3 fused conditioning
        else:
            hidden, kv_cache = out
        if num_spec > 0:
            # Spec-decode verification: logits at every draft position plus
            # the bonus position, rejection-sampled in one traced pass.
            from vllm_tpu.sample.rejection_sampler import rejection_sample

            r, s1 = spec["sample_pos"].shape
            flat_pos = spec["sample_pos"].reshape(-1)
            logits3 = self.model.compute_logits(
                params, hidden[flat_pos]
            ).reshape(r, s1, -1)
            spec_nan = (
                jnp.isnan(logits3).sum() if self._nan_check else None
            )
            # Per-row numeric guard: any non-finite logit at any draft
            # position poisons the row (rides the same feedback fetch).
            spec_row_bad = (
                ~jnp.all(jnp.isfinite(logits3), axis=(1, 2))
                if self._guard_numerics else None
            )
            if self.tree is not None:
                from vllm_tpu.sample.tree_rejection import (
                    tree_rejection_sample,
                )

                draft_full = jnp.concatenate(
                    [jnp.zeros((r, 1), jnp.int32), spec["draft_ids"]],
                    axis=1,
                )
                out_tokens, num_out, kv_src = tree_rejection_sample(
                    logits3, draft_full, self.tree, sampling,
                    active=tree_active,
                    num_draft=spec["num_draft"],
                    needs_penalties=needs_penalties,
                    needs_top_k=needs_top_k,
                    needs_top_p_min_p=needs_top_p_min_p,
                    needs_gumbel=needs_gumbel,
                )
                kv_cache = self._consolidate_tree_kv(
                    kv_cache, md.slot_mapping, spec["sample_pos"][:, 0],
                    kv_src, num_out, tree_active,
                )
                anchor = jnp.clip(
                    spec["sample_pos"][:, 0] + kv_src[:, -1],
                    0, hidden.shape[0] - 1,
                )
                drafts = self.medusa.propose_tree(
                    params["medusa"], hidden[anchor], self.tree
                )
                return (kv_cache, draft_kv, (out_tokens, num_out), None,
                        drafts, None, spec_nan, None, moe_counts,
                        spec_row_bad)
            out_tokens, num_out = rejection_sample(
                logits3,
                spec["draft_ids"],
                spec["num_draft"],
                sampling,
                needs_penalties=needs_penalties,
                needs_top_k=needs_top_k,
                needs_top_p_min_p=needs_top_p_min_p,
                needs_gumbel=needs_gumbel,
            )
            drafts = None
            if self.draft_model is not None:
                rows_r = jnp.arange(r_pad)
                anchor = spec["sample_pos"][rows_r, num_out - 1]
                emitted = out_tokens[rows_r, num_out - 1]
                drafts, draft_kv = self._in_jit_drafts(
                    params, draft_kv, token_ids,
                    aux_h if aux_h is not None else hidden, md, anchor,
                    emitted, draft_next, r_pad,
                )
            elif self.medusa is not None:
                rows_r = jnp.arange(r_pad)
                anchor = spec["sample_pos"][rows_r, num_out - 1]
                drafts = self.medusa.propose(
                    params["medusa"], hidden[anchor]
                )
            return (kv_cache, draft_kv, (out_tokens, num_out), None, drafts,
                    None, spec_nan, None, moe_counts, spec_row_bad)
        if block_len:
            return self._block_epilogue(
                params, kv_cache, draft_kv, hidden, sampling, blk,
                flat_idx, blk_tok, blk_com, moe_counts,
                block_len=block_len, r_pad=r_pad, needs_top_k=needs_top_k,
                needs_top_p_min_p=needs_top_p_min_p,
                needs_gumbel=needs_gumbel, num_logprobs=num_logprobs,
                enable_sampler_kernel=enable_sampler_kernel,
                num_live=md.num_seqs[0],
            )
        last = hidden[md.logits_indices]  # [R, D]
        nan_count = None
        pooled = None
        prompt_lp = None
        if num_prompt_logprobs > 0:
            # Per-POSITION next-token logprobs over the whole chunk: the
            # [T, V] logits matmul is the inherent cost of the feature.
            full_lp = jax.nn.log_softmax(
                self.model.compute_logits(params, hidden), axis=-1
            )  # [T, V]
            pk_vals, pk_ids = jax.lax.top_k(full_lp, num_prompt_logprobs)
            # True successor per position, shipped from the host (a
            # chunk's last position's successor is not in this buffer).
            tok_lp = jnp.take_along_axis(
                full_lp, plp_next[:, None], axis=-1
            )[:, 0]
            tok_rank = jnp.sum(
                full_lp > tok_lp[:, None], axis=-1
            ).astype(jnp.int32)
            prompt_lp = (pk_vals, pk_ids, tok_lp, tok_rank)
        if needs_pooling:
            # "last" pooling = the gathered last-token hidden; "mean" is a
            # masked segment mean (live tokens only; single-chunk prompts,
            # enforced at admission). Both shipped; finalize picks per
            # request.
            t_live_dev = md.query_start_loc[md.num_seqs[0]]
            valid = jnp.arange(token_ids.shape[0]) < t_live_dev
            seg = jnp.where(valid, md.token_req_idx, r_pad)
            sums = jnp.zeros((r_pad, hidden.shape[-1]), jnp.float32)
            sums = sums.at[seg].add(
                hidden.astype(jnp.float32), mode="drop"
            )
            counts_seg = jnp.maximum(
                md.query_start_loc[1:] - md.query_start_loc[:-1], 1
            )
            mean = sums / counts_seg[:, None]
            pooled = (last.astype(jnp.float32), mean)
            if hasattr(self.model, "pooled_extra"):
                # Model-defined third pooling plane: CLS pooler vector or
                # classification logits (encoder-only family).
                pooled = pooled + (
                    self.model.pooled_extra(params, hidden, md, r_pad),
                )
        logits = self.model.compute_logits(params, last)  # [R, V] f32
        if self._nan_check:
            nan_count = jnp.isnan(logits).sum()
        # Per-row numeric guard on the RAW logits (before grammar/adjust
        # masking injects intentional -1e30s): a row with any NaN/Inf is
        # failed individually downstream, never the engine.
        row_bad = (
            ~jnp.all(jnp.isfinite(logits), axis=-1)
            if self._guard_numerics else None
        )
        if needs_grammar:
            # Gather each row's packed grammar bitmask from the
            # device-resident table and unpack bits (bit v%32 of word v//32
            # = token v); -inf out disallowed tokens before sampling.
            rows = mask_table[grammar_rows]  # [R, W] u32
            bits = (
                rows[:, :, None]
                >> jnp.arange(32, dtype=jnp.uint32)[None, None, :]
            ) & jnp.uint32(1)
            allowed = bits.reshape(r_pad, -1)[:, : logits.shape[-1]] != 0
            logits = jnp.where(allowed, logits, jnp.float32(-1e30))
        adj_ids, adj_vals, allow_ids, allow_active = logit_adjust
        if num_adj > 0:
            # Sparse scatter-add: bias entries carry their bias, bans carry
            # -1e30; padded entries (id = vocab) drop.
            logits = logits.at[
                jnp.arange(r_pad)[:, None], adj_ids
            ].add(adj_vals, mode="drop")
        if num_allow > 0:
            allow = jnp.zeros(logits.shape, bool)
            allow = allow.at[
                jnp.arange(r_pad)[:, None], allow_ids
            ].set(True, mode="drop")
            allow = allow | (allow_active == 0)[:, None]
            logits = jnp.where(allow, logits, jnp.float32(-1e30))
        sampled, raw_logprobs = dispatch_sample(
            logits,
            sampling,
            needs_penalties=needs_penalties,
            needs_top_k=needs_top_k,
            needs_top_p_min_p=needs_top_p_min_p,
            needs_gumbel=needs_gumbel,
            enable_kernel=enable_sampler_kernel,
            allow_interpret=True,
            tp_size=self.tp_size,
            num_active=md.num_seqs[0],  # live rows come first
        )

        drafts = None
        if self.draft_model is not None:
            # Runs even on logprob batches (whose drafts finalize discards):
            # the draft prefill maintains the draft KV cache for every
            # computed position — skipping it would leave permanent holes
            # that poison later proposals.
            drafts, draft_kv = self._in_jit_drafts(
                params, draft_kv, token_ids,
                aux_h if aux_h is not None else hidden, md,
                md.logits_indices, sampled, draft_next, r_pad,
            )
        elif self.medusa is not None:
            drafts = (
                self.medusa.propose_tree(params["medusa"], last, self.tree)
                if self.tree is not None
                else self.medusa.propose(params["medusa"], last)
            )
        if num_logprobs > 0:
            topk_vals, topk_ids = jax.lax.top_k(raw_logprobs, num_logprobs)
            sampled_lp = jnp.take_along_axis(
                raw_logprobs, sampled[:, None], axis=-1
            )[:, 0]
            sampled_rank = jnp.sum(
                raw_logprobs > sampled_lp[:, None], axis=-1
            ).astype(jnp.int32)
            lp = (topk_vals, topk_ids, sampled_lp, sampled_rank)
        else:
            lp = None
        return (kv_cache, draft_kv, sampled, lp, drafts, pooled, nan_count,
                prompt_lp, moe_counts, row_bad)

    def _block_epilogue(self, params, kv_cache, draft_kv, hidden, sampling,
                        blk, flat_idx, blk_tok, blk_com, moe_counts,
                        *, block_len, r_pad, needs_top_k, needs_top_p_min_p,
                        needs_gumbel, num_logprobs, enable_sampler_kernel,
                        num_live):
        """The end of a step under generation by diffusion over blocks:
        every position of every live row is sampled, with the probability
        of the token drawn under the distribution it was drawn from (its
        confidence), and the row's rule commits some of the uncommitted
        ones. Rule, count and threshold are data of the row, so requests
        under different rules share one program. ``flat_idx`` holds a
        row's LAST ``block_len`` positions: the open block, which in a row
        of two blocks (kind 3: the clean block, stored, then the next) is
        the second, so such a row pays the head for one block. A lone
        store pass (kind 2) commits nothing; after either the state handed
        to the next pass is the next block's: all masks after a lone
        store, what this pass committed after a row of two."""
        from dataclasses import replace as _replace

        from vllm_tpu.models.generation import REMASKING_RULES

        kind, rule, n_pass, _, threshold = blk
        b = block_len
        i32 = jnp.int32
        idx = flat_idx.reshape(-1)

        def rep(a):
            return jnp.repeat(a, b, axis=0)

        keys = rep(sampling.prng_keys)
        keys = keys.at[:, 1].add(
            jnp.tile(jnp.arange(b, dtype=keys.dtype), r_pad)
        )
        per_pos = {f: rep(getattr(sampling, f)) for f in (
            "temperature", "top_k", "top_p", "min_p", "presence_penalty",
            "frequency_penalty", "repetition_penalty")}
        per_pos["prng_keys"] = keys

        def over(rows):
            """The head, the sampler and whatever else reads the logits,
            over the first ``rows`` rows' positions: the head's weights
            are read once, the [n, V] logits stay in here, and what comes
            out is a position's (or a row's) own, padded to all ``r_pad``
            rows' with the zeros a row past the live ones reads from the
            sampler's kernel."""
            n = rows * b
            logits = self.model.compute_logits(
                params, hidden[idx[:n]]
            )  # [n, V] f32: the logits AT a position are its own token's
            drawn, raw_logprobs, prob = dispatch_sample(
                logits,
                _replace(sampling, **{k: a[:n] for k, a in per_pos.items()}),
                needs_penalties=False, needs_top_k=needs_top_k,
                needs_top_p_min_p=needs_top_p_min_p,
                needs_gumbel=needs_gumbel,
                enable_kernel=enable_sampler_kernel, allow_interpret=True,
                tp_size=self.tp_size, return_prob=True,
                num_active=num_live * b,  # live rows come first
            )
            res = {"drawn": drawn, "prob": prob}
            if num_logprobs > 0:
                topk_vals, topk_ids = jax.lax.top_k(raw_logprobs, num_logprobs)
                drawn_lp = jnp.take_along_axis(
                    raw_logprobs, drawn[:, None], axis=-1)
                rank = jnp.sum(raw_logprobs > drawn_lp, axis=-1).astype(i32)
                res["lp"] = (topk_vals, topk_ids, drawn_lp[:, 0], rank)
            if self._guard_numerics:
                res["row_bad"] = ~jnp.all(
                    jnp.isfinite(logits).reshape(rows, -1), axis=-1)
            res = jax.tree.map(
                lambda a: jnp.pad(
                    a, [(0, a.shape[0] * r_pad // rows - a.shape[0])]
                    + [(0, 0)] * (a.ndim - 1)),
                res)
            if self._nan_check:
                res["nan_count"] = jnp.isnan(logits).sum()
            return res

        # Live rows come first, so the rows that anybody reads are the
        # first num_live: the step takes the least rung that holds them.
        # One head a step on every rung.
        rungs = head_rungs(r_pad)
        res = jax.lax.switch(
            head_rung(rungs, num_live),
            [functools.partial(over, r) for r in rungs],
        )
        drawn, prob = res["drawn"], res["prob"]
        drawn = drawn.reshape(r_pad, b)
        with jax.named_scope("commit"):
            conf = jnp.where(blk_com, -jnp.inf, prob.reshape(r_pad, b))
            open_ = ~blk_com
            n = jnp.minimum(n_pass, jnp.sum(open_, axis=1))[:, None]
            # sequential: the n leftmost uncommitted positions.
            leftmost = open_ & (jnp.cumsum(open_, axis=1) <= n)
            # low_confidence_static: the n most confident, the leftmost
            # first among equals.
            pos = jnp.arange(b)
            ahead = (conf[:, None, :] > conf[:, :, None]) | (
                (conf[:, None, :] == conf[:, :, None])
                & (pos[None, None, :] < pos[None, :, None])
            )
            surest = open_ & (jnp.sum(ahead, axis=-1) < n)
            # low_confidence_dynamic: every position above the threshold,
            # but no fewer than n.
            high = open_ & (conf > threshold[:, None])
            dynamic = jnp.where(
                jnp.sum(high, axis=1, keepdims=True) >= n, high, surest
            )
            picked = jnp.select(
                [rule[:, None] == REMASKING_RULES.index("sequential"),
                 rule[:, None] == REMASKING_RULES.index(
                     "low_confidence_static")],
                [leftmost, surest], dynamic,
            ) & ((kind == 1) | (kind == 3))[:, None]
            tokens = jnp.where(picked, drawn, blk_tok)
            committed = blk_com | picked
        # To the host: tokens, flags after the pass, how many were new.
        out = jnp.concatenate(
            [tokens, committed.astype(i32),
             jnp.sum(picked, axis=1, keepdims=True).astype(i32)], axis=1)
        # To the next pass: after a lone store pass the next block opens
        # (a row of two blocks opened it itself: blk_com came in empty).
        state = jnp.concatenate(
            [tokens, (committed & (kind != 2)[:, None]).astype(i32)], axis=1)
        lp = None
        if num_logprobs > 0:
            lp = tuple(x.reshape(r_pad, b, *x.shape[1:]) for x in res["lp"])
        nan_count, row_bad = res.get("nan_count"), res.get("row_bad")
        return (kv_cache, draft_kv, (out, state), lp, None, None, nan_count,
                None, moe_counts, row_bad)

    def _eagle_drafts(self, params, draft_kv, token_ids, hidden, md,
                      anchor, emitted, draft_next, r_pad):
        """In-jit EAGLE proposal (reference: vllm/v1/spec_decode/eagle.py).

        1. Draft prefill over this step's ragged batch with inputs shifted
           one position (position p consumes token p+1 + target hidden p),
           maintaining the single-layer draft KV cache. The anchor position
           (each row's last emitted token's predecessor) gets the freshly
           emitted token — device-side — as its shifted input.
        2. Greedy chain of num_spec single-position draft decodes, feeding
           the draft's own hidden states forward and writing draft KV into
           the lookahead slots the scheduler allocated.
        """
        dm, dp = self.draft_model, self.draft_params
        k_spec = self.num_spec
        bs = self.block_size
        rows_r = jnp.arange(r_pad)
        num_live = md.num_seqs[0]

        # Shifted inputs: next in-buffer token within the same request.
        nxt = jnp.roll(token_ids, -1)
        same = jnp.concatenate(
            [md.token_req_idx[1:] == md.token_req_idx[:-1],
             jnp.zeros((1,), bool)]
        )
        shifted = jnp.where(same, nxt, 0)
        # Anchor override: the emitted token (or, for chunked prefills, the
        # next known prompt token shipped from the host). Padded rows
        # scatter out of bounds (dropped).
        anchor_tok = jnp.where(draft_next >= 0, draft_next, emitted)
        anchor_idx = jnp.where(rows_r < num_live, anchor, token_ids.shape[0])
        shifted = shifted.at[anchor_idx].set(anchor_tok, mode="drop")

        embed = params["embed"]
        is_e3 = getattr(dm, "is_eagle3", False)
        if is_e3:
            # EAGLE-3: own reduced-vocab head + d2t target-id mapping;
            # chained steps feed the draft hidden without re-fusing.
            def tok_of(h):
                return dm.draft_argmax(dp, h)
            fuse0, fusek = {"fuse": True}, {"fuse": False}
        else:
            def tok_of(h):
                return jnp.argmax(
                    self.model.compute_logits(params, h), axis=-1
                ).astype(jnp.int32)
            fuse0 = fusek = {}
        h_d, draft_kv = dm.forward(
            dp, embed, draft_kv, shifted, hidden, md, **fuse0
        )
        d_tok = tok_of(h_d[anchor])
        drafts = [d_tok]
        h_prev = h_d[anchor]  # [R, D]
        pos0 = md.positions[anchor]
        for k in range(1, k_spec):
            md_k = self._single_pos_metadata(md, pos0 + k, r_pad)
            h_prev, draft_kv = dm.forward(
                dp, embed, draft_kv, d_tok, h_prev, md_k, **fusek
            )
            d_tok = tok_of(h_prev)
            drafts.append(d_tok)
        return jnp.stack(drafts, axis=1), draft_kv

    def _rebalance_experts(self) -> None:
        """Re-pack experts onto EP groups by accumulated load (reference:
        ``rearrange_expert_weights_inplace`` + router remap). Runs between
        steps; in-flight async steps keep their internally consistent old
        (weights, map) pair."""
        from vllm_tpu.parallel.eplb import (
            invert_perms,
            permute_expert_weights,
        )

        perms = self.eplb_state.make_perms()  # [L, E] phys -> logical
        old_layers = self.params["layers"]
        # The weights currently sit in the PREVIOUS physical layout:
        # compose the new logical target through the current l2p map so
        # new slot p = logical[perms[p]] regardless of prior rebalances.
        cur_l2p = np.asarray(jax.device_get(old_layers["eplb_l2p"]))
        rows = np.arange(perms.shape[0])[:, None]
        take_idx = cur_l2p[rows, perms].astype(np.int32)
        new_layers = permute_expert_weights(old_layers, take_idx)
        new_layers["eplb_l2p"] = jnp.asarray(invert_perms(perms))
        if self.mesh is not None:
            # Keep the EP/TP shardings after the permutation gather.
            from jax.sharding import NamedSharding

            specs = self.model.param_shardings()["layers"]
            for key in ("we_gate", "we_up", "we_down"):
                new_layers[key] = jax.device_put(
                    new_layers[key], NamedSharding(self.mesh, specs[key])
                )
        self.params = {**self.params, "layers": new_layers}

    def _draft_lm_drafts(self, params, draft_kv, token_ids, hidden, md,
                         anchor, emitted, draft_next, r_pad):
        """In-jit draft-model proposal (reference:
        ``vllm/v1/spec_decode/draft_model.py``).

        1. Draft prefill over this step's ragged batch (UNshifted — the
           draft is an independent LM at the same positions), maintaining
           its own multi-layer paged KV in the target's block geometry.
        2. Feed the freshly emitted token at the next position, then chain
           ``num_spec`` greedy decodes through the full draft model,
           writing its KV into the scheduler's lookahead slots.
        """
        dm, dp = self.draft_model, self.draft_params
        _, draft_kv = dm.apply(dp, draft_kv, token_ids, md)
        pos0 = md.positions[anchor]
        tok = jnp.where(draft_next >= 0, draft_next, emitted)
        drafts = []
        for k in range(self.num_spec):
            md_k = self._single_pos_metadata(md, pos0 + 1 + k, r_pad)
            h1, draft_kv = dm.apply(dp, draft_kv, tok, md_k)
            tok = jnp.argmax(
                dm.compute_logits_own(dp, h1), axis=-1
            ).astype(jnp.int32)
            drafts.append(tok)
        return jnp.stack(drafts, axis=1), draft_kv

    # ------------------------------------------------------------------
    # Host side
    # ------------------------------------------------------------------

    def _release_state_slot(self, req_id: str) -> None:
        slot = self._state_slot_of.pop(req_id, None)
        if slot is not None:
            self._state_slot_free.append(slot)

    def _take_state_slot(self, req_id: str) -> None:
        if req_id in self._state_slot_of:
            return
        if not self._state_slot_free:
            raise RuntimeError(
                f"hybrid state slots exhausted admitting {req_id!r}: "
                f"{len(self._state_slot_of)} held "
                f"({sorted(self._state_slot_of)[:8]}...) — a holder was "
                "not released (preemption/profile leak?)"
            )
        self._state_slot_of[req_id] = self._state_slot_free.pop()

    def _suffix_corpus_share(self):
        """DP-pool suffix-corpus share, built lazily once the kv-fabric
        connector (the transport) is attached with peer wiring. The
        local PeerServer — when the fabric binds one — gets this share's
        ingest as its corpus sink, so every engine both pushes finished
        generations pool-wide and folds peers' generations into its own
        proposer corpus. Returns None when there is no connector, no
        peers, or a prior build failed (local-only drafting — the
        proposer works unchanged)."""
        if self._suffix_share is not None or self._suffix_share_dead:
            return self._suffix_share
        conn = self.kv_connector
        if conn is None:
            return None
        peers = tuple(getattr(conn, "peer_urls", ()) or ())
        server = getattr(conn, "_server", None)
        if not peers and server is None:
            self._suffix_share_dead = True  # fabric without peer wiring
            return None
        from vllm_tpu.spec_decode.adaptive import SuffixCorpusShare

        try:
            share = SuffixCorpusShare(self.proposer, peers)
            if server is not None:
                server.corpus_sink = (
                    lambda header, body, _s=share: _s.ingest(
                        SuffixCorpusShare.decode_frame(header, body)
                    )
                )
            self._suffix_share = share
        except Exception:
            self._suffix_share_dead = True
        return self._suffix_share

    def _update_states(self, so: SchedulerOutput) -> None:
        if self._is_hybrid:
            # Preempted requests recompute from position 0 with zero SSM
            # state on resume (prefix caching is off for hybrids), so
            # their slot is released now and re-assigned at resume —
            # otherwise running + preempted holders can exceed the pool.
            for req_id in so.preempted_req_ids:
                self._release_state_slot(req_id)
        for req_id in so.finished_req_ids:
            if self._is_hybrid:
                self._release_state_slot(req_id)
            # Suffix decoding: finished generations feed the cross-request
            # continuation corpus.
            state = self.input_batch.req_states.get(req_id)
            if (
                state is not None
                and state.in_batch_row >= 0
                and self.proposer is not None
                and hasattr(self.proposer, "observe_finished")
                # Multi-tenant off switch: without it, one user's
                # generations seed another's speculative drafts.
                and self.config.speculative_config.suffix_cross_request_corpus
            ):
                row = state.in_batch_row
                n_tok = int(self.input_batch.num_tokens[row])
                toks = self.input_batch.token_ids[row, :n_tok]
                self.proposer.observe_finished(toks)
                share = self._suffix_corpus_share()
                if share is not None:
                    share.observe(toks)
            self.input_batch.remove_request(req_id)
        cached = so.scheduled_cached_reqs
        for i, req_id in enumerate(cached.req_ids):
            if cached.resumed_from_preemption[i]:
                tokens = cached.resumed_req_token_ids[i]
                assert tokens is not None
                if req_id not in self.input_batch.req_states:
                    # Resume into a FRESH runner (elastic re-mesh rebuilt
                    # it — worker.reinitialize_parallel): rebuild the row
                    # from the scheduler's request ref; the resumed path
                    # already carries full token ids / blocks / positions.
                    self._resume_unknown_request(so, i, req_id, tokens)
                    continue
                self.input_batch.reset_for_resume(
                    req_id, tokens, cached.new_block_ids[i], cached.num_computed_tokens[i]
                )
                if self._is_hybrid:
                    # Fresh slot; the model reseeds zero state at pos 0.
                    self._take_state_slot(req_id)
            else:
                if cached.new_block_ids[i]:
                    self.input_batch.append_block_ids(req_id, cached.new_block_ids[i])
                self.input_batch.set_num_computed(
                    req_id, cached.num_computed_tokens[i]
                )
        for new in so.scheduled_new_reqs:
            row = self.input_batch.add_request(new)
            if self._is_hybrid:
                # Constant-size Mamba state slot, stable for the request's
                # batch lifetime (rows swap on removal; slots don't).
                self._take_state_slot(new.req_id)
            if self.lora_manager is not None:
                self.input_batch.lora_slot[row] = self.lora_manager.slot_of(
                    new.lora_name
                )
            if getattr(self.model, "needs_mrope", False):
                from vllm_tpu.models.qwen2_vl import mrope_positions

                tpi = self.model.tokens_per_image
                vstep = getattr(self.model, "video_t_step", 1)
                spans = [
                    (mi.offset, mi.num_tokens // tpi,
                     self.model.llm_grid, self.model.llm_grid,
                     vstep if getattr(mi, "is_video", False) else 1)
                    for mi in (new.mm_inputs or [])
                ]
                self.input_batch.req_states[new.req_id].mrope = (
                    mrope_positions(len(new.prompt_token_ids), spans)
                )

    def _resume_unknown_request(
        self, so: SchedulerOutput, i: int, req_id: str, tokens: list[int]
    ) -> None:
        """Preemption-resume for a request this runner has never seen
        (the elastic re-mesh rebuilt the runner with an empty batch)."""
        from vllm_tpu.core.sched_output import NewRequestData

        cached = so.scheduled_cached_reqs
        req = so.req_refs[req_id]
        row = self.input_batch.add_request(NewRequestData(
            req_id=req_id,
            prompt_token_ids=tokens,
            sampling_params=req.sampling_params,
            block_ids=cached.new_block_ids[i],
            num_computed_tokens=cached.num_computed_tokens[i],
            lora_name=req.lora_name,
            mm_inputs=req.mm_inputs or None,
            eos_token_id=req.eos_token_id,
            pooling_params=req.pooling_params,
        ))
        state = self.input_batch.req_states[req_id]
        # Restore the prompt/output split: seeded PRNG streams, penalties
        # and min-tokens all key off `generated`.
        state.generated = len(tokens) - len(req.prompt_token_ids)
        self.input_batch.generated[row] = state.generated
        if self._is_hybrid:
            self._take_state_slot(req_id)
        if self.lora_manager is not None:
            self.input_batch.lora_slot[row] = self.lora_manager.slot_of(
                req.lora_name
            )
        if getattr(self.model, "needs_mrope", False):
            from vllm_tpu.models.qwen2_vl import mrope_positions

            tpi = self.model.tokens_per_image
            vstep = getattr(self.model, "video_t_step", 1)
            spans = [
                (mi.offset, mi.num_tokens // tpi,
                 self.model.llm_grid, self.model.llm_grid,
                 vstep if getattr(mi, "is_video", False) else 1)
                for mi in (req.mm_inputs or [])
            ]
            self.input_batch.req_states[req_id].mrope = mrope_positions(
                len(req.prompt_token_ids), spans
            )

    def _run_encoders(self, so: SchedulerOutput) -> None:
        """Drop freed encoder outputs, run newly scheduled ones (one jit
        per image geometry; outputs stay on device until their placeholder
        span is fully computed)."""
        for key in so.free_encoder_input_ids:
            self._mm_cache.pop(tuple(key), None)
        for rid, idxs in so.scheduled_encoder_inputs.items():
            state = self.input_batch.req_states.get(rid)
            if state is None or not state.mm_inputs:
                logger.error("encoder scheduled for unknown request %s", rid)
                continue
            if self.is_encdec:
                # Encoder-decoder: run the encoder once and write the
                # request's cross-KV slot (re-runs after preemption —
                # the slot was released and resume restarts at 0).
                slot = self._state_slot_of[rid]
                feats = getattr(
                    state.mm_inputs[0], "encoder_features", None
                )
                if feats is not None:
                    # Whisper-class: mel frames, zero-padded to the full
                    # 30 s window (HF feature-extractor semantics); the
                    # cross length is the post-conv position count.
                    f_max = self.model.max_source_frames
                    feats = np.asarray(feats, np.float32)
                    padded_f = np.zeros(
                        (f_max, feats.shape[1]), np.float32
                    )
                    padded_f[: len(feats)] = feats[:f_max]
                    self.kv_cache = self._encode_fn(
                        self.kv_cache, self.params,
                        jnp.asarray(padded_f),
                        jnp.int32(self.model.max_encoder_len),
                        jnp.int32(slot),
                    )
                    continue
                enc = np.asarray(
                    state.mm_inputs[0].encoder_token_ids, np.int32
                )
                s_max = self.model.max_encoder_len
                padded = np.zeros(s_max, np.int32)
                padded[: len(enc)] = enc[:s_max]
                self.kv_cache = self._encode_fn(
                    self.kv_cache, self.params, jnp.asarray(padded),
                    jnp.int32(min(len(enc), s_max)), jnp.int32(slot),
                )
                continue
            for i in idxs:
                mi = state.mm_inputs[i]
                pixels = jnp.asarray(mi.pixel_values)
                fn = (
                    self._encode_video_fn
                    if getattr(mi, "is_video", False)
                    else self._encode_fn
                )
                self._mm_cache[(rid, i)] = fn(self.params, pixels[None])[0]

    def _prepare_inputs(self, so: SchedulerOutput):
        batch = self.input_batch
        num_sched = so.num_scheduled_tokens
        rows: list[int] = []
        req_order: list[str] = []
        for row in range(batch.num_reqs):
            rid = batch.req_ids[row]
            if rid in num_sched:
                rows.append(row)
                req_order.append(rid)  # type: ignore[arg-type]
        r_live = len(rows)
        t_live = so.total_num_scheduled_tokens

        # Decode-only batches (the steady-state throughput shape): one
        # scheduled token per row, no draft verification. Forcing the
        # token bucket to the row bucket gives the step the T == R
        # layout (token i IS row i, padding included) the
        # sequence-pipelined decode kernel requires.
        decode_only = (
            self.enable_decode_attention
            and bool(r_live)
            and not so.scheduled_spec_decode_tokens
            and t_live == r_live
        )
        t_pad = _bucket(max(t_live, 1), self.token_buckets)
        r_pad = _bucket(max(r_live, 1), self.request_buckets)
        if decode_only:
            t_pad = r_pad
        held = batch.num_blocks_by_group[self._longest_tables]
        max_blocks = max((int(held[row]) for row in rows), default=1)
        b_pad = _bucket(max(max_blocks, 1), self.block_buckets)
        # Bucket-cache observability: first sight of a (tokens, reqs,
        # blocks) triple compiles a new jitted-step variant (possibly
        # served from the persistent XLA cache), later sights reuse it.
        bkey = (t_pad, r_pad, b_pad)
        if bkey in self._seen_buckets:
            self.bucket_hits += 1
        else:
            self._seen_buckets.add(bkey)
            self.bucket_compiles += 1
            self._first_use_key = bkey

        # Packed i32 buffer; layout must match _unpack.
        t, r, b = t_pad, r_pad, b_pad
        # Spec sections appear only on steps that verify drafts (separate
        # trace either way since num_spec is static).
        spec_map = so.scheduled_spec_decode_tokens
        s = self.num_spec if spec_map else 0
        spec_len = (r + r * s + r * (s + 1)) if s else 0

        # Logits processors: sparse per-row adjustments + allowlists,
        # bucketed so the trace count stays bounded.
        adj_lists, allow_lists = self._logit_adjustments(
            rows, req_order, num_sched
        )
        cap = self._adj_buckets[-1]
        num_adj = 0
        if adj_lists is not None:
            widest = max(len(a) for a in adj_lists)
            if widest > cap:
                logger.warning(
                    "logit adjustments truncated: %d entries > %d cap",
                    widest, cap,
                )
                adj_lists = [a[:cap] for a in adj_lists]
                widest = cap
            num_adj = _bucket(widest, self._adj_buckets)
        num_allow = 0
        if allow_lists is not None:
            widest = max(
                (len(a) for a in allow_lists if a is not None), default=0
            )
            num_allow = _bucket(min(widest, cap), self._adj_buckets)
        lp_len = r * num_adj + (r * num_allow + r if num_allow else 0)
        eagle_len = r if self.draft_model is not None else 0
        lora_len = t if self.lora_manager is not None else 0
        # Prompt logprobs: rows whose chunk covers prompt-token positions
        # (offsets derivable pre-fill from the running count sum).
        num_prompt_lp = 0
        prompt_rows: list[tuple] = []
        if not s:
            run_off = 0
            for i, row in enumerate(rows):
                state = batch.req_states[req_order[i]]
                n = num_sched[req_order[i]]
                pl = state.sampling_params.prompt_logprobs
                if pl is not None:
                    start = int(batch.num_computed_tokens[row])
                    prompt_len = state.num_tokens - state.generated
                    count = max(0, min(start + n, prompt_len - 1) - start)
                    if count:
                        # k=0 still needs the true-token logprob: compute
                        # top-1 on device, slice [:0] host-side.
                        num_prompt_lp = max(num_prompt_lp, pl, 1)
                        prompt_rows.append((i, row, run_off, start, count, pl))
                run_off += n
        plp_len = t if num_prompt_lp else 0
        # Multimodal: placeholder positions covered this step get their
        # embeddings overlaid from the device-side encoder cache.
        mm_mask_np = None
        mm_spans: list[tuple] = []
        if self.is_mm:
            mm_mask_np = np.zeros(t, bool)
            run_off = 0
            for i, row in enumerate(rows):
                state = batch.req_states[req_order[i]]
                n = num_sched[req_order[i]]
                if state.mm_inputs:
                    start = int(batch.num_computed_tokens[row])
                    for idx, mm in enumerate(state.mm_inputs):
                        lo = max(mm.offset, start)
                        hi = min(mm.offset + mm.num_tokens, start + n)
                        if lo < hi:
                            dst = run_off + (lo - start)
                            mm_mask_np[dst : dst + hi - lo] = True
                            mm_spans.append((
                                dst, (req_order[i], idx), lo - mm.offset,
                                hi - lo,
                            ))
                run_off += n
        # seq_lens(r) + qsl(r+1) + logits_idx(r) + num_seqs(1) + bt(r*b)
        # + top_k(r) + prng(2r) + feedback(r) + grammar_rows(r)
        # [+ adj_ids(r*num_adj)] [+ allow_ids(r*num_allow) + allow_flag(r)]
        # [+ num_draft(r) + draft(r*s) + sample_pos(r*(s+1))]
        # [+ state_slots(r)]
        state_len = r if self._is_hybrid else 0
        # [+ blk_kind(r) + blk_rule(r) + blk_n(r) + blk_committed(r*B)]
        blen = self.block_len
        blk_len = r * (3 + blen) if blen else 0
        # [+ group_slots((G-1)*t) + group_tables((G-1)*r*b)], last
        more_groups = self.num_kv_groups - 1
        ibuf = np.zeros(
            4 * t + 7 * r + (r + 1) + 1 + r * b + lp_len + eagle_len
            + lora_len + plp_len + spec_len + state_len + blk_len
            + more_groups * (t + r * b),
            np.int32,
        )
        token_ids = ibuf[0:t]
        positions = ibuf[t : 2 * t]
        slot_mapping = ibuf[2 * t : 3 * t]
        token_req_idx = ibuf[3 * t : 4 * t]
        o = 4 * t
        seq_lens = ibuf[o : o + r]; o += r
        query_start_loc = ibuf[o : o + r + 1]; o += r + 1
        logits_indices = ibuf[o : o + r]; o += r
        ibuf[o] = r_live; o += 1
        block_tables = ibuf[o : o + r * b].reshape(r, b); o += r * b
        top_k = ibuf[o : o + r]; o += r
        prng = ibuf[o : o + 2 * r].view(np.uint32).reshape(r, 2); o += 2 * r
        feedback = ibuf[o : o + r]; o += r
        feedback[:] = -1
        grammar_rows = ibuf[o : o + r]; o += r
        sor = so.structured_output_request_ids
        if sor:  # skip the row loop entirely on unconstrained batches
            for i, rid in enumerate(req_order):
                grammar_rows[i] = sor.get(rid, 0)
        v_pad = self.model.vocab_size  # out-of-range id -> scatter drop
        if num_adj:
            adj_ids = ibuf[o : o + r * num_adj].reshape(r, num_adj); o += r * num_adj
            adj_ids[:] = v_pad
            for i, lst in enumerate(adj_lists):
                for j, (tok, _val) in enumerate(lst):
                    adj_ids[i, j] = tok
        if num_allow:
            allow_ids = ibuf[o : o + r * num_allow].reshape(r, num_allow); o += r * num_allow
            allow_ids[:] = v_pad
            allow_flag = ibuf[o : o + r]; o += r
            for i, lst in enumerate(allow_lists):
                if lst is not None:
                    allow_flag[i] = 1
                    allow_ids[i, : len(lst)] = lst
        if self.draft_model is not None:
            draft_next = ibuf[o : o + r]; o += r
            draft_next[:] = -1
        if self.lora_manager is not None:
            token_lora = ibuf[o : o + t]; o += t
        if num_prompt_lp:
            plp_next = ibuf[o : o + t]; o += t
            for (i, row, off, start, count, k) in prompt_rows:
                plp_next[off : off + count] = batch.token_ids[
                    row, start + 1 : start + 1 + count
                ]
        if s:
            num_draft = ibuf[o : o + r]; o += r
            draft_ids = ibuf[o : o + r * s].reshape(r, s); o += r * s
            sample_pos = ibuf[o : o + r * (s + 1)].reshape(r, s + 1)
            o += r * (s + 1)
        if self._is_hybrid:
            state_slots = ibuf[o : o + r]; o += r
            # Padding rows write to the reserved SCRATCH slot (index
            # max_num_seqs) — slot 0 belongs to a live request.
            state_slots[:] = self.config.scheduler_config.max_num_seqs
            for i, rid in enumerate(req_order):
                state_slots[i] = self._state_slot_of[rid]
        if blen:
            blk_kind = ibuf[o : o + r]; o += r
            blk_rule = ibuf[o : o + r]; o += r
            blk_n = ibuf[o : o + r]; o += r
            blk_committed = ibuf[o : o + r * blen].reshape(r, blen)
            o += r * blen
        token_req_idx[:] = max(r_pad - 1, 0)
        do_sample = np.zeros(r_pad, bool)

        bs = self.group_block_sizes[0]
        offset = 0
        pending_rows: list[int] = []
        # The native fill runs on EVERY batch shape (the old `and not s`
        # guard sent whole spec-decode batches down the Python loop);
        # only the rows that actually carry draft tokens re-patch in
        # Python afterwards, and those are counted as fallbacks.
        use_native = self._native_prep is not None
        draft_rows: set[int] = set()
        if use_native:
            from vllm_tpu.native import ptr, ptr_u8

            rows_np = np.asarray(rows, np.int32)
            starts_np = batch.num_computed_tokens[rows_np]  # owned copy
            counts_np = np.asarray(
                [num_sched[rid] for rid in req_order], np.int32
            )
            ds_u8 = np.zeros(r_pad, np.uint8)
            lora_ptr = (
                ptr(token_lora) if self.lora_manager is not None else None
            )
            offset = int(self._native_prep.fill_step_inputs(
                ptr(batch.token_ids), batch.token_ids.shape[1],
                ptr(batch.block_table), batch.block_table.shape[1],
                ptr(batch.num_blocks),
                ptr(rows_np), ptr(starts_np), ptr(counts_np),
                ptr(batch.num_tokens),
                np.int32(r_live), np.int32(bs), np.int32(b),
                ptr(token_ids), ptr(positions), ptr(slot_mapping),
                ptr(token_req_idx), ptr(seq_lens), ptr(query_start_loc),
                ptr(logits_indices), ptr_u8(ds_u8), ptr(block_tables),
                lora_ptr, ptr(batch.lora_slot),
            ))
            do_sample[:r_live] = ds_u8[:r_live].astype(bool)
            ends = starts_np + counts_np
            known_live = batch.num_tokens[rows_np]
            if s:
                # Draft-verification rows: the native fill copied stale
                # tokens past the known prefix; overlay the draft ids and
                # the per-row sample positions (token tail = drafts).
                for i, rid in enumerate(req_order):
                    off = int(query_start_loc[i])
                    n = num_sched[rid]
                    drafts = spec_map.get(rid)
                    if drafts:
                        draft_rows.add(int(i))
                        n_known = min(
                            n, int(known_live[i]) - int(starts_np[i])
                        )
                        nd = min(len(drafts), n - n_known)
                        token_ids[off + n_known : off + n] = drafts[:nd]
                        draft_ids[i, :nd] = drafts[:nd]
                        num_draft[i] = nd
                        base = off + n - 1 - nd
                        sample_pos[i, : nd + 1] = np.arange(
                            base, base + nd + 1
                        )
                        sample_pos[i, nd + 1 :] = base + nd
                    else:
                        sample_pos[i, :] = off + n - 1
                self.prep_fallback_rows += len(draft_rows)
            # Rows whose latest tokens are still in flight (device-side
            # feedback) — the native fill copied stale values there, which
            # the jitted step overwrites. Draft rows extend past the known
            # prefix by construction and are NOT in-flight feedback.
            for i in () if blen else np.nonzero(ends > known_live)[0]:
                if int(i) in draft_rows:
                    continue
                rid = req_order[i]
                lag = int(ends[i] - known_live[i])
                prev_row = self._prev_rows.get(rid, -1)
                assert lag <= self._max_pipeline_depth and prev_row >= 0, (
                    rid, lag, prev_row)
                feedback[i] = prev_row
                pending_rows.append((int(i), lag))
            if self.draft_model is not None:
                for i in np.nonzero(~do_sample[:r_live])[0]:
                    row = rows[i]
                    end = int(ends[i])
                    draft_next[i] = batch.token_ids[row, end]
        if not use_native:
            self.prep_fallback_rows += r_live
        for i, row in enumerate(rows) if not use_native else ():
            rid = req_order[i]
            n = num_sched[rid]
            start = int(batch.num_computed_tokens[row])
            known = int(batch.num_tokens[row])
            drafts = spec_map.get(rid) if s else None
            if drafts:
                # Draft tokens being verified run as regular input tokens
                # after the known prefix; every draft position plus the
                # bonus position gets sampled.
                n_known = min(n, known - start)
                nd = min(len(drafts), n - n_known)
                token_ids[offset : offset + n_known] = (
                    batch.token_ids[row, start : start + n_known]
                )
                token_ids[offset + n_known : offset + n] = drafts[:nd]
                # Rejection sampling verifies against these ids; the
                # token stream alone is not consulted.
                draft_ids[i, :nd] = drafts[:nd]
                num_draft[i] = nd
                base = offset + n - 1 - nd
                sample_pos[i, : nd + 1] = np.arange(base, base + nd + 1)
                sample_pos[i, nd + 1 :] = base + nd
            elif start + n > known and not blen:
                # Latest token(s) still in flight (async pipelining): the
                # input token for this step is fed on device from the
                # immediately previous step's sampled array. Earlier
                # in-flight tokens were inputs to earlier in-flight steps,
                # so only the newest matters here; `lag` tracks how many
                # sampled tokens the host state is behind (bumps the PRNG
                # counter so seeded streams don't repeat).
                lag = start + n - known
                prev_row = self._prev_rows.get(rid, -1)
                assert lag <= self._max_pipeline_depth and prev_row >= 0, (
                    rid, start, n, known, prev_row)
                feedback[i] = prev_row
                pending_rows.append((i, lag))
                token_ids[offset : offset + n] = (
                    batch.token_ids[row, start : start + n]
                )
                if s:
                    sample_pos[i, :] = offset + n - 1
            else:
                token_ids[offset : offset + n] = (
                    batch.token_ids[row, start : start + n]
                )
                if s:
                    sample_pos[i, :] = offset + n - 1
            pos = np.arange(start, start + n, dtype=np.int32)
            positions[offset : offset + n] = pos
            bt_row = batch.block_table[row]
            slot_mapping[offset : offset + n] = bt_row[pos // bs] * bs + pos % bs
            token_req_idx[offset : offset + n] = i
            if self.lora_manager is not None:
                token_lora[offset : offset + n] = batch.lora_slot[row]
            seq_lens[i] = start + n
            query_start_loc[i + 1] = offset + n
            logits_indices[i] = offset + n - 1
            will_sample = start + n >= int(batch.num_tokens[row])
            do_sample[i] = will_sample
            if self.draft_model is not None and not will_sample:
                # Chunked prefill: the draft's anchor input token is the
                # next (known) prompt token, not a sampled one.
                draft_next[i] = batch.token_ids[row, start + n]
            nb = int(batch.num_blocks[row])
            block_tables[i, :nb] = bt_row[:nb]
            offset += n
        query_start_loc[r_live + 1 :] = offset
        if more_groups:
            # The further groups' tables and slots, as group 0's above:
            # live rows' entries under their block count, live tokens'
            # slots; padding stays 0, the null page.
            tail = ibuf[ibuf.shape[0] - more_groups * (t + r * b):]
            rows_idx = np.asarray(rows, np.int64)
            tok_row = token_req_idx[:offset]
            tok_pos = positions[:offset]
            for g in range(more_groups):
                held = (np.arange(b)[None, :]
                        < batch.num_blocks_by_group[g + 1][rows_idx][:, None])
                table = np.where(
                    held, batch.block_tables[g + 1][rows_idx, :b], 0)
                tail[more_groups * t + g * r * b:][: r * b].reshape(
                    r, b)[:r_live] = table
                gbs = self.group_block_sizes[g + 1]
                tail[g * t:][:offset] = (
                    table[tok_row, tok_pos // gbs] * gbs + tok_pos % gbs)

        # Packed f32 sampling buffer: 6 R-vectors (+ optional adjustment
        # values) (+ under generation by diffusion the dynamic rule's
        # threshold, last); layout must match _unpack.
        idx = np.asarray(rows, np.int64)
        fbuf = np.zeros(6 * r + r * num_adj + (r if blen else 0), np.float32)
        if blen:
            # Rows that run their open block: the host's tokens and flags
            # where no pass of the request is in flight, else the previous
            # step's own output, on the device. Prefill chunks sample
            # nothing; a lone store pass neither. A row of two blocks (the
            # clean one, stored, then the next, all masks) is a sampling
            # row like a denoising pass, and counts as one.
            from vllm_tpu.models.generation import REMASKING_RULES

            do_sample[:] = False
            block_ctx = 0
            for i, rid in enumerate(req_order):
                bp = so.block_passes.get(rid)
                if bp is None:
                    continue
                p = batch.req_states[rid].sampling_params
                off = int(query_start_loc[i])
                blk_kind[i] = (3 if bp.denoise_next else 2) if bp.store else 1
                blk_rule[i] = REMASKING_RULES.index(p.remasking)
                blk_n[i] = blen // p.denoising_steps
                fbuf[fbuf.shape[0] - r + i] = p.confidence_threshold
                if bp.tokens is None:
                    prev_row = self._prev_rows.get(rid, -1)
                    assert prev_row >= 0, (rid, bp)
                    feedback[i] = prev_row
                else:
                    token_ids[off : off + blen] = bp.tokens
                    blk_committed[i] = bp.committed
                if bp.denoise_next:
                    token_ids[off + blen : off + 2 * blen] = self.mask_token_id
                    self.diffusion_stores_fused += 1
                do_sample[i] = bp.denoises
                block_ctx += int(seq_lens[i])
                self.diffusion_passes_by_kind[
                    "denoise" if bp.denoises else "store"] += 1
            # The context a block row reads, once a row.
            self.attn_context_tokens_by_kind["block"] = (
                self.attn_context_tokens_by_kind.get("block", 0) + block_ctx
            )
            # The head, as _block_epilogue's switch takes its rung.
            rungs = head_rungs(r_pad)
            rung = head_rung(rungs, r_live)
            self.diffusion_head_positions["live"] += r_live * blen
            self.diffusion_head_positions["computed"] += rungs[rung] * blen
            name = ("quarter", "half", "whole")[-len(rungs):][rung]
            self.diffusion_head_steps_by_rung[name] = (
                self.diffusion_head_steps_by_rung.get(name, 0) + 1)
        if num_adj:
            adj_vals = fbuf[6 * r :].reshape(r, num_adj)
            for i, lst in enumerate(adj_lists):
                for j, (_tok, val) in enumerate(lst):
                    adj_vals[i, j] = val

        temperature = fbuf[0:r]
        top_p = fbuf[r : 2 * r]
        min_p = fbuf[2 * r : 3 * r]
        presence = fbuf[3 * r : 4 * r]
        frequency = fbuf[4 * r : 5 * r]
        repetition = fbuf[5 * r : 6 * r]
        if use_native:
            # One C pass gathers all nine sampling columns (incl. the
            # PRNG seed/counter pair) instead of eight numpy fancy-
            # gathers plus a per-row Python loop.
            from vllm_tpu.native import ptr_f32, ptr_i32_cast

            needs_penalties = bool(self._native_prep.fill_sampling_inputs(
                ptr(rows_np), np.int32(r_live), np.int32(r),
                ptr_f32(batch.temperature), ptr_f32(batch.top_p),
                ptr_f32(batch.min_p), ptr_f32(batch.presence_penalty),
                ptr_f32(batch.frequency_penalty),
                ptr_f32(batch.repetition_penalty),
                ptr(batch.top_k), ptr_i32_cast(batch.seeds),
                ptr(batch.generated),
                ptr_f32(fbuf), ptr(top_k), ptr_i32_cast(prng),
            ))
        else:
            def gather_into(dst, col, pad_value=0):
                dst[:] = pad_value
                if r_live:
                    dst[:r_live] = col[idx]
                return dst

            gather_into(temperature, batch.temperature)
            gather_into(top_p, batch.top_p, 1.0)
            gather_into(min_p, batch.min_p)
            gather_into(presence, batch.presence_penalty)
            gather_into(frequency, batch.frequency_penalty)
            gather_into(repetition, batch.repetition_penalty, 1.0)
            gather_into(top_k, batch.top_k)
            gather_into(prng[:, 0], batch.seeds)
            gather_into(prng[:, 1], batch.generated)
            needs_penalties = bool(
                np.any(presence[:r_live] != 0)
                or np.any(frequency[:r_live] != 0)
                or np.any(repetition[:r_live] != 1.0)
            )
        for i, lag in pending_rows:
            # The in-flight token(s) haven't been appended yet; advance the
            # PRNG counter so this step's Gumbel stream doesn't repeat.
            prng[i, 1] += lag
        if blen:
            # A position's Gumbel stream is keyed by the request's pass
            # count and its place in the block, whatever is in flight.
            for i, rid in enumerate(req_order):
                bp = so.block_passes.get(rid)
                prng[i, 1] = 0 if bp is None else bp.commit_seq * blen
        if needs_penalties:
            counts_np, mask_np = self._penalty_tensors(rows, r_pad)
            counts, prompt_mask = jnp.asarray(counts_np), jnp.asarray(mask_np)
        else:
            counts, prompt_mask = self._empty_penalty

        num_logprobs = 0
        if r_live and not s:
            num_logprobs = int(np.max(batch.num_logprobs[idx], initial=0))
        dims = dict(t_pad=t_pad, r_pad=r_pad, b_pad=b_pad)
        # Masking flags only consider sampling rows: greedy rows take a raw
        # argmax, so an all-greedy batch (the throughput-bench shape) skips
        # every [R, V] sort and the Gumbel draw (static trace selection).
        # Cascade attention: longest block-table prefix shared by EVERY
        # live row, bucketed to powers of two (static jit arg). Worth it
        # only with several requests and >= 2 shared blocks.
        cascade_blocks = 0
        if (
            self.config.scheduler_config.enable_cascade_attention
            and not s
            and r_live >= 2
        ):
            tables = batch.block_table[rows]  # [r_live, max_b]
            min_blocks = int(batch.num_blocks[rows].min())
            same = (tables[:, : min_blocks] == tables[0, : min_blocks]).all(0)
            ncb = int(np.argmin(same)) if not same.all() else min_blocks
            ncb = min(ncb, min_blocks - 1)  # keep >= 1 suffix block
            if ncb >= 2:
                cascade_blocks = 1 << (ncb.bit_length() - 1)  # floor pow2
        nongreedy = temperature[:r_live] > 0.0
        flags = dict(
            cascade_blocks=cascade_blocks,
            needs_penalties=needs_penalties,
            needs_top_k=bool(np.any(top_k[:r_live][nongreedy] > 0)),
            needs_top_p_min_p=bool(
                np.any(top_p[:r_live][nongreedy] < 1.0)
                or np.any(min_p[:r_live][nongreedy] > 0)
            ),
            needs_gumbel=bool(np.any(nongreedy)),
            needs_grammar=bool(so.structured_output_request_ids),
            needs_pooling=any(
                batch.req_states[rid].pooling_params is not None
                for rid in req_order
            ),
            num_logprobs=num_logprobs,
            num_prompt_logprobs=num_prompt_lp,
            num_spec=s,
            has_state_slots=int(self._is_hybrid),
            num_adj=num_adj,
            num_allow=num_allow,
            # Cascade rewrites the attention call shape; keep such
            # batches on the general kernel.
            decode_only=decode_only and cascade_blocks == 0,
            enable_sampler_kernel=self.enable_sampler_kernel,
            block_len=blen,
        )
        num_experts = getattr(self.model, "num_experts", 0)
        if num_experts and hasattr(self.model, "top_k"):
            # Token-expert pairs of the step, every layer that has
            # experts: the live tokens' are assigned (under a share the
            # device counts those routed HERE, below), the pad tokens' are
            # kept out of the grouped matmul (layers/moe.py).
            pairs_a_token = self.model.top_k * getattr(
                self.model, "num_moe_layers", self.model.num_layers
            )
            if not self._moe_share:
                self.moe_assignments += t_live * pairs_a_token
            self.moe_pad_pairs_skipped += (t_pad - t_live) * pairs_a_token
        q_lens = np.diff(query_start_loc[: r_live + 1])
        linear_layers = getattr(self.model, "num_linear_attn_layers", 0)
        if linear_layers:
            one = q_lens == 1
            self.linear_attn_state_rows += r_live * linear_layers
            self.linear_attn_tokens_by_kind["decode"] += (
                int(one.sum()) * linear_layers)
            self.linear_attn_tokens_by_kind["chunk"] += (
                int(q_lens[~one].sum()) * linear_layers)
        window = getattr(self.model, "attn_window", 0)
        if window:
            # Window layers beside full ones: the rows they run, the keys
            # a row's window layer must read (its queries' windows, once a
            # row a step) and the pairs the window's mask lets through, a
            # q head a window layer.
            kv_lens = seq_lens[:r_live].astype(np.int64)
            self.window_attn_rows += (
                r_live * self.model.num_window_attn_layers)
            self.attn_context_tokens_by_kind["window"] = (
                self.attn_context_tokens_by_kind.get("window", 0)
                + int(np.minimum(kv_lens, q_lens + window - 1).sum()))
            self.window_attn_pairs += window_live_pairs(
                q_lens, kv_lens, window)
            self.attn_sink_rows += r_live * getattr(
                self.model, "num_sink_attn_layers", 0)
        if getattr(self.model, "latent_dim", 0):
            # What each row's latent attention reads, once a row a step;
            # and its kernel's pairs, which every step of such a model
            # runs (the fork of the ragged kernel, ops/mla_kernel.py).
            self.attn_context_tokens_by_kind["latent"] = (
                self.attn_context_tokens_by_kind.get("latent", 0)
                + int(seq_lens[:r_live].sum())
            )
            q_blk, pages_blk = latent_blocks(
                self.model.num_heads, t_pad, b_pad, self.block_size)
            live, computed = count_latent_pairs(
                q_lens, seq_lens[:r_live], q_blk, pages_blk * self.block_size,
            )
            self.attn_ragged_pairs["live"] += live
            self.attn_ragged_pairs["computed"] += computed
        self.step_launches += 1
        if flags["decode_only"]:
            self.decode_only_launches += 1
        kind = "decode" if flags["decode_only"] else "mixed"
        self.steps_by_kind[kind] += 1
        self.step_tokens_by_kind[kind] += t_live
        self.attn_context_tokens_by_kind[kind] += int(
            seq_lens[:r_live].sum())
        if flags["decode_only"]:
            self._count_decode_tiles(seq_lens[:r_live], r_pad, b_pad)
        else:
            self._count_ragged_pairs(q_lens, seq_lens[:r_live], t_pad, b_pad)
        # Sampler-kernel routing accounting (the device decision is made
        # at trace time by dispatch_sample; this mirrors it host-side).
        # All-greedy launches are neither: the XLA argmax path is not a
        # fallback, it's the design for that shape.
        if flags["needs_gumbel"]:
            use_kernel, _ = sampler_kernel_eligible(
                self.model.vocab_size,
                needs_gumbel=True,
                enable_kernel=self.enable_sampler_kernel,
                allow_interpret=True,
                tp_size=self.tp_size,
            )
            if blen:
                # Every position of the open block of a sampling row (a
                # lone store pass is none).
                rows = int(np.sum(nongreedy & do_sample[:r_live])) * blen
            else:
                rows = int(np.sum(nongreedy))
            if use_kernel:
                self.sampler_kernel_launches += 1
            else:
                self.sampler_fallback_rows += rows
        arrays = (jnp.asarray(ibuf), jnp.asarray(fbuf), counts, prompt_mask)
        mm_arrays = None
        if self.is_mm:
            # Overlay assembled device-side from cached encoder outputs —
            # the embeddings never round-trip through the host.
            overlay = jnp.zeros((t_pad, self.model.hidden_size),
                                self.model.dtype)
            for dst, key, src0, ln in mm_spans:
                emb = self._mm_cache.get(key)
                if emb is None:
                    logger.error("missing encoder output for %s", key)
                    continue
                overlay = jax.lax.dynamic_update_slice(
                    overlay, emb[src0 : src0 + ln].astype(overlay.dtype),
                    (dst, 0),
                )
            mm_arrays = (overlay, jnp.asarray(mm_mask_np))
        if getattr(self.model, "needs_mrope", False):
            # Multimodal 3D rope (Qwen2-VL): per-token (t, h, w) position
            # streams; prompt tokens read the request's get_rope_index
            # table, generated tokens run at position + delta.
            mrope_np = np.zeros((3, t_pad), np.int32)
            off2 = 0
            for i, rid in enumerate(req_order):
                state = batch.req_states[rid]
                n = num_sched[rid]
                start = int(batch.num_computed_tokens[rows[i]])
                table, delta = state.mrope
                k = max(0, min(n, table.shape[1] - start))
                if k:
                    mrope_np[:, off2 : off2 + k] = (
                        table[:, start : start + k]
                    )
                if k < n:
                    mrope_np[:, off2 + k : off2 + n] = (
                        np.arange(start + k, start + n, dtype=np.int32)
                        + delta
                    )
                off2 += n
            if mm_arrays is None:
                mm_arrays = (None, None)
            mm_arrays = mm_arrays + (jnp.asarray(mrope_np),)
        return (arrays, req_order, do_sample[:r_live], dims | flags,
                prompt_rows, mm_arrays)

    def kv_connector_save(self, entries: list[tuple]) -> None:
        """Persist (block_id, key) payloads to the external store. Runs
        before any scheduling that could hand the freed blocks to another
        request, so the pre-extraction content is intact (in-flight steps
        never touch freed blocks)."""
        assert self.kv_connector is not None
        if fail_point(
            "kv_fabric.demote", lambda: f"blocks={len(entries)}"
        ) == "drop":
            # Chaos: a torn demotion loses persistence, never data — the
            # blocks stay recomputable from the prompt.
            return
        ids = jnp.asarray([bid for bid, _ in entries], jnp.int32)
        payloads = np.asarray(jax.device_get(self.kv_cache[:, ids]))
        # [L, N, BS, rows, lanes] -> per-block [L, BS, rows, lanes]
        self.kv_connector.save_blocks(
            [key for _, key in entries],
            [payloads[:, i] for i in range(payloads.shape[1])],
        )

    def kv_connector_push(self, req_id: str, url: str, keys: list) -> bool:
        """Disaggregated handoff: stream a finished request's prefix
        blocks (already demoted to the host tier by the preceding save
        flush) to the decode peer at ``url``. Best-effort: a failed push
        is only counted — the decode side recomputes."""
        assert self.kv_connector is not None
        push = getattr(self.kv_connector, "push_blocks", None)
        if push is None:
            return False
        return push(keys, url, req_id=req_id)

    def kv_connector_reserve(self, req_id: str, n_blocks: int) -> int:
        """Decode-side handoff admission: hold host-tier budget for an
        incoming push before the prefill engine starts streaming."""
        assert self.kv_connector is not None
        reserve = getattr(self.kv_connector, "reserve_push", None)
        if reserve is None:
            return 0
        return reserve(req_id, n_blocks)

    def _kv_connector_loads(self, load_map: dict) -> set[str]:
        """Fill freshly allocated blocks from the external store before
        the step that reads them enqueues. Block counts pad to power-of-2
        buckets (padding scatters zeros into the write-only null block 0)
        so the jitted scatter compiles a bounded set of variants.

        Returns the request ids whose load FAILED (store died between the
        scheduler's hit accounting and now): their step output is garbage
        and the scheduler reschedules them to recompute — a request-level
        failure, never an engine crash (reference: scheduler.py:2123
        invalid-block recovery)."""
        assert self.kv_connector is not None
        failed: set[str] = set()
        for rid, (block_ids, keys) in load_map.items():
            try:
                if fail_point(
                    "kv_fabric.fetch", lambda: f"req={rid}"
                ) == "drop":
                    raise ConnectionError(
                        "torn fabric transfer (failpoint)")
                arrs = self.kv_connector.load_blocks(keys)
            except Exception as exc:
                logger.warning(
                    "external KV load failed for %s (%s); rescheduling "
                    "for recompute", rid, exc,
                )
                note = getattr(
                    self.kv_connector, "note_fetch_failure", None)
                if note is not None:
                    note(rid)
                failed.add(rid)
                continue
            vals = np.stack(arrs, axis=1)  # [L, N, BS, ...]
            n = vals.shape[1]
            n_pad = 1 << (n - 1).bit_length()
            ids = np.zeros(n_pad, np.int32)
            ids[:n] = block_ids
            if n_pad != n:
                pad = np.zeros(
                    vals.shape[:1] + (n_pad - n,) + vals.shape[2:],
                    vals.dtype,
                )
                vals = np.concatenate([vals, pad], axis=1)
            self.kv_cache = self._kv_load_fn(
                self.kv_cache, jnp.asarray(ids),
                jnp.asarray(vals).astype(self.kv_cache.dtype),
            )
        return failed

    def _single_pos_metadata(self, md, p, r_pad):
        """Per-row single-position AttentionMetadata (the in-jit draft
        chains): query at position p[row], same block tables. One
        token per row by construction, so the decode-specialized kernel
        is eligible whenever the config allows it."""
        decode_ok = self.enable_decode_attention
        bs = self.block_size
        rows_r = jnp.arange(r_pad, dtype=jnp.int32)
        slot = md.block_tables[rows_r, p // bs] * bs + p % bs
        return AttentionMetadata(
            positions=p,
            slot_mapping=slot,
            block_tables=md.block_tables,
            seq_lens=p + 1,
            query_start_loc=jnp.arange(r_pad + 1, dtype=jnp.int32),
            token_req_idx=rows_r,
            logits_indices=rows_r,
            num_seqs=md.num_seqs,
            state_slots=md.state_slots,
            decode_only=decode_ok,
            # (the rows' contexts grow by a token each: the order holds)
            decode_order=md.decode_order,
            tp_mesh=md.tp_mesh,
        )

    def _logit_adjustments(self, rows: list[int], req_order: list[str],
                           num_sched: dict[str, int]):
        """Per-row sparse logits-processor inputs (reference:
        ``vllm/v1/sample/logits_processor/``): logit_bias entries, banned
        bad-words continuations (suffix-matched against the row's tokens),
        min-tokens EOS/stop suppression, and allowed-token whitelists.
        Returns (adj_lists, allow_lists), each None when inactive."""
        batch = self.input_batch
        any_adj = any(
            batch.req_states[rid].needs_logit_adjust for rid in req_order
        )
        any_allow = any(
            batch.req_states[rid].sampling_params.allowed_token_ids
            is not None
            for rid in req_order
        )
        adj_lists = [] if any_adj else None
        allow_lists = [] if any_allow else None
        if not any_adj and not any_allow:
            return None, None
        ban = -1e30
        for i, rid in enumerate(req_order):
            state = batch.req_states[rid]
            p = state.sampling_params
            if any_adj:
                # Bans (hard guarantees) first: width truncation at the
                # bucket cap drops trailing bias entries, never bans.
                lst: list[tuple[int, float]] = []
                if state.needs_logit_adjust:
                    if p.min_tokens:
                        # Output index of the token sampled THIS step; under
                        # async pipelining the host's `generated` count lags
                        # by the in-flight steps, so derive it from the
                        # scheduled position instead.
                        row = rows[i]
                        prompt_len = state.num_tokens - state.generated
                        outputs_before = (
                            int(batch.num_computed_tokens[row])
                            + num_sched[rid]
                            - prompt_len
                        )
                        if outputs_before < p.min_tokens:
                            if state.eos_token_id is not None:
                                lst.append((state.eos_token_id, ban))
                            lst.extend((t, ban) for t in p.stop_token_ids)
                    if p.bad_words_token_ids:
                        row = rows[i]
                        n_tok = int(batch.num_tokens[row])
                        toks = batch.token_ids[row, :n_tok]
                        for seq in p.bad_words_token_ids:
                            k = len(seq) - 1
                            if k == 0 or (
                                n_tok >= k
                                and list(toks[n_tok - k :]) == seq[:-1]
                            ):
                                lst.append((seq[-1], ban))
                    if p.logit_bias:
                        lst.extend(state.logit_bias_items)
                adj_lists.append(lst)
            if any_allow:
                allow_lists.append(p.allowed_token_ids)
        return adj_lists, allow_lists

    def _penalty_tensors(self, rows: list[int], r_pad: int):
        """[R, V] output-token counts + prompt-token mask, built host-side
        only for penalty-bearing batches (rare path)."""
        batch = self.input_batch
        v = self.model.vocab_size
        counts = np.zeros((r_pad, v), np.int32)
        prompt_mask = np.zeros((r_pad, v), bool)
        for i, row in enumerate(rows):
            state = batch.req_states[batch.req_ids[row]]
            n_tok = int(batch.num_tokens[row])
            n_prompt = n_tok - state.generated
            prompt_mask[i, batch.token_ids[row, :n_prompt]] = True
            out_ids = batch.token_ids[row, n_prompt:n_tok]
            np.add.at(counts[i], out_ids, 1)
        return counts, prompt_mask

    def _sync_grammar_table(self) -> None:
        """Fold newly compiled grammars' per-state mask rows into the
        device-resident table (amortized: once per new grammar, never per
        step)."""
        mgr = self.structured_output_manager
        assert mgr is not None, "structured request without a manager"
        version = mgr.version  # capture before draining (compile races)
        if version == self._grammar_version:
            return
        if self._mask_table is None:
            init = np.zeros((mgr.table_rows, self._mask_w), np.uint32)
            init[0, :] = 0xFFFFFFFF  # row 0: unconstrained
            self._mask_table = jnp.asarray(init)
        for g in mgr.take_pending_uploads():
            lo, hi = g.row_offset, g.row_offset + g.num_states
            rows = np.zeros((g.num_states, self._mask_w), np.uint32)
            w = min(self._mask_w, g.masks.shape[1])
            rows[:, :w] = g.masks[:, :w]
            self._mask_table = self._mask_table.at[lo:hi].set(
                jnp.asarray(rows)
            )
        self._grammar_version = version

    # ------------------------------------------------------------------

    def _note_first_use(self, seconds: float, before: tuple) -> dict:
        """Book a first use of ``seconds``: what JAX reported of tracing,
        lowering and the backend since ``before`` (``compiles.reading()``
        taken just before the call), "run" the rest, and whether the
        persistent cache had the program."""
        after = compiles.reading()
        split = dict(zip(("trace", "lower", "backend"),
                         (a - b for a, b in zip(after, before))))
        split["run"] = seconds - sum(split.values())
        self.first_use_s += seconds
        for stage, s in split.items():
            self.first_use_stage_s[stage] += s
        hits, misses = after[3] - before[3], after[4] - before[4]
        split["cache"] = "miss" if misses else "hit" if hits else "none"
        return split

    def dispatch(self, so: SchedulerOutput) -> "StepHandle":
        """Upload inputs and enqueue the jitted step; returns immediately
        with device-array handles (no host sync). The async engine pipeline
        dispatches step N+1 before finalizing step N."""
        t0 = time.perf_counter()
        with trace_span("prepare", "runner",
                        tokens=so.total_num_scheduled_tokens,
                        reqs=so.num_reqs):
            self._update_states(so)
            if so.total_num_scheduled_tokens == 0:
                return StepHandle(empty=True)
            failed_loads: set[str] = set()
            if so.kv_connector_load:
                failed_loads = self._kv_connector_loads(so.kv_connector_load)
            if self.is_mm or self.is_encdec:
                self._run_encoders(so)
            (arrays, req_order, do_sample, flags,
             prompt_rows, mm_arrays) = self._prepare_inputs(so)
            mask_table = None
            if flags["needs_grammar"]:
                self._sync_grammar_table()
                mask_table = self._mask_table
        t1 = time.perf_counter()
        prep_s = t1 - t0
        self.timing["prep_s"] += prep_s
        prev = self._last_sampled if self._last_sampled is not None else self._zero_sampled
        mm_kwargs = {}
        if mm_arrays is not None:
            if mm_arrays[0] is not None:
                mm_kwargs["mm_embeds"] = mm_arrays[0]
                mm_kwargs["mm_mask"] = mm_arrays[1]
            if len(mm_arrays) > 2:
                mm_kwargs["mrope_positions"] = mm_arrays[2]
        # Watchdog window opens HERE — before the failpoint, so an
        # injected hang_step lands inside it exactly like a wedged XLA
        # dispatch would. It closes when this step's finalize completes.
        # (An exception below crashes the engine core anyway, so a stale
        # arm never outlives the process that would observe it.)
        if self.watchdog is not None:
            self.watchdog.arm(req_order)
        # Failpoint `model_runner.step`: nan = poison this step's logits
        # (numeric-guard path), hang_step = stall inside the watchdog
        # window, raise = crash the step (quarantine path).
        forced_nan = fail_point(
            "model_runner.step", lambda: f"reqs={req_order}"
        ) == "nan"
        step = dict(tokens=so.total_num_scheduled_tokens, reqs=len(req_order),
                    kind="decode" if flags["decode_only"] else "mixed")
        first_use, self._first_use_key = self._first_use_key, None
        # A first use of a step program is traced, lowered and compiled
        # (or loaded from the persistent cache) inside this call, with
        # every running request waiting. JAX's own events say which of
        # the three took the seconds (tracing.compiles, read before and
        # after); the span's attributes are filled in before it closes.
        before = compiles.reading() if first_use is not None else None
        with (trace_span("first_use", "runner", key=str(first_use), **step)
              if first_use is not None else nullcontext()) as span_args, \
                trace_span("dispatch", "runner", **step):
            (self.kv_cache, self.draft_kv, sampled, lp, drafts, pooled,
             nan_count, prompt_lp, moe_counts, row_bad) = self._step_fn(
                self.params, self.kv_cache, self.draft_kv, *arrays, prev,
                mask_table, **mm_kwargs, **flags,
            )
            dispatch_s = time.perf_counter() - t1
            if before is not None:
                split = self._note_first_use(dispatch_s, before)
                if span_args is not None:
                    span_args.update(split)
        self.timing["dispatch_s"] += dispatch_s
        self.timing["steps"] += 1
        if first_use is not None:
            logger.info(
                "first use of step program (t_pad, r_pad, b_pad)=%s (%s "
                "step): %.2f s (trace %.2f, lower %.2f, backend %.2f, run "
                "%.2f; cache %s); %d request(s) in the step, %d held by "
                "the runner (running or in prefill)", first_use,
                step["kind"], dispatch_s, split["trace"], split["lower"],
                split["backend"], split["run"], split["cache"],
                len(req_order), self.input_batch.num_reqs)
        is_spec = flags["num_spec"] > 0
        if self.block_len:
            # Beside what goes to the host, the next pass's tokens and
            # flags, by this step's rows.
            sampled, state = sampled
            self._last_sampled = jnp.pad(
                state, ((0, self._max_r - state.shape[0]), (0, 0)))
            self._prev_rows = {rid: i for i, rid in enumerate(req_order)}
        elif not is_spec:
            # The next step's feedback source, by this step's rows.
            self._last_sampled = (
                sampled
                if sampled.shape[0] == self._max_r
                else jnp.pad(sampled, (0, self._max_r - sampled.shape[0]))
            )
            self._prev_rows = {rid: i for i, rid in enumerate(req_order)}
        # Kick off the D2H copy now: it runs as soon as the step completes,
        # so finalize()'s device_get is a no-op wait instead of paying the
        # full host<->device round trip on the critical path.
        for x in sampled if is_spec else (sampled,):
            x.copy_to_host_async()
        if lp is not None:
            for x in lp:
                x.copy_to_host_async()
        if drafts is not None:
            drafts.copy_to_host_async()
        if pooled is not None:
            for x in pooled:
                x.copy_to_host_async()
        if prompt_lp is not None:
            for x in prompt_lp:
                x.copy_to_host_async()
        if row_bad is not None:
            row_bad.copy_to_host_async()
        if moe_counts is not None:
            moe_counts.copy_to_host_async()
        handle = StepHandle(
            req_order=req_order, do_sample=do_sample, sampled=sampled, lp=lp,
            row_states=[self.input_batch.req_states[r] for r in req_order],
            spec=is_spec,
        )
        handle.prep_s = prep_s
        handle.spec_suspended = so.spec_suspended
        handle.spec_draft_budgets = so.spec_draft_budgets
        handle.drafts = drafts
        handle.pooled = pooled
        handle.nan_count = nan_count
        handle.prompt_lp = prompt_lp
        handle.moe_counts = moe_counts
        handle.prompt_rows = (
            prompt_rows if flags["num_prompt_logprobs"] else None
        )
        handle.failed_loads = failed_loads
        handle.row_bad = row_bad
        handle.forced_nan = forced_nan
        handle.block_passes = so.block_passes if self.block_len else None
        return handle

    def finalize(self, handle: "StepHandle") -> ModelRunnerOutput:
        """Fetch the sampled tokens of a dispatched step and fold them into
        host state (the only host<->device sync of the step)."""
        if handle.empty:
            return ModelRunnerOutput()
        t0 = time.perf_counter()
        req_order, do_sample = handle.req_order, handle.do_sample
        # The host waits for the device here and nowhere else in a step.
        with trace_span("fetch", "runner", reqs=len(req_order)):
            if handle.spec:
                out_tokens = np.asarray(jax.device_get(handle.sampled[0]))
                num_out = np.asarray(jax.device_get(handle.sampled[1]))
            else:
                sampled_np = np.asarray(jax.device_get(handle.sampled))
            lp_np = None
            if handle.lp is not None:
                lp_np = [np.asarray(jax.device_get(x)) for x in handle.lp]
            drafts_np = (
                np.asarray(jax.device_get(handle.drafts))
                if handle.drafts is not None
                else None
            )
            if handle.prompt_lp is not None and handle.prompt_rows:
                pk_vals, pk_ids, tok_lp, tok_rank = (
                    np.asarray(jax.device_get(x)) for x in handle.prompt_lp
                )
            pooled_np = (
                tuple(np.asarray(jax.device_get(x)) for x in handle.pooled)
                if handle.pooled is not None
                else None
            )
        handle.wait_s = time.perf_counter() - t0
        self.timing["wait_s"] += handle.wait_s
        if handle.nan_count is not None:
            n_nan = int(jax.device_get(handle.nan_count))
            if n_nan:
                logger.error(
                    "NaNs detected in step logits: %d values (reference "
                    "analog: _get_nans_in_logits)", n_nan,
                )
        # Numeric guard, kind "nan": per-row non-finite logits. A forced
        # trip (failpoint action `nan`) models a fully poisoned logits
        # tensor, so every sampled row of the batch is afflicted.
        bad_rows = None
        if handle.row_bad is not None:
            bad_rows = np.asarray(jax.device_get(handle.row_bad))
        if handle.forced_nan:
            bad_rows = np.ones(len(req_order), bool)
        if handle.moe_counts is not None:
            moe_counts = np.asarray(jax.device_get(handle.moe_counts))
            # Experts with a live token, a layer.
            self.moe_experts_hit += int(np.count_nonzero(moe_counts))
            if self._gmm_column_tiles:
                first, resident = count_gmm_visits(
                    moe_counts, self._gmm_column_tiles)
                self.moe_gmm_visits["first"] += first
                self.moe_gmm_visits["resident"] += resident
            if self._moe_share:
                self.moe_assignments += int(moe_counts.sum())
            if self.eplb_state is not None:
                self.eplb_state.update(moe_counts)
                if self.eplb_state.due:
                    self._rebalance_experts()

        out = ModelRunnerOutput(
            req_ids=req_order, invalid_req_ids=handle.failed_loads
        )
        if handle.block_passes is not None:
            return self._finalize_block_rows(
                handle, out, sampled_np, lp_np, bad_rows)
        if handle.prompt_lp is not None and handle.prompt_rows:
            for (i, row, off, start, count, k) in handle.prompt_rows:
                rid = req_order[i]
                if self.input_batch.req_states.get(rid) is not handle.row_states[i]:
                    continue
                entries = []
                for j in range(count):
                    p = off + j
                    tok = int(self.input_batch.token_ids[row, start + 1 + j])
                    entries.append((
                        [int(x) for x in pk_ids[p, :k]],
                        [float(x) for x in pk_vals[p, :k]],
                        tok,
                        float(tok_lp[p]),
                        int(tok_rank[p]),
                    ))
                out.prompt_logprobs[rid] = (start, entries)
        # Logprobs aren't emitted on draft-carrying steps (the scheduler's
        # per-token logprob contract is single-token), and a spec step
        # disables logprobs for the WHOLE batch — so drafting is suppressed
        # for everyone while any live request wants logprobs, keeping that
        # request's logprob rows aligned with its tokens.
        batch_has_logprobs = bool(
            np.any(self.input_batch.num_logprobs[: self.input_batch.num_reqs] > 0)
        )
        for i, rid in enumerate(req_order):
            state_i = handle.row_states[i]
            if (
                pooled_np is not None
                and do_sample[i]
                and state_i.pooling_params is not None
            ):
                pp = state_i.pooling_params
                # Plane 2 (cls / classify) exists only for models with a
                # pooled_extra hook; admission validates the pairing.
                plane = {"last": 0, "mean": 1}.get(pp.pooling_type, 2)
                vec = pooled_np[plane][i]
                if pp.normalize:
                    vec = vec / max(float(np.linalg.norm(vec)), 1e-12)
                out.pooler_outputs[rid] = [float(x) for x in vec]
                out.sampled_token_ids.append([])
                continue
            if do_sample[i]:
                if handle.spec:
                    # Variable-length run: the accepted drafts and one more.
                    toks = [int(x) for x in out_tokens[i, : num_out[i]]]
                else:
                    toks = [int(sampled_np[i])]
                bad_kind = None
                if bad_rows is not None and i < len(bad_rows) and bad_rows[i]:
                    bad_kind = "nan"
                elif self._guard_numerics and any(
                    t < 0 or t >= self.model.vocab_size for t in toks
                ):
                    bad_kind = "sampled"
                if bad_kind is not None:
                    # Contain to this request: emit nothing and don't fold
                    # garbage tokens into host state; the scheduler
                    # finishes it with finish_reason="error".
                    if (
                        self.input_batch.req_states.get(rid)
                        is handle.row_states[i]
                    ):
                        out.numeric_error_req_ids.add(rid)
                        self.numeric_guard_trips[bad_kind] = (
                            self.numeric_guard_trips.get(bad_kind, 0) + 1
                        )
                    out.sampled_token_ids.append([])
                    continue
                # The request may have finished (async: stop detected while
                # this step was in flight) and its row dropped — or even
                # replaced by a new request reusing the id (identity check).
                if self.input_batch.req_states.get(rid) is handle.row_states[i]:
                    for tok in toks:
                        self.input_batch.append_token(rid, tok)
                    # Adaptive speculation: under occupancy suspension all
                    # proposer work is skipped (drafting cost is pure
                    # overhead in a compute-bound batch); otherwise clip
                    # proposals to the request's acceptance-ratcheted
                    # budget at the source. None budget = controller off.
                    budget = (
                        0 if handle.spec_suspended
                        else handle.spec_draft_budgets.get(rid)
                    )
                    if budget == 0:
                        pass
                    elif self.proposer is not None and not batch_has_logprobs:
                        row = self.input_batch.row_of(rid)
                        n_tok = int(self.input_batch.num_tokens[row])
                        drafts = self.proposer.propose(
                            self.input_batch.token_ids[row, :n_tok]
                        )
                        if drafts and budget is not None:
                            drafts = drafts[:budget]
                        if drafts:
                            out.draft_token_ids[rid] = drafts
                    elif drafts_np is not None and not batch_has_logprobs:
                        dtoks = [int(x) for x in drafts_np[i]]
                        if budget is not None:
                            # In-jit proposals are fixed-shape; the clip
                            # keeps the BFS node prefix (trees) or chain
                            # prefix the scheduler would re-trim anyway.
                            dtoks = dtoks[:budget]
                        if dtoks:
                            out.draft_token_ids[rid] = dtoks
                out.sampled_token_ids.append(toks)
            else:
                out.sampled_token_ids.append([])
        # Realized emission count (spec accepts, prefill = 0):
        # vllm:sampled_tokens_per_launch reads this.
        self.launch_sampled_tokens += sum(
            len(toks) for toks in out.sampled_token_ids
        )
        if lp_np is not None:
            from vllm_tpu.core.sched_output import LogprobsLists

            topk_vals, topk_ids, sampled_lp, sampled_rank = lp_np
            out.logprobs = LogprobsLists(
                logprob_token_ids=topk_ids[: len(req_order)].tolist(),
                logprobs=topk_vals[: len(req_order)].tolist(),
                sampled_token_ranks=sampled_rank[: len(req_order)].tolist(),
                sampled_logprobs=sampled_lp[: len(req_order)].tolist(),
            )
        if out.numeric_error_req_ids:
            logger.error(
                "numeric guard tripped: failing %d request(s) with "
                "finish_reason=error: %s (engine keeps serving)",
                len(out.numeric_error_req_ids),
                sorted(out.numeric_error_req_ids),
            )
        if self.watchdog is not None:
            self.watchdog.disarm()
        return out

    def _finalize_block_rows(self, handle, out, sampled_np, lp_np,
                             bad_rows) -> ModelRunnerOutput:
        """Generation by diffusion over blocks: a block row's result is
        its tokens and committed flags after the pass (and per position
        the logprobs of this pass, which are a token's if this pass
        committed it); the scheduler folds them into its view of the
        block. Nothing is appended to the batch's token rows: a resumed
        request brings its tokens with it."""
        b = self.block_len
        for i, rid in enumerate(handle.req_order):
            out.sampled_token_ids.append([])
            bp = handle.block_passes.get(rid)
            if bp is None or (
                self.input_batch.req_states.get(rid) is not handle.row_states[i]
            ):
                continue
            if bad_rows is not None and bad_rows[i]:
                out.numeric_error_req_ids.add(rid)
                self.numeric_guard_trips["nan"] = (
                    self.numeric_guard_trips.get("nan", 0) + 1
                )
                continue
            row = sampled_np[i]
            lps = None
            if lp_np is not None and bp.denoises:
                k = int(handle.row_states[i].sampling_params.logprobs or 0)
                vals, ids, drawn_lp, rank = lp_np
                lps = [
                    (ids[i, j, :k].tolist(), vals[i, j, :k].tolist(),
                     float(drawn_lp[i, j]), int(rank[i, j]))
                    for j in range(b)
                ] if k else None
            out.block_results[rid] = (
                row[:b].tolist(), (row[b : 2 * b] > 0).tolist(), lps)
            self.diffusion_committed_tokens += int(row[2 * b])
            self.launch_sampled_tokens += int(row[2 * b])
        if self.watchdog is not None:
            self.watchdog.disarm()
        return out

    def execute_model(self, so: SchedulerOutput) -> ModelRunnerOutput:
        return self.finalize(self.dispatch(so))

    # ------------------------------------------------------------------
    # Sleep / wake / weight reload
    # ------------------------------------------------------------------

    def sleep(self, level: int = 1) -> None:
        """Release device memory (reference: ``gpu_worker.py sleep :158``,
        CuMem VMM offload). Level 1 offloads weights to host RAM and
        discards the KV cache; level 2 discards the weights too (wake needs
        a reload source). TPU-native: jax.device_get + buffer deletion —
        no custom allocator needed."""
        import jax

        if level >= 2:
            self._host_params = None
        else:
            self._host_params = jax.device_get(self.params)
        for leaf in jax.tree_util.tree_leaves(self.params):
            leaf.delete()
        self.params = None
        for leaf in jax.tree_util.tree_leaves(self.kv_cache):
            leaf.delete()
        self.kv_cache = None
        if self.draft_kv is not None:
            self._host_draft = jax.device_get(self.draft_params) if level < 2 else None
            for leaf in jax.tree_util.tree_leaves(
                (self.draft_params, self.draft_kv)
            ):
                leaf.delete()
            self.draft_params = None
            self.draft_kv = None
        self._last_sampled = None
        logger.info("runner asleep (level %d)", level)

    def _kv_dtype(self):
        cache = self.config.cache_config
        return (
            self.model.dtype
            if cache.cache_dtype == "auto"
            else jnp.dtype(cache.jax_cache_dtype)
        )

    def _alloc_kv_cache(self):
        """The ONE place KV geometry/dtype/sharding is decided (used at
        init and after wake)."""
        from vllm_tpu.ops.attention import kv_cache_shape

        cache = self.config.cache_config
        kv_dtype = self._kv_dtype()
        custom_alloc = getattr(self.model, "alloc_kv_cache", None)
        custom_shape = getattr(self.model, "kv_cache_shape", None)
        device = jax.local_devices()[0]
        in_use_before = (device.memory_stats() or {}).get("bytes_in_use")
        if custom_alloc is not None:
            # Model-defined state pytree (SSM conv+state buffers).
            kv = custom_alloc(self.num_kv_blocks, cache.block_size, kv_dtype)
        else:
            if custom_shape is not None:
                # Model-defined geometry (MLA latent cache: one shared row
                # per token instead of K/V planes).
                kv_shape = custom_shape(self.num_kv_blocks, cache.block_size)
            else:
                # A page id holds one GROUP's layers (every layer of a
                # uniform model): the cache's first axis is a layer's
                # place in its group.
                group = self.kv_groups[0]
                kv_shape = kv_cache_shape(
                    len(group.layer_names) if group else self.model.num_layers,
                    self.num_kv_blocks,
                    cache.block_size,
                    self.model.num_kv_heads,
                    self.model.head_dim,
                )
            kv = jnp.zeros(kv_shape, kv_dtype)
        if self.mesh is not None:
            from jax.sharding import NamedSharding

            kv = jax.tree.map(
                lambda leaf, spec: jax.device_put(
                    leaf, NamedSharding(self.mesh, spec)
                ),
                kv,
                self.model.kv_cache_sharding(),
                is_leaf=lambda n: isinstance(n, jnp.ndarray),
            )
        reckoned = sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(kv)
        )
        logger.info(
            "KV cache allocated: %s (%.2f GiB)",
            jax.tree.map(lambda a: (a.shape, str(a.dtype)), kv),
            reckoned / 2**30,
        )
        if in_use_before is not None and self.mesh is None:
            # What the device holds for it against what the shapes say: a
            # page whose second-minor dim pads on the device shows here
            # (``KVCacheSpec.page_size_bytes`` models the lanes only).
            jax.block_until_ready(kv)
            took = device.memory_stats()["bytes_in_use"] - in_use_before
            log = logger.warning if abs(took - reckoned) > 0.02 * reckoned \
                else logger.info
            log("KV cache on the device: %d bytes in use for %d reckoned "
                "(%+.2f%%)", took, reckoned, 100.0 * (took - reckoned) / reckoned)
        return kv

    def _alloc_draft_kv(self):
        cache = self.config.cache_config
        dkv_shape = self.draft_model.kv_shape(
            self.num_kv_blocks, cache.block_size
        )
        dkv = jnp.zeros(dkv_shape, self._kv_dtype())
        if self.mesh is not None:
            from jax.sharding import NamedSharding

            dkv = jax.device_put(
                dkv,
                NamedSharding(
                    self.mesh, self.draft_model.kv_cache_sharding()
                ),
            )
        logger.info(
            "EAGLE draft KV cache allocated: %s (%.2f GiB)",
            dkv_shape,
            np.prod(dkv_shape) * jnp.dtype(self._kv_dtype()).itemsize
            / 2**30,
        )
        return dkv

    def wake_up(self, params=None, draft_params=None) -> None:
        """Restore device state. ``params`` (device-ready, e.g. freshly
        loaded) overrides the host copy — required after a level-2 sleep."""
        import jax

        if params is not None:
            self.params = params
        else:
            assert self._host_params is not None, (
                "level-2 sleep requires reload params"
            )
            self.params = self._put_params(self._host_params)
        if self._eplb and "eplb_l2p" not in self.params["layers"]:
            # Level-2 wake reloaded logical-order weights: identity map.
            from vllm_tpu.parallel.eplb import identity_l2p
            self.params["layers"]["eplb_l2p"] = identity_l2p(
                self.model.num_layers, self.model.num_experts
            )
        if self.medusa is not None and "medusa" not in self.params:
            # Level-2 wake reloads the target checkpoint, which has no
            # draft heads: reload them from their own source.
            spec = self.config.speculative_config
            mp = (
                self.medusa.load_params(spec.model)
                if spec.model
                else self.medusa.init_dummy_params(
                    jax.random.PRNGKey(self.config.model_config.seed + 2)
                )
            )
            self.params = {**self.params, "medusa": mp}
        self._host_params = None
        self.kv_cache = self._alloc_kv_cache()
        if self.draft_model is not None:
            if draft_params is not None:
                self.draft_params = draft_params
            else:
                assert self._host_draft is not None
                if self.mesh is None:
                    self.draft_params = jax.tree_util.tree_map(
                        jnp.asarray, self._host_draft
                    )
                else:
                    from vllm_tpu.parallel.mesh import named_shardings

                    dsh = named_shardings(
                        self.mesh, self.draft_model.param_shardings()
                    )
                    self.draft_params = jax.tree_util.tree_map(
                        lambda x, sp: jax.device_put(jnp.asarray(x), sp),
                        self._host_draft, dsh,
                    )
            self._host_draft = None
            self.draft_kv = self._alloc_draft_kv()
        logger.info("runner awake")

    def _full_param_shardings(self):
        """Model shardings plus runner-grafted trees (medusa heads)."""
        from jax.sharding import PartitionSpec as P

        specs = self.model.param_shardings()
        if self.medusa is not None:
            specs = {
                **specs,
                "medusa": {
                    "res_w": P(None, None, None),
                    "res_b": P(None, None),
                    "head_w": P(None, None, None),
                },
            }
        return specs

    def _put_params(self, host_tree):
        import jax

        if self.mesh is None:
            return jax.tree_util.tree_map(jnp.asarray, host_tree)
        from vllm_tpu.parallel.mesh import named_shardings

        shardings = named_shardings(self.mesh, self._full_param_shardings())
        return jax.tree_util.tree_map(
            lambda x, sp: jax.device_put(jnp.asarray(x), sp),
            host_tree, shardings,
        )

    def receive_weights_push(self, port: int, timeout: float = 300.0) -> int:
        """Disk-free RL weight update: accept ONE streamed push on
        ``port`` and apply each leaf in place with the resident leaf's
        sharding (reference: weight_transfer/nccl_engine.py semantics;
        see kv_connector/weight_transfer.py for the wire contract)."""
        import dataclasses

        from vllm_tpu.kv_connector.weight_transfer import (
            leaf_paths,
            receive_weights,
        )

        resident = leaf_paths(self.params)

        def set_by_path(node, parts, leaf):
            k = parts[0]
            if len(parts) == 1:
                if isinstance(node, dict):
                    node[k] = leaf
                    return node
                return dataclasses.replace(node, **{k: leaf})
            child = node[k] if isinstance(node, dict) else getattr(node, k)
            new_child = set_by_path(child, parts[1:], leaf)
            if isinstance(node, dict):
                node[k] = new_child
                return node
            return dataclasses.replace(node, **{k: new_child})

        def apply_leaf(path: str, arr) -> None:
            leaf = resident.get(path)
            if leaf is None:
                raise KeyError(
                    f"unknown param leaf {path!r} (trainer/serving trees "
                    "out of sync)"
                )
            if tuple(leaf.shape) != tuple(arr.shape):
                raise ValueError(
                    f"{path}: shape {tuple(arr.shape)} != resident "
                    f"{tuple(leaf.shape)}"
                )
            new_leaf = jnp.asarray(arr).astype(leaf.dtype)
            if getattr(leaf, "sharding", None) is not None:
                new_leaf = jax.device_put(new_leaf, leaf.sharding)
            set_by_path(self.params, path.split("."), new_leaf)

        return receive_weights(apply_leaf, port=port, timeout=timeout)

    def push_weights_to(self, host: str, port: int,
                        timeout: float = 300.0) -> int:
        """Elastic scale-up re-seed, donor side: stream every resident
        param leaf to a peer's :meth:`receive_weights_push` listener.
        Leaves are device_get on the way out (params are immutable, so
        a serving engine can donate without quiescing); the receiver
        re-applies them with its own resident shardings."""
        import jax

        from vllm_tpu.kv_connector.weight_transfer import (
            leaf_paths,
            push_weights,
        )

        leaves = [
            (path, np.asarray(jax.device_get(leaf)))
            for path, leaf in leaf_paths(self.params).items()
        ]
        push_weights((host, port), leaves, timeout=timeout)
        return len(leaves)

    def update_weights(self, path: str) -> None:
        """In-place weight swap for RL rollouts (reference:
        ``gpu_worker.py update_weights :978``). Loads a new checkpoint with
        the existing shardings; KV cache survives (same model geometry)."""
        import jax

        shardings = None
        if self.mesh is not None:
            from vllm_tpu.parallel.mesh import named_shardings

            shardings = named_shardings(
                self.mesh, self.model.param_shardings()
            )
        old = self.params
        new = self.model.load_params(path, self.model.dtype, shardings)
        carried = False
        if self.lora_manager is not None:
            # Adapter slots are runtime state, not checkpoint state: carry
            # them (and the scaling vector) into the new tree.
            for key, leaf in old["layers"].items():
                if key.startswith("lora_"):
                    new["layers"][key] = leaf
            new["lora_scaling"] = old["lora_scaling"]
            carried = True
        if self.medusa is not None:
            # Draft heads are not part of the target checkpoint.
            new["medusa"] = old["medusa"]
            carried = True
        if self._eplb:
            # Fresh checkpoints arrive in LOGICAL expert order: reset the
            # indirection to identity (and the load window with it).
            from vllm_tpu.parallel.eplb import identity_l2p
            new["layers"]["eplb_l2p"] = identity_l2p(
                self.model.num_layers, self.model.num_experts
            )
            self.eplb_state.counts[:] = 0
            self.eplb_state.steps = 0
        self.params = new
        kept = (
            {id(leaf) for leaf in jax.tree_util.tree_leaves(new)}
            if carried
            else set()
        )
        for leaf in jax.tree_util.tree_leaves(old):
            if id(leaf) not in kept:
                leaf.delete()
        if self.lora_manager is not None:
            self.lora_manager.params = new
        logger.info("weights updated from %s", path)

    # ------------------------------------------------------------------

    def profile_step_memory(self) -> int | None:
        """Measured activation high-water mark for KV sizing.

        Reference analog: ``gpu_worker.py:352 determine_available_memory``
        profiles a dummy max-batch run and reads allocator stats. The
        TPU-native equivalent is ahead-of-time: lower + compile the real
        jitted step at the LARGEST buckets (max token bucket, max request
        bucket, max blocks/request, worst-case sampler variant: penalties +
        top-k + top-p + Gumbel) and ask XLA for the executable's peak
        temp-buffer footprint. This adapts automatically when spec-decode
        draft KV, grammar tables, penalty tensors, or larger buckets grow
        the high-water mark — unlike a device-kind table.

        Returns estimated per-device activation bytes, or None when the
        backend cannot report a memory analysis.
        """
        sched = self.config.scheduler_config
        t_max = min(sched.max_num_batched_tokens, sched.max_model_len)
        # A decoding row is one token, or one block under generation by
        # diffusion.
        row = self.block_len or 1
        r = min(sched.max_num_seqs, t_max // row)
        first = t_max - (r - 1) * row
        so = _dummy_scheduler_output(
            first, num_reqs=r, max_blocks=self.max_blocks_per_req,
            worst_case_sampling=True, block_len=self.block_len,
            num_kv_groups=self.num_kv_groups,
        )
        try:
            self._update_states(so)
            (arrays, req_order, _do_sample, flags, _prompt_rows,
             _mm) = self._prepare_inputs(so)
            prev = self._zero_sampled
            lowered = self._step_fn.lower(
                self.params, self.kv_cache, self.draft_kv, *arrays, prev,
                None, **flags,
            )
            ma = lowered.compile().memory_analysis()
            if ma is None:
                return None
            temp = int(getattr(ma, "temp_size_in_bytes", 0))
            out = int(getattr(ma, "output_size_in_bytes", 0))
            alias = int(getattr(ma, "alias_size_in_bytes", 0))
            act = temp + max(0, out - alias)
            logger.info(
                "profiled step memory (t=%d r=%d): temp %.2f GiB, "
                "out-alias %.2f GiB",
                t_max, r, temp / 2**30, max(0, out - alias) / 2**30,
            )
            return act
        except Exception as exc:  # pragma: no cover - backend specific
            logger.warning("step memory profiling unavailable: %s", exc)
            return None
        finally:
            names = (
                ["__profile__"] if r == 1
                else [f"__profile_{i}__" for i in range(r)]
            )
            for rid in names:
                try:
                    self.input_batch.remove_request(rid)
                except Exception:
                    pass
                if self._is_hybrid:
                    self._release_state_slot(rid)

    def resize_kv_cache(self, num_blocks: int) -> None:
        """Re-allocate the paged KV (and draft KV) for the measured block
        budget; must run before any step is dispatched."""
        if num_blocks == self.num_kv_blocks:
            return
        self.num_kv_blocks = num_blocks
        self.kv_cache = None  # free before the larger alloc
        self.kv_cache = self._alloc_kv_cache()
        if self.draft_model is not None:
            self.draft_kv = None
            self.draft_kv = self._alloc_draft_kv()

    def profile_run(self) -> None:
        """Compile + run the largest bucket (memory high-water mark).
        Reference analog: ``gpu_model_runner.py profile_run :5846``."""
        so = _dummy_scheduler_output(
            min(
                self.config.scheduler_config.max_num_batched_tokens,
                self.config.scheduler_config.max_model_len,
            ),
            num_kv_groups=self.num_kv_groups,
        )
        self.execute_model(so)
        self.input_batch.remove_request("__profile__")
        if self._is_hybrid:
            self._release_state_slot("__profile__")

    def execute_dummy_batch(self) -> None:
        """Smallest-bucket step with a throwaway request: keeps an idle DP
        rank stepping in lockstep with busy ranks (cross-rank collectives
        need all participants). Reference: ``core.py:731``."""
        self.execute_model(_dummy_scheduler_output(
            1, num_kv_groups=self.num_kv_groups))
        self.input_batch.remove_request("__profile__")
        if self._is_hybrid:
            self._release_state_slot("__profile__")


def _dummy_scheduler_output(
    num_tokens: int,
    num_reqs: int = 1,
    max_blocks: int = 1,
    worst_case_sampling: bool = False,
    block_len: int = 0,
    num_kv_groups: int = 1,
) -> SchedulerOutput:
    """Synthetic batch: request 0 carries ``num_tokens`` prompt tokens (and
    ``max_blocks`` block-table entries a KV group), requests 1..n-1 one
    token each —
    the mixed chunked-prefill shape that maxes every bucket dimension at
    once for memory profiling. With ``block_len`` (generation by diffusion)
    requests 1..n-1 each run a denoising pass over a block of masks."""
    from vllm_tpu.core.sched_output import BlockPass, NewRequestData
    from vllm_tpu.sampling_params import SamplingParams

    if block_len:
        # Penalties are refused under this mechanism; the dynamic rule
        # reads every per-row lane.
        sp = SamplingParams(
            max_tokens=1, temperature=1.0, top_k=8, top_p=0.9,
            remasking="low_confidence_dynamic", denoising_steps=1,
            confidence_threshold=0.9,
        )
    elif worst_case_sampling:
        sp = SamplingParams(
            max_tokens=1, temperature=1.0, top_k=8, top_p=0.9,
            repetition_penalty=1.1,
        )
    else:
        sp = SamplingParams(max_tokens=1)
    reqs = []
    sched: dict[str, int] = {}
    passes: dict[str, BlockPass] = {}
    for i in range(num_reqs):
        n = num_tokens if i == 0 else max(block_len, 1)
        rid = "__profile__" if num_reqs == 1 else f"__profile_{i}__"
        reqs.append(
            NewRequestData(
                req_id=rid,
                prompt_token_ids=[1] * n,
                sampling_params=sp,
                block_ids=tuple(
                    [0] * (max_blocks if i == 0 else 1)
                    for _ in range(num_kv_groups)
                ),
                num_computed_tokens=0,
            )
        )
        sched[rid] = n
        if block_len and i:
            passes[rid] = BlockPass(
                store=False, seq=0, tokens=[1] * n, committed=[False] * n
            )
    return SchedulerOutput(
        scheduled_new_reqs=reqs,
        num_scheduled_tokens=sched,
        total_num_scheduled_tokens=sum(sched.values()),
        block_passes=passes,
    )
