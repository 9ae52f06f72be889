"""Configuration dataclasses for vllm-tpu.

The reference aggregates 30 frozen dataclasses into ``VllmConfig``
(``vllm/config/vllm.py:269``); we keep the same decomposition at the scale
this framework needs, in one module to start. All cross-validation happens in
``__post_init__`` or ``EngineConfig.finalize``.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import Any, Literal

from vllm_tpu.logger import init_logger
from vllm_tpu.resilience.config import ResilienceConfig
from vllm_tpu.resilience.lifecycle import LifecycleConfig

logger = init_logger(__name__)


@dataclass
class ModelConfig:
    """What model to run and how to interpret it.

    Reference analog: ``vllm/config/model.py`` (ModelConfig).
    """

    model: str = "meta-llama/Meta-Llama-3-8B"
    tokenizer: str | None = None
    trust_remote_code: bool = False
    dtype: str = "bfloat16"  # "bfloat16" | "float32" | "float16"
    seed: int = 0
    max_model_len: int | None = None  # None -> derive from HF config
    revision: str | None = None
    # Weight-only quantization: None | "int8" | "fp8" (per-output-channel,
    # applied at load; reference: vllm/model_executor/layers/quantization/).
    quantization: str | None = None
    # Also quantize the embedding table (per-row int8) and lm_head
    # (per-out-channel int8). Saves the 2·V·D bf16 bytes that dominate
    # small-chip headroom on big-vocab models; off by default for quality.
    quantize_embedding_layers: bool = False
    # "auto" streams real weights from safetensors; "dummy" random-initializes
    # (reference: load_format="dummy", model_loader/dummy_loader.py) so engine
    # tests need no checkpoints.
    load_format: Literal["auto", "dummy"] = "auto"
    # Populated by the loader from the HF config.
    hf_config: Any = None
    # Optional override dict applied on top of the HF config (tests).
    hf_overrides: dict[str, Any] | None = None
    # How the model generates, when not one token a sequence a step: set
    # by EngineConfig.resolve_generation from the model class and its
    # config.json (models/generation.py). None = autoregressive.
    generation: dict[str, Any] | None = None

    def __post_init__(self) -> None:
        if self.tokenizer is None:
            self.tokenizer = self.model
        if self.quantization is not None:
            from vllm_tpu.layers.quant import QUANT_METHODS

            if self.quantization not in QUANT_METHODS:
                raise ValueError(
                    f"unknown quantization {self.quantization!r}; "
                    f"supported: {QUANT_METHODS}"
                )
        if self.quantize_embedding_layers and self.quantization is None:
            raise ValueError(
                "quantize_embedding_layers requires a weight quantization "
                "scheme (--quantization int8/fp8/int4/...); on its own it "
                "would be a silent no-op"
            )

    @property
    def jax_dtype(self):
        import jax.numpy as jnp

        return {
            "bfloat16": jnp.bfloat16,
            "float32": jnp.float32,
            "float16": jnp.float16,
        }[self.dtype]


@dataclass
class CacheConfig:
    """KV-cache geometry. Reference analog: ``vllm/config/cache.py``."""

    block_size: int = 16  # tokens per KV block
    # Fraction of free HBM given to the KV cache (after weights+activations).
    gpu_memory_utilization: float = 0.9
    # Explicit block count override (tests / CPU runs). None -> profile.
    num_gpu_blocks_override: int | None = None
    enable_prefix_caching: bool = True
    # KV cache dtype: "auto" follows model dtype; "fp8"/"fp8_e4m3" and
    # "fp8_e5m2" store KV in 8 bits (2x context capacity; kernels
    # dequantize pages on the fly).
    cache_dtype: str = "auto"

    @property
    def jax_cache_dtype(self):
        import jax.numpy as jnp

        return {
            "fp8": jnp.float8_e4m3fn,
            "fp8_e4m3": jnp.float8_e4m3fn,
            "fp8_e5m2": jnp.float8_e5m2,
        }.get(self.cache_dtype, self.cache_dtype)
    # Populated at engine init after profiling.
    num_gpu_blocks: int | None = None
    # Context-parallel striping: the pool is split into this many colors
    # (= cp mesh ranks); a request's k-th block comes from color k % cp.
    # Set from ParallelConfig.context_parallel_size at engine-config build.
    num_kv_stripes: int = 1
    # Populated at model load from the layers' KV specs
    # (``core/kv_cache_utils.group_kv_cache_specs``): the groups the cache
    # manager keeps a block table and a freeing policy for. A uniform
    # model has one; ``sliding_window`` is then its window (None = full
    # attention) and what drives out-of-window block freeing. With
    # several groups each group's spec says whether it has a window, and
    # ``sliding_window`` is None.
    kv_cache_groups: list | None = None
    sliding_window: int | None = None
    # External KV store ("host_offload" = content-addressed host-RAM tier
    # reloading evicted prefixes; "fabric" = the full tiered KV fabric:
    # host RAM + peer engines behind a fetch-vs-recompute cost model).
    kv_connector: str | None = None
    kv_connector_cache_gb: float = 4.0
    # "host:port" of the shared KV block store: the disaggregated-prefill
    # transport (kv_connector="remote"), or a write-through shared cold
    # tier (kv_connector="fabric").
    kv_connector_url: str | None = None
    # Tiered KV fabric (kv_connector="fabric"): cold-tier codec applied
    # on demotion to host RAM and on the peer wire ("none"|"int8"|"int4").
    kv_fabric_quant: str = "int8"
    # "host:port" this engine serves its host tier on (None = don't serve
    # peers). In DP pools the client assigns per-engine binds/peers.
    kv_fabric_bind: str | None = None
    # Peer fabric endpoints, comma-separated string or sequence of
    # "host:port".
    kv_fabric_peers: str | tuple | list | None = None
    # Pin the cost model's link bandwidth (GB/s); None = live EWMA over
    # observed transfers (env VLLM_TPU_KV_FABRIC_LINK_GBPS also pins).
    kv_fabric_link_gbps: float | None = None
    # KV-cache event publishing endpoint (ZMQ PUB, e.g. tcp://*:5557) for
    # cache-aware routers; None disables (reference: kv_events.py).
    kv_events_endpoint: str | None = None

    def __post_init__(self) -> None:
        if self.block_size & (self.block_size - 1):
            raise ValueError(f"block_size must be a power of 2, got {self.block_size}")
        if self.cache_dtype not in (
            "auto", "fp8", "fp8_e4m3", "fp8_e5m2", "bfloat16", "float16",
            "float32",
        ):
            raise ValueError(f"unknown cache_dtype {self.cache_dtype!r}")
        if self.kv_fabric_quant not in ("none", "int8", "int4"):
            raise ValueError(
                f"unknown kv_fabric_quant {self.kv_fabric_quant!r}; "
                "expected 'none', 'int8' or 'int4'")

    @property
    def kv_fabric_peer_list(self) -> list[str]:
        peers = self.kv_fabric_peers
        if not peers:
            return []
        if isinstance(peers, str):
            return [p.strip() for p in peers.split(",") if p.strip()]
        return list(peers)


@dataclass
class ParallelConfig:
    """Device-mesh topology.

    Reference analog: ``vllm/config/parallel.py``; rank layout
    ``ExternalDP x DP x PP x PCP x TP`` (``parallel_state.py:1560``). On TPU
    these become named mesh axes consumed by GSPMD rather than process groups.
    """

    tensor_parallel_size: int = 1
    data_parallel_size: int = 1
    pipeline_parallel_size: int = 1
    expert_parallel_size: int = 1
    # Context parallelism (sequence sharding) axis size.
    context_parallel_size: int = 1
    enable_expert_parallel: bool = False
    # Engine-level data parallelism (the reference's DP: one engine-core
    # process per rank + coordinator, ``vllm/v1/engine/coordinator.py``).
    # Distinct from ``data_parallel_size``, which is the in-mesh GSPMD
    # batch-sharding axis within ONE engine.
    data_parallel_engines: int = 1
    # Disaggregated prefill/decode: comma-separated per-engine roles
    # ("prefill"/"decode"/"unified" or P/D/U), one entry per DP engine
    # (a single entry broadcasts). None = all unified = today's
    # behavior. With at least one prefill AND one decode engine, the
    # DP client hands eligible requests off: prompt runs on prefill
    # capacity, KV streams to a decode peer over the fabric, decoding
    # resumes there (see vllm_tpu/disagg/).
    engine_roles: str | None = None
    # Prompts shorter than this many tokens skip the handoff (the
    # transfer isn't worth it); they still route via the phase rung.
    disagg_min_prompt_tokens: int = 0
    # MoE wave lockstep: idle DP engines run dummy batches while any rank
    # has work, so expert groups spanning DP ranks keep their collectives
    # alive (reference ``DPEngineCoreProc.run_busy_loop``).
    data_parallel_lockstep: bool = False
    # Microbatches per pipelined step (0 -> pipeline_parallel_size). More
    # microbatches shrink in-step bubbles at the cost of smaller per-tick
    # matmuls; the engine's in-flight step queue fills the rest.
    pipeline_microbatches: int = 0
    # EPLB (expert-parallel load balancing, reference vllm/distributed/
    # eplb/): accumulate per-expert token counts and re-pack experts onto
    # EP groups every eplb_window steps.
    enable_eplb: bool = False
    eplb_window: int = 32
    # EP group count for balancing (0 -> the expert-sharding axis size).
    eplb_num_groups: int = 0
    # Backend for engine<->worker transport: in-proc by default on TPU since
    # one host drives all local chips via a single jax client.
    distributed_executor_backend: Literal["uniproc", "mp", "external"] = "uniproc"
    # Frontend scale-out (reference: the `A` in `A + DP + N` — many API
    # server processes sharing one engine pool over ZMQ; see
    # vllm_tpu/router/topology.py). 1 = classic single-process frontend.
    api_server_count: int = 1

    @property
    def world_size(self) -> int:
        return (
            self.tensor_parallel_size
            * self.data_parallel_size
            * self.pipeline_parallel_size
            * self.context_parallel_size
        )


@dataclass
class SchedulerConfig:
    """Token-budget continuous-batching knobs.

    Reference analog: ``vllm/config/scheduler.py``; semantics of
    ``vllm/v1/core/sched/scheduler.py:352``.
    """

    max_num_batched_tokens: int = 8192  # per-step token budget
    max_num_seqs: int = 256  # max concurrent requests in a step
    # Tree spec verification: schedule a request's draft tokens
    # all-or-nothing (a budget-truncated tree is unverifiable).
    spec_all_or_nothing: bool = False
    # Max draft tokens acceptable per step (tree DEPTH; 0 = all drafts).
    # Keeps the reported acceptance-rate denominator honest: a 2x2 tree
    # schedules 6 nodes but can accept at most 2.
    spec_max_accept_per_step: int = 0
    max_model_len: int = 8192  # mirrored from ModelConfig at finalize
    # Lag-N pipelined scheduling (schedule step N+k before step N's tokens
    # reach the host); forced off when spec decode is on.
    async_scheduling: bool = True
    # Max steps in flight (device + D2H) at once. Each extra step hides one
    # host->device->host turnaround behind compute; tokens are fed
    # device-side from the previous step's sampled array, so any depth is
    # exact for greedy/seeded sampling (penalty-bearing requests are capped
    # at 2 in flight — the device-side count correction covers one token).
    # Default retuned 6 -> 3 after PR 8: at the post-PR8 step phase split
    # (old round: ~3ms host prep+dispatch vs ~188ms device wall) depth 2
    # already hides the host turnaround; 3 keeps one step of slack for
    # scheduler jitter while halving the stale-work window on aborts and
    # the depth-capped penalty-row exposure. See README knobs table.
    async_pipeline_depth: int = 3
    enable_chunked_prefill: bool = True
    # Decode-specialized attention: batches where every row is a pure
    # decode (one query token) dispatch to the sequence-pipelined kernel
    # (ops/rpa_decode_kernel.py) instead of the general ragged kernel.
    # Off routes everything to the general kernel; the
    # VLLM_TPU_DISABLE_DECODE_KERNEL env is the no-restart escape hatch
    # for the same switch.
    enable_decode_attention: bool = True
    # Fused sort-free sampling kernel (ops/sampler_kernel.py): sampling
    # batches (any non-greedy row) run the whole sampling epilogue —
    # penalties, temperature, top-k/top-p/min-p, seeded Gumbel draw — in
    # one Pallas kernel reading the logits from HBM exactly once, instead
    # of the XLA path's multiple [R, V] passes. Bit-exact vs the XLA
    # reference (sample/sampler.py); the VLLM_TPU_DISABLE_SAMPLER_KERNEL
    # env is the no-restart escape hatch for the same switch.
    enable_sampler_kernel: bool = True
    # Slots allocated beyond the scheduled tokens (EAGLE writes draft KV at
    # speculative positions); set at EngineConfig.finalize.
    num_lookahead_tokens: int = 0
    # Long-prefill throttle (reference: long_prefill_token_threshold).
    long_prefill_token_threshold: int = 0
    # Multimodal encoder-output cache budget in encoder tokens (reference:
    # EncoderCacheManager / max_num_encoder_input_tokens).
    encoder_cache_budget: int = 4096
    # Cascade (shared-prefix) attention: compute the common-prefix part of
    # attention once per step and LSE-merge with per-request suffixes
    # (reference: gpu_model_runner.py cascade path). Off by default: the
    # cascade path is the XLA formulation, which can lose to the Pallas
    # flash kernel unless the shared prefix dominates the context.
    enable_cascade_attention: bool = False
    policy: Literal["fcfs", "priority"] = "fcfs"
    # Adaptive speculation (copied from SpeculativeConfig at
    # EngineConfig.finalize — the controller lives scheduler-side and the
    # scheduler only sees this config).
    spec_adaptive: bool = False
    spec_num_speculative_tokens: int = 0
    spec_tree_spec: str | None = None
    spec_adaptive_high_watermark: float = 0.85
    spec_adaptive_low_watermark: float = 0.60
    spec_adaptive_ema_half_life_s: float = 10.0
    # QoS pressure preemption (the scheduler half of the brownout/QoS
    # layer, resilience/qos.py): when a higher-priority request has
    # waited longer than pressure_preemption_s and the step is out of
    # request slots, preempt the lowest-priority running decode (it
    # resumes token-identically via the normal PREEMPTED path). 0 =
    # derive from the lifecycle TTFT timeout (half of it) at
    # EngineConfig.finalize, or stay off when no TTFT budget is set;
    # < 0 = explicitly off. Bounded per step and per victim so nothing
    # starves. The VLLM_TPU_DISABLE_QOS env is the no-restart off
    # switch.
    pressure_preemption_s: float = 0.0
    max_preemptions_per_step: int = 1
    max_preemptions_per_request: int = 4
    # Generation by diffusion over blocks (models/generation.py): a
    # decoding request holds a block of this many positions that is run
    # whole several times; 0 = autoregressive. Mirrored from
    # ModelConfig.generation by EngineConfig.resolve_generation.
    block_length: int = 0

    def __post_init__(self) -> None:
        if self.max_num_batched_tokens < 1:
            raise ValueError("max_num_batched_tokens must be >= 1")
        if self.max_preemptions_per_step < 0:
            raise ValueError("max_preemptions_per_step must be >= 0")
        if self.max_preemptions_per_request < 0:
            raise ValueError("max_preemptions_per_request must be >= 0")


@dataclass
class DeviceConfig:
    """Which jax backend to run on. "auto" picks the default jax backend."""

    device: Literal["auto", "tpu", "cpu"] = "auto"


@dataclass
class SpeculativeConfig:
    """Speculative decoding. Reference analog: ``vllm/config/speculative.py``."""

    method: Literal[
        "ngram", "eagle", "eagle3", "draft_model", "suffix", "medusa"
    ] | None = None
    num_speculative_tokens: int = 0
    # ngram proposer window
    prompt_lookup_max: int = 4
    prompt_lookup_min: int = 1
    # Draft checkpoint path: EAGLE head / full draft model / medusa heads.
    model: str | None = None
    # Suffix decoding: whether finished generations feed a CROSS-REQUEST
    # continuation corpus. Verification keeps outputs correct either way,
    # but drafts derived from other users' generations are an
    # information-flow channel in multi-tenant serving (draft acceptance
    # patterns are observable via timing) — flip off there.
    suffix_cross_request_corpus: bool = True
    # Adaptive speculation: a scheduler-side controller ratchets each
    # request's draft budget on a measured acceptance-rate EMA (seeded
    # from a global per-proposer EMA), prunes tree topology to the
    # per-depth acceptance curve, and suspends speculation batch-wide
    # when batch occupancy crosses high_watermark (resuming under
    # low_watermark, with hysteresis). Changes proposals only — accepted
    # text is verification-identical to static drafting. The
    # VLLM_TPU_DISABLE_ADAPTIVE_SPEC env is the no-restart escape hatch.
    adaptive: bool = False
    adaptive_high_watermark: float = 0.85
    adaptive_low_watermark: float = 0.60
    adaptive_ema_half_life_s: float = 10.0
    # Tree verification (Medusa): a static branching spec like "2x2x1"
    # — depth-d candidates = head d's top-b_d tokens, verified as a TREE
    # in one step (tree-masked attention + rejection sampling over
    # root-to-leaf paths). None = chain verification. Reference:
    # v1/attention/backends/tree_attn.py. When set,
    # num_speculative_tokens is derived (= node count) and the scheduler
    # schedules draft trees all-or-nothing (a partial tree is
    # unverifiable).
    spec_tree: str | None = None

    @property
    def enabled(self) -> bool:
        return self.method is not None and self.num_speculative_tokens > 0


@dataclass
class LoRAConfig:
    """Reference analog: ``vllm/config/lora.py``."""

    max_lora_rank: int = 16
    max_loras: int = 1
    enable_lora: bool = False


@dataclass
class ObservabilityConfig:
    collect_detailed_traces: bool = False
    otlp_traces_endpoint: str | None = None
    log_stats: bool = True
    log_stats_interval_s: float = 10.0
    # SLO scoreboard (vllm_tpu/metrics/reqtrace.py): directory for the
    # append-only request-trace JSONL. None = capture fully disabled
    # (no recorder object, no per-request work).
    request_trace_dir: str | None = None
    # Per-class latency targets for the live sliding-window
    # vllm:slo_attainment{slo_class} gauge, e.g.
    # "interactive=ttft:200ms,itl:50ms;batch=ttft:5s". None = gauge off.
    slo_targets: str | None = None


@dataclass
class CompilationConfig:
    """Bucketing for the persistent-jit step (replaces CUDA-graph capture
    lists + ``cudagraph_dispatcher`` in the reference)."""

    # Token-count buckets for the unified fwd step; actual list derived at
    # finalize from max_num_batched_tokens if empty.
    token_buckets: list[int] = field(default_factory=list)
    # Request-count buckets for decode-state tensors.
    request_buckets: list[int] = field(default_factory=list)
    # Precompile all buckets at startup (vs lazily on first use).
    precompile: bool = False
    # Bucket budget: cap on len(token_buckets) * len(request_buckets).
    # Derived bucket lists are thinned (every other entry, keeping both
    # endpoints) until they fit; explicit bucket lists are never thinned.
    # NOTE this bounds the t x r bucket grid only — the block-count bucket
    # (b_pad) and static sampler-variant flags multiply the true worst-case
    # executable count further; in practice a workload exercises few of
    # those variants. More buckets = less padding waste per step but more
    # compile time/cache pressure. The default admits the full pow2
    # ladders at 8k tokens x 512 reqs.
    max_step_compilations: int = 128
    # Set by finalize: one explicit token bucket and one explicit request
    # bucket (see page_buckets).
    one_step_shape: bool = False

    @staticmethod
    def _pow2_buckets(lo: int, hi: int) -> list[int]:
        out = []
        v = lo
        while v < hi:
            out.append(v)
            v *= 2
        out.append(hi)
        return out

    @staticmethod
    def _thin(buckets: list[int]) -> list[int]:
        if len(buckets) <= 2:
            return buckets
        return buckets[:-1:2] + [buckets[-1]]

    def page_buckets(self, max_blocks_per_req: int) -> list[int]:
        """Pages-per-request buckets of the block table: powers of two
        from 16 up to the pages of max_model_len. A server whose explicit
        ladders pin the step to ONE shape of tokens and rows gets the
        whole table alone, so one program of each kind: the kernels read
        a row's pages by its length, not by the table's width."""
        if self.one_step_shape:
            return [max_blocks_per_req]
        return self._pow2_buckets(min(16, max_blocks_per_req), max_blocks_per_req)

    def finalize(self, sched: SchedulerConfig) -> None:
        explicit_t = bool(self.token_buckets)
        explicit_r = bool(self.request_buckets)
        self.one_step_shape = (
            len(self.token_buckets) == 1 and len(self.request_buckets) == 1)
        for name, given, most in (
            ("token_buckets", self.token_buckets, sched.max_num_batched_tokens),
            ("request_buckets", self.request_buckets, sched.max_num_seqs),
        ):
            if given and (given != sorted(set(given)) or given[0] < 1
                          or given[-1] < most):
                raise ValueError(
                    f"{name} {given} must be ascending and reach {most}, "
                    "the most a step can hold")
        if not explicit_t:
            self.token_buckets = self._pow2_buckets(
                16, max(16, sched.max_num_batched_tokens)
            )
        if not explicit_r:
            self.request_buckets = self._pow2_buckets(8, max(8, sched.max_num_seqs))
        while (
            len(self.token_buckets) * len(self.request_buckets)
            > self.max_step_compilations
        ):
            can_t = not explicit_t and len(self.token_buckets) > 2
            can_r = not explicit_r and len(self.request_buckets) > 2
            if not can_t and not can_r:
                break
            if can_t and (
                not can_r
                or len(self.token_buckets) >= len(self.request_buckets)
            ):
                self.token_buckets = self._thin(self.token_buckets)
            else:
                self.request_buckets = self._thin(self.request_buckets)


@dataclass
class EngineConfig:
    """Aggregate of everything the engine needs (reference: ``VllmConfig``)."""

    model_config: ModelConfig = field(default_factory=ModelConfig)
    cache_config: CacheConfig = field(default_factory=CacheConfig)
    parallel_config: ParallelConfig = field(default_factory=ParallelConfig)
    scheduler_config: SchedulerConfig = field(default_factory=SchedulerConfig)
    device_config: DeviceConfig = field(default_factory=DeviceConfig)
    speculative_config: SpeculativeConfig = field(default_factory=SpeculativeConfig)
    lora_config: LoRAConfig = field(default_factory=LoRAConfig)
    observability_config: ObservabilityConfig = field(default_factory=ObservabilityConfig)
    compilation_config: CompilationConfig = field(default_factory=CompilationConfig)
    resilience_config: ResilienceConfig = field(default_factory=ResilienceConfig)
    lifecycle_config: LifecycleConfig = field(default_factory=LifecycleConfig)

    def finalize(self) -> "EngineConfig":
        """Cross-validate and derive dependent fields. Idempotent."""
        self.resilience_config.finalize()
        self.lifecycle_config.finalize()
        mc, sc = self.model_config, self.scheduler_config
        if mc.max_model_len is not None:
            sc.max_model_len = mc.max_model_len
        if (sc.pressure_preemption_s == 0.0
                and self.lifecycle_config.ttft_timeout_s > 0):
            # Ride the PR 3 deadline sweep: preempt for a waiting
            # higher-priority request at half its TTFT budget, before
            # the timeout fires.
            sc.pressure_preemption_s = (
                self.lifecycle_config.ttft_timeout_s / 2)
        if not sc.enable_chunked_prefill:
            sc.max_num_batched_tokens = max(sc.max_num_batched_tokens, sc.max_model_len)
        if self.speculative_config.spec_tree is not None:
            from vllm_tpu.spec_decode.tree import build_tree

            if self.speculative_config.method != "medusa":
                raise ValueError(
                    "spec_tree requires the medusa proposer (per-depth "
                    "candidate heads); chain proposers have no branches"
                )
            if self.parallel_config.context_parallel_size > 1:
                raise ValueError(
                    "spec_tree under context parallelism is not supported "
                    "yet (the CP attention path has no tree-window part)"
                )
            tree = build_tree(self.speculative_config.spec_tree)
            # The engine-level draft count is the NODE count; the head
            # count (= depth) is derived from the spec by the runner.
            self.speculative_config.num_speculative_tokens = tree.num_nodes
            sc.spec_all_or_nothing = True
            sc.spec_max_accept_per_step = tree.num_levels
        if (
            self.speculative_config.enabled
            and self.speculative_config.method in ("eagle", "eagle3",
                                                   "draft_model")
        ):
            # In-jit draft chains write draft KV at speculative positions:
            # EAGLE's chain reaches pos0+k-1, a draft model's pos0+k.
            sc.num_lookahead_tokens = (
                self.speculative_config.num_speculative_tokens
                + (1 if self.speculative_config.method == "draft_model" else 0)
            )
        self.compilation_config.finalize(sc)
        if self.speculative_config.enabled and self.parallel_config.pipeline_parallel_size > 1:
            raise ValueError("speculative decoding is incompatible with pipeline parallelism")
        spec = self.speculative_config
        if spec.enabled:
            # The scheduler owns the adaptive controller but only sees
            # SchedulerConfig — copy what it needs across here.
            sc.spec_num_speculative_tokens = spec.num_speculative_tokens
            sc.spec_tree_spec = spec.spec_tree
            sc.spec_adaptive = spec.adaptive
            sc.spec_adaptive_high_watermark = spec.adaptive_high_watermark
            sc.spec_adaptive_low_watermark = spec.adaptive_low_watermark
            sc.spec_adaptive_ema_half_life_s = spec.adaptive_ema_half_life_s
            if spec.adaptive and not (
                0.0 < spec.adaptive_low_watermark
                < spec.adaptive_high_watermark <= 1.0
            ):
                raise ValueError(
                    "adaptive speculation watermarks must satisfy "
                    "0 < low < high <= 1, got "
                    f"low={spec.adaptive_low_watermark} "
                    f"high={spec.adaptive_high_watermark}"
                )
        elif spec.adaptive:
            raise ValueError(
                "--spec-adaptive requires speculative decoding to be "
                "enabled (set --speculative-method and "
                "--num-speculative-tokens)"
            )
        pc = self.parallel_config
        if pc.engine_roles:
            from vllm_tpu.disagg.roles import parse_engine_roles

            roles = parse_engine_roles(pc.engine_roles,
                                       pc.data_parallel_engines)
            if (any(r != "unified" for r in roles)
                    and self.cache_config.kv_connector != "fabric"):
                raise ValueError(
                    "--engine-roles needs the KV fabric for the prefill->"
                    "decode handoff; set --kv-connector fabric")
        return self

    def resolve_generation(self) -> "EngineConfig":
        """Read how the model generates from its class and ``config.json``
        and refuse what does not compose with it, by mechanism. Called
        where the model's config is first needed (EngineCore, the input
        processor); idempotent."""
        from vllm_tpu.models.generation import resolve_generation

        resolve_generation(self)
        return self

    def compute_hash(self) -> str:
        """Stable hash of the config (used to key compile caches)."""
        parts = []
        for f in (
            self.model_config,
            self.cache_config,
            self.parallel_config,
            self.scheduler_config,
            self.compilation_config,
        ):
            parts.append(repr(f))
        return hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]
