"""EngineArgs: the flat user-facing knob surface -> EngineConfig.

Reference analog: ``vllm/engine/arg_utils.py:403`` (2.5k LoC of argparse);
same idea at the scale we need, with CLI args generated from the dataclass
fields so the flag surface can't drift from the config.
"""

from __future__ import annotations

import argparse
import dataclasses
from dataclasses import dataclass
from typing import Any, get_args, get_origin, Literal

from vllm_tpu.config import (
    CacheConfig,
    CompilationConfig,
    DeviceConfig,
    EngineConfig,
    LoRAConfig,
    ModelConfig,
    ObservabilityConfig,
    ParallelConfig,
    SchedulerConfig,
    SpeculativeConfig,
)
from vllm_tpu.resilience.config import ResilienceConfig
from vllm_tpu.resilience.lifecycle import LifecycleConfig


def _int_list(text: str | None) -> list[int]:
    """``"128,256"`` -> ``[128, 256]``; None or empty -> ``[]``."""
    return [int(x) for x in (text or "").split(",") if x.strip()]


@dataclass
class EngineArgs:
    model: str = "meta-llama/Meta-Llama-3-8B"
    tokenizer: str | None = None
    trust_remote_code: bool = False
    dtype: str = "bfloat16"
    seed: int = 0
    max_model_len: int | None = None
    load_format: str = "auto"
    revision: str | None = None
    quantization: str | None = None
    quantize_embedding_layers: bool = False

    block_size: int = 16
    gpu_memory_utilization: float = 0.9
    num_gpu_blocks_override: int | None = None
    enable_prefix_caching: bool = True
    kv_cache_dtype: str = "auto"
    kv_connector: str | None = None
    kv_connector_cache_gb: float = 4.0
    kv_connector_url: str | None = None
    kv_fabric_quant: str = "int8"
    kv_fabric_bind: str | None = None
    kv_fabric_peers: str | None = None
    kv_fabric_link_gbps: float | None = None
    kv_events_endpoint: str | None = None

    max_num_batched_tokens: int = 8192
    max_num_seqs: int = 256
    enable_chunked_prefill: bool = True
    scheduling_policy: str = "fcfs"
    async_scheduling: bool = True
    encoder_cache_budget: int = 4096
    enable_cascade_attention: bool = False
    enable_decode_attention: bool = True
    enable_sampler_kernel: bool = True

    tensor_parallel_size: int = 1
    data_parallel_size: int = 1
    pipeline_parallel_size: int = 1
    context_parallel_size: int = 1
    enable_expert_parallel: bool = False
    distributed_executor_backend: str = "uniproc"
    data_parallel_engines: int = 1
    # Disaggregated prefill/decode: per-engine roles ("prefill,decode",
    # P/D/U aliases). Needs --kv-connector fabric; see vllm_tpu/disagg/.
    engine_roles: str | None = None
    disagg_min_prompt_tokens: int = 0
    # Frontend scale-out: N API-server processes sharing the listen
    # socket (SO_REUSEPORT) in front of one shared engine pool.
    api_server_count: int = 1
    data_parallel_lockstep: bool = False
    pipeline_microbatches: int = 0
    enable_eplb: bool = False
    eplb_window: int = 32
    eplb_num_groups: int = 0

    device: str = "auto"

    speculative_method: str | None = None
    num_speculative_tokens: int = 0
    speculative_model: str | None = None
    spec_tree: str | None = None
    suffix_cross_request_corpus: bool = True
    # Adaptive speculation (--spec-adaptive): acceptance-driven draft
    # budgets + occupancy-gated shutoff; see SpeculativeConfig.
    spec_adaptive: bool = False
    spec_adaptive_high_watermark: float = 0.85
    spec_adaptive_low_watermark: float = 0.60
    spec_adaptive_ema_half_life_s: float = 10.0

    enable_lora: bool = False
    max_lora_rank: int = 16
    max_loras: int = 4

    # Resilience (vllm_tpu/resilience): opt-in engine-core crash recovery.
    enable_engine_recovery: bool = False
    max_engine_restarts: int = 3
    max_request_retries: int = 1
    restart_backoff_s: float = 0.5
    heartbeat_timeout_s: float = 0.0
    max_coordinator_restarts: int = 10
    coordinator_stale_after_s: float = 5.0
    journal_dir: str | None = None
    # Execution-layer fault containment (PR 5): step watchdog, restart
    # budget healing, numeric guards, poison-request quarantine.
    step_watchdog_s: float = 0.0
    restart_budget_heal_s: float = 0.0
    numeric_guard: bool = False
    max_suspect_strikes: int = 2
    quarantine_probation_cap: int = 8
    # Multi-host mesh fault tolerance (armed via VLLM_TPU_MESH_HB_ADDRS):
    # silence > death timeout = host death (supervised shrink); less is a
    # transient partition (no action).
    mesh_death_timeout_s: float = 2.0
    mesh_heartbeat_interval_s: float = 0.2
    # Elastic capacity (vllm_tpu/resilience/autoscale): traffic-driven
    # scale-up (peer weight re-seed) / scale-down (graceful drain) of the
    # DP engine pool. Opt-in via --autoscale; requires engine recovery.
    autoscale: bool = False
    autoscale_min_engines: int = 1
    autoscale_max_engines: int = 0  # 0 = initial pool size
    autoscale_up_queue_depth: float = 4.0
    autoscale_down_queue_depth: float = 0.5
    autoscale_slo_floor: float = 0.0
    autoscale_occupancy_high: float = 0.95
    autoscale_hold_s: float = 5.0
    autoscale_cooldown_s: float = 30.0
    autoscale_interval_s: float = 1.0
    autoscale_drain_deadline_s: float = 30.0
    autoscale_reseed_timeout_s: float = 120.0
    # Rolling upgrades (vllm_tpu/resilience/rolling): health gate for the
    # replacement engine each cycle slot boots. Escape hatch:
    # VLLM_TPU_DISABLE_ROLLING=1.
    upgrade_gate_requests: int = 4
    upgrade_gate_timeout_s: float = 120.0
    upgrade_slo_floor: float = 0.0

    # Lifecycle (vllm_tpu/resilience/lifecycle): overload protection.
    # All off by default; see LifecycleConfig for semantics.
    max_inflight_requests: int = 0
    max_queued_prompt_tokens: int = 0
    default_deadline_s: float = 0.0
    ttft_timeout_s: float = 0.0
    stream_buffer_size: int = 0
    stream_overflow_policy: str = "drop_oldest"
    drain_timeout_s: float = 30.0
    retry_after_s: float = 1.0
    # QoS (vllm_tpu/resilience/qos): per-tenant weighted fair queueing
    # over the prompt-token budget ("acme:3,bulk:1"), the brownout
    # degradation ladder, and pressure preemption of low-priority
    # decodes. Escape hatch: VLLM_TPU_DISABLE_QOS=1.
    tenant_weights: str | None = None
    brownout: bool = False
    brownout_occupancy_high: float = 0.92
    brownout_queue_depth_high: float = 8.0
    brownout_slo_floor: float = 0.0
    brownout_step_up_hold_s: float = 0.25
    brownout_step_down_hold_s: float = 2.0
    brownout_interval_s: float = 0.05
    brownout_max_rung: int = 4
    brownout_shed_classes: str = "batch"
    pressure_preemption_s: float = 0.0
    max_preemptions_per_step: int = 1
    max_preemptions_per_request: int = 4

    disable_log_stats: bool = False
    # SLO scoreboard: request-trace capture directory (None = off) and
    # the per-class latency targets feeding the live attainment gauge
    # ("interactive=ttft:200ms,itl:50ms;batch=ttft:5s").
    request_trace_dir: str | None = None
    slo_targets: str | None = None
    precompile: bool = False
    # Cap on token-bucket x request-bucket step compilations (derived
    # bucket ladders are thinned to fit; see CompilationConfig).
    max_step_compilations: int = 128
    # Explicit bucket ladders of the step program, comma-separated and
    # ascending ("512", "128,256"); never thinned. A server kept near its
    # capacity pins them so that it meets few step programs, each a first
    # use that stalls every running request (CompilationConfig).
    token_buckets: str | None = None
    request_buckets: str | None = None

    # Test/bench hook: inject an HF config object directly.
    hf_config: Any = None
    hf_overrides: dict | None = None

    def create_engine_config(self) -> EngineConfig:
        config = EngineConfig(
            model_config=ModelConfig(
                model=self.model,
                tokenizer=self.tokenizer,
                trust_remote_code=self.trust_remote_code,
                dtype=self.dtype,
                seed=self.seed,
                max_model_len=self.max_model_len,
                load_format=self.load_format,  # type: ignore[arg-type]
                revision=self.revision,
                quantization=self.quantization,
                quantize_embedding_layers=self.quantize_embedding_layers,
                hf_config=self.hf_config,
                hf_overrides=self.hf_overrides,
            ),
            cache_config=CacheConfig(
                block_size=self.block_size,
                gpu_memory_utilization=self.gpu_memory_utilization,
                num_gpu_blocks_override=self.num_gpu_blocks_override,
                enable_prefix_caching=self.enable_prefix_caching,
                cache_dtype=self.kv_cache_dtype,
                num_kv_stripes=self.context_parallel_size,
                kv_connector=self.kv_connector,
                kv_connector_cache_gb=self.kv_connector_cache_gb,
                kv_connector_url=self.kv_connector_url,
                kv_fabric_quant=self.kv_fabric_quant,
                kv_fabric_bind=self.kv_fabric_bind,
                kv_fabric_peers=self.kv_fabric_peers,
                kv_fabric_link_gbps=self.kv_fabric_link_gbps,
                kv_events_endpoint=self.kv_events_endpoint,
            ),
            parallel_config=ParallelConfig(
                tensor_parallel_size=self.tensor_parallel_size,
                data_parallel_size=self.data_parallel_size,
                pipeline_parallel_size=self.pipeline_parallel_size,
                context_parallel_size=self.context_parallel_size,
                enable_expert_parallel=self.enable_expert_parallel,
                distributed_executor_backend=self.distributed_executor_backend,  # type: ignore[arg-type]
                data_parallel_engines=self.data_parallel_engines,
                engine_roles=self.engine_roles,
                disagg_min_prompt_tokens=self.disagg_min_prompt_tokens,
                api_server_count=self.api_server_count,
                data_parallel_lockstep=self.data_parallel_lockstep,
                pipeline_microbatches=self.pipeline_microbatches,
                enable_eplb=self.enable_eplb,
                eplb_window=self.eplb_window,
                eplb_num_groups=self.eplb_num_groups,
            ),
            scheduler_config=SchedulerConfig(
                max_num_batched_tokens=self.max_num_batched_tokens,
                max_num_seqs=self.max_num_seqs,
                enable_chunked_prefill=self.enable_chunked_prefill,
                policy=self.scheduling_policy,  # type: ignore[arg-type]
                async_scheduling=self.async_scheduling,
                encoder_cache_budget=self.encoder_cache_budget,
                enable_cascade_attention=self.enable_cascade_attention,
                enable_decode_attention=self.enable_decode_attention,
                enable_sampler_kernel=self.enable_sampler_kernel,
                pressure_preemption_s=self.pressure_preemption_s,
                max_preemptions_per_step=self.max_preemptions_per_step,
                max_preemptions_per_request=(
                    self.max_preemptions_per_request
                ),
            ),
            device_config=DeviceConfig(device=self.device),  # type: ignore[arg-type]
            speculative_config=SpeculativeConfig(
                method=self.speculative_method,  # type: ignore[arg-type]
                num_speculative_tokens=self.num_speculative_tokens,
                model=self.speculative_model,
                spec_tree=self.spec_tree,
                suffix_cross_request_corpus=(
                    self.suffix_cross_request_corpus
                ),
                adaptive=self.spec_adaptive,
                adaptive_high_watermark=self.spec_adaptive_high_watermark,
                adaptive_low_watermark=self.spec_adaptive_low_watermark,
                adaptive_ema_half_life_s=(
                    self.spec_adaptive_ema_half_life_s
                ),
            ),
            lora_config=LoRAConfig(
                enable_lora=self.enable_lora,
                max_lora_rank=self.max_lora_rank,
                max_loras=self.max_loras,
            ),
            observability_config=ObservabilityConfig(
                log_stats=not self.disable_log_stats,
                request_trace_dir=self.request_trace_dir,
                slo_targets=self.slo_targets,
            ),
            compilation_config=CompilationConfig(
                precompile=self.precompile,
                max_step_compilations=self.max_step_compilations,
                token_buckets=_int_list(self.token_buckets),
                request_buckets=_int_list(self.request_buckets),
            ),
            resilience_config=ResilienceConfig(
                enable_recovery=self.enable_engine_recovery,
                max_engine_restarts=self.max_engine_restarts,
                max_request_retries=self.max_request_retries,
                restart_backoff_s=self.restart_backoff_s,
                heartbeat_timeout_s=self.heartbeat_timeout_s,
                max_coordinator_restarts=self.max_coordinator_restarts,
                coordinator_stale_after_s=self.coordinator_stale_after_s,
                journal_dir=self.journal_dir,
                step_watchdog_s=self.step_watchdog_s,
                restart_budget_heal_s=self.restart_budget_heal_s,
                numeric_guard=self.numeric_guard,
                max_suspect_strikes=self.max_suspect_strikes,
                quarantine_probation_cap=self.quarantine_probation_cap,
                mesh_death_timeout_s=self.mesh_death_timeout_s,
                mesh_heartbeat_interval_s=self.mesh_heartbeat_interval_s,
                autoscale=self.autoscale,
                autoscale_min_engines=self.autoscale_min_engines,
                autoscale_max_engines=self.autoscale_max_engines,
                autoscale_up_queue_depth=self.autoscale_up_queue_depth,
                autoscale_down_queue_depth=self.autoscale_down_queue_depth,
                autoscale_slo_floor=self.autoscale_slo_floor,
                autoscale_occupancy_high=self.autoscale_occupancy_high,
                autoscale_hold_s=self.autoscale_hold_s,
                autoscale_cooldown_s=self.autoscale_cooldown_s,
                autoscale_interval_s=self.autoscale_interval_s,
                autoscale_drain_deadline_s=self.autoscale_drain_deadline_s,
                autoscale_reseed_timeout_s=self.autoscale_reseed_timeout_s,
                upgrade_gate_requests=self.upgrade_gate_requests,
                upgrade_gate_timeout_s=self.upgrade_gate_timeout_s,
                upgrade_slo_floor=self.upgrade_slo_floor,
            ),
            lifecycle_config=LifecycleConfig(
                max_inflight_requests=self.max_inflight_requests,
                max_queued_prompt_tokens=self.max_queued_prompt_tokens,
                default_deadline_s=self.default_deadline_s,
                ttft_timeout_s=self.ttft_timeout_s,
                stream_buffer_size=self.stream_buffer_size,
                stream_overflow_policy=self.stream_overflow_policy,  # type: ignore[arg-type]
                drain_timeout_s=self.drain_timeout_s,
                retry_after_s=self.retry_after_s,
                tenant_weights=self.tenant_weights,
                brownout=self.brownout,
                brownout_occupancy_high=self.brownout_occupancy_high,
                brownout_queue_depth_high=(
                    self.brownout_queue_depth_high
                ),
                brownout_slo_floor=self.brownout_slo_floor,
                brownout_step_up_hold_s=self.brownout_step_up_hold_s,
                brownout_step_down_hold_s=(
                    self.brownout_step_down_hold_s
                ),
                brownout_interval_s=self.brownout_interval_s,
                brownout_max_rung=self.brownout_max_rung,
                brownout_shed_classes=self.brownout_shed_classes,
            ),
        )
        # If the model's max length is unknown and unset, derive after the HF
        # config loads (worker does it); default scheduler cap holds till then.
        return config.finalize()

    # ------------------------------------------------------------------
    # CLI
    # ------------------------------------------------------------------

    _SKIP_CLI = {"hf_config", "hf_overrides"}

    @classmethod
    def add_cli_args(cls, parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
        for f in dataclasses.fields(cls):
            if f.name in cls._SKIP_CLI:
                continue
            name = "--" + f.name.replace("_", "-")
            ftype = f.type if not isinstance(f.type, str) else eval(f.type)  # noqa: S307
            origin = get_origin(ftype)
            if ftype is bool or (origin is type(None)):
                pass
            if ftype == bool or ftype == "bool" or isinstance(f.default, bool):
                group = parser.add_mutually_exclusive_group()
                group.add_argument(
                    name, dest=f.name, action="store_true", default=f.default
                )
                group.add_argument(
                    "--no-" + f.name.replace("_", "-"),
                    dest=f.name,
                    action="store_false",
                )
                continue
            base = ftype
            if origin is not None:  # Optional[X] -> X
                args = [a for a in get_args(ftype) if a is not type(None)]
                base = args[0] if args else str
                if get_origin(base) is Literal:
                    base = str
            parser.add_argument(name, dest=f.name, type=base, default=f.default)
        return parser

    @classmethod
    def from_cli_args(cls, args: argparse.Namespace) -> "EngineArgs":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in vars(args).items() if k in fields})


@dataclass
class AsyncEngineArgs(EngineArgs):
    """Serving variant (reference keeps a separate dataclass; ours only adds
    streaming-relevant toggles)."""

    enable_log_requests: bool = False
