"""Per-host worker: device init, model load, KV sizing, runner ownership.

Reference analog: ``vllm/v1/worker/gpu_worker.py`` (init_device :237,
load_model :336, determine_available_memory :352). On TPU one worker drives
all local chips through a single jax client + GSPMD mesh, so there is no
per-device process fanout on a host (the reference needs one worker process
per GPU).
"""

from __future__ import annotations

import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from vllm_tpu.config import EngineConfig
from vllm_tpu.core.kv_cache_utils import group_kv_cache_specs
from vllm_tpu.core.sched_output import ModelRunnerOutput, SchedulerOutput
from vllm_tpu.layers.rotary import serving_positions
from vllm_tpu.logger import init_logger
# load_hf_config lives beside the registry; its importers find it here too.
from vllm_tpu.models.registry import get_model_class, load_hf_config  # noqa: F401
from vllm_tpu.tracing import compiles, startup
from vllm_tpu.utils.compile_cache import enable_compilation_cache
from vllm_tpu.worker.model_runner import ModelRunner

logger = init_logger(__name__)

# Fraction of the post-weights free HBM held back for activations and XLA
# temporaries when profiling data is unavailable.
_ACTIVATION_HEADROOM = 0.08

def _per_device_param_bytes(params, device) -> int:
    """Bytes of model weights resident on `device` (shard-exact)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        shards = getattr(leaf, "addressable_shards", None)
        if shards:
            total += sum(
                s.data.nbytes for s in shards if s.device == device
            )
        else:
            total += leaf.nbytes
    return total


class Worker:
    def __init__(self, config: EngineConfig, mesh: Any | None = None) -> None:
        self.config = config
        self.mesh = mesh
        self.model: Any = None
        self.params: Any = None
        self.runner: ModelRunner | None = None
        # name -> adapter path, for re-application across an elastic
        # runner rebuild (reinitialize_parallel).
        self._lora_paths: dict[str, str] = {}

    # ------------------------------------------------------------------

    def init_device(self) -> None:
        compiles.listen()
        enable_compilation_cache()
        dev_cfg = self.config.device_config.device
        if dev_cfg != "auto":
            jax.config.update("jax_default_device", jax.devices(dev_cfg)[0])
        self.device = jax.devices()[0]
        logger.info("worker device: %s (backend %s)", self.device, jax.default_backend())

    def load_model(self) -> None:
        mc = self.config.model_config
        hf_config = load_hf_config(mc)
        from vllm_tpu.models.native_ckpt import native_meta

        nmeta = native_meta(mc.model)
        if nmeta:
            # Native (pre-assembled) checkpoint: quantization flags were
            # decided at save time and ride the index.
            if mc.quantization is None:
                mc.quantization = nmeta.get("quantization")
            if nmeta.get("quantize_embedding_layers"):
                mc.quantize_embedding_layers = True
        if mc.max_model_len is None:
            mc.max_model_len = (
                getattr(hf_config, "max_position_embeddings", None)
                # Whisper-class: the decoder position table is
                # max_target_positions long; a larger default would
                # silently clip positions past it.
                or getattr(hf_config, "max_target_positions", None)
                or 8192
            )
        self.config.scheduler_config.max_model_len = mc.max_model_len
        quant_zero_bias = None
        ct_scheme = None
        if getattr(hf_config, "quantization_config", None) is not None:
            # Pre-quantized checkpoint: the quant method comes from the
            # checkpoint, not the CLI. compressed-tensors maps onto the
            # native int8/fp8/int4 formats; GPTQ/AWQ onto int4.
            from vllm_tpu.layers.compressed_tensors import detect_ct

            ct_scheme = detect_ct(hf_config)
            if ct_scheme is not None:
                method = ct_scheme.native_method
            else:
                from vllm_tpu.layers.gptq_import import (
                    detect_checkpoint_quant,
                )

                method, _bits, quant_zero_bias = detect_checkpoint_quant(
                    hf_config
                )
            if mc.quantization not in (None, method):
                raise ValueError(
                    f"--quantization={mc.quantization} conflicts with the "
                    f"checkpoint's quantization_config ({method})"
                )
            mc.quantization = method
        model_cls = get_model_class(hf_config)
        # Rope tables that would pass the inline limit are built for the
        # positions this server serves (``layers/rotary.py``).
        with serving_positions(mc.max_model_len):
            self.model = model_cls(
                hf_config, dtype=mc.jax_dtype, quantization=mc.quantization
            )
        if getattr(self.model, "is_encoder_decoder", False):
            cap = getattr(self.model, "max_position", None)
            if cap and mc.max_model_len > cap:
                # Finite learned decoder position tables (BART/Whisper):
                # positions past the table would silently clip.
                raise ValueError(
                    f"max_model_len ({mc.max_model_len}) exceeds the "
                    f"decoder position table ({cap})"
                )
        if (getattr(self.model, "needs_mrope", False)
                and self.config.speculative_config.enabled):
            raise ValueError(
                "speculative decoding with m-rope models is not "
                "supported yet"
            )
        if mc.quantize_embedding_layers:
            if not getattr(self.model, "supports_quantized_embedding", False):
                raise ValueError(
                    f"quantize_embedding_layers is not supported by "
                    f"{type(self.model).__name__} (its forward path "
                    "indexes the raw embedding table)"
                )
            self.model.quantize_embedding_layers = True
        from vllm_tpu import envs as _envs

        if _envs.VLLM_TPU_UNROLL_LAYERS and hasattr(
            self.model, "scan_layers"
        ):
            self.model.scan_layers = False
        if quant_zero_bias is not None:
            # gptq_v2/AWQ store the zero directly; AutoGPTQ v1 stores
            # zero-1 (the loader passes this to the importer).
            self.model.quant_zero_bias = quant_zero_bias
        if ct_scheme is not None:
            # The loader routes quantized payloads through the
            # compressed-tensors converters instead of requantizing.
            self.model.ckpt_ct_scheme = ct_scheme
        pc = self.config.parallel_config
        if pc.enable_eplb:
            if not getattr(self.model, "supports_eplb", False):
                raise ValueError(
                    f"{type(self.model).__name__} does not support EPLB "
                    "(MoE models with stacked expert weights only)"
                )
            self.model.enable_eplb = True
        if pc.enable_expert_parallel:
            if (
                not hasattr(self.model, "expert_parallel")
                or not getattr(self.model, "num_experts", None)
            ):
                raise ValueError(
                    f"{type(self.model).__name__} is not a MoE model; "
                    "--enable-expert-parallel needs stacked expert weights"
                )
            ep = pc.tensor_parallel_size
            if self.model.num_experts % max(ep, 1):
                raise ValueError(
                    f"num_experts ({self.model.num_experts}) must be "
                    f"divisible by the EP size (tp={ep})"
                )
            # EP rides the tp mesh axis (experts sharded over tp instead of
            # FFN-dim sharding); the ragged all_to_all dispatch path needs
            # the concrete mesh.
            self.model.expert_parallel = True
            self.model.ep_mesh = self.mesh
        if pc.context_parallel_size > 1:
            from vllm_tpu.models.llama import LlamaForCausalLM

            if getattr(type(self.model), "apply", None) is not LlamaForCausalLM.apply:
                raise ValueError(
                    f"{type(self.model).__name__} does not support context "
                    "parallelism yet (Llama-family only)"
                )
            if pc.pipeline_parallel_size > 1:
                raise ValueError("cp x pp composition is not supported yet")
            if self.config.speculative_config.enabled:
                # The draft KV cache is sized with the cp-multiplied global
                # block count but carries no cp sharding axis — each device
                # would hold cp x the budgeted draft bytes.
                raise ValueError(
                    "context parallelism with speculative decoding is not "
                    "supported yet"
                )
            assert self.mesh is not None, "cp requires a device mesh"
            self.model.cp_size = pc.context_parallel_size
            self.model.cp_mesh = self.mesh
        if pc.pipeline_parallel_size > 1:
            from vllm_tpu.models.llama import LlamaForCausalLM

            if getattr(type(self.model), "apply", None) is not LlamaForCausalLM.apply:
                raise ValueError(
                    f"{type(self.model).__name__} does not support pipeline "
                    "parallelism yet (Llama-family only)"
                )
            if self.config.lora_config.enable_lora:
                raise ValueError(
                    "LoRA serving is not supported with pipeline "
                    "parallelism yet (adapter deltas are not threaded "
                    "through the pipelined layer scan)"
                )
            assert self.mesh is not None, "pp requires a device mesh"
            self.model.pp_size = pc.pipeline_parallel_size
            self.model.pp_microbatches = pc.pipeline_microbatches
            self.model.pp_mesh = self.mesh
        # The model decides whether it really uses a window (some HF
        # configs carry sliding_window for archs that ignore it), and
        # which of its layers do: its layers' KV specs, grouped, are what
        # the cache manager keeps block tables and frees pages by.
        cache = self.config.cache_config
        spec_fn = getattr(self.model, "get_kv_cache_spec", None)
        groups = spec_fn and group_kv_cache_specs(spec_fn(
            cache.block_size, 1 if cache.cache_dtype != "auto"
            else jnp.dtype(mc.jax_dtype).itemsize))
        if groups and len(groups) > 1:
            cache.kv_cache_groups = groups
            cache.sliding_window = None
            if pc.context_parallel_size > 1:
                raise NotImplementedError(
                    "context parallelism with several KV groups")
        else:
            cache.sliding_window = (
                groups[0].sliding_window if groups
                else getattr(self.model, "sliding_window", None))

        shardings = None
        if self.mesh is not None:
            from vllm_tpu.parallel.mesh import named_shardings

            shardings = named_shardings(self.mesh, self.model.param_shardings())
        if mc.load_format == "dummy":
            from vllm_tpu.models.loader import init_dummy_params

            self.params = init_dummy_params(self.model, mc.seed, mc.jax_dtype, shardings)
        else:
            self.params = self.model.load_params(mc.model, mc.jax_dtype, shardings)

        self.draft_model = None
        self.draft_params = None
        spec = self.config.speculative_config
        if spec.enabled and spec.method in ("eagle", "eagle3"):
            self._load_eagle(spec, mc)
        elif spec.enabled and spec.method == "draft_model":
            self._load_draft_lm(spec, mc)

    def _load_eagle(self, spec, mc) -> None:
        """Load the EAGLE / EAGLE-3 draft head (reference: eagle.py)."""
        import jax

        from vllm_tpu.models.eagle import EagleDraftModel, Eagle3DraftModel

        cls = Eagle3DraftModel if spec.method == "eagle3" else EagleDraftModel
        if spec.model:
            from transformers import AutoConfig

            draft_cfg = AutoConfig.from_pretrained(spec.model)
            self.draft_model = cls(draft_cfg, mc.jax_dtype)
            self.draft_params = self.draft_model.load_params(
                spec.model, mc.jax_dtype
            )
        else:
            # Dummy draft head with the target's dims (benches/tests).
            assert mc.load_format == "dummy", (
                "eagle spec decode needs speculative_config.model"
            )
            self.draft_model = cls(mc.hf_config, mc.jax_dtype)
            self.draft_params = self.draft_model.init_dummy_params(
                jax.random.PRNGKey(mc.seed + 1), mc.jax_dtype
            )
        if self.mesh is not None:
            # Shard the draft head like the target (TP over heads/ffn).
            from vllm_tpu.parallel.mesh import named_shardings

            sh = named_shardings(self.mesh, self.draft_model.param_shardings())
            self.draft_params = jax.tree_util.tree_map(
                lambda x, sp: jax.device_put(x, sp), self.draft_params, sh
            )

    def _load_draft_lm(self, spec, mc) -> None:
        """Load a full small LM as the draft proposer (reference:
        ``vllm/v1/spec_decode/draft_model.py``)."""
        import jax

        from vllm_tpu.spec_decode.draft_model import DraftLM

        if spec.model:
            from transformers import AutoConfig

            draft_cfg = AutoConfig.from_pretrained(spec.model)
            self.draft_model = DraftLM(draft_cfg, mc.jax_dtype)
            self.draft_params = self.draft_model.load_params(
                spec.model, mc.jax_dtype
            )
        else:
            assert mc.load_format == "dummy", (
                "draft_model spec decode needs speculative_config.model"
            )
            self.draft_model = DraftLM(mc.hf_config, mc.jax_dtype)
            self.draft_params = self.draft_model.init_dummy_params(
                jax.random.PRNGKey(mc.seed + 1), mc.jax_dtype
            )
        if self.mesh is not None:
            from vllm_tpu.parallel.mesh import named_shardings

            sh = named_shardings(self.mesh, self.draft_model.param_shardings())
            self.draft_params = jax.tree_util.tree_map(
                lambda x, sp: jax.device_put(x, sp), self.draft_params, sh
            )

    # ------------------------------------------------------------------

    def _memory_limit_known(self) -> bool:
        """Whether the device reports a memory budget — profiling is
        pointless without one (the CPU backend reports none)."""
        stats = self.device.memory_stats()
        return bool(stats) and "bytes_limit" in stats

    def determine_num_kv_blocks(
        self, activation_bytes: int | None = None
    ) -> int:
        """KV sizing (reference: determine_available_memory + profile_run).

        ``activation_bytes`` is the measured step high-water mark from
        ``ModelRunner.profile_step_memory`` (XLA memory analysis of the
        compiled max-bucket step); when provided it replaces the fixed
        activation-headroom fraction. The device's ``memory_stats()`` bound
        the budget (a v5e reports them: ``bytes_limit`` 16909336064); a
        backend that reports none (the CPU) needs an explicit
        ``num_gpu_blocks_override``.
        """
        cache = self.config.cache_config
        cp = self.config.parallel_config.context_parallel_size
        if cache.num_gpu_blocks_override is not None:
            if cache.num_gpu_blocks_override % max(cp, 1):
                raise ValueError(
                    f"num_gpu_blocks_override "
                    f"({cache.num_gpu_blocks_override}) must be divisible "
                    f"by context_parallel_size ({cp})"
                )
            return cache.num_gpu_blocks_override

        kv_dtype = (
            self.config.model_config.jax_dtype
            if cache.cache_dtype == "auto"
            else cache.jax_cache_dtype
        )
        specs = self.model.get_kv_cache_spec(
            cache.block_size, jnp.dtype(kv_dtype).itemsize
        )
        draft_page_bytes = 0
        if self.draft_model is not None:
            # The draft KV (1 layer for EAGLE, the full stack for a
            # draft model) comes out of the same budget: its layers ride
            # the target's page ids.
            from vllm_tpu.core.kv_cache_utils import FullAttentionSpec

            draft_page_bytes = getattr(
                self.draft_model, "num_layers", 1
            ) * FullAttentionSpec(
                block_size=cache.block_size,
                num_kv_heads=self.draft_model.num_kv_heads,
                head_size=self.draft_model.head_dim,
                dtype_bytes=jnp.dtype(kv_dtype).itemsize,
            ).page_size_bytes
        if not self._memory_limit_known():
            raise ValueError(
                "cannot size the KV cache: device "
                f"{self.device.device_kind!r} reports no memory_stats(); "
                "pass num_gpu_blocks_override"
            )
        stats = self.device.memory_stats()
        limit = stats["bytes_limit"] * cache.gpu_memory_utilization
        in_use = stats.get("bytes_in_use", 0)
        if self.config.parallel_config.enable_eplb:
            # Online rebalancing transiently holds BOTH expert-weight
            # copies (in-flight steps pin the old one): reserve that
            # headroom so the first mid-serving rebalance cannot OOM.
            # PER-DEVICE bytes (the budget is per device; global stacked
            # sizes would over-reserve by the TP/EP shard factor).
            layers = (
                self.params.get("layers", {})
                if isinstance(self.params, dict)
                else {}
            )
            expert_tree = {
                k: layers[k]
                for k in ("we_gate", "we_up", "we_down")
                if k in layers
            }
            reserve = _per_device_param_bytes(expert_tree, self.device)
            if reserve:
                logger.info(
                    "EPLB: reserving %.2f GiB for rebalance double-"
                    "residency", reserve / 2**30,
                )
                in_use += reserve
        fixed_fn = getattr(self.model, "fixed_state_bytes", None)
        if fixed_fn is not None:
            # Hybrid models: constant-size Mamba slots come off the top of
            # the budget before paged blocks are sized.
            slots = self.config.scheduler_config.max_num_seqs
            state = fixed_fn(slots)
            # The provisional runner of a profiled start holds the slots
            # already (``alloc_kv_cache`` makes them whatever the page
            # count), and ``bytes_in_use`` has counted them once.
            held = 0
            if self.runner is not None and isinstance(
                self.runner.kv_cache, dict
            ):
                held = sum(
                    leaf.nbytes for name, leaf in self.runner.kv_cache.items()
                    if name != "paged"
                )
            logger.info(
                "reserving %.2f GiB for per-request state: %d slots and a "
                "scratch one of %.1f MB each (%.2f GiB of it allocated "
                "already)", state / 2**30, slots,
                state / (slots + 1) / 1e6, held / 2**30,
            )
            in_use += state - held
        if activation_bytes is not None:
            # Measured peak + 2% of the limit as safety margin (allocator
            # fragmentation, host-side staging buffers).
            free_for_kv = limit - in_use - activation_bytes - 0.02 * limit
            logger.info(
                "KV sizing from measured activations: %.2f GiB peak",
                activation_bytes / 2**30,
            )
        else:
            free_for_kv = (limit - in_use) * (1 - _ACTIVATION_HEADROOM)
        # One page id costs one GROUP's layers (every layer, where the
        # model has one spec), whichever group it is handed to.
        groups = group_kv_cache_specs(specs)
        page_bytes = groups[0].page_size_bytes + draft_page_bytes
        if free_for_kv < page_bytes:
            fixed = (
                f", of which per-request state {state} bytes for "
                f"{slots} slots (max_num_seqs)" if fixed_fn is not None else ""
            )
            raise RuntimeError(
                f"no HBM left for KV cache (limit={limit}, in_use={in_use}"
                f"{fixed}, activations={activation_bytes}, one page "
                f"{page_bytes} bytes)"
            )
        num_blocks = int(free_for_kv) // page_bytes
        if cp > 1:
            # The budget above is PER DEVICE and the cache's block dim is
            # cp-sharded: the global pool holds cp x the per-device count.
            num_blocks *= cp
        logger.info(
            "KV sizing: %.2f GiB free -> %d blocks of %d tokens",
            free_for_kv / 2**30,
            num_blocks,
            cache.block_size,
        )
        if len(groups) > 1:
            # A page is one size in bytes and, where the kinds' layers
            # cost unlike bytes a token, unlike tokens a group; a short
            # group's page pays for places no layer writes.
            lost = sum(g.lost_bytes_per_page for g in groups)
            logger.info(
                "KV groups: %d over the one pool, a page %s tokens of one "
                "group's layers, %d bytes: %s%s",
                len(groups),
                "/".join(str(n) for n in sorted(
                    {g.block_size for g in groups}, reverse=True)),
                page_bytes,
                "; ".join(
                    "%d %s%s of %d tokens a page" % (
                        len(g.layer_names), type(g.kv_cache_spec).__name__,
                        "" if g.sliding_window is None
                        else " (window %d)" % g.sliding_window,
                        g.block_size,
                    ) for g in groups),
                "" if not lost else
                "; %d of %d places hold no layer: %d bytes of a page of "
                "the short last group are lost" % (
                    sum(g.num_places - len(g.layer_names) for g in groups),
                    sum(g.num_places for g in groups), lost),
            )
        return num_blocks

    def initialize(self) -> int:
        """Full startup; returns the KV block count for the scheduler.
        Each part is a phase of the start's timeline (``tracing.startup``)
        and logs its seconds as it ends, so a start that hangs says where."""
        with startup.phase("init_device") as note:
            self.init_device()
            note.append(f"{self.device}")
        with startup.phase("load_model") as note:
            self.load_model()
            note.append("%.1f GB of weights on the device" % (
                _per_device_param_bytes(self.params, self.device) / 1e9))
        if getattr(self.model, "is_stateful_ssm", False):
            # Pure-SSM models: constant-size per-request state, so one
            # "block" = the whole sequence (reference MambaSpec block_size
            # semantics) and prefix caching is meaningless (state is not
            # content-addressable per block).
            cache = self.config.cache_config
            cache.block_size = self.config.model_config.max_model_len
            if cache.enable_prefix_caching:
                logger.info("prefix caching disabled for SSM model")
                cache.enable_prefix_caching = False
        if getattr(self.model, "is_hybrid_ssm", False) or getattr(
            self.model, "is_encoder_decoder", False
        ):
            # Per-request slot state: hybrid attention+SSM Mamba state
            # (Jamba/Bamba-class) or encoder-decoder cross-attention KV
            # (BART-class, reference: CrossAttentionManager). Paged KV
            # stays block-addressed, but prefix hits cannot restore slot
            # state, so caching is off; spec-decode verification would
            # need slot-state rollback.
            kind = (
                "hybrid SSM" if getattr(self.model, "is_hybrid_ssm", False)
                else "encoder-decoder"
            )
            cache = self.config.cache_config
            if cache.enable_prefix_caching:
                logger.info("prefix caching disabled for %s model", kind)
                cache.enable_prefix_caching = False
            if self.config.speculative_config.enabled:
                raise ValueError(
                    f"speculative decoding with {kind} models is not "
                    "supported yet (verification would need per-request "
                    "state rollback)"
                )
            self.model.max_state_slots = (
                self.config.scheduler_config.max_num_seqs
            )
        cache = self.config.cache_config
        from vllm_tpu import envs

        def make_runner(num_blocks: int) -> ModelRunner:
            return ModelRunner(
                self.config, self.model, self.params, num_blocks, self.mesh,
                draft_model=self.draft_model, draft_params=self.draft_params,
            )

        # Profile-based sizing: build the runner with a provisional pool,
        # measure the compiled max-bucket step's peak memory, then size and
        # re-allocate the real KV cache (reference: gpu_worker.py:352).
        # Under an explicit budget: no profiling (the phase reads 0), a
        # single allocation.
        act = None
        with startup.phase("profile") as note:
            if cache.num_gpu_blocks_override is None:
                self.runner = make_runner(64)
                if (envs.VLLM_TPU_PROFILE_KV_SIZING
                        and self._memory_limit_known()):
                    act = self.runner.profile_step_memory()
                    if act is not None:
                        note.append(
                            "%.0f MiB of activations" % (act / 2**20))
        with startup.phase("kv_cache") as note:
            num_blocks = self.determine_num_kv_blocks(act)
            cache.num_gpu_blocks = num_blocks
            if self.runner is None:
                self.runner = make_runner(num_blocks)
            else:
                self.runner.resize_kv_cache(num_blocks)
            note.append(f"{num_blocks} blocks of {cache.block_size} tokens")
        return num_blocks

    def compile_or_warm_up_model(self) -> None:
        with startup.phase("precompile"):
            if self.config.compilation_config.precompile:
                assert self.runner is not None
                self.runner.profile_run()

    # ------------------------------------------------------------------

    def execute_model(self, scheduler_output: SchedulerOutput) -> ModelRunnerOutput:
        assert self.runner is not None
        if getattr(self, "_mesh_poisoned", False):
            # A failed reinitialize_mesh left partially-rebuilt state; a
            # step here could compute on garbage. The engine is supposed
            # to be dying already — make sure of it.
            raise RuntimeError(
                "worker is half-meshed after a failed mesh recovery; "
                "refusing to execute")
        return self.runner.execute_model(scheduler_output)

    def execute_dummy_batch(self) -> None:
        """One 1-token no-op device step (DP wave lockstep; ``core.py:731``)."""
        assert self.runner is not None
        self.runner.execute_dummy_batch()

    def set_structured_output_manager(self, manager: Any) -> None:
        assert self.runner is not None
        self.runner.structured_output_manager = manager

    def sleep(self, level: int = 1) -> None:
        assert self.runner is not None
        self.runner.sleep(level)

    def wake_up(self) -> None:
        assert self.runner is not None
        runner = self.runner
        params = None
        draft_params = None
        if runner._host_params is None:
            # Level-2 sleep discarded the weights: reload from source.
            mc = self.config.model_config
            shardings = None
            if self.mesh is not None:
                from vllm_tpu.parallel.mesh import named_shardings

                shardings = named_shardings(
                    self.mesh, self.model.param_shardings()
                )
            if mc.load_format == "dummy":
                from vllm_tpu.models.loader import init_dummy_params

                params = init_dummy_params(
                    self.model, mc.seed, mc.jax_dtype, shardings
                )
            else:
                params = self.model.load_params(
                    mc.model, mc.jax_dtype, shardings
                )
            self.params = params
            if runner.draft_model is not None and runner._host_draft is None:
                spec = self.config.speculative_config
                self._load_eagle(spec, mc)
                draft_params = self.draft_params
        runner.wake_up(params=params, draft_params=draft_params)

    def update_weights(self, path: str) -> None:
        assert self.runner is not None
        self.runner.update_weights(path)

    def receive_weights(self, port: int, timeout: float = 300.0) -> int:
        assert self.runner is not None
        return self.runner.receive_weights_push(port, timeout)

    def push_weights_to(self, host: str, port: int,
                        timeout: float = 300.0) -> int:
        assert self.runner is not None
        return self.runner.push_weights_to(host, port, timeout)

    def save_sharded_state(self, path: str) -> None:
        """Dump the ASSEMBLED param tree for fast reload (reference:
        ``gpu_worker.py:939 save_sharded_state`` + sharded_state_loader).
        The saved directory is a self-contained model path: HF config +
        native index + leaf payloads; pointing ``--model`` at it skips
        HF name mapping, stacking, and quantize-at-load."""
        import json as _json

        from vllm_tpu.models.native_ckpt import save_native

        assert self.params is not None, "load_model() before saving"
        mc = self.config.model_config
        # The runner's tree is authoritative once it exists (RL weight
        # updates land there; worker.params is the load-time snapshot).
        params = self.runner.params if self.runner is not None else self.params
        save_native(params, path, meta={
            "quantization": mc.quantization,
            "quantize_embedding_layers": bool(
                getattr(self.model, "quantize_embedding_layers", False)
            ),
        })
        hf_config = mc.hf_config
        cfg = _json.loads(hf_config.to_json_string())
        cfg.setdefault("architectures", getattr(
            hf_config, "architectures", None
        ) or [type(self.model).__name__])
        # GPTQ/AWQ configs carry quantization_config; the native payload
        # is already converted — a reload must not re-trigger importers.
        cfg.pop("quantization_config", None)
        with open(os.path.join(path, "config.json"), "w") as f:
            _json.dump(cfg, f, indent=1)
        # Tokenizer files ride along so the directory really is a
        # self-contained --model path (a reload runs AutoTokenizer on it).
        import shutil

        src_dir = self.config.model_config.tokenizer or mc.model
        if os.path.isdir(src_dir):
            for name in (
                "tokenizer.json", "tokenizer_config.json",
                "special_tokens_map.json", "vocab.json", "merges.txt",
                "tokenizer.model", "added_tokens.json", "tekken.json",
                "chat_template.jinja",
            ):
                src = os.path.join(src_dir, name)
                if os.path.exists(src):
                    shutil.copy2(src, os.path.join(path, name))

    def validate_parallel_resize(self, new_tp: int) -> bool:
        """Side-effect-free constraint check for an elastic resize — the
        engine calls this BEFORE the destructive drain/preempt/cache-
        reset so a rejected resize (bad divisibility, too few devices)
        costs nothing (ADVICE r4 #1)."""
        pc = self.config.parallel_config
        if new_tp == pc.tensor_parallel_size:
            return True
        if new_tp < 1:
            raise ValueError(f"tensor_parallel_size must be >= 1, got {new_tp}")
        if (
            pc.pipeline_parallel_size > 1
            or pc.context_parallel_size > 1
            or pc.data_parallel_size > 1
        ):
            raise ValueError(
                "elastic resize supports tp/ep-only meshes (pp/cp/dp "
                "axes must be 1)"
            )
        avail = len(jax.devices())
        if new_tp > avail:
            raise ValueError(
                f"elastic resize to tp={new_tp} needs {new_tp} devices, "
                f"have {avail}"
            )
        if pc.enable_expert_parallel and new_tp > 1:
            e = getattr(self.model, "num_experts", 0) or 0
            if e % new_tp:
                raise ValueError(
                    f"num_experts ({e}) not divisible by new EP size {new_tp}"
                )
        kvh = getattr(self.model, "num_kv_heads", 0) or 0
        if new_tp > 1 and kvh and kvh % new_tp:
            raise ValueError(
                f"num_kv_heads ({kvh}) not divisible by tp size {new_tp} "
                "(KV-cache head sharding)"
            )
        return True

    def reinitialize_parallel(self, new_tp: int) -> int:
        """Elastic EP: resize the expert/tensor-parallel world at runtime.

        Reference analog: ``vllm/distributed/elastic_ep/elastic_state.py``
        and ``EngineCore.reinitialize_distributed`` (``core.py:1865``) —
        there, NCCL groups are torn down and rebuilt and expert weights are
        shuffled point-to-point. The TPU formulation: parallelism is a mesh
        plus sharding annotations, so scaling the EP world is (1) build a
        mesh over the new device set, (2) ``device_put`` the params onto it
        (XLA moves the shards over ICI; done leaf-by-leaf with eager
        deletion so peak overhead is one leaf, not a second full copy),
        (3) rebuild the runner so every jitted executable re-traces against
        the new mesh. KV-cache content is discarded — the engine preempts
        running requests first, so they recompute from their token ids
        (the reference also drops KV across a reconfigure).

        Returns the KV block count (unchanged — the scheduler's block pool
        stays valid; only the content was dropped).
        """
        assert self.runner is not None, "initialize() before resizing"
        pc = self.config.parallel_config
        old_tp = pc.tensor_parallel_size
        num_blocks = self.config.cache_config.num_gpu_blocks
        if new_tp == old_tp:
            return num_blocks
        self.validate_parallel_resize(new_tp)
        if self.runner._host_params is not None:
            raise RuntimeError("cannot resize a sleeping engine; wake_up first")

        pc.tensor_parallel_size = new_tp
        new_mesh = None
        if pc.world_size > 1:
            from vllm_tpu.parallel.mesh import build_mesh

            new_mesh = build_mesh(pc)

        def _reshard(tree, model):
            if tree is None:
                return None
            if new_mesh is not None:
                from vllm_tpu.parallel.mesh import named_shardings

                shardings = named_shardings(new_mesh, model.param_shardings())
            else:
                from jax.sharding import SingleDeviceSharding

                one = SingleDeviceSharding(jax.devices()[0])
                shardings = jax.tree_util.tree_map(lambda _: one, tree)

            def _put(x, s):
                # donate=True lets the runtime reuse old shards in the
                # new layout where device sets overlap. NO explicit
                # delete: the result may alias source buffers on shared
                # devices without marking the source deleted (observed on
                # the CPU backend), so a manual delete would corrupt the
                # resharded array. Non-aliased old shards free when the
                # old tree's references drop below.
                return jax.device_put(x, s, donate=True)

            return jax.tree_util.tree_map(_put, tree, shardings)

        self.params = _reshard(self.params, self.model)
        if self.draft_params is not None and self.draft_model is not None:
            self.draft_params = _reshard(self.draft_params, self.draft_model)
        self.mesh = new_mesh
        if getattr(self.model, "expert_parallel", False):
            self.model.ep_mesh = new_mesh

        # Rebuild the runner: jitted executables and the KV cache are
        # mesh-shaped. Cross-step wiring (grammar tables, KV connector,
        # LoRA adapters) is re-applied onto the fresh runner.
        old = self.runner
        som = old.structured_output_manager
        connector = getattr(old, "kv_connector", None)
        old.kv_cache = None  # free before the new runner allocates
        old.draft_kv = None
        self.runner = ModelRunner(
            self.config, self.model, self.params, num_blocks, new_mesh,
            draft_model=self.draft_model, draft_params=self.draft_params,
        )
        if som is not None:
            self.runner.structured_output_manager = som
        if connector is not None:
            self.runner.kv_connector = connector
        if self.runner.lora_manager is not None:
            for name, path in self._lora_paths.items():
                self.runner.lora_manager.add_lora(name, path)
        logger.info(
            "elastic resize: tp/ep %d -> %d (mesh %s)", old_tp, new_tp,
            None if new_mesh is None else
            dict(zip(new_mesh.axis_names, new_mesh.devices.shape)),
        )
        return num_blocks

    def reinitialize_mesh(
        self,
        coordinator_address: str | None,
        num_processes: int | None,
        process_id: int | None,
    ) -> int:
        """Mesh-shrink/grow recovery: tear down the jax.distributed
        runtime and re-bootstrap it over the given survivor world, then
        rebuild mesh + weights + runner against the new global device set.

        Differs from :meth:`reinitialize_parallel` in one crucial way:
        the OLD global arrays are invalid (their device set includes the
        dead host / the old backend is gone), so weights cannot be
        resharded in place — they are reloaded from the checkpoint onto
        the new mesh. A ``None`` world means the original launch was
        uniproc (or metadata-discovered): there is no runtime to re-form,
        and the recovery degenerates to the request-replay the engine
        already performed — weights and runner are untouched.

        Any failure after the teardown leaves this worker poisoned
        (``_mesh_poisoned``): the exception propagates as a fatal
        MeshRecoveryError upstream, and no step may run on the
        half-built state in between — fully recovered or cleanly dead,
        never half-meshed.
        """
        from vllm_tpu.resilience.failpoints import fail_point

        fail_point("worker.reinitialize_mesh",
                   lambda: f"world={coordinator_address},{num_processes},"
                           f"{process_id}")
        num_blocks = self.config.cache_config.num_gpu_blocks
        if coordinator_address is None:
            return num_blocks
        from vllm_tpu.parallel.distributed import (init_distributed,
                                                   shutdown_distributed)

        self._mesh_poisoned = True
        try:
            old_ndev = len(jax.devices())
            pc = self.config.parallel_config
            old_tp = pc.tensor_parallel_size
            # Drop every reference into the old world BEFORE the
            # teardown: live Device/Array handles keep the old backend —
            # and through its collectives, the old coordination client —
            # alive. An undead client that later polls the NEW world's
            # coordination service aborts the process from a C++ thread.
            old_runner = self.runner
            som = (old_runner.structured_output_manager
                   if old_runner is not None else None)
            connector = getattr(old_runner, "kv_connector", None)
            old_runner = None
            self.runner = None
            self.params = None
            self.mesh = None
            if getattr(self.model, "expert_parallel", False):
                self.model.ep_mesh = None
            # Forced teardown: on a shrink a peer is already dead and can
            # never join the cooperative shutdown barrier; on a grow the
            # old (shrunken) world is being abandoned anyway.
            shutdown_distributed(force=True)
            init_distributed(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
            )
            new_ndev = len(jax.devices())
            # Scale tp proportionally with the device count (a 2-host
            # tp=8 world losing one host re-forms at tp=4). Other mesh
            # axes were validated =1 by validate_parallel_resize rules.
            new_tp = max(1, old_tp * new_ndev // old_ndev)
            pc.tensor_parallel_size = new_tp
            new_mesh = None
            shardings = None
            if pc.world_size > 1:
                from vllm_tpu.parallel.mesh import (build_mesh,
                                                    named_shardings)

                new_mesh = build_mesh(pc)
                shardings = named_shardings(
                    new_mesh, self.model.param_shardings())
            mc = self.config.model_config
            # Reload, don't reshard: the dead host's shards are gone and
            # the old arrays belong to a torn-down backend.
            if mc.load_format == "dummy":
                from vllm_tpu.models.loader import init_dummy_params

                self.params = init_dummy_params(
                    self.model, mc.seed, mc.jax_dtype, shardings)
            else:
                self.params = self.model.load_params(
                    mc.model, mc.jax_dtype, shardings)
            self.mesh = new_mesh
            if getattr(self.model, "expert_parallel", False):
                self.model.ep_mesh = new_mesh
            self.runner = ModelRunner(
                self.config, self.model, self.params, num_blocks, new_mesh,
                draft_model=self.draft_model,
                draft_params=self.draft_params,
            )
            if som is not None:
                self.runner.structured_output_manager = som
            if connector is not None:
                self.runner.kv_connector = connector
            if self.runner.lora_manager is not None:
                for name, path in self._lora_paths.items():
                    self.runner.lora_manager.add_lora(name, path)
            logger.info(
                "mesh recovery: re-bootstrapped %d processes "
                "(process %s), devices %d -> %d, tp %d -> %d",
                num_processes, process_id, old_ndev, new_ndev,
                old_tp, new_tp)
        except Exception:
            logger.exception("mesh re-bootstrap failed; worker poisoned")
            raise
        self._mesh_poisoned = False
        return num_blocks

    def set_kv_connector(self, connector) -> None:
        assert self.runner is not None
        self.runner.kv_connector = connector

    def kv_connector_save(self, entries: list[tuple]) -> None:
        assert self.runner is not None
        self.runner.kv_connector_save(entries)

    def kv_cache_block_bytes(self) -> int:
        """Device bytes per KV block (all layers) — sizes the fabric's
        device-tier byte gauge."""
        assert self.runner is not None
        cache = getattr(self.runner, "kv_cache", None)
        if cache is None or cache.shape[1] == 0:
            return 0
        return int(cache.nbytes // cache.shape[1])

    def kv_connector_push(
        self, req_id: str, url: str, keys: list
    ) -> bool:
        assert self.runner is not None
        return self.runner.kv_connector_push(req_id, url, keys)

    def kv_connector_reserve(self, req_id: str, n_blocks: int) -> int:
        assert self.runner is not None
        return self.runner.kv_connector_reserve(req_id, n_blocks)

    def add_lora(self, name: str, path: str) -> bool:
        assert self.runner is not None and self.runner.lora_manager is not None, (
            "LoRA serving requires enable_lora=True"
        )
        ok = self.runner.lora_manager.add_lora(name, path)
        if ok:
            self._lora_paths[name] = path
        return ok

    def remove_lora(self, name: str) -> bool:
        assert self.runner is not None and self.runner.lora_manager is not None
        self._lora_paths.pop(name, None)
        return self.runner.lora_manager.remove_lora(name)

    def list_loras(self) -> list[str]:
        assert self.runner is not None and self.runner.lora_manager is not None
        return self.runner.lora_manager.list_loras()

    def start_profile(self, trace_dir: str | None = None,
                      python_tracer_level: int = 0) -> dict:
        """JAX profiler (xplane/TensorBoard) start — reference:
        ``gpu_worker.py profile :866`` torch-profiler RPC. The profiler
        itself lives in ``vllm_tpu/tracing.py``, which also turns the
        program's own spans on for as long as the profile runs."""
        from vllm_tpu.tracing import start_profiler

        info = start_profiler(trace_dir, python_tracer_level)
        logger.info("profiler started -> %s", info["trace_dir"])
        return info

    def stop_profile(self) -> dict:
        from vllm_tpu.tracing import stop_profiler

        info = stop_profiler()
        logger.info("profiler stopped after %.2f s of trace; the stop took "
                    "%.2f s -> %s", info["traced_s"], info["stop_s"],
                    info["trace_dir"])
        return info

    def roofline_info(self) -> dict:
        """The model's roofline parameters (msgpack-able; the KV fabric's
        fetch-or-recompute cost model prices a recompute with them, via
        ``vllm_tpu/metrics/roofline.py``)."""
        from vllm_tpu.metrics import roofline as rf

        assert self.params is not None
        hf = load_hf_config(self.config.model_config)
        wbytes = rf.weight_bytes(self.params)
        logical = rf.logical_params(self.params)
        vocab = int(getattr(hf, "vocab_size", 0) or 0)
        hidden = int(getattr(hf, "hidden_size", 0) or 0)
        active = max(0, logical - vocab * hidden)
        heads = int(getattr(hf, "num_attention_heads", 1) or 1)
        kv_heads = int(getattr(hf, "num_key_value_heads", heads) or heads)
        head_dim = int(
            getattr(hf, "head_dim", None) or (hidden // max(heads, 1))
        )
        layers = int(getattr(hf, "num_hidden_layers", 0) or 0)
        kv_byte = (
            1 if self.config.cache_config.cache_dtype == "fp8" else 2
        )
        return {
            "weight_bytes": wbytes,
            "active_params": active,
            "kv_tok_bytes": rf.kv_bytes_per_token(
                layers, kv_heads, head_dim, kv_byte),
            "device_kind": getattr(self.device, "device_kind", ""),
        }
