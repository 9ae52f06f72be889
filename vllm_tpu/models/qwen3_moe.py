"""Qwen3-MoE decoder (GPT-OSS-class sparse MoE breadth).

Reference analog: ``vllm/model_executor/models/qwen3_moe.py``. The Mixtral
graph (fused MoE with layer-stacked expert weights) plus Qwen3's per-head
q/k RMSNorm and decoupled head_dim; router normalization follows the
config's ``norm_topk_prob``.
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp

from vllm_tpu.models.mixtral import MixtralForCausalLM


class Qwen2MoeForCausalLM(MixtralForCausalLM):
    """Qwen1.5/2-MoE: qkv bias + sigmoid-gated shared expert.

    Reference analog: ``vllm/model_executor/models/qwen2_moe.py``.
    """

    attention_bias = True

    def __init__(self, hf_config: Any, dtype=jnp.bfloat16,
                 quantization: str | None = None) -> None:
        c = hf_config
        if not hasattr(c, "num_local_experts"):
            c.num_local_experts = c.num_experts
        super().__init__(c, dtype, quantization)
        self.renormalize = bool(getattr(c, "norm_topk_prob", False))
        self.sliding_window = None
        self.shared_intermediate = (
            getattr(c, "shared_expert_intermediate_size", 0) or 0
        )
        step = getattr(c, "decoder_sparse_step", 1)
        only = list(getattr(c, "mlp_only_layers", []) or [])
        if step != 1 or only:
            raise NotImplementedError(
                "Qwen2-MoE mixed dense/sparse layer patterns "
                "(decoder_sparse_step/mlp_only_layers) are not supported"
            )

    def hf_weight_map(self) -> dict:
        from vllm_tpu.models.llama import LlamaForCausalLM

        # Base Llama names (incl. qkv biases), then the Qwen2-MoE MLP
        # naming (NOT Mixtral's block_sparse_moe).
        m = LlamaForCausalLM.hf_weight_map(self)
        for i in range(self.num_layers):
            hf = f"model.layers.{i}"
            for name in ("gate_proj", "up_proj", "down_proj"):
                m.pop(f"{hf}.mlp.{name}.weight", None)
            m[f"{hf}.mlp.gate.weight"] = (f"layers.router.{i}", True)
            for j in range(self.num_experts):
                base = f"{hf}.mlp.experts.{j}"
                m[f"{base}.gate_proj.weight"] = (f"layers.we_gate.{i}.{j}", True)
                m[f"{base}.up_proj.weight"] = (f"layers.we_up.{i}.{j}", True)
                m[f"{base}.down_proj.weight"] = (f"layers.we_down.{i}.{j}", True)
            sh = f"{hf}.mlp.shared_expert"
            m[f"{sh}.gate_proj.weight"] = (f"layers.ws_gate.{i}", True)
            m[f"{sh}.up_proj.weight"] = (f"layers.ws_up.{i}", True)
            m[f"{sh}.down_proj.weight"] = (f"layers.ws_down.{i}", True)
            m[f"{hf}.mlp.shared_expert_gate.weight"] = (
                f"layers.wsg.{i}", True)
        return m


class Qwen3MoeForCausalLM(MixtralForCausalLM):
    qk_norm = True

    def __init__(self, hf_config: Any, dtype=jnp.bfloat16,
                 quantization: str | None = None) -> None:
        c = hf_config
        # Mixtral reads num_local_experts; Qwen3Moe calls it num_experts.
        if not hasattr(c, "num_local_experts"):
            c.num_local_experts = c.num_experts
        super().__init__(c, dtype, quantization)
        self.renormalize = bool(getattr(c, "norm_topk_prob", True))
        self.sliding_window = None

    def hf_weight_map(self) -> dict:
        m = super().hf_weight_map()
        for i in range(self.num_layers):
            # Qwen3Moe naming: mlp.gate (router) + mlp.experts.{j}.*_proj.
            del m[f"model.layers.{i}.block_sparse_moe.gate.weight"]
            m[f"model.layers.{i}.mlp.gate.weight"] = (
                f"layers.router.{i}", True)
            for j in range(self.num_experts):
                old = f"model.layers.{i}.block_sparse_moe.experts.{j}"
                for k in ("w1", "w2", "w3"):
                    del m[f"{old}.{k}.weight"]
                new = f"model.layers.{i}.mlp.experts.{j}"
                m[f"{new}.gate_proj.weight"] = (f"layers.we_gate.{i}.{j}", True)
                m[f"{new}.up_proj.weight"] = (f"layers.we_up.{i}.{j}", True)
                m[f"{new}.down_proj.weight"] = (f"layers.we_down.{i}.{j}", True)
        return m


class SDARMoeForCausalLM(Qwen3MoeForCausalLM):
    """SDAR-MoE: the Qwen3-MoE layer, key for key, generating by diffusion
    over blocks (``models/generation.py``, which reads the block length
    and the mask token from ``config.json``). The model's part is the
    attention causal over blocks, which ``apply`` runs as the step's
    ``AttentionMetadata.block_len`` says;
    the logits at a position are for the token AT that position (no
    shift): an uncommitted position is fed ``mask_token_id`` and its own
    output row predicts it."""

    generation = "block_diffusion"
