"""Mixtral (sparse-MoE Llama-family decoder).

Reference analog: ``vllm/model_executor/models/mixtral.py`` (MixtralMoE
using the FusedMoE layer). Attention/norm/rope are inherited from the Llama
graph; the dense MLP is replaced by the fused MoE layer with layer-stacked
expert weights ``[L, E, ...]`` (scan layout, experts shardable over a mesh
axis for EP — SURVEY.md §2.4).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from vllm_tpu.layers.layernorm import rms_norm
from vllm_tpu.layers.moe import fused_experts_of_layer, select_experts
from vllm_tpu.layers.rotary import _apply_interleaved, _apply_rotate_half
from vllm_tpu.models.llama import LlamaForCausalLM
from vllm_tpu.ops.attention import (
    AttentionMetadata,
    kv_dequant_scale,
    paged_attention,
    write_kv,
)


class MixtralForCausalLM(LlamaForCausalLM):
    supports_lora = False  # MoE expert adapters are future work
    dense_mlp = False
    supports_eplb = True
    # Set by the worker when EPLB is on: routing stays in logical expert
    # ids, a per-layer [E] map redirects to physical slots, and apply()
    # returns per-layer logical-expert token counts as a third output.
    enable_eplb = False
    # Set by the runner: apply() returns those [L, E] counts (of LIVE
    # tokens) whether or not EPLB is on; vllm:moe_experts_hit_total is
    # their non-zero entries.
    return_expert_counts = False

    def __init__(self, hf_config: Any, dtype=jnp.bfloat16,
                 quantization: str | None = None) -> None:
        if quantization:
            from vllm_tpu.logger import init_logger

            init_logger(__name__).warning(
                "weight quantization is not yet supported for MoE models; "
                "running %s unquantized", type(self).__name__,
            )
        super().__init__(hf_config, dtype, quantization=None)
        self.num_experts = hf_config.num_local_experts
        self.top_k = hf_config.num_experts_per_tok
        self.renormalize = True
        self.sliding_window = getattr(hf_config, "sliding_window", None)
        # Per-expert FFN width may differ from the dense intermediate
        # (Qwen2-MoE's moe_intermediate_size).
        self.moe_intermediate = getattr(
            hf_config, "moe_intermediate_size", self.intermediate_size
        )
        # Sigmoid-gated shared expert (Qwen2-MoE); 0 = none (Mixtral).
        self.shared_intermediate = 0
        # EP toggle: experts sharded over the tp axis (vLLM
        # enable_expert_parallel semantics) vs FFN-dim sharding. With a
        # mesh attached (set by the worker), the ragged all_to_all
        # dispatch + grouped-GEMM path runs; without one, dense one-hot.
        self.expert_parallel = False
        self.ep_mesh = None

    # ------------------------------------------------------------------

    def init_dummy_params(self, rng: jax.Array, dtype=None) -> dict:
        import math
        from functools import partial

        dtype = dtype or self.dtype
        params = super().init_dummy_params(rng, dtype)
        if self.enable_eplb:
            # Identity logical->physical map (must exist in the dummy tree
            # too: the shardings tree includes it, and a meshed dummy init
            # tree_maps the two together).
            from vllm_tpu.parallel.eplb import identity_l2p

            params["layers"]["eplb_l2p"] = identity_l2p(
                self.num_layers, self.num_experts
            )
        layers = params["layers"]
        L, D, F, E = (
            self.num_layers,
            self.hidden_size,
            self.moe_intermediate,
            self.num_experts,
        )
        keys = jax.random.split(jax.random.fold_in(rng, 1), 8)

        @partial(jax.jit, static_argnums=(1, 2))
        def init(key, shape, fan_in):
            return (
                jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
            ).astype(dtype)

        # An expert stack is drawn a layer at a time into its place, each
        # layer one fused program: drawn whole and eagerly, the float32
        # draw and its scaled copy are both live (2 x 5.6 GB for 7 layers
        # of 128 experts at 2048 x 768) beside the weights already placed.
        @partial(jax.jit, static_argnums=(3,), donate_argnums=(0,))
        def draw_layer(stack, key, i, fan_in):
            w = jax.random.normal(key, stack.shape[1:], jnp.float32)
            return jax.lax.dynamic_update_index_in_dim(
                stack, (w / math.sqrt(fan_in)).astype(dtype), i, 0
            )

        def init_experts(key, shape, fan_in):
            stack = jnp.zeros(shape, dtype)
            for i, k in enumerate(jax.random.split(key, shape[0])):
                stack = draw_layer(stack, k, i, fan_in)
            return stack

        layers["router"] = init(keys[0], (L, D, E), D)
        layers["we_gate"] = init_experts(keys[1], (L, E, D, F), D)
        layers["we_up"] = init_experts(keys[2], (L, E, D, F), D)
        layers["we_down"] = init_experts(keys[3], (L, E, F, D), F)
        if self.shared_intermediate:
            Fs = self.shared_intermediate
            layers["ws_gate"] = init(keys[4], (L, D, Fs), D)
            layers["ws_up"] = init(keys[5], (L, D, Fs), D)
            layers["ws_down"] = init(keys[6], (L, Fs, D), Fs)
            layers["wsg"] = init(keys[7], (L, D, 1), D)
        return params

    def hf_weight_map(self) -> dict:
        m = super().hf_weight_map()
        # Drop dense-MLP entries; add router + per-expert weights.
        for i in range(self.num_layers):
            for name in ("gate_proj", "up_proj", "down_proj"):
                m.pop(f"model.layers.{i}.mlp.{name}.weight", None)
            m[f"model.layers.{i}.block_sparse_moe.gate.weight"] = (
                f"layers.router.{i}", True)
            for j in range(self.num_experts):
                base = f"model.layers.{i}.block_sparse_moe.experts.{j}"
                m[f"{base}.w1.weight"] = (f"layers.we_gate.{i}.{j}", True)
                m[f"{base}.w3.weight"] = (f"layers.we_up.{i}.{j}", True)
                m[f"{base}.w2.weight"] = (f"layers.we_down.{i}.{j}", True)
        return m

    # ------------------------------------------------------------------

    def apply(
        self,
        params: dict,
        kv_cache: jnp.ndarray,
        input_ids: jnp.ndarray,
        md: AttentionMetadata,
        token_lora_slot: jnp.ndarray | None = None,  # unused (no LoRA yet)
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        from vllm_tpu.layers.quant import embedding_lookup

        x = embedding_lookup(params["embed"], input_ids, self.dtype)
        if self.embedding_multiplier != 1.0:
            x = x * self.embedding_multiplier
        t = x.shape[0]
        H, KH, Dh = self.num_heads, self.num_kv_heads, self.head_dim
        rope_cos, rope_sin = self.rope.cos, self.rope.sin

        rope_apply = (
            _apply_interleaved if self.rope_interleaved
            else _apply_rotate_half
        )

        # The expert stacks stay whole, outside what the scan slices a
        # layer at a time (layers/moe.py::fused_experts_of_layer).
        expert_keys = ("we_gate", "we_up", "we_down")
        expert_stacks = [params["layers"][k] for k in expert_keys]
        scanned = {
            k: v for k, v in params["layers"].items() if k not in expert_keys
        }
        count_experts = self.enable_eplb or self.return_expert_counts
        # Pad slots are routed like any token (the router is a dense matmul
        # over ``t``), wherever what the attention kernel left in them
        # leads; they are counted nowhere and computed nowhere
        # (layers/moe.py::_grouped_moe).
        live = md.live_tokens()

        def layer_fn(carry, inputs):
            x, kv = carry
            lp, li = inputs
            h = self._norm(x, lp, "input_norm")
            with jax.named_scope("qkv_proj"):
                q, k, v = h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]
            if self.attention_bias:
                q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
            if self.clip_qkv is not None:
                q = jnp.clip(q, -self.clip_qkv, self.clip_qkv)
                k = jnp.clip(k, -self.clip_qkv, self.clip_qkv)
                v = jnp.clip(v, -self.clip_qkv, self.clip_qkv)
            if self.qk_norm_full:
                q = rms_norm(q, lp["q_norm"], self.rms_eps)
                k = rms_norm(k, lp["k_norm"], self.rms_eps)
            q = q.reshape(t, H, Dh)
            k = k.reshape(t, KH, Dh)
            v = v.reshape(t, KH, Dh)
            if self.qk_norm:
                q = rms_norm(q, lp["q_norm"], self.rms_eps)
                k = rms_norm(k, lp["k_norm"], self.rms_eps)
            cos = rope_cos[md.positions][:, None, :]
            sin = rope_sin[md.positions][:, None, :]
            q = rope_apply(q, cos, sin, self.rope.rotary_dim)
            k = rope_apply(k, cos, sin, self.rope.rotary_dim)
            kv = write_kv(kv, li, k, v, md.slot_mapping)
            kv_scale = kv_dequant_scale(kv)
            with jax.named_scope("attn"):
                attn = paged_attention(
                    q, kv, li, md, self.scale,
                    sliding_window=self.sliding_window,
                    k_scale=kv_scale, v_scale=kv_scale,
                )
            with jax.named_scope("o_proj"):
                x = x + self.residual_multiplier * (
                    attn.reshape(t, H * Dh) @ lp["wo"]
                )

            h2 = self._norm(x, lp, "post_norm")
            with jax.named_scope("moe_router"):
                logits = (
                    h2.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
                )
                weights, ids = select_experts(
                    logits, self.top_k, self.renormalize
                )
            counts_l = None
            if count_experts:
                # Load statistics in LOGICAL expert ids over LIVE tokens
                # only.
                contrib = jnp.broadcast_to(
                    live[:, None], ids.shape
                ).astype(jnp.int32)
                counts_l = jnp.zeros(
                    self.num_experts, jnp.int32
                ).at[ids.reshape(-1)].add(contrib.reshape(-1))
            if self.enable_eplb:
                # The l2p table redirects dispatch to the balanced
                # physical layout.
                ids = lp["eplb_l2p"][ids]
            with jax.named_scope("moe_experts"):
                moe_out = fused_experts_of_layer(
                    h2, *expert_stacks, li, weights, ids,
                    ep_mesh=self.ep_mesh if self.expert_parallel else None,
                    ep_axis="tp", live=live,
                )
            if self.shared_intermediate:
                # Sigmoid-gated shared expert (Qwen2-MoE semantics).
                from vllm_tpu.layers.activation import silu_and_mul

                gate_up = jnp.concatenate(
                    [h2 @ lp["ws_gate"], h2 @ lp["ws_up"]], -1
                )
                shared = silu_and_mul(gate_up) @ lp["ws_down"]
                moe_out = moe_out + jax.nn.sigmoid(h2 @ lp["wsg"]) * shared
            return (x + self.residual_multiplier * moe_out, kv), counts_l

        # Whole cache in the carry: in-place paged KV (see models/llama.py).
        (x, new_kv), counts = jax.lax.scan(
            layer_fn,
            (x, kv_cache),
            (scanned, jnp.arange(self.num_layers, dtype=jnp.int32)),
        )
        x = self._norm(x, params, "final_norm")
        if count_experts:
            return x, new_kv, counts  # counts [L, E]
        return x, new_kv

    # ------------------------------------------------------------------

    def param_shardings(self, data_axis: str | None = None, model_axis: str = "tp") -> dict:
        out = super().param_shardings(data_axis, model_axis)
        layers = out["layers"]
        for name in ("wgate", "wup", "wdown"):
            del layers[name]
        tp = model_axis
        layers["router"] = P(None, None, None)
        if self.expert_parallel:
            # EP: experts distributed over the tp axis, dense per-expert
            # weights; combine becomes a psum over tp.
            layers["we_gate"] = P(None, tp, None, None)
            layers["we_up"] = P(None, tp, None, None)
            layers["we_down"] = P(None, tp, None, None)
        else:
            # TP within every expert (Megatron FFN sharding).
            layers["we_gate"] = P(None, None, None, tp)
            layers["we_up"] = P(None, None, None, tp)
            layers["we_down"] = P(None, None, tp, None)
        if self.shared_intermediate:
            layers["ws_gate"] = P(None, None, tp)
            layers["ws_up"] = P(None, None, tp)
            layers["ws_down"] = P(None, tp, None)
            layers["wsg"] = P(None, None, None)
        if self.enable_eplb:
            layers["eplb_l2p"] = P(None, None)
        return out
